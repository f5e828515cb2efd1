"""Mixture-of-Experts FFN (TPU/GSPMD-friendly, expert-parallel).

Top-k token-choice routing with a per-expert capacity.  Dispatch/combine
use scatter-add / gather (linear in tokens) instead of the classic
[T, E, C] dispatch einsum, which is quadratic in sequence length and
dominates expert compute at 32k tokens.  Expert weights shard over the
"model" mesh axis (expert parallelism); shared experts (DeepSeek-V2) are
plain dense MLPs added on top.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.sharding import constrain


def init_moe(key, cfg, dtype):
    d, ff, E = cfg.d_model, (cfg.moe_d_ff or cfg.d_ff), cfg.num_experts
    ks = jax.random.split(key, 7)
    p = {
        "router": layers.dense_init(ks[0], (d, E), jnp.float32),
        "moe_w_gate": layers.dense_init(ks[1], (E, d, ff), dtype),
        "moe_w_up": layers.dense_init(ks[2], (E, d, ff), dtype),
        "moe_w_down": layers.dense_init(ks[3], (E, ff, d), dtype),
    }
    if cfg.num_shared_experts:
        sff = ff * cfg.num_shared_experts
        p["sh_w_gate"] = layers.dense_init(ks[4], (d, sff), dtype)
        p["sh_w_up"] = layers.dense_init(ks[5], (d, sff), dtype)
        p["sh_w_down"] = layers.dense_init(ks[6], (sff, d), dtype)
    return p


# module toggle for the data-shard-aware dispatch (EXPERIMENTS.md §Perf);
# flipped by the dry-run's --moe-dispatch flag
DATA_SHARDED_DISPATCH = False


def moe_ffn(p, x, cfg, *, lossless: bool = False,
            data_sharded_dispatch=None):
    """x: [B, S, d] -> ([B, S, d], aux load-balance loss).

    ``lossless`` uses capacity == T (no token ever dropped) — used by the
    decode step, where T = batch is small and dropping would corrupt
    generation.  Otherwise capacity = cfg.moe_capacity_factor * T * k / E
    (Switch-style dropping, faithful for training).
    """
    if data_sharded_dispatch is None:
        data_sharded_dispatch = DATA_SHARDED_DISPATCH
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, d)

    logits = xt.astype(jnp.float32) @ p["router"]              # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)            # [T, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    if lossless:
        capacity = T
    else:
        capacity = min(max(1, int(cfg.moe_capacity_factor * T * k / E)), T)

    # queue position of each (token, slot) within its expert — sort-based
    # ranking, O(T*k) memory (a cumsum over a [T*k, E] one-hot would
    # materialize terabytes at 1M tokens x 160 experts)
    e_flat = expert_ids.reshape(T * k)
    order = jnp.argsort(e_flat, stable=True)       # stable = arrival order
    counts = jnp.bincount(e_flat, length=E)
    starts = jnp.cumsum(counts) - counts
    ranks = jnp.arange(T * k) - starts[e_flat[order]]
    pos = jnp.zeros((T * k,), jnp.int32).at[order].set(ranks.astype(jnp.int32))
    keep = pos < capacity
    p_flat = jnp.where(keep, pos, capacity)                    # C = overflow row

    # Switch-style aux load-balance loss (counts-based: no [T,k,E] one-hot)
    me = jnp.mean(probs, axis=0)
    ce = counts.astype(jnp.float32) / (T * k)
    aux_loss = E * jnp.sum(me * jax.lax.stop_gradient(ce) +
                           jax.lax.stop_gradient(me) * ce) * 0.5

    # dispatch: scatter tokens into per-expert buffers [E, C+1, d]
    xt_rep = jnp.repeat(xt[:, None], k, axis=1).reshape(T * k, d)
    from repro.models.sharding import current_mesh
    mesh = current_mesh()
    n_ds = mesh.shape.get("data", 1) if mesh is not None else 1
    if data_sharded_dispatch and n_ds > 1 and T % n_ds == 0:
        # Beyond-paper optimization (see EXPERIMENTS.md §Perf): give the
        # capacity buffer a leading data-shard dim and rank tokens within
        # (expert, shard) so every scatter update stays on its own data
        # shard — GSPMD then avoids all-gathering the [T*k, d] dispatch
        # tokens across the data axis (64 GB/layer for DeepSeek train_4k).
        T_loc = T // n_ds
        cap_l = min(max(1, capacity // n_ds + 1), T_loc)
        shard_id = (jnp.arange(T * k) // (T_loc * k)).astype(jnp.int32)
        # rank within (expert, shard): sort by (expert, shard)
        key2 = e_flat * n_ds + shard_id
        order2 = jnp.argsort(key2, stable=True)
        counts2 = jnp.bincount(key2, length=E * n_ds)
        starts2 = jnp.cumsum(counts2) - counts2
        ranks2 = jnp.arange(T * k) - starts2[key2[order2]]
        pos_l = jnp.zeros((T * k,), jnp.int32).at[order2].set(
            ranks2.astype(jnp.int32))
        pos_l = jnp.where(pos_l < cap_l, pos_l, cap_l)
        buf = jnp.zeros((E, n_ds, cap_l + 1, d), x.dtype)
        buf = buf.at[e_flat, shard_id, pos_l].add(xt_rep)
        # constrain the scatter RESULT: without this GSPMD materializes the
        # scatter with a replicated output and all-gathers it across data
        # (~288 GB/layer measured — see EXPERIMENTS.md §Perf pair 3)
        buf = constrain(buf, "model", "data", None, None)
        xe = buf[:, :, :cap_l].reshape(E, n_ds * cap_l, d)
        gather_idx = (shard_id, pos_l)
    else:
        buf = jnp.zeros((E, capacity + 1, d), x.dtype)
        buf = buf.at[e_flat, p_flat].add(xt_rep)
        xe = buf[:, :capacity]                                 # [E, C, d]
        gather_idx = None
    xe = constrain(xe, "model", None, None)

    a = layers.act_fn(cfg.act)
    h = a(jnp.einsum("ecd,edf->ecf", xe, p["moe_w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", xe, p["moe_w_up"])
    h = constrain(h, "model", None, None)
    ye = jnp.einsum("ecf,efd->ecd", h, p["moe_w_down"])        # [E, C, d]
    ye = constrain(ye, "model", None, None)

    # combine: gather each (token, slot)'s output and mix by gate value
    if gather_idx is not None:
        shard_id, pos_l = gather_idx
        cap_l = ye.shape[1] // n_ds
        ye4 = jnp.concatenate(
            [ye.reshape(E, n_ds, cap_l, d),
             jnp.zeros((E, n_ds, 1, d), ye.dtype)], axis=2)
        y_tok = ye4[e_flat, shard_id, pos_l].reshape(T, k, d)
    else:
        ye_pad = jnp.concatenate([ye, jnp.zeros((E, 1, d), ye.dtype)], axis=1)
        y_tok = ye_pad[e_flat, p_flat].reshape(T, k, d)
    out = jnp.sum(y_tok * gate_vals[..., None].astype(ye.dtype), axis=1)
    out = out.astype(x.dtype)

    if "sh_w_gate" in p:
        out = out + layers.gated_mlp(
            {"w_gate": p["sh_w_gate"], "w_up": p["sh_w_up"],
             "w_down": p["sh_w_down"]}, xt, cfg.act)
    return out.reshape(B, S, d), aux_loss


# ---------------------------------------------------------------------------
# shard_map expert-parallel dispatch (EXPERIMENTS.md §Perf pair 3 fix)
# ---------------------------------------------------------------------------
# Plain-GSPMD capacity dispatch pays a cross-shard gather/reduce because the
# SPMD scatter partitioner cannot prove update locality (two refuted
# iterations recorded in EXPERIMENTS.md).  Here the communication is
# explicit: per-device routing -> all_to_all over the "model" axis (tokens
# to their expert's owner) -> local scatter + expert matmuls -> all_to_all
# back -> local combine.
MOE_SHARDMAP = False


def _local_rank(ids, n_bins):
    """Stable rank of each element within its bin; O(T) memory."""
    order = jnp.argsort(ids, stable=True)
    counts = jnp.bincount(ids, length=n_bins)
    starts = jnp.cumsum(counts) - counts
    ranks = jnp.arange(ids.shape[0]) - starts[ids[order]]
    return jnp.zeros_like(ids).at[order].set(ranks.astype(ids.dtype))


def moe_ffn_shardmap(p, x, cfg, mesh):
    """x: [B, S, d] sharded (dp, "model", None).  Returns (out, aux)."""
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    E, k = cfg.num_experts, cfg.experts_per_token
    M = mesh.shape["model"]
    E_loc = E // M
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    all_axes = tuple(mesh.axis_names)

    def body(router, wg, wu, wd, xb):
        Bl, Sl, d = xb.shape
        T = Bl * Sl
        xt = xb.reshape(T, d)
        logits = xt.astype(jnp.float32) @ router
        probs = _jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = _jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        e_flat = expert_ids.reshape(T * k)
        g_flat = gate_vals.reshape(T * k)
        src = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        dest = e_flat // E_loc                          # target model shard
        e_local = e_flat % E_loc

        C_s = min(max(1, int(cfg.moe_capacity_factor * T * k / M)), T * k)
        pos = _local_rank(dest, M)
        ok = pos < C_s
        slot = jnp.where(ok, pos, C_s)

        def scat(values, fill):
            buf = jnp.full((M, C_s + 1) + values.shape[1:], fill,
                           values.dtype)
            return buf.at[dest, slot].set(values)[:, :C_s]

        send_x = scat(xt[src], 0.0)                     # [M, C_s, d]
        send_e = scat(e_local, E_loc)                   # E_loc = invalid
        send_s = scat(src, -1)

        recv_x = _jax.lax.all_to_all(send_x, "model", 0, 0, tiled=False)
        recv_e = _jax.lax.all_to_all(send_e, "model", 0, 0, tiled=False)
        recv_s = _jax.lax.all_to_all(send_s, "model", 0, 0, tiled=False)
        # [M, C_s, ...] -> flat local work queue
        R = M * C_s
        rx = recv_x.reshape(R, d)
        re = recv_e.reshape(R)
        valid = re < E_loc
        re_c = jnp.where(valid, re, E_loc)

        C_l = min(max(1, int(cfg.moe_capacity_factor * R / max(E_loc, 1))), R)
        pos_l = _local_rank(re_c.astype(jnp.int32), E_loc + 1)
        ok_l = valid & (pos_l < C_l)
        slot_l = jnp.where(ok_l, pos_l, C_l)
        buf = jnp.zeros((E_loc, C_l + 1, d), xb.dtype)
        buf = buf.at[re_c, slot_l].set(rx.astype(xb.dtype))
        xe = buf[:, :C_l]

        a = layers.act_fn(cfg.act)
        h = a(jnp.einsum("ecd,edf->ecf", xe, wg))
        h = h * jnp.einsum("ecd,edf->ecf", xe, wu)
        ye = jnp.einsum("ecf,efd->ecd", h, wd)          # [E_loc, C_l, d]
        ye_pad = jnp.concatenate([ye, jnp.zeros((E_loc, 1, d), ye.dtype)], 1)
        back = ye_pad[re_c, slot_l]                     # [R, d]
        back = jnp.where(ok_l[:, None], back, 0.0)
        back = back.reshape(M, C_s, d)
        ret = _jax.lax.all_to_all(back, "model", 0, 0, tiled=False)
        ret = ret.reshape(M * C_s, d)                   # rows align with send

        # combine on the source shard
        contrib = jnp.zeros((T + 1, d), jnp.float32)
        src_pad = scat(src, T)                          # [M, C_s] w/ sentinel
        g_pad = scat(g_flat, 0.0)
        contrib = contrib.at[src_pad.reshape(-1)].add(
            ret.astype(jnp.float32) * g_pad.reshape(-1, 1))
        out = contrib[:T].astype(xb.dtype).reshape(Bl, Sl, d)

        # aux load-balance loss (global means via psum-mean)
        me = _jax.lax.pmean(jnp.mean(probs, axis=0), all_axes)
        ce = _jax.lax.pmean(
            jnp.bincount(e_flat, length=E).astype(jnp.float32) / (T * k),
            all_axes)
        aux = E * jnp.sum(me * ce)
        return out, aux

    in_specs = (P(None, None), P("model", None, None),
                P("model", None, None), P("model", None, None),
                P(dp, "model", None))
    out_specs = (P(dp, "model", None), P())
    fn = _jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)
    out, aux = fn(p["router"], p["moe_w_gate"], p["moe_w_up"],
                  p["moe_w_down"], x)

    if "sh_w_gate" in p:
        B, S, d = x.shape
        sh = layers.gated_mlp(
            {"w_gate": p["sh_w_gate"], "w_up": p["sh_w_up"],
             "w_down": p["sh_w_down"]}, x.reshape(B * S, d), cfg.act)
        out = out + sh.reshape(B, S, d)
    return out, aux
