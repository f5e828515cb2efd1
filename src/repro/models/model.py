"""Unified multi-architecture decoder.

One generic stack with per-layer *kind* dispatch covers all ten assigned
architectures plus the paper's own LLaVA-1.5 model:

  - ATTN_MLP / ATTN_MOE : dense GQA attention (+ optional sliding window,
    optional whisper cross-attention) + gated/plain MLP or MoE FFN
  - MLA_MLP / MLA_MOE   : DeepSeek-V2 multi-head latent attention
  - MAMBA1 / MAMBA2     : selective-scan SSM blocks
  - SHARED_ATTN         : Zamba-style shared attention+MLP block

Public API (all pure functions of (cfg, params, ...)):
  init_params / param_specs
  forward        - full-sequence logits (train / eval)
  prefill        - full-sequence + per-layer caches (serving prefill)
  init_cache / cache_specs / cache_pspecs
  decode_step    - one token against the cache
  encode_media   - the encode-stage computation (projector / audio encoder)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN_MLP, ATTN_MOE, MLA_MLP, MLA_MOE, MAMBA1,
                                MAMBA2, SHARED_ATTN, ModelConfig)
from repro.models import layers, mamba, mla, moe
from repro.models.layers import rmsnorm
from repro.models.sharding import constrain

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_attn(key, cfg, dtype, cross: bool):
    d, H, Kh, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 9)
    p = {
        "norm1": jnp.zeros((d,), jnp.float32),
        "wq": layers.dense_init(ks[0], (d, H * Dh), dtype),
        "wk": layers.dense_init(ks[1], (d, Kh * Dh), dtype),
        "wv": layers.dense_init(ks[2], (d, Kh * Dh), dtype),
        "wo": layers.dense_init(ks[3], (H * Dh, d), dtype),
        "norm2": jnp.zeros((d,), jnp.float32),
    }
    if cross:
        p.update({
            "xnorm": jnp.zeros((d,), jnp.float32),
            "xq": layers.dense_init(ks[4], (d, H * Dh), dtype),
            "xk": layers.dense_init(ks[5], (d, Kh * Dh), dtype),
            "xv": layers.dense_init(ks[6], (d, Kh * Dh), dtype),
            "xo": layers.dense_init(ks[7], (H * Dh, d), dtype),
        })
    return p


def _init_mlp(key, cfg, dtype, d_ff: int):
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    if cfg.act == "gelu_mlp":  # plain (whisper)
        return {"w_up": layers.dense_init(ks[0], (d, d_ff), dtype),
                "w_down": layers.dense_init(ks[1], (d_ff, d), dtype)}
    return {"w_gate": layers.dense_init(ks[0], (d, d_ff), dtype),
            "w_up": layers.dense_init(ks[1], (d, d_ff), dtype),
            "w_down": layers.dense_init(ks[2], (d_ff, d), dtype)}


def _init_layer(key, cfg, kind, dtype):
    k1, k2 = jax.random.split(key)
    if kind == MAMBA1:
        return mamba.init_mamba1(k1, cfg, dtype)
    if kind == MAMBA2:
        return mamba.init_mamba2(k1, cfg, dtype)
    if kind == SHARED_ATTN:
        return {"norm": jnp.zeros((cfg.d_model,), jnp.float32)}
    if kind in (MLA_MLP, MLA_MOE):
        p = {"norm1": jnp.zeros((cfg.d_model,), jnp.float32),
             "norm2": jnp.zeros((cfg.d_model,), jnp.float32)}
        p.update(mla.init_mla(k1, cfg, dtype))
    else:
        p = _init_attn(k1, cfg, dtype, cross=cfg.cross_attention)
    if kind in (ATTN_MOE, MLA_MOE):
        p.update(moe.init_moe(k2, cfg, dtype))
    else:
        p.update(_init_mlp(k2, cfg, dtype, cfg.d_ff))
    return p


def init_params(cfg: ModelConfig, key, dtype=jnp.float32):
    kinds = cfg.layer_kinds()
    n_keys = cfg.num_layers + 8 + cfg.encoder_layers
    ks = list(jax.random.split(key, n_keys))
    params = {
        "embed": layers.dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype,
                                   scale=0.02),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
        "layers": [_init_layer(ks[2 + i], cfg, kind, dtype)
                   for i, kind in enumerate(kinds)],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                              dtype, scale=0.02)
    if any(k == SHARED_ATTN for k in kinds):
        sp = _init_attn(ks[-1], cfg, dtype, cross=False)
        sp.update(_init_mlp(ks[-2], cfg, dtype, cfg.d_ff))
        params["shared"] = sp
    if cfg.frontend == "vision":
        d = cfg.d_model
        params["media_proj_w1"] = layers.dense_init(ks[-3], (d, 2 * d), dtype)
        params["media_proj_w2"] = layers.dense_init(ks[-4], (2 * d, d), dtype)
    if cfg.encoder_layers:
        off = 8 + cfg.num_layers
        params["encoder"] = {
            "layers": [_init_attn(ks[off + i], cfg, dtype, cross=False)
                       | _init_mlp(jax.random.fold_in(ks[off + i], 1), cfg,
                                   dtype, cfg.d_ff)
                       for i in range(cfg.encoder_layers)],
            "norm": jnp.zeros((cfg.d_model,), jnp.float32),
        }
    return params


def param_specs(cfg: ModelConfig, dtype=jnp.bfloat16):
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return jax.eval_shape(lambda k: init_params(cfg, k, dtype), key)


# ---------------------------------------------------------------------------
# sub-layers (full sequence)
# ---------------------------------------------------------------------------
def _attn_full(p, x, cfg, positions, window, causal=True):
    B, S, _ = x.shape
    H, Kh, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    k = (x @ p["wk"]).reshape(B, S, Kh, Dh)
    v = (x @ p["wv"]).reshape(B, S, Kh, Dh)
    if cfg.rope_theta:
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, positions, cfg.rope_theta)
    o = layers.blockwise_attention(q, k, v, causal=causal, window=window)
    o = o.reshape(B, S, H * Dh)
    o = constrain(o, "dp", None, "model")
    return o @ p["wo"], (k.reshape(B, S, Kh * Dh), v.reshape(B, S, Kh * Dh))


def _cross_full(p, x, enc_out, cfg):
    B, S, _ = x.shape
    H, Kh, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    T = enc_out.shape[1]
    q = (x @ p["xq"]).reshape(B, S, H, Dh)
    k = (enc_out @ p["xk"]).reshape(B, T, Kh, Dh)
    v = (enc_out @ p["xv"]).reshape(B, T, Kh, Dh)
    o = layers.blockwise_attention(q, k, v, causal=False)
    o = o.reshape(B, S, H * Dh)
    o = constrain(o, "dp", None, "model")
    return o @ p["xo"], (k.reshape(B, T, Kh * Dh), v.reshape(B, T, Kh * Dh))


def _ffn(p, x, cfg, kind, lossless_moe=False):
    if kind in (ATTN_MOE, MLA_MOE):
        from repro.models.sharding import current_mesh
        mesh = current_mesh()
        if moe.MOE_SHARDMAP and mesh is not None and not lossless_moe \
                and cfg.num_experts % mesh.shape["model"] == 0:
            return moe.moe_ffn_shardmap(p, x, cfg, mesh)
        return moe.moe_ffn(p, x, cfg, lossless=lossless_moe)
    return layers.mlp(p, x, cfg.act), 0.0


def _block_full(cfg, kind, p, shared, h, positions, enc_out, window,
                collect_cache):
    """Apply one block.  Returns (h, cache_entry, aux_loss)."""
    cache = {}
    aux = 0.0
    if kind in (MAMBA1, MAMBA2):
        fn = mamba.mamba1_seq if kind == MAMBA1 else mamba.mamba2_seq
        y, (state, conv) = fn(p, rmsnorm(h, p["norm"], cfg.norm_eps), cfg)
        h = h + y
        if collect_cache:
            cache = {"state": state, "conv": conv}
    elif kind == SHARED_ATTN:
        x_in = rmsnorm(h, p["norm"], cfg.norm_eps)
        a, (k, v) = _attn_full(shared, x_in, cfg, positions, window=0)
        h = h + a
        f = layers.mlp(shared, rmsnorm(h, shared["norm2"], cfg.norm_eps), cfg.act)
        h = h + f
        if collect_cache:
            cache = {"k": k, "v": v}
    elif kind in (MLA_MLP, MLA_MOE):
        a, (ckv, krope) = mla.mla_full(p, rmsnorm(h, p["norm1"], cfg.norm_eps),
                                       cfg, positions)
        h = h + a
        f, aux = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind)
        h = h + f
        if collect_cache:
            cache = {"ckv": ckv, "krope": krope}
    else:  # ATTN_MLP / ATTN_MOE
        a, (k, v) = _attn_full(p, rmsnorm(h, p["norm1"], cfg.norm_eps), cfg,
                               positions, window)
        h = h + a
        if collect_cache:
            cache = {"k": k, "v": v}
        if cfg.cross_attention:
            c, (xk, xv) = _cross_full(p, rmsnorm(h, p["xnorm"], cfg.norm_eps),
                                      enc_out, cfg)
            h = h + c
            if collect_cache:
                cache.update({"xk": xk, "xv": xv})
        f, aux = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind)
        h = h + f
    return h, cache, aux


# ---------------------------------------------------------------------------
# encode stage / embedding
# ---------------------------------------------------------------------------
def encode_media(cfg, params, media):
    """The encode-stage computation: vision projector or audio encoder."""
    if cfg.frontend == "vision":
        w1 = params["media_proj_w1"]
        h = jax.nn.gelu(media.astype(w1.dtype) @ w1, approximate=True)
        return h @ params["media_proj_w2"]
    if cfg.frontend == "audio":
        enc = params["encoder"]
        T = media.shape[1]
        h = media + layers.sinusoidal_positions(
            jnp.arange(T), cfg.d_model, media.dtype)
        pos = jnp.arange(T)
        for lp in enc["layers"]:
            a, _ = _attn_full(lp, rmsnorm(h, lp["norm1"], cfg.norm_eps), cfg,
                              pos, window=0, causal=False)
            h = h + a
            h = h + layers.mlp(lp, rmsnorm(h, lp["norm2"], cfg.norm_eps), cfg.act)
        return rmsnorm(h, enc["norm"], cfg.norm_eps)
    return media


def _embed(cfg, params, tokens, media_emb, positions):
    h = params["embed"][tokens]
    if media_emb is not None:
        h = jnp.concatenate([media_emb.astype(h.dtype), h], axis=1)
    if not cfg.rope_theta:  # absolute sinusoidal positions (whisper)
        h = h + layers.sinusoidal_positions(positions, cfg.d_model, h.dtype)
    return constrain(h, "dp", None, None)


def _logits(cfg, params, h):
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = h @ w
    return constrain(logits, "dp", None, "model") if logits.ndim == 3 \
        else constrain(logits, "dp", "model")


# ---------------------------------------------------------------------------
# full-sequence forward / prefill
# ---------------------------------------------------------------------------
def forward(cfg: ModelConfig, params, tokens, media=None, frames=None, *,
            remat: bool = False, collect_cache: bool = False):
    """Returns (logits [B, S_total, V], caches | None, aux_loss)."""
    enc_out = None
    media_emb = None
    if frames is not None:
        enc_out = encode_media(cfg, params, frames)
    if media is not None:
        media_emb = encode_media(cfg, params, media)
    S_total = tokens.shape[1] + (media_emb.shape[1] if media_emb is not None else 0)
    positions = jnp.arange(S_total)
    h = _embed(cfg, params, tokens, media_emb, positions)

    caches = []
    aux_total = 0.0
    seq_shard = cfg.family not in ("ssm", "hybrid")
    for i, kind in enumerate(cfg.layer_kinds()):
        window = cfg.sliding_window if cfg.is_local_layer(i) else 0
        p = params["layers"][i]
        shared = params.get("shared")

        def body(h, p, shared):
            return _block_full(cfg, kind, p, shared, h, positions, enc_out,
                               window, collect_cache)

        if remat:
            body = jax.checkpoint(body)
        h, cache, aux = body(h, p, shared)
        if seq_shard:
            h = constrain(h, "dp", "model", None)
        aux_total = aux_total + aux
        caches.append(cache)
    logits = _logits(cfg, params, h)
    return logits, (caches if collect_cache else None), aux_total


def prefill(cfg: ModelConfig, params, tokens, media=None, frames=None):
    """Serving prefill: returns (last-token logits [B, V], cache dict)."""
    logits, caches, _ = forward(cfg, params, tokens, media=media, frames=frames,
                                collect_cache=True)
    cache = {"layers": caches}
    return logits[:, -1], cache


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------
def _layer_cache_shape(cfg, kind, i, batch, max_len):
    if kind == MAMBA1:
        return {k: s for k, s in mamba.mamba1_cache_shape(cfg, batch).items()}
    if kind == MAMBA2:
        return {k: s for k, s in mamba.mamba2_cache_shape(cfg, batch).items()}
    if kind in (MLA_MLP, MLA_MOE):
        return {"ckv": (batch, max_len, cfg.kv_lora_rank),
                "krope": (batch, max_len, cfg.qk_rope_head_dim)}
    S_c = max_len
    if cfg.is_local_layer(i) and cfg.sliding_window:
        S_c = min(max_len, cfg.sliding_window)
    ent = {"k": (batch, S_c, cfg.num_kv_heads * cfg.head_dim),
           "v": (batch, S_c, cfg.num_kv_heads * cfg.head_dim)}
    if cfg.cross_attention and kind in (ATTN_MLP, ATTN_MOE):
        ent["xk"] = (batch, cfg.media_tokens, cfg.num_kv_heads * cfg.head_dim)
        ent["xv"] = (batch, cfg.media_tokens, cfg.num_kv_heads * cfg.head_dim)
    return ent


def _cache_tree(cfg, batch, max_len, leaf):
    out = []
    for i, kind in enumerate(cfg.layer_kinds()):
        shapes = _layer_cache_shape(cfg, kind, i, batch, max_len)
        ent = {}
        for name, shape in shapes.items():
            dtype = jnp.float32 if name == "state" else None
            ent[name] = leaf(shape, dtype)
        out.append(ent)
    return {"layers": out}


def init_cache(cfg, batch, max_len, dtype=jnp.float32):
    return _cache_tree(cfg, batch, max_len,
                       lambda s, dt: jnp.zeros(s, dt or dtype))


def cache_specs(cfg, batch, max_len, dtype=jnp.bfloat16):
    return _cache_tree(cfg, batch, max_len,
                       lambda s, dt: jax.ShapeDtypeStruct(s, dt or dtype))


def cache_pspecs(cfg, layout: str = "kvdim"):
    """PartitionSpecs matching the cache tree (for in_shardings).

    layout="kvdim" (paper-faithful baseline): shard the flattened
    kv_heads*head_dim feature dim over "model" — matches the weight layout
    but forces GSPMD to all-gather the cache for per-head attention when
    kv_heads doesn't divide the model axis.

    layout="seq" (beyond-paper): shard the cache SEQUENCE dim over "model"
    (ring-attention-style decode) — each device scores its context slice,
    softmax combines with tiny [B,H] collectives, and the new token's write
    lands on one shard.
    """
    from jax.sharding import PartitionSpec as P

    def leaf_spec(name, ndim):
        if name in ("k", "v", "xk", "xv", "conv"):
            if layout == "seq" and name in ("k", "v"):
                return ("dp", "model", None)
            return ("dp", None, "model")
        if name in ("ckv", "krope") and layout == "seq":
            return ("dp", "model", None)
        if name == "state":
            return ("dp", "model") + (None,) * (ndim - 2)
        return ("dp",) + (None,) * (ndim - 1)  # ckv / krope replicated on model

    out = []
    for i, kind in enumerate(cfg.layer_kinds()):
        shapes = _layer_cache_shape(cfg, kind, i, batch=1, max_len=2)
        out.append({name: leaf_spec(name, len(s)) for name, s in shapes.items()})
    return {"layers": out}


def build_cache_from_prefill(cfg, prefill_cache, max_len):
    """Pad/arrange prefill per-layer entries into fixed-size decode caches."""
    out = []
    for i, (kind, ent) in enumerate(zip(cfg.layer_kinds(), prefill_cache["layers"])):
        if kind in (MAMBA1, MAMBA2):
            out.append(ent)
            continue
        new = {}
        for name, arr in ent.items():
            if name in ("xk", "xv"):
                new[name] = arr
                continue
            S = arr.shape[1]
            S_c = max_len
            if name in ("k", "v") and cfg.is_local_layer(i) and cfg.sliding_window:
                S_c = min(max_len, cfg.sliding_window)
            if S >= S_c:  # ring: keep the last S_c entries at slot = pos % S_c
                tail = arr[:, S - S_c:]
                new[name] = jnp.roll(tail, S % S_c, axis=1)
            else:
                pad = jnp.zeros((arr.shape[0], S_c - S) + arr.shape[2:], arr.dtype)
                new[name] = jnp.concatenate([arr, pad], axis=1)
        out.append(new)
    return {"layers": out}


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def _attn_decode(p, x, cfg, ent, cache_len, window):
    B = x.shape[0]
    H, Kh, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = layers.lengths_vector(cache_len, B)[:, None]
    q = (x @ p["wq"]).reshape(B, 1, H, Dh)
    k = (x @ p["wk"]).reshape(B, 1, Kh, Dh)
    v = (x @ p["wv"]).reshape(B, 1, Kh, Dh)
    if cfg.rope_theta:
        q = layers.rope(q, pos, cfg.rope_theta)
        k = layers.rope(k, pos, cfg.rope_theta)
    k_flat = k.reshape(B, 1, Kh * Dh)
    v_flat = v.reshape(B, 1, Kh * Dh)
    S_c = ent["k"].shape[1]
    ring = bool(window) and S_c <= window
    write = layers.ring_write if ring else layers.cache_write
    k_cache = write(ent["k"], k_flat, cache_len)
    v_cache = write(ent["v"], v_flat, cache_len)
    o = layers.decode_attention(q, k_cache, v_cache, cache_len,
                                n_kv_heads=Kh, ring=ring, window=window)
    o = constrain(o, "dp", None, "model")
    out = o @ p["wo"]
    return out, {**ent, "k": k_cache, "v": v_cache}


def _cross_decode(p, x, cfg, ent):
    B = x.shape[0]
    H, Kh, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ p["xq"]).reshape(B, 1, H, Dh)
    T = ent["xk"].shape[1]
    o = layers.decode_attention(q, ent["xk"], ent["xv"], jnp.int32(T - 1),
                                n_kv_heads=Kh)
    o = constrain(o, "dp", None, "model")
    return o @ p["xo"]


def decode_step(cfg: ModelConfig, params, cache, cache_len, token):
    """One decode step.  token: [B, 1] int32.  Returns (logits [B,V], cache)."""
    B = token.shape[0]
    h = params["embed"][token]
    if not cfg.rope_theta:
        pos_b = layers.lengths_vector(cache_len, B)
        h = h + layers.sinusoidal_positions(pos_b, cfg.d_model, h.dtype)[:, None]
    h = constrain(h, "dp", None, None)

    new_layers = []
    for i, kind in enumerate(cfg.layer_kinds()):
        p = params["layers"][i]
        ent = cache["layers"][i]
        window = cfg.sliding_window if cfg.is_local_layer(i) else 0
        if kind in (MAMBA1, MAMBA2):
            fn = mamba.mamba1_decode if kind == MAMBA1 else mamba.mamba2_decode
            y, (state, conv) = fn(p, rmsnorm(h, p["norm"], cfg.norm_eps), cfg,
                                  ent["state"], ent["conv"])
            h = h + y
            new_layers.append({"state": state, "conv": conv})
        elif kind == SHARED_ATTN:
            sp = params["shared"]
            x_in = rmsnorm(h, p["norm"], cfg.norm_eps)
            a, ent2 = _attn_decode(sp, x_in, cfg, ent, cache_len, window=0)
            h = h + a
            h = h + layers.mlp(sp, rmsnorm(h, sp["norm2"], cfg.norm_eps), cfg.act)
            new_layers.append(ent2)
        elif kind in (MLA_MLP, MLA_MOE):
            a, ckv, krope = mla.mla_decode(
                p, rmsnorm(h, p["norm1"], cfg.norm_eps), cfg,
                ent["ckv"], ent["krope"], cache_len)
            h = h + a
            f, _ = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind,
                         lossless_moe=True)
            h = h + f
            new_layers.append({"ckv": ckv, "krope": krope})
        else:
            a, ent2 = _attn_decode(p, rmsnorm(h, p["norm1"], cfg.norm_eps),
                                   cfg, ent, cache_len, window)
            h = h + a
            if cfg.cross_attention:
                h = h + _cross_decode(p, rmsnorm(h, p["xnorm"], cfg.norm_eps),
                                      cfg, ent)
            f, _ = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind,
                         lossless_moe=True)
            h = h + f
            new_layers.append(ent2)
    logits = _logits(cfg, params, h[:, 0])
    return logits, {"layers": new_layers}


# ---------------------------------------------------------------------------
# on-device sampling (DESIGN.md §13)
# ---------------------------------------------------------------------------
def sample_from_logits(logits, sample):
    """Batched categorical sampling with per-lane controls, fused into the
    jitted serving steps so only the sampled token ids [B] cross the host
    boundary (instead of [B, V] logits).

    ``sample``: {"temp": [B] f32, "top_k": [B] i32 (<=0 disables),
    "top_p": [B] f32, "seed": [B] u32, "step": [B] i32}.  Each lane draws
    its own PRNG key as ``fold_in(PRNGKey(seed), step)`` — a pure function
    of (request seed, token index), so sampling is deterministic no matter
    how requests are batched together.  Lanes with ``temp <= 0`` return the
    plain argmax, bit-exact with host-side greedy decoding.
    """
    temp = sample["temp"]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    V = logits.shape[-1]
    lg = logits / jnp.where(temp > 0, temp, 1.0)[:, None]
    # top-k: drop logits below each lane's k-th largest (k <= 0 disables)
    k = sample["top_k"]
    k_eff = jnp.clip(jnp.where(k > 0, k, V), 1, V)
    srt = jnp.flip(jnp.sort(lg, axis=-1), axis=-1)
    kth = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
    lg = jnp.where(lg < kth, -jnp.inf, lg)
    # top-p (nucleus): keep the smallest prefix of the descending
    # distribution whose mass reaches p; ties at the boundary stay in
    p = jnp.maximum(sample["top_p"], 1e-6)
    probs = jax.nn.softmax(lg, axis=-1)
    srt_p = jnp.flip(jnp.sort(probs, axis=-1), axis=-1)
    keep = (jnp.cumsum(srt_p, axis=-1) - srt_p) < p[:, None]
    pmin = jnp.min(jnp.where(keep, srt_p, jnp.inf), axis=-1)
    lg = jnp.where(probs >= pmin[:, None], lg, -jnp.inf)

    def gumbel(seed, step):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        return jax.random.gumbel(key, (V,), jnp.float32)

    noise = jax.vmap(gumbel)(sample["seed"], sample["step"])
    sampled = jnp.argmax(lg + noise, axis=-1).astype(jnp.int32)
    return jnp.where(temp <= 0, greedy, sampled)


# ---------------------------------------------------------------------------
# decode over device-resident paged caches (DESIGN.md §11)
# ---------------------------------------------------------------------------
def paged_impl_flags(attn_impl: Optional[str]) -> dict:
    """Map an engine-level backend name onto the kernel ops' flag pair.

    None      : the Pallas kernels, compiled or interpreted by platform
    kernel    : compiled Pallas kernels (TPU)
    interpret : Pallas kernels in interpret mode (CPU parity/testing)
    ref       : pure-jnp oracles (fast CPU path, same paged semantics)
    """
    if attn_impl is None:
        return {"interpret": None, "use_kernel": True}
    if attn_impl == "kernel":
        return {"interpret": False, "use_kernel": True}
    if attn_impl == "interpret":
        return {"interpret": True, "use_kernel": True}
    if attn_impl == "ref":
        return {"interpret": True, "use_kernel": False}
    raise ValueError(f"unknown paged attention impl {attn_impl!r}")


def _attn_decode_paged(p, x, cfg, data, layer, tables, slots, lens, window,
                       flags):
    """Dense-attention decode step against the paged KV store: append the
    new token's K/V via the fused cache write, then attend through the
    paged-attention kernel over pages + block tables."""
    from repro.kernels.cache_write.ops import paged_token_write
    from repro.kernels.paged_attention.ops import paged_attention

    B = x.shape[0]
    H, Kh, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = layers.lengths_vector(lens, B)[:, None]
    q = (x @ p["wq"]).reshape(B, 1, H, Dh)
    k = (x @ p["wk"]).reshape(B, 1, Kh, Dh)
    v = (x @ p["wv"]).reshape(B, 1, Kh, Dh)
    if cfg.rope_theta:
        q = layers.rope(q, pos, cfg.rope_theta)
        k = layers.rope(k, pos, cfg.rope_theta)
    rows = jnp.stack([k.reshape(B, Kh * Dh), v.reshape(B, Kh * Dh)])
    data = paged_token_write(data, layer, rows.astype(data.dtype), slots,
                             **flags)
    NB, bs = data.shape[2], data.shape[3]
    k_pages = data[0, layer].reshape(NB, bs, Kh, Dh)
    v_pages = data[1, layer].reshape(NB, bs, Kh, Dh)
    o = paged_attention(q[:, 0].astype(k_pages.dtype), k_pages, v_pages,
                        tables, lens + 1, window=window, **flags)
    o = o.reshape(B, 1, H * Dh).astype(x.dtype)
    o = constrain(o, "dp", None, "model")
    return o @ p["wo"], data


def decode_step_paged(cfg: ModelConfig, params, data, ctl, state, lens,
                      token, *, attn_impl: Optional[str] = None):
    """One decode step reading/writing device-resident paged caches in place.

    ``data``: {"kv": [T, L_kind, num_blocks+1, bs, width], "mla": ...}
    (either may be absent) — the bulk page storage, *donated* by the caller
    so the kernel's append lands in place.  ``ctl``: matching per-step
    control tensors {"kv": {"tables": [B, P] int32, "slots": [B] int32
    within-plane row slot of the token being appended}, ...}.  ``state``:
    {"layers": [...]} batched per-layer entries for the non-paged state
    (mamba state/conv, whisper cross xk/xv); paged layers carry empty
    dicts.  ``lens``: [B] int32 tokens already cached; ``token``: [B, 1].

    Returns (logits [B, V], {"kv": new data, "mla": new data}, new state).
    With ``ctl["sample"]`` present (see :func:`sample_from_logits`), the
    first element is instead the sampled token ids [B] — sampling fuses
    into the same jitted computation and only [B] ints cross the host
    boundary.  Unlike :func:`decode_step` there is no per-request
    gather/scatter: the cache never leaves the device and grows by exactly
    one row per request.
    """
    flags = paged_impl_flags(attn_impl)
    B = token.shape[0]
    h = params["embed"][token]
    if not cfg.rope_theta:
        pos_b = layers.lengths_vector(lens, B)
        h = h + layers.sinusoidal_positions(pos_b, cfg.d_model, h.dtype)[:, None]
    h = constrain(h, "dp", None, None)

    kv = dict(ctl.get("kv") or {})
    if "kv" in data:
        kv["data"] = data["kv"]
    mla_e = dict(ctl.get("mla") or {})
    if "mla" in data:
        mla_e["data"] = data["mla"]
    new_state = []
    aj = mj = 0  # running index into the attn / mla cache-layer planes
    for i, kind in enumerate(cfg.layer_kinds()):
        p = params["layers"][i]
        ent = state["layers"][i]
        window = cfg.sliding_window if cfg.is_local_layer(i) else 0
        if kind in (MAMBA1, MAMBA2):
            fn = mamba.mamba1_decode if kind == MAMBA1 else mamba.mamba2_decode
            y, (st, conv) = fn(p, rmsnorm(h, p["norm"], cfg.norm_eps), cfg,
                               ent["state"], ent["conv"])
            h = h + y
            new_state.append({"state": st, "conv": conv})
            continue
        if kind == SHARED_ATTN:
            sp = params["shared"]
            a, kv["data"] = _attn_decode_paged(
                sp, rmsnorm(h, p["norm"], cfg.norm_eps), cfg, kv["data"], aj,
                kv["tables"], kv["slots"], lens, 0, flags)
            aj += 1
            h = h + a
            h = h + layers.mlp(sp, rmsnorm(h, sp["norm2"], cfg.norm_eps),
                               cfg.act)
        elif kind in (MLA_MLP, MLA_MOE):
            a, mla_e["data"] = mla.mla_decode_paged(
                p, rmsnorm(h, p["norm1"], cfg.norm_eps), cfg, mla_e["data"],
                mj, mla_e["tables"], mla_e["slots"], lens, **flags)
            mj += 1
            h = h + a
            f, _ = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind,
                        lossless_moe=True)
            h = h + f
        else:  # ATTN_MLP / ATTN_MOE
            a, kv["data"] = _attn_decode_paged(
                p, rmsnorm(h, p["norm1"], cfg.norm_eps), cfg, kv["data"], aj,
                kv["tables"], kv["slots"], lens, window, flags)
            aj += 1
            h = h + a
            if cfg.cross_attention:
                h = h + _cross_decode(p, rmsnorm(h, p["xnorm"], cfg.norm_eps),
                                      cfg, ent)
            f, _ = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind,
                        lossless_moe=True)
            h = h + f
        new_state.append({})
    logits = _logits(cfg, params, h[:, 0])
    out = logits if ctl.get("sample") is None \
        else sample_from_logits(logits, ctl["sample"])
    new_paged = {}
    if "data" in kv:
        new_paged["kv"] = kv["data"]
    if "data" in mla_e:
        new_paged["mla"] = mla_e["data"]
    return out, new_paged, {"layers": new_state}


# ---------------------------------------------------------------------------
# chunked prefill (paper §3.2/§4.2): extend a cache prefix by a token chunk
# ---------------------------------------------------------------------------
def _attn_chunk(p, x, cfg, prior_k, prior_v, offset, window):
    """x: [B, C, d] chunk; prior_k/v: [B, P, kv_dim].  Returns out + chunk kv."""
    B, C, _ = x.shape
    H, Kh, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    positions = offset + jnp.arange(C)
    q = (x @ p["wq"]).reshape(B, C, H, Dh)
    k = (x @ p["wk"]).reshape(B, C, Kh, Dh)
    v = (x @ p["wv"]).reshape(B, C, Kh, Dh)
    if cfg.rope_theta:
        q = layers.rope(q, positions, cfg.rope_theta)
        k = layers.rope(k, positions, cfg.rope_theta)
    P_len = prior_k.shape[1]
    k_full = jnp.concatenate([prior_k.reshape(B, P_len, Kh, Dh), k], axis=1)
    v_full = jnp.concatenate([prior_v.reshape(B, P_len, Kh, Dh), v], axis=1)
    o = layers.blockwise_attention(q, k_full, v_full, causal=True,
                                   window=window, kv_offset=P_len)
    o = o.reshape(B, C, H * Dh)
    o = constrain(o, "dp", None, "model")
    return o @ p["wo"], (k.reshape(B, C, Kh * Dh), v.reshape(B, C, Kh * Dh))


def prefill_chunk(cfg: ModelConfig, params, tokens, prior, offset, *,
                  enc_out=None, media_emb=None):
    """Process one prefill chunk against an existing cache prefix.

    tokens: [B, C] (or None if the chunk is pure media); ``prior``: dict
    {"layers": [per-layer prefix entries]} with seq-like entries length P =
    offset tokens; mamba entries carry (state, conv).  Returns
    (last-token logits, chunk cache entries to append, new mamba states).
    """
    if media_emb is not None:
        h = media_emb
        if tokens is not None:
            h = jnp.concatenate([h, params["embed"][tokens]], axis=1)
    else:
        h = params["embed"][tokens]
    C = h.shape[1]
    if not cfg.rope_theta:
        h = h + layers.sinusoidal_positions(offset + jnp.arange(C),
                                            cfg.d_model, h.dtype)
    positions = offset + jnp.arange(C)

    new_entries = []
    for i, kind in enumerate(cfg.layer_kinds()):
        p = params["layers"][i]
        ent = prior["layers"][i]
        window = cfg.sliding_window if cfg.is_local_layer(i) else 0
        if kind in (MAMBA1, MAMBA2):
            fn = mamba.mamba1_seq if kind == MAMBA1 else mamba.mamba2_seq
            y, (state, conv) = fn(p, rmsnorm(h, p["norm"], cfg.norm_eps), cfg,
                                  ent.get("state"), ent.get("conv"))
            h = h + y
            new_entries.append({"state": state, "conv": conv})
        elif kind == SHARED_ATTN:
            sp = params["shared"]
            x_in = rmsnorm(h, p["norm"], cfg.norm_eps)
            a, (k, v) = _attn_chunk(sp, x_in, cfg, ent["k"], ent["v"],
                                    offset, 0)
            h = h + a
            h = h + layers.mlp(sp, rmsnorm(h, sp["norm2"], cfg.norm_eps),
                               cfg.act)
            new_entries.append({"k": k, "v": v})
        elif kind in (MLA_MLP, MLA_MOE):
            x_in = rmsnorm(h, p["norm1"], cfg.norm_eps)
            a, (ckv, krope) = mla.mla_chunk(p, x_in, cfg, ent["ckv"],
                                            ent["krope"], offset)
            h = h + a
            f, _ = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind)
            h = h + f
            new_entries.append({"ckv": ckv, "krope": krope})
        else:
            x_in = rmsnorm(h, p["norm1"], cfg.norm_eps)
            a, (k, v) = _attn_chunk(p, x_in, cfg, ent["k"], ent["v"],
                                    offset, window)
            h = h + a
            new_ent = {"k": k, "v": v}
            if cfg.cross_attention:
                if "xk" in ent and ent["xk"] is not None:
                    xk, xv = ent["xk"], ent["xv"]
                    B = h.shape[0]
                    Kh, Dh = cfg.num_kv_heads, cfg.head_dim
                    q = (rmsnorm(h, p["xnorm"], cfg.norm_eps) @ p["xq"]) \
                        .reshape(B, C, cfg.num_heads, Dh)
                    T = xk.shape[1]
                    o = layers.blockwise_attention(
                        q, xk.reshape(B, T, Kh, Dh), xv.reshape(B, T, Kh, Dh),
                        causal=False)
                    h = h + o.reshape(B, C, -1) @ p["xo"]
                else:
                    c, (xk, xv) = _cross_full(
                        p, rmsnorm(h, p["xnorm"], cfg.norm_eps), enc_out, cfg)
                    h = h + c
                    new_ent.update({"xk": xk, "xv": xv})
            f, _ = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind)
            h = h + f
            new_entries.append(new_ent)
    logits = _logits(cfg, params, h[:, -1])
    return logits, {"layers": new_entries}


def empty_prior(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    """Zero-length cache prefix for the first prefill chunk."""
    out = []
    for i, kind in enumerate(cfg.layer_kinds()):
        if kind in (MAMBA1, MAMBA2):
            out.append({"state": None, "conv": None})
        elif kind in (MLA_MLP, MLA_MOE):
            out.append({"ckv": jnp.zeros((batch, 0, cfg.kv_lora_rank), dtype),
                        "krope": jnp.zeros((batch, 0, cfg.qk_rope_head_dim),
                                           dtype)})
        else:
            kvd = cfg.num_kv_heads * cfg.head_dim
            out.append({"k": jnp.zeros((batch, 0, kvd), dtype),
                        "v": jnp.zeros((batch, 0, kvd), dtype)})
    return {"layers": out}


def extend_prior(cfg: ModelConfig, prior, chunk_entries):
    """Append a chunk's cache entries onto the prefix (engine bookkeeping)."""
    out = []
    for kind, old, new in zip(cfg.layer_kinds(), prior["layers"],
                              chunk_entries["layers"]):
        if kind in (MAMBA1, MAMBA2):
            out.append(new)  # state replaces
            continue
        ent = {}
        for name in old.keys() | new.keys():
            if name in ("xk", "xv"):
                ent[name] = new.get(name, old.get(name))
            else:
                parts = [x for x in (old.get(name), new.get(name))
                         if x is not None and x.shape[1] > 0]
                ent[name] = jnp.concatenate(parts, axis=1) if len(parts) > 1 \
                    else (parts[0] if parts else old.get(name))
        out.append(ent)
    return {"layers": out}


# ---------------------------------------------------------------------------
# batched chunked prefill over device-resident paged caches (DESIGN.md §12)
# ---------------------------------------------------------------------------
def _attn_chunk_paged(p, x, cfg, data, layer, tables, slots, ctx_lens,
                      window, flags):
    """Chunked-prefill dense attention against the paged KV store: write the
    chunk's K/V rows with one fused launch, then attend the chunk's queries
    through the chunked paged-attention kernel (chunk-causal over pages)."""
    from repro.kernels.cache_write.ops import paged_chunk_write
    from repro.kernels.paged_attention.ops import paged_prefill_attention

    B, C, _ = x.shape
    H, Kh, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = ctx_lens[:, None] + jnp.arange(C)                  # [B, C]
    q = (x @ p["wq"]).reshape(B, C, H, Dh)
    k = (x @ p["wk"]).reshape(B, C, Kh, Dh)
    v = (x @ p["wv"]).reshape(B, C, Kh, Dh)
    if cfg.rope_theta:
        q = layers.rope(q, pos, cfg.rope_theta)
        k = layers.rope(k, pos, cfg.rope_theta)
    rows = jnp.stack([k.reshape(B, C, Kh * Dh), v.reshape(B, C, Kh * Dh)])
    data = paged_chunk_write(data, layer, rows.astype(data.dtype), slots,
                             **flags)
    NB, bs = data.shape[2], data.shape[3]
    k_pages = data[0, layer].reshape(NB, bs, Kh, Dh)
    v_pages = data[1, layer].reshape(NB, bs, Kh, Dh)
    o = paged_prefill_attention(q.astype(k_pages.dtype), k_pages, v_pages,
                                tables, ctx_lens, window=window, **flags)
    o = o.reshape(B, C, H * Dh).astype(x.dtype)
    o = constrain(o, "dp", None, "model")
    return o @ p["wo"], data


def _cross_chunk(p, x, enc_out, cfg):
    """Batched cross-attention for a prefill chunk; returns (out, (xk, xv)).
    Recomputed from ``enc_out`` every chunk — deterministic in the encoder
    output, so recomputation keeps the batched step branch-free when the
    batch mixes first and later chunks."""
    B, C, _ = x.shape
    H, Kh, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    T = enc_out.shape[1]
    q = (x @ p["xq"]).reshape(B, C, H, Dh)
    k = (enc_out.astype(x.dtype) @ p["xk"]).reshape(B, T, Kh, Dh)
    v = (enc_out.astype(x.dtype) @ p["xv"]).reshape(B, T, Kh, Dh)
    o = layers.blockwise_attention(q, k, v, causal=False)
    o = o.reshape(B, C, H * Dh)
    o = constrain(o, "dp", None, "model")
    return o @ p["xo"], (k.reshape(B, T, Kh * Dh), v.reshape(B, T, Kh * Dh))


def prefill_chunk_paged(cfg: ModelConfig, params, data, ctl, state, ctx_lens,
                        tokens, *, attn_impl: Optional[str] = None):
    """One batched prefill chunk reading/writing device paged caches in place.

    The prefill analogue of :func:`decode_step_paged`: C tokens per request
    for a whole batch of requests in ONE jitted computation — no host
    gather of the prior context, no numpy round-trip of the chunk's K/V.

    ``data``: {"kv": [2, L_attn, NB+1, bs, w], "mla": ...} bulk page pools,
    *donated* by the caller.  ``ctl``: per-chunk control tensors —
    {"kv"|"mla": {"tables": [B, P] int32, "slots": [B, C] int32 within-plane
    row slots of the chunk tokens (padded positions point at scratch)},
    "img": {"slots": [B, C] int32 image-cache row per media position or -1,
    "pages": image page pool} (optional), "mask": [B, C] bool valid chunk
    positions, "last": [B] int32 index of each request's last valid
    position}.  ``state``: {"layers": [...batched mamba state/conv...],
    "enc_out": [B, T, d] (cross-attention archs)}.  ``ctx_lens``: [B] int32
    tokens already cached; ``tokens``: [B, C] int32 (0 at media positions —
    media embeddings are read straight off the image-cache pages).

    Returns (last-token logits [B, V], new paged data, new state with
    per-layer mamba state/conv and cross xk/xv for host bookkeeping).
    With ``ctl["sample"]`` present the first element is the sampled
    next-token ids [B] (see :func:`sample_from_logits`) — this is how a
    request's *first* token is drawn without shipping logits to the host.
    """
    flags = paged_impl_flags(attn_impl)
    B, C = tokens.shape
    h = params["embed"][tokens]
    img = ctl.get("img")
    if img is not None:
        # media positions read their embedding rows off the image-cache
        # pages on device (no host gather of media embeddings)
        img_flat = img["pages"][0, 0].reshape(-1, img["pages"].shape[-1])
        islots = img["slots"]
        media_h = img_flat[jnp.maximum(islots, 0)]
        h = jnp.where((islots >= 0)[..., None], media_h.astype(h.dtype), h)
    if not cfg.rope_theta:
        pos = (ctx_lens[:, None] + jnp.arange(C)).reshape(-1)
        h = h + layers.sinusoidal_positions(pos, cfg.d_model,
                                            h.dtype).reshape(B, C, -1)
    h = constrain(h, "dp", None, None)

    mask = ctl["mask"]
    kv = dict(ctl.get("kv") or {})
    if "kv" in data:
        kv["data"] = data["kv"]
    mla_e = dict(ctl.get("mla") or {})
    if "mla" in data:
        mla_e["data"] = data["mla"]
    enc_out = state.get("enc_out")
    new_state = []
    aj = mj = 0  # running index into the attn / mla cache-layer planes
    for i, kind in enumerate(cfg.layer_kinds()):
        p = params["layers"][i]
        ent = state["layers"][i]
        window = cfg.sliding_window if cfg.is_local_layer(i) else 0
        if kind in (MAMBA1, MAMBA2):
            fn = mamba.mamba1_seq if kind == MAMBA1 else mamba.mamba2_seq
            y, (st, conv) = fn(p, rmsnorm(h, p["norm"], cfg.norm_eps), cfg,
                               ent["state"], ent["conv"], mask=mask)
            h = h + y
            new_state.append({"state": st, "conv": conv})
            continue
        if kind == SHARED_ATTN:
            sp = params["shared"]
            a, kv["data"] = _attn_chunk_paged(
                sp, rmsnorm(h, p["norm"], cfg.norm_eps), cfg, kv["data"], aj,
                kv["tables"], kv["slots"], ctx_lens, 0, flags)
            aj += 1
            h = h + a
            h = h + layers.mlp(sp, rmsnorm(h, sp["norm2"], cfg.norm_eps),
                               cfg.act)
            new_state.append({})
        elif kind in (MLA_MLP, MLA_MOE):
            a, mla_e["data"] = mla.mla_chunk_paged(
                p, rmsnorm(h, p["norm1"], cfg.norm_eps), cfg, mla_e["data"],
                mj, mla_e["tables"], mla_e["slots"], ctx_lens, **flags)
            mj += 1
            h = h + a
            f, _ = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind,
                        lossless_moe=True)
            h = h + f
            new_state.append({})
        else:  # ATTN_MLP / ATTN_MOE
            a, kv["data"] = _attn_chunk_paged(
                p, rmsnorm(h, p["norm1"], cfg.norm_eps), cfg, kv["data"], aj,
                kv["tables"], kv["slots"], ctx_lens, window, flags)
            aj += 1
            h = h + a
            ent2 = {}
            if cfg.cross_attention:
                c, (xk, xv) = _cross_chunk(
                    p, rmsnorm(h, p["xnorm"], cfg.norm_eps), enc_out, cfg)
                h = h + c
                ent2 = {"xk": xk, "xv": xv}
            f, _ = _ffn(p, rmsnorm(h, p["norm2"], cfg.norm_eps), cfg, kind,
                        lossless_moe=True)
            h = h + f
            new_state.append(ent2)
    h_last = jnp.take_along_axis(h, ctl["last"][:, None, None], axis=1)[:, 0]
    logits = _logits(cfg, params, h_last)
    if ctl.get("sample") is not None:
        logits = sample_from_logits(logits, ctl["sample"])
    new_paged = {}
    if "data" in kv:
        new_paged["kv"] = kv["data"]
    if "data" in mla_e:
        new_paged["mla"] = mla_e["data"]
    return logits, new_paged, {"layers": new_state}
