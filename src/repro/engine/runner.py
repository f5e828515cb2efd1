"""ModelRunner: real-JAX stage execution over paged caches.

Executes the three HydraInfer stages on actual model weights:

  encode         : modality frontend -> image-token cache (paged, block 576)
  prefill_chunks : ONE batched chunked-prefill step for every request's
                   chunk this iteration (paged KV; DESIGN.md §12)
  decode         : batched one-token step over heterogeneous contexts
  joint_step     : encode + decode fused into ONE jitted computation — the
                   TPU-native analogue of the paper's two CUDA streams

Decode and prefill each have two paths (DESIGN.md §11/§12):

  device-resident paged (default in the engine): block storage stays on
  device as jnp arrays; the jitted step reads pages + block tables through
  the Pallas paged-attention kernel (compiled on TPU, interpret mode on
  CPU) and appends the new token — or the whole prefill chunk — in place
  via the fused cache-write kernel.  Only tiny control tensors (block
  tables, lengths, slots) and the logits cross the host boundary each
  step.  Batch size, chunk length, and page count are bucketed to powers
  of two so the steps compile O(log) distinct shapes.

  dense gather (``device=False`` caches): the seed fallback — per-request
  host gather, padded concat, full cache scatter / numpy chunk round-trip.
  Kept for migration endpoints and as the benchmark baseline.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (ATTN_MLP, ATTN_MOE, MLA_MLP, MLA_MOE, MAMBA1,
                                MAMBA2, SHARED_ATTN, ModelConfig)
from repro.engine.paged_cache import (DevicePagedCache, PagedCache,
                                      PagedCacheSpec, StateStore,
                                      migrate_request)
from repro.engine.trace import OFF, Trace
from repro.models import mamba
from repro.models import model as M

KV_BLOCK = 16        # paper §5.1
IMG_BLOCK = 576      # paper §5.1 (one LLaVA-1.5 image)


def bucket_pow2(n: int) -> int:
    """Smallest power of two >= n (jit shape bucketing)."""
    return 1 << max(0, n - 1).bit_length()


def default_attn_impl() -> str:
    """Paged-kernel backend: the compiled kernels on a TPU, interpret mode
    elsewhere, where REPRO_PAGED_IMPL=interpret|ref may pick the backend.
    On a TPU any other REPRO_PAGED_IMPL than "kernel" is an error: an
    emulated kernel there would run without saying so."""
    env = os.environ.get("REPRO_PAGED_IMPL") or None
    if jax.default_backend() == "tpu":
        if env not in (None, "kernel"):
            raise ValueError(f"REPRO_PAGED_IMPL={env!r} on a TPU: only the "
                             f"compiled kernels run there")
        return "kernel"
    return env or "interpret"


def _seq_layers(cfg: ModelConfig):
    """(attn_layer_ids, mla_layer_ids) — layers with seq-like paged caches."""
    attn, mla = [], []
    for i, kind in enumerate(cfg.layer_kinds()):
        if kind in (MLA_MLP, MLA_MOE):
            mla.append(i)
        elif kind in (ATTN_MLP, ATTN_MOE, SHARED_ATTN):
            attn.append(i)
    return attn, mla


class RunnerCaches:
    """Per-instance cache pool: paged KV + paged image cache + state store,
    all sharing the unified transfer interface (paper §4.5)."""

    def __init__(self, cfg: ModelConfig, *, kv_blocks: int = 512,
                 img_blocks: int = 16, dtype=np.float32,
                 device: bool = False, sharing: bool = False):
        self.cfg = cfg
        self.device = device
        cache_cls = DevicePagedCache if device else PagedCache
        self.attn_layers, self.mla_layers = _seq_layers(cfg)
        # Prefix sharing of the seq caches is unsound for architectures
        # with recurrent (SSM) layers: the mamba state at a prefix boundary
        # is not paged/snapshotted, so an adopted KV prefix would pair with
        # a zero recurrent state.  Gate seq-cache sharing off there; the
        # image cache (pure content, position-free) still shares.
        kinds = cfg.layer_kinds()
        self.has_recurrent = any(k in (MAMBA1, MAMBA2) for k in kinds)
        self.sharing = sharing
        share_seq = sharing and not self.has_recurrent
        stores = []
        self.kv = self.mla = self.img = None
        if self.attn_layers:
            self.kv = cache_cls(PagedCacheSpec(
                n_tensors=2, n_layers=len(self.attn_layers),
                block_size=KV_BLOCK, width=cfg.num_kv_heads * cfg.head_dim,
                num_blocks=kv_blocks, dtype=dtype), sharing=share_seq)
            stores.append(self.kv)
        if self.mla_layers:
            self.mla = cache_cls(PagedCacheSpec(
                n_tensors=1, n_layers=len(self.mla_layers),
                block_size=KV_BLOCK,
                width=cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                num_blocks=kv_blocks, dtype=dtype), sharing=share_seq)
            stores.append(self.mla)
        if cfg.frontend != "none":
            # one image per block so a repeated image shares exactly its
            # own pages (media_tokens when set, the LLaVA default otherwise)
            self.img = cache_cls(PagedCacheSpec(
                n_tensors=1, n_layers=1,
                block_size=cfg.media_tokens or IMG_BLOCK,
                width=cfg.d_model, num_blocks=img_blocks, dtype=dtype),
                sharing=sharing)
            stores.append(self.img)
        self.states = StateStore()
        stores.append(self.states)
        self.stores = stores

    def release(self, rid: int):
        """THE release path for every retire/abort/migrate-source site: with
        sharing enabled this drops *references* — a block survives while any
        other request's table still points at it (ISSUE 6 satellite: the
        PR-4 leak class came from per-path bookkeeping divergence)."""
        for s in self.stores:
            s.free(rid)

    # legacy alias: callers predating the sharing work said "free"
    free = release

    def kv_tokens_free(self) -> int:
        pools = [c for c in (self.kv, self.mla) if c is not None]
        if not pools:
            return 1 << 30  # SSM-only: no token-proportional cache
        return min(c.available_blocks * c.spec.block_size for c in pools)

    def kv_tokens_total(self) -> int:
        """Whole-pool KV capacity in tokens: the admission check's
        can-this-request-EVER-fit bound (DESIGN.md §15)."""
        pools = [c for c in (self.kv, self.mla) if c is not None]
        if not pools:
            return 1 << 30
        return min(c.spec.num_blocks * c.spec.block_size for c in pools)

    def live_rids(self) -> set:
        """Every rid holding any state on this instance's stores — the set
        an instance quarantine must release (DESIGN.md §15)."""
        rids: set = set()
        for s in self.stores:
            if isinstance(s, StateStore):
                rids.update(s.store.keys())
            else:
                rids.update(s.tables.keys())
        return rids


def migrate(rid: int, src: RunnerCaches, dst: RunnerCaches, *,
            fault=None, timeout=None, trace: Trace = OFF) -> int:
    return migrate_request(rid, src.stores, dst.stores, fault=fault,
                           timeout=timeout, trace=trace)


class ModelRunner:
    def __init__(self, cfg: ModelConfig, params, caches: RunnerCaches, *,
                 attn_impl: Optional[str] = None, trace: Trace = OFF):
        self.cfg = cfg
        self.params = params
        self.caches = caches
        self.attn_impl = attn_impl or default_attn_impl()
        self.trace = trace
        impl = self.attn_impl

        # the steps are named functions, not partials or lambdas, so XLA
        # names each program jit_<step> (a partial would be jit__unknown)
        def decode(params, cache, lens, tok):
            return M.decode_step(cfg, params, cache, lens, tok)

        def encode(params, media):
            return M.encode_media(cfg, params, media)

        def paged_decode(params, data, ctl, state, lens, tok):
            return M.decode_step_paged(cfg, params, data, ctl, state, lens,
                                       tok, attn_impl=impl)

        def prefill(params, data, ctl, state, lens, tokens):
            return M.prefill_chunk_paged(cfg, params, data, ctl, state, lens,
                                         tokens, attn_impl=impl)

        def argmax(logits):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)

        self._decode_jit = jax.jit(decode)
        self._encode_jit = jax.jit(encode)
        self._joint_jit = jax.jit(self._joint_fn)
        # device-paged decode: the cache buffers are donated so the
        # cache-write lands in place — without this every step would copy
        # the whole pool just to insert one row per request.  (Backends
        # without donation support fall back to a copy with a warning.)
        self._paged_jit = jax.jit(paged_decode, donate_argnums=(1,))
        self._joint_paged_jit = jax.jit(self._joint_paged_fn,
                                        donate_argnums=(2,))
        # batched chunked prefill over the same device-resident caches
        # (DESIGN.md §12): the page pools are donated for the same reason
        self._prefill_jit = jax.jit(prefill, donate_argnums=(1,))
        # standalone sampler for the dense fallback paths (the paged paths
        # fuse sampling into the step jit via ctl["sample"])
        self._sample_jit = jax.jit(M.sample_from_logits)
        # all-greedy fast path: plain on-device argmax over the no-sample
        # trace's logits — skips the top-k/top-p sorts entirely while still
        # sending only [B] ints to the host (two dispatches, zero copies)
        self._argmax_jit = jax.jit(argmax)

    # ------------------------------------------------------------------
    # sampling control prep
    # ------------------------------------------------------------------
    @staticmethod
    def _all_greedy(sample, idxs=None) -> bool:
        if sample is None:
            return False
        t = np.asarray(sample["temp"])
        return not np.any((t if idxs is None else t[idxs]) > 0)

    @staticmethod
    def _sample_ctl(sample, B_pad: int, idxs=None):
        """Pad/select host sample arrays (see ``M.sample_from_logits``) into
        the jit's control subtree.  Padded lanes get temp=0 (greedy over
        garbage logits, discarded on the host)."""
        if sample is None:
            return None
        out = {}
        for name, dt in (("temp", np.float32), ("top_k", np.int32),
                         ("top_p", np.float32), ("seed", np.uint32),
                         ("step", np.int32)):
            v = np.asarray(sample[name], dt)
            if idxs is not None:
                v = v[idxs]
            pad = B_pad - v.shape[0]
            if pad:
                v = np.concatenate([v, np.zeros(pad, dt)])
            out[name] = jnp.asarray(v)
        return out

    # ------------------------------------------------------------------
    # encode stage
    # ------------------------------------------------------------------
    def encode(self, items):
        """items: [(rid, media [n_media, d_model])] -> image cache entries.

        One item per media element, so a multi-image request contributes
        several items (same rid) that batch alongside everyone else's.
        Mixed media shapes batch per shape group, but the results commit in
        the original item order, so a request's images always land in its
        image cache in submission order.
        """
        if not items:
            return
        with self.trace.span("runner.encode"):
            self._encode(items)

    def _encode(self, items):
        groups: dict[tuple, list] = {}          # shape -> item indices
        for i, (_, m) in enumerate(items):
            groups.setdefault(m.shape, []).append(i)
        embs: list = [None] * len(items)
        for idxs in groups.values():
            grp = [items[i] for i in idxs]
            emb = self._encode_jit(self.params, self._media_batch(grp))
            if not self.caches.device:  # host caches: one batched transfer
                emb = np.asarray(emb)
            for i, e in zip(idxs, emb):
                embs[i] = e
        self._store_encoded(items, embs)

    def _media_batch(self, items):
        """Stack media, padding the batch to a power of two (shape bucket)."""
        media = jnp.stack([m for _, m in items])
        pad = bucket_pow2(media.shape[0]) - media.shape[0]
        if pad:
            media = jnp.concatenate(
                [media, jnp.zeros((pad,) + media.shape[1:], media.dtype)], 0)
        return media

    def _store_encoded(self, items, emb):
        for (rid, _), e in zip(items, emb):
            if self.cfg.cross_attention:
                st = self.caches.states.get(rid) or {}
                if "enc_out" in st:  # later media of the same request
                    e = jnp.concatenate([jnp.asarray(st["enc_out"]), e], 0)
                st["enc_out"] = e
                self.caches.states.put(rid, st)
            else:
                self.caches.img.append(rid, e[None, None])  # [1, 1, T, d]

    # ------------------------------------------------------------------
    # prefill (chunked)
    # ------------------------------------------------------------------
    def _gather_prior(self, rid: int, dtype=jnp.float32):
        cfg = self.cfg
        ents = [dict() for _ in range(cfg.num_layers)]
        if self.caches.kv is not None:
            kv = self.caches.kv.gather(rid)        # [2, L_attn, n, w]
            for j, li in enumerate(self.caches.attn_layers):
                ents[li] = {"k": jnp.asarray(kv[0, j])[None],
                            "v": jnp.asarray(kv[1, j])[None]}
        if self.caches.mla is not None:
            lat = self.caches.mla.gather(rid)      # [1, L_mla, n, R+rope]
            R = cfg.kv_lora_rank
            for j, li in enumerate(self.caches.mla_layers):
                ents[li] = {"ckv": jnp.asarray(lat[0, j, :, :R])[None],
                            "krope": jnp.asarray(lat[0, j, :, R:])[None]}
        st = self.caches.states.get(rid) or {}
        for i, kind in enumerate(cfg.layer_kinds()):
            if kind in (MAMBA1, MAMBA2):
                s = st.get(f"mamba{i}")  # arrays stored with batch dim 1
                ents[i] = {"state": None if s is None else jnp.asarray(s["state"]),
                           "conv": None if s is None else jnp.asarray(s["conv"])}
            if cfg.cross_attention and f"xk{i}" in st:
                ents[i]["xk"] = jnp.asarray(st[f"xk{i}"])
                ents[i]["xv"] = jnp.asarray(st[f"xv{i}"])
        return {"layers": ents}

    def _append_entries(self, rid: int, entries):
        cfg = self.cfg
        if self.caches.kv is not None:
            ks, vs = [], []
            for li in self.caches.attn_layers:
                e = entries["layers"][li]
                ks.append(np.asarray(e["k"][0]))
                vs.append(np.asarray(e["v"][0]))
            self.caches.kv.append(rid, np.stack([np.stack(ks), np.stack(vs)]))
        if self.caches.mla is not None:
            lats = []
            for li in self.caches.mla_layers:
                e = entries["layers"][li]
                lats.append(np.concatenate([np.asarray(e["ckv"][0]),
                                            np.asarray(e["krope"][0])], -1))
            self.caches.mla.append(rid, np.stack(lats)[None])
        st = self.caches.states.get(rid) or {}
        for i, kind in enumerate(cfg.layer_kinds()):
            e = entries["layers"][i]
            if kind in (MAMBA1, MAMBA2):
                st[f"mamba{i}"] = {"state": np.asarray(e["state"]),
                                   "conv": np.asarray(e["conv"])}
            if cfg.cross_attention and "xk" in e:
                st[f"xk{i}"] = np.asarray(e["xk"])
                st[f"xv{i}"] = np.asarray(e["xv"])
        self.caches.states.put(rid, st)

    def prefill_chunk(self, rid: int, tokens: Optional[np.ndarray], *,
                      use_media: bool = False):
        """Run one chunk; returns last-token logits [V] (np).  Device caches
        go through the batched paged path (B=1); host caches run the dense
        gather/concat fallback."""
        if self.caches.device:
            return self.prefill_chunks([(rid, tokens, use_media)])[0]
        return self._prefill_chunk_dense(rid, tokens, use_media=use_media)

    def _prefill_chunk_dense(self, rid: int, tokens: Optional[np.ndarray], *,
                             use_media: bool = False):
        cfg = self.cfg
        prior = self._gather_prior(rid)
        offset = self._ctx_len(rid)
        media_emb = None
        enc_out = None
        if use_media and self.caches.img is not None:
            media_emb = jnp.asarray(self.caches.img.gather(rid)[0, 0])[None]
        st = self.caches.states.get(rid) or {}
        if cfg.cross_attention and "enc_out" in st:
            enc_out = jnp.asarray(st["enc_out"])[None]
        tok = None if tokens is None else jnp.asarray(tokens)[None]
        logits, entries = M.prefill_chunk(cfg, self.params, tok, prior,
                                          offset, enc_out=enc_out,
                                          media_emb=media_emb)
        self._append_entries(rid, entries)
        n_new = (0 if tokens is None else len(tokens)) + \
            (media_emb.shape[1] if media_emb is not None else 0)
        st = self.caches.states.get(rid) or {}
        st["ctx_len"] = offset + n_new
        self.caches.states.put(rid, st)
        return np.asarray(logits[0])

    def _ctx_len(self, rid: int) -> int:
        if self.caches.kv is not None:
            return self.caches.kv.lengths.get(rid, 0)
        if self.caches.mla is not None:
            return self.caches.mla.lengths.get(rid, 0)
        st = self.caches.states.get(rid) or {}
        return int(st.get("ctx_len", 0))

    # ------------------------------------------------------------------
    # prefill (batched, device-resident paged path, DESIGN.md §12)
    # ------------------------------------------------------------------
    def prefill_chunks(self, items, sample=None):
        """One prefill chunk for a batch of requests.  items: [(rid,
        tokens | None, use_media)].  Returns last-token logits
        [len(items), V] (np) in input order — or, when ``sample`` carries
        per-item sampling controls, the sampled next-token ids
        [len(items)] (np int32; only meaningful for items whose prefill
        completes this chunk).

        Device caches run ONE jitted ``prefill_chunk_paged`` call per pow2
        chunk-length bucket (so a whole-image media chunk doesn't pad every
        short text chunk up to its length), batch-padded to a power of two;
        host caches fall back to the per-request dense path.
        """
        with self.trace.span("runner.prefill"):
            return self._prefill_chunks(items, sample)

    def _prefill_chunks(self, items, sample):
        if not self.caches.device:
            lg = np.stack([self._prefill_chunk_dense(rid, toks, use_media=um)
                           for rid, toks, um in items])
            if sample is None:
                return lg
            if self._all_greedy(sample):
                return np.argmax(lg, axis=-1).astype(np.int32)
            return np.asarray(self._sample_jit(
                jnp.asarray(lg), self._sample_ctl(sample, len(items))))
        out = np.zeros((len(items),) if sample is not None
                       else (len(items), self.cfg.vocab_size),
                       np.int32 if sample is not None else np.float32)
        groups: dict[int, list] = {}
        for idx, (rid, toks, um) in enumerate(items):
            n = (0 if toks is None else len(toks)) + \
                (self.caches.img.lengths.get(rid, 0) if um else 0)
            groups.setdefault(bucket_pow2(max(n, 1)), []).append(
                (idx, rid, toks, um, n))
        for C_pad, grp in sorted(groups.items()):
            res = self._prefill_group(grp, C_pad, sample=sample)
            for (idx, *_), lg in zip(grp, res):
                out[idx] = lg
        return out

    def _prefill_group(self, grp, C_pad: int, sample=None):
        """Run one equal-bucket group: [(idx, rid, tokens, use_media,
        n_new)] -> last-token logits [len(grp), V] (np), or sampled token
        ids [len(grp)] when ``sample`` is given (fused into the jit)."""
        cfg = self.cfg
        B = len(grp)
        B_pad = bucket_pow2(B)
        rids = [g[1] for g in grp]
        n_new = [g[4] for g in grp]
        ctx = [self._ctx_len(r) for r in rids]
        tokens = np.zeros((B_pad, C_pad), np.int32)
        mask = np.zeros((B_pad, C_pad), bool)
        img_slots = None
        for b, (_, rid, toks, um, n) in enumerate(grp):
            off = 0
            if um:
                m = self.caches.img.lengths.get(rid, 0)
                if img_slots is None:
                    img_slots = np.full((B_pad, C_pad), -1, np.int32)
                img_slots[b, :m] = self.caches.img.row_slots(rid, 0, m)
                off = m
            if toks is not None:
                tokens[b, off:off + len(toks)] = toks
            mask[b, :n] = True
        last = np.zeros(B_pad, np.int32)
        last[:B] = np.maximum(np.asarray(n_new, np.int32) - 1, 0)
        lens_arr = np.zeros(B_pad, np.int32)
        lens_arr[:B] = ctx
        data, ctl = {}, {}
        for name, cache in (("kv", self.caches.kv), ("mla", self.caches.mla)):
            if cache is None:
                continue
            bs = cache.spec.block_size
            pages = max(-(-(c + n) // bs) for c, n in zip(ctx, n_new))
            tables, slots = cache.prepare_prefill(rids, n_new, B_pad, C_pad,
                                                  bucket_pow2(pages))
            data[name] = cache.data
            ctl[name] = {"tables": jnp.asarray(tables),
                         "slots": jnp.asarray(slots)}
        if img_slots is not None:
            # media positions read the device image cache in the jitted
            # step; the pool rides along read-only (not donated)
            ctl["img"] = {"slots": jnp.asarray(img_slots),
                          "pages": self.caches.img.data}
        ctl["mask"] = jnp.asarray(mask)
        ctl["last"] = jnp.asarray(last)
        idxs = np.asarray([g[0] for g in grp])
        greedy = self._all_greedy(sample, idxs)
        if sample is not None and not greedy:
            ctl["sample"] = self._sample_ctl(sample, B_pad, idxs=idxs)
        state = self._prefill_state(rids, B_pad)
        logits, new_paged, new_state = self._prefill_jit(
            self.params, data, ctl, state, jnp.asarray(lens_arr),
            jnp.asarray(tokens))
        if greedy:
            logits = self._argmax_jit(logits)
        for name, cache in (("kv", self.caches.kv), ("mla", self.caches.mla)):
            if name in new_paged:
                cache.data = new_paged[name]
                cache.commit_prefill(rids, n_new)
        for b, (_, rid, toks, um, n) in enumerate(grp):
            st = self.caches.states.get(rid) or {}
            for i, kind in enumerate(cfg.layer_kinds()):
                e = new_state["layers"][i]
                if kind in (MAMBA1, MAMBA2):
                    st[f"mamba{i}"] = {"state": e["state"][b:b + 1],
                                       "conv": e["conv"][b:b + 1]}
                elif cfg.cross_attention and "xk" in e:
                    st[f"xk{i}"] = e["xk"][b:b + 1]
                    st[f"xv{i}"] = e["xv"][b:b + 1]
            st["ctx_len"] = ctx[b] + n
            self.caches.states.put(rid, st)
        return np.asarray(logits[:B])

    def _prefill_state(self, rids, B_pad: int):
        """Batch the small non-paged per-request prefill state: mamba
        state/conv (zeros for first chunks) and the encoder output for
        cross-attention archs.  Padded lanes get zeros."""
        cfg = self.cfg
        pad = B_pad - len(rids)

        def stack(arrs):
            a = jnp.concatenate([jnp.asarray(x) for x in arrs], 0)
            if pad:
                a = jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0)
            return a

        sts = [self.caches.states.get(r) or {} for r in rids]
        out = []
        for i, kind in enumerate(cfg.layer_kinds()):
            ent = {}
            if kind in (MAMBA1, MAMBA2):
                shapes = (mamba.mamba1_cache_shape(cfg, 1) if kind == MAMBA1
                          else mamba.mamba2_cache_shape(cfg, 1))
                per = [st.get(f"mamba{i}") for st in sts]
                ent["state"] = stack(
                    [np.zeros(shapes["state"], np.float32) if e is None
                     else e["state"] for e in per])
                ent["conv"] = stack(
                    [np.zeros(shapes["conv"], np.float32) if e is None
                     else e["conv"] for e in per])
            out.append(ent)
        tree = {"layers": out}
        if cfg.cross_attention:
            tree["enc_out"] = stack([jnp.asarray(st["enc_out"])[None]
                                     for st in sts])
        return tree

    # ------------------------------------------------------------------
    # decode (batched, heterogeneous contexts)
    # ------------------------------------------------------------------
    def _batched_cache(self, rids):
        cfg = self.cfg
        lens = [self._ctx_len(r) for r in rids]
        # SSM-only archs track context only in states
        S_max = max(lens) + 1 if lens else 1
        B = len(rids)
        priors = [self._gather_prior(r) for r in rids]
        ents_out = []
        for i, kind in enumerate(cfg.layer_kinds()):
            ent = {}
            per = [p["layers"][i] for p in priors]
            if kind in (MAMBA1, MAMBA2):
                ent["state"] = jnp.concatenate([e["state"] for e in per], 0)
                ent["conv"] = jnp.concatenate([e["conv"] for e in per], 0)
            else:
                for name in per[0]:
                    if name in ("xk", "xv"):
                        ent[name] = jnp.concatenate([e[name] for e in per], 0)
                        continue
                    arrs = []
                    for e, L in zip(per, lens):
                        a = e[name]
                        pad = S_max - a.shape[1]
                        arrs.append(jnp.pad(a, ((0, 0), (0, pad), (0, 0))))
                    ent[name] = jnp.concatenate(arrs, 0)
            ents_out.append(ent)
        return {"layers": ents_out}, jnp.asarray(lens, jnp.int32)

    def decode(self, rids, tokens: np.ndarray, sample=None):
        """One decode step for a batch.  tokens: [B].  Returns logits [B, V],
        or sampled next-token ids [B] (np int32) when ``sample`` carries
        per-request sampling controls (see ``M.sample_from_logits``)."""
        with self.trace.span("runner.decode"):
            return self._decode(rids, tokens, sample)

    def _decode(self, rids, tokens: np.ndarray, sample):
        if self.caches.device:
            return self._decode_paged(rids, tokens, sample)
        cfg = self.cfg
        cache, lens = self._batched_cache(rids)
        tok = jnp.asarray(tokens, jnp.int32)[:, None]
        logits, new_cache = self._decode_jit(self.params, cache, lens, tok)
        self._scatter_decoded(rids, new_cache, lens)
        if sample is not None:
            if self._all_greedy(sample):
                return np.asarray(self._argmax_jit(logits))
            return np.asarray(self._sample_jit(
                logits, self._sample_ctl(sample, len(rids))))
        return np.asarray(logits)

    # ------------------------------------------------------------------
    # decode (device-resident paged path, DESIGN.md §11)
    # ------------------------------------------------------------------
    def _prepare_paged(self, rids):
        """Host-side per-step control prep: one-token block headroom, padded
        block tables / slot mappings / lengths.  All tiny int32 arrays — the
        bulk cache never crosses the host boundary."""
        B = len(rids)
        B_pad = bucket_pow2(B)
        lens = [self._ctx_len(r) for r in rids]
        lens_arr = np.zeros(B_pad, np.int32)
        lens_arr[:B] = lens
        data, ctl = {}, {}
        for name, cache in (("kv", self.caches.kv), ("mla", self.caches.mla)):
            if cache is None:
                continue
            bs = cache.spec.block_size
            pages = max(-(-(n + 1) // bs) for n in lens)
            tables, slots = cache.prepare_decode(rids, B_pad,
                                                 bucket_pow2(pages))
            data[name] = cache.data
            ctl[name] = {"tables": jnp.asarray(tables),
                         "slots": jnp.asarray(slots)}
        state = self._batched_state(rids, B_pad)
        return data, ctl, state, jnp.asarray(lens_arr), lens

    def _batched_state(self, rids, B_pad):
        """Batch the small non-paged per-request state (mamba state/conv,
        whisper cross xk/xv); padded lanes get zeros."""
        cfg = self.cfg
        pad = B_pad - len(rids)

        def stack(arrs):
            a = jnp.concatenate([jnp.asarray(x) for x in arrs], 0)
            if pad:
                a = jnp.concatenate(
                    [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], 0)
            return a

        sts = [self.caches.states.get(r) or {} for r in rids]
        out = []
        for i, kind in enumerate(cfg.layer_kinds()):
            ent = {}
            if kind in (MAMBA1, MAMBA2):
                per = [st[f"mamba{i}"] for st in sts]
                ent["state"] = stack([e["state"] for e in per])
                ent["conv"] = stack([e["conv"] for e in per])
            elif cfg.cross_attention and any(f"xk{i}" in st for st in sts):
                # probe per request (not just lane 0 — a batch whose first
                # request lacks cross K/V must not drop everyone else's);
                # lanes without it get zero rows, built from shape metadata
                # only (no device->host transfer of present entries)
                for name in ("xk", "xv"):
                    ref = next(st[f"{name}{i}"] for st in sts
                               if f"{name}{i}" in st)
                    zero = None
                    per = []
                    for st in sts:
                        e = st.get(f"{name}{i}")
                        if e is None:
                            if zero is None:
                                zero = np.zeros(ref.shape, np.float32)
                            e = zero
                        per.append(e)
                    ent[name] = stack(per)
            out.append(ent)
        return {"layers": out}

    def _commit_paged(self, rids, new_paged, new_state, lens):
        """Adopt the (donated) cache buffers and scatter back the small
        per-request state; block tables/lengths advance by one token."""
        for name, cache in (("kv", self.caches.kv), ("mla", self.caches.mla)):
            if name in new_paged:
                cache.data = new_paged[name]
                cache.commit_decode(rids)
        for b, rid in enumerate(rids):
            st = self.caches.states.get(rid) or {}
            for i, kind in enumerate(self.cfg.layer_kinds()):
                if kind in (MAMBA1, MAMBA2):
                    e = new_state["layers"][i]
                    st[f"mamba{i}"] = {"state": e["state"][b:b + 1],
                                      "conv": e["conv"][b:b + 1]}
            st["ctx_len"] = lens[b] + 1
            self.caches.states.put(rid, st)

    def _decode_paged(self, rids, tokens: np.ndarray, sample=None):
        data, ctl, state, lens_arr, lens = self._prepare_paged(rids)
        B_pad = lens_arr.shape[0]
        greedy = self._all_greedy(sample)
        if sample is not None and not greedy:
            ctl["sample"] = self._sample_ctl(sample, B_pad)
        tok = np.zeros((B_pad, 1), np.int32)
        tok[:len(rids), 0] = tokens
        out, new_paged, new_state = self._paged_jit(
            self.params, data, ctl, state, lens_arr, jnp.asarray(tok))
        self._commit_paged(rids, new_paged, new_state, lens)
        if greedy:
            out = self._argmax_jit(out)
        return np.asarray(out[:len(rids)])

    def _scatter_decoded(self, rids, new_cache, lens):
        cfg = self.cfg
        lens = np.asarray(lens)
        for b, rid in enumerate(rids):
            one = {"layers": []}
            for i, kind in enumerate(cfg.layer_kinds()):
                e = new_cache["layers"][i]
                if kind in (MAMBA1, MAMBA2):
                    one["layers"].append(
                        {"state": jnp.asarray(e["state"][b:b + 1]),
                         "conv": jnp.asarray(e["conv"][b:b + 1])})
                else:
                    ent = {}
                    for name, a in e.items():
                        if name in ("xk", "xv"):
                            continue
                        # the newly written token sits at position lens[b]
                        ent[name] = a[b:b + 1, lens[b]:lens[b] + 1]
                    one["layers"].append(ent)
            self._append_entries(rid, one)
            st = self.caches.states.get(rid) or {}
            st["ctx_len"] = int(lens[b]) + 1
            self.caches.states.put(rid, st)

    # ------------------------------------------------------------------
    # fused encode+decode (multi-stream analogue; paper §3.1 / Fig 4)
    # ------------------------------------------------------------------
    def _joint_fn(self, params, media, cache, lens, tok):
        emb = M.encode_media(self.cfg, params, media)
        logits, new_cache = M.decode_step(self.cfg, params, cache, lens, tok)
        return emb, logits, new_cache

    def _joint_paged_fn(self, params, media, data, ctl, state, lens, tok):
        emb = M.encode_media(self.cfg, params, media)
        logits, new_paged, new_state = M.decode_step_paged(
            self.cfg, params, data, ctl, state, lens, tok,
            attn_impl=self.attn_impl)
        return emb, logits, new_paged, new_state

    def joint_encode_decode(self, enc_items, rids, tokens, sample=None):
        """Encode a media batch AND decode a token batch in one jitted
        computation so XLA overlaps MXU-bound encode with HBM-bound decode.

        Returns the decode logits [len(rids), V] (np) — or the sampled
        next-token ids [len(rids)] when ``sample`` is given — or None when
        there was no decode work.  The embeddings land in the image cache /
        state store via ``_store_encoded`` — on device caches they never
        cross the host boundary, so they are deliberately NOT returned
        (every caller only consumes the logits)."""
        if not enc_items:
            return self.decode(rids, tokens, sample)
        if not rids:
            self.encode(enc_items)
            return None
        if len({m.shape for _, m in enc_items}) > 1:
            # mixed media shapes can't stack into one encode batch: run the
            # (shape-grouped) encode separately and decode as usual
            self.encode(enc_items)
            return self.decode(rids, tokens, sample)
        with self.trace.span("runner.joint"):
            return self._joint(enc_items, rids, tokens, sample)

    def _joint(self, enc_items, rids, tokens, sample):
        media = self._media_batch(enc_items)
        greedy = self._all_greedy(sample)
        if self.caches.device:
            data, ctl, state, lens_arr, lens = self._prepare_paged(rids)
            B_pad = lens_arr.shape[0]
            if sample is not None and not greedy:
                ctl["sample"] = self._sample_ctl(sample, B_pad)
            tok = np.zeros((B_pad, 1), np.int32)
            tok[:len(rids), 0] = tokens
            emb, out, new_paged, new_state = self._joint_paged_jit(
                self.params, media, data, ctl, state, lens_arr,
                jnp.asarray(tok))
            self._store_encoded(enc_items, emb)
            self._commit_paged(rids, new_paged, new_state, lens)
            if greedy:
                out = self._argmax_jit(out)
            return np.asarray(out[:len(rids)])
        cache, lens = self._batched_cache(rids)
        tok = jnp.asarray(tokens, jnp.int32)[:, None]
        emb, logits, new_cache = self._joint_jit(self.params, media, cache,
                                                 lens, tok)
        self._store_encoded(enc_items, np.asarray(emb))
        self._scatter_decoded(rids, new_cache, lens)
        if sample is not None:
            if greedy:
                return np.asarray(self._argmax_jit(logits))
            return np.asarray(self._sample_jit(
                logits, self._sample_ctl(sample, len(rids))))
        return np.asarray(logits)
