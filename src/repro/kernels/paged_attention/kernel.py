"""Pallas TPU paged attention: decode and chunked prefill.

Decode: one new query token per request attends to its paged KV cache.
Chunked prefill: a chunk of C query tokens per request attends the same
pages with a *chunk-causal* mask — query c (absolute position ctx+c) sees
key positions <= ctx+c, so one kernel covers both the prior context and
the intra-chunk triangle once the chunk's K/V rows are written into the
pages (write-then-attend).

In both, the block table is a *scalar-prefetched* operand
(PrefetchScalarGridSpec) so the BlockSpec index_map can chase page
indirections at grid-issue time — the TPU-native replacement for GPU
pointer-chasing page tables.

Grid: (batch, max_pages) with per-batch online-softmax scratch persisting
across the page dimension.  KV pages are tiled HBM->VMEM one page at a
time: block (1, page_size, Kh*D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(block_tables, lengths, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page: int, n_kv_heads: int,
                  max_pages: int, window: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    H, D = q_ref.shape[1], q_ref.shape[2]
    Kh = n_kv_heads
    G = H // Kh
    q = q_ref[0].astype(jnp.float32) / math.sqrt(D)       # [H, D]
    k = k_ref[0].astype(jnp.float32)                      # [page, Kh, D]
    v = v_ref[0].astype(jnp.float32)

    # positions of this page's tokens within the request
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)[0]
    valid = pos < lengths[b]                              # [page]
    if window:  # sliding-window lower bound (static: baked per-layer)
        valid &= pos >= lengths[b] - window

    qg = q.reshape(Kh, G, D)
    s = jnp.einsum("kgd,pkd->kgp", qg, k,
                   preferred_element_type=jnp.float32)    # [Kh, G, page]
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev = m_scr[...]                                   # [Kh, G]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1)
    acc = jnp.einsum("kgp,pkd->kgd", p, v,
                     preferred_element_type=jnp.float32)  # [Kh, G, D]
    acc_scr[...] = alpha[..., None] * acc_scr[...] + acc
    m_scr[...] = m_new

    @pl.when(j == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = (acc_scr[...] / l).reshape(H, D).astype(o_ref.dtype)


def paged_attention_tpu(q, k_pages, v_pages, block_tables, lengths, *,
                        interpret: bool = False, window: int = 0):
    """q: [B, H, D]; pages: [n_pages, page, Kh, D];
    block_tables: [B, max_pages]; lengths: [B]."""
    B, H, D = q.shape
    n_pages, page, Kh, _ = k_pages.shape
    max_pages = block_tables.shape[1]

    kernel = functools.partial(_paged_kernel, page=page, n_kv_heads=Kh,
                               max_pages=max_pages, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, bt, ln: (b, 0, 0)),
            # page indirection: the block index comes from the prefetched table
            pl.BlockSpec((1, page, Kh, D), lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((1, page, Kh, D), lambda b, j, bt, ln: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, bt, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Kh, H // Kh), jnp.float32),
            pltpu.VMEM((Kh, H // Kh), jnp.float32),
            pltpu.VMEM((Kh, H // Kh, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )(block_tables, lengths, q, k_pages, v_pages)


_ROW_TILE = 64   # query rows (chunk positions x group heads) per grid step
_LANES = 128     # m/l scratch rows are replicated across one lane tile


def _query_tile(C: int, G: int) -> int:
    """Chunk positions per grid step: the whole chunk when its C*G query
    rows fit one row tile, else the largest halving of C that does."""
    tc = C
    while tc * G > _ROW_TILE and tc % 2 == 0:
        tc //= 2
    return tc


def _last_page(ctx_lens, b, i, *, tc: int, page: int, max_pages: int):
    """Last page that any query of chunk tile ``i`` can see."""
    return jnp.minimum((ctx_lens[b] + (i + 1) * tc - 1) // page,
                       max_pages - 1)


def _paged_prefill_kernel(block_tables, ctx_lens, q_ref, k_ref, v_ref, o_ref,
                          m_scr, l_scr, acc_scr, *, page: int, tc: int,
                          group: int, max_pages: int, window: int):
    b = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # pages past the tile's last query are fully masked: skip them (the
    # index_map already stopped fetching them)
    @pl.when(j <= _last_page(ctx_lens, b, i, tc=tc, page=page,
                             max_pages=max_pages))
    def _step():
        Kh, R, D = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
        q = q_ref[0].astype(jnp.float32) / math.sqrt(D)     # [Kh, R, D]
        k = k_ref[0].astype(jnp.float32)                     # [page, Kh, D]
        v = v_ref[0].astype(jnp.float32)

        # chunk-causal mask: row r is chunk position i*tc + r // group at
        # absolute position ctx + that, and sees key positions <= it
        # (page-fully-masked rows self-correct through the online-softmax
        # rescale: their junk is accumulated under m == NEG_INF and zeroed
        # by alpha once a real score arrives)
        pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (R, page), 1)
        qpos = (ctx_lens[b] + i * tc
                + jax.lax.broadcasted_iota(jnp.int32, (R, page), 0) // group)
        valid = pos <= qpos                                  # [R, page]
        if window:  # sliding-window lower bound (static: baked per-layer)
            valid &= pos > qpos - window

        s = jnp.einsum("krd,pkd->krp", q, k,
                       preferred_element_type=jnp.float32)  # [Kh, R, page]
        s = jnp.where(valid[None], s, NEG_INF)

        m_prev = m_scr[...][..., :1]                         # [Kh, R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[...][..., :1] + jnp.sum(p, axis=-1,
                                                      keepdims=True)
        acc = jnp.einsum("krp,pkd->krd", p, v,
                         preferred_element_type=jnp.float32)  # [Kh, R, D]
        acc_scr[...] = alpha * acc_scr[...] + acc
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == max_pages - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...][..., :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_prefill_attention_tpu(q, k_pages, v_pages, block_tables, ctx_lens,
                                *, interpret: bool = False, window: int = 0):
    """q: [B, C, H, D] chunk queries (query c at position ctx_lens[b] + c);
    pages: [n_pages, page, Kh, D]; block_tables: [B, max_pages];
    ctx_lens: [B] tokens cached before the chunk.

    The queries are regrouped per KV head as ``[B, Kh, C*G, D]`` rows
    (row = chunk position * G + group head), and the grid tiles the chunk
    so that one step holds at most ``_ROW_TILE`` rows: the online-softmax
    scratch is then ``[Kh, rows, 128]`` whatever the chunk length.
    """
    B, C, H, D = q.shape
    n_pages, page, Kh, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    G = H // Kh
    tc = _query_tile(C, G)
    R = tc * G
    qr = q.reshape(B, C, Kh, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, Kh, C * G, D)

    def page_map(b, i, j, bt, cl):
        # past the tile's last visible page, keep the block index unchanged
        # so the pipeline issues no fetch for the skipped steps
        last = _last_page(cl, b, i, tc=tc, page=page, max_pages=max_pages)
        return (bt[b, jnp.minimum(j, last)], 0, 0, 0)

    kernel = functools.partial(_paged_prefill_kernel, page=page, tc=tc,
                               group=G, max_pages=max_pages, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, C // tc, max_pages),
        in_specs=[
            pl.BlockSpec((1, Kh, R, D), lambda b, i, j, bt, cl: (b, 0, i, 0)),
            # page indirection: the block index comes from the prefetched table
            pl.BlockSpec((1, page, Kh, D), page_map),
            pl.BlockSpec((1, page, Kh, D), page_map),
        ],
        out_specs=pl.BlockSpec((1, Kh, R, D),
                               lambda b, i, j, bt, cl: (b, 0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((Kh, R, _LANES), jnp.float32),
            pltpu.VMEM((Kh, R, _LANES), jnp.float32),
            pltpu.VMEM((Kh, R, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kh, C * G, D), q.dtype),
        interpret=interpret,
    )(block_tables, ctx_lens, qr, k_pages, v_pages)
    return out.reshape(B, Kh, C, G, D).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, D)
