"""Pallas TPU fused paged-cache write.

The paper (§4.5) fuses the many small per-token cache writes — for BOTH the
multi-layer KV cache and the single-layer image-token cache, which share a
block layout — into one kernel launch to avoid per-write launch overhead.
Here: grid over new rows; the destination *page* of the paged cache is
selected via a scalar-prefetched slot mapping in the BlockSpec index_map,
and the cache operand is input/output-aliased so the write is in place.

The TPU tiles the last two dims of a VMEM block in (8, 128) units, so a
block is never one row: each grid step holds a ``(1, 16, w)`` row group of
the destination page (a 576-row image page would not fit VMEM whole) and
selects the new row into it.  Consecutive rows that land in the same group
(a prefill chunk, or a decode step's padding lanes) share one fetch and one
write-back, because Pallas only moves a block when its index changes.  The rows are sorted by slot first, so each page
is visited in one run: a page visited again after another page would be
re-fetched while its earlier write-back may still be in flight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_GROUP = 16   # rows per block: a whole bf16 (16, 128) tile row


def _write_kernel(slots, new_ref, cache_in_ref, cache_out_ref, *, bs: int):
    t = pl.program_id(0)
    page = slots[t] // bs

    # first row of a run into this page: start from the page as it is in
    # HBM; later rows of the run keep building on the resident out block
    @pl.when((t == 0) | (slots[jnp.maximum(t - 1, 0)] // bs != page))
    def _load():
        cache_out_ref[...] = cache_in_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, cache_out_ref.shape, 1)
    cache_out_ref[...] = jnp.where(row == slots[t] % bs,
                                   new_ref[...].astype(cache_out_ref.dtype),
                                   cache_out_ref[...])


def cache_write_tpu(cache, new, slot_mapping, *, interpret: bool = False):
    """cache: [n_blocks, bs, w]; new: [T, w]; slot_mapping: [T] -> updated cache."""
    n_blocks, page_rows, w = cache.shape
    # view the pages as row groups; slot = row index either way
    bs = _GROUP if page_rows % _GROUP == 0 else page_rows
    T = new.shape[0]
    order = jnp.argsort(slot_mapping, stable=True)
    page_spec = pl.BlockSpec((1, bs, w),
                             lambda t, slots: (slots[t] // bs, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T,),
        in_specs=[pl.BlockSpec((1, 1, w), lambda t, slots: (t, 0, 0)),
                  page_spec],
        out_specs=page_spec,
    )
    groups = cache.reshape(n_blocks * page_rows // bs, bs, w)
    out = pl.pallas_call(
        functools.partial(_write_kernel, bs=bs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(groups.shape, cache.dtype),
        input_output_aliases={2: 0},   # cache operand aliases the output
        interpret=interpret,
    )(slot_mapping[order], new[order].reshape(T, 1, w), groups)
    return out.reshape(cache.shape)
