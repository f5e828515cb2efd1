"""Jitted wrapper for the fused cache write (KV cache AND image cache —
they share the paged block layout, so one kernel serves both)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.cache_write.kernel import cache_write_tpu
from repro.kernels.cache_write.ref import cache_write_ref


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"),
                   donate_argnums=(0,))
def cache_write(cache, new, slot_mapping, *, interpret=None,
                use_kernel: bool = True):
    if not use_kernel:
        return cache_write_ref(cache, new, slot_mapping)
    return cache_write_tpu(cache, new, slot_mapping,
                           interpret=resolve_interpret(interpret))


def paged_token_write(data, layer: int, rows, slots, *, interpret=None,
                      use_kernel: bool = True):
    """Append one token per request into every tensor of one layer of a
    ``[T, L, num_blocks, bs, width]`` paged store with ONE fused kernel
    launch (paper §4.5: batch the many small per-token cache writes).

    rows: [T, B, width] new per-tensor rows; slots: [B] within-plane row
    slots (``block * bs + offset``); ``layer`` is a static layer index.
    Returns the updated store (in place under donation/aliasing).

    Exactly the C == 1 case of :func:`paged_chunk_write`.
    """
    return paged_chunk_write(data, layer, rows[:, :, None, :], slots[:, None],
                             interpret=interpret, use_kernel=use_kernel)


def paged_chunk_write(data, layer: int, rows, slots, *, interpret=None,
                      use_kernel: bool = True):
    """Append a whole prefill *chunk* per request — C tokens each — into
    every tensor of one layer of a ``[T, L, num_blocks, bs, width]`` paged
    store with ONE fused kernel launch (the multi-token extension of
    :func:`paged_token_write`).

    rows: [T, B, C, width] new per-tensor chunk rows; slots: [B, C]
    within-plane row slots (``block * bs + offset``; padded chunk positions
    point at the scratch block); ``layer`` is a static layer index.
    Returns the updated store (in place under donation/aliasing).
    """
    T, L, NB, bs, w = data.shape
    B, C = slots.shape
    flat = data.reshape(T * L * NB, bs, w)
    new = rows.reshape(T * B * C, w)
    plane = (jnp.arange(T, dtype=jnp.int32) * L + layer) * (NB * bs)
    slot_vec = (plane[:, None] + slots.reshape(-1)[None, :]).reshape(-1)
    flat = cache_write(flat, new, slot_vec, interpret=interpret,
                       use_kernel=use_kernel)
    return flat.reshape(T, L, NB, bs, w)
