"""Jitted wrappers for paged attention (decode + chunked prefill)."""
from __future__ import annotations

import functools

import jax

from repro.kernels import resolve_interpret
from repro.kernels.paged_attention.kernel import (paged_attention_tpu,
                                                  paged_prefill_attention_tpu)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_prefill_attention_ref)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "use_kernel", "window"))
def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    interpret=None, use_kernel: bool = True,
                    window: int = 0):
    if not use_kernel:
        return paged_attention_ref(q, k_pages, v_pages, block_tables, lengths,
                                   window=window)
    return paged_attention_tpu(q, k_pages, v_pages, block_tables, lengths,
                               interpret=resolve_interpret(interpret),
                               window=window)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "use_kernel", "window"))
def paged_prefill_attention(q, k_pages, v_pages, block_tables, ctx_lens, *,
                            interpret=None, use_kernel: bool = True,
                            window: int = 0):
    """Chunk queries [B, C, H, D] against pages, chunk-causal (query c sits
    at absolute position ``ctx_lens[b] + c``; the chunk's K/V rows must
    already be written into the pages)."""
    if not use_kernel:
        return paged_prefill_attention_ref(q, k_pages, v_pages, block_tables,
                                           ctx_lens, window=window)
    return paged_prefill_attention_tpu(q, k_pages, v_pages, block_tables,
                                       ctx_lens,
                                       interpret=resolve_interpret(interpret),
                                       window=window)
