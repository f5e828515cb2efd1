"""Jitted wrapper for the selective scan."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.selective_scan.kernel import selective_scan_tpu
from repro.kernels.selective_scan.ref import selective_scan_ref


@functools.partial(jax.jit, static_argnames=("block_d", "chunk", "interpret",
                                             "use_kernel"))
def selective_scan(dt, x, A, Bmat, Cmat, h0=None, *, block_d: int = 256,
                   chunk: int = 256, interpret=None,
                   use_kernel: bool = True):
    if h0 is None:
        Bsz, _, d = x.shape
        h0 = jnp.zeros((Bsz, d, A.shape[1]), jnp.float32)
    if not use_kernel:
        return selective_scan_ref(dt, x, A, Bmat, Cmat, h0)
    return selective_scan_tpu(dt, x, A, Bmat, Cmat, h0, block_d=block_d,
                              chunk=chunk,
                              interpret=resolve_interpret(interpret))
