"""Jitted wrapper: pads sequences to block multiples, dispatches kernel/ref."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_tpu
from repro.kernels.flash_attention.ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret",
                                             "use_kernel"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret=None, use_kernel: bool = True):
    """Public op.  q: [B, H, Sq, D]; k/v: [B, Kh, Sk, D].

    ``interpret`` defaults to the platform: the compiled kernel on a TPU,
    the Pallas interpreter (kernel body run in Python) elsewhere.
    """
    if not use_kernel:
        return flash_attention_ref(q, k, v, causal=causal, window=window)
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    pad_q = (-Sq) % bq
    pad_k = (-Sk) % bk
    if pad_q or pad_k:
        qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    out = flash_attention_tpu(qp, kp, vp, causal=causal, window=window,
                              block_q=bq, block_k=bk,
                              interpret=resolve_interpret(interpret),
                              kv_len=Sk)
    return out[:, :, :Sq]
