"""Pallas TPU kernels for the compute hot-spots HydraInfer optimizes:

  flash_attention  - chunked-prefill causal/windowed/GQA attention
  paged_attention  - decode attention over paged KV (scalar-prefetched
                     block tables; paper uses FlashAttention/FlashInfer)
  cache_write      - the paper's fused KV+image-cache write-block kernel
  selective_scan   - Mamba-1 recurrence (falcon-mamba / zamba2 hot loop)

Each subpackage: kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jitted wrapper), ref.py (pure-jnp oracle).  The ops' ``interpret``
flag defaults to the platform: compiled kernels on a TPU, the Pallas
interpreter everywhere else (CPU tests, ``JAX_PLATFORMS=cpu``).
"""
import jax


def resolve_interpret(interpret) -> bool:
    """``interpret=None`` -> the platform default: compiled Mosaic kernels
    on a TPU, the Pallas interpreter elsewhere.  Called at trace time."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
