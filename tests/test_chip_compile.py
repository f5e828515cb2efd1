"""Ahead-of-time compiles of the serving path's Pallas kernels for a TPU
v5e, at llava-1.5-7b widths in bf16 (32 heads = 32 KV heads of 128, 16-row
pages, 4096-wide KV rows).

Nothing runs: the TPU compiler, which is installed with JAX, compiles for a
described chip and raises what the chip's compiler would raise (tiling of
block shapes, scoped-VMEM limits).  Interpret-mode tests cannot see either.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import every test file.  Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cache_write.ops import paged_chunk_write, paged_token_write
from repro.kernels.paged_attention.ops import (paged_attention,
                                              paged_prefill_attention)

H = KH = 32          # llava-1.5-7b: multi-head attention
D = 128
PAGE = 16            # engine.runner.KV_BLOCK
N_PAGES = 512
MAX_PAGES = 64
LAYERS = 16
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles can be written to the persistent cache
    # but not read back: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_decode_attention_compiles(one_chip):
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    B = 8
    _compile(lambda q, k, v, bt, ln: paged_attention(q, k, v, bt, ln,
                                                     interpret=False),
             S((B, H, D), BF16), S((N_PAGES, PAGE, KH, D), BF16),
             S((N_PAGES, PAGE, KH, D), BF16), S((B, MAX_PAGES), jnp.int32),
             S((B,), jnp.int32))


@pytest.mark.parametrize("B,C", [(8, 16), (4, 64), (1, 1024)])
def test_prefill_attention_compiles(one_chip, B, C):
    """C = 64 is the prefill token budget; 1024 is the pow2 bucket of a
    576-token image chunk."""
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    _compile(lambda q, k, v, bt, cl: paged_prefill_attention(
                 q, k, v, bt, cl, interpret=False),
             S((B, C, H, D), BF16), S((N_PAGES, PAGE, KH, D), BF16),
             S((N_PAGES, PAGE, KH, D), BF16), S((B, MAX_PAGES), jnp.int32),
             S((B,), jnp.int32))


@pytest.mark.parametrize("kind", ["token", "chunk"])
def test_cache_write_compiles(one_chip, kind):
    """The fused write of one decode token (B = 8) or one prefill chunk
    (B = 4, C = 64) into every tensor of one layer of a 16-layer pool."""
    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    pool = S((2, LAYERS, N_PAGES + 1, PAGE, KH * D), BF16)
    if kind == "token":
        rows, slots = S((2, 8, KH * D), BF16), S((8,), jnp.int32)
        fn = lambda d, r, s: paged_token_write(d, 3, r, s, interpret=False)
    else:
        rows, slots = S((2, 4, 64, KH * D), BF16), S((4, 64), jnp.int32)
        fn = lambda d, r, s: paged_chunk_write(d, 3, r, s, interpret=False)
    compiled = _compile(fn, pool, rows, slots)
    # the pool is written in place: no second pool-sized buffer
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("helper", ["read", "import", "copy"])
def test_pool_block_moves_stay_in_place(one_chip, helper):
    """Migration reads/landings and COW copies of a 16-layer llava pool
    move block columns without a pool-sized temporary (a gather or scatter
    over the block axis needs one, and leaves no room for it on a chip
    whose memory the pools fill)."""
    from repro.engine import paged_cache as PC

    S = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
    pool = S((2, LAYERS, N_PAGES + 1, PAGE, KH * D), BF16)
    n = 53                       # one 576-image + 256-token request
    blocks = S((n,), jnp.int32)
    impl, args, donate = {
        "read": (PC._read_impl, (pool, blocks), ()),
        "import": (PC._import_impl,
                   (pool, blocks, S((2, LAYERS, n, PAGE, KH * D), BF16)),
                   (0,)),
        "copy": (PC._copy_impl, (pool, blocks, blocks), (0,)),
    }[helper]
    compiled = jax.jit(impl, donate_argnums=donate).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("payload", ["kv", "image"])
def test_transfer_digest_needs_no_payload_sized_temporary(one_chip, payload):
    """The hand-off's on-device checksum of one request's KV payload (a
    576-image + 256-token request) or image page reads the payload in a
    fused pass: the words, indices and multipliers are never materialised."""
    from repro.engine.faults import transfer_digest

    shape = {"kv": (2, LAYERS, 53, PAGE, KH * D),
             "image": (1, 1, 1, 576, 4096)}[payload]
    x = jax.ShapeDtypeStruct(shape, BF16, sharding=one_chip)
    compiled = jax.jit(transfer_digest).lower(x).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
