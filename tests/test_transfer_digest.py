"""The transfer checksums (DESIGN.md §15): device-array leaves get an
on-device 128-bit positional digest whose bytes never leave the device,
numpy leaves keep blake2b on the host, and ``migrate_request`` compares
both checksums of every payload before it imports anything."""
import hashlib
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.engine.faults import (TransferError, corrupt_payload,
                                 payload_checksum, payload_digest,
                                 transfer_digest)
from repro.engine.paged_cache import (DevicePagedCache, PagedCache,
                                      PagedCacheSpec, migrate_request)
from repro.engine.trace import Trace

KV_SHAPE = (2, 3, 6, 4, 16)          # [T, L, blocks, block size, width]
UINT = {4: np.uint32, 2: np.uint16}


def _payload(dtype, seed=0, shape=KV_SHAPE):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


def _lanes(a):
    return np.asarray(transfer_digest(a))


def _flip(a, flat_index: int, bit: int):
    """``a`` with one bit of one element flipped, built on the host."""
    words = np.asarray(a).view(UINT[a.dtype.itemsize]).reshape(-1).copy()
    words[flat_index] ^= words.dtype.type(1 << bit)
    return jnp.asarray(words.view(np.dtype(a.dtype)).reshape(a.shape))


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("dtype,bit", [(jnp.float32, 0), (jnp.float32, 15),
                                       (jnp.float32, 31), (jnp.bfloat16, 0),
                                       (jnp.bfloat16, 15)])
def test_single_bit_flip_changes_every_lane(where, dtype, bit):
    a = _payload(dtype)
    i = {"first": 0, "middle": a.size // 2, "last": a.size - 1}[where]
    bad = _flip(a, i, bit)
    assert np.all(_lanes(bad) != _lanes(a))
    assert payload_checksum(bad) != payload_checksum(a)


def test_swapped_kv_block_columns_are_caught():
    a = _payload(jnp.bfloat16)
    order = np.arange(a.shape[2])
    order[[1, 4]] = order[[4, 1]]
    swapped = a[:, :, order]
    assert not np.array_equal(np.asarray(swapped), np.asarray(a))
    assert payload_checksum(swapped) != payload_checksum(a)


def test_equal_payloads_give_equal_digests():
    a, b = _payload(jnp.bfloat16, seed=3), _payload(jnp.bfloat16, seed=3)
    assert a is not b
    ck = payload_checksum({"kv": a, "meta": {"len": 7}})
    assert ck == payload_checksum({"meta": {"len": 7}, "kv": b})
    assert ck == payload_checksum({"kv": a, "meta": {"len": 7}})


@pytest.mark.parametrize("change", ["shape", "dtype"])
def test_same_bytes_under_another_shape_or_dtype_differ(change):
    a = _payload(jnp.float32)
    if change == "shape":
        b = a.reshape(a.shape[0], -1)
    else:
        b = jax.lax.bitcast_convert_type(a, jnp.int32)
    # the device digest sees only the words and their positions; the
    # shape and dtype are folded in on the host
    assert np.array_equal(_lanes(a), _lanes(b))
    assert payload_digest(a).host != payload_digest(b).host
    assert payload_checksum(a) != payload_checksum(b)


def test_numpy_leaf_goes_through_blake2b_on_the_host():
    a = np.random.default_rng(1).standard_normal((4, 8)).astype(np.float32)
    h = hashlib.blake2b(digest_size=16)
    h.update(str((a.shape, a.dtype.str)).encode())
    h.update(a.tobytes())
    trace = Trace(on=True)
    assert payload_checksum(a, trace) == h.digest()
    assert payload_digest(a).device == ()
    assert trace.counters == {"migrate.host_bytes": a.nbytes}
    assert [n for n, *_ in trace.spans] == ["migrate.fetch", "migrate.hash"]


def test_host_checksums_never_import_jax():
    code = ("import sys, numpy as np\n"
            "from repro.engine.faults import payload_checksum\n"
            "payload_checksum({'k': np.ones((2, 3)), 'n': 4})\n"
            "assert 'jax' not in sys.modules\n")
    src = Path(__file__).resolve().parents[1] / "src"
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"PYTHONPATH": str(src), "JAX_PLATFORMS": "cpu"},
                   timeout=120)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_corrupt_payload_flips_one_word_on_the_device(dtype):
    a = _payload(dtype)
    bad = corrupt_payload({"kv": a, "meta": 3})["kv"]
    assert isinstance(bad, jax.Array)
    assert bad.dtype == a.dtype and bad.shape == a.shape
    diff = (np.asarray(bad).view(UINT[a.dtype.itemsize])
            != np.asarray(a).view(UINT[a.dtype.itemsize]))
    assert diff.sum() == 1 and diff.reshape(-1)[0]
    # the corruption is caught by the device digest alone: the host part
    # (structure, shape, dtype) is unchanged
    good, flipped = payload_digest(a), payload_digest(bad)
    assert flipped.host == good.host
    assert np.all(np.asarray(flipped.device[0]) != np.asarray(good.device[0]))


def _pools(cls):
    dtype = jnp.bfloat16 if cls is DevicePagedCache else np.float32
    spec = PagedCacheSpec(n_tensors=2, n_layers=3, block_size=4, width=16,
                          num_blocks=8, dtype=dtype)
    src, dst = cls(spec), cls(spec)
    rows = np.random.default_rng(2).standard_normal((2, 3, 10, 16))
    src.append(7, rows.astype(np.float32))
    return src, dst


@pytest.mark.parametrize("cls", [DevicePagedCache, PagedCache])
def test_migration_checks_each_payload_by_its_leaf_type(cls):
    src, dst = _pools(cls)
    before = np.asarray(src.gather(7))
    with pytest.raises(TransferError) as ei:
        migrate_request(7, [src], [dst], fault="corrupt")
    assert ei.value.kind == "corrupt"
    assert 7 in src.tables and 7 not in dst.tables    # nothing landed
    trace = Trace(on=True)
    moved = migrate_request(7, [src], [dst], trace=trace)
    assert np.array_equal(np.asarray(dst.gather(7)), before)
    assert 7 not in src.tables                        # released on success
    if cls is DevicePagedCache:
        # digested twice on the device; only the two digests come over
        assert trace.counters == {"migrate.device_bytes": 2 * moved,
                                  "migrate.host_bytes": 2 * 16}
    else:
        assert trace.counters == {"migrate.host_bytes": 2 * moved}
