"""Fault model for the real-execution serving stack (DESIGN.md §15).

Disaggregated EPD serving multiplies failure domains: a single dead or
wedged instance strands every request mid-pipeline and every migrated KV
block on it.  This module holds the *leaf* pieces of the fault-tolerance
layer — it imports nothing from the engine but its tracing leaf
(``engine/trace.py``), so every other engine module can depend on it:

  FaultPlan / FaultEvent   seeded, deterministic fault injection keyed on
                           the scheduler iteration counter: instance
                           crashes, step stalls (a wedged device), cache
                           allocation failures, and dropped / corrupted
                           E->P / P->D transfers
  TransferError            typed failure of a cache transfer (dropped,
                           corrupt-checksum, destination OOM, timeout) —
                           the migration path retries these with bounded
                           backoff before falling back to journal replay
  AdmissionError           typed rejection of a submit under deadline-aware
                           load shedding (capacity durably degraded)
  RequestJournal           the minimal per-request durable record (prompt,
                           media content-hashes, sampling seed; accepted
                           tokens live in the ServeItem) that makes a
                           stranded request re-dispatchable with bit-exact
                           greedy/seeded continuation
  payload_checksum         end-to-end checksum over a transfer payload
                           (numpy / jnp arrays or nested dict trees), how
                           corrupted transfers are *detected*: a jax.Array
                           leaf by an on-device positional digest
                           (``transfer_digest``) whose bytes stay on the
                           device, a numpy leaf by blake2b on the host

Injection is deterministic by construction: a plan is a sorted set of
(iteration, kind, instance) events, and ``FaultPlan.random`` derives one
from a seed, so a failing fault sweep reproduces from its seed alone.
"""
from __future__ import annotations

import functools
import hashlib
import re
import sys
from dataclasses import dataclass, field
from typing import NamedTuple, Optional

import numpy as np

from repro.engine.trace import OFF, Trace

# fault kinds
CRASH = "crash"          # instance dies: all device state lost
STALL = "stall"          # instance wedges for `arg` iterations (no progress)
ALLOC = "alloc"          # cache allocations fail for `arg` iterations
DROP = "drop"            # migration payload lost in flight
CORRUPT = "corrupt"      # migration payload corrupted in flight
KINDS = (CRASH, STALL, ALLOC, DROP, CORRUPT)


class TransferError(RuntimeError):
    """A cache transfer failed in a retryable way.  ``kind`` is one of
    "drop" | "corrupt" | "oom" | "timeout"."""

    def __init__(self, kind: str, msg: str):
        super().__init__(msg)
        self.kind = kind


class AdmissionError(RuntimeError):
    """Submit rejected: capacity is durably degraded and the request could
    never meet its deadline (deadline-aware load shedding, DESIGN.md §15).
    Typed so fronts can map it to a proper 503 instead of queueing the
    request forever."""


@dataclass(frozen=True)
class FaultEvent:
    """One injected fault.  ``iteration`` counts *productive* scheduler
    iterations (steps where some instance had pending work — idle spins
    between Poisson arrivals don't advance fault time, so plans stay
    meaningful under open-loop load).  ``iid`` targets one instance; -1
    matches any.  ``arg`` is the window length in iterations for
    stall/alloc, and the number of failing transfer *attempts* for
    drop/corrupt (1 = first attempt fails, the retry succeeds)."""
    iteration: int
    kind: str
    iid: int = -1
    arg: int = 1

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"known: {KINDS}")


@dataclass
class RequestJournal:
    """Minimal durable record for failure recovery (DESIGN.md §15): enough
    to re-dispatch a stranded request to a surviving instance and replay it
    to a bit-exact continuation.  The original prompt is kept verbatim (the
    live ServeItem.prompt is rewritten with replay context on recovery);
    media is identified by content hash so the host-side copy can be
    integrity-checked before re-encoding; the resolved sampling seed plus
    the accepted-token count pin the per-lane PRNG stream."""
    prompt: np.ndarray          # original prompt token ids (copy)
    media_hashes: tuple = ()    # per-image blake2b content hashes
    seed: int = 0               # resolved sampling seed


class FaultPlan:
    """A deterministic schedule of injected faults, queried by the server
    each scheduler iteration.  Build one explicitly from events, randomly
    from a seed (``FaultPlan.random``), or from a CLI spec string
    (``FaultPlan.parse``)."""

    def __init__(self, events=()):
        self.events = tuple(sorted(events, key=lambda e: (e.iteration,
                                                          e.kind, e.iid)))
        self._crashed: set = set()   # one-shot crash events already fired

    def __repr__(self):
        return f"FaultPlan({list(self.events)!r})"

    def __bool__(self):
        return bool(self.events)

    # ------------------------------------------------------------------
    def _match(self, ev: FaultEvent, iid: int) -> bool:
        return ev.iid < 0 or ev.iid == iid

    def crash(self, iteration: int, iid: int) -> bool:
        """True exactly once per crash event, at (or after — an instance
        that was idle at the chosen iteration still dies) its iteration."""
        for i, ev in enumerate(self.events):
            if ev.kind == CRASH and self._match(ev, iid) \
                    and iteration >= ev.iteration and i not in self._crashed:
                self._crashed.add(i)
                return True
        return False

    def _in_window(self, kind: str, iteration: int, iid: int) -> bool:
        return any(ev.kind == kind and self._match(ev, iid)
                   and ev.iteration <= iteration < ev.iteration + max(ev.arg, 1)
                   for ev in self.events)

    def stalled(self, iteration: int, iid: int) -> bool:
        """Instance ``iid`` is wedged this iteration (builds batches but
        executes nothing — the no-progress failure mode)."""
        return self._in_window(STALL, iteration, iid)

    def alloc_fail(self, iteration: int, iid: int) -> bool:
        """Cache allocations on ``iid`` fail this iteration."""
        return self._in_window(ALLOC, iteration, iid)

    def transfer_fault(self, iteration: int, attempt: int) -> Optional[str]:
        """Fault applied to a migration attempted this iteration, or None.
        ``attempt`` indexes retries: an event only affects attempts below
        its ``arg``, so ``arg=1`` exercises retry-and-succeed while a large
        ``arg`` exhausts the retry budget and forces journal replay."""
        for ev in self.events:
            if ev.kind in (DROP, CORRUPT) and ev.iteration <= iteration \
                    and attempt < ev.arg:
                # windows are open-ended on attempts, not iterations: a
                # migration deferred past the chosen iteration still hits
                if iteration < ev.iteration + 1:
                    return ev.kind
        return None

    # ------------------------------------------------------------------
    @classmethod
    def random(cls, seed: int, *, horizon: int, iids,
               p_crash: float = 0.0, p_stall: float = 0.02,
               p_alloc: float = 0.02, p_transfer: float = 0.02,
               max_crashes: int = 0, stall_len: int = 3) -> "FaultPlan":
        """Derive a plan from a seed: per (iteration, instance) Bernoulli
        draws for stalls/allocation failures/transfer faults, plus up to
        ``max_crashes`` crashes at uniform iterations (never more than
        len(iids) - 1, so at least one instance survives)."""
        rng = np.random.default_rng(seed)
        iids = list(iids)
        events = []
        n_crash = min(int(max_crashes), max(len(iids) - 1, 0))
        if n_crash and p_crash > 0:
            victims = rng.choice(len(iids), size=n_crash, replace=False)
            for v in victims:
                if rng.random() < p_crash:
                    events.append(FaultEvent(
                        int(rng.integers(1, max(horizon, 2))), CRASH,
                        iid=iids[int(v)]))
        for it in range(1, horizon + 1):
            for iid in iids:
                if rng.random() < p_stall:
                    events.append(FaultEvent(it, STALL, iid=iid,
                                             arg=int(rng.integers(
                                                 1, stall_len + 1))))
                if rng.random() < p_alloc:
                    events.append(FaultEvent(it, ALLOC, iid=iid))
            if rng.random() < p_transfer:
                events.append(FaultEvent(
                    it, DROP if rng.random() < 0.5 else CORRUPT))
        return cls(events)

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        """CLI knob: comma-separated ``kind@iteration[:iid][+arg]`` parts,
        e.g. ``crash@100:1,stall@40:0+5,drop@60,alloc@80:2``."""
        events = []
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            m = re.fullmatch(
                r"(\w+)@(\d+)(?::(-?\d+))?(?:\+(\d+))?", part)
            if not m:
                raise ValueError(
                    f"bad fault spec {part!r} "
                    f"(expected kind@iteration[:iid][+arg])")
            events.append(FaultEvent(int(m.group(2)), m.group(1),
                                     iid=int(m.group(3) or -1),
                                     arg=int(m.group(4) or 1)))
        return cls(events)


# ---------------------------------------------------------------------------
# transfer checksums (corruption *detection*; injection lives in the plan)
# ---------------------------------------------------------------------------
# seeds of the four lanes of the device digest (any distinct constants)
_LANE_SEEDS = (0x243F6A88, 0x85A308D3, 0x13198A2E, 0x03707344)


def _fmix32(x):
    """murmur3's 32-bit finaliser: a bijection of uint32 that spreads
    every input bit over the output."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _words(a):
    """``a`` as uint32 words, elementwise: 4-byte elements bitcast,
    narrower ones zero-extended, 8-byte ones split in two (a trailing
    axis of 2)."""
    import jax.numpy as jnp
    from jax import lax

    bits = 8 * min(a.dtype.itemsize, 4)
    return lax.bitcast_convert_type(a, jnp.dtype(f"uint{bits}")).astype(
        jnp.uint32)


def transfer_digest(a):
    """128-bit positional digest of one array, in one fused pass over its
    bytes: four uint32 lanes, lane k = sum_i w_i * (fmix32(i ^ seed_k) | 1)
    mod 2**32 over the words w_i at flat index i.  A multiplier is odd and
    d * odd is never 0 mod 2**32 for 0 < |d| < 2**32, so any change to any
    one word changes every lane; words moved between positions change the
    digest unless the multipliers collide.  Shape and dtype are not in it:
    the caller folds them in on the host."""
    import jax.numpy as jnp
    from jax import lax

    w = _words(a)
    idx = jnp.zeros(w.shape, jnp.uint32)
    stride = 1
    for d in reversed(range(w.ndim)):
        idx = idx + (lax.broadcasted_iota(jnp.uint32, w.shape, d)
                     * np.uint32(stride % (1 << 32)))
        stride *= w.shape[d]
    return jnp.stack([
        jnp.sum(w * (_fmix32(idx ^ np.uint32(s)) | np.uint32(1)),
                dtype=jnp.uint32)
        for s in _LANE_SEEDS])


@functools.cache
def _digest_jit():
    """The jitted digest, built on first use (host-only tools never import
    jax); one program per payload shape and dtype."""
    import jax
    return jax.jit(transfer_digest)


def _is_device_array(x) -> bool:
    """A ``jax.Array`` leaf.  jax is looked up, not imported: a process
    that never imported it holds no device arrays."""
    jax = sys.modules.get("jax")
    return jax is not None and isinstance(x, jax.Array)


class PayloadDigest(NamedTuple):
    """One payload's checksum, its device part not yet fetched."""
    host: bytes       # blake2b-16: structure, shapes, dtypes, numpy bytes
    device: tuple     # one uint32[4] digest per jax.Array leaf, on device


def _walk_arrays(payload, visit, device: list, trace: Trace):
    """Deterministic traversal of a transfer payload: arrays directly, dict
    trees in sorted key order, scalars by repr.  Every array's shape and
    dtype go to ``visit``.  A jax.Array's digest is dispatched on its
    device (span ``migrate.hash``, counter ``migrate.device_bytes``) and
    appended to ``device``: its bytes never leave the device.  A numpy
    array's bytes are copied out (span ``migrate.fetch``, counter
    ``migrate.host_bytes``) and go to ``visit`` (``migrate.hash``)."""
    if isinstance(payload, dict):
        for k in sorted(payload, key=str):
            visit(str(k).encode())
            _walk_arrays(payload[k], visit, device, trace)
    elif _is_device_array(payload):
        visit(str((payload.shape, payload.dtype.str)).encode())
        with trace.span("migrate.hash"):
            device.append(_digest_jit()(payload))
        trace.count("migrate.device_bytes", payload.nbytes)
    elif hasattr(payload, "shape"):
        with trace.span("migrate.fetch"):
            a = np.ascontiguousarray(np.asarray(payload))
            data = a.tobytes()
        trace.count("migrate.host_bytes", len(data))
        with trace.span("migrate.hash"):
            visit(str((a.shape, a.dtype.str)).encode())
            visit(data)
    else:
        visit(repr(payload).encode())


def payload_digest(payload, trace: Trace = OFF) -> PayloadDigest:
    """Start the checksum of one store's transfer payload: the host part
    is done, the device digests are dispatched and left on the device."""
    h = hashlib.blake2b(digest_size=16)
    device: list = []
    _walk_arrays(payload, h.update, device, trace)
    return PayloadDigest(h.digest(), tuple(device))


def fetch_checksums(digests, trace: Trace = OFF) -> list:
    """The checksums of ``digests`` as bytes: the host part, then 16 bytes
    per device leaf.  Every device digest comes to the host in one
    transfer (span ``migrate.fetch``, counter ``migrate.host_bytes``),
    which waits for the device's reads and digests."""
    parts = [d.device for d in digests]
    if any(parts):
        import jax
        with trace.span("migrate.fetch"):
            parts = jax.device_get(parts)
        trace.count("migrate.host_bytes",
                    sum(x.nbytes for p in parts for x in p))
    return [d.host + b"".join(np.asarray(x).tobytes() for x in p)
            for d, p in zip(digests, parts)]


def payload_checksum(payload, trace: Trace = OFF) -> bytes:
    """End-to-end checksum of one store's transfer payload."""
    return fetch_checksums([payload_digest(payload, trace)], trace)[0]


def corrupt_payload(payload):
    """Return a bit-flipped copy of ``payload`` (the simulated wire
    corruption a checksum must catch): the low 8 bits of the first
    element of the first array leaf flip.  A jax.Array is flipped on its
    device and comes back a jax.Array; dict trees corrupt their first
    array leaf; empty payloads come back unchanged."""
    if isinstance(payload, dict):
        for k in sorted(payload, key=str):
            flipped = corrupt_payload(payload[k])
            if flipped is not payload[k]:
                out = dict(payload)
                out[k] = flipped
                return out
        return payload
    if _is_device_array(payload):
        if not payload.size:
            return payload
        import jax.numpy as jnp
        from jax import lax
        u = lax.bitcast_convert_type(
            payload, jnp.dtype(f"uint{8 * payload.dtype.itemsize}"))
        first = (0,) * u.ndim
        return lax.bitcast_convert_type(u.at[first].set(u[first] ^ 0xFF),
                                        payload.dtype)
    if hasattr(payload, "shape"):
        a = np.array(np.asarray(payload), copy=True)
        if a.size:
            flat = a.view(np.uint8).reshape(-1)
            flat[0] ^= 0xFF
            return a
    return payload
