"""The engine's own spans and counters, off unless asked for.

Each ``HydraServer`` holds one :class:`Trace` (``HydraServer(...,
trace=True)``, or ``Engine(..., trace=True)``), shared by its instances'
runners and its hand-offs; ``Trace.on`` may also be switched on a live
engine.  Off, ``span`` returns one shared no-op object and ``count``
returns at once: a span site costs one attribute test.  On, a span opens
``jax.profiler.TraceAnnotation("hydra.<name>")``, so it lands on the
profiler's host timeline beside the device ops of any capture, and appends
``(name, t0, t1)`` on ``time.perf_counter`` to ``Trace.spans``, which grows
while the trace is on: switch it on for a measured stretch, not for the
life of a server.

Spans:

  submit.lock_wait     ``Engine.submit`` waiting for the engine lock
  step                 ``HydraServer.step``, from the first batch it builds
  runner.encode / runner.prefill / runner.decode / runner.joint
                       the ``ModelRunner`` calls
  migrate              ``HydraServer._migrate``: a whole hand-off, retries
                       included
  migrate.read         the source's device read dispatch
  migrate.fetch        the device->host copies the checksums force: a
                       numpy leaf's bytes, or the 16-byte digests of the
                       device leaves, whose fetch waits for the device's
                       reads and digests
  migrate.hash         blake2b over a numpy leaf's bytes, or the dispatch
                       of a device leaf's digest
  migrate.import       the destination's import
  migrate.backoff      the sleep between transfer retries
  loop.idle            ``Engine._loop`` finding no work, until the next
                       step that builds a batch returns

Counters:

  migrate.host_bytes   bytes the transfer checksums pull to the host
  migrate.device_bytes bytes the transfer checksums digest on the device

Waits of single requests overlap, so they are not spans: the engine logs
them in ``Request.stage_log`` (always on).
"""
from __future__ import annotations

import time


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("_trace", "_name", "_ann", "_t0")

    def __init__(self, trace: "Trace", name: str):
        self._trace = trace
        self._name = name

    def __enter__(self):
        # imported here: host-only users of the engine's modules never
        # import jax
        from jax.profiler import TraceAnnotation

        self._ann = TraceAnnotation(f"hydra.{self._name}")
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._trace.spans.append((self._name, self._t0, t1))
        return False


class Trace:
    """Spans and counters of one server (see the module docstring)."""

    def __init__(self, on: bool = False):
        self.on = on
        self.spans: list = []        # [(name, t0, t1)], perf_counter
        self.counters: dict = {}

    def span(self, name: str):
        """A context manager timing ``name``; also entered and left by
        hand where a span does not follow one block of code."""
        if not self.on:
            return _NO_SPAN
        return _Span(self, name)

    def count(self, name: str, n: int):
        if self.on:
            self.counters[name] = self.counters.get(name, 0) + n


OFF = Trace()   # the default of callers that are given no trace
