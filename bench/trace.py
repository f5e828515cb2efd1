"""Reduction of a profiler trace to device time, idle share and the
breakdown.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain event lists; everything after that works on those lists, so a small
recorded trace (``tests/bench/data``) checks the arithmetic without JAX.

Events, all on the profiler's clock in nanoseconds:

  ops       per device: (name, start, duration) for every operation that
            ran on it
  spans     the harness's host annotations (``bench.*``): (name, start,
            duration)
  program_spans
            the program's own host spans (``hydra.*``, ``repro.engine.trace``):
            (name, start, duration, thread), where thread names the host
            plane's line the span ran on

The program's steps are jitted ``functools.partial`` objects, which XLA
names ``_unknown``; so device work is attributed to a stage by the
harness's host span (``bench.prefill``, ``bench.decode``, ...) in which it
ran.  The runner calls are synchronous (they return host arrays), so a
call's device work lies inside its span.

Busy time is the union of the op intervals inside the window.  An idle gap
is a stretch of the window in which no op ran; it is named by the host
span in which it falls (the innermost one: latest start), or "no span".
"""
from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict
from dataclasses import dataclass, field

_OP_ID = re.compile(r"\.\d+$")
WINDOW = "bench.window"
PROGRAM = "hydra."


def op_family(name: str) -> str:
    """``%paged_attention.16 = bf16[...] custom-call(...)`` ->
    ``paged_attention``: the HLO instruction's name without its number."""
    return _OP_ID.sub("", name.split(" ", 1)[0].lstrip("%"))


@dataclass
class Events:
    ops: dict = field(default_factory=dict)        # device -> [(n, s, d)]
    spans: list = field(default_factory=list)      # [(name, s, d)]
    program_spans: list = field(default_factory=list)  # [(n, s, d, thread)]

    def to_json(self) -> dict:
        return {"ops": self.ops, "spans": self.spans,
                "program_spans": self.program_spans}

    @classmethod
    def from_json(cls, d: dict) -> "Events":
        return cls(ops={k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                   spans=[tuple(e) for e in d["spans"]],
                   program_spans=[tuple(e)
                                  for e in d.get("program_spans", ())])


def read_xplane(path: str, span_prefix: str = "bench.") -> Events:
    """Device planes' ops, the host spans whose names start with
    ``span_prefix``, and the program's (``hydra.*``) with their thread;
    each list sorted by start."""
    from jax.profiler import ProfileData

    ev = Events()
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            ops = [(e.name, e.start_ns, e.duration_ns)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if ops:
                ev.ops[plane.name] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        ev.spans.append((e.name, e.start_ns, e.duration_ns))
                    elif e.name.startswith(PROGRAM):
                        ev.program_spans.append(
                            (e.name, e.start_ns, e.duration_ns,
                             f"{plane.name}#{k}"))
    ev.spans.sort(key=lambda s: s[1])
    ev.program_spans.sort(key=lambda s: s[1])
    return ev


def _union(intervals, lo: float, hi: float):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(s + d, hi))
                       for s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window(ev: Events, name: str = WINDOW):
    """(start, end) of the measured window's host span."""
    spans = [(s, s + d) for n, s, d in ev.spans if n == name]
    if not spans:
        raise ValueError(f"no {name!r} span in the trace")
    return spans[0]


def busy_seconds(ev: Events, lo: float, hi: float) -> float:
    """Seconds in [lo, hi) in which an op ran, averaged over devices."""
    if not ev.ops:
        return 0.0
    tot = 0.0
    for ops in ev.ops.values():
        tot += sum(e - s for s, e in _union([(o[1], o[2]) for o in ops],
                                            lo, hi))
    return tot / len(ev.ops) / 1e9


def busy_in(ev: Events, spans) -> list:
    """Device-busy seconds inside each of ``spans``, summed over
    devices."""
    merged = [_union([(o[1], o[2]) for o in ops], float("-inf"),
                     float("inf")) for ops in ev.ops.values()]
    ends = [[iv[1] for iv in m] for m in merged]
    out = []
    for lo, hi in spans:
        tot = 0.0
        for m, me in zip(merged, ends):
            i = bisect.bisect_right(me, lo)
            while i < len(m) and m[i][0] < hi:
                tot += max(0.0, min(m[i][1], hi) - max(m[i][0], lo))
                i += 1
        out.append(tot / 1e9)
    return out


def op_seconds_in(ev: Events, spans, family: str) -> float:
    """Device seconds of the ops of one family (``op_family``) that start
    inside ``spans``, summed over devices."""
    tot = 0
    for ops in ev.ops.values():
        starts = [o[1] for o in ops]
        for lo, hi in spans:
            i = bisect.bisect_left(starts, lo)
            while i < len(ops) and ops[i][1] < hi:
                if op_family(ops[i][0]) == family:
                    tot += ops[i][2]
                i += 1
    return tot / 1e9


def label(ev: Events, times, skip=(WINDOW, "bench.submit")) -> list:
    """For each time (ascending), the innermost harness span covering it
    (latest start), or "no span".  The window and the submits (made on
    the client's thread, not the engine's) do not count."""
    spans = sorted((s, s + d, n) for n, s, d in ev.spans if n not in skip)
    out, heap, k = [], [], 0
    for t in times:
        while k < len(spans) and spans[k][0] <= t:
            s, e, n = spans[k]
            heapq.heappush(heap, (-s, e, n))
            k += 1
        while heap and heap[0][1] <= t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else "no span")
    return out


def idle_gaps(ev: Events, lo: float, hi: float):
    """[(start, end)] of the stretches of [lo, hi) with no op on the first
    device."""
    if not ev.ops:
        return [(lo, hi)]
    dev = sorted(ev.ops)[0]
    busy = _union([(o[1], o[2]) for o in ev.ops[dev]], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _short(span: str) -> str:
    return span[len("bench."):] if span.startswith("bench.") else span


def breakdown(ev: Events, lo: float, hi: float, top: int = 10) -> dict:
    """The device ops that took most time, by op family and the host span
    they ran in (``prefill/paged_prefill_attention``), and the idle gaps by
    the host span they fell in; each a list of [name, seconds]."""
    ops = defaultdict(float)
    for opl in ev.ops.values():
        inside = [o for o in opl if lo <= o[1] < hi]
        for o, span in zip(inside, label(ev, [o[1] for o in inside])):
            ops[f"{_short(span)}/{op_family(o[0])}"] += o[2] / 1e9
    gaps = defaultdict(float)
    g = idle_gaps(ev, lo, hi)
    for (s, e), span in zip(g, label(ev, [(s + e) / 2 for s, e in g])):
        gaps[_short(span)] += (e - s) / 1e9
    order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in order(ops)],
            "idle_gaps": [[k, v] for k, v in order(gaps)]}
