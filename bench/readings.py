"""What a per-layer metric reader (``bench/metrics/<name>.py``) is given.

A reader is a module with ``read(r: Readings) -> float | None``.  It
returns None where it finds nothing to read (a stage that did not run, a
kernel absent from the trace, a span the program did not record), and the
harness then leaves the metric out of the result line; it never returns 0
for a share of a peak or of a roofline.

Besides the harness's own records, a traced run hands the readers what
the program recorded (``repro.engine.trace``, on for the traced window):
``program``, a ``bench.program.snapshot`` of the server (its spans on
``time.perf_counter``, its counters, each request's ``stage_log``), and
``program_spans``, its ``hydra.*`` spans in the profiler's capture.  A
reader of a new counter or span needs only ``program_total``.
"""
from __future__ import annotations

import importlib.util
from dataclasses import dataclass, field
from pathlib import Path

from bench import roofline, trace

METRICS = Path(__file__).with_name("metrics")


def load_reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}",
        METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Readings:
    config: dict              # the configuration file, as run
    device_kind: str
    events: trace.Events      # the traced window's device ops and spans
    rec: object               # bench.probes.Record (host clock)
    reqs: list                # bench.traffic.Request, by index
    submitted: list           # [(rid | None, submit-returned time)]
    t_open: float             # window, host clock (perf_counter)
    t_close: float
    program: dict = field(default_factory=dict)       # snapshot
    program_spans: list = field(default_factory=list)  # [(n, s, d, thread)]
    lo: float = field(init=False)     # window, trace clock (ns)
    hi: float = field(init=False)

    def __post_init__(self):
        self.lo, self.hi = trace.window(self.events)

    @property
    def peak(self) -> dict:
        return roofline.peaks(self.device_kind)

    def traced_calls(self, stage: str) -> list:
        """[(call, (span start, span end))] of the runner calls of a stage
        whose span starts inside the traced window.  The k-th span of a
        stage in the trace is the k-th call the harness recorded (calls
        run one at a time, and the first of them comes after the trace
        starts)."""
        name = f"bench.{stage}"
        spans = [(s, s + d) for n, s, d in self.events.spans if n == name]
        calls = [c for c in self.rec.calls if c.stage == stage]
        return [(c, sp) for c, sp in zip(calls, spans)
                if self.lo <= sp[0] < self.hi]

    def stage_device_seconds(self, stage: str) -> tuple:
        """(calls, device seconds) of a stage's runner calls in the traced
        window: all device work that ran inside their spans."""
        pairs = self.traced_calls(stage)
        return ([c for c, _ in pairs],
                sum(trace.busy_in(self.events, [sp for _, sp in pairs])))

    def kernel_seconds(self, stage: str, family: str) -> float:
        """Device seconds of one kernel (op family) inside a stage's
        calls in the traced window."""
        return trace.op_seconds_in(
            self.events, [sp for _, sp in self.traced_calls(stage)], family)

    def spans_in_window(self, name: str) -> list:
        """Durations (s) of the program's ``name`` spans that start in the
        window, from ``Trace.spans``."""
        return [t1 - t0 for n, t0, t1 in self.program.get("spans", ())
                if n == name and self.t_open <= t0 < self.t_close]

    def program_total(self, name: str):
        """The program's counter ``name`` over the traced run or, where it
        has no such counter, the seconds of its ``name`` spans that start
        in the window; None where it recorded neither."""
        counters = self.program.get("counters", {})
        if name in counters:
            return counters[name]
        spans = self.spans_in_window(name)
        return sum(spans) if spans else None

    def moved(self, window: bool = True) -> set:
        """Rids with a hand-off (``stage_log`` "migrate") that starts in the
        window, or at any time."""
        off = self.program.get("clock", 0.0)
        return {rid for rid, log in self.program.get("stage_logs", {}).items()
                for n, t0, _ in log if n == "migrate"
                and (not window or self.t_open <= t0 + off < self.t_close)}

    def due(self, k: int) -> float:
        return self.t_open + self.reqs[k].due

    def rid_index(self) -> dict:
        """rid -> request index."""
        return {s[0]: k for k, s in enumerate(self.submitted)
                if s is not None and s[0] is not None}
