"""What a per-layer metric reader (``bench/metrics/<name>.py``) is given.

A reader is a module with ``read(r: Readings) -> float | None``.  It
returns None where it finds nothing to read (a stage that did not run, a
kernel absent from the trace), and the harness then leaves the metric out
of the result line; it never returns 0 for a share of a peak or of a
roofline.
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

    def due(self, k: int) -> float:
        return self.t_open + self.reqs[k].due

    def rid_index(self) -> dict:
        """rid -> request index."""
        return {s[0]: k for k, s in enumerate(self.submitted)
                if s is not None and s[0] is not None}
