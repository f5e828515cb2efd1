"""The reduction from a profiler trace to device time, idle share and
the breakdown (bench/trace.py), on hand-worked events and on a small
trace recorded on a TPU v5e (tests/bench/data/v5e_vqa_trace.json)."""
import json
from types import SimpleNamespace

import pytest

from bench import trace
from bench.cell import ROOT
from bench.readings import Readings

NS = 1e-9
DEV = "/device:TPU:0"


def _spans(ev, name, lo, hi):
    return [(s, s + d) for n, s, d in ev.spans if n == name and lo <= s < hi]


def _events():
    return trace.Events(
        ops={DEV: [("%fusion.1 = f32[8]", 100, 50),
                   ("%paged_attention.3 = bf16[1]", 120, 20),
                   ("%fusion.2", 200, 100)]},
        spans=[("bench.window", 50, 300), ("bench.step", 60, 200),
               ("bench.decode", 95, 60), ("bench.prefill", 195, 110),
               ("bench.submit", 90, 250), ("bench.migrate", 310, 30)])


def test_busy_is_the_union_of_op_intervals():
    ev = _events()
    lo, hi = trace.window(ev)
    assert (lo, hi) == (50, 350)
    # [100, 150) and [200, 300): the kernel inside the fusion counts once
    assert trace.busy_seconds(ev, lo, hi) == pytest.approx(150 * NS)
    assert trace.busy_seconds(ev, 120, 250) == pytest.approx(80 * NS)


def test_stage_device_time_and_kernel_time_by_host_span():
    ev = _events()
    dec = _spans(ev, "bench.decode", 50, 350)
    pre = _spans(ev, "bench.prefill", 50, 350)
    assert dec == [(95, 155)] and pre == [(195, 305)]
    assert trace.busy_in(ev, dec) == pytest.approx([50 * NS])
    assert trace.busy_in(ev, pre + [(120, 210)]) == pytest.approx(
        [100 * NS, 40 * NS])
    assert trace.op_seconds_in(ev, dec, "paged_attention") == \
        pytest.approx(20 * NS)
    assert trace.op_seconds_in(ev, pre, "paged_attention") == 0


def test_breakdown_names_ops_and_gaps_by_the_engine_span_they_fall_in():
    ev = _events()
    assert trace.idle_gaps(ev, 50, 350) == [(50, 100), (150, 200),
                                            (300, 350)]
    out = trace.breakdown(ev, 50, 350)
    assert out["device_ops"] == [
        ["prefill/fusion", pytest.approx(100 * NS)],
        ["decode/fusion", pytest.approx(50 * NS)],
        ["decode/paged_attention", pytest.approx(20 * NS)]]
    # [50, 100) and [150, 200) fall in step (the client's submit span does
    # not name a gap), [300, 350) in migrate
    assert out["idle_gaps"] == [["step", pytest.approx(100 * NS)],
                                ["migrate", pytest.approx(50 * NS)]]


def test_op_family_is_the_instruction_name_without_its_number():
    assert trace.op_family("%paged_prefill_attention.16 = bf16[1,8] "
                           "custom-call(s32[1] %a)") == \
        "paged_prefill_attention"
    assert trace.op_family("%fusion") == "fusion"


@pytest.fixture(scope="module")
def recorded():
    d = json.loads((ROOT / "tests" / "bench" / "data" /
                    "v5e_vqa_trace.json").read_text())
    return trace.Events.from_json(d)


def test_recorded_trace_reduces_consistently(recorded):
    ev = recorded
    lo, hi = trace.window(ev)
    busy = trace.busy_seconds(ev, lo, hi)
    assert 0 < busy < (hi - lo) * NS
    ops = ev.ops[DEV]
    # prefill attention: every paged_prefill_attention op inside a
    # bench.prefill span, summed by hand
    pre = _spans(ev, "bench.prefill", lo, hi)
    by_hand = sum(d for n, s, d in ops
                  if n.startswith("%paged_prefill_attention.")
                  and any(a <= s < b for a, b in pre)) * NS
    assert by_hand > 0
    assert trace.op_seconds_in(ev, pre, "paged_prefill_attention") == \
        pytest.approx(by_hand)
    # the stages' device time adds up to no more than the busy time
    stages = sum(sum(trace.busy_in(ev, _spans(
        ev, f"bench.{st}", lo, hi))) for st in ("encode", "prefill",
                                                 "decode", "migrate"))
    assert 0.9 * busy < stages <= busy * (1 + 1e-9)
    out = trace.breakdown(ev, lo, hi)
    assert out["device_ops"][0][0] == "prefill/paged_prefill_attention"
    assert len(out["device_ops"]) == 10
    # the P->D hand-off, staged through the host, holds the longest gap
    assert out["idle_gaps"][0][0] == "migrate"
    gaps = sum(v for _, v in out["idle_gaps"])
    assert gaps == pytest.approx((hi - lo) * NS - busy, rel=1e-6)


def test_readings_pair_each_span_with_its_recorded_call(recorded):
    ev = recorded
    lo, hi = trace.window(ev)
    spans = [s for s in ev.spans if s[0] == "bench.prefill"]
    calls = [SimpleNamespace(stage="prefill", t0=float(k), items=[(0, k)])
             for k in range(len(spans))]
    r = Readings(config={}, device_kind="TPU v5 lite", events=ev,
                 rec=SimpleNamespace(calls=calls), reqs=[], submitted=[],
                 t_open=0.0, t_close=1e9)
    pairs = r.traced_calls("prefill")
    assert [c.t0 for c, _ in pairs] == [float(k) for k, s in
                                        enumerate(spans) if lo <= s[1] < hi]
    got, t = r.stage_device_seconds("prefill")
    assert len(got) == len(pairs) and t > 0
    assert r.kernel_seconds("prefill", "paged_prefill_attention") <= t

