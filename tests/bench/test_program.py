"""The readings of the program's own spans and counters
(``bench/program.py``): each on hand-worked values, the harness's ten
readers unchanged beside them on the recorded v5e trace, and one traced
window rehearsed on the CPU."""
import json
import threading
from types import SimpleNamespace

import pytest

from bench import program, trace
from bench.cell import ROOT, load_cell
from bench.probes import Call, Record
from bench.readings import Readings, load_reader

CELL = "pixtral-12b.vqa-short"
DEV = "/device:TPU:0"


def _synthetic():
    """A window [10 s, 20 s) on the harness's clock and [0, 1000) ns on
    the profiler's, with everything the readers look at."""
    spans = [("submit.lock_wait", 10.5 + i, 10.5 + i + 0.1 * (i + 1))
             for i in range(9, -1, -1)]
    spans += [("submit.lock_wait", 25.0, 34.0),        # after the window
              ("migrate.read", 10.40, 10.45),
              ("migrate.fetch", 10.45, 10.75),
              ("migrate.hash", 10.75, 10.85),
              ("migrate.fetch", 10.85, 11.05),
              ("migrate.import", 11.05, 11.25),
              ("migrate", 10.4, 11.4),
              ("migrate.fetch", 25.0, 26.0)]           # after the window
    prog = {"spans": spans,
            "counters": {"migrate.host_bytes": 400_000_000},
            "clock": 100.0,                    # engine clock + 100 s
            "stage_logs": {
                0: [("encode_queue", -90.0, -89.99),
                    ("encode_exec", -89.99, -89.6),
                    ("migrate", -89.6, -88.6)],        # in the window
                1: [("prefill_queue", -70.0, -69.95),
                    ("migrate", -75.0, -74.0)],        # after it
                2: []}}
    # profiler clock: the engine's thread (#1) idles, steps and hands off;
    # the client's thread (#2) waits on the lock over a device-idle gap
    pspans = [("hydra.loop.idle", 0, 150, "/host:CPU#1"),
              ("hydra.step", 150, 550, "/host:CPU#1"),
              ("hydra.migrate", 250, 250, "/host:CPU#1"),
              ("hydra.submit.lock_wait", 300, 200, "/host:CPU#2"),
              ("hydra.loop.idle", 700, 300, "/host:CPU#1")]
    ev = trace.Events(ops={DEV: [("%fusion.1", 100, 100),
                                 ("%fusion.2", 600, 100)]},
                      spans=[("bench.window", 0, 1000)])
    return Readings(
        config={}, device_kind="TPU v5 lite", events=ev, rec=Record(),
        reqs=[SimpleNamespace(due=0.0)] * 3,
        submitted=[(0, 10.0), (1, 10.0), (2, 10.0)], t_open=10.0,
        t_close=20.0, program=prog, program_spans=pspans)


@pytest.mark.parametrize("name,expected", [
    # nearest-rank P90 of 0.1..1.0 s: the 9th, 0.9 s
    ("front.lock_wait_p90_ms", 900.0),
    # first queue waits 10 ms and 50 ms: the P90 of two is the larger
    ("sched.engine_queue_p90_ms", 50.0),
    # fetches of 300 + 200 ms in the window over the one request moved in
    # it (rid 1 moves at 25 s)
    ("migrate.fetch_ms_per_req", 500.0),
    ("migrate.hash_ms_per_req", 100.0),
    # 400 MB over the two requests moved at all
    ("migrate.host_mb_per_req", 200.0),
    # gaps [0, 100) and [700, 1000) are loop idle; [200, 600) falls in the
    # hand-off (the client's lock wait over it does not count): 40% of 1000
    ("device.idle_with_work_share", 40.0),
])
def test_each_program_reading_on_hand_worked_values(name, expected):
    assert program.READERS[name](_synthetic()) == pytest.approx(expected)


def test_idle_table_and_hand_off_closure_on_hand_worked_values():
    r = _synthetic()
    assert program.idle_by_span(r.events, r.program_spans, r.lo, r.hi) == \
        {"loop.idle": pytest.approx(400e-9), "migrate": pytest.approx(400e-9)}
    # read 50 + fetch 500 + hash 100 + import 200 of a 1000 ms hand-off
    assert program.closure(r) == {"migrate_ms_per_req": pytest.approx(1000.0),
                                  "parts_share": pytest.approx(0.85)}


def test_a_run_without_the_program_trace_reads_nothing():
    r = _synthetic()
    r.program = program.snapshot(SimpleNamespace(items={}, now=lambda: 0.0))
    r.program_spans = []
    assert all(fn(r) is None for fn in program.READERS.values())
    assert program.closure(r) == {}


def test_capture_keeps_the_thread_of_each_program_span(tmp_path):
    import jax

    from repro.engine.trace import Trace

    t = Trace(on=True)

    def loop():
        with t.span("loop.idle"):
            with t.span("step"):
                pass

    jax.profiler.start_trace(str(tmp_path))
    th = threading.Thread(target=loop)
    th.start()
    th.join()
    with t.span("submit.lock_wait"):
        pass
    jax.profiler.stop_trace()
    spans = trace.read_xplane(
        str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])).program_spans
    assert sorted(n for n, *_ in spans) == [
        "hydra.loop.idle", "hydra.step", "hydra.submit.lock_wait"]
    assert len({s[3] for s in spans}) == 2
    assert sorted(n for n, *_ in program.engine_spans(spans)) == [
        "hydra.loop.idle", "hydra.step"]


@pytest.fixture(scope="module")
def recorded():
    d = json.loads((ROOT / "tests" / "bench" / "data" /
                    "v5e_vqa_trace.json").read_text())
    return trace.Events.from_json(d)


def _recorded_readings(cls, ev, **extra):
    """The recorded window with a hand-made record of its calls."""
    items = {"encode": [[(0, 1024)]],
             "prefill": [[(0, 1024)], [(1024, 35)], [(0, 1024)]],
             "decode": [[(1059, 1)]]}
    rec = Record()
    for stage, calls in items.items():
        rec.calls += [Call(stage, 1.0 + k, 1.5 + k, [k], it)
                      for k, it in enumerate(calls)]
    rec.first_call = {0: 0.5, 1: 1.5}
    rec.migrations = [(0, 1.0, 1.2), (0, 2.0, 2.5), (1, 3.0, 3.1)]
    return cls(config=load_cell(CELL).config, device_kind="TPU v5 lite",
               events=ev, rec=rec,
               reqs=[SimpleNamespace(due=0.0), SimpleNamespace(due=1.0)],
               submitted=[(0, 0.3), (1, 1.4)], t_open=0.0, t_close=10.0,
               **extra)


def test_harness_readers_and_breakdown_read_the_recorded_trace_as_before(
        recorded):
    names = [m["name"] for m in load_cell(CELL).per_layer
             if m["name"] not in program.READERS]
    assert len(names) == 10
    plain = _recorded_readings(Readings, recorded)
    with_program = _recorded_readings(
        Readings, recorded,
        program=_synthetic().program, program_spans=_synthetic().program_spans)
    values = {n: load_reader(n)(plain) for n in names}
    assert values == {n: load_reader(n)(with_program) for n in names}
    assert values["front.submit_block_p90_ms"] == pytest.approx(400.0)
    assert values["sched.queue_wait_p90_ms"] == pytest.approx(500.0)
    assert values["migrate.ms_per_req"] == pytest.approx(400.0)
    assert all(values[n] is not None for n in names)
    lo, hi = trace.window(recorded)
    out = trace.breakdown(recorded, lo, hi)
    assert out["device_ops"][0] == ["prefill/paged_prefill_attention",
                                    pytest.approx(0.147680006)]
    assert out["idle_gaps"] == [["migrate", pytest.approx(0.701646278)],
                                ["prefill", pytest.approx(0.014161328)],
                                ["decode", pytest.approx(0.005094053)],
                                ["encode", pytest.approx(0.00453976)]]


@pytest.mark.parametrize("on", [True, False], ids=["on", "off"])
def test_traced_window_rehearsal_reads_the_program(on):
    out = program.run_window(load_cell(CELL), 2**31 + 5, 3.0, on,
                             rehearse=True)
    read = out["program"]
    # no device trace on the CPU: the idle share finds nothing to read
    assert read["device.idle_with_work_share"] is None
    # the stage log is kept with the trace off too
    assert read["sched.engine_queue_p90_ms"] is not None
    spans = ["front.lock_wait_p90_ms", "migrate.fetch_ms_per_req",
             "migrate.hash_ms_per_req", "migrate.host_mb_per_req"]
    if on:
        assert all(read[n] is not None for n in spans), read
        assert 0.5 < read["closure"]["parts_share"] <= 1.0
        assert out["cost"]["spans_per_req"] > 0
    else:
        assert all(read[n] is None for n in spans), read
        assert read["closure"] == {} and out["cost"]["spans_per_req"] == 0
    assert set(out["per_layer"]) == {m["name"]
                                     for m in load_cell(CELL).per_layer}
    assert out["per_layer"]["migrate.ms_per_req"] is not None
    assert out["compiles"]["built"] > 0
