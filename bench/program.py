#!/usr/bin/env python3
"""The program's own spans and counters (``repro.engine.trace``) in a
traced window of a cell, and the per-layer readings taken from them.

    python3 bench/program.py --workload <cell> --seed <n> --seconds <s> \\
        [--program-trace 0|1]

A run is ``bench/run.py --trace 1`` up to its readers: the engine's trace
is switched on after the warm-up, or left off with ``--program-trace 0``
to measure what it costs.  It checks no correctness; ``bench/run.py``
does.  The last line of stdout is a JSON object:

  per_layer   the cell's per-layer metrics, read as ``bench/run.py`` reads
              them; those in ``READERS`` are read by
              ``bench/metrics/<name>.py`` through this module
  program     the readings of the program's spans (``READERS``), and the
              closure of the hand-off: ``hydra.migrate`` ms per request
              moved, and the share of it that its phases cover
  idle_by_program_span
              the window's device-idle seconds by the innermost
              engine-thread program span they fall in (the arithmetic of
              ``bench/trace.py``'s idle gaps), also logged before the line
  cost        the engine's host ms per request (its steps' time less the
              device's work in them), program spans per request, and the
              host cost of one span, on and off, with a profiler running
  compiles    programs built by the process, and how many of those were
              loaded from the persistent compile cache

Program spans come twice: from the profiler's host planes (``hydra.*``,
profiler clock, with the thread they ran on: ``Events.program_spans`` of
``bench/trace.py``) and from ``Trace.spans``
(``time.perf_counter``, the harness's clock).  Requests' waits come from
their ``Request.stage_log`` (engine clock, shifted to the harness's).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402
from bench.readings import Readings  # noqa: E402
from bench.stats import quantile  # noqa: E402

ENGINE_LOOP = ("hydra.step", "hydra.loop.idle")
MIGRATE_PARTS = ("migrate.read", "migrate.fetch", "migrate.hash",
                 "migrate.import", "migrate.backoff")


# ---------------------------------------------------------------------------
# what the program recorded
# ---------------------------------------------------------------------------
def snapshot(server) -> dict:
    """The server's spans, counters and stage logs, with the offset that
    takes its engine clock to ``time.perf_counter``."""
    tr = getattr(server, "trace", None)
    return {"spans": list(tr.spans) if tr is not None else [],
            "counters": dict(tr.counters) if tr is not None else {},
            "stage_logs": {rid: list(it.req.stage_log)
                           for rid, it in server.items.items()},
            "clock": time.perf_counter() - server.now()}


def engine_spans(spans) -> list:
    """The spans of the threads that ran the engine's loop."""
    threads = {t for n, _, _, t in spans if n in ENGINE_LOOP}
    return [s for s in spans if s[3] in threads]


def idle_by_span(ev: trace.Events, spans, lo: float, hi: float) -> dict:
    """Device-idle seconds of [lo, hi) by the innermost engine-thread
    program span they fall in, or "no span": each idle gap is named by the
    span around its middle, as ``trace.breakdown`` names its idle gaps."""
    eng = trace.Events(spans=[(n[len(trace.PROGRAM):], s, d)
                              for n, s, d, _ in engine_spans(spans)])
    gaps = trace.idle_gaps(ev, lo, hi)
    out: dict = {}
    for (s, e), name in zip(gaps, trace.label(eng, [(s + e) / 2
                                                    for s, e in gaps],
                                              skip=())):
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# ---------------------------------------------------------------------------
# the readings; each returns None where it finds nothing to read
# ---------------------------------------------------------------------------
def lock_wait_p90_ms(r: Readings):
    """Front end: P90 over the window's submits of ``hydra.submit.lock_wait``
    (``Engine.submit`` waiting for the engine lock), in ms."""
    waits = r.spans_in_window("submit.lock_wait")
    return 1e3 * quantile(waits, 0.9) if waits else None


def engine_queue_p90_ms(r: Readings):
    """Scheduler: P90 over the served requests of their first
    ``<stage>_queue`` wait in ``Request.stage_log`` (enqueue to the first
    batch that carries it), in ms."""
    logs = r.program.get("stage_logs", {})
    waits = []
    for rid in r.rid_index():
        first = next((t1 - t0 for n, t0, t1 in logs.get(rid, ())
                      if n.endswith("_queue")), None)
        if first is not None:
            waits.append(first)
    return 1e3 * quantile(waits, 0.9) if waits else None


def _per_moved_ms(r: Readings, name: str):
    moved = r.moved()
    t = r.program_total(name)
    return 1e3 * t / len(moved) if moved and t else None


def fetch_ms_per_req(r: Readings):
    """Migration: ``hydra.migrate.fetch`` (the one fetch of a hand-off's
    digests on device pools; the payload copies for numpy leaves) in the
    window, per request moved, in ms."""
    return _per_moved_ms(r, "migrate.fetch")


def hash_ms_per_req(r: Readings):
    """Migration: ``hydra.migrate.hash`` (the dispatch of each on-device
    digest; blake2b over numpy leaves) in the window, per request moved,
    in ms."""
    return _per_moved_ms(r, "migrate.hash")


def host_mb_per_req(r: Readings):
    """Migration: counter ``migrate.host_bytes`` over the requests moved
    while the trace was on, in MB (1e6 bytes)."""
    n = r.program_total("migrate.host_bytes")
    moved = r.moved(window=False)
    return n / len(moved) / 1e6 if n and moved else None


def idle_with_work_share(r: Readings):
    """Device: share of the window in which the chip was idle and the
    innermost engine-thread program span was not ``hydra.loop.idle``, in %:
    the idle time an engine change can remove."""
    if not r.events.ops or not engine_spans(r.program_spans):
        return None
    idle = idle_by_span(r.events, r.program_spans, r.lo, r.hi)
    window = (r.hi - r.lo) / 1e9
    return 100.0 * (sum(idle.values()) - idle.get("loop.idle", 0.0)) / window


READERS = {"front.lock_wait_p90_ms": lock_wait_p90_ms,
           "sched.engine_queue_p90_ms": engine_queue_p90_ms,
           "migrate.fetch_ms_per_req": fetch_ms_per_req,
           "migrate.hash_ms_per_req": hash_ms_per_req,
           "migrate.host_mb_per_req": host_mb_per_req,
           "device.idle_with_work_share": idle_with_work_share}


def closure(r: Readings) -> dict:
    """``hydra.migrate`` ms per request moved in the window, and the share
    of it that its phases (``MIGRATE_PARTS``) cover."""
    whole = sum(r.spans_in_window("migrate"))
    moved = r.moved()
    if not whole or not moved:
        return {}
    parts = sum(sum(r.spans_in_window(p)) for p in MIGRATE_PARTS)
    return {"migrate_ms_per_req": 1e3 * whole / len(moved),
            "parts_share": parts / whole}


# ---------------------------------------------------------------------------
# the traced window
# ---------------------------------------------------------------------------
def host_ms_per_req(r: Readings):
    """The engine's host time per request due in the window: its steps'
    time (harness span ``bench.step``) less the device's work inside them,
    in ms."""
    steps = [(s, s + d) for n, s, d in r.events.spans
             if n == "bench.step" and r.lo <= s < r.hi]
    if not steps or not r.reqs:
        return None
    busy = sum(trace.busy_in(r.events, steps)) if r.events.ops else 0.0
    host = sum(e - s for s, e in steps) / 1e9 - busy
    return 1e3 * host / len(r.reqs)


def span_cost_us(jax, n: int = 20000) -> dict:
    """Host microseconds per program span, on and off, with a profiler
    capture running (as in a traced run)."""
    from repro.engine.trace import Trace

    d = tempfile.mkdtemp(prefix="bench-span-cost-")
    out = {}
    jax.profiler.start_trace(d)
    try:
        for on in (True, False):
            t = Trace(on)
            t0 = time.perf_counter()
            for _ in range(n):
                with t.span("cost"):
                    pass
            out["on" if on else "off"] = 1e6 * (time.perf_counter() - t0) / n
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(d, ignore_errors=True)
    return out


def run_window(cell, seed: int, seconds: float, program_trace: bool, *,
               rehearse: bool = False) -> dict:
    """One traced window of ``cell`` (see the module docstring)."""
    import jax
    import numpy as np

    from bench import run, weights
    from bench.cell import model_config
    from bench.probes import Record, instrument, record_events
    from bench.readings import load_reader
    from bench.traffic import make_window
    from repro.engine.runner import KV_BLOCK

    devs = jax.devices()
    dev = devs[0]
    if not rehearse and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise run.NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX "
                         f"found {len(devs)} {dev.platform!r} device(s)")
    config, traffic, _ = run.prepare(cell, rehearse)
    compile_log = run.CompileLog(jax)
    params = weights.make_params(config, seed)
    jax.block_until_ready(params)
    engine = run.build_engine(config, model_config(config), params, dev,
                              rehearse)
    rec = Record()
    record_events(engine, rec)
    reqs = make_window(traffic, seed, seconds, vocab=config["vocab_size"],
                       image_tokens=config["image_tokens"],
                       d_model=config["hidden_size"])
    image = next((q.image for q in reqs if q.image is not None), None)
    run.warm_up(engine, run.warm_plan(config, traffic, KV_BLOCK), image, np)
    compiles = {"built": compile_log.builds,
                "loaded": compile_log.cache_loads}
    run.log(f"warm-up: {compiles['built']} programs built, "
            f"{compiles['loaded']} of them loaded from the persistent cache")
    instrument(engine, rec, jax)
    engine.server.trace.on = program_trace
    tracer = run.Tracer(jax)
    t_open, t_close, t_end, submitted = run.serve(engine, reqs, seconds,
                                                  traffic, jax, tracer)
    program = snapshot(engine.server)
    rows, in_window = run.outcomes(reqs, submitted, rec, t_open, t_close,
                                   t_end, traffic["limits"])
    del engine
    gc.collect()
    ev = tracer.events()
    spans = ev.program_spans
    r = Readings(config=config, device_kind=dev.device_kind, events=ev,
                 rec=rec, reqs=reqs, submitted=submitted, t_open=t_open,
                 t_close=t_close, program=program, program_spans=spans)
    idle = idle_by_span(ev, spans, r.lo, r.hi) if ev.ops else {}
    run.log("device idle by engine-thread program span (s): " + ", ".join(
        f"{k} {v:.3f}" for k, v in idle.items()))
    return {
        "workload": cell.name, "seed": seed, "program_trace": program_trace,
        "end_to_end": run.end_to_end(rows, in_window, t_open, t_close,
                                     t_open - T_START),
        "per_layer": {m["name"]: load_reader(m["name"])(r)
                      for m in cell.per_layer},
        "program": {**{k: fn(r) for k, fn in READERS.items()},
                    "closure": closure(r)},
        "idle_by_program_span": idle,
        "device": {"kind": dev.device_kind,
                   "busy_s": trace.busy_seconds(ev, r.lo, r.hi),
                   "window_s": (r.hi - r.lo) / 1e9},
        "cost": {"host_ms_per_req": host_ms_per_req(r),
                 "spans_per_req": len(program["spans"]) / len(reqs),
                 "span_us": span_cost_us(jax)},
        "compiles": compiles,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(1, str(ROOT / "src"))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.cell import load_cell
    from bench.run import NoChip, log
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        out = run_window(load_cell(args.workload), args.seed, args.seconds,
                         bool(args.program_trace))
    except NoChip as e:
        log(f"FAIL: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
