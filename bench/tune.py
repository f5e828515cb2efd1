#!/usr/bin/env python3
"""Set a cell's numbers on the chip: the rate sweep that finds the knee,
and the readings that the correctness limit is set from.

    python3 bench/tune.py sweep --workload <cell> --seed <n> --seconds <s>
        --rates 0.5,1,2,3
    python3 bench/tune.py check --workload <cell> --seeds 1,2,3 --seconds <s>

``sweep`` builds the engine once and offers the cell's traffic at each
rate in turn, one window each, printing per rate the TTFT and TPOT
quantiles, the share of requests that meet the traffic file's limits, and
the requests still unfinished when the window closed (a growing backlog).
A rate of 0 sends one request at a time, each after the last finished:
what a lone request sees at the cell's shapes, from which the limits are
set.

``check`` runs, for each seed, a window at the cell's own rate and
compares the served tokens with the plain f32 reference of the
configuration's architecture (``bench/arch``), as a run does; then it
puts the reference's float8 forward in the program's place on the same
sample (the control).  It prints each seed's widest gap of both: the
limit lies between the program's largest and the control's smallest.

Neither is part of a benchmark run; both print their readings for
``PERF.md``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _setup():
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    return jax


def _engine(cell, config, seed, jax, np):
    from bench import run, weights
    from bench.cell import model_config
    from bench.probes import Record, record_events
    from repro.engine.runner import KV_BLOCK

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise run.NoChip(f"no TPU: JAX found {dev.platform!r}")
    params = weights.make_params(config, seed)
    engine = run.build_engine(config, model_config(config), params, dev,
                              False)
    rec = Record()
    record_events(engine, rec)
    from bench.traffic import make_window
    probe = make_window(cell.traffic, seed, 1.0, vocab=config["vocab_size"],
                        image_tokens=config["image_tokens"],
                        d_model=config["hidden_size"])
    run.warm_up(engine, run.warm_plan(config, cell.traffic, KV_BLOCK),
                probe[0].image, np)
    return params, engine, rec


def _window(cell, config, engine, rec, reqs, seconds, jax):
    from bench import run

    t_open, t_close, t_end, sub = run.serve(engine, reqs, seconds,
                                            cell.traffic, jax, None)
    rows, toks = run.outcomes(reqs, sub, rec, t_open, t_close, t_end,
                              cell.traffic["limits"])
    backlog = sum(1 for q, s in zip(reqs, sub)
                  if s is None or s[0] not in rec.finish
                  or rec.finish[s[0]][1] > t_close)
    return rows, toks, t_open, t_close, sub, backlog


def sweep(cell, config, args, jax, np):
    from bench import run
    from bench.stats import quantile
    from bench.traffic import make_window

    params, engine, rec = _engine(cell, config, args.seed, jax, np)
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = dict(cell.traffic)
        if rate > 0:
            traffic["arrivals"] = {**traffic["arrivals"], "rate_per_s": rate}
        reqs = make_window(traffic, args.seed, args.seconds,
                           vocab=config["vocab_size"],
                           image_tokens=config["image_tokens"],
                           d_model=config["hidden_size"])
        if rate == 0:                  # one at a time: a lone request
            rows = []
            for q in reqs[:args.lone]:
                q.due = 0.0
                r, *_ = _window(cell, config, engine, rec, [q], 0.0, jax)
                rows += r
            backlog, toks, span = 0, 0, 1.0
        else:
            rows, toks, t_open, t_close, _, backlog = _window(
                cell, config, engine, rec, reqs, args.seconds, jax)
            span = t_close - t_open
        ttft = [r[0] for r in rows]
        gaps = [g for r in rows for g in r[1]]
        if args.dump:
            with open(args.dump, "a") as f:
                f.write(json.dumps({"rate": rate, "span": span,
                                    "rows": rows}) + "\n")
        print(json.dumps({
            "rate": rate, "requests": len(rows),
            "ttft_p50_ms": 1e3 * quantile(ttft, 0.5),
            "ttft_p90_ms": 1e3 * quantile(ttft, 0.9),
            "tpot_p50_ms": 1e3 * quantile(gaps, 0.5) if gaps else None,
            "tpot_p90_ms": 1e3 * quantile(gaps, 0.9) if gaps else None,
            "attainment": sum(r[3] for r in rows) / len(rows),
            "finished": sum(r[2] for r in rows),
            "unfinished_at_close": backlog,
            "output_tok_per_s": toks / span}), flush=True)
    engine.close(drain_timeout=0)


def check(cell, config, args, jax, np):
    from bench import arch, run
    from bench.traffic import make_window

    reference = arch.load(config)
    limits = {**config["check"], **cell.traffic["check"]}
    shape = run.reference_shape(config, cell.traffic)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        params, engine, rec = _engine(cell, config, seed, jax, np)
        reqs = make_window(cell.traffic, seed, args.seconds,
                           vocab=config["vocab_size"],
                           image_tokens=config["image_tokens"],
                           d_model=config["hidden_size"])
        rows, _, _, _, sub, _ = _window(cell, config, engine, rec, reqs,
                                        args.seconds, jax)
        picked = run.pick_sample(reqs, rows, limits["requests"], seed, np)
        sample = run.check_sample(config, reqs, sub, rec, picked, np)
        del engine
        gc.collect()
        t1 = time.perf_counter()
        prog = reference.logit_gaps(config, params, sample, **shape,
                                    group=limits["requests_per_call"])
        t2 = time.perf_counter()
        ctrl = reference.logit_gaps(config, params, sample, **shape,
                                    group=limits["requests_per_call"],
                                    control=True)
        print(json.dumps({
            "seed": seed, "requests": len(sample), "tokens": int(prog.size),
            "program_max_gap": float(prog.max()),
            "program_p99_gap": float(np.quantile(prog, 0.99)),
            "control_max_gap": float(ctrl.max()),
            "control_p50_gap": float(np.quantile(ctrl, 0.5)),
            "serve_s": t1 - t0, "reference_s": t2 - t1}), flush=True)
        del params
        gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "check"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", default="0,1,2")
    ap.add_argument("--lone", type=int, default=8,
                    help="requests sent one at a time at rate 0")
    ap.add_argument("--dump", help="append each rate's per-request "
                                   "(ttft, gaps, finished, met) here")
    args = ap.parse_args()
    jax = _setup()
    import numpy as np

    from bench.cell import load_cell

    cell = load_cell(args.workload)
    config = dict(cell.config)
    (sweep if args.mode == "sweep" else check)(cell, config, args, jax, np)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
