#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run makes the weights from the seed on the device, builds the engine
the configuration names (``Engine`` over ``HydraServer``, E1,P1,D1) with
its pools sized from the chip's memory, warms up every shape the cell's
traffic can produce, and then offers the traffic open-loop for
``--seconds``: each request is submitted when it is due and timed from
that moment.  After the window the requests in flight drain up to the
traffic file's cap; a request refused, errored or unfinished then counts
in ``failed`` and misses the limits.  Then the program's state is freed
and the plain f32 reference (``bench/reference.py``) re-reads a sample of
the greedy requests: ``correct`` says whether every served token's logit
lies within the configuration's limit of the reference's best.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window with the JAX profiler, switches the engine's own spans and
counters on (``repro.engine.trace``) after the warm-up, and reports the
per-layer metrics, read by ``bench/metrics/<name>.py``, with the device's
busy time and a breakdown.  The last line of stdout is the result; the
last lines of stderr are the numbers compared for ``correct``, each
beside its limit.

Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
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
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
RESERVE_BYTES = 2 << 30        # left free for step temporaries


def log(msg: str):
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


class CompileLog:
    """Counts the programs XLA builds, process-wide: every build is timed
    as a backend compile, whether it is compiled or loaded from the
    persistent cache, and a load is also counted as such.  A build inside
    the window means the warm-up missed a shape."""

    def __init__(self, jax):
        self.lock = threading.Lock()
        self.builds = 0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        with self.lock:
            if event == "/jax/core/compile/backend_compile_duration":
                self.builds += 1
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.cache_loads += 1


# ---------------------------------------------------------------------------
# engine construction
# ---------------------------------------------------------------------------
def pool_sizes(config: dict, dev, rehearse: bool):
    """(kv_blocks, img_blocks) per instance such that the three instances'
    pools and the weights fit the device's ``bytes_limit``.  Each of an
    instance's sequence pools has kv_blocks blocks."""
    from bench import arch
    from repro.engine import runner as R

    pools = config["pools"]
    img_blocks = pools["img_blocks"]
    if rehearse:
        return pools["rehearsal_kv_blocks"], img_blocks
    stats = dev.memory_stats()
    limit, in_use = stats["bytes_limit"], stats["bytes_in_use"]
    item = 2                                    # bf16 pools
    kv_block = R.KV_BLOCK * arch.load(config).seq_bytes_per_token(config,
                                                                  item)
    img_block = config["image_tokens"] * config["hidden_size"] * item
    per_inst = (limit - in_use - RESERVE_BYTES) // 3
    kv_blocks = (per_inst - (img_blocks + 1) * img_block) // kv_block - 1
    log(f"memory: bytes_limit {limit}, in use after weights {in_use}; per "
        f"instance {kv_blocks} KV blocks ({kv_blocks * R.KV_BLOCK} tokens) "
        f"+ {img_blocks} image blocks")
    return kv_blocks, img_blocks


def build_engine(config: dict, cfg, params, dev, rehearse: bool):
    from repro.core.budgets import Budgets
    from repro.engine.api import Engine
    from repro.launch.serve import parse_disagg

    kv_blocks, img_blocks = pool_sizes(config, dev, rehearse)
    b = config["budgets"]
    return Engine(cfg, params, parse_disagg(config["disagg"]),
                  kv_blocks=kv_blocks, img_blocks=img_blocks,
                  budgets=Budgets(b["token_budget"], b["image_budget"]))


# ---------------------------------------------------------------------------
# warm-up: every shape the cell's traffic can produce, driven through the
# runners' own entry points with placeholder requests (negative rids)
# ---------------------------------------------------------------------------
def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def _page_buckets(lo_rows: int, hi_rows: int, bs: int) -> list:
    """pow2 page-count buckets of contexts of lo_rows..hi_rows rows."""
    return sorted({_pow2(-(-x // bs)) for x in range(max(lo_rows, 1),
                                                     hi_rows + 1)})


def warm_plan(config: dict, traffic: dict, block: int) -> dict:
    """The runner calls that cover every shape the traffic can produce.

    Prefill text chunks of n rows land in the pow2 bucket C >= n; a group
    of B chunks of one bucket runs as one program padded to pow2(B) lanes,
    and the host slices the B real lanes back out, which is a program of
    its own for each B.  The chunks of one scheduler iteration share the
    token budget, and a chunk in bucket C holds more than C/2 rows, so B
    chunks fit only where B * (C//2 + 1) <= budget.  Decode runs B lanes
    of 1..``warm.decode_batch``.  Greedy and sampled requests run
    different programs, so both variants are warmed where the traffic has
    both."""
    n_img = config["image_tokens"] if traffic["images_per_request"] else 0
    pt, ot = traffic["prompt_tokens"], traffic["output_tokens"]
    budget = config["budgets"]["token_budget"]
    warm = traffic["warm"]
    g = traffic["greedy_share"]
    variants = [v for v, on in ((True, g > 0), (False, g < 1)) if on]
    text = []                      # (C, B, pages)
    for c in sorted({_pow2(n) for n in range(1, min(budget, pt["max"]) + 1)}):
        for b in range(1, warm["prefill_batch"] + 1):
            if b * (c // 2 + 1) > budget and b > 1:
                continue
            for p in _page_buckets(n_img + 1, n_img + pt["max"], block):
                if p * block >= c:
                    text.append((c, b, p))
    decode_pages = _page_buckets(n_img + pt["min"] + 1,
                                 n_img + pt["max"] + ot["max"], block)
    return {
        "encode_batches": list(range(1, config["budgets"]["image_budget"]
                                     + 1)) if n_img else [],
        "image_chunk": n_img,
        "text_chunks": text,
        "kv_blocks_moved": sorted({-(-(n_img + p) // block)
                                   for p in range(pt["min"], pt["max"] + 1)}),
        "decode": [(b, p) for p in decode_pages
                   for b in range(1, warm["decode_batch"] + 1)],
        "variants": variants,
    }


def _hold(cache, rid: int, n: int):
    """Give ``rid`` n rows of ``cache`` without running a program."""
    if n > 0:
        nb = -(-n // cache.spec.block_size)
        cache.prepare_prefill([rid], [n], 1, n, nb)
        cache.commit_prefill([rid], [n])


def _hold_seq(caches, rid: int, n: int):
    """Give ``rid`` n rows of every sequence pool of an instance."""
    from bench.probes import seq_pools

    for cache in seq_pools(caches):
        _hold(cache, rid, n)


def _sample(n: int, greedy: bool, np):
    return {"temp": np.full(n, 0.0 if greedy else 0.7, np.float32),
            "top_k": np.zeros(n, np.int32),
            "top_p": np.full(n, 0.9, np.float32),
            "seed": np.arange(n, dtype=np.uint32),
            "step": np.zeros(n, np.int32)}


def warm_up(engine, plan: dict, image, np) -> int:
    """Run every call of ``plan`` once; returns the number of calls."""
    from repro.core.request import Stage
    from repro.engine import runner as R

    insts = engine.server.instances
    enc = [i for i in insts if Stage.ENCODE in i.role]
    pre = [i for i in insts if Stage.PREFILL in i.role]
    dec = [i for i in insts if Stage.DECODE in i.role]
    bs = R.KV_BLOCK
    n_img = plan["image_chunk"]
    rid = iter(range(-1, -10**9, -1))
    n_calls = 0

    # encode at every batch size, and the E->P hand-off of one image
    for inst in enc:
        for b in plan["encode_batches"]:
            rids = [next(rid) for _ in range(b)]
            inst.runner.encode([(r, image) for r in rids])
            n_calls += 1
            for r in rids[1:]:
                inst.caches.release(r)
            dst = next((i for i in pre if i is not inst), None)
            if dst is not None:
                R.migrate(rids[0], inst.caches, dst.caches)
                dst.caches.release(rids[0])
            else:
                inst.caches.release(rids[0])

    for inst in pre:
        for greedy in plan["variants"]:
            if n_img:                                  # the image chunk
                r = next(rid)
                _hold(inst.caches.img, r, n_img)
                inst.runner.prefill_chunks([(r, None, True)],
                                           sample=_sample(1, greedy, np))
                inst.caches.release(r)
                n_calls += 1
            for c, b, p in plan["text_chunks"]:
                rids = [next(rid) for _ in range(b)]
                _hold_seq(inst.caches, rids[0], p * bs - c)
                inst.runner.prefill_chunks(
                    [(r, np.zeros(c, np.int32), False) for r in rids],
                    sample=_sample(b, greedy, np))
                for r in rids:
                    inst.caches.release(r)
                n_calls += 1

    # the P->D hand-off of every size the traffic produces
    for inst in pre:
        dst = next((i for i in dec if i is not inst), None)
        if dst is None:
            continue
        for n_blocks in plan["kv_blocks_moved"]:
            r = next(rid)
            _hold_seq(inst.caches, r, n_blocks * bs)
            if n_img:
                _hold(inst.caches.img, r, n_img)
            R.migrate(r, inst.caches, dst.caches)
            dst.caches.release(r)
            n_calls += 1

    for inst in dec:
        for b, p in plan["decode"]:
            for greedy in plan["variants"]:
                rids = [next(rid) for _ in range(b)]
                _hold_seq(inst.caches, rids[0], p * bs - 1)
                inst.runner.decode(rids, np.zeros(b, np.int32),
                                   sample=_sample(b, greedy, np))
                for r in rids:
                    inst.caches.release(r)
                n_calls += 1
    return n_calls


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------
def serve(engine, reqs, seconds: float, traffic: dict, jax, tracer):
    """Offer ``reqs`` open-loop.  Returns (t_open, t_close, t_end,
    submitted) where submitted[k] = (rid | None, submit-returned time)."""
    from repro.core.request import SamplingParams

    submitted = [None] * len(reqs)
    sampling, drain_s = traffic["sampling"], traffic["drain_s"]
    engine.start()
    t_open = time.perf_counter() + 0.05

    def generate():
        for k, q in enumerate(reqs):
            wait = t_open + q.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            sp = SamplingParams(max_tokens=q.max_tokens) if q.greedy else \
                SamplingParams(temperature=sampling["temperature"],
                               top_p=sampling["top_p"], seed=q.sample_seed,
                               max_tokens=q.max_tokens)
            try:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    rid = engine.submit(q.prompt, media=q.image, sampling=sp)
            except Exception as e:        # a refused request counts failed
                log(f"request {q.index} refused: {e!r}")
                rid = None
            submitted[k] = (rid, time.perf_counter())

    gen = threading.Thread(target=generate, name="bench-generator")
    if tracer is not None:
        tracer.start()
    while time.perf_counter() < t_open:
        time.sleep(0.001)
    with jax.profiler.TraceAnnotation("bench.window"):
        gen.start()
        time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
        t_close = time.perf_counter()
    if tracer is not None:
        tracer.stop()
    gen.join()
    rids = [s[0] for s in submitted if s[0] is not None]
    engine.wait(rids, timeout=max(0.0, t_close + drain_s
                                  - time.perf_counter()))
    t_end = time.perf_counter()
    engine.close(drain_timeout=0)
    return t_open, t_close, t_end, submitted


class Tracer:
    """The JAX profiler over the window, into a temporary directory."""

    def __init__(self, jax):
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self):
        self.jax.profiler.start_trace(self.dir)

    def stop(self):
        self.jax.profiler.stop_trace()

    def events(self):
        from bench.trace import read_xplane

        paths = sorted(Path(self.dir).rglob("*.xplane.pb"))
        try:
            return read_xplane(str(paths[-1]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# arithmetic of the end-to-end metrics
# ---------------------------------------------------------------------------
def outcomes(reqs, submitted, rec, t_open, t_close, t_end, lim):
    """Per request: (ttft s, gaps [s], finished, met); and the output
    tokens emitted inside the window."""
    from bench.stats import meets

    rows = []
    in_window = 0
    for q, sub in zip(reqs, submitted):
        due = t_open + q.due
        rid = sub[0] if sub else None
        toks = rec.tokens.get(rid, []) if rid is not None else []
        in_window += sum(1 for t, _ in toks if t_open <= t <= t_close)
        fin = rec.finish.get(rid) if rid is not None else None
        finished = (fin is not None and fin[0] == "length"
                    and len(toks) == q.max_tokens)
        ttft = (toks[0][0] - due) if toks else (t_end - due)
        gaps = [b[0] - a[0] for a, b in zip(toks, toks[1:])]
        rows.append((ttft, gaps, finished,
                     finished and meets(ttft, gaps, lim)))
    return rows, in_window


def end_to_end(rows, tokens_in_window, t_open, t_close, setup_s) -> dict:
    from bench.stats import quantile

    gaps = [g for r in rows for g in r[1]]
    return {
        "ttft_p90_ms": 1e3 * quantile([r[0] for r in rows], 0.9),
        "tpot_p90_ms": 1e3 * quantile(gaps, 0.9) if gaps else None,
        "output_tok_per_s": tokens_in_window / (t_close - t_open),
        "slo_attainment": 100.0 * sum(r[3] for r in rows) / len(rows),
        "setup_s": setup_s}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def pick_sample(reqs, rows, n: int, seed: int, np) -> list:
    """Up to n finished greedy requests: the one with most output tokens,
    then others drawn from the seed."""
    done = [k for k, (q, row) in enumerate(zip(reqs, rows))
            if q.greedy and row[2]]
    if not done:
        return []
    longest = max(done, key=lambda k: (reqs[k].max_tokens, -k))
    rng = np.random.default_rng(seed % 2**64)
    rest = [int(k) for k in rng.permutation(done) if k != longest]
    return [longest] + rest[:n - 1]


def check_sample(config, reqs, submitted, rec, picked, np) -> list:
    """The checked requests as the reference takes them."""
    return [{"prompt": reqs[k].prompt, "image": reqs[k].image,
             "served": np.array([t for _, t in rec.tokens[submitted[k][0]]],
                                np.int32)} for k in picked]


def reference_shape(config: dict, traffic: dict) -> dict:
    """Fixed shapes of the reference's calls for a cell: rows padded to
    the longest request the traffic can make, one read per output token."""
    from bench import arch

    block = arch.load(config).Q_BLOCK
    n_img = config["image_tokens"] if traffic["images_per_request"] else 0
    reads = traffic["output_tokens"]["max"]
    rows = n_img + traffic["prompt_tokens"]["max"] + reads - 1
    return {"seq_len": -(-rows // block) * block, "reads": reads}


def judge(config, limits, shape, params, sample, np, reference) -> tuple:
    """(correct, numbers) with numbers {name: (value, limit, rule)};
    ``reference`` is the architecture's module (``bench/arch``)."""
    numbers = {"checked_tokens": (int(sum(len(s["served"]) for s in sample)),
                                  limits["min_tokens"], ">=")}
    if sample:
        vocab = config["vocab_size"]
        numbers["in_vocab"] = (int(all(np.all((s["served"] >= 0)
                                              & (s["served"] < vocab))
                                       for s in sample)), 1, ">=")
        gaps = reference.logit_gaps(config, params, sample, **shape,
                                    group=limits["requests_per_call"])
        numbers["max_logit_gap"] = (float(gaps.max()),
                                    limits["max_logit_gap"], "<=")
    ok = all((v <= lim) if rule == "<=" else (v >= lim)
             for v, lim, rule in numbers.values()) and "max_logit_gap" in \
        numbers
    return ok, numbers


# ---------------------------------------------------------------------------
def prepare(cell, rehearse: bool):
    """(config, traffic, check limits) of a cell as run; the rehearsal
    swaps in the configuration's small sizes and limits, and the traffic's
    smaller warm-up for its few requests."""
    config, traffic = dict(cell.config), dict(cell.traffic)
    limits = {**config["check"], **traffic["check"]}
    if rehearse:
        config.update(config["rehearsal"]["sizes"])
        limits.update(config["rehearsal"]["check"])
        traffic.update(traffic["rehearsal"])
    return config, traffic, limits


def run_cell(cell, seed: int, seconds: float, trace_on: bool, *,
             rehearse: bool = False, fault=None):
    """One run of ``cell``; returns (result dict, numbers compared).
    ``fault(engine)``, if given, breaks the engine before the warm-up (the
    tests' way to see ``correct`` fail)."""
    import jax
    import numpy as np

    from bench import arch, program, weights
    from bench.cell import model_config
    from bench.probes import Record, instrument, record_events
    from bench.traffic import make_window

    devs = jax.devices()
    dev = devs[0]
    if not rehearse and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devs)} {dev.platform!r} device(s)")
    config, traffic, limits = prepare(cell, rehearse)
    compile_log = CompileLog(jax)
    params = weights.make_params(config, seed)
    jax.block_until_ready(params)
    log(f"weights: {sum(x.size for x in jax.tree.leaves(params)) / 1e9:.3f}"
        f" B parameters, seed {seed}")
    engine = build_engine(config, model_config(config), params, dev, rehearse)
    if fault is not None:
        fault(engine)
    rec = Record()
    record_events(engine, rec)
    reqs = make_window(traffic, seed, seconds, vocab=config["vocab_size"],
                       image_tokens=config["image_tokens"],
                       d_model=config["hidden_size"])
    from repro.engine.runner import KV_BLOCK
    image = next((q.image for q in reqs if q.image is not None), None)
    n = warm_up(engine, warm_plan(config, traffic, KV_BLOCK), image, np)
    log(f"warm-up: {n} runner calls; {compile_log.builds} programs built so "
        f"far, {compile_log.cache_loads} of them loaded from the persistent "
        f"cache")
    tracer = None
    if trace_on:
        instrument(engine, rec, jax)
        engine.server.trace.on = True
        tracer = Tracer(jax)
    c0 = compile_log.builds
    t_open, t_close, t_end, submitted = serve(engine, reqs, seconds,
                                              traffic, jax, tracer)
    setup_s = t_open - T_START
    in_window_compiles = compile_log.builds - c0
    late = [s[1] - (t_open + q.due) for q, s in zip(reqs, submitted)
            if s is not None]
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    rows, tokens_in_window = outcomes(reqs, submitted, rec, t_open, t_close,
                                      t_end, traffic["limits"])
    n_failed = sum(1 for r in rows if not r[2])
    srv = engine.server
    log(f"served {len(rows) - n_failed} of {len(rows)}; migrations "
        f"{ {f'{s}->{d}': k for (s, d), k in srv.migration_routes.items()} }"
        f"; transfer retries {srv.n_transfer_retries}, replays "
        f"{srv.n_replays}; submit returned at most "
        f"{1e3 * max(late, default=0):.1f} ms after due")
    log(f"set-up {setup_s:.1f} s; window {t_close - t_open:.2f} s; drain "
        f"{t_end - t_close:.1f} s")
    print(f"compiles inside the window and drain: {in_window_compiles}",
          flush=True)
    picked = pick_sample(reqs, rows, limits["requests"], seed, np)
    sample = check_sample(config, reqs, submitted, rec, picked, np)
    recorded = program.snapshot(srv) if trace_on else {}
    # the program's state goes before the reference runs
    del engine, srv
    gc.collect()

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    metrics = {}
    extra = {}
    if not trace_on:
        values = end_to_end(rows, tokens_in_window, t_open, t_close, setup_s)
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        from bench.readings import Readings, load_reader
        from bench.trace import breakdown, busy_seconds

        ev = tracer.events()
        r = Readings(config=config, device_kind=dev.device_kind, events=ev,
                     rec=rec, reqs=reqs, submitted=submitted,
                     t_open=t_open, t_close=t_close, program=recorded,
                     program_spans=ev.program_spans)
        for m in cell.per_layer:
            v = load_reader(m["name"])(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = busy_seconds(ev, r.lo, r.hi)
        device["window_s"] = (r.hi - r.lo) / 1e9
        extra["breakdown"] = breakdown(ev, r.lo, r.hi)

    t_ref = time.perf_counter()
    correct, numbers = judge(config, limits,
                             reference_shape(config, traffic), params,
                             sample, np, arch.load(config))
    log(f"reference: {len(sample)} requests in "
        f"{time.perf_counter() - t_ref:.1f} s")
    result = {"correct": correct, "attempted": len(reqs),
              "failed": n_failed, "metrics": metrics, "device": device,
              **extra, "compiles_in_window": in_window_compiles,
              "check": {k: {"value": v, "limit": lim, "rule": rule}
                        for k, (v, lim, rule) in numbers.items()}}
    return result, numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        log(f"{SRC / 'repro'} not found: run from a checkout of the repo")
        return 2
    sys.path[:0] = [str(ROOT), str(SRC)]
    # the compile cache lives at one fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.cell import load_cell
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = load_cell(args.workload)
    try:
        result, numbers = run_cell(cell, args.seed, args.seconds,
                                   bool(args.trace))
    except NoChip as e:
        log(f"FAIL: {e}")
        return 3
    print(json.dumps(result), flush=True)
    for name, (v, lim, rule) in numbers.items():
        print(f"check {name}: {v} (limit {rule} {lim})", file=sys.stderr)
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
