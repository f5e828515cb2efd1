#!/usr/bin/env python3
"""Chip smoke test: serve llava-1.5-7b at its published widths on one TPU
through the E+P+D engine, and check the compiled kernels against the
pure-jnp oracle on the same chip.

    python chip_smoke.py              # on a host with one TPU chip
    python chip_smoke.py --rehearse   # CPU rehearsal of the same phases

Phases, each of which fails the run:

  1. model       llava-1.5-7b widths as published, depth cut to 16 of 32
                 layers so the bf16 weights leave room for three KV pools;
                 random weights from ``--seed``
  2. logits      one image+text request prefilled (image chunk, then text
                 chunks) and decoded one step through the compiled kernels
                 and through the ``attn_impl="ref"`` oracle: the logits must
                 agree within ``LOGIT_RTOL`` of the reference logit range
  3. serve       ``Engine`` with ``DisaggConfig`` E1,P1,D1 (three instances
                 on the one chip), pools sized from the device's
                 ``bytes_limit``; 8 requests, half with one 576-token
                 image, greedy and seeded sampling, ``max_tokens`` 16.
                 Every request must finish with 16 in-vocabulary tokens,
                 encode, prefill and decode steps must all have run, and at
                 least one E->P and one P->D migration must have happened

JAX's "donated buffers were not usable" warning is an error here: the paged
steps donate the page pools, and a silent copy of every pool on every step
would otherwise go unnoticed.

The timings printed are host clocks around work that ends in
``block_until_ready``: smoke timings, not benchmark metrics.  On success the
last line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero and prints no result.  ``--rehearse`` runs the phases
on the CPU at the reduced config with interpreted kernels, exits 0 when
they pass, and never prints ``"ok"``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading
import time
import warnings
from collections import defaultdict
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "llava-1.5-7b"
LAYERS = 16            # of 32: bf16 weights ~7.1 GB, leaving ~8 GB for KV
N_REQUESTS = 8
MAX_TOKENS = 16
PROMPT_RANGE = (64, 256)
# Compiled kernels and the oracle see the same bf16 weights and inputs;
# both attend in f32 and round the attention output to bf16.  They differ
# in f32 summation order (and in the MXU pass structure), which flips some
# bf16 roundings (one ulp = 2^-8 relative); the flips then compound through
# 16 residual layers.  5% of the reference logit range covers that, while a
# wrong mask, page or slot moves the logits by O(1) of their range.
LOGIT_RTOL = 5e-2
RESERVE_BYTES = 2 << 30   # left free for step temporaries and migrations
SERVE_TIMEOUT_S = 900     # of the 1200 s the whole script may take


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class CompileLog:
    """Counts XLA backend compiles (and persistent-cache hits) process-wide
    through ``jax.monitoring``."""

    def __init__(self, jax):
        self.lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        with self.lock:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.cache_hits += 1


class PhaseTimer:
    """Wraps one instance's runner entry points: counts each stage's steps
    and times them (host clock, ended by ``block_until_ready`` on the
    instance's pools).  A call during which something compiled is cold;
    the others are steady state."""

    STAGES = {"encode": "encode", "prefill_chunks": "prefill",
              "decode": "decode"}

    def __init__(self, jax, compile_log: CompileLog):
        self.jax = jax
        self.log = compile_log
        self.lock = threading.Lock()
        self.steps = defaultdict(int)
        self.steady = defaultdict(list)
        self.cold = defaultdict(list)

    def wrap(self, inst):
        runner = inst.runner
        for method, stage in self.STAGES.items():
            fn = getattr(runner, method)

            def timed(*a, _fn=fn, _stage=stage, **kw):
                c0 = self.log.compiles
                t0 = time.perf_counter()
                out = _fn(*a, **kw)
                self.jax.block_until_ready(
                    [c.data for c in (inst.caches.kv, inst.caches.img)
                     if c is not None])
                dt = time.perf_counter() - t0
                with self.lock:
                    self.steps[_stage] += 1
                    (self.cold if self.log.compiles > c0
                     else self.steady)[_stage].append(dt)
                return out

            setattr(runner, method, timed)


def build_model(args, jax, jnp):
    from repro.configs import get_config
    from repro.models import model as M

    base = get_config(ARCH)
    if args.rehearse:
        cfg = base.reduced()
        print(f"model: {cfg.name} (rehearsal: reduced config, "
              f"{cfg.num_layers} layers, d_model {cfg.d_model})")
    else:
        cfg = dataclasses.replace(base, num_layers=LAYERS)
        print(f"model: {ARCH} at published widths: d_model {cfg.d_model}, "
              f"{cfg.num_heads} heads x head_dim {cfg.head_dim} "
              f"({cfg.num_kv_heads} KV heads), d_ff {cfg.d_ff}, "
              f"vocab {cfg.vocab_size}, {cfg.media_tokens} image tokens; "
              f"depth cut {base.num_layers} -> {cfg.num_layers} layers so "
              f"the bf16 weights leave room for the KV pools")
    t0 = time.perf_counter()
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed), jnp.bfloat16)
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"params: {n / 1e9:.3f} B in bf16 ({nbytes / 1e9:.3f} GB), "
          f"seed {args.seed}, init {time.perf_counter() - t0:.1f} s "
          f"(smoke timing)")
    return cfg, params


def media_for(cfg, rng, np):
    """One image's patch embeddings: the encode stage's input (the repo's
    vision frontend is the LLaVA projector over d_model-wide features)."""
    return (rng.standard_normal((cfg.media_tokens, cfg.d_model))
            * 0.1).astype(np.float32)


def check_logits(cfg, params, rng, np):
    """Phase 2: compiled kernels vs the ref oracle on one image+text
    request — last-position prefill logits and one decode step."""
    from repro.engine import runner as R

    media = media_for(cfg, rng, np)
    prompt = rng.integers(0, cfg.vocab_size, 100).astype(np.int32)
    need = -(-(cfg.media_tokens + len(prompt) + 2) // R.KV_BLOCK)
    out = {}
    for impl in (None, "ref"):
        caches = R.RunnerCaches(cfg, kv_blocks=R.bucket_pow2(need),
                                img_blocks=1, dtype=params["embed"].dtype,
                                device=True)
        run = R.ModelRunner(cfg, params, caches, attn_impl=impl)
        run.encode([(0, media)])
        run.prefill_chunks([(0, None, True)])          # the image chunk
        for t0 in range(0, len(prompt), 64):           # then text chunks
            pre = run.prefill_chunks([(0, prompt[t0:t0 + 64], False)])[0]
        nxt = int(np.argmax(pre))
        dec = run.decode([0], np.asarray([nxt]))[0]
        out[impl or run.attn_impl] = (pre.astype(np.float32),
                                      dec.astype(np.float32))
        del run, caches
        gc.collect()   # the runner's jitted bound methods form a cycle
    (kernel_name, got), (_, ref) = out.items()
    for name, a, b in (("prefill", got[0], ref[0]),
                       ("decode", got[1], ref[1])):
        if not (np.all(np.isfinite(a)) and np.all(np.isfinite(b))):
            fail(f"{name} logits are not finite")
        span = float(np.max(np.abs(b)))
        err = float(np.max(np.abs(a - b)))
        rel = err / span
        same_top = int(np.argmax(a)) == int(np.argmax(b))
        print(f"logits {name} ({kernel_name} vs ref): max|diff| {err:.5g}, "
              f"max|ref| {span:.5g}, ratio {rel:.5g} (tolerance "
              f"{LOGIT_RTOL}), argmax agrees: {same_top}")
        if not rel <= LOGIT_RTOL:
            fail(f"{name} logits of the {kernel_name} kernels differ from "
                 f"the ref oracle by {rel:.4g} of the logit range")


def pool_sizes(cfg, params, dev, rehearse: bool):
    """(kv_blocks, img_blocks) per instance such that three instances'
    pools plus the weights fit the device's ``bytes_limit``."""
    from repro.engine import runner as R

    img_blocks = N_REQUESTS
    if rehearse:
        return 64, img_blocks
    stats = dev.memory_stats()
    limit, in_use = stats["bytes_limit"], stats["bytes_in_use"]
    item = params["embed"].dtype.itemsize
    kv_block = 2 * cfg.num_layers * R.KV_BLOCK * cfg.kv_dim * item
    img_block = cfg.media_tokens * cfg.d_model * item
    per_inst = (limit - in_use - RESERVE_BYTES) // 3
    kv_blocks = (per_inst - (img_blocks + 1) * img_block) // kv_block - 1
    print(f"memory: bytes_limit {limit}, in use before pools {in_use}, "
          f"reserve {RESERVE_BYTES}; per instance {kv_blocks} KV blocks "
          f"({kv_blocks * R.KV_BLOCK} tokens, {kv_block} B each) + "
          f"{img_blocks} image blocks")
    if kv_blocks * R.KV_BLOCK < cfg.media_tokens + PROMPT_RANGE[1] + 64:
        fail(f"only {kv_blocks} KV blocks fit per instance")
    return kv_blocks, img_blocks


def serve(cfg, params, dev, rng, args, jax, np, compile_log):
    """Phase 3: 8 requests through ``Engine`` on E1,P1,D1."""
    from repro.core.request import SamplingParams
    from repro.engine.api import Engine
    from repro.launch.serve import parse_disagg

    kv_blocks, img_blocks = pool_sizes(cfg, params, dev, args.rehearse)
    engine = Engine(cfg, params, parse_disagg("E1,P1,D1"),
                    kv_blocks=kv_blocks, img_blocks=img_blocks)
    timer = PhaseTimer(jax, compile_log)
    for inst in engine.server.instances:
        timer.wrap(inst)
    thread_errors = []
    prev_hook = threading.excepthook
    threading.excepthook = lambda a: thread_errors.append(a)
    rids = []
    try:
        engine.start()
        t0 = time.perf_counter()
        for i in range(N_REQUESTS):
            n = int(rng.integers(PROMPT_RANGE[0], PROMPT_RANGE[1] + 1))
            prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            media = media_for(cfg, rng, np) if i % 2 == 0 else None
            sampling = (SamplingParams(max_tokens=MAX_TOKENS) if i % 4 < 2
                        else SamplingParams(temperature=0.8, top_k=40,
                                            top_p=0.95, seed=1000 + i,
                                            max_tokens=MAX_TOKENS))
            rids.append(engine.submit(prompt, media=media,
                                      sampling=sampling))
        deadline = time.monotonic() + SERVE_TIMEOUT_S
        while not engine.wait(rids, timeout=1.0):
            if thread_errors:
                e = thread_errors[0]
                raise RuntimeError("engine thread died") from e.exc_value
            if time.monotonic() > deadline:
                fail(f"requests unfinished after {SERVE_TIMEOUT_S} s")
        wall = time.perf_counter() - t0
    finally:
        engine.close(drain_timeout=0)
        threading.excepthook = prev_hook
    if thread_errors:
        raise RuntimeError("engine thread died") from \
            thread_errors[0].exc_value

    for rid in rids:
        it = engine.result(rid)
        toks = list(it.generated)
        if it.req.finish_reason != "length" or len(toks) != MAX_TOKENS:
            fail(f"request {rid} finished {it.req.finish_reason!r} with "
                 f"{len(toks)} tokens")
        if not all(0 <= t < cfg.vocab_size for t in toks):
            fail(f"request {rid} produced an out-of-vocabulary token")
        kind = "image+text" if it.media else "text"
        mode = "greedy" if it.req.sampling.temperature <= 0 else "sampled"
        print(f"request {rid} ({kind}, {len(it.prompt)} prompt tokens, "
              f"{mode}): {toks}")
    for stage in ("encode", "prefill", "decode"):
        if not timer.steps[stage]:
            fail(f"no {stage} step ran")
    routes = engine.server.migration_routes
    named = {f"{s}->{d}": n for (s, d), n in routes.items()}
    print(f"migrations: {named}, {engine.server.migrated_bytes} bytes")
    if not routes[("E", "P")] or not routes[("P", "D")]:
        fail(f"expected E->P and P->D migrations, got {dict(routes)}")
    print(f"served {len(rids)} requests in {wall:.2f} s wall "
          f"(smoke timing, compiles included)")
    for stage in sorted(timer.steps):
        st, cold = timer.steady[stage], timer.cold[stage]
        mean = f"{1e3 * sum(st) / len(st):.2f} ms" if st else "n/a"
        print(f"phase {stage}: {timer.steps[stage]} steps, {len(cold)} cold "
              f"(compiling, {sum(cold):.1f} s), steady mean {mean} over "
              f"{len(st)} steps (smoke timing)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases on the CPU at the reduced config "
                         "with interpreted kernels (never reports ok)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and images")
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"{SRC / 'repro'} not found: run from a checkout of the repo")
    sys.path.insert(0, str(SRC))
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    warnings.filterwarnings("error", message=".*donated buffers were not "
                                             "usable.*")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compile_cache import enable_compile_cache

    t_start = time.perf_counter()
    cache_dir = enable_compile_cache()
    compile_log = CompileLog(jax)
    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    print(f"device: {device}; compile cache {cache_dir}")
    if not args.rehearse and dev.platform != "tpu":
        fail(f"no TPU found (JAX platform {dev.platform!r}); "
             f"use --rehearse for the CPU rehearsal")

    rng = np.random.default_rng(args.seed)
    cfg, params = build_model(args, jax, jnp)
    t0 = time.perf_counter()
    check_logits(cfg, params, rng, np)
    print(f"logits phase: {time.perf_counter() - t0:.1f} s (smoke timing)")
    serve(cfg, params, dev, rng, args, jax, np, compile_log)
    if "repro.launch.dryrun" in sys.modules:  # forces 512 host devices
        fail("the serving path imported repro.launch.dryrun")

    stats = dev.memory_stats() or {}
    print(f"compiles: {compile_log.compiles} XLA compiles in "
          f"{compile_log.compile_s:.1f} s, {compile_log.cache_hits} "
          f"persistent-cache hits")
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')} "
          f"of bytes_limit {stats.get('bytes_limit', 'n/a')}")
    print(f"total: {time.perf_counter() - t_start:.1f} s (smoke timing)")
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
