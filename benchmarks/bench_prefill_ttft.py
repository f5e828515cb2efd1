"""End-to-end engine prefill throughput / TTFT: dense vs device-paged.

Drives ``HydraServer`` (encode + prefill + decode, reduced LLaVA-1.5-7B,
single EPD instance) with the same B=8 multimodal workload under each
prefill backend:

  dense            : the seed path (``device_cache=False``) — one request
                     per Python-loop iteration, a full host gather of the
                     prior context per chunk, dense attention, a numpy
                     round-trip of every layer's chunk K/V back into the
                     cache, and a retrace for each novel (chunk, context)
                     shape
  paged-interpret  : the batched device-resident path (DESIGN.md §12) —
                     ONE jitted ``prefill_chunk_paged`` per scheduler
                     iteration over all requests' chunks, Pallas chunked
                     paged-attention + fused chunk cache-write in interpret
                     mode (the CPU default), pow2-bucketed batch/chunk/page
                     shapes so steady state never recompiles
  paged-ref        : same batched paged semantics through the pure-jnp
                     oracles (``REPRO_PAGED_IMPL=ref``), the fastest CPU
                     option

Each server is warmed with a *different* random workload first: the paged
buckets are workload-independent, while the dense path keeps its
production behavior of retracing along the novel (chunk, context)
trajectory.  Only prefill runner calls are timed (wall clock around
``ModelRunner.prefill_chunks`` / the dense ``prefill_chunk``); prefilled
tokens include media tokens entering the LM stream.  Mean/P90 TTFT over
the measured run ride along for the SLO story (they include decode time
for requests that interleave).  Results land in ``BENCH_prefill.json`` at
the repo root; the acceptance bar is paged-interpret >= 3x dense prefill
tokens/s at B=8.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path

import jax
import numpy as np

B = 8                # concurrent requests (acceptance point)
PROMPT_LO, PROMPT_HI = 24, 49   # text tokens (+ 16 media tokens in the LM)
MAX_NEW = 4          # a little decode so TTFT interleaving is realistic


class _PrefillTimer:
    """Wraps a runner's batched prefill entry point, accumulating wall
    time.  The dense server path goes through ``prefill_chunks`` too (the
    host fallback loops per request inside it), so one wrapper covers both
    backends."""

    def __init__(self, runner):
        self.seconds = 0.0
        self._chunks = runner.prefill_chunks
        runner.prefill_chunks = self._timed_chunks

    def _timed_chunks(self, items, *a, **kw):
        t0 = time.perf_counter()
        out = self._chunks(items, *a, **kw)
        self.seconds += time.perf_counter() - t0
        return out


def _submit_batch(srv, cfg, rng):
    for _ in range(B):
        n = int(rng.integers(PROMPT_LO, PROMPT_HI))
        prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        media = (rng.standard_normal((cfg.media_tokens, cfg.d_model))
                 * 0.1).astype(np.float32)
        srv.submit(prompt, media=media, max_new_tokens=MAX_NEW)


def _drive(device_cache: bool):
    from repro.configs import get_config
    from repro.core.simulator import DisaggConfig
    from repro.engine.server import HydraServer
    from repro.models import model as M

    cfg = get_config("llava-1.5-7b").reduced()
    if "p" not in _drive._params:
        _drive._params["p"] = M.init_params(cfg, jax.random.PRNGKey(0))
    params = _drive._params["p"]
    srv = HydraServer(cfg, params, DisaggConfig({"EPD": 1}),
                      device_cache=device_cache, kv_blocks=64)
    # warm on a different random workload (paged buckets are
    # workload-independent; dense keeps retracing in the measured run)
    _submit_batch(srv, cfg, np.random.default_rng(1))
    srv.run()
    warm_rids = set(srv.items)
    timers = [_PrefillTimer(i.runner) for i in srv.instances]
    _submit_batch(srv, cfg, np.random.default_rng(0))
    out = srv.run()
    secs = sum(t.seconds for t in timers)
    # every token that entered the LM prefill stream this measured run
    # (media + text; warm-up requests are excluded)
    meas = [r.req for rid, r in out.items() if rid not in warm_rids]
    toks = sum(r.prefill_total for r in meas
               if r.first_token_time is not None)
    ttfts = sorted(r.ttft() for r in meas if r.ttft() is not None)
    ttft_mean = float(np.mean(ttfts)) if ttfts else 0.0
    ttft_p90 = float(ttfts[int(0.9 * (len(ttfts) - 1))]) if ttfts else 0.0
    return toks / max(secs, 1e-12), toks, ttft_mean, ttft_p90


_drive._params = {}


def run(out=None):
    rows = []
    results = {}
    # on a TPU only the compiled kernels may run (runner.default_attn_impl)
    variants = [("dense", False, None)] + (
        [("paged-kernel", True, "kernel")] if jax.default_backend() == "tpu"
        else [("paged-interpret", True, "interpret"),
              ("paged-ref", True, "ref")])
    paged = variants[1][0]
    for name, device_cache, impl in variants:
        prev = os.environ.pop("REPRO_PAGED_IMPL", None)
        if impl:
            os.environ["REPRO_PAGED_IMPL"] = impl
        try:
            tok_per_s, toks, ttft_mean, ttft_p90 = _drive(device_cache)
        finally:
            os.environ.pop("REPRO_PAGED_IMPL", None)
            if prev:
                os.environ["REPRO_PAGED_IMPL"] = prev
        results[name] = {"prefill_tokens_per_s": tok_per_s,
                         "prefill_tokens": toks, "batch": B,
                         "ttft_mean_s": ttft_mean, "ttft_p90_s": ttft_p90}
        rows.append((f"engine/prefill/{name}", 1e6 / max(tok_per_s, 1e-12),
                     f"tok_per_s={tok_per_s:.1f} ttft_p90={ttft_p90:.3f}s"))
    speedup = (results[paged]["prefill_tokens_per_s"]
               / results["dense"]["prefill_tokens_per_s"])
    results["speedup"] = speedup
    results["backend"] = jax.default_backend()
    if out is None:
        out = Path(__file__).resolve().parent.parent / "BENCH_prefill.json"
    Path(out).write_text(json.dumps(results, indent=2) + "\n")
    rows.append(("engine/prefill/speedup", 0.0, f"speedup={speedup:.2f}x"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
