"""End-to-end engine decode throughput: dense-gather vs device-paged.

Drives ``HydraServer`` (encode + prefill + decode, reduced LLaVA-1.5-7B,
single EPD instance) with the same B=8 multimodal workload under each
decode backend:

  dense            : the seed fallback (``device_cache=False``) — every
                     decode step round-trips the whole KV cache between
                     host numpy and device AND retraces/compiles for each
                     novel (batch, max-context) shape, because context
                     lengths grow every step
  paged-interpret  : the device-resident path (DESIGN.md §11) — Pallas
                     paged-attention + fused cache-write over block tables
                     in interpret mode (the CPU default), bucketed jit
                     shapes so steady state never recompiles
  paged-ref        : same paged semantics through the pure-jnp oracles
                     (``REPRO_PAGED_IMPL=ref``), the fastest CPU option

Each server is warmed with a *different* random workload first: that fully
warms the paged paths (their shape buckets are workload-independent) while
leaving the dense path its production behavior of recompiling on the novel
context-length trajectory — exactly the host-bound cost the paged decode
eliminates.  Only decode calls are timed (wall clock around
``ModelRunner.decode``).  Results land in ``BENCH_engine.json`` at the repo
root; the acceptance bar is paged-interpret >= 3x dense tokens/s at B=8.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path

import jax
import numpy as np

B = 8                # concurrent requests (acceptance point)
MAX_NEW = 18         # max context 24 text + 16 media + 18 <= 64 (4 KV pages)


class _DecodeTimer:
    """Wraps a runner's decode entry point, accumulating wall time/tokens."""

    def __init__(self, runner):
        self.seconds = 0.0
        self.tokens = 0
        self._decode = runner.decode
        runner.decode = self._timed

    def _timed(self, rids, toks, *a, **kw):
        t0 = time.perf_counter()
        out = self._decode(rids, toks, *a, **kw)
        self.seconds += time.perf_counter() - t0
        self.tokens += len(rids)
        return out


def _submit_batch(srv, cfg, rng):
    for _ in range(B):
        n = int(rng.integers(8, 25))  # heterogeneous context lengths
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
    # pool sized to the workload (8 requests x <=64 tokens + headroom):
    # interpret-mode kernel emulation copies scale with pool size
    srv = HydraServer(cfg, params, DisaggConfig({"EPD": 1}),
                      device_cache=device_cache, kv_blocks=64)
    # warm the server on a different random workload: paged shape buckets
    # are workload-independent, the dense path keeps retracing in the
    # measured run (its per-step shapes are novel there, as in production)
    _submit_batch(srv, cfg, np.random.default_rng(1))
    srv.run()
    timers = [_DecodeTimer(i.runner) for i in srv.instances]
    _submit_batch(srv, cfg, np.random.default_rng(0))
    srv.run()
    secs = sum(t.seconds for t in timers)
    toks = sum(t.tokens for t in timers)
    return toks / max(secs, 1e-12), toks


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
            tok_per_s, toks = _drive(device_cache)
        finally:
            os.environ.pop("REPRO_PAGED_IMPL", None)
            if prev:
                os.environ["REPRO_PAGED_IMPL"] = prev
        results[name] = {"decode_tokens_per_s": tok_per_s,
                         "decode_tokens": toks, "batch": B}
        rows.append((f"engine/decode/{name}", 1e6 / tok_per_s,
                     f"tok_per_s={tok_per_s:.1f}"))
    speedup = (results[paged]["decode_tokens_per_s"]
               / results["dense"]["decode_tokens_per_s"])
    results["speedup"] = speedup
    results["backend"] = jax.default_backend()
    if out is None:
        out = Path(__file__).resolve().parent.parent / "BENCH_engine.json"
    Path(out).write_text(json.dumps(results, indent=2) + "\n")
    rows.append(("engine/decode/speedup", 0.0, f"speedup={speedup:.2f}x"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
