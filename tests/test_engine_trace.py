"""The engine's own spans and counters (engine/trace.py), the stage log
the real engine keeps for every request, and the names of the runner's
jitted steps."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.costmodel import H800
from repro.core.request import SLO
from repro.core.simulator import Cluster, DisaggConfig, Simulator
from repro.data.workload import PROFILES, make_requests
from repro.engine.api import Engine
from repro.engine.server import HydraServer
from repro.engine.trace import OFF, Trace
from repro.models import model as M

from conftest import reduced_cfg

EPD_SPLIT = DisaggConfig({"E": 1, "P": 1, "D": 1})
QUEUES = {"encode_queue", "prefill_queue", "decode_queue"}


@pytest.fixture(scope="module")
def llava():
    cfg = reduced_cfg("llava-1.5-7b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(3))


def _requests(cfg, n=3, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(0, cfg.vocab_size, 7 + i).astype(np.int32)
        media = None if i == n - 1 else \
            (rng.standard_normal((cfg.media_tokens, cfg.d_model)) * 0.1
             ).astype(np.float32)
        out.append((prompt, media))
    return out


def _serve(cfg, params, disagg, *, tokens=4, **kw):
    srv = HydraServer(cfg, params, disagg, **kw)
    rids = [srv.submit(p, media=m, max_new_tokens=tokens)
            for p, m in _requests(cfg)]
    srv.run()
    return srv, rids


def _names(spans):
    return [n for n, *_ in spans]


def test_off_trace_records_nothing_with_one_shared_noop():
    t = Trace()
    a, b = t.span("step"), t.span("migrate.fetch")
    assert a is b is OFF.span("runner.decode")
    with a:
        with b:
            pass
    t.count("migrate.host_bytes", 10)
    assert t.spans == [] and t.counters == {}


def test_on_trace_records_nested_spans_and_counters():
    t = Trace(on=True)
    with t.span("migrate"):
        with t.span("migrate.fetch"):
            pass
        t.count("migrate.host_bytes", 3)
    t.count("migrate.host_bytes", 4)
    assert _names(t.spans) == ["migrate.fetch", "migrate"]
    (_, i0, i1), (_, o0, o1) = t.spans
    assert o0 <= i0 <= i1 <= o1
    assert t.counters == {"migrate.host_bytes": 7}
    # a span entered and left by hand records the same way
    s = t.span("loop.idle").__enter__()
    s.__exit__(None, None, None)
    assert t.spans[-1][0] == "loop.idle"


def test_split_hand_offs_record_their_phases_and_host_bytes(llava):
    cfg, params = llava
    srv, _ = _serve(cfg, params, EPD_SPLIT, trace=True)
    names = set(_names(srv.trace.spans))
    assert {"step", "runner.encode", "runner.prefill", "runner.decode",
            "migrate", "migrate.read", "migrate.fetch", "migrate.hash",
            "migrate.import"} <= names
    assert "migrate.backoff" not in names          # no transfer failed
    moves = [s for s in srv.trace.spans if s[0] == "migrate"]
    # two image requests hand off E->P and P->D, the text one P->D
    assert len(moves) == srv.n_migrations == 5
    # the device pools' payloads are digested on the device, each twice
    # (at the read and before the import), and never copied to the host:
    # one fetch a hand-off brings the 16-byte digests of its two device
    # leaves (the KV and the image pool's payload), from both checksums
    assert srv.trace.counters == {
        "migrate.device_bytes": 2 * srv.migrated_bytes,
        "migrate.host_bytes": srv.n_migrations * 2 * 2 * 16}
    assert _names(srv.trace.spans).count("migrate.fetch") == len(moves)
    # the phases lie inside the hand-off spans
    parts = [s for s in srv.trace.spans if s[0].startswith("migrate.")]
    assert all(any(m0 <= p0 and p1 <= m1 for _, m0, m1 in moves)
               for _, p0, p1 in parts)


def test_untraced_server_keeps_only_the_stage_log(llava):
    cfg, params = llava
    srv, rids = _serve(cfg, params, EPD_SPLIT)
    assert srv.trace.spans == [] and srv.trace.counters == {}
    assert all(srv.items[r].req.stage_log for r in rids)


def _simulator_stage_names():
    reqs = make_requests(PROFILES["textcaps"], rate=8.0, n=12,
                         image_tokens_per_image=576, slo=SLO(0.25, 0.04),
                         seed=3)
    cl = Cluster(get_config("llava-1.5-7b"), H800, EPD_SPLIT,
                 SLO(0.25, 0.04))
    done = Simulator(cl).run(reqs, until=reqs[-1].arrival + 300)
    return {n for r in done for n, _, _ in r.stage_log}


@pytest.mark.parametrize("disagg,image_log,text_log", [
    (EPD_SPLIT,
     ["encode_queue", "encode_exec", "migrate", "prefill_queue",
      "prefill_exec", "migrate", "decode_queue", "decode_exec"],
     ["prefill_queue", "prefill_exec", "migrate", "decode_queue",
      "decode_exec"]),
    (DisaggConfig({"EPD": 1}),
     ["encode_queue", "encode_exec", "prefill_queue", "prefill_exec",
      "decode_queue", "decode_exec"],
     ["prefill_queue", "prefill_exec", "decode_queue", "decode_exec"]),
], ids=["E1,P1,D1", "EPD1"])
def test_real_stage_log_uses_the_simulator_names(llava, disagg, image_log,
                                                 text_log):
    cfg, params = llava
    srv, rids = _serve(cfg, params, disagg)
    sim = _simulator_stage_names()
    assert sim == {"encode_exec", "prefill_exec", "decode_exec", "migrate"}
    for rid, (_, media) in zip(rids, _requests(cfg)):
        log = srv.items[rid].req.stage_log
        # one entry per stage and instance, not per decode step
        assert _names(log) == (image_log if media is not None else text_log)
        assert set(_names(log)) <= sim | QUEUES
        assert all(t0 <= t1 for _, t0, t1 in log)
        assert all(a[2] <= b[1] for a, b in zip(log, log[1:]))


def test_threaded_engine_times_submit_waits_and_idle_stretches(llava):
    cfg, params = llava
    eng = Engine(cfg, params, EPD_SPLIT, trace=True).start()
    try:
        rids = [eng.submit(p, media=m, max_new_tokens=3)
                for p, m in _requests(cfg)]
        assert eng.wait(rids, timeout=300)
    finally:
        eng.close()
    spans = eng.server.trace.spans
    assert _names(spans).count("submit.lock_wait") == len(rids)
    idle = [s for s in spans if s[0] == "loop.idle"]
    assert idle
    # a step either runs inside an idle stretch (the one that ends it) or
    # outside every one of them; it never straddles an edge
    for _, s0, s1 in (s for s in spans if s[0] == "step"):
        inside = [(i0 <= s0 and s1 <= i1) for _, i0, i1 in idle]
        apart = [(s1 <= i0 or i1 <= s0) for _, i0, i1 in idle]
        assert all(a or b for a, b in zip(inside, apart))


STEP_MODULES = {"_decode_jit": "jit_decode", "_encode_jit": "jit_encode",
                "_paged_jit": "jit_paged_decode",
                "_prefill_jit": "jit_prefill", "_argmax_jit": "jit_argmax"}


def _capture(runner, seen):
    """Record the abstract arguments of each named step's first call."""
    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype) \
            if hasattr(x, "shape") else x

    for attr in STEP_MODULES:
        fn = getattr(runner, attr)

        def rec(*args, _fn=fn, _attr=attr):
            seen.setdefault(_attr, (_fn, jax.tree.map(spec, args)))
            return _fn(*args)

        setattr(runner, attr, rec)


def test_runner_steps_lower_to_named_modules(llava):
    cfg, params = llava
    seen = {}
    for device_cache in (True, False):
        srv = HydraServer(cfg, params, EPD_SPLIT, device_cache=device_cache)
        for inst in srv.instances:
            _capture(inst.runner, seen)
        for p, m in _requests(cfg, n=2):
            srv.submit(p, media=m, max_new_tokens=3)
        srv.run()
    assert set(seen) == set(STEP_MODULES)
    for attr, (fn, args) in seen.items():
        text = fn.lower(*args).as_text()
        head = text[:text.index("{")]
        assert f"module @{STEP_MODULES[attr]} " in head, (attr, head)
        assert "_unknown" not in head and "_lambda_" not in head
