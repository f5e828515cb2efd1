"""What the harness records around the engine, on the host clock
(``time.perf_counter``), without touching the program's code.

Always on (the end-to-end metrics need them):
  token events   the time each token reaches the engine's event stream
                 (``HydraServer.on_event``), the moment a client would see it

With ``--trace 1`` only (they add host work and a device sync):
  runner calls   each instance's ``encode`` / ``prefill_chunks`` /
                 ``decode``: start, end, the rids it carries and the shapes
                 of the real work (context and new rows per item), inside
                 a ``jax.profiler.TraceAnnotation`` named bench.<stage>
  steps          ``HydraServer.step`` as the span bench.step
  migrations     ``HydraServer._migrate``: host time ended by
                 ``block_until_ready`` on every instance's pools (span
                 bench.migrate)

A request's context is read from the instance's paged sequence pools
(``kv`` for dense attention, ``mla`` for latent attention, whichever the
architecture has).
"""
from __future__ import annotations

import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field


@dataclass
class Call:
    stage: str            # encode | prefill | decode
    t0: float
    t1: float
    rids: list
    items: list           # [(ctx, n)] of real work


@dataclass
class Record:
    tokens: dict = field(default_factory=lambda: defaultdict(list))
    finish: dict = field(default_factory=dict)        # rid -> (reason, t)
    calls: list = field(default_factory=list)
    first_call: dict = field(default_factory=dict)    # rid -> t
    migrations: list = field(default_factory=list)    # (rid, t0, t1)
    lock: threading.Lock = field(default_factory=threading.Lock)


def record_events(engine, rec: Record):
    """Timestamp every token and finish event as the engine emits it."""
    forward = engine.server.on_event

    def on_event(ev):
        t = time.perf_counter()
        if ev.kind in ("first_token", "token"):
            rec.tokens[ev.rid].append((t, ev.token))
        elif ev.kind == "finish":
            rec.finish[ev.rid] = (ev.finish_reason, t)
        forward(ev)

    engine.server.on_event = on_event


def seq_pools(caches) -> list:
    """An instance's paged sequence pools: ``kv`` and ``mla``, whichever
    exist."""
    return [c for c in (caches.kv, caches.mla) if c is not None]


def _kv_len(caches, rid) -> int:
    pools = seq_pools(caches)
    return pools[0].lengths.get(rid, 0) if pools else 0


def instrument(engine, rec: Record, jax):
    """Spans and call records for the traced run (see module docstring)."""
    server = engine.server

    for inst in server.instances:
        runner, caches = inst.runner, inst.caches

        def wrap(method, stage, shapes, _runner=runner):
            fn = getattr(_runner, method)

            def timed(*a, **kw):
                rids, items = shapes(*a, **kw)
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation(f"bench.{stage}"):
                    out = fn(*a, **kw)
                t1 = time.perf_counter()
                with rec.lock:
                    rec.calls.append(Call(stage, t0, t1, rids, items))
                    for r in rids:
                        rec.first_call.setdefault(r, t0)
                return out

            setattr(_runner, method, timed)

        def enc_shapes(items, *a, _c=caches, **kw):
            return [r for r, _ in items], [(0, m.shape[0]) for _, m in items]

        def pre_shapes(items, *a, _c=caches, **kw):
            out = []
            for rid, toks, um in items:
                n = (0 if toks is None else len(toks)) + \
                    (_c.img.lengths.get(rid, 0) if um else 0)
                out.append((_kv_len(_c, rid), n))
            return [r for r, *_ in items], out

        def dec_shapes(rids, *a, _c=caches, **kw):
            return list(rids), [(_kv_len(_c, r), 1) for r in rids]

        wrap("encode", "encode", enc_shapes)
        wrap("prefill_chunks", "prefill", pre_shapes)
        wrap("decode", "decode", dec_shapes)

    step = server.step

    def traced_step(*a, **kw):
        with jax.profiler.TraceAnnotation("bench.step"):
            return step(*a, **kw)

    server.step = traced_step

    migrate = server._migrate

    def traced_migrate(r, src):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.migrate"):
            migrate(r, src)
            jax.block_until_ready(
                [c.data for i in server.instances
                 for c in (*seq_pools(i.caches), i.caches.img)
                 if c is not None])
        with rec.lock:
            rec.migrations.append((r.rid, t0, time.perf_counter()))

    server._migrate = traced_migrate
