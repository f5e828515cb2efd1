"""Scheduler (``HydraServer.step``, ``core/batch_scheduler.py``): P90 over
requests of the time from a request's due time to the start of the first
runner call that carries it (its encode, or its first prefill chunk), in
ms.  Requests never scheduled are left out; they count in ``failed``."""
from bench.stats import quantile


def read(r):
    idx = r.rid_index()
    waits = [t - r.due(idx[rid]) for rid, t in r.rec.first_call.items()
             if rid in idx]
    return 1e3 * quantile(waits, 0.9) if waits else None
