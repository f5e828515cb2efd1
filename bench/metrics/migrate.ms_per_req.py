"""Migration (``HydraServer._migrate`` -> ``paged_cache.migrate_request``):
host time spent handing requests between instances (E->P and P->D, each
ended by ``block_until_ready`` on the pools), per request that moved, in
ms."""


def read(r):
    moves = [(rid, t1 - t0) for rid, t0, t1 in r.rec.migrations
             if r.t_open <= t0 < r.t_close]
    if not moves:
        return None
    return 1e3 * sum(d for _, d in moves) / len({rid for rid, _ in moves})
