"""Migration (``paged_cache.migrate_request``): the program's
``hydra.migrate.hash`` spans in the window (the dispatch of each on-device
digest), per request moved in the window, in ms.

Read by ``bench/program.py`` ``READERS["migrate.hash_ms_per_req"]``."""
from bench.program import READERS


def read(r):
    return READERS["migrate.hash_ms_per_req"](r)
