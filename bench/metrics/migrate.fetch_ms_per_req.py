"""Migration (``paged_cache.migrate_request``): the program's
``hydra.migrate.fetch`` spans in the window (the one fetch of a hand-off's
digests on device pools), per request moved in the window, in ms.

Read by ``bench/program.py`` ``READERS["migrate.fetch_ms_per_req"]``."""
from bench.program import READERS


def read(r):
    return READERS["migrate.fetch_ms_per_req"](r)
