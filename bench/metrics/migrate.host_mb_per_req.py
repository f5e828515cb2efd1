"""Migration (``paged_cache.migrate_request``): the program's counter
``migrate.host_bytes`` (bytes brought to the host to check hand-offs) over
the requests moved while the trace was on, in MB (1e6 bytes).

Read by ``bench/program.py`` ``READERS["migrate.host_mb_per_req"]``."""
from bench.program import READERS


def read(r):
    return READERS["migrate.host_mb_per_req"](r)
