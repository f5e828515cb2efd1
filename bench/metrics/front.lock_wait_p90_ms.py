"""Front end (``Engine.submit``): P90 over the window's submits of the
program's span ``hydra.submit.lock_wait``, the wait for the engine lock,
which the serve loop holds through every scheduler iteration, in ms.

Read by ``bench/program.py`` ``READERS["front.lock_wait_p90_ms"]``."""
from bench.program import READERS


def read(r):
    return READERS["front.lock_wait_p90_ms"](r)
