"""Device (one TPU v5e): share of the traced window in which no operation
ran on the chip and the engine's thread was not in the program's span
``hydra.loop.idle`` (the serve loop finding no work), in %: the idle time
a change to the engine can remove.

Read by ``bench/program.py`` ``READERS["device.idle_with_work_share"]``."""
from bench.program import READERS


def read(r):
    return READERS["device.idle_with_work_share"](r)
