"""Scheduler (``HydraServer.step``, ``core/batch_scheduler.py``): P90 over
the served requests of their first ``<stage>_queue`` wait in the program's
``Request.stage_log`` (enqueued on an instance to the first batch that
carries it), in ms.

Read by ``bench/program.py`` ``READERS["sched.engine_queue_p90_ms"]``."""
from bench.program import READERS


def read(r):
    return READERS["sched.engine_queue_p90_ms"](r)
