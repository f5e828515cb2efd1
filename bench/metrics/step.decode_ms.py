"""Stage execution (``ModelRunner.decode``): device time per decode call
(the work of the decode program ``decode_step_paged`` and its sampling,
inside the call's span), in ms."""


def read(r):
    calls, t = r.stage_device_seconds("decode")
    return 1e3 * t / len(calls) if calls and t else None
