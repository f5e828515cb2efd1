"""Stage execution (``ModelRunner.prefill_chunks``): device time per
prefill call (the chunked-prefill program ``prefill_chunk_paged`` and its
sampling, inside the call's span), in ms."""


def read(r):
    calls, t = r.stage_device_seconds("prefill")
    return 1e3 * t / len(calls) if calls and t else None
