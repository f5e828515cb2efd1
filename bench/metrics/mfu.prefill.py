"""Model step (``models/model.py`` ``prefill_chunk_paged``): the model
FLOPs of the prefill calls in the traced window, counted on real rows only
(``bench/roofline.py``), over their device time times the chip's bf16
peak, in %."""
from bench import roofline


def read(r):
    calls, t = r.stage_device_seconds("prefill")
    if not calls or not t:
        return None
    flops = sum(roofline.prefill_flops(r.config, c.items) for c in calls)
    return 100.0 * flops / (t * r.peak["bf16_flops_per_s"])
