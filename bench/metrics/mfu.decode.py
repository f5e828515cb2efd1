"""Model step (``models/model.py`` ``decode_step_paged``): the model FLOPs
of the decode calls in the traced window, real lanes only
(``bench/roofline.py``), over their device time times the chip's bf16
peak, in %."""
from bench import roofline


def read(r):
    calls, t = r.stage_device_seconds("decode")
    if not calls or not t:
        return None
    flops = sum(roofline.decode_flops(r.config, [c for c, _ in call.items])
                for call in calls)
    return 100.0 * flops / (t * r.peak["bf16_flops_per_s"])
