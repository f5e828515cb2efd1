"""Kernels (``kernels/paged_attention`` chunked prefill, the Pallas call
``paged_prefill_attention``): the least time the chip could take for the
traced window's prefill attention (per call and layer, the larger of
FLOPs over peak and bytes over bandwidth, counted on real rows:
``bench/roofline.py``), over the kernel's device time, in %."""
from bench import roofline

KERNEL = "paged_prefill_attention"


def read(r):
    pairs = r.traced_calls("prefill")
    t = r.kernel_seconds("prefill", KERNEL)
    if not pairs or not t:
        return None
    L = r.config["num_hidden_layers"]
    bound = sum(roofline.bound_seconds(
        *roofline.prefill_attn_cost(r.config, c.items), r.peak)
        for c, _ in pairs) * L
    return 100.0 * bound / t
