"""Kernels (``kernels/paged_attention`` decode, the Pallas call
``paged_attention``): the least time the chip could take for the traced
window's decode attention (per call and layer, the larger of FLOPs over
peak and bytes over bandwidth, real context rows only:
``bench/roofline.py``), over the kernel's device time, in %."""
from bench import roofline

KERNEL = "paged_attention"


def read(r):
    pairs = r.traced_calls("decode")
    t = r.kernel_seconds("decode", KERNEL)
    if not pairs or not t:
        return None
    L = r.config["num_hidden_layers"]
    bound = sum(roofline.bound_seconds(
        *roofline.decode_attn_cost(r.config, [c for c, _ in call.items]),
        r.peak) for call, _ in pairs) * L
    return 100.0 * bound / t
