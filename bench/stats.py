"""Arithmetic of the end-to-end metrics (copied from the program's
``core/metrics.py`` ``quantile`` and ``core/request.py`` ``meets_slo``, so
that a change to the program cannot change the yardstick)."""
import math


def quantile(xs, q):
    """Nearest-rank quantile (the rank is ceil(q * n))."""
    xs = sorted(xs)
    i = min(len(xs) - 1, max(0, int(math.ceil(q * len(xs))) - 1))
    return xs[i]


def meets(ttft: float, gaps, lim: dict) -> bool:
    """TTFT within its limit and at least 90% of token gaps within theirs
    (HydraInfer paper, section 2.3)."""
    if ttft > lim["ttft_ms"] / 1e3:
        return False
    if not gaps:
        return True
    ok = sum(1 for g in gaps if g <= lim["tpot_ms"] / 1e3)
    return ok >= 0.9 * len(gaps)


