"""Device (one TPU v5e): share of the measured window in which no
operation ran on the chip, in %."""
from bench import trace


def read(r):
    busy = trace.busy_seconds(r.events, r.lo, r.hi)
    window = (r.hi - r.lo) / 1e9
    return 100.0 * (1.0 - busy / window) if window > 0 and busy > 0 else None
