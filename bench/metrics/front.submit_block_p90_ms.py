"""Front end (``Engine.submit``): P90 over requests of the time from a
request's due time to the return of its ``submit``, in ms.  ``submit``
takes the engine's lock, which the serve loop holds through every
scheduler iteration, so this is how long arrivals wait to get in."""
from bench.stats import quantile


def read(r):
    waits = [s[1] - r.due(k) for k, s in enumerate(r.submitted)
             if s is not None]
    return 1e3 * quantile(waits, 0.9) if waits else None
