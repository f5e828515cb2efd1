"""The one traffic generator: turns a traffic file into the requests of a
window.

Every seed gets the same arrivals and the same set of sizes, in another
order.  Gaps are the quantiles of an exponential (Poisson arrivals) at
(k + 0.5)/n, in one fixed order (a permutation drawn from a constant
seed), scaled so that the n arrivals fill the window: every seed offers
the same arrival schedule.  Lengths are the quantiles of the file's
lognormal at (k + 0.5)/n, rounded and clipped to the file's range.  The
seed permutes lengths and the greedy/sampled split over the arrivals, and
draws the prompt tokens and the images.  So two seeds ask for the same
work at the same moments, and differ only in which request gets which
size: with some tens of requests in a window, a seed that also moved the
arrivals would move the tails by more than a change to the program can.

A traffic file (``bench/traffic/<name>.json``) holds::

  images_per_request   0 or 1
  prompt_tokens        {"median", "sigma", "min", "max"}  lognormal
  output_tokens        {"median", "sigma", "min", "max"}  lognormal, forced
  arrivals             {"process": "poisson", "rate_per_s"}
  greedy_share         share of requests decoded greedily (the rest sample)
  sampling             {"temperature", "top_p"} of the sampled requests
  limits               {"ttft_ms", "tpot_ms"} a request must meet
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Optional

import numpy as np

ARRIVAL_ORDER_SEED = 0     # the one order of the gaps, for every seed


@dataclass
class Request:
    index: int
    due: float                    # seconds after the window opens
    prompt: np.ndarray            # int32 token ids
    image: Optional[np.ndarray]   # [image_tokens, d_model] bf16, or None
    max_tokens: int
    greedy: bool
    sample_seed: int


def lognormal_set(dist: dict, n: int) -> np.ndarray:
    """n lengths: the lognormal's quantiles at (k + 0.5)/n, clipped."""
    nd = NormalDist(math.log(dist["median"]), dist["sigma"])
    q = np.array([math.exp(nd.inv_cdf((k + 0.5) / n)) for k in range(n)])
    return np.clip(np.rint(q), dist["min"], dist["max"]).astype(np.int64)


def gap_set(arrivals: dict, n: int) -> np.ndarray:
    """n inter-arrival gaps, unscaled, of the file's arrival process."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return np.array([-math.log(1.0 - (k + 0.5) / n) for k in range(n)])


def n_requests(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["arrivals"]["rate_per_s"] * seconds)))


def size_set(traffic: dict, n: int):
    """(prompt lengths, output lengths) of a window of n requests, sorted."""
    return (lognormal_set(traffic["prompt_tokens"], n),
            lognormal_set(traffic["output_tokens"], n))


def make_window(traffic: dict, seed: int, seconds: float, *, vocab: int,
                image_tokens: int, d_model: int) -> list[Request]:
    """The requests due in a window of ``seconds``, ordered by due time."""
    import ml_dtypes

    n = n_requests(traffic, seconds)
    gaps = np.random.default_rng(ARRIVAL_ORDER_SEED).permutation(
        gap_set(traffic["arrivals"], n))
    rng = np.random.default_rng(seed % 2**64)
    prompts, outputs = size_set(traffic, n)
    prompts, outputs = rng.permutation(prompts), rng.permutation(outputs)
    due = seconds * np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum()
    n_greedy = int(round(traffic["greedy_share"] * n))
    greedy = rng.permutation(np.arange(n) < n_greedy)
    images = None
    if traffic["images_per_request"]:
        # one buffer of rows; request k's image is rows [k, k + image_tokens)
        images = (0.1 * rng.standard_normal((image_tokens + n, d_model),
                                            np.float32)
                  ).astype(ml_dtypes.bfloat16)
    out = []
    for k in range(n):
        out.append(Request(
            index=k, due=float(due[k]),
            prompt=rng.integers(0, vocab, int(prompts[k])).astype(np.int32),
            image=None if images is None else images[k:k + image_tokens],
            max_tokens=int(outputs[k]), greedy=bool(greedy[k]),
            sample_seed=int(rng.integers(0, 2**31))))
    return out
