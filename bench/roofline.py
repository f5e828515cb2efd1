"""Operations and bytes that the served model's work needs, counted from
shapes by the configuration's architecture (``bench/arch``), and the
chip's peaks.

Counts are of real tokens: a prefill item of n new rows after c cached
ones, a decode lane whose context holds c tokens before its new one.
Padding (batch lanes, chunk rows, pages past a context's end) is work the
model does not need, so it is not counted: a kernel that stops doing it
reads as closer to its roofline, not as doing less.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench import arch

PEAKS = json.loads((Path(__file__).with_name("peaks.json")).read_text())


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def layer_weights(config: dict) -> int:
    """Weights of one layer, for an architecture whose layers are alike."""
    return arch.load(config).layer_weights(config)


def prefill_flops(config: dict, items) -> int:
    """Model FLOPs of one prefill step over items [(ctx, n)]."""
    return arch.load(config).prefill_flops(config, items)


def decode_flops(config: dict, ctx_lens) -> int:
    """Model FLOPs of one decode step over lanes whose contexts hold
    ctx_lens tokens."""
    return arch.load(config).decode_flops(config, ctx_lens)


def prefill_attn_cost(config: dict, items, itemsize: int = 2):
    """(FLOPs, bytes) of one paged prefill-attention kernel call (one
    layer) over items [(ctx, n)]."""
    return arch.load(config).prefill_attn_cost(config, items, itemsize)


def decode_attn_cost(config: dict, ctx_lens, itemsize: int = 2):
    """(FLOPs, bytes) of one paged decode-attention kernel call (one
    layer)."""
    return arch.load(config).decode_attn_cost(config, ctx_lens, itemsize)


def bound_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])
