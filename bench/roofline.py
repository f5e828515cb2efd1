"""Operations and bytes that the served model's work needs, counted from
shapes, and the chip's peaks.

Counts are of real tokens: a prefill item of n new rows after c cached
ones, a decode lane whose context holds c tokens before its new one.
Padding (batch lanes, chunk rows, pages past a context's end) is work the
model does not need, so it is not counted: a kernel that stops doing it
reads as closer to its roofline, not as doing less.

Model FLOPs of one layer for one new token at position p (0-based):
2 * (weights of the layer's projections and MLP) + 4 * H * Dh * (p + 1)
for the scores and the weighted sum over the p + 1 visible keys.  The
output head adds 2 * d * V for each row whose logits are taken: the last
row of each prefill item, and each decode lane.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = json.loads((Path(__file__).with_name("peaks.json")).read_text())


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def layer_weights(config: dict) -> int:
    d, F = config["hidden_size"], config["intermediate_size"]
    H, Kh = config["num_attention_heads"], config["num_key_value_heads"]
    Dh = config["head_dim"]
    return d * H * Dh * 2 + d * Kh * Dh * 2 + 3 * d * F


def _attn_pairs(ctx: int, n: int) -> int:
    """Sum over n new rows at positions ctx..ctx+n-1 of their visible
    keys (p + 1)."""
    return n * ctx + n * (n + 1) // 2


def prefill_flops(config: dict, items) -> int:
    """One prefill step over items [(ctx, n)]."""
    L = config["num_hidden_layers"]
    H, Dh = config["num_attention_heads"], config["head_dim"]
    head = 2 * config["hidden_size"] * config["vocab_size"]
    per_row = 2 * layer_weights(config)
    total = 0
    for ctx, n in items:
        total += L * (n * per_row + 4 * H * Dh * _attn_pairs(ctx, n)) + head
    return total


def decode_flops(config: dict, ctx_lens) -> int:
    """One decode step over lanes whose contexts hold ctx_lens tokens."""
    return prefill_flops(config, [(c, 1) for c in ctx_lens])


def _kv_row_bytes(config: dict, itemsize: int) -> int:
    return 2 * config["num_key_value_heads"] * config["head_dim"] * itemsize


def prefill_attn_cost(config: dict, items, itemsize: int = 2):
    """(FLOPs, bytes) of one paged prefill-attention kernel call (one
    layer) over items [(ctx, n)]: keys and values of ctx + n rows read
    once, queries read and outputs written once."""
    H, Dh = config["num_attention_heads"], config["head_dim"]
    flops = bytes_ = 0
    for ctx, n in items:
        flops += 4 * H * Dh * _attn_pairs(ctx, n)
        bytes_ += (ctx + n) * _kv_row_bytes(config, itemsize) \
            + 2 * n * H * Dh * itemsize
    return flops, bytes_


def decode_attn_cost(config: dict, ctx_lens, itemsize: int = 2):
    """(FLOPs, bytes) of one paged decode-attention kernel call (one
    layer); each lane attends its ctx + 1 rows."""
    return prefill_attn_cost(config, [(c, 1) for c in ctx_lens], itemsize)


def bound_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               bytes_ / peak["hbm_bytes_per_s"])
