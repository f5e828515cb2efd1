"""Mistral-family decoder (``model_type`` "mistral"): dense GQA attention
and a SwiGLU MLP in every layer, RMS norms, rotate-half rope, untied output
head; a LLaVA-style projector (d -> 2d -> d, GELU-tanh) in the encode
stage.  The plain reference is ``bench/reference.py``.

Weights, in the program's layout (``repro.models.model`` parameter tree):
the norm weights are offsets (the program scales by ``1 + w``), drawn
small and nonzero, N(0, 0.1^2), so that the reference is checked on that
path too.  Matrices are N(0, 1/fan_in); the embedding is N(0, 1) and the
output head N(0, 1/d), which gives logits a spread of about 1.

Model FLOPs of one layer for one new token at position p (0-based):
2 * (weights of the layer's projections and MLP) + 4 * H * Dh * (p + 1)
for the scores and the weighted sum over the p + 1 visible keys.  The
output head adds 2 * d * V for each row whose logits are taken: the last
row of each prefill item, and each decode lane.
"""
from __future__ import annotations

import math

from bench.reference import Q_BLOCK, logit_gaps  # noqa: F401


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=config["name"], family="vlm",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"],
        act=config["hidden_act"],
        norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"],
        frontend="vision",
        media_tokens=config["image_tokens"],
        source=config["source"])


def shapes(config: dict) -> dict:
    """Leaf tables (name -> (shape, std, kind)) of the top level and of
    each layer; every layer has the same leaves."""
    d, V = config["hidden_size"], config["vocab_size"]
    H, Kh = config["num_attention_heads"], config["num_key_value_heads"]
    Dh, F = config["head_dim"], config["intermediate_size"]
    top = {"embed": ((V, d), 1.0, "w"), "final_norm": ((d,), 0.1, "n"),
           "lm_head": ((d, V), 1 / math.sqrt(d), "w"),
           # the encode stage's projector: d -> 2d -> d, GELU (tanh)
           "media_proj_w1": ((d, 2 * d), 1 / math.sqrt(d), "w"),
           "media_proj_w2": ((2 * d, d), 1 / math.sqrt(2 * d), "w")}
    layer = {"norm1": ((d,), 0.1, "n"), "norm2": ((d,), 0.1, "n"),
             "wq": ((d, H * Dh), 1 / math.sqrt(d), "w"),
             "wk": ((d, Kh * Dh), 1 / math.sqrt(d), "w"),
             "wv": ((d, Kh * Dh), 1 / math.sqrt(d), "w"),
             "wo": ((H * Dh, d), 1 / math.sqrt(H * Dh), "w"),
             "w_gate": ((d, F), 1 / math.sqrt(d), "w"),
             "w_up": ((d, F), 1 / math.sqrt(d), "w"),
             "w_down": ((F, d), 1 / math.sqrt(F), "w")}
    return {"top": top, "layers": [layer] * config["num_hidden_layers"]}


def _kv_row_bytes(config: dict, itemsize: int) -> int:
    """One token's key and value in one layer."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * itemsize


def seq_bytes_per_token(config: dict, itemsize: int) -> int:
    """The ``kv`` pool holds every layer's key and value."""
    return config["num_hidden_layers"] * _kv_row_bytes(config, itemsize)


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
