"""Random weights from the seed, made on the device in one jitted call, in
the layout the program serves (``repro.models.model`` parameter tree) and
in the type it serves them in (bf16 matrices, f32 norm offsets).

The norm weights are offsets: the program scales by ``1 + w``.  They are
drawn small and nonzero, N(0, 0.1^2), so that the reference is checked on
that path too.  Matrices are N(0, 1/fan_in); the embedding is N(0, 1) and
the output head N(0, 1/d), which gives logits a spread of about 1.

The benchmark makes these weights; the reference (``bench/reference.py``)
reads the same tree, and imports nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def shapes(config: dict) -> dict:
    """Leaf name -> (shape, std, dtype name) in the program's layout."""
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
    return {"top": top, "layer": layer,
            "layers": config["num_hidden_layers"]}


def _leaf(key, shape, std, kind):
    dtype = jnp.bfloat16 if kind == "w" else jnp.float32
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


@functools.lru_cache(maxsize=None)
def _maker(frozen: tuple):
    """One jitted maker per set of shapes (``frozen`` is hashable)."""
    n_layers, layer, top = frozen

    def make(key):
        out = {}
        for i, (name, s) in enumerate(top):
            out[name] = _leaf(jax.random.fold_in(key, i), *s)
        out["layers"] = []
        for li in range(n_layers):
            lk = jax.random.fold_in(key, 1000 + li)
            out["layers"].append({
                name: _leaf(jax.random.fold_in(lk, j), *s)
                for j, (name, s) in enumerate(layer)})
        return out

    return jax.jit(make)


def seed_key(seed: int):
    """A PRNG key from all 64 bits of ``seed`` (``PRNGKey`` alone keeps
    only the low 32 without x64)."""
    seed %= 2**64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_params(config: dict, seed: int):
    """The whole parameter tree on the default device, from ``seed``."""
    spec = shapes(config)
    frozen = (spec["layers"], tuple(sorted(spec["layer"].items())),
              tuple(sorted(spec["top"].items())))
    return _maker(frozen)(seed_key(seed))
