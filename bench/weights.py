"""Random weights from the seed, made on the device in one jitted call, in
the layout the program serves (``repro.models.model`` parameter tree) and
in the type it serves them in.

The configuration's architecture (``bench/arch``) gives the leaf tables:
name -> (shape, std, kind), for the top level and for each layer, so that
layers may differ.  A leaf is N(0, std^2); kind "w" is made in bfloat16
(matrices), any other kind in float32 (norm offsets, routers).

The benchmark makes these weights; the reference reads the same tree, and
imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import arch


def shapes(config: dict) -> dict:
    """{"top": leaf table, "layers": [leaf table per layer]}."""
    return arch.load(config).shapes(config)


def _leaf(key, shape, std, kind):
    dtype = jnp.bfloat16 if kind == "w" else jnp.float32
    return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)


@functools.lru_cache(maxsize=None)
def _maker(frozen: tuple):
    """One jitted maker per set of shapes (``frozen`` is hashable).  Leaf
    i of the top level takes ``fold_in(key, i)``; leaf j of layer li takes
    ``fold_in(fold_in(key, 1000 + li), j)``, leaves in name order."""
    layers, top = frozen

    def make(key):
        out = {}
        for i, (name, s) in enumerate(top):
            out[name] = _leaf(jax.random.fold_in(key, i), *s)
        out["layers"] = []
        for li, layer in enumerate(layers):
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
    frozen = (tuple(tuple(sorted(layer.items())) for layer in spec["layers"]),
              tuple(sorted(spec["top"].items())))
    return _maker(frozen)(seed_key(seed))
