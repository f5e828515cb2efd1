"""Everything the harness knows of one architecture, in one module each.

A configuration file names its language model's Hugging Face
``model_type``; ``load`` imports ``bench.arch.<model_type>``.  An
architecture module provides:

  model_config(config)          the program's ``ModelConfig``
  shapes(config)                {"top": leaf table, "layers": [leaf table
                                per layer]}; a leaf table maps a leaf name
                                of the program's parameter tree to
                                (shape, std, kind) (``bench/weights.py``)
  seq_bytes_per_token(config, itemsize)
                                one token's bytes, over all layers, in the
                                program's paged sequence pools (``kv``,
                                ``mla``)
  prefill_flops(config, items)  model FLOPs of one prefill step over
                                items [(ctx, n)]
  decode_flops(config, ctx_lens)
  prefill_attn_cost(config, items, itemsize=2)
                                (FLOPs, bytes) of one paged prefill
                                attention kernel call, one layer
  decode_attn_cost(config, ctx_lens, itemsize=2)
  Q_BLOCK                       the reference's query block: checked rows
                                are padded to a multiple of it
  logit_gaps(config, params, requests, *, seq_len, reads, group,
             control=False)     the plain f32 reference's gap of each
                                served token (``bench/reference.py``)

The reference imports nothing of the program.  A configuration of a new
architecture is a new module here and new files elsewhere; nothing that
exists is edited.
"""
from __future__ import annotations

import importlib
import pkgutil
import sys


def known() -> list:
    """The architectures this package holds, and any registered in
    ``sys.modules`` under it."""
    names = {m.name for m in pkgutil.iter_modules(__path__)}
    names |= {k.rsplit(".", 1)[1] for k in sys.modules
              if k.startswith(__name__ + ".")}
    return sorted(names)


def load(config: dict):
    """The module of the configuration's ``model_type``."""
    name = config["model_type"]
    if not name.isidentifier():
        raise KeyError(f"model_type {name!r} is not a module name")
    try:
        return importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        raise KeyError(f"no architecture {name!r} in bench/arch; known: "
                       f"{known()}") from None
