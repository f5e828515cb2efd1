"""A run whose timed path is broken underneath must come out not
correct: each fault a serving cell can have, planted in the engine of a
rehearsal run (the harness's look for a chip is skipped)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.cell import load_cell

def token_altered(engine):
    """A served token altered where the decode step produces it."""
    vocab = engine.server.cfg.vocab_size
    for inst in engine.server.instances:
        decode = inst.runner.decode

        def altered(rids, tokens, sample=None, _decode=decode):
            out = np.array(_decode(rids, tokens, sample=sample))
            out[0] = (out[0] + 1) % vocab
            return out

        inst.runner.decode = altered


def state_unchanged(engine):
    """The prefill and decode steps hand back the KV pool as it came in:
    no key or value is ever written."""
    for inst in engine.server.instances:
        for name in ("_prefill_jit", "_paged_jit"):
            step = getattr(inst.runner, name)

            def unchanged(params, data, *a, _step=step):
                kept = jax.tree.map(jnp.copy, data)
                out, _, state = _step(params, data, *a)
                return out, kept, state

            setattr(inst.runner, name, unchanged)


def half_batch(engine):
    """Half of each prefill and decode batch left out: its lanes get the
    tokens of the other half."""
    def halve(out):
        out = np.array(out)
        half = (len(out) + 1) // 2
        out[half:] = out[:len(out) - half]
        return out

    for inst in engine.server.instances:
        decode, prefill = inst.runner.decode, inst.runner.prefill_chunks
        inst.runner.decode = lambda *a, _f=decode, **k: halve(_f(*a, **k))
        inst.runner.prefill_chunks = \
            lambda *a, _f=prefill, **k: halve(_f(*a, **k))


# arrivals close enough together that requests share batches
RATES = {"pixtral-12b.vqa-short": 8.0}


@pytest.mark.parametrize("cell_name", sorted(RATES))
@pytest.mark.parametrize("fault", [token_altered, state_unchanged,
                                   half_batch], ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(fault, cell_name):
    cell = load_cell(cell_name)
    traffic = {**cell.traffic, "arrivals": {"process": "poisson",
                                            "rate_per_s": RATES[cell_name]}}
    traffic["rehearsal"] = {**traffic["rehearsal"],
                            "arrivals": traffic["arrivals"]}
    result, numbers = run.run_cell(
        dataclasses.replace(cell, traffic=traffic), 2**31 + 21, 1.5, False,
        rehearse=True, fault=fault)
    assert not result["correct"]
    value, limit, _ = numbers["max_logit_gap"]
    assert value > limit
