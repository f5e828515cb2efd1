"""The benchmark's plain f32 reference (bench/reference.py) against the
program's dense ``forward`` (models/model.py) at the rehearsal size, and
its float8 control against the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, weights
from bench.cell import load_cell, model_config
from bench.run import prepare


@pytest.fixture(scope="module")
def setup():
    config, _, limits = prepare(load_cell("pixtral-12b.vqa-short"),
                                rehearse=True)
    params = weights.make_params(config, 2**31 + 3)
    rng = np.random.default_rng(0)
    n_img = config["image_tokens"]
    tokens = rng.integers(0, config["vocab_size"], (4, 256 - n_img),
                          dtype=np.int32)
    images = (0.1 * rng.standard_normal((4, n_img, config["hidden_size"]))
              ).astype(np.float32)
    return config, params, tokens, images, limits


def _all_logits(config, params, h, positions):
    V = config["vocab_size"]
    R = h.shape[0]
    pos = jnp.asarray(np.repeat(positions, V)[None].repeat(R, 0))
    tok = jnp.asarray(np.tile(np.arange(V), len(positions))[None].repeat(R, 0))
    out = reference._logits_of(h, pos, tok, params["final_norm"],
                               params["lm_head"], eps=config["rms_norm_eps"])
    return np.asarray(out).reshape(R, len(positions), V)


def test_reference_matches_the_programs_dense_forward(setup):
    from repro.models import model as M

    config, params, tokens, images, _ = setup
    tokens, images = tokens[:2], images[:2]
    h = reference.hidden(config, params, jnp.asarray(tokens),
                         jnp.asarray(images))
    positions = np.array([0, 127, 128, 200, 255])
    ref = _all_logits(config, params, h, positions)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = M.forward(model_config(config), p32,
                                 jnp.asarray(tokens), media=jnp.asarray(images))
    got = np.asarray(logits)[:, positions]
    span = np.abs(ref).max()
    assert span > 0.3                        # logits of a useful spread
    assert np.abs(got - ref).max() <= 1e-4 * span
    best, arg = reference._head(h, jnp.asarray(positions[None].repeat(2, 0)),
                                params["final_norm"], params["lm_head"],
                                eps=config["rms_norm_eps"], control=False)
    np.testing.assert_allclose(np.asarray(best), ref.max(-1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(arg), ref.argmax(-1))


def test_control_in_float8_fails_the_limit(setup):
    """The float8 forward's first choices lie below the f32 reference's
    best by more than the limit allows: the control that has to fail."""
    config, params, tokens, images, limits = setup
    reqs = [{"prompt": tokens[r, :60], "image": images[r],
             "served": tokens[r, 60:124]} for r in range(4)]
    ctrl = reference.logit_gaps(config, params, reqs, seq_len=256, reads=64,
                                group=4, control=True)
    assert ctrl.shape == (256,)
    assert ctrl.min() > -1e-5                # the best is the best
    assert ctrl.max() > limits["max_logit_gap"]
