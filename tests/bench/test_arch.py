"""The harness's dispatch on the architecture (``bench/arch``) and its
reading of the program's own spans and counters.

(a) Pixtral goes through the dispatch to what the dense harness built
    before it: the same ``ModelConfig`` and bit-identical weights.
(b) A second architecture, supplied as a module only (registered in
    ``sys.modules`` by the test, nothing under ``bench/`` edited): the
    program's MLA + MoE layers at deepseek-v2-236b's reduced sizes behind
    an image, rehearsed end to end through ``run.run_cell``.
(c) A traced Pixtral rehearsal reads the program's spans and counters
    through ``bench/metrics/<name>.py`` as ``bench/program.py`` does.

On the CPU the profiler records no device, so the traced rehearsals are
given one device op inside each runner call's span (``_device_ops``):
enough for the device readers to find something to read, not a time.
"""
import dataclasses
import json
import math
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, program, readings, roofline, run, weights
from bench.cell import Cell, load_cell, model_config

CELL = "pixtral-12b.vqa-short"


# ---------------------------------------------------------------------------
# (a) Pixtral through the dispatch
# ---------------------------------------------------------------------------
def _rehearsal_config():
    config, _, _ = run.prepare(load_cell(CELL), rehearse=True)
    return config


def test_pixtral_model_config_is_the_dense_one_field_by_field():
    from repro.configs.base import ModelConfig

    config = _rehearsal_config()
    before = ModelConfig(
        name=config["name"], family="vlm",
        num_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        vocab_size=config["vocab_size"], act=config["hidden_act"],
        norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        tie_embeddings=config["tie_word_embeddings"], frontend="vision",
        media_tokens=config["image_tokens"], source=config["source"])
    assert dataclasses.asdict(model_config(config)) == \
        dataclasses.asdict(before)


def _dense_params_before(config, seed):
    """The dense harness's weights as it made them before the dispatch:
    one leaf table for every layer, leaves in name order, top-level leaf
    i from ``fold_in(key, i)``, layer li's from ``fold_in(key, 1000 + li)``.
    """
    d, V = config["hidden_size"], config["vocab_size"]
    H, Kh = config["num_attention_heads"], config["num_key_value_heads"]
    Dh, F = config["head_dim"], config["intermediate_size"]
    top = {"embed": ((V, d), 1.0, "w"), "final_norm": ((d,), 0.1, "n"),
           "lm_head": ((d, V), 1 / math.sqrt(d), "w"),
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

    def leaf(key, shape, std, kind):
        dtype = jnp.bfloat16 if kind == "w" else jnp.float32
        return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)

    def make(key):
        out = {name: leaf(jax.random.fold_in(key, i), *s)
               for i, (name, s) in enumerate(sorted(top.items()))}
        out["layers"] = []
        for li in range(config["num_hidden_layers"]):
            lk = jax.random.fold_in(key, 1000 + li)
            out["layers"].append({
                name: leaf(jax.random.fold_in(lk, j), *s)
                for j, (name, s) in enumerate(sorted(layer.items()))})
        return out

    return jax.jit(make)(weights.seed_key(seed))


def test_pixtral_weights_are_bit_identical_to_the_dense_harness():
    config = _rehearsal_config()
    seed = 2**31 + 1234
    got = jax.tree_util.tree_flatten_with_path(
        weights.make_params(config, seed))[0]
    want = jax.tree_util.tree_flatten_with_path(
        _dense_params_before(config, seed))[0]
    assert [p for p, _ in got] == [p for p, _ in want]
    for (path, a), (_, b) in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        assert np.array_equal(np.asarray(a), np.asarray(b)), path


def test_unknown_model_type_raises_and_names_the_known_ones():
    with pytest.raises(KeyError, match=r"no architecture 'nonesuch'.*"
                                       r"mistral"):
        arch.load({"model_type": "nonesuch"})
    with pytest.raises(KeyError, match="not a module name"):
        arch.load({"model_type": "../mistral"})


# ---------------------------------------------------------------------------
# traced rehearsals: device ops where the CPU records none
# ---------------------------------------------------------------------------
KERNELS = {"bench.prefill": "paged_prefill_attention",
           "bench.decode": "paged_attention", "bench.encode": "fusion"}


def _device_ops(monkeypatch):
    """Give each traced window one op in the middle half of every runner
    call's span, named after the stage's attention kernel, and the v5e's
    peaks to the CPU's device kind."""
    events = run.Tracer.events

    def with_ops(self):
        ev = events(self)
        ops = [(f"%{KERNELS[n]}.1", s + d // 4, d // 2)
               for n, s, d in ev.spans if n in KERNELS]
        ev.ops = {"/device:TPU:0": sorted(ops, key=lambda o: o[1])}
        return ev

    monkeypatch.setattr(run.Tracer, "events", with_ops)
    monkeypatch.setitem(roofline.PEAKS, jax.devices()[0].device_kind,
                        roofline.PEAKS["TPU v5 lite"])


def _kept_readings(monkeypatch) -> list:
    """The ``Readings`` that the harness hands its readers."""
    kept = []
    load = readings.load_reader

    def keeping(name):
        read = load(name)

        def reader(r):
            if not kept or kept[-1] is not r:
                kept.append(r)
            return read(r)

        return reader

    monkeypatch.setattr(readings, "load_reader", keeping)
    return kept


# ---------------------------------------------------------------------------
# (b) a second architecture, supplied as a module only
# ---------------------------------------------------------------------------
MODEL_TYPE = "mla_moe_rehearsal"
SEED = 2**31 + 41


def _mla_moe_cfg(config):
    """The program's MLA + MoE layers (layer 0 dense, then experts) at
    deepseek-v2-236b's reduced sizes, behind an image."""
    from repro.configs.deepseek_v2_236b import CONFIG

    return dataclasses.replace(CONFIG.reduced(), frontend="vision",
                               media_tokens=config["image_tokens"])


def _mla_moe_module():
    """The architecture module, written as a later configuration would
    write ``bench/arch/<model_type>.py``.  Its reference is the program's
    dense ``forward`` in f32 with no token dropped at the experts (a
    test-only stand-in for a plain reference)."""
    from bench import reference
    from repro.configs.base import MLA_MOE
    from repro.models import model as M

    mod = types.ModuleType(f"bench.arch.{MODEL_TYPE}")
    mod.Q_BLOCK = 16
    mod.model_config = _mla_moe_cfg

    def shapes(config):
        cfg = _mla_moe_cfg(config)
        specs = M.param_specs(cfg, jnp.bfloat16)

        def table(tree):
            out = {}
            for name, s in tree.items():
                if s.dtype == jnp.float32 and s.ndim == 1:       # norms
                    out[name] = (s.shape, 0.1, "n")
                else:                                    # matrices, router
                    std = 1.0 if name == "embed" else \
                        1 / math.sqrt(s.shape[-2])
                    out[name] = (s.shape, std,
                                 "w" if s.dtype == jnp.bfloat16 else "n")
            return out

        return {"top": table({k: v for k, v in specs.items()
                              if k != "layers"}),
                "layers": [table(layer) for layer in specs["layers"]]}

    def seq_bytes_per_token(config, itemsize):
        cfg = _mla_moe_cfg(config)
        return cfg.num_layers * cfg.kv_dim * itemsize

    def _row_flops(cfg, kind):
        """2 x the weights one token passes through in a layer, absorbed
        attention (the latent's up-projections applied to q and out)."""
        d, H, R = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        attn = d * H * (nope + rope) + d * (R + rope) + H * nope * R \
            + H * R * vd + H * vd * d
        if kind == MLA_MOE:
            ff = d * cfg.num_experts + 3 * d * cfg.moe_d_ff * (
                cfg.experts_per_token + cfg.num_shared_experts)
        else:
            ff = 3 * d * cfg.d_ff
        return 2 * (attn + ff)

    def _pairs(ctx, n):
        return n * ctx + n * (n + 1) // 2

    def prefill_flops(config, items):
        cfg = _mla_moe_cfg(config)
        per_row = sum(_row_flops(cfg, k) for k in cfg.layer_kinds())
        attn = 4 * cfg.num_heads * cfg.kv_dim * cfg.num_layers
        head = 2 * cfg.d_model * cfg.vocab_size
        return sum(n * per_row + attn * _pairs(ctx, n) + head
                   for ctx, n in items)

    def prefill_attn_cost(config, items, itemsize=2):
        cfg = _mla_moe_cfg(config)
        H, W = cfg.num_heads, cfg.kv_dim
        return (sum(4 * H * W * _pairs(c, n) for c, n in items),
                sum(((c + n) * W + 2 * n * H * W) * itemsize
                    for c, n in items))

    def logit_gaps(config, params, requests, *, seq_len, reads, group):
        base = _mla_moe_cfg(config)
        cfg = dataclasses.replace(base,
                                  moe_capacity_factor=float(base.num_experts))
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        tokens, images, pos, tok, valid = reference.batch(
            requests, seq_len=seq_len, reads=reads, group=group)
        with jax.default_matmul_precision("highest"):
            logits, _, _ = M.forward(cfg, p32, jnp.asarray(tokens),
                                     media=jnp.asarray(images))
        lg = np.take_along_axis(np.asarray(logits), pos[..., None], 1)
        got = np.take_along_axis(lg, tok[..., None], 2)[..., 0]
        return (lg.max(-1) - got)[valid]

    mod.shapes = shapes
    mod.seq_bytes_per_token = seq_bytes_per_token
    mod.prefill_flops = prefill_flops
    mod.decode_flops = lambda config, ctx: prefill_flops(
        config, [(c, 1) for c in ctx])
    mod.prefill_attn_cost = prefill_attn_cost
    mod.decode_attn_cost = lambda config, ctx, itemsize=2: \
        prefill_attn_cost(config, [(c, 1) for c in ctx], itemsize)
    mod.logit_gaps = logit_gaps
    return mod


def _mla_moe_cell() -> Cell:
    """A cell of that architecture under Pixtral's vqa-short traffic,
    with its pools, budgets and checks."""
    pix = load_cell(CELL)
    # an image of more rows than the token budget, as in every served
    # configuration: the warm-up runs the image chunk alone in its bucket
    cfg = _mla_moe_cfg({"image_tokens": 128})
    sizes = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.num_layers,
             "vocab_size": cfg.vocab_size, "image_tokens": 128}
    config = {"name": "mla-moe-rehearsal", "model_type": MODEL_TYPE, **sizes,
              **{k: pix.config[k] for k in ("pools", "check", "budgets",
                                            "disagg")},
              "rehearsal": {**pix.config["rehearsal"], "sizes": sizes}}
    return Cell(name="mla-moe-rehearsal.vqa-short", chips=1, config=config,
                traffic=pix.traffic, end_to_end=pix.end_to_end,
                per_layer=pix.per_layer)


def _count_mla_imports(moved: list):
    """A ``fault`` hook that breaks nothing: it records each import into an
    instance's ``mla`` pool (rid, rows) and checks there is no ``kv``
    pool."""
    def hook(engine):
        for inst in engine.server.instances:
            caches = inst.caches
            assert caches.kv is None and caches.mla is not None
            imp = caches.mla.import_blocks

            def counted(rid, length, payload, _imp=imp):
                moved.append((rid, length))
                return _imp(rid, length, payload)

            caches.mla.import_blocks = counted

    return hook


@pytest.fixture(scope="module")
def mla_moe_run():
    """One traced rehearsal of the second architecture: (result, numbers
    compared, imports into the mla pools, the readers' ``Readings``)."""
    moved = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, f"bench.arch.{MODEL_TYPE}",
                   _mla_moe_module())
        assert MODEL_TYPE in arch.known()
        _device_ops(mp)
        kept = _kept_readings(mp)
        result, numbers = run.run_cell(_mla_moe_cell(), SEED, 3.0, True,
                                       rehearse=True,
                                       fault=_count_mla_imports(moved))
    return result, numbers, moved, kept[-1]


def test_second_architecture_rehearses_correct_through_the_mla_pool(
        mla_moe_run):
    result, numbers, moved, _ = mla_moe_run
    assert result["correct"], numbers
    assert result["compiles_in_window"] == 0
    assert result["attempted"] >= 2 and result["failed"] == 0
    # every request served crossed P->D with its latent rows
    served = {rid for rid, rows in moved if rid >= 0 and rows > 0}
    assert len(served) == result["attempted"]


def test_second_architecture_traced_reads_its_flops_and_rooflines(
        mla_moe_run):
    result, _, _, r = mla_moe_run
    got = result["metrics"]
    for name in ("mfu.prefill", "mfu.decode", "paged_prefill_attn_roofline",
                 "paged_decode_attn_roofline"):
        assert got[name]["value"] > 0, name
    # contexts are read from the latent pool: every decode lane attends
    # its image and prompt
    n_img = r.config["image_tokens"]
    lanes = [it for c in r.rec.calls if c.stage == "decode" for it in c.items]
    assert lanes and all(ctx > n_img for ctx, _ in lanes)


def test_second_architecture_reference_fails_altered_tokens(
        mla_moe_run, monkeypatch):
    """The served tokens pass the reference, and the same tokens each moved
    to the next id fail it."""
    result, numbers, _, r = mla_moe_run
    monkeypatch.setitem(sys.modules, f"bench.arch.{MODEL_TYPE}",
                        _mla_moe_module())
    config, traffic = r.config, load_cell(CELL).traffic
    params = weights.make_params(config, SEED)
    done = [k for k, s in enumerate(r.submitted) if s and s[0] is not None]
    sample = run.check_sample(config, r.reqs, r.submitted, r.rec, done, np)
    shape = run.reference_shape(config, traffic)
    limits = {**config["check"], **traffic["check"],
              **config["rehearsal"]["check"]}
    mod = arch.load(config)
    ok, got = run.judge(config, limits, shape, params, sample, np, mod)
    assert ok and got["max_logit_gap"] == numbers["max_logit_gap"]
    for s in sample:
        s["served"] = (s["served"] + 1) % config["vocab_size"]
    ok, got = run.judge(config, limits, shape, params, sample, np, mod)
    assert not ok and got["max_logit_gap"][0] > limits["max_logit_gap"]


# ---------------------------------------------------------------------------
# (c) the program's readings in a traced Pixtral rehearsal
# ---------------------------------------------------------------------------
def test_traced_rehearsal_reads_the_program_as_bench_program_does(
        monkeypatch):
    _device_ops(monkeypatch)
    kept = _kept_readings(monkeypatch)
    cell = load_cell(CELL)
    result, numbers = run.run_cell(cell, 2**31 + 47, 3.0, True,
                                   rehearse=True)
    assert result["correct"], numbers
    r = kept[-1]
    assert r.program["spans"] and r.program_spans
    for name, read in program.READERS.items():
        assert result["metrics"][name]["value"] == read(r), name
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    ours = [m for m in spec["per_layer"] if m["name"] in program.READERS]
    assert {m["name"] for m in ours} == set(program.READERS)
    assert all(m["moves"] == "ttft_p90_ms" and m["workloads"] == [CELL]
               for m in ours)
    # the harness's ten readers read beside them
    assert set(result["metrics"]) == {m["name"] for m in cell.per_layer}
