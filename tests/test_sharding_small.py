"""Distribution smoke tests on the real local device(s): the same model code
must produce identical results with and without sharding constraints, and
the dry-run builder must work on a host-size mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.models import model as M
from repro.models import sharding as SH

from conftest import reduced_cfg


@pytest.mark.parametrize("arch", ["llama3-8b", "granite-moe-1b-a400m",
                                  "falcon-mamba-7b"])
def test_constrained_forward_matches_unconstrained(arch):
    cfg = reduced_cfg(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab_size)
    ref, _, _ = M.forward(cfg, params, tokens)
    mesh = make_host_mesh()
    with SH.use_mesh(mesh):
        out = jax.jit(lambda p, t: M.forward(cfg, p, t)[0])(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_param_shardings_cover_tree():
    cfg = reduced_cfg("deepseek-v2-236b")
    pspec = M.param_specs(cfg, jnp.bfloat16)
    mesh = make_host_mesh()
    sh = SH.param_shardings(mesh, pspec)
    n_leaves = len(jax.tree.leaves(pspec))
    n_shard = len(jax.tree.leaves(sh, is_leaf=lambda x: hasattr(x, "spec")))
    assert n_leaves == n_shard


def test_fsdp_shards_more():
    """FSDP must strictly reduce (or keep) per-device parameter bytes."""
    cfg = get_config("llama3-8b")
    pspec = M.param_specs(cfg, jnp.bfloat16)
    mesh = make_mesh((1, 1), ("data", "model"))

    def per_device_bytes(shardings):
        total = 0
        for leaf, s in zip(jax.tree.leaves(pspec),
                           jax.tree.leaves(shardings,
                                           is_leaf=lambda x: hasattr(x, "spec"))):
            shard = 1
            for name in jax.tree.leaves(tuple(s.spec)):
                if name:
                    shard *= mesh.shape[name]
            total += leaf.size * leaf.dtype.itemsize // max(shard, 1)
        return total

    base = per_device_bytes(SH.param_shardings(mesh, pspec))
    fsdp = per_device_bytes(SH.param_shardings(mesh, pspec, fsdp=True))
    assert fsdp <= base


def test_dryrun_collective_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %x), dimensions={0}
  %ar = f32[64]{0} all-reduce(f32[64]{0} %y), to_apply=%sum
  %aa = bf16[4,32]{1,0} all-to-all(bf16[4,32]{1,0} %z), dimensions={0}
  %cp = u32[2]{0} collective-permute(u32[2]{0} %w)
  %dot = f32[8,8]{1,0} dot(f32[8,4] %a, f32[4,8] %b)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 64 * 4
    assert out["all-to-all"] == 4 * 32 * 2
    assert out["collective-permute"] == 2 * 4
    assert out["total"] == sum(out[k] for k in
                               ("all-gather", "all-reduce", "reduce-scatter",
                                "all-to-all", "collective-permute"))
