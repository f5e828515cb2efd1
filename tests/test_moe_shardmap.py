"""shard_map expert-parallel MoE dispatch == dense MoE (fwd + grad + aux).

Runs in a subprocess with 8 forced host devices (the main test process must
keep the single real device — see conftest)."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models import model as M, moe, sharding as SH
    from repro.train.train import loss_fn

    cfg = dataclasses.replace(get_config('granite-moe-1b-a400m').reduced(),
                              moe_capacity_factor=16.0)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    ref = M.forward(cfg, params, tokens)[0]
    g_ref = jax.grad(lambda p: loss_fn(cfg, p, batch, remat=False)[0])(params)

    mesh = make_mesh((2, 4), ("data", "model"))
    SH.set_mesh(mesh)
    moe.MOE_SHARDMAP = True
    out = jax.jit(lambda p, t: M.forward(cfg, p, t)[0])(params, tokens)
    g_sm = jax.jit(jax.grad(
        lambda p: loss_fn(cfg, p, batch, remat=False)[0]))(params)

    assert float(jnp.max(jnp.abs(out - ref))) < 2e-4
    gerr = max(float(jnp.max(jnp.abs(a - b))) for a, b in
               zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_sm)))
    assert gerr < 5e-3, gerr
    print("OK")
""")


def test_shardmap_moe_matches_dense():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout
