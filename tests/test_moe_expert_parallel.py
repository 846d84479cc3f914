"""Expert-parallel shard_map MoE (§Perf iteration 14): loss parity with the
GSPMD scatter path on a real (data, model) mesh, in a subprocess (needs 8
virtual devices). Without a mesh it must fall back to the GSPMD path."""
import os
import subprocess
import sys
import textwrap

import dataclasses
import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS
from repro.models import get_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_fallback_without_mesh_matches_gspmd_path():
    c0 = ARCHS["qwen3-moe-30b-a3b"].smoke()
    c1 = dataclasses.replace(c0, moe_sharding="expert_parallel")
    m = get_model(c0)
    key = jax.random.PRNGKey(0)
    params = m.init(key, c0)
    batch = {"tokens": jax.random.randint(key, (2, 32), 0, c0.vocab_size),
             "targets": jnp.ones((2, 32), jnp.int32)}
    assert abs(float(m.loss_fn(params, batch, c0))
               - float(m.loss_fn(params, batch, c1))) < 1e-6


def test_expert_parallel_on_mesh_subprocess():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs.registry import ARCHS
        from repro.models import get_model

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        c0 = ARCHS["qwen3-moe-30b-a3b"].smoke()
        c1 = dataclasses.replace(c0, moe_sharding="expert_parallel")
        m = get_model(c0)
        key = jax.random.PRNGKey(0)
        params = m.init(key, c0)
        batch = {"tokens": jax.random.randint(key, (4, 32), 0, c0.vocab_size),
                 "targets": jnp.ones((4, 32), jnp.int32)}
        l0 = float(m.loss_fn(params, batch, c0))
        with jax.set_mesh(mesh):
            pspec = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s),
                                 m.param_specs(c1, "train"),
                                 is_leaf=lambda x: isinstance(x, P))
            bspec = jax.tree.map(
                lambda _: jax.sharding.NamedSharding(mesh, P(("data",))), batch)
            fn = jax.jit(lambda p, b: m.loss_fn(p, b, c1),
                         in_shardings=(pspec, bspec))
            l1 = float(fn(params, batch))
            g = jax.jit(jax.grad(lambda p: m.loss_fn(p, batch, c1)))(params)
        assert abs(l0 - l1) < 5e-2, (l0, l1)   # capacity-drop ordering differs
        gn = sum(float(jnp.sum(jnp.abs(x.astype(jnp.float32))))
                 for x in jax.tree.leaves(g))
        assert np.isfinite(gn) and gn > 0
        print("OK", l0, l1)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "OK" in proc.stdout
