"""Numeric equivalence of the shard_map all-to-all MoE vs the global-view
dispatch — run on a 4-device forced-host mesh in a subprocess (the main test
process keeps 1 device)."""
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.models.moe import moe_ffn, moe_ffn_a2a

    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    use_mesh = lambda: jax.set_mesh(mesh)
    key = jax.random.PRNGKey(0)
    B, S, D, E, F, k = 4, 8, 16, 4, 32, 2
    ks = jax.random.split(key, 5)
    router = jax.random.normal(ks[0], (D, E)) * 0.1
    wi = jax.random.normal(ks[1], (E, D, F)) * 0.1
    wg = jax.random.normal(ks[2], (E, D, F)) * 0.1
    wo = jax.random.normal(ks[3], (E, F, D)) * 0.1
    x = jax.random.normal(ks[4], (B, S, D))

    with use_mesh():
        y_ref, aux_ref = jax.jit(lambda *a: moe_ffn(
            *a, num_experts=E, top_k=k, capacity_factor=32.0, groups=1))(
            x, router, wi, wg, wo)
        y_a2a, aux_a2a = jax.jit(lambda *a: moe_ffn_a2a(
            *a, num_experts=E, top_k=k, capacity_factor=32.0,
            mesh=mesh, batch_axes=("data",), model_axis="model",
            seq_axis="model"))(x, router, wi, wg, wo)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_a2a),
                               atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(float(aux_ref), float(aux_a2a), rtol=1e-4)
    # gradient path through the double all_to_all
    def loss(fn, *args):
        y, aux = fn(*args)
        return jnp.sum(y ** 2) + aux
    g_ref = jax.grad(lambda w: loss(lambda *a: moe_ffn(
        *a, num_experts=E, top_k=k, capacity_factor=32.0, groups=1),
        x, router, w, wg, wo))(wi)
    with use_mesh():
        g_a2a = jax.grad(lambda w: loss(lambda *a: moe_ffn_a2a(
            *a, num_experts=E, top_k=k, capacity_factor=32.0,
            mesh=mesh, batch_axes=("data",), model_axis="model",
            seq_axis="model"), x, router, w, wg, wo))(wi)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_a2a),
                               atol=2e-4, rtol=1e-3)
    print("A2A_OK")
""")


def test_moe_a2a_matches_global_dispatch():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        cwd="/root/repo", timeout=600,
    )
    assert "A2A_OK" in res.stdout, res.stderr[-3000:]
