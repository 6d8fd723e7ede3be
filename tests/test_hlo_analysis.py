"""HLO analyzer: trip-count-corrected costs vs XLA's own cost_analysis."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import HloCostModel, analyze_text, parse_type


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_parse_type():
    s = parse_type("f32[4,8]{1,0}")
    assert s[0].dtype == "f32" and s[0].dims == (4, 8) and s[0].bytes == 128
    t = parse_type("(f32[2], bf16[3,4])")
    assert len(t) == 2 and t[1].bytes == 24
    assert parse_type("s32[]")[0].elems == 1


def test_while_trip_count_correction():
    """Scanned matmul flops must match the unrolled reference (XLA's own
    cost_analysis undercounts the scan by ~8x)."""
    L = 8
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 256), jnp.float32)

    def scanned(w, x):
        def body(c, _):
            return c @ w, ()
        y, _ = jax.lax.scan(body, x, None, length=L)
        return jnp.sum(y)

    def unrolled(w, x):
        for _ in range(L):
            x = x @ w
        return jnp.sum(x)

    cs = _compile(scanned, w, x)
    cu = _compile(unrolled, w, x)
    mine_s = analyze_text(cs.as_text())["flops_per_device"]
    mine_u = analyze_text(cu.as_text())["flops_per_device"]
    xla_u = cu.cost_analysis()["flops"]
    xla_s = cs.cost_analysis()["flops"]
    # XLA undercounts the scan: body visited once
    assert xla_s < xla_u / 2
    # our corrected count matches the unrolled one within 10%
    assert abs(mine_s - mine_u) / mine_u < 0.10
    # and matches XLA's unrolled ground truth within 15%
    assert abs(mine_u - xla_u) / xla_u < 0.15


def test_dot_flops_exact():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    c = _compile(lambda a, b: a @ b, a, b)
    got = analyze_text(c.as_text())["flops_per_device"]
    want = 2 * 64 * 128 * 32
    assert abs(got - want) / want < 0.05


def test_nested_scan():
    def fn(x):
        def outer(c, _):
            def inner(d, _):
                return d * 1.5 + 1.0, ()
            d, _ = jax.lax.scan(inner, c, None, length=4)
            return d, ()
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    c = _compile(fn, jax.ShapeDtypeStruct((128,), jnp.float32))
    got = analyze_text(c.as_text())["flops_per_device"]
    # 3*4 = 12 iterations of ~2 ops on 128 elems; just check the 12x scaling
    assert got >= 12 * 128


def test_collective_bytes_on_spmd_program():
    import os
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)")


def test_collective_formulas_via_mock_hlo():
    text = """
HloModule test

ENTRY %main.1 (p0: f32[64,64]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  %ag = f32[64,64]{1,0} all-gather(%p0), channel_id=1, replica_groups=[4,8]<=[32], dimensions={0}
  %ar = f32[64,64]{1,0} all-reduce(%ag), channel_id=2, replica_groups=[4,8]<=[32], to_apply=%add
  ROOT %cp = f32[64,64]{1,0} collective-permute(%ar), channel_id=3, source_target_pairs={{0,1}}
}
"""
    res = analyze_text(text)
    size = 64 * 64 * 4
    want = size * 7 / 8 + 2 * size * 7 / 8 + size
    assert abs(res["collective_bytes_per_device"] - want) < 1
    assert res["collective_counts"] == {
        "all-gather": 1, "all-reduce": 1, "collective-permute": 1
    }


def test_entry_detection_on_real_module():
    c = _compile(lambda x: x * 2.0 + 1.0, jax.ShapeDtypeStruct((8,), jnp.float32))
    m = HloCostModel(c.as_text())
    assert m.entry is not None
    assert m.entry_cost().bytes_accessed > 0
