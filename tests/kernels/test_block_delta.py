"""Block-delta kernel vs oracle + end-to-end compression roundtrip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_delta.ops import blockify, compute_block_delta, pack_dirty
from repro.kernels.block_delta.ref import apply_delta_ref

CASES = [(4, 128), (8, 256), (16, 512), (1, 1024)]


@pytest.mark.parametrize("nb,be", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_vs_ref(nb, be, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    new = jax.random.normal(ks[0], (nb, be), dtype)
    old = new + jax.random.normal(ks[1], (nb, be), dtype) * 0.01
    qk, nk, sk, ck = compute_block_delta(new, old, impl="pallas_interpret")
    qr, nr, sr, cr = compute_block_delta(new, old, impl="xla")
    assert int(jnp.sum(jnp.abs(qk.astype(jnp.int32) - qr.astype(jnp.int32)))) == 0
    np.testing.assert_allclose(np.asarray(nk), np.asarray(nr), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))


def test_identical_blocks_have_zero_norm():
    x = jnp.ones((4, 128), jnp.float32)
    q, norm2, scale, nchanged = compute_block_delta(x, x, impl="pallas_interpret")
    assert float(jnp.max(norm2)) == 0.0
    assert int(jnp.max(nchanged)) == 0
    assert int(jnp.max(jnp.abs(q.astype(jnp.int32)))) == 0


def test_quantized_roundtrip_error_bounded():
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    old = jax.random.normal(ks[0], (8, 256), jnp.float32)
    new = old + jax.random.normal(ks[1], (8, 256), jnp.float32) * 0.05
    q, norm2, scale, _ = compute_block_delta(new, old, impl="pallas_interpret")
    rec = apply_delta_ref(old, q, scale)
    # int8 quantization error is bounded by scale/2 per element
    err = np.max(np.abs(np.asarray(rec) - np.asarray(new)))
    assert err <= float(jnp.max(scale)) / 2 + 1e-6


def test_pack_dirty_selects_changed_blocks_only():
    old = np.zeros((6, 64), np.float32)
    new = old.copy()
    new[1] += 0.5
    new[4] += 0.1
    q, norm2, scale, _ = compute_block_delta(jnp.asarray(new), jnp.asarray(old), impl="xla")
    idx, qd, sd = pack_dirty(np.asarray(q), np.asarray(norm2), np.asarray(scale))
    assert list(idx) == [1, 4]
    assert qd.shape == (2, 64)


def test_blockify_pads():
    flat = np.arange(100, dtype=np.float32)
    b = blockify(flat, 64)
    assert b.shape == (2, 64)
    assert b[1, 36:].sum() == 0
    np.testing.assert_array_equal(b.reshape(-1)[:100], flat)
