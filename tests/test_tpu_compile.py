"""Compile the main path's kernels and the full-width decode step for a
described TPU v5e, without a chip.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and a worker that loads
it keeps it until it exits. Where no v5e can be described, every test here
skips. Compiles run with the persistent cache off: what a described chip
compiles cannot be read back from it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.block_delta.ops import compute_block_delta
from repro.kernels.flash_attention.ops import attention
from repro.models import model as M

BLOCK_ELEMS = 256 * 1024 // 4          # CheckpointManager's 256 KiB blocks
QWEN2_EMBED_BLOCKS = 3561              # 151936 x 1536 f32 at 256 KiB
V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("nb", [64, QWEN2_EMBED_BLOCKS, 1])
def test_block_delta_compiles(one_chip, nb):
    x = _spec((nb, BLOCK_ELEMS), jnp.float32, one_chip)
    hlo = compute_block_delta.lower(x, x, impl="pallas").compile().as_text()
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles_at_qwen2_heads(one_chip):
    cfg = get_config("qwen2-1.5b")
    q = _spec((1, 512, cfg.num_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _spec((1, 512, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    hlo = attention.lower(q, kv, kv, impl="pallas").compile().as_text()
    assert "tpu_custom_call" in hlo


def test_qwen2_decode_step_fits_one_v5e(one_chip):
    cfg = get_config("qwen2-1.5b")
    batch, max_len = 4, 16 + 8                   # chip_smoke's decode shapes

    def place(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = place(jax.eval_shape(
        lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0)))
    cache = place(jax.eval_shape(
        lambda: M.make_decode_cache(cfg, batch, max_len)))
    tok = _spec((batch, 1), jnp.int32, one_chip)
    idx = _spec((), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda p, c, t, i: M.decode_step(cfg, p, c, t, i)
    ).lower(params, cache, tok, idx).compile()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 6e9      # f32 params at full width
    assert need < V5E_HBM, need
