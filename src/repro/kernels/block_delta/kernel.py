"""Block-delta Pallas kernel: the paper's fine-grained change tracking,
computed on-device.

Given the new and previous values of a flat parameter buffer laid out in
FaaSFS blocks, one grid step per block computes, entirely in VMEM:

  * the block's delta L2 norm^2 (significance),
  * the block's max-abs (int8 quantization scale),
  * the int8-quantized delta,
  * how many of its elements changed bit pattern (exact dirty detection:
    a NaN, a signed zero or a step that squares to zero still counts).

The commit path then ships only blocks whose norm clears a threshold, as
int8 + one fp32 scale — the paper's block-granular cache-update protocol
doubling as gradient/update compression.

TPU layout: a block of ``block_elems`` values is viewed as a
``(block_elems // 128, 128)`` slab, so each grid step's tile spans the
whole of its array's last two dimensions and meets the (8, 128) f32 and
(32, 128) int8 tiling rules at any block size. The per-block ``norm2``,
``scale`` and ``nchanged`` are written broadcast across one 128-lane row
rather than as scalar VMEM stores, which Mosaic refuses. A slab lives in VMEM whole
(double-buffered inputs and outputs), so a block must stay well inside
the scoped VMEM budget: 256 KiB f32 blocks take about 1.5 MiB.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _delta_kernel(new_ref, old_ref, q_ref, norm_ref, scale_ref, changed_ref):
    new = new_ref[...].astype(jnp.float32)      # (1, rows, LANES)
    old = old_ref[...].astype(jnp.float32)
    diff = new - old
    norm2 = jnp.sum(diff * diff)
    maxabs = jnp.max(jnp.abs(diff))
    scale = jnp.where(maxabs > 0, maxabs / 127.0, 1.0)
    # bits, not values: widening to f32 is exact, so this is exact for
    # narrower floats too
    ne = jax.lax.bitcast_convert_type(new, jnp.int32) != \
        jax.lax.bitcast_convert_type(old, jnp.int32)
    nchanged = jnp.sum(ne.astype(jnp.float32))  # <= 2**24: exact in f32
    q_ref[...] = jnp.clip(jnp.round(diff / scale), -127, 127).astype(jnp.int8)
    norm_ref[...] = jnp.full(norm_ref.shape, norm2, jnp.float32)
    scale_ref[...] = jnp.full(scale_ref.shape, scale, jnp.float32)
    changed_ref[...] = jnp.full(changed_ref.shape, nchanged, jnp.float32)


def block_delta(
    new: jax.Array,      # (nblocks, block_elems)
    old: jax.Array,      # (nblocks, block_elems)
    *,
    interpret: bool = False,
):
    """Returns (q int8 (nblocks, block_elems), norm2 (nblocks,),
    scale (nblocks,), nchanged int32 (nblocks,))."""
    nb, be = new.shape
    if be % LANES:
        raise ValueError(f"block_elems={be} is not a multiple of {LANES}")
    rows = be // LANES
    slab = pl.BlockSpec((1, rows, LANES), lambda i: (i, 0, 0))
    lane = pl.BlockSpec((1, 1, LANES), lambda i: (i, 0, 0))
    q, norm2, scale, nchanged = pl.pallas_call(
        _delta_kernel,
        grid=(nb,),
        in_specs=[slab, slab],
        out_specs=[slab, lane, lane, lane],
        out_shape=[
            jax.ShapeDtypeStruct((nb, rows, LANES), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1, LANES), jnp.float32),
        ],
        interpret=interpret,
        name="block_delta",
    )(new.reshape(nb, rows, LANES), old.reshape(nb, rows, LANES))
    return (q.reshape(nb, be), norm2[:, 0, 0], scale[:, 0, 0],
            nchanged[:, 0, 0].astype(jnp.int32))
