"""Jit'd public wrapper for the block-delta kernel + host-side helpers."""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.block_delta.kernel import block_delta
from repro.kernels.block_delta.ref import apply_delta_ref, block_delta_ref


@functools.partial(jax.jit, static_argnames=("impl",))
def compute_block_delta(new: jax.Array, old: jax.Array, *, impl: str = "pallas"):
    """new/old: (nblocks, block_elems) -> (q int8, norm2 f32, scale f32,
    nchanged int32: elements per block whose bits differ)."""
    if impl == "xla":
        return block_delta_ref(new, old)
    return block_delta(new, old, interpret=(impl == "pallas_interpret"))


def pack_dirty(
    q: np.ndarray, norm2: np.ndarray, scale: np.ndarray, threshold: float = 0.0
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Select blocks whose delta norm^2 clears ``threshold``.

    Returns (dirty_indices, q_dirty, scales_dirty) — what a commit ships.
    """
    idx = np.flatnonzero(np.asarray(norm2) > threshold)
    return idx, np.asarray(q)[idx], np.asarray(scale)[idx]


def blockify(flat: np.ndarray, block_elems: int) -> np.ndarray:
    """Pad a flat array to a whole number of blocks and reshape (a view
    when no padding is needed)."""
    n = len(flat)
    nb = -(-n // block_elems)
    if n == nb * block_elems:
        return flat.reshape(nb, block_elems)
    out = np.zeros((nb * block_elems,), flat.dtype)
    out[:n] = flat
    return out.reshape(nb, block_elems)
