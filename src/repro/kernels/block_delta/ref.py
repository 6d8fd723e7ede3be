"""Pure-jnp oracle for the block-delta kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _bits(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


def block_delta_ref(new: jax.Array, old: jax.Array):
    diff = new.astype(jnp.float32) - old.astype(jnp.float32)
    norm2 = jnp.sum(diff * diff, axis=1)
    maxabs = jnp.max(jnp.abs(diff), axis=1)
    scale = jnp.where(maxabs > 0, maxabs / 127.0, 1.0)
    q = jnp.clip(jnp.round(diff / scale[:, None]), -127, 127).astype(jnp.int8)
    nchanged = jnp.sum(_bits(new) != _bits(old), axis=1, dtype=jnp.int32)
    return q, norm2, scale, nchanged


def apply_delta_ref(old: jax.Array, q: jax.Array, scale: jax.Array) -> jax.Array:
    """Dequantize + apply: reconstruct new params from the shipped delta."""
    return (old.astype(jnp.float32) + q.astype(jnp.float32) * scale[:, None]).astype(old.dtype)
