"""GQA attention: full/causal/sliding-window, chunked long-seq path, decode.

The ``xla`` implementation here is the pure-jnp reference used for CPU tests
and compile-only lowering; on TPU the flash-attention Pallas kernel
(`repro.kernels.flash_attention`) replaces the core softmax(QK^T)V when
``impl="pallas"``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, Sq, KV, G, hd); k: (B, Sk, KV, hd) -> (B, KV, G, Sq, Sk)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k)


def _mask_bias(
    q_pos: jax.Array,
    k_pos: jax.Array,
    *,
    causal: bool,
    window: int,
    kv_len: Optional[jax.Array],
) -> jax.Array:
    """Additive mask bias (Sq, Sk) in float32."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    if kv_len is not None:
        ok &= k_pos[None, :] < kv_len
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: jax.Array,
    scale: float,
) -> jax.Array:
    """q: (B,Sq,KV,G,hd), k/v: (B,Sk,KV,hd), bias: (Sq,Sk) -> (B,Sq,KV,G,hd)."""
    scores = _gqa_scores(q, k).astype(jnp.float32) * scale
    scores = scores + bias[None, None, None]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)


def attention_xla(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    kv_len: Optional[jax.Array] = None,
    q_chunk: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """GQA attention.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd). Returns (B, Sq, H, hd).
    ``scale`` multiplies the scores (None => 1/sqrt(hd)).
    ``q_chunk > 0`` scans over query chunks so Sq x Sk scores never
    materialize (the long-sequence / prefill path; also the oracle the
    flash kernel is validated against).
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    Sk = k.shape[1]
    k_pos = jnp.arange(Sk)

    if q_chunk and Sq > q_chunk and Sq % q_chunk == 0:
        n = Sq // q_chunk
        qs = qg.reshape(B, n, q_chunk, KV, G, hd).swapaxes(0, 1)

        @jax.checkpoint
        def chunk(qc, i):
            q_pos = q_offset + i * q_chunk + jnp.arange(q_chunk)
            bias = _mask_bias(q_pos, k_pos, causal=causal, window=window, kv_len=kv_len)
            return _sdpa(qc, k, v, bias, scale)

        def body(_, inp):
            qc, i = inp
            return (), chunk(qc, i)

        _, out = jax.lax.scan(body, (), (qs, jnp.arange(n)))
        out = out.swapaxes(0, 1).reshape(B, Sq, KV, G, hd)
    else:
        q_pos = q_offset + jnp.arange(Sq)
        bias = _mask_bias(q_pos, k_pos, causal=causal, window=window, kv_len=kv_len)
        out = _sdpa(qg, k, v, bias, scale)
    return out.reshape(B, Sq, H, hd)


def decode_attention_xla(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cur_index: jax.Array,
    *,
    window: int = 0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token decode. q: (B, 1, H, hd); caches: (B, S, KV, hd).

    ``cur_index`` is the position of the query token; cache entries at
    positions <= cur_index are attended (the new token's k/v must already be
    written). Sliding window limits attention to the last ``window`` keys;
    ``scale`` as in ``attention_xla``.
    """
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd)
    if scale is None:
        scale = 1.0 / (hd ** 0.5)
    S = k_cache.shape[1]
    k_pos = jnp.arange(S)
    ok = k_pos <= cur_index
    if window > 0:
        ok &= k_pos > (cur_index - window)
    bias = jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)  # (S,)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache).astype(jnp.float32) * scale
    scores = scores + bias[None, None, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v_cache)
    return out.reshape(B, 1, H, hd)
