"""The plain reference of a served dense decoder, and the checks built on it.

A straightforward float32 forward pass over whole sequences, written from
the published architecture (Qwen2: RMSNorm, rotary embeddings on halves,
grouped-query attention with q/k/v biases, SwiGLU, tied embeddings), with
every matrix product at ``precision="highest"``. It reads its sizes from
the configuration file and its weights from the benchmark's own
generator, never from the program, in whatever type they are served and
computes in float32. ``cast`` rounds every matrix product's
operands to a narrower type: the lower-precision control.

In the weight tree ``wg`` is the gate and ``wi`` the up projection of the
MLP, ``wo`` its down projection.
"""
from __future__ import annotations

import functools
import json
from typing import Optional

import numpy as np

from harness.weights import dims


def forward_logits(c: dict, p: dict, tokens, cast: Optional[str] = None):
    """``tokens`` (B, S) int32 -> logits (B, S, V) float32, causal."""
    import jax
    import jax.numpy as jnp

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    d = dims(c)
    eps = c["rms_norm_eps"]
    hi = jax.lax.Precision.HIGHEST
    low = None if cast is None else getattr(jnp, cast)

    def rnd(a):
        return a if low is None else a.astype(low).astype(jnp.float32)

    def mm(spec, a, b):
        return jnp.einsum(spec, rnd(a), rnd(b), precision=hi)

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    B, S = tokens.shape
    inv = 1.0 / (c["rope_theta"] ** (np.arange(0, d["hd"], 2) / d["hd"]))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):  # (B, S, heads, hd)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    group = d["heads"] // d["kv_heads"]
    causal = jnp.tril(jnp.ones((S, S), bool))
    x = rnd(p["embed"][tokens])
    for l in range(d["L"]):
        a = p["layers"]["attn"]
        h = norm(x, p["layers"]["ln1"][l])
        q, k, v = (mm("bsd,dh->bsh", h, a[w][l]) for w in ("wq", "wk", "wv"))
        if c.get("qkv_bias"):
            q, k, v = q + a["bq"][l], k + a["bk"][l], v + a["bv"][l]
        q = rope(q.reshape(B, S, d["heads"], d["hd"]))
        k = rope(k.reshape(B, S, d["kv_heads"], d["hd"]))
        v = v.reshape(B, S, d["kv_heads"], d["hd"])
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = mm("bqhd,bkhd->bhqk", q, k) / np.sqrt(d["hd"])
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + mm("bsh,hd->bsd", o.reshape(B, S, d["H"]), a["wo"][l])
        m = p["layers"]["mlp"]
        h = norm(x, p["layers"]["ln2"][l])
        up = mm("bsd,df->bsf", h, m["wi"][l])
        gate = mm("bsd,df->bsf", h, m["wg"][l])
        x = x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up, m["wo"][l])
    x = norm(x, p["final_norm"])
    head = p["embed"].T if c.get("tie_word_embeddings") else p["lm_head"]
    return mm("bsd,dv->bsv", x, head)


def widest_gap(c: dict, p: dict, seqs: np.ndarray, cast: Optional[str] = None) -> float:
    """How far below the reference's best logit a token of ``seqs`` lies,
    at its widest. ``seqs`` (B, 1 + T) holds each prompt token and the T
    tokens served after it; position ``i`` is judged given ``seqs[:, :i+1]``.
    With ``cast``, the judged token is instead the one that the reference
    in that precision puts first (the control)."""
    import jax.numpy as jnp

    ctx = jnp.asarray(seqs[:, :-1], jnp.int32)
    ref = _jitted(json.dumps(c, sort_keys=True), None)(p, ctx)
    if cast is None:
        pick = jnp.asarray(seqs[:, 1:], jnp.int32)
    else:
        pick = jnp.argmax(_jitted(json.dumps(c, sort_keys=True), cast)(p, ctx), -1)
    got = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return float(jnp.max(jnp.max(ref, -1) - got))


@functools.lru_cache(maxsize=None)
def _jitted(config_json: str, cast: Optional[str]):
    import jax

    c = json.loads(config_json)
    return jax.jit(lambda p, t: forward_logits(c, p, t, cast))
