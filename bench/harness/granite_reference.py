"""The plain reference of a served IBM Granite model, and its logit check.

A straightforward float32 forward pass over whole sequences, with every
matrix product at ``precision="highest"``, written from the published
models (Hugging Face ``GraniteForCausalLM`` and
``GraniteMoeHybridForCausalLM``) and Mamba-2's paper (Dao & Gu,
arXiv:2405.21060). It reads its sizes from the configuration file and its
weights, in whatever type they are served, from the benchmark's own
generator (``granite_weights.py``); it imports nothing of the program and
has no cache and no batching of its own. ``cast`` rounds every matrix
product's operands to a narrower type: the lower-precision control.

A layer, with Granite's four multipliers:

  x = embed[tokens] * embedding_multiplier
  h = rmsnorm(x); x = x + residual_multiplier * mixer(h)
  h = rmsnorm(x); x = x + residual_multiplier * (silu(h wg) * (h wi)) wo
  logits = rmsnorm(x) embed^T / logits_scaling

The mixer is grouped-query attention with scores scaled by
``attention_multiplier`` (rotary embeddings on halves, unless
``position_embedding_type`` is ``nope``), or a Mamba-2 mixer:

  [z | xBC | dt] = h in_proj;  xBC = silu(causal depthwise conv(xBC) + b)
  [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
  per head: s_t = exp(dt_t A) s_{t-1} + dt_t x_t (outer) B_t,
            y_t = s_t C_t + D x_t       (a sequential scan, one step a position)
  out = rmsnorm(y * silu(z)) out_proj

Departures: the norm after the gate is taken over all of d_inner, which is
Mamba-2's grouped norm when ``mamba_n_groups`` is 1 (both configurations
here); dt is not clamped (the published ``time_step_limit`` is (0, inf));
no routed experts (``num_local_experts`` is 0, so the shared MLP is the
whole MLP).
"""
from __future__ import annotations

import functools
import json
from typing import Optional

import numpy as np

from harness.granite_weights import dims


def hidden(c: dict, p: dict, tokens, cast: Optional[str] = None):
    """``tokens`` (B, S) int32 -> the final-normed hidden state (B, S, D)
    float32, causal."""
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
    kinds = c.get("layer_types") or ["attention"] * d["L"]
    rope = c.get("position_embedding_type", "rope") != "nope"
    inv = 1.0 / (c["rope_theta"] ** (np.arange(0, d["hd"], 2) / d["hd"]))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rotary(x):  # (B, S, heads, hd)
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = jnp.tril(jnp.ones((S, S), bool))

    def attention(h, a, i):
        q, k, v = (mm("bsd,dh->bsh", h, a[w][i]) for w in ("wq", "wk", "wv"))
        q = q.reshape(B, S, d["heads"], d["hd"])
        k = k.reshape(B, S, d["kv_heads"], d["hd"])
        v = v.reshape(B, S, d["kv_heads"], d["hd"])
        if rope:
            q, k = rotary(q), rotary(k)
        group = d["heads"] // d["kv_heads"]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        s = mm("bqhd,bkhd->bhqk", q, k) * c["attention_multiplier"]
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return mm("bsh,hd->bsd", o.reshape(B, S, d["H"]), a["wo"][i])

    def mamba(h, m, i):
        Di, G, N, Hs, P, K = d["Di"], d["G"], d["N"], d["Hs"], d["P"], d["K"]
        proj = mm("bsd,de->bse", h, m["in_proj"][i])
        z, xbc, dt = proj[..., :Di], proj[..., Di:Di + d["C"]], proj[..., Di + d["C"]:]
        w = m["conv_w"][i]                                   # (C, K)
        padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        xbc = sum(padded[:, j:j + S] * w[:, j] for j in range(K)) + m["conv_b"][i]
        xbc = jax.nn.silu(xbc)
        x = xbc[..., :Di].reshape(B, S, Hs, P)
        Bm = jnp.repeat(xbc[..., Di:Di + G * N].reshape(B, S, G, N), Hs // G, axis=2)
        Cm = jnp.repeat(xbc[..., Di + G * N:].reshape(B, S, G, N), Hs // G, axis=2)
        dt = jax.nn.softplus(dt + m["dt_bias"][i])           # (B, S, Hs)
        A = -jnp.exp(m["A_log"][i])

        def step(s, t):
            xt, Bt, Ct, dtt = t
            s = (jnp.exp(dtt * A)[..., None, None] * s
                 + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :])
            return s, mm("bhpn,bhn->bhp", s, Ct)

        seq = [a.swapaxes(0, 1) for a in (x, Bm, Cm, dt)]
        _, y = jax.lax.scan(step, jnp.zeros((B, Hs, P, N), jnp.float32), seq)
        y = y.swapaxes(0, 1) + x * m["D"][i][:, None]
        y = norm(y.reshape(B, S, Di) * jax.nn.silu(z), m["norm"][i])
        return mm("bse,ed->bsd", y, m["out_proj"][i])

    lp = p["layers"]
    x = rnd(p["embed"][tokens]) * c["embedding_multiplier"]
    r = c["residual_multiplier"]
    for l, kind in enumerate(kinds):
        slot = kinds[:l].count(kind)
        h = norm(x, lp["ln1"][l])
        if kind == "attention":
            x = x + r * attention(h, lp["attn"], slot)
        else:
            x = x + r * mamba(h, lp["mamba"], slot)
        h = norm(x, lp["ln2"][l])
        up = mm("bsd,df->bsf", h, lp["mlp"]["wi"][l])
        gate = mm("bsd,df->bsf", h, lp["mlp"]["wg"][l])
        x = x + r * mm("bsf,fd->bsd", jax.nn.silu(gate) * up, lp["mlp"]["wo"][l])
    return norm(x, p["final_norm"])


def widest_gap(c: dict, p: dict, seqs: np.ndarray, prompt: int,
               cast: Optional[str] = None, rows: int = 1) -> float:
    """How far below the reference's best logit a served token lies, at its
    widest. ``seqs`` (B, prompt + T) holds each prompt and the T tokens
    served after it; the token at position ``i >= prompt`` is judged given
    ``seqs[:, :i]``. Logits are computed at the judged positions alone,
    ``rows`` sequences at a time. With ``cast``, the judged token is instead
    the one that the reference in that precision puts first (the control)."""
    import jax.numpy as jnp

    key = json.dumps(c, sort_keys=True)
    ctx = jnp.asarray(seqs[:, :-1], jnp.int32)
    h = _jitted_hidden(key, None)(p, ctx)[:, prompt - 1:]
    h_low = None if cast is None else _jitted_hidden(key, cast)(p, ctx)[:, prompt - 1:]
    served = jnp.asarray(seqs[:, prompt:], jnp.int32)
    gap = 0.0
    for b in range(0, seqs.shape[0], rows):
        sl = slice(b, b + rows)
        pick = served[sl] if cast is None else _jitted_argmax(key, cast)(p, h_low[sl])
        gap = max(gap, float(_jitted_gap(key)(p, h[sl], pick)))
    return gap


def _head(c: dict, p: dict):
    import jax.numpy as jnp

    head = p["embed"].T if c.get("tie_word_embeddings") else p["lm_head"]
    return head.astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _jitted_hidden(config_json: str, cast: Optional[str]):
    import jax

    c = json.loads(config_json)
    return jax.jit(lambda p, t: hidden(c, p, t, cast))


@functools.lru_cache(maxsize=None)
def _jitted_gap(config_json: str):
    """Widest gap between the best logit and the picked token's, over a
    block of positions: logits = h embed^T / logits_scaling."""
    import jax
    import jax.numpy as jnp

    c = json.loads(config_json)

    def gap(p, h, pick):
        logits = jnp.einsum("bsd,dv->bsv", h, _head(c, p),
                            precision=jax.lax.Precision.HIGHEST) / c["logits_scaling"]
        got = jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
        return jnp.max(jnp.max(logits, -1) - got)

    return jax.jit(gap)


@functools.lru_cache(maxsize=None)
def _jitted_argmax(config_json: str, cast: str):
    """The token the reference in ``cast`` puts first at each position."""
    import jax
    import jax.numpy as jnp

    c = json.loads(config_json)
    low = getattr(jnp, cast)

    def first(p, h):
        rnd = lambda a: a.astype(low).astype(jnp.float32)  # noqa: E731
        logits = jnp.einsum("bsd,dv->bsv", rnd(h), rnd(_head(c, p)),
                            precision=jax.lax.Precision.HIGHEST)
        return jnp.argmax(logits, -1)

    return jax.jit(first)
