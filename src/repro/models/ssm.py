"""Selective SSM mixers (xla path): Mamba-1 and Mamba-2 (SSD).

Mamba-1 training/prefill uses a chunked scan: a sequential ``lax.scan`` over
sequence chunks carrying the (B, D_inner, N) state, with an associative scan
inside each chunk — this bounds the materialized (B, chunk, D_inner, N)
tensors (the same chunking scheme the Pallas ``ssm_scan`` kernel implements
with VMEM tiles). Decode is the O(1) single-step recurrence.

Mamba-2 (Dao & Gu, "Transformers are SSMs", arXiv:2405.21060) has one scalar
decay per head: h <- exp(dt*A) h + dt x (x) B, y = C.h + D x. Its prefill is
the chunked SSD form (a quadratic, attention-like product inside each chunk,
a recurrence over chunk states between them); its decode is the same
one-step recurrence.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv. x: (B, S, Di); w: (Di, K); b: (Di,)."""
    K = w.shape[1]
    out = jnp.zeros_like(x)
    for i in range(K):
        shift = K - 1 - i
        xi = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, : x.shape[1]]
        out = out + xi * w[:, i][None, None, :]
    return out + b[None, None, :]


def _ssm_params(x: jax.Array, p: Dict[str, jax.Array], dt_rank: int, n: int):
    """x: (B, S, Di) -> dt (B,S,Di) fp32, B_ (B,S,N) fp32, C (B,S,N) fp32."""
    proj = jnp.einsum("bsd,dr->bsr", x, p["x_proj"]).astype(jnp.float32)
    dt_in, B_, C = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    dt = jnp.einsum("bsr,rd->bsd", dt_in, p["dt_proj"].astype(jnp.float32))
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32)[None, None, :])
    return dt, B_, C


def _discretize(dt, B_, x, A):
    """dt: (B,S,Di); B_: (B,S,N); x: (B,S,Di); A: (Di,N) negative.

    Returns Abar (B,S,Di,N), Bx (B,S,Di,N) in fp32.
    """
    Abar = jnp.exp(dt[..., None] * A[None, None])             # (B,S,Di,N)
    Bx = dt[..., None] * B_[..., None, :] * x.astype(jnp.float32)[..., None]
    return Abar, Bx


def _chunk_scan(Abar, Bx, h0):
    """Associative scan within a chunk, seeded with carry state h0.

    Abar/Bx: (B, c, Di, N); h0: (B, Di, N). Returns (h_all (B,c,Di,N), h_last).
    """
    def combine(a, b):
        a_l, b_l = a
        a_r, b_r = b
        return a_l * a_r, b_l * a_r + b_r

    Aacc, Bacc = jax.lax.associative_scan(combine, (Abar, Bx), axis=1)
    h_all = Aacc * h0[:, None] + Bacc
    return h_all, h_all[:, -1]


def selective_scan(
    x: jax.Array,
    dt: jax.Array,
    B_: jax.Array,
    C: jax.Array,
    A: jax.Array,
    D: jax.Array,
    *,
    chunk: int = 1024,
    h0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """y = SSM(x) with selective (input-dependent) dynamics.

    x: (B, S, Di); dt: (B, S, Di); B_/C: (B, S, N); A: (Di, N) (negative);
    D: (Di,) skip. Returns (y (B,S,Di) in x.dtype, h_last (B,Di,N) fp32).
    """
    Bsz, S, Di = x.shape
    N = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((Bsz, Di, N), jnp.float32)

    c = min(chunk, S)
    while S % c:
        c -= 1
    n_chunks = S // c

    if n_chunks == 1:
        Abar, Bx = _discretize(dt, B_, x, A)
        h_all, h_last = _chunk_scan(Abar, Bx, h0)
        y = jnp.einsum("bsdn,bsn->bsd", h_all, C)
    else:
        # Discretize INSIDE the chunk so (B, c, Di, N) tensors never
        # materialize for the full sequence (and remat recomputes them in
        # the backward pass instead of saving them).
        def split(t):
            return t.reshape(Bsz, n_chunks, c, *t.shape[2:]).swapaxes(0, 1)

        x_c, dt_c, B_c, C_c = split(x), split(dt), split(B_), split(C)

        @jax.checkpoint
        def chunk_fn(h, xc, dtc, Bc, Cc):
            Abar, Bx = _discretize(dtc, Bc, xc, A)
            h_all, h_last = _chunk_scan(Abar, Bx, h)
            yc = jnp.einsum("bsdn,bsn->bsd", h_all, Cc)
            return h_last, yc

        def body(h, inp):
            xc, dtc, Bc, Cc = inp
            return chunk_fn(h, xc, dtc, Bc, Cc)

        h_last, ys = jax.lax.scan(body, h0, (x_c, dt_c, B_c, C_c))
        y = ys.swapaxes(0, 1).reshape(Bsz, S, Di)

    y = y + x.astype(jnp.float32) * D[None, None, :]
    return y.astype(x.dtype), h_last


def mamba_block(
    x: jax.Array,
    p: Dict[str, jax.Array],
    *,
    dt_rank: int,
    ssm_state: int,
    chunk: int = 256,
) -> jax.Array:
    """Full mamba-1 mixer. x: (B, S, D) -> (B, S, D)."""
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xi, z = jnp.split(xz, 2, axis=-1)  # (B,S,Di) each
    xi = causal_conv1d(xi, p["conv_w"], p["conv_b"])
    xi = xi * jax.nn.sigmoid(xi.astype(jnp.float32)).astype(xi.dtype)  # silu
    dt, B_, C = _ssm_params(xi, p, dt_rank, ssm_state)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, _ = selective_scan(xi, dt, B_, C, A, p["D"].astype(jnp.float32), chunk=chunk)
    y = y * (z * jax.nn.sigmoid(z.astype(jnp.float32)).astype(z.dtype))  # gate
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"])


# --------------------------------------------------------------------------- #
# Decode (single-step recurrence)
# --------------------------------------------------------------------------- #
def mamba_decode_step(
    x: jax.Array,
    p: Dict[str, jax.Array],
    conv_state: jax.Array,
    ssm_state_v: jax.Array,
    *,
    dt_rank: int,
    ssm_state: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, 1, D); conv_state: (B, K-1, Di); ssm_state_v: (B, Di, N).

    Returns (y (B,1,D), new_conv_state, new_ssm_state).
    """
    B = x.shape[0]
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xi, z = jnp.split(xz, 2, axis=-1)          # (B,1,Di)
    K = p["conv_w"].shape[1]
    window = jnp.concatenate([conv_state, xi], axis=1)      # (B,K,Di)
    conv = jnp.einsum("bkd,dk->bd", window, p["conv_w"]) + p["conv_b"]
    conv = conv[:, None, :]                                  # (B,1,Di)
    conv = conv * jax.nn.sigmoid(conv.astype(jnp.float32)).astype(conv.dtype)
    dt, B_, C = _ssm_params(conv, p, dt_rank, ssm_state)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    Abar = jnp.exp(dt[:, 0, :, None] * A[None])              # (B,Di,N)
    Bx = dt[:, 0, :, None] * B_[:, 0, None, :] * conv.astype(jnp.float32)[:, 0, :, None]
    h = Abar * ssm_state_v + Bx                              # (B,Di,N)
    y = jnp.einsum("bdn,bn->bd", h, C[:, 0]) + conv.astype(jnp.float32)[:, 0] * p["D"].astype(jnp.float32)[None]
    y = y.astype(x.dtype)[:, None, :]
    y = y * (z * jax.nn.sigmoid(z.astype(jnp.float32)).astype(z.dtype))
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, window[:, 1:], h


# --------------------------------------------------------------------------- #
# Mamba-2 (SSD)
# --------------------------------------------------------------------------- #
def _silu(x: jax.Array) -> jax.Array:
    return x * jax.nn.sigmoid(x.astype(jnp.float32)).astype(x.dtype)


def gated_rms_norm(y: jax.Array, z: jax.Array, w: jax.Array, groups: int,
                   eps: float) -> jax.Array:
    """Mamba-2's output norm: ``y * silu(z)``, RMS-normalised over each of
    ``groups`` equal slices of the channels in float32, times ``w``; in
    ``z``'s dtype. y/z: (B, S, Di); w: (Di,)."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = g.shape
    g = g.reshape(*shape[:-1], groups, shape[-1] // groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shape).astype(z.dtype) * w.astype(z.dtype)


def ssd_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B_: jax.Array,
    C: jax.Array,
    *,
    chunk: int,
    h0: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD: the Mamba-2 recurrence over a whole sequence.

    x: (B, S, H, P); dt: (B, S, H) (after softplus); A: (H,) negative;
    B_/C: (B, S, G, N), each group shared by H // G heads; h0: (B, H, P, N).
    Returns (y (B, S, H, P) float32 without the D skip, h_last (B, H, P, N)
    float32). A ``lax.scan`` over chunks of ``chunk`` positions carries the
    state: inside a chunk the output is ``(C B^T o L) X`` with the decay
    matrix ``L[l, s] = exp(sum(dt A) over (s, l])``, plus the carried
    state's contribution; a sequence that is not a whole number of chunks
    is padded with dt = 0, which leaves the state unchanged.
    """
    Bsz, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    Hg = H // G
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        widths = lambda t: ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)  # noqa: E731
        x, dt, B_, C = (jnp.pad(t, widths(t)) for t in (x, dt, B_, C))
    n = (S + pad) // c
    f32 = jnp.float32
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), f32)

    def split(t):  # (B, n*c, ...) -> (n, B, c, ...)
        return t.reshape(Bsz, n, c, *t.shape[2:]).swapaxes(0, 1)

    xs = split((x.astype(f32) * dt[..., None]).reshape(Bsz, S + pad, G, Hg, P))
    a = split((dt * A[None, None, :]).reshape(Bsz, S + pad, G, Hg))
    Bs, Cs = split(B_.astype(f32)), split(C.astype(f32))
    tril = jnp.tril(jnp.ones((c, c), bool))

    @jax.checkpoint
    def chunk_fn(h, inp):
        xc, ac, Bc, Cc = inp                       # (B,c,G,Hg,P), (B,c,G,Hg), (B,c,G,N)
        cs = jnp.cumsum(ac, axis=1)                # (B,c,G,Hg)
        seg = cs[:, :, None] - cs[:, None, :]      # (B,l,s,G,Hg)
        decay = jnp.exp(jnp.where(tril[None, :, :, None, None], seg, -jnp.inf))
        cb = jnp.einsum("blgn,bsgn->blsg", Cc, Bc)
        y = jnp.einsum("blsg,blsgh,bsghp->blghp", cb, decay, xc)
        y = y + jnp.einsum("blgn,bghpn->blghp", Cc, h) * jnp.exp(cs)[..., None]
        last = cs[:, -1]                           # (B,G,Hg)
        into = jnp.exp(last[:, None] - cs)         # (B,c,G,Hg)
        h = (h * jnp.exp(last)[..., None, None]
             + jnp.einsum("bsgn,bsgh,bsghp->bghpn", Bc, into, xc))
        return h, y

    h, ys = jax.lax.scan(chunk_fn, h0.reshape(Bsz, G, Hg, P, N), (xs, a, Bs, Cs))
    y = ys.swapaxes(0, 1).reshape(Bsz, S + pad, H, P)[:, :S]
    return y, h.reshape(Bsz, H, P, N)


def _mamba2_in(x: jax.Array, p: Dict[str, jax.Array], d_inner: int, heads: int):
    """in_proj, split into the gate z, the conv's input xBC and raw dt."""
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:-heads]
    dt = zxbcdt[..., -heads:]
    return z, xbc, dt


def _mamba2_out(y, xs, z, p, *, groups: int, eps: float):
    """The D skip, the gated norm and out_proj. y/xs: (B, S, Di)."""
    y = y + xs.astype(jnp.float32) * jnp.repeat(
        p["D"].astype(jnp.float32), xs.shape[-1] // p["D"].shape[0])
    y = gated_rms_norm(y, z, p["norm"], groups, eps)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"])


def mamba2_block(
    x: jax.Array,
    p: Dict[str, jax.Array],
    *,
    heads: int,
    head_dim: int,
    state: int,
    groups: int,
    chunk: int,
    eps: float,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Mamba-2 mixer over a sequence. x: (B, S, D) -> (y (B, S, D),
    (conv_state (B, K-1, conv_dim), ssm_state (B, H, P, N) float32)): the
    decode state after the last position."""
    Bsz, S, _ = x.shape
    Di, GN = heads * head_dim, groups * state
    z, xbc, dt = _mamba2_in(x, p, Di, heads)
    K = p["conv_w"].shape[1]
    conv_state = jnp.pad(xbc, ((0, 0), (max(0, K - 1 - S), 0), (0, 0)))[:, -(K - 1):]
    xbc = _silu(causal_conv1d(xbc, p["conv_w"], p["conv_b"]))
    xs, B_, C = xbc[..., :Di], xbc[..., Di:Di + GN], xbc[..., Di + GN:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, h = ssd_scan(
        xs.reshape(Bsz, S, heads, head_dim), dt, A,
        B_.reshape(Bsz, S, groups, state), C.reshape(Bsz, S, groups, state),
        chunk=chunk)
    out = _mamba2_out(y.reshape(Bsz, S, Di), xs, z, p, groups=groups, eps=eps)
    return out, (conv_state, h)


def mamba2_decode_step(
    x: jax.Array,
    p: Dict[str, jax.Array],
    conv_state: jax.Array,
    ssm_state_v: jax.Array,
    *,
    heads: int,
    head_dim: int,
    state: int,
    groups: int,
    eps: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, 1, D); conv_state: (B, K-1, conv_dim); ssm_state_v: (B, H, P, N).

    Returns (y (B,1,D), new_conv_state, new_ssm_state).
    """
    Bsz = x.shape[0]
    Di, GN, Hg = heads * head_dim, groups * state, heads // groups
    z, xbc, dt = _mamba2_in(x, p, Di, heads)
    window = jnp.concatenate([conv_state, xbc], axis=1)     # (B,K,C)
    conv = jnp.einsum("bkc,ck->bc", window, p["conv_w"]) + p["conv_b"]
    conv = _silu(conv)                                      # (B,C)
    xs, B_, C = conv[:, :Di], conv[:, Di:Di + GN], conv[:, Di + GN:]
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt * A[None]).reshape(Bsz, groups, Hg)     # (B,G,Hg)
    xh = (xs.astype(jnp.float32).reshape(Bsz, heads, head_dim)
          * dt[..., None]).reshape(Bsz, groups, Hg, head_dim)
    Bg = B_.astype(jnp.float32).reshape(Bsz, groups, state)
    Cg = C.astype(jnp.float32).reshape(Bsz, groups, state)
    h = ssm_state_v.reshape(Bsz, groups, Hg, head_dim, state)
    h = h * dA[..., None, None] + xh[..., None] * Bg[:, :, None, None, :]
    y = jnp.einsum("bghpn,bgn->bghp", h, Cg).reshape(Bsz, 1, Di)
    out = _mamba2_out(y, xs[:, None], z, p, groups=groups, eps=eps)
    return out, window[:, 1:], h.reshape(Bsz, heads, head_dim, state)
