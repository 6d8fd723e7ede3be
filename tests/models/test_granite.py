"""Granite on the model's normal path: Mamba-2 (SSD) mixers interleaved
with NoPE attention layers, Granite's four multipliers, prefill into a
decode cache of two kinds of state, and the serving launcher over it.

The comparison with the benchmark's plain float32 reference is in
``bench/tests/test_bench_granite.py``; here the program is held to its own
recurrences."""
import dataclasses
import hashlib
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced_config
from repro.core import obs
from repro.launch.serve import make_decode_fn
from repro.models import model as M
from repro.models import ssm

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def hybrid():
    """Two periods of a Mamba-2 / attention pattern at a tiny width."""
    return reduced_config(get_config("granite-4.0-h-micro"),
                          layer_types=("mamba", "mamba", "attention") * 2)


def _recurrence(x, dt, A, B_, C, h):
    """The SSD recurrence one position at a time, in float64 numpy."""
    x, dt, A, B_, C, h = (np.asarray(a, np.float64) for a in (x, dt, A, B_, C, h))
    heads_per_group = x.shape[2] // B_.shape[2]
    Bh = np.repeat(B_, heads_per_group, axis=2)
    Ch = np.repeat(C, heads_per_group, axis=2)
    ys = []
    for t in range(x.shape[1]):
        decay = np.exp(dt[:, t] * A)[..., None, None]
        h = decay * h + (dt[:, t, :, None] * x[:, t])[..., None] * Bh[:, t, :, None, :]
        ys.append(np.einsum("bhpn,bhn->bhp", h, Ch[:, t]))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("S,chunk,groups", [(32, 8, 1), (21, 8, 2), (5, 8, 1), (16, 16, 4)])
def test_chunked_ssd_scan_matches_the_recurrence(S, chunk, groups):
    """The chunked scan (whole chunks, a padded last chunk, one short
    chunk; grouped B/C) equals the one-step recurrence from a nonzero
    state. Both in float32 against float64: a sum of S decayed terms."""
    rng = np.random.default_rng(S)
    Bsz, H, P, N = 2, 4, 3, 5
    x = rng.standard_normal((Bsz, S, H, P)).astype(np.float32)
    dt = rng.uniform(0.01, 0.5, (Bsz, S, H)).astype(np.float32)
    A = -rng.uniform(0.5, 4.0, H).astype(np.float32)
    B_ = rng.standard_normal((Bsz, S, groups, N)).astype(np.float32)
    C = rng.standard_normal((Bsz, S, groups, N)).astype(np.float32)
    h0 = rng.standard_normal((Bsz, H, P, N)).astype(np.float32)
    y, h = ssm.ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A), jnp.asarray(B_),
                        jnp.asarray(C), chunk=chunk, h0=jnp.asarray(h0))
    y_ref, h_ref = _recurrence(x, dt, A, B_, C, h0)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), h_ref, rtol=1e-4, atol=1e-4)


def test_mamba2_prefill_state_continues_as_the_decode_steps(hybrid):
    """A mixer's prefill over S positions, then one decode step, equals S+1
    decode steps from an empty state: output, conv window and SSM state."""
    p = jax.tree.map(lambda a: a[0], M.init_params(hybrid, jax.random.PRNGKey(1))["layers"]["mamba"])
    dims = dict(heads=hybrid.ssm_heads, head_dim=hybrid.ssm_head_dim,
                state=hybrid.ssm_state, groups=hybrid.ssm_groups, eps=hybrid.norm_eps)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, hybrid.d_model), jnp.float32)
    y_pre, (conv, h) = ssm.mamba2_block(x[:, :11], p, chunk=hybrid.ssm_chunk, **dims)
    conv0 = jnp.zeros((2, hybrid.ssm_conv - 1, hybrid.ssm_conv_dim), jnp.float32)
    h0 = jnp.zeros((2, hybrid.ssm_heads, hybrid.ssm_head_dim, hybrid.ssm_state), jnp.float32)
    steps = []
    for t in range(12):
        y, conv0, h0 = ssm.mamba2_decode_step(x[:, t:t + 1], p, conv0, h0, **dims)
        steps.append(y)
        if t == 10:
            np.testing.assert_allclose(conv, conv0, rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(h, h0, rtol=1e-5, atol=1e-6)
    y_last, _, _ = ssm.mamba2_decode_step(x[:, 11:], p, conv, h, **dims)
    np.testing.assert_allclose(y_pre, jnp.concatenate(steps[:11], 1), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(y_last, steps[11], rtol=1e-4, atol=1e-5)


def test_decode_cache_holds_each_kind_for_its_own_layers(hybrid):
    cache = M.make_decode_cache(hybrid, 3, 10)
    La, Lm = len(hybrid.layer_slots("attention")), len(hybrid.layer_slots("mamba"))
    assert (La, Lm) == (2, 4)
    assert cache["k"].shape == (La, 3, 10, hybrid.num_kv_heads, hybrid.head_dim)
    assert cache["conv"].shape == (Lm, 3, hybrid.ssm_conv - 1, hybrid.ssm_conv_dim)
    assert cache["ssm"].shape == (Lm, 3, hybrid.ssm_heads, hybrid.ssm_head_dim,
                                  hybrid.ssm_state)
    params = M.init_params(hybrid, jax.random.PRNGKey(0))
    assert params["layers"]["attn"]["wq"].shape[0] == La
    assert params["layers"]["mamba"]["in_proj"].shape[0] == Lm
    assert params["layers"]["mlp"]["wi"].shape[0] == hybrid.num_layers


@pytest.mark.parametrize("arch", ["granite-4.0-h-micro", "granite-3-2b"])
def test_prefill_then_decode_through_the_cache_matches_decode_alone(arch, hybrid):
    """Prefill of P tokens into a ``max_seq`` cache, then decoding the rest
    through it, gives the logits of decoding every token from an empty
    cache, at every position after the prompt (float32 compute: the two
    paths differ only in summation order)."""
    cfg = hybrid if arch.startswith("granite-4") else reduced_config(get_config(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    B, S, P = 2, 20, 11
    toks = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab_size)
    f32 = dict(compute_dtype=jnp.float32)
    cache = M.make_decode_cache(cfg, B, S, jnp.float32)
    alone = []
    for i in range(S):
        logits, cache = M.decode_step(cfg, params, cache, toks[:, i:i + 1], jnp.int32(i), **f32)
        alone.append(logits)
    logits, cache = M.prefill(cfg, params, toks[:, :P], max_seq=S, q_chunk=0, **f32)
    through = [logits]
    for i in range(P, S):
        logits, cache = M.decode_step(cfg, params, cache, toks[:, i:i + 1], jnp.int32(i), **f32)
        through.append(logits)
    np.testing.assert_allclose(np.stack(through), np.stack(alone[P - 1:]), rtol=1e-4, atol=1e-6)


def test_multipliers_enter_the_decode_program():
    """Granite's multipliers change the lowered decode step; without them
    it is the program it was (``test_qwen2_decode_step_lowers_as_before``)."""
    base = reduced_config(get_config("qwen2-1.5b"))
    granite = dataclasses.replace(base, embedding_multiplier=12.0, residual_multiplier=0.22,
                                  attention_multiplier=0.015625, logits_scaling=8.0)
    assert _lowered_decode(base) != _lowered_decode(granite)


def _lowered_decode(cfg, batch=8, max_len=40):
    def decode_one(p, cache, tok, idx):
        return M.decode_step(cfg, p, cache, tok, idx)

    params = M.abstract_params(cfg, jnp.bfloat16)
    cache = jax.eval_shape(lambda: M.make_decode_cache(cfg, batch, max_len))
    tok = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    idx = jax.ShapeDtypeStruct((), jnp.int32)
    return jax.jit(decode_one).lower(params, cache, tok, idx).as_text()


#: sha256 of the lowered decode step of qwen2-1.5b at the benchmark's
#: shapes (4 layers at full width, batch 8, 32 tokens + 8), taken from the
#: program before interleaved layers and multipliers existed
QWEN2_DECODE_SHA256 = "5e1c4eb9a5e600578cd736c3b34bdda16388c341155e22c26d6f2317d4e0e041"


def test_qwen2_decode_step_lowers_as_before():
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), num_layers=4)
    text = _lowered_decode(cfg)
    assert hashlib.sha256(text.encode()).hexdigest() == QWEN2_DECODE_SHA256


@pytest.fixture
def ring(monkeypatch):
    ring = obs.SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    return ring


@pytest.mark.parametrize("prompt", [1, 9])
def test_decode_fn_prefills_longer_prompts_and_reports_both_states(hybrid, ring, prompt):
    params = jax.device_get(M.init_params(hybrid, jax.random.PRNGKey(5)))
    prompts = np.random.default_rng(0).integers(0, hybrid.vocab_size, (3, prompt), np.int32)
    decode_fn = make_decode_fn(hybrid, 6)
    with obs.span("test", root=True):
        out = decode_fn({"params": params}, prompts)
    assert out.shape == (3, prompt + 6) and np.array_equal(out[:, :prompt], prompts)
    spans = {s["n"]: s for s in ring.spans()}
    assert ("replica.prefill" in spans) == (prompt > 1)
    args = spans["replica.decode"]["ar"]
    cache = M.make_decode_cache(hybrid, 3, prompt - 1 + 6 + 8)
    assert args["kv_bytes"] == cache["k"].nbytes + cache["v"].nbytes > 0
    assert args["ssm_bytes"] == cache["conv"].nbytes + cache["ssm"].nbytes > 0


def test_serve_cli_runs_granite_4_with_a_prefilled_prompt(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               TMPDIR=str(tmp_path))
    r = subprocess.run([sys.executable, "-m", "repro.launch.serve", "--arch",
                        "granite-4.0-h-micro", "--prompt", "10", "--tokens", "4"],
                       cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert "arch=granite-4.0-h-micro-reduced" in r.stdout
    rows = [l for l in r.stdout.splitlines() if l.strip().startswith("[")]
    assert len(rows) == 2 and all(len(eval(row)) == 14 for row in rows)
