"""The serving launcher's ``decode_fn`` dispatches a request's decode
steps ahead and reads its ids back once: the ids equal those of a loop
that reads every token back before the next step, and the jitted step
still runs once per decoded token under the HLO module name that the
benchmark's ``decode_mfu`` readers count."""
import ast
import functools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced_config
from repro.core import obs
from repro.launch import serve as S
from repro.models import model as M

REPO = Path(__file__).resolve().parents[1]
TOKENS, BATCH = 6, 3

#: a tiny dense GQA model and a tiny Mamba-2 / attention hybrid, each with
#: the bench reader of its decode FLOPs
CONFIGS = {
    "qwen2": (lambda: reduced_config(get_config("qwen2-1.5b")), "decode_mfu.py"),
    "granite-4-hybrid": (lambda: reduced_config(
        get_config("granite-4.0-h-micro"), layer_types=("mamba", "mamba", "attention") * 2),
        "hybrid.decode_mfu.py"),
}


@pytest.fixture(scope="module", params=list(CONFIGS))
def model(request):
    make, reader = CONFIGS[request.param]
    cfg = make()
    params = jax.device_get(M.init_params(cfg, jax.random.PRNGKey(3)))
    return cfg, params, reader


@pytest.fixture
def ring(monkeypatch):
    ring = obs.SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    return ring


def prompts_of(cfg, length):
    return np.random.default_rng(length).integers(
        0, cfg.vocab_size, (BATCH, length), dtype=np.int32)


def reference(cfg, params, prompts, tokens):
    """Greedy decode that reads each token back to the host before it
    dispatches the next step: ``M.prefill`` for a prompt longer than one
    token, then ``M.decode_step`` per token, into ``decode_fn``'s cache
    layout."""
    start = prompts.shape[1]
    max_len = start - 1 + tokens + 8
    step = jax.jit(functools.partial(M.decode_step, cfg))
    p = jax.device_put(params)
    out = [prompts]
    if start > 1:
        logits, cache = jax.jit(functools.partial(M.prefill, cfg, max_seq=max_len))(
            p, jnp.asarray(prompts))
        toks = np.asarray(jnp.argmax(logits, axis=-1)[:, None])
        out.append(toks)
        steps = range(start, start + tokens - 1)
    else:
        cache = M.make_decode_cache(cfg, prompts.shape[0], max_len)
        toks = prompts[:, :1]
        steps = range(tokens)
    for i in steps:
        logits, cache = step(p, cache, jnp.asarray(toks), jnp.int32(i))
        toks = np.asarray(jnp.argmax(logits, axis=-1)[:, None])
        out.append(toks)
    return np.concatenate(out, axis=1)


def counted_decode_fn(monkeypatch, cfg, tokens):
    """``make_decode_fn(cfg, tokens)`` with each function it jits wrapped
    to record its calls: ``(decode_fn, {name: jitted}, [(name, args)])``."""
    jit, jitted, calls = jax.jit, {}, []

    def counting_jit(fun=None, **kw):
        if fun is None:
            return functools.partial(counting_jit, **kw)
        jitted[fun.__name__] = j = jit(fun, **kw)

        def call(*args):
            calls.append((fun.__name__, args))
            return j(*args)
        return call

    with monkeypatch.context() as m:
        m.setattr(jax, "jit", counting_jit)
        decode_fn = S.make_decode_fn(cfg, tokens)
    return decode_fn, jitted, calls


@pytest.mark.parametrize("prompt", [1, 7])
def test_pipelined_decode_serves_the_per_token_read_ids(model, ring, prompt):
    cfg, params, _ = model
    prompts = prompts_of(cfg, prompt)
    with obs.span("test", root=True):
        out = S.make_decode_fn(cfg, TOKENS)({"params": params}, prompts)
    want = reference(cfg, params, prompts, TOKENS)
    assert out.dtype == np.int32 and out.shape == (BATCH, prompt + TOKENS)
    np.testing.assert_array_equal(out, want)
    assert len(np.unique(out[:, prompt:])) > 1  # the greedy ids are not one repeated id

    spans = {s["n"]: s for s in ring.spans()}
    assert ("replica.prefill" in spans) == (prompt > 1)
    args = spans["replica.decode"]["ar"]
    assert args["host_reads"] == 1
    cache = M.make_decode_cache(cfg, BATCH, prompt - 1 + TOKENS + 8)
    mamba = M.KIND_CACHE["mamba"]
    assert args["ssm_bytes"] == sum(a.nbytes for k, a in cache.items() if k in mamba)
    assert args["kv_bytes"] == sum(a.nbytes for k, a in cache.items() if k not in mamba) > 0
    assert (args["ssm_bytes"] > 0) == bool(cfg.layer_types)


def reader_program(reader):
    """``PROGRAM`` of a bench metric reader, read from its source."""
    tree = ast.parse((REPO / "bench" / "metrics" / reader).read_text())
    for node in tree.body:
        if isinstance(node, ast.Assign) and [t.id for t in node.targets] == ["PROGRAM"]:
            return ast.literal_eval(node.value)
    raise AssertionError(f"{reader} names no PROGRAM")


@pytest.mark.parametrize("prompt", [1, 7])
def test_decode_step_runs_once_per_token_as_the_program_the_readers_count(
        model, monkeypatch, prompt):
    """The bench's ``decode_mfu`` and ``hybrid.decode_mfu`` credit each
    execution of ``PROGRAM`` with one decode step, step ``j`` of a request
    at position ``start + j``: a fused or renamed loop would empty them."""
    cfg, params, reader = model
    decode_fn, jitted, calls = counted_decode_fn(monkeypatch, cfg, TOKENS)
    decode_fn({"params": params}, prompts_of(cfg, prompt))
    steps = [args for name, args in calls if name == "decode_one"]
    assert [int(a[3]) for a in steps] == (
        list(range(prompt, prompt + TOKENS - 1)) if prompt > 1 else list(range(TOKENS)))
    assert [name for name, _ in calls].count("prefill_prompt") == (prompt > 1)
    hlo = jitted["decode_one"].lower(*steps[0]).compile().as_text()
    assert hlo.split(None, 2)[:2] == ["HloModule", reader_program(reader) + ","]
