"""The serving launcher's ``decode_fn`` keeps the served version on the
device: one upload per version, reused while the same leaves come back,
and never a stale copy after a refresh."""
import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduced_config
from repro.core import obs
from repro.core.backend import BackendService
from repro.core.client import LocalServer
from repro.launch.serve import make_decode_fn, publish
from repro.models import model as M
from repro.serving.engine import SnapshotServer

TOKENS = 4


@pytest.fixture(scope="module")
def cfg():
    return reduced_config(get_config("qwen2-1.5b"), num_layers=2, d_model=32,
                          d_ff=64, vocab_size=64)


def host_params(cfg, seed):
    return jax.device_get(M.init_params(cfg, jax.random.PRNGKey(seed)))


def amplified(params):
    """The layers' matrices times 8: at this size the residual stream no
    longer decides the greedy tokens, so they change."""
    out = jax.tree.map(lambda a: a * 8 if a.ndim >= 2 else a, params)
    out["embed"] = params["embed"]
    return out


def state_bytes(params):
    return sum(a.nbytes for a in jax.tree.leaves(params))


@pytest.fixture
def ring(monkeypatch):
    ring = obs.SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    return ring


def uploads(ring, fn):
    """``fn()`` under a root span; the ``bytes`` of each ``replica.upload``."""
    ring.clear()
    with obs.span("test", root=True):
        out = fn()
    return out, [s["ar"]["bytes"] for s in ring.spans() if s["n"] == "replica.upload"]


def test_one_upload_per_version_and_none_stale(cfg, ring):
    old = host_params(cfg, 0)
    new = amplified(old)
    be = BackendService(block_size=4096)
    publish(LocalServer(be), old)
    srv = SnapshotServer(LocalServer(be), make_decode_fn(cfg, TOKENS),
                         {"params": old})
    srv.refresh()
    prompts = np.arange(4, dtype=np.int32)[:, None]

    outs, got = uploads(ring, lambda: [srv.serve(prompts) for _ in range(5)])
    assert got == [state_bytes(old), 0, 0, 0, 0]
    assert all(np.array_equal(o, outs[0]) for o in outs)

    publish(LocalServer(be), new)
    srv.refresh()
    out, got = uploads(ring, lambda: srv.serve(prompts))
    assert got == [state_bytes(new)]
    fresh = make_decode_fn(cfg, TOKENS)(srv.params, prompts)
    np.testing.assert_array_equal(out, fresh)
    assert not np.array_equal(out, outs[0])


def test_new_leaves_upload_and_same_leaves_in_a_new_dict_do_not(cfg, ring):
    params = host_params(cfg, 0)
    decode_fn = make_decode_fn(cfg, TOKENS)
    prompts = np.zeros((2, 1), np.int32)
    first, got = uploads(ring, lambda: decode_fn({"params": params}, prompts))
    assert got == [state_bytes(params)]

    out, got = uploads(ring, lambda: decode_fn({"params": dict(params)}, prompts))
    assert got == [0]
    np.testing.assert_array_equal(out, first)

    copy = jax.tree.map(np.array, params)
    assert all(a is not b for a, b in zip(jax.tree.leaves(copy), jax.tree.leaves(params)))
    out, got = uploads(ring, lambda: decode_fn({"params": copy}, prompts))
    assert got == [state_bytes(params)]
    np.testing.assert_array_equal(out, first)
