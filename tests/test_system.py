"""End-to-end system test: the paper's architecture, assembled.

A tiny real JAX model trains through the transactional loop (each step a
function-grained FaaSFS transaction with delta commits), checkpoints
atomically, serves from a pinned snapshot while training continues, and
survives a simulated worker crash mid-step.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeCell, get_config, reduced_config
from repro.core.backend import BackendService
from repro.core.client import LocalServer
from repro.core.posix import FaaSFS
from repro.core.runtime import runtime_for
from repro.core.types import CachePolicy
from repro.data.pipeline import DataConfig, synth_batch
from repro.models import model as M
from repro.models.runtime import CellPlan, make_train_step
from repro.optim import adamw
from repro.serving.engine import SnapshotServer
from repro.state.checkpoint import CheckpointManager
from repro.train.loop import TransactionalTrainer


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = reduced_config(get_config("qwen2-1.5b"), num_layers=2, d_model=32,
                         d_ff=64, vocab_size=64)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw.init_opt_state(params)}
    plan = CellPlan(cfg, ShapeCell("t", "train", 32, 4), None, {}, M.NO_SHARDING, 0, 16)
    jit_step = jax.jit(
        make_train_step(plan, adamw.AdamWConfig(lr_peak=3e-3, warmup_steps=2, decay_steps=50))
    )
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
    return cfg, state, jit_step, dcfg


def np_state(state):
    return jax.tree.map(np.asarray, state)


def test_full_stack_train_checkpoint_serve(tiny_setup):
    cfg, state0, jit_step, dcfg = tiny_setup
    be = BackendService(block_size=4096, policy=CachePolicy.EAGER)
    local = LocalServer(be)

    def train_step(state, batch):
        new_state, metrics = jit_step(state, batch)
        return new_state, {k: float(v) for k, v in metrics.items()}

    trainer = TransactionalTrainer(local, train_step, np_state(state0))
    trainer.init(np_state(state0))

    # train a few transactional steps
    losses = []
    for i in range(4):
        res = trainer.step(synth_batch(dcfg, i))
        losses.append(res.metrics["loss"])
        assert res.attempts >= 1
    assert losses[-1] < losses[0]

    # atomic checkpoint + snapshot restore
    cm = CheckpointManager(local)
    final = trainer.read_state()
    info = cm.save(4, final)
    assert info.bytes_written > 0
    restored, step = cm.restore(np_state(state0))
    assert step == 4
    np.testing.assert_array_equal(
        restored["params"]["embed"], final["params"]["embed"]
    )

    # snapshot serving while training keeps committing
    def decode_fn(state, batch):
        jparams = jax.tree.map(jnp.asarray, state["params"])
        logits, _ = M.prefill(cfg, jparams, jnp.asarray(batch), q_chunk=0)
        return np.asarray(logits)

    srv = SnapshotServer(LocalServer(be), decode_fn, np_state(state0))
    srv.refresh()
    toks = synth_batch(dcfg, 99)["tokens"][:, :16]
    out1 = srv.serve(toks)
    trainer.step(synth_batch(dcfg, 5))           # concurrent commit
    out_same = srv.serve(toks)                    # pinned snapshot unchanged
    np.testing.assert_array_equal(out1, out_same)
    srv.refresh()
    out2 = srv.serve(toks)
    assert not np.array_equal(out1, out2)


def test_crash_mid_step_leaves_no_partial_state(tiny_setup):
    cfg, state0, jit_step, dcfg = tiny_setup
    be = BackendService(block_size=4096)
    local = LocalServer(be)

    def train_step(state, batch):
        return jit_step(state, batch)

    trainer = TransactionalTrainer(local, train_step, np_state(state0))
    trainer.init(np_state(state0))
    before = trainer.read_state()

    class Boom(RuntimeError):
        pass

    def crashing(fs: FaaSFS):
        from repro.core.tensorstate import TensorStore
        st = TensorStore(fs, prefix="/mnt/tsfs/train")
        flat = st.load("state")
        # mutate every leaf, then crash before commit
        st.save("state", {n: np.asarray(a) + 1 for n, a in flat.items()})
        raise Boom()

    with pytest.raises(Boom):
        runtime_for(local).invoke(crashing)

    after = trainer.read_state()
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)   # nothing leaked


def test_two_workers_shared_state_makes_progress(tiny_setup):
    cfg, state0, jit_step, dcfg = tiny_setup
    be = BackendService(block_size=65536, policy=CachePolicy.EAGER)

    def train_step(state, batch):
        return jit_step(state, batch)

    trainers = [
        TransactionalTrainer(LocalServer(be), train_step, np_state(state0))
        for _ in range(2)
    ]
    trainers[0].init(np_state(state0))

    def run(tr, base):
        for i in range(3):
            tr.step(synth_batch(dcfg, base + i))

    ts = [threading.Thread(target=run, args=(t, 100 * i)) for i, t in enumerate(trainers)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    final = trainers[0].read_state()
    # every committed step counted once despite conflicts
    assert int(np.asarray(final["opt"]["count"])) == 6


def test_replica_records_refresh_upload_and_decode_spans_under_profiler(
        tiny_setup, tmp_path, monkeypatch):
    from repro.core import obs
    from repro.launch.serve import make_decode_fn, publish

    cfg, state0, _, _ = tiny_setup
    ring = obs.SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    be = BackendService(block_size=4096)
    publish(LocalServer(be), np_state(state0)["params"])
    srv = SnapshotServer(LocalServer(be), make_decode_fn(cfg, 2),
                         {"params": np_state(state0)["params"]})
    prompts = np.zeros((2, 1), np.int32)
    srv.refresh()
    srv.serve(prompts)  # compiles outside the trace
    assert ring.spans() == []
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.refresh()
        srv.serve(prompts)
    finally:
        jax.profiler.stop_trace()
    spans = ring.spans()
    by_id = {s["sp"]: s for s in spans}
    parent = lambda s: by_id.get(s["pa"], {}).get("n")  # noqa: E731
    names = [s["n"] for s in spans]
    assert names.count("replica.refresh") == 1
    assert names.count("replica.upload") == names.count("replica.decode") == 1
    root = next(s for s in spans if s["n"] == "replica.refresh")
    lands = [s for s in spans if s["n"] == "tensor.land"]
    assert lands and all(parent(s) == "tensor.load" and s["tr"] == root["tr"]
                         for s in lands)
    assert sum(s["ar"]["bytes"] for s in lands) == sum(
        a.nbytes for a in jax.tree.leaves(np_state(state0)["params"]))
    up, dec = (next(s for s in spans if s["n"] == n)
               for n in ("replica.upload", "replica.decode"))
    assert up["pa"] == dec["pa"] == 0 and up["ts"] + up["du"] <= dec["ts"]


def test_replica_refresh_by_delta_records_what_it_landed(tiny_setup, monkeypatch):
    """After a publish of ~1% of the blocks, the replica lands only those:
    ``replica.refresh`` says ``delta`` 1 and the ``tensor.land`` spans'
    ``landed`` sums to its ``bytes_landed``. A refresh of the same version
    keeps every leaf, so the next request uploads nothing."""
    from repro.core import obs
    from repro.core.tensorstate import flatten_with_names, unflatten_like
    from repro.launch.serve import make_decode_fn, publish

    cfg, state0, _, _ = tiny_setup
    ring = obs.SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    be = BackendService(block_size=256)
    params = np_state(state0)["params"]
    publish(LocalServer(be), params)
    srv = SnapshotServer(LocalServer(be), make_decode_fn(cfg, 2),
                         {"params": params})
    prompts = np.zeros((2, 1), np.int32)
    srv.refresh()
    srv.serve(prompts)
    old = dict(flatten_with_names({"params": params}))
    state_bytes = sum(a.nbytes for a in old.values())
    new = dict(old)
    for name in sorted(old, key=lambda n: -old[n].nbytes)[:4]:
        new[name] = old[name].copy()
        new[name].reshape(-1)[0] += 1.0
    publish(LocalServer(be), unflatten_like({"params": params}, new)["params"],
            baseline=old)

    def traced():
        tid = obs.new_trace_id()
        prev = obs.set_trace((tid, 1))
        try:
            srv.refresh()
            srv.serve(prompts)
        finally:
            obs.set_trace(prev)
        spans = ring.spans(tid)
        one = lambda n: next(s for s in spans if s["n"] == n)  # noqa: E731
        return (one("replica.refresh")["ar"], one("replica.upload")["ar"],
                [s["ar"] for s in spans if s["n"] == "tensor.land"])

    refresh, upload, lands = traced()
    assert refresh["delta"] == 1 and refresh["versions"] == 1
    assert 0 < refresh["bytes_landed"] < 0.05 * state_bytes
    assert sum(a["landed"] for a in lands) == refresh["bytes_landed"]
    assert sum(a["copied"] for a in lands) == refresh["bytes_copied"]
    assert sum(a["bytes"] for a in lands) == state_bytes
    assert refresh["leaves_kept"] == len(old) - 4
    assert upload["bytes"] == state_bytes
    served = dict(flatten_with_names(srv.params))
    assert all(served[n].tobytes() == new[n].tobytes() for n in new)
    refresh, upload, lands = traced()
    assert (refresh["delta"], refresh["versions"], refresh["bytes_landed"],
            refresh["leaves_kept"]) == (1, 0, 0, len(old))
    assert upload["bytes"] == 0
