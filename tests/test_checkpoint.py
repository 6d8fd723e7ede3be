"""CheckpointManager: atomic saves, delta commits, snapshot restores."""
import numpy as np
import pytest

from repro.core.backend import BackendService
from repro.core.client import LocalServer
from repro.state.checkpoint import CheckpointManager


@pytest.fixture
def local(backend_factory):
    return LocalServer(backend_factory(block_size=512))


def state(v=0.0):
    return {
        "params": {"w": np.full((32, 32), v, np.float32)},
        "opt": {"m": np.zeros((32, 32), np.float32)},
        "count": np.int32(0),
    }


def test_save_restore_roundtrip(local):
    cm = CheckpointManager(local)
    s = state(1.5)
    info = cm.save(100, s)
    assert info.step == 100 and info.bytes_written > 0
    restored, step = cm.restore(state())
    assert step == 100
    np.testing.assert_array_equal(restored["params"]["w"], s["params"]["w"])


def test_latest_pointer_advances(local):
    cm = CheckpointManager(local)
    cm.save(1, state(1.0))
    cm.save(2, state(2.0))
    assert cm.latest_step() == 2
    restored, step = cm.restore(state())
    assert step == 2
    assert restored["params"]["w"][0, 0] == 2.0
    # explicit historical restore
    r1, _ = cm.restore(state(), step=1)
    assert r1["params"]["w"][0, 0] == 1.0


def test_delta_checkpoint_ships_fewer_bytes(local):
    cm = CheckpointManager(local)
    s = state(1.0)
    full = cm.save(1, s)
    s2 = {
        "params": {"w": s["params"]["w"].copy()},
        "opt": s["opt"],
        "count": s["count"],
    }
    s2["params"]["w"][0, 0] = 9.0  # tiny change
    delta = cm.save(2, s2)
    assert delta.bytes_written < full.bytes_written / 2
    restored, _ = cm.restore(state())
    assert restored["params"]["w"][0, 0] == 9.0


def test_delta_save_of_a_one_percent_run_writes_at_most_5pct_of_the_state(local):
    """1% of a float32 leaf's elements change as one contiguous run at a
    seeded offset (an updated layer slab or embedding rows): the delta
    save writes at most 5% of the full state's bytes, so its cost follows
    the write rate, not the state size. (A scattered 1% change would
    dirty every block.)"""
    rng = np.random.default_rng(20090)
    w = rng.normal(size=1 << 18).astype(np.float32)  # 1 MiB, 256 blocks
    cm = CheckpointManager(local, block_bytes=4096)
    full = cm.save(1, {"w": w})
    assert full.bytes_written == w.nbytes
    n = w.size // 100
    lo = int(rng.integers(0, w.size - n + 1))
    w2 = w.copy()
    w2[lo:lo + n] += rng.normal(size=n).astype(np.float32) * 0.01
    delta = cm.save(2, {"w": w2})
    assert delta.dirty is not None
    assert delta.bytes_written <= 0.05 * w.nbytes
    restored, step = cm.restore({"w": np.zeros_like(w)})
    assert step == 2
    np.testing.assert_array_equal(restored["w"], w2)


def test_restore_from_second_worker(local):
    cm = CheckpointManager(local)
    cm.save(5, state(5.0))
    other = LocalServer(local.backend)
    cm2 = CheckpointManager(other)
    restored, step = cm2.restore(state())
    assert step == 5 and restored["params"]["w"][0, 0] == 5.0


def test_sigkill_during_save_never_restores_torn_checkpoint(tmp_path):
    """SIGKILL a real server process mid-save-stream, restart it on the
    same WAL directory: every acked save survives, and the latest
    pointer names a FULLY committed checkpoint — all leaves from the
    SAME step, never a torn mix (saves are one atomic transaction)."""
    import os
    import subprocess
    import sys
    import threading
    import time
    from pathlib import Path

    from repro.core.remote import RemoteBackend

    repo_root = Path(__file__).resolve().parents[1]
    wal = tmp_path / "wal"

    def spawn():
        env = dict(os.environ)
        env["PYTHONPATH"] = str(repo_root / "src") + os.pathsep + \
            env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.core.server",
             "--wal", str(wal), "--block-size", "4096"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(repo_root),
        )
        line = proc.stdout.readline()
        assert line.startswith("LISTENING"), (line, proc.stderr.read())
        return proc, int(line.split()[1])

    proc, port = spawn()
    rb = RemoteBackend("127.0.0.1", port)
    cm = CheckpointManager(LocalServer(rb))
    acked = []
    stop = threading.Event()

    def save_loop():
        step = 0
        while not stop.is_set():
            step += 1
            try:
                cm.save(step, state(float(step)), delta_from_last=False)
            except Exception:
                return                     # server died mid-save: expected
            acked.append(step)

    t = threading.Thread(target=save_loop)
    t.start()
    deadline = time.monotonic() + 30
    while len(acked) < 3 and time.monotonic() < deadline:
        time.sleep(0.002)
    assert len(acked) >= 3
    proc.kill()                            # SIGKILL, mid-save with high odds
    proc.wait()
    stop.set()
    t.join()
    rb.close()

    proc2, port2 = spawn()                 # recovers checkpoint + WAL tail
    try:
        rb2 = RemoteBackend("127.0.0.1", port2)
        cm2 = CheckpointManager(LocalServer(rb2))
        step = cm2.latest_step()
        # acked saves are durable; a commit may outrun its lost ack, so
        # the recovered latest can only be >= the last acked step
        assert step is not None and step >= max(acked)
        restored, got = cm2.restore(state(), zero_copy=False)
        assert got == step
        np.testing.assert_array_equal(
            restored["params"]["w"],
            np.full((32, 32), float(step), np.float32),
        )
        np.testing.assert_array_equal(restored["opt"]["m"], 0)
        rb2.close()
    finally:
        proc2.kill()
        proc2.wait()


def test_delta_checkpoint_restores_blocks_it_did_not_write(local):
    """A delta step stores only its dirty blocks; every restore, latest or
    historical, reads the rest from the steps it is chained to."""
    cm = CheckpointManager(local, block_bytes=4096)
    s1 = {"w": np.arange(4096, dtype=np.float32)}          # 4 blocks
    cm.save(1, s1)
    s2 = {"w": s1["w"].copy()}
    s2["w"][0] = -1.0
    assert cm.save(2, s2).blocks_written == 1
    s3 = {"w": s2["w"].copy()}
    s3["w"][3000] = -2.0
    assert cm.save(3, s3).blocks_written == 1
    for step, want in ((3, s3), (2, s2), (1, s1)):
        for zero_copy in (True, False):
            got, _ = cm.restore({"w": None}, step=step, zero_copy=zero_copy)
            np.testing.assert_array_equal(got["w"], want["w"])


def test_resaving_a_step_keeps_the_steps_chained_to_it(local):
    """Saving a step again writes a new version of it: a delta step
    chained to the old version still restores exactly."""
    cm = CheckpointManager(local, block_bytes=4096)
    s1 = {"w": np.arange(4096, dtype=np.float32)}
    cm.save(1, s1)
    s2 = {"w": s1["w"].copy()}
    s2["w"][0] = -1.0
    assert cm.save(2, s2).dirty == {"w": [0]}
    again = CheckpointManager(LocalServer(local.backend), block_bytes=4096)
    s1b = {"w": s1["w"] + 10.0}
    info = again.save(1, s1b)
    assert info.dirty is None and info.bytes_written == info.bytes_total
    for step, want in ((2, s2), (1, s1b)):
        got, _ = cm.restore({"w": None}, step=step)
        np.testing.assert_array_equal(got["w"], want["w"])


def test_a_delta_chains_to_the_version_its_blocks_were_found_against(local):
    """Another writer re-saving the previous step between two saves does
    not change what the next delta is compared with or chained to."""
    cm = CheckpointManager(local, block_bytes=4096)
    s1 = {"w": np.arange(4096, dtype=np.float32)}
    cm.save(1, s1)
    other = CheckpointManager(LocalServer(local.backend), block_bytes=4096)
    other.save(1, {"w": s1["w"] + 10.0})
    s2 = {"w": s1["w"].copy()}
    s2["w"][0] = -1.0
    assert cm.save(2, s2).dirty == {"w": [0]}
    got, _ = cm.restore({"w": None}, step=2)
    np.testing.assert_array_equal(got["w"], s2["w"])


def test_delta_chain_is_cut_by_a_full_save():
    from repro.core.tensorstate import DELTA_CHAIN_MAX

    cm = CheckpointManager(LocalServer(BackendService(block_size=512)),
                           block_bytes=4096)
    s = {"w": np.zeros(4096, np.float32)}
    infos = []
    for step in range(DELTA_CHAIN_MAX + 2):
        s = {"w": s["w"].copy()}
        s["w"][step] += 1.0
        infos.append(cm.save(step, s))
    full = [i.step for i in infos if i.bytes_written == i.bytes_total]
    assert full == [0, DELTA_CHAIN_MAX + 1]
    got, _ = cm.restore({"w": None})
    np.testing.assert_array_equal(got["w"], s["w"])


def test_kernel_detector_writes_what_the_numpy_compare_writes():
    rng = np.random.default_rng(3)
    base = {
        "w": rng.normal(size=(8, 1024)).astype(np.float32),   # 8 blocks
        "small": np.ones(10, np.float32),                     # < 1 block
        "n": np.int32(3),
    }
    new = {k: np.array(v) for k, v in base.items()}
    new["w"][2, 5] += 1.0
    new["w"][7, 1000] += 1.0
    new["small"][0] = 2.0
    infos = {}
    for impl in ("auto", "pallas_interpret"):
        cm = CheckpointManager(LocalServer(BackendService(block_size=512)),
                               block_bytes=4096, dirty_impl=impl)
        cm.save(0, base)
        infos[impl] = cm.save(1, new)
    auto, kern = infos["auto"], infos["pallas_interpret"]
    assert auto.dirty["w"] == [2, 7]
    assert (kern.dirty, kern.blocks_written, kern.bytes_written) == \
        (auto.dirty, auto.blocks_written, auto.bytes_written)


def test_a_failing_kernel_detector_raises(monkeypatch):
    from repro.kernels.block_delta import ops

    def refused(*args, **kwargs):
        raise RuntimeError("kernel refused by the compiler")

    monkeypatch.setattr(ops, "compute_block_delta", refused)
    cm = CheckpointManager(LocalServer(BackendService(block_size=512)),
                           block_bytes=4096, dirty_impl="pallas")
    s = {"w": np.zeros((8, 1024), np.float32)}
    cm.save(0, s)
    with pytest.raises(RuntimeError, match="refused"):
        cm.save(1, {"w": s["w"] + 1.0})


@pytest.mark.parametrize("change", ["nan", "signed_zero", "tiny", "nan_payload"])
def test_kernel_detector_sees_changes_that_square_to_zero(change):
    """Dirty means the bits differ: the kernel flags a NaN, a sign flip
    of zero and a change whose square underflows, exactly where the
    numpy byte compare does — and an unchanged NaN nowhere."""
    from repro.state.checkpoint import dirty_block_indices

    old = np.zeros((4, 1024), np.float32)      # 4 blocks of 4 KiB
    old[3, 7] = np.nan
    new = old.copy()
    if change == "nan":
        new[1, 5] = np.nan
    elif change == "signed_zero":
        new[1, 5] = -0.0
    elif change == "tiny":
        new[1, 5] = 1e-30                      # 1e-60 flushes to zero
    else:
        new.view(np.uint32)[3, 7] ^= 1         # another NaN bit pattern
    want = [3] if change == "nan_payload" else [1]
    auto = dirty_block_indices(new, old, 4096, "auto")
    kern = dirty_block_indices(new, old, 4096, "pallas_interpret")
    assert auto == kern == want


def _tree(ring):
    """{name: [span, ...]} and a parent-name lookup over a ring's spans."""
    spans = ring.spans()
    by_id = {s["sp"]: s for s in spans}
    names = {}
    for s in spans:
        names.setdefault(s["n"], []).append(s)
    return names, lambda s: by_id.get(s["pa"], {}).get("n")


def test_save_and_restore_record_their_span_tree_under_profiler(tmp_path, monkeypatch):
    jax = pytest.importorskip("jax")
    from repro.core import obs

    ring = obs.SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    cm = CheckpointManager(LocalServer(BackendService(block_size=512)),
                           block_bytes=512, dirty_impl="xla")
    s = state(1.0)
    cm.save(1, s)
    assert ring.spans() == []  # no profiler, no context: nothing recorded
    s["params"]["w"] = s["params"]["w"].copy()
    s["params"]["w"][3, 3] = 9.0
    jax.profiler.start_trace(str(tmp_path))
    try:
        info = cm.save(2, s)
        cm.restore(state(), 2)
    finally:
        jax.profiler.stop_trace()
    assert info.dirty is not None
    names, parent = _tree(ring)
    save, = names["ckpt.save"]
    assert save["pa"] == 0
    for child in ("ckpt.detect", "ckpt.commit", "ckpt.baseline"):
        sp, = names[child]
        assert parent(sp) == "ckpt.save" and sp["tr"] == save["tr"]
    detect, = names["ckpt.detect"]
    assert detect["ar"] == {"leaves": 3, "bytes": 2 * 32 * 32 * 4 + 4}
    assert parent(names["tensor.plan"][0]) == "ckpt.commit"
    stage, = names["tensor.stage"]
    assert parent(stage) == "invoke.attempt"
    attempt = next(a for a in names["invoke.attempt"] if a["sp"] == stage["pa"])
    assert parent(attempt) == "invoke.write"
    assert any(parent(c) == "invoke.attempt" and c["pa"] == attempt["sp"]
               for c in names["invoke.commit"])
    restore, = names["ckpt.restore"]
    assert restore["pa"] == 0 and restore["tr"] != save["tr"]
    load, = names["tensor.load"]
    assert load["tr"] == restore["tr"]
    lands = names["tensor.land"]
    assert len(lands) == 3 and all(parent(x) == "tensor.load" for x in lands)
    assert sorted(x["ar"]["bytes"] for x in lands) == [4, 4096, 4096]
