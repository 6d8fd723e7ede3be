"""TensorState: pytree <-> blocks, delta saves, snapshot loads, and the
zero-copy arena restore path — over every backend kind (in-process,
sharded, real-socket remote, multi-process cluster)."""
import gc

import numpy as np
import pytest

from repro.core.arena import BlockArena
from repro.core.client import LocalServer
from repro.core.posix import FaaSFS
from repro.core.runtime import runtime_for
from repro.core.tensorstate import TensorStore, flatten_with_names, unflatten_like


@pytest.fixture
def local(backend_factory):
    return LocalServer(backend_factory(block_size=256))


def tree():
    rng = np.random.default_rng(0)
    return {
        "w": {"a": rng.normal(size=(16, 8)).astype(np.float32),
              "b": rng.normal(size=(64,)).astype(np.float32)},
        "step": np.int32(7),
    }


def test_roundtrip(local):
    t = tree()

    def save(fs):
        TensorStore(fs).save("m", t)

    runtime_for(local).invoke(save)
    out = {}

    def load(fs):
        out["flat"] = TensorStore(fs).load("m")

    runtime_for(local).invoke(load, read_only=True)
    restored = unflatten_like(t, out["flat"])
    for (n1, a), (n2, b) in zip(flatten_with_names(t), flatten_with_names(restored)):
        assert n1 == n2
        np.testing.assert_array_equal(a, b)


def test_delta_save_writes_only_dirty_blocks(local):
    t = tree()
    stats = {}

    def save_full(fs):
        stats["full"] = TensorStore(fs).save("m", t, block_bytes=256)

    runtime_for(local).invoke(save_full)

    # mutate a few elements of one leaf only
    t2 = {"w": {"a": t["w"]["a"].copy(), "b": t["w"]["b"].copy()},
          "step": t["step"]}
    t2["w"]["a"][0, 0] += 1.0
    baseline = {n: a for n, a in flatten_with_names(t)}

    def save_delta(fs):
        stats["delta"] = TensorStore(fs).save("m", t2, baseline=baseline, block_bytes=256)

    runtime_for(local).invoke(save_delta)
    assert stats["delta"]["bytes_written"] < stats["full"]["bytes_written"]
    assert stats["delta"]["blocks_written"] == 1   # single dirty 256B block

    out = {}

    def load(fs):
        out["flat"] = TensorStore(fs).load("m")

    runtime_for(local).invoke(load, read_only=True)
    np.testing.assert_array_equal(out["flat"]["w/a"], t2["w"]["a"])


def test_snapshot_load_is_consistent_under_concurrent_save(local):
    t = tree()

    def save(fs):
        TensorStore(fs).save("m", t)

    runtime_for(local).invoke(save)

    # open a snapshot reader, then commit a new version from another client
    other = LocalServer(local.backend)
    txn = local.begin(read_only=True)
    fs = FaaSFS(txn)
    store = TensorStore(fs)
    first_leaf = store.load("m")["w/a"]

    t2 = {"w": {"a": t["w"]["a"] + 100, "b": t["w"]["b"] + 100}, "step": t["step"]}

    def save2(fs2):
        TensorStore(fs2).save("m", t2)

    runtime_for(other).invoke(save2)

    # the pinned snapshot still reads the OLD version of the other leaf
    second_leaf = store.load("m")["w/b"]
    np.testing.assert_array_equal(second_leaf, t["w"]["b"])
    np.testing.assert_array_equal(first_leaf, t["w"]["a"])
    txn.commit()


def test_zero_copy_load_counters_prove_no_assembly_copies(backend_factory):
    """The copy-accounting gate: a cold-cache zero-copy load lands every
    block either straight off the wire into the arena (``bytes_sunk``)
    or via exactly one counted copy (``bytes_copied_into`` — LRU hits
    and non-sink transports). Over a real socket the per-block copy
    counter must be ZERO: the single wire decode IS the landing."""
    backend = backend_factory(block_size=256)
    writer = LocalServer(backend)
    t = tree()

    def save(fs):
        TensorStore(fs).save("m", t)

    runtime_for(writer).invoke(save)

    # a FRESH worker: cold block cache, so every byte crosses the backend
    reader = LocalServer(backend)
    arena = BlockArena()
    counts = {}

    def load(fs):
        out = TensorStore(fs, arena=arena).load("m", zero_copy=True)
        counts["sunk"] = fs.txn.bytes_sunk
        counts["copied"] = fs.txn.bytes_copied_into
        counts["flat"] = out

    runtime_for(reader).invoke(load, read_only=True)
    flat = counts["flat"]
    total = sum(a.nbytes for _, a in flatten_with_names(t))
    for name, a in flatten_with_names(t):
        np.testing.assert_array_equal(a, flat[name])
        assert not flat[name].flags.writeable      # sealed arena views
    # every payload byte is accounted to exactly one landing path
    assert counts["sunk"] + counts["copied"] >= total
    assert arena.bytes_filled == counts["sunk"]
    assert arena.bytes_copied == counts["copied"]
    if backend_factory.kind.startswith("remote"):
        # networked path: zero per-block copies beyond the wire decode
        assert counts["copied"] == 0
        assert counts["sunk"] >= total
        wire_stats = backend.connection_stats()
        assert wire_stats["bytes_sunk"] >= total


def test_arena_buffers_recycle_when_views_die(backend_factory):
    """Sealed arena buffers return to the pool when the LAST aliasing
    array view is garbage-collected — a second load reuses the same
    pooled memory instead of allocating fresh."""
    backend = backend_factory(block_size=256)
    local = LocalServer(backend)
    t = tree()

    def save(fs):
        TensorStore(fs).save("m", t)

    runtime_for(local).invoke(save)
    arena = BlockArena()
    out = {}

    def load(fs):
        out["flat"] = TensorStore(fs, arena=arena).load("m", zero_copy=True)

    runtime_for(local).invoke(load, read_only=True)
    assert arena.outstanding == len(out["flat"])
    view = out["flat"]["w/a"][:4]                  # slice keeps buffer alive
    out.clear()
    gc.collect()
    assert arena.outstanding == 1                  # only w/a's buffer left
    del view
    gc.collect()
    assert arena.outstanding == 0
    runtime_for(local).invoke(load, read_only=True)
    assert arena.reuses > 0                        # pool hits, not fresh allocs
    out.clear()
    gc.collect()
    assert arena.outstanding == 0


def test_save_tree_splits_a_large_save_and_shows_it_at_once(local, monkeypatch):
    """Past ``TXN_WRITE_BYTES`` a save takes several transactions; the
    pointer to its version rides the last, so ``load`` sees nothing until
    it is whole."""
    from repro.core import tensorstate
    from repro.core.tensorstate import save_tree

    monkeypatch.setattr(tensorstate, "TXN_WRITE_BYTES", 4096)
    rt = runtime_for(local)

    class Watch:
        visible = []

        def invoke(self, fn, *args, **kwargs):
            out = rt.invoke(fn, *args, **kwargs)
            self.visible.append(rt.invoke(
                lambda fs: TensorStore(fs).exists("big"), read_only=True))
            return out

    t = {"w": np.arange(4096, dtype=np.float32), "b": np.ones(3, np.float32)}
    stats = save_tree(Watch(), "/mnt/tsfs/state", "big", t, block_bytes=1024)
    assert stats["txns"] == 5 and stats["bytes_written"] == 16396
    assert Watch.visible == [False] * 4 + [True]
    out = {}

    def load(fs):
        out["flat"] = TensorStore(fs).load("big", zero_copy=True)

    rt.invoke(load, read_only=True)
    np.testing.assert_array_equal(out["flat"]["w"], t["w"])
    np.testing.assert_array_equal(out["flat"]["b"], t["b"])


def test_split_publish_is_seen_whole_by_a_refreshing_replica(local, monkeypatch):
    """A save of a name replicas serve, split over several transactions,
    writes a new version: a replica refreshing after each part sees the
    old state whole until the last part commits, then the new one whole
    — for a full rewrite and for a delta. Refreshing by delta, it keeps
    every leaf while the pointer stands, and after a delta lands only the
    leaves that delta touched."""
    from repro.core import tensorstate
    from repro.core.tensorstate import save_tree
    from repro.serving.engine import SnapshotServer

    monkeypatch.setattr(tensorstate, "TXN_WRITE_BYTES", 2048)
    rt = runtime_for(local)
    replica = SnapshotServer(LocalServer(local.backend), lambda p, b: p,
                             {"w": None, "b": None})
    seen, served = [], []
    invoke = rt.invoke

    def watched(fn, *args, **kwargs):
        out = invoke(fn, *args, **kwargs)
        replica.refresh()
        seen.append({k: np.array(v) for k, v in replica.params.items()})
        served.append(dict(replica.params))
        return out

    def save(t, **kw):
        return save_tree(rt, "/mnt/tsfs/train", "state", t,
                         block_bytes=256, **kw)

    def same(a, b):
        return all(np.array_equal(a[k], b[k]) for k in a)

    v1 = {"w": np.arange(2048, dtype=np.float32), "b": np.zeros(8, np.float32)}
    save(v1)
    monkeypatch.setattr(rt, "invoke", watched)
    v2 = {"w": -1.0 - v1["w"], "b": np.ones(8, np.float32)}
    v3 = {"w": v2["w"].copy(), "b": v2["b"]}
    v3["w"][::128] = 7.0                      # every other 256 B block
    v4 = {"w": v3["w"].copy(), "b": v3["b"]}
    v4["w"][64::128] = 3.0                    # the blocks v3 did not touch
    for old, new, kw in ((v1, v2, {}), (v2, v3, {"baseline": v2}),
                         (v3, v4, {"baseline": v3})):
        seen.clear()
        served.clear()
        before = dict(replica.params) if replica.params is not None else None
        stats = save(new, **kw)
        assert stats["txns"] > 1
        assert [same(s, old) for s in seen[:-1]] == [True] * (len(seen) - 1)
        assert same(seen[-1], new)
        if kw:
            # the old version whole, as the same arrays, until the pointer
            # moves; then only the leaf the delta touched is new
            assert all(p[k] is before[k] for p in served[:-1] for k in p)
            assert served[-1]["b"] is before["b"]
            assert served[-1]["w"] is not before["w"]
    assert stats["base"] is not None and stats["blocks_written"] == 16


def test_state_larger_than_a_frame_saves_and_loads_over_a_socket(
        tmp_path, monkeypatch):
    """Commits and block fetches each stay inside the wire's frame cap:
    with the cap shrunk to 64 KiB, a 256 KiB leaf still round-trips."""
    from repro.core import tensorstate, wire
    from repro.core.backend import BackendService
    from repro.core.remote import RemoteBackend
    from repro.core.server import BackendServer
    from repro.core.tensorstate import save_tree

    monkeypatch.setattr(wire, "MAX_BODY", 64 * 1024)
    monkeypatch.setattr(tensorstate, "TXN_WRITE_BYTES", 16 * 1024)
    server = BackendServer(BackendService(block_size=4096),
                           wal_path=str(tmp_path / "wal")).start()
    rb = RemoteBackend("127.0.0.1", server.port)
    try:
        local = LocalServer(rb, max_blocks=8)
        t = {"w": np.random.default_rng(1).normal(size=65536).astype(np.float32)}
        stats = save_tree(runtime_for(local), "/mnt/tsfs/state", "m", t,
                          block_bytes=4096)
        assert stats["txns"] == 16
        rpcs = rb.connection_stats()["rpcs"]
        for zero_copy in (True, False):
            out = {}

            def load(fs):
                out["flat"] = TensorStore(fs).load("m", zero_copy=zero_copy)

            runtime_for(LocalServer(rb, max_blocks=8)).invoke(load, read_only=True)
            np.testing.assert_array_equal(out["flat"]["w"], t["w"])
        # 64 blocks per load, at most 4 per fetch frame
        assert rb.connection_stats()["rpcs"] - rpcs >= 2 * 16
    finally:
        rb.close()
        server.shutdown()


# -- delta loads: landing only the blocks written since a held version -- #
ROOT = "/mnt/tsfs/train"


def _publish(local, t, **kw):
    from repro.core.tensorstate import save_tree

    return save_tree(runtime_for(local), ROOT, "state", t, block_bytes=256, **kw)


def _load(local, held=None):
    """(flat, loaded) of one zero-copy load of the served state."""
    out = {}

    def read(fs):
        store = TensorStore(fs, prefix=ROOT)
        out["flat"] = store.load("state", zero_copy=True, held=held)
        out["loaded"] = store.loaded

    runtime_for(local).invoke(read, read_only=True)
    return out["flat"], out["loaded"]


def _state():
    """64 blocks of ``w``, one of ``b``, four of ``e`` at 256 B a block."""
    return {"w": np.arange(4096, dtype=np.float32),
            "b": np.zeros(8, np.float32),
            "e": np.ones((32, 16), np.float16)}


def _bump(t, elems):
    """A copy of ``t`` with the given elements of ``w`` raised by one;
    the other leaves are the same objects."""
    new = dict(t, w=t["w"].copy())
    new["w"][list(elems)] += 1.0
    return new


def _chain(local, t, k, seed=0):
    """Publish ``k`` deltas after ``t``, each of a few seeded elements of
    ``w``; returns the last tree."""
    rng = np.random.default_rng(seed)
    for _ in range(k):
        new = _bump(t, rng.choice(4096, 3, replace=False))
        _publish(local, new, baseline=t)
        t = new
    return t


@pytest.mark.parametrize("deltas", [1, 3, 8])
def test_delta_load_is_bit_equal_to_a_full_load(local, deltas):
    """From a held version, a load after 1, 3 or 8 deltas (the versions
    between them skipped) reads only their blocks and is bit-equal to a
    full load; untouched leaves come back as the held arrays, and the held
    arrays stay as they were."""
    v0 = _state()
    _publish(local, v0)
    held, first = _load(local)
    before = {k: a.copy() for k, a in held.items()}
    last = _chain(local, v0, deltas, seed=deltas)
    flat, loaded = _load(local, held=(first["version"], held))
    full, whole = _load(local)
    assert loaded["version"] == whole["version"] != first["version"]
    assert (loaded["delta"], loaded["versions"]) == (1, deltas)
    for k in full:
        assert flat[k].dtype == full[k].dtype and flat[k].shape == full[k].shape
        assert flat[k].tobytes() == full[k].tobytes() == last[k].tobytes()
        assert held[k].tobytes() == before[k].tobytes()
        assert not flat[k].flags.writeable
    assert flat["b"] is held["b"] and flat["e"] is held["e"]
    assert flat["w"] is not held["w"]
    assert loaded["leaves_kept"] == 2
    assert loaded["bytes_copied"] == last["w"].nbytes
    assert 0 < loaded["bytes_landed"] <= 3 * deltas * 256
    assert whole["bytes_landed"] == sum(a.nbytes for a in last.values())


def test_delta_load_from_a_version_in_the_middle_of_the_chain(local):
    """A held version that is itself a delta: only the versions after it
    count, and the non-zero-copy path gives the same bytes."""
    v0 = _state()
    _publish(local, v0)
    v2 = _chain(local, v0, 2, seed=1)
    held, mid = _load(local)
    assert mid["delta"] == 0
    v5 = _chain(local, v2, 3, seed=2)
    flat, loaded = _load(local, held=(mid["version"], held))
    assert (loaded["delta"], loaded["versions"]) == (1, 3)
    out = {}

    def read(fs):
        store = TensorStore(fs, prefix=ROOT)
        out["flat"] = store.load("state", held=(mid["version"], held))
        out["loaded"] = store.loaded

    runtime_for(local).invoke(read, read_only=True)
    assert out["loaded"]["delta"] == 1
    for k in v5:
        assert flat[k].tobytes() == out["flat"][k].tobytes() == v5[k].tobytes()


def test_delta_load_falls_back_to_a_full_load(local):
    """Every block is read again when the held arrays cannot stand for the
    blocks left out: the held version is off the chain or behind a full
    version, a leaf changed dtype or shape, or the save is in place."""
    from repro.core.tensorstate import DELTA_CHAIN_MAX

    v0 = _state()
    _publish(local, v0)
    held, first = _load(local)
    h = (first["version"], held)

    def full_again(expect):
        flat, loaded = _load(local, held=h)
        assert loaded["delta"] == 0 and loaded["leaves_kept"] == 0
        assert loaded["bytes_landed"] == sum(a.nbytes for a in expect.values())
        assert all(flat[k] is not held[k] for k in flat)
        for k in expect:
            assert flat[k].tobytes() == expect[k].tobytes()

    # a version the served chain never passes
    flat, loaded = _load(local, held=("state@0000000000000000", held))
    assert loaded["delta"] == 0
    # DELTA_CHAIN_MAX deltas still chain to the held version; the next
    # publish is written full, and the chain ends there
    t = _chain(local, v0, DELTA_CHAIN_MAX, seed=3)
    assert _load(local, held=h)[1]["delta"] == 1
    t = _chain(local, t, 1, seed=4)
    full_again(t)
    # a leaf of another dtype, then of another shape, in a delta
    held, first = _load(local)
    h = (first["version"], held)
    for new in (dict(t, b=t["b"].astype(np.float64)),
                dict(t, b=np.zeros(9, np.float32))):
        stats = _publish(local, new, baseline=t)
        assert stats["base"] is not None
        full_again(new)
        t = new
    # an in-place save under the served name is rewritten in place
    runtime_for(local).invoke(
        lambda fs: TensorStore(fs, prefix=ROOT).save("state", t))
    held, first = _load(local)
    assert first["version"] == "state"
    h = (first["version"], held)
    full_again(t)


def test_a_leaf_whose_blocks_are_all_dirty_lands_whole_with_no_copy(
        local, monkeypatch):
    from repro.core import obs

    v0 = _state()
    _publish(local, v0)
    held, first = _load(local)
    v1 = dict(v0, w=v0["w"] + 1.0, b=v0["b"].copy())
    v1["b"][0] = 5.0
    _publish(local, v1, baseline=v0)
    ring = obs.SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    prev = obs.set_trace((obs.new_trace_id(), 1))
    try:
        flat, loaded = _load(local, held=(first["version"], held))
    finally:
        obs.set_trace(prev)
    lands = {s["ar"]["bytes"]: s["ar"] for s in ring.spans()
             if s["n"] == "tensor.land"}
    assert lands[v1["w"].nbytes] == {"bytes": v1["w"].nbytes,
                                     "landed": v1["w"].nbytes, "copied": 0}
    assert lands[v1["b"].nbytes] == {"bytes": 32, "landed": 32, "copied": 0}
    assert lands[v1["e"].nbytes] == {"bytes": v1["e"].nbytes,
                                     "landed": 0, "copied": 0}
    assert loaded["bytes_copied"] == 0 and loaded["leaves_kept"] == 1
    for k in v1:
        assert flat[k].tobytes() == v1[k].tobytes()


def test_refresh_of_an_unmoved_pointer_keeps_every_leaf(local):
    """A replica refreshing twice at one version lands nothing the second
    time: every leaf is the array it already serves."""
    from repro.serving.engine import SnapshotServer

    v0 = _state()
    _publish(local, v0)
    replica = SnapshotServer(LocalServer(local.backend), lambda p, b: p,
                             {"w": None, "b": None, "e": None}, root=ROOT)
    replica.refresh()
    first = dict(replica.params)
    replica.refresh()
    assert all(replica.params[k] is first[k] for k in first)
    v1 = _chain(local, v0, 1, seed=5)
    replica.refresh()
    assert replica.params["w"] is not first["w"]
    assert replica.params["b"] is first["b"] and replica.params["e"] is first["e"]
    for k in v1:
        assert replica.params[k].tobytes() == v1[k].tobytes()
