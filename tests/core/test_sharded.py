"""Cross-shard semantics of ShardedBackend: 2PC atomicity, consistent
snapshots across shards, group-commit batching, and partitioning sanity."""
import threading

import pytest

from repro.core.backend import BackendService
from repro.core.client import LocalServer
from repro.core.posix import FaaSFS, O_CREAT
from repro.core.runtime import runtime_for
from repro.core.sharded import ShardedBackend
from repro.core.types import CachePolicy, Conflict


def path_on_shard(be: ShardedBackend, shard: int, stem: str) -> str:
    """Deterministic FULL path (mount prefix included — that's what gets
    hashed) whose namespace entry lives on ``shard``."""
    for i in range(10_000):
        p = f"/mnt/tsfs/{stem}{i}"
        if be.shard_of_name(p) == shard:
            return p
    raise AssertionError("no path found")  # pragma: no cover


def new_file(local, path, size=0):
    txn = local.begin()
    fid = txn.create(path)
    if size:
        txn.write(fid, 0, b"\0" * size)
    txn.commit()
    return fid


def test_state_spreads_across_shards():
    be = ShardedBackend(n_shards=4, block_size=16)
    a = LocalServer(be)
    fids = [new_file(a, f"/f{i}", size=16) for i in range(8)]
    assert {be.shard_of_fid(f) for f in fids} == {0, 1, 2, 3}
    holding_blocks = [sh for sh in be.shards.values() if list(sh.store._blocks)]
    holding_names = [sh for sh in be.shards.values() if sh.store._names]
    assert len(holding_blocks) == 4      # round-robin fids spread block state
    assert len(holding_names) >= 2       # path hash spreads the namespace


def test_cross_shard_ww_conflict_aborts_exactly_one():
    be = ShardedBackend(n_shards=2, block_size=16)
    a, b = LocalServer(be), LocalServer(be)
    f1 = new_file(a, "/x", size=16)
    f2 = new_file(a, "/y", size=16)
    assert be.shard_of_fid(f1) != be.shard_of_fid(f2)  # genuinely cross-shard

    ta, tb = a.begin(), b.begin()
    for t in (ta, tb):
        t.read(f1, 0, 4)
        t.read(f2, 0, 4)
        t.write(f1, 0, b"AAAA")
        t.write(f2, 0, b"BBBB")
    ta.commit()                    # first racer commits via 2PC
    with pytest.raises(Conflict):
        tb.commit()                # second aborts on both shards' reads

    tc = a.begin()
    assert tc.read(f1, 0, 4) == b"AAAA"
    assert tc.read(f2, 0, 4) == b"BBBB"
    tc.commit()


def test_2pc_abort_leaves_no_partial_state():
    """A conflicted cross-shard commit must not leave writes on ANY shard."""
    be = ShardedBackend(n_shards=2, block_size=16)
    a, b = LocalServer(be), LocalServer(be)
    f1 = new_file(a, "/x", size=16)
    f2 = new_file(a, "/y", size=16)
    s2 = be.shards[be.shard_of_fid(f2)]
    v2_before = s2.store.block_version((f2, 0))

    ta = a.begin()
    ta.read(f1, 0, 4)
    ta.write(f1, 0, b"TTTT")
    ta.write(f2, 0, b"TTTT")       # second shard participant

    tb = b.begin()                 # invalidate ta's read on f1's shard
    tb.read(f1, 0, 4)
    tb.write(f1, 0, b"ZZZZ")
    tb.commit()

    with pytest.raises(Conflict):
        ta.commit()
    # the non-conflicting shard saw no partial apply
    assert s2.store.block_version((f2, 0)) == v2_before
    tc = a.begin()
    assert tc.read(f2, 0, 4) == b"\0\0\0\0"
    assert tc.read(f1, 0, 4) == b"ZZZZ"
    tc.commit()


def test_cross_shard_rename_atomic_snapshots():
    """A rename spanning two name shards is never observed under both
    names or neither name by any snapshot reader."""
    # versions_kept > number of flips: the name chains must retain every
    # version a concurrently pinned snapshot might need (otherwise GC
    # legitimately raises SnapshotTooOld, which is not what we test here)
    be = ShardedBackend(
        n_shards=2, block_size=16, policy=CachePolicy.STALE, versions_kept=128
    )
    w = LocalServer(be)
    src = path_on_shard(be, 0, "src")
    dst = path_on_shard(be, 1, "dst")
    assert be.shard_of_name(src) != be.shard_of_name(dst)

    def create(fs):
        fd = fs.open(src, O_CREAT)
        fs.write(fd, b"payload")

    runtime_for(w).invoke(create)

    stop = threading.Event()
    errors = []

    def reader():
        from repro.core.blockstore import SnapshotTooOld

        r = LocalServer(be)
        while not stop.is_set():
            txn = r.begin(read_only=True)
            fs = FaaSFS(txn)
            try:
                visible = [p for p in (src, dst) if fs.exists(p)]
            except SnapshotTooOld:
                txn.abort()
                continue
            txn.commit()
            if len(visible) != 1:
                errors.append(visible)
                return

    threads = [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    cur, other = src, dst
    for _ in range(50):            # ping-pong the name between shards
        def flip(fs, cur=cur, other=other):
            fs.rename(cur, other)

        runtime_for(w).invoke(flip)
        cur, other = other, cur
    stop.set()
    for t in threads:
        t.join()
    assert not errors, f"torn rename snapshots observed: {errors[:3]}"


def test_snapshot_sum_invariant_across_shards():
    """Writers move value between two files on different shards; snapshot
    readers must always see the conserved total."""
    from repro.core.blockstore import SnapshotTooOld

    be = ShardedBackend(n_shards=2, block_size=16, versions_kept=64)
    w = LocalServer(be)
    f1 = new_file(w, "/acct_a", size=8)
    f2 = new_file(w, "/acct_b", size=8)
    assert be.shard_of_fid(f1) != be.shard_of_fid(f2)

    t = w.begin()
    t.write(f1, 0, (100).to_bytes(8, "little"))
    t.commit()

    stop = threading.Event()
    errors = []

    def transfer():
        local = LocalServer(be)
        for i in range(40):
            while True:
                txn = local.begin()
                a = int.from_bytes(txn.read(f1, 0, 8), "little")
                b = int.from_bytes(txn.read(f2, 0, 8), "little")
                amt = (i % 5) + 1
                if a >= amt:
                    txn.write(f1, 0, (a - amt).to_bytes(8, "little"))
                    txn.write(f2, 0, (b + amt).to_bytes(8, "little"))
                else:
                    txn.write(f1, 0, (a + b).to_bytes(8, "little"))
                    txn.write(f2, 0, (0).to_bytes(8, "little"))
                try:
                    txn.commit()
                    break
                except Conflict:
                    continue

    def audit():
        local = LocalServer(be)
        while not stop.is_set():
            txn = local.begin(read_only=True)
            try:
                a = int.from_bytes(txn.read(f1, 0, 8), "little")
                b = int.from_bytes(txn.read(f2, 0, 8), "little")
            except SnapshotTooOld:
                # hot-block churn outran the undo log for this snapshot —
                # the system refused (rather than misread); retry fresh
                txn.abort()
                continue
            txn.commit()
            if a + b != 100:
                errors.append((a, b))
                return

    writers = [threading.Thread(target=transfer) for _ in range(2)]
    auditors = [threading.Thread(target=audit) for _ in range(2)]
    for t in auditors + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in auditors:
        t.join()
    assert not errors, f"inconsistent cross-shard snapshots: {errors[:3]}"


def test_single_shard_fast_path_is_used():
    be = ShardedBackend(n_shards=4, block_size=16)
    a = LocalServer(be)
    f1 = new_file(a, "/solo", size=16)
    f2 = new_file(a, "/other", size=16)

    fast_before, cross_before = (
        be.coord_stats.fast_commits, be.coord_stats.cross_commits,
    )
    txn = a.begin()                    # single-file RMW: one shard
    txn.read(f1, 0, 4)
    txn.write(f1, 0, b"QQQQ")
    txn.commit()
    assert be.coord_stats.fast_commits == fast_before + 1
    assert be.coord_stats.cross_commits == cross_before

    txn = a.begin()                    # two files on two shards: 2PC
    txn.write(f1, 0, b"RRRR")
    txn.write(f2, 0, b"RRRR")
    txn.commit()
    assert be.coord_stats.cross_commits == cross_before + 1


def test_2pc_read_participant_lock_pins_the_cut():
    """Regression for a tempting-but-unsound optimization: releasing a
    read-only 2PC participant's lock after validation. T1 reads f1 on
    shard A and writes f2 on shard B; T1's validated read pins T1 < any
    later writer of f1. While T1 is still applying on B, a racing
    T2 = write(f1) must NOT be able to commit on A and register — a
    snapshot reader beginning in that window would observe T2 without T1
    (a non-serializable cut). With A's lock held through registration,
    the reader sees a consistent prefix: T2 visible implies T1 visible."""
    be = ShardedBackend(n_shards=2, block_size=16, versions_kept=64)
    a, b, r = LocalServer(be), LocalServer(be), LocalServer(be)
    f1 = new_file(a, "/x", size=16)
    f2 = new_file(a, "/y", size=16)
    s_w = be.shard_of_fid(f2)
    assert be.shard_of_fid(f1) != s_w

    t1 = a.begin()
    assert t1.read(f1, 0, 4) == b"\0\0\0\0"   # read participant on A
    t1.write(f2, 0, b"T1T1")                   # effect on B
    # T2 begins BEFORE T1's commit window (begin scans every shard and
    # would otherwise block on B's held lock); only its commit — a
    # single-shard fast path needing just A's lock — races T1
    t2 = b.begin()
    t2.read(f1, 0, 4)
    t2.write(f1, 0, b"T2T2")

    entered, gate = threading.Event(), threading.Event()
    orig_apply = be.shards[s_w].apply_locked

    def slow_apply(payload, ts):
        entered.set()
        assert gate.wait(5)
        return orig_apply(payload, ts)

    be.shards[s_w].apply_locked = slow_apply
    worker = threading.Thread(target=t1.commit)
    worker.start()
    observed = []

    def t2_commit():
        t2.commit()

    def read_snapshot():
        # begin() captures the registered vector BEFORE its per-shard
        # scans, so the cut it reads at is whatever was registered in
        # the race window — exactly what must stay consistent
        snap = r.begin(read_only=True)
        observed.append((snap.read(f1, 0, 4), snap.read(f2, 0, 4)))
        snap.commit()

    racer = threading.Thread(target=t2_commit)
    reader = threading.Thread(target=read_snapshot)
    try:
        assert entered.wait(5)
        racer.start()              # must block on shard A's commit lock
        racer.join(timeout=0.3)
        reader.start()             # pins its cut inside the race window
        reader.join(timeout=0.3)
    finally:
        gate.set()
        worker.join()
        racer.join()
        reader.join()
    be.shards[s_w].apply_locked = orig_apply

    (x, y), = observed
    # T2-visible-but-not-T1 is the forbidden cut (T1 serializes first)
    assert not (x == b"T2T2" and y != b"T1T1"), (x, y)


def test_2pc_applies_shards_in_parallel():
    """2PC phase 2 applies the effectful participants concurrently: both
    shards must be inside ``apply_locked`` at once (a serial apply would
    leave the barrier one party short and fail the commit)."""
    be = ShardedBackend(n_shards=2, block_size=16)
    a = LocalServer(be)
    f1 = new_file(a, "/x", size=16)
    f2 = new_file(a, "/y", size=16)
    assert be.shard_of_fid(f1) != be.shard_of_fid(f2)

    rendezvous = threading.Barrier(2)

    def overlapping(apply_locked):
        def wrapped(*args, **kwargs):
            # passes only if BOTH shard applies are in flight concurrently
            rendezvous.wait(timeout=5)
            return apply_locked(*args, **kwargs)
        return wrapped

    for s in (be.shard_of_fid(f1), be.shard_of_fid(f2)):
        be.shards[s].apply_locked = overlapping(be.shards[s].apply_locked)

    txn = a.begin()
    txn.write(f1, 0, b"PPPP")
    txn.write(f2, 0, b"QQQQ")
    txn.commit()                      # BrokenBarrierError if serial

    check = a.begin()
    assert check.read(f1, 0, 4) == b"PPPP"
    assert check.read(f2, 0, 4) == b"QQQQ"
    check.commit()


def test_2pc_pure_validation_txn_commits_without_burning_timestamps():
    """A multi-shard transaction with reads but no effects validates
    under every participant's lock and commits without assigning
    timestamps or moving the sync vector."""
    be = ShardedBackend(n_shards=2, block_size=16)
    a = LocalServer(be)
    f1 = new_file(a, "/x", size=16)
    f2 = new_file(a, "/y", size=16)
    assert be.shard_of_fid(f1) != be.shard_of_fid(f2)

    vec_before = be.latest_ts
    txn = a.begin()                    # NOT read_only: reads are validated
    txn.read(f1, 0, 4)
    txn.read(f2, 0, 4)
    txn.commit()
    assert be.latest_ts == vec_before

    # and it still detects conflicts: stale read aborts
    b = LocalServer(be)
    ta = a.begin()
    ta.read(f1, 0, 4)
    ta.read(f2, 0, 4)
    tb = b.begin()
    tb.read(f1, 0, 4)
    tb.write(f1, 0, b"ZZZZ")
    tb.commit()
    with pytest.raises(Conflict):
        ta.commit()


def test_group_commit_batches_amortize_lock_acquisitions():
    be = BackendService(block_size=16, group_commit_window_s=0.02)
    setup = LocalServer(be)
    fids = [new_file(setup, f"/g{i}", size=16) for i in range(4)]

    committed_before = be.stats.group_committed
    batches_before = be.stats.group_batches
    barrier = threading.Barrier(4)

    def worker(i):
        local = LocalServer(be)
        barrier.wait()
        for _ in range(3):
            txn = local.begin()
            cur = int.from_bytes(txn.read(fids[i], 0, 8), "little")
            txn.write(fids[i], 0, (cur + 1).to_bytes(8, "little"))
            txn.commit()

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    committed = be.stats.group_committed - committed_before
    batches = be.stats.group_batches - batches_before
    assert committed == 12                         # all write txns batched
    assert 0 < batches < 12                        # fewer lock acquisitions
    check = setup.begin()
    for i in range(4):
        assert int.from_bytes(check.read(fids[i], 0, 8), "little") == 3
    check.commit()


def test_group_commit_validates_against_batch_members():
    """Two conflicting increments landing in one batch: exactly one wins."""
    be = BackendService(block_size=16, group_commit_window_s=0.02)
    setup = LocalServer(be)
    fid = new_file(setup, "/ctr", size=16)

    barrier = threading.Barrier(2)
    results = []

    def worker():
        local = LocalServer(be)
        txn = local.begin()
        cur = int.from_bytes(txn.read(fid, 0, 8), "little")
        txn.write(fid, 0, (cur + 1).to_bytes(8, "little"))
        barrier.wait()
        try:
            txn.commit()
            results.append("commit")
        except Conflict:
            results.append("abort")

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sorted(results) == ["abort", "commit"]


def test_exists_surfaces_snapshot_too_old_instead_of_false():
    """A pinned snapshot whose name-chain undo entries were GC'd must get
    SnapshotTooOld from exists(), not a silent 'file absent'."""
    from repro.core.blockstore import SnapshotTooOld

    be = BackendService(block_size=16, versions_kept=4)
    w = LocalServer(be)

    def create(fs):
        fs.open("/mnt/tsfs/hot", O_CREAT)

    runtime_for(w).invoke(create)
    r = LocalServer(be)
    txn = r.begin(read_only=True)      # pin the snapshot
    fs = FaaSFS(txn)
    cur, other = "/mnt/tsfs/hot", "/mnt/tsfs/cold"
    for _ in range(10):                # churn the name past versions_kept
        def flip(fs2, cur=cur, other=other):
            fs2.rename(cur, other)

        runtime_for(w).invoke(flip)
        cur, other = other, cur
    with pytest.raises(SnapshotTooOld):
        fs.exists("/mnt/tsfs/hot")


def test_lru_cache_evicts_oldest_and_counts():
    be = BackendService(block_size=16)
    local = LocalServer(be, max_blocks=3)
    for i in range(3):
        local._put((1, i), 1, b"x" * 16)
    local.cached_read((1, 0))             # hit: (1,0) becomes MRU
    local._put((1, 3), 1, b"y" * 16)      # evicts LRU -> (1,1)
    assert (1, 0) in local.cache
    assert (1, 1) not in local.cache
    assert (1, 2) in local.cache and (1, 3) in local.cache
    stats = local.cache_stats()
    assert stats["evictions"] == 1
    assert stats["size"] == 3 and stats["capacity"] == 3
    assert stats["hits"] == 1


def test_group_commit_registration_waits_for_durability():
    """Regression for the 'group-commit visibility window' (formerly a
    docs/transport.md known limitation): a sharded fast-path commit going
    through the group committer must NOT register in the sync vector
    until the batch's WAL fsync completes — otherwise a begin racing the
    window observes a commit a crash could still lose. Fails on the old
    ordering (register inside _commit_locked, barrier afterwards)."""

    class GatedWAL:
        """WAL double whose sync() blocks until released."""

        def __init__(self):
            self.entered = threading.Event()
            self.release = threading.Event()
            self.release.set()          # setup commits pass through
            self.records = []
            self.fsyncs = 0

        def append(self, rec):
            self.records.append(rec)
            return len(self.records)

        def sync(self, lsn=None):
            self.entered.set()
            assert self.release.wait(5), "test never released the fsync"
            self.fsyncs += 1

        def close(self):
            pass

    be = ShardedBackend(n_shards=2, block_size=16,
                        group_commit_window_s=0.005)
    wal = GatedWAL()
    be.set_wal(wal)

    setup = LocalServer(be)
    t = setup.begin()
    fid = t.create("/f")
    t.write(fid, 0, b"\0" * 16)
    t.commit()

    vec_before = be.latest_ts
    wal.entered.clear()
    wal.release.clear()               # next fsync parks until we say so

    committed = threading.Event()

    def writer():
        txn = setup.begin()
        txn.write(fid, 0, b"Y" * 16)
        txn.commit()                  # group-commit leader: blocks in sync
        committed.set()

    w = threading.Thread(target=writer, daemon=True)
    w.start()
    assert wal.entered.wait(5)        # commit applied, fsync in flight
    # the commit is NOT yet durable: no begin may observe it
    assert be.latest_ts == vec_before
    assert not committed.is_set()
    wal.release.set()                 # fsync completes
    w.join(timeout=5)
    assert committed.is_set()
    assert be.latest_ts != vec_before  # now registered, post-durability
