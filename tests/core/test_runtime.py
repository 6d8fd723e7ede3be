"""FunctionRuntime: implicit transactions, conflict restart, read-only
inference, and warm-container cache semantics."""
import pytest

from repro.core.client import LocalServer
from repro.core.posix import O_CREAT, O_RDWR, FaaSFS
from repro.core.runtime import FunctionRuntime, InvocationStats
from repro.core.types import Conflict, TxnStateError


@pytest.fixture
def backend(backend_factory):
    return backend_factory(block_size=16)


def test_decorator_invocation_commits(backend):
    runtime = FunctionRuntime(LocalServer(backend))

    @runtime.function
    def put(fs, path, data):
        fd = fs.open(path, O_CREAT | O_RDWR)
        fs.write(fd, data)
        fs.close(fd)
        return len(data)

    assert put("/mnt/tsfs/doc", b"hello") == 5

    @runtime.function(read_only=True)
    def get(fs, path):
        fd = fs.open(path)
        return fs.pread(fd, 100, 0)

    assert get("/mnt/tsfs/doc") == b"hello"
    assert runtime.stats.invocations == 2
    assert runtime.stats.read_only_invocations == 1


def test_conflict_restarts_with_fresh_fs(backend):
    a = FunctionRuntime(LocalServer(backend))
    b = FunctionRuntime(LocalServer(backend))

    @a.function
    def setup(fs):
        fd = fs.open("/mnt/tsfs/ctr", O_CREAT | O_RDWR)
        fs.pwrite(fd, (0).to_bytes(8, "little"), 0)

    setup()

    seen_fs = []
    fired = {"done": False}

    @a.function
    def bump(fs):
        seen_fs.append(fs)
        fd = fs.open("/mnt/tsfs/ctr", O_RDWR)
        n = int.from_bytes(fs.pread(fd, 8, 0), "little")
        if not fired["done"]:
            fired["done"] = True

            @b.function
            def interfere(fs2):
                fd2 = fs2.open("/mnt/tsfs/ctr", O_RDWR)
                m = int.from_bytes(fs2.pread(fd2, 8, 0), "little")
                fs2.pwrite(fd2, (m + 100).to_bytes(8, "little"), 0)

            interfere()  # commits between our read and our commit
        fs.pwrite(fd, (n + 1).to_bytes(8, "little"), 0)

    stats = InvocationStats()
    bump(stats=stats)
    assert stats.attempts == 2 and stats.aborts == 1
    # every retry got a FRESH FaaSFS over the warm LocalServer
    assert len(seen_fs) == 2 and seen_fs[0] is not seen_fs[1]

    @a.function(read_only=True)
    def read(fs):
        fd = fs.open("/mnt/tsfs/ctr")
        return int.from_bytes(fs.pread(fd, 8, 0), "little")

    assert read() == 101


def test_read_only_inference_fast_path(backend):
    runtime = FunctionRuntime(LocalServer(backend))

    @runtime.function
    def setup(fs):
        fd = fs.open("/mnt/tsfs/data", O_CREAT | O_RDWR)
        fs.write(fd, b"payload")

    setup()

    @runtime.function
    def reader(fs):
        fd = fs.open("/mnt/tsfs/data")
        return fs.pread(fd, 7, 0)

    s1 = InvocationStats()
    assert reader(stats=s1) == b"payload"
    assert not s1.read_only            # first run: read-write, observes
    s2 = InvocationStats()
    assert reader(stats=s2) == b"payload"
    assert s2.read_only                # inferred: snapshot fast path
    before = backend.latest_ts
    s3 = InvocationStats()
    assert reader(stats=s3) == b"payload"
    assert s3.read_only
    assert backend.latest_ts == before  # read-only commits burn no timestamps


def test_inference_demotes_when_function_writes(backend):
    runtime = FunctionRuntime(LocalServer(backend))
    behavior = {"write": False}

    @runtime.function
    def sometimes_writes(fs):
        fd = fs.open("/mnt/tsfs/sw", O_CREAT | O_RDWR)
        if behavior["write"]:
            fs.write(fd, b"x")
            return "wrote"
        return "read"

    assert sometimes_writes() == "read"      # rw, no effects -> infer ro
    behavior["write"] = True
    s = InvocationStats()
    # inferred-read-only run hits the write, transparently restarts rw
    assert sometimes_writes(stats=s) == "wrote"
    assert not s.read_only
    s2 = InvocationStats()
    assert sometimes_writes(stats=s2) == "wrote"   # pinned as writer now
    assert not s2.read_only


def test_declared_read_only_write_raises(backend):
    runtime = FunctionRuntime(LocalServer(backend))

    @runtime.function(read_only=True)
    def bad(fs):
        fd = fs.open("/mnt/tsfs/new", O_CREAT | O_RDWR)
        fs.write(fd, b"x")

    with pytest.raises(TxnStateError):
        bad()


def test_warm_container_cache_survives_invocations(backend):
    local = LocalServer(backend)
    runtime = FunctionRuntime(local)

    @runtime.function
    def setup(fs):
        fd = fs.open("/mnt/tsfs/warm", O_CREAT | O_RDWR)
        fs.write(fd, b"w" * 64)

    setup()

    @runtime.function
    def read(fs):
        fd = fs.open("/mnt/tsfs/warm")
        return fs.pread(fd, 64, 0)

    read()
    hits_before = local.hits
    read()  # warm: blocks served from the surviving cache
    assert local.hits > hits_before


def test_retries_exhausted_raises_conflict(backend):
    a = FunctionRuntime(LocalServer(backend), max_retries=2, backoff_s=0)
    b = FunctionRuntime(LocalServer(backend))

    @a.function
    def setup(fs):
        fd = fs.open("/mnt/tsfs/hot", O_CREAT | O_RDWR)
        fs.pwrite(fd, b"0", 0)

    setup()

    @b.function
    def stomp(fs):
        fd = fs.open("/mnt/tsfs/hot", O_RDWR)
        cur = fs.pread(fd, 1, 0)
        fs.pwrite(fd, b"1" if cur != b"1" else b"2", 0)

    @a.function
    def doomed(fs):
        fd = fs.open("/mnt/tsfs/hot", O_RDWR)
        fs.pread(fd, 1, 0)
        stomp()  # every attempt loses to a fresh interfering commit
        fs.pwrite(fd, b"9", 0)

    with pytest.raises(Conflict):
        doomed()
    assert a.stats.retries_exhausted == 1


def test_invoke_plain_callable_and_kwargs(backend):
    runtime = FunctionRuntime(LocalServer(backend))

    def fn(fs, path, data=b"default"):
        fd = fs.open(path, O_CREAT | O_RDWR)
        fs.write(fd, data)
        return fs.fstat(fd)["st_size"]

    assert runtime.invoke(fn, "/mnt/tsfs/k", data=b"abc") == 3
