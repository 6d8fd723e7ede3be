"""core/obs unit tests: registry semantics, histogram bucket edges,
the hot-path identity contract (no per-op label joins), snapshots under
concurrent increments, Prometheus rendering, spans (and their bridge to
JAX's profiler), and the logger."""
import glob
import io
import json
import os
import threading
import time
import urllib.request

import pytest

from repro.core import obs
from repro.core.obs import (
    Logger,
    MetricsRegistry,
    SlowOpLog,
    SpanRecorder,
    chrome_trace,
    render_prometheus,
)


# ------------------------------------------------------------------------- #
# registry + hot-path contract
# ------------------------------------------------------------------------- #
def test_labels_returns_identity_stable_child():
    # THE overhead contract: label resolution happens once at setup; the
    # per-op hot path holds the child object and never joins strings
    reg = MetricsRegistry()
    fam = reg.counter("c", labels=("op",))
    child = fam.labels("begin")
    for _ in range(100):
        assert fam.labels("begin") is child
    assert fam.labels("commit") is not child
    # re-asking the registry for the family is identity-stable too
    assert reg.counter("c", labels=("op",)).labels("begin") is child


def test_label_arity_checked_and_reregister_mismatch_raises():
    reg = MetricsRegistry()
    fam = reg.counter("c", labels=("op",))
    with pytest.raises(ValueError):
        fam.labels()
    with pytest.raises(ValueError):
        fam.labels("a", "b")
    with pytest.raises(ValueError):
        reg.gauge("c", labels=("op",))       # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("c", labels=("other",))  # label mismatch


def test_counter_gauge_basicops():
    reg = MetricsRegistry()
    c = reg.counter("hits").labels()
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("depth").labels()
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5
    sampled = reg.gauge_fn("live", lambda: 42)
    assert sampled.value == 42
    reg.gauge_fn("live", lambda: 43)  # rebind wins
    assert sampled.value == 43


def test_histogram_bucket_edges_are_le_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(10, 20, 50)).labels()
    # v == bound lands IN that bucket (le= semantics), v just above
    # spills to the next; above the last bound lands in +Inf
    for v in (9, 10):
        h.observe(v)
    h.observe(10.001)
    h.observe(20)
    h.observe(50)
    h.observe(50.5)
    snap = h.snapshot()
    assert snap["buckets"] == [10, 20, 50]
    assert snap["counts"] == [2, 2, 1, 1]   # le10, le20, le50, +Inf
    assert snap["count"] == 6
    assert snap["sum"] == pytest.approx(9 + 10 + 10.001 + 20 + 50 + 50.5)


def test_histogram_rejects_unsorted_bounds():
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("bad", buckets=(5, 1)).labels()


def test_histogram_quantile_upper_bound_approximation():
    h = MetricsRegistry().histogram("q", buckets=(1, 10, 100)).labels()
    for _ in range(90):
        h.observe(0.5)
    for _ in range(10):
        h.observe(50)
    assert h.quantile(0.5) == 1.0
    assert h.quantile(0.99) == 100.0


def test_snapshot_under_concurrent_increment():
    reg = MetricsRegistry()
    c = reg.counter("n").labels()
    h = reg.histogram("h", buckets=(10, 100)).labels()
    stop = threading.Event()
    N, T = 20_000, 4

    def hammer():
        for i in range(N):
            c.inc()
            h.observe(i % 150)

    threads = [threading.Thread(target=hammer) for _ in range(T)]
    for t in threads:
        t.start()
    seen = 0
    while any(t.is_alive() for t in threads):
        snap = reg.snapshot()
        v = snap["n"]["values"][""]
        hs = snap["h"]["values"][""]
        assert v >= seen                      # monotonic across snapshots
        assert hs["count"] == sum(hs["counts"])  # internally consistent
        seen = v
    for t in threads:
        t.join()
    stop.set()
    final = reg.snapshot()
    assert final["n"]["values"][""] == N * T
    assert final["h"]["values"][""]["count"] == N * T


def test_render_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("req_total", labels=("op",)).labels("begin").inc(3)
    reg.gauge("depth").labels().set(2)
    h = reg.histogram("lat_us", buckets=(10, 100)).labels()
    h.observe(5)
    h.observe(50)
    h.observe(5000)
    text = render_prometheus(reg.snapshot())
    assert "# TYPE req_total counter" in text
    assert 'req_total{op="begin"} 3' in text
    assert "depth 2" in text
    # cumulative buckets + the +Inf catch-all
    assert 'lat_us_bucket{le="10"} 1' in text
    assert 'lat_us_bucket{le="100"} 2' in text
    assert 'lat_us_bucket{le="+Inf"} 3' in text
    assert "lat_us_count 3" in text


def test_serve_metrics_http_scrape():
    reg = MetricsRegistry()
    reg.counter("up").labels().inc()
    srv = obs.serve_metrics(0, reg)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.server_port}/metrics", timeout=5
        ).read().decode()
        assert "# TYPE up counter" in body and "up 1" in body
    finally:
        srv.shutdown()


# ------------------------------------------------------------------------- #
# spans
# ------------------------------------------------------------------------- #
def test_span_is_noop_without_trace_context():
    rec = SpanRecorder()
    with obs.span("x", "test", recorder=rec):
        pass
    assert rec.spans() == []


def test_span_nesting_parents_and_chrome_trace_export():
    rec = SpanRecorder()
    tid = obs.new_trace_id()
    prev = obs.set_trace((tid, 1))
    try:
        with obs.span("outer", "test", recorder=rec):
            octx = obs.current_trace()
            assert octx[0] == tid and octx[1] != 1
            with obs.span("inner", "test", recorder=rec, args={"k": 3}):
                pass
        assert obs.current_trace() == (tid, 1)  # restored
    finally:
        obs.set_trace(prev)
    spans = rec.spans(trace_id=tid)
    by_name = {s["n"]: s for s in spans}
    assert by_name["inner"]["pa"] == by_name["outer"]["sp"]
    assert by_name["outer"]["pa"] == 1
    ct = chrome_trace(spans)
    ev = {e["name"]: e for e in ct["traceEvents"]}
    assert ev["inner"]["ph"] == "X" and ev["inner"]["dur"] >= 1
    assert ev["inner"]["args"]["k"] == 3
    assert ev["inner"]["args"]["trace_id"] == f"{tid:016x}"
    json.dumps(ct)  # must be JSON-serializable as-is


def test_span_ring_is_bounded():
    rec = SpanRecorder(capacity=8)
    for i in range(20):
        rec.record(f"s{i}", "t", 1, i + 1, 0, 1)
    got = rec.spans()
    assert len(got) == 8 and got[0]["n"] == "s12"
    rec.spans(clear=True)
    assert rec.spans() == []


def test_span_ring_counts_drops():
    rec = SpanRecorder(capacity=4)
    for i in range(10):
        rec.record(f"s{i}", "t", 1, i + 1, 0, 1)
    assert rec.dropped == 6 and len(rec.spans()) == 4
    rec.clear()
    assert rec.dropped == 0
    for i in range(5):
        rec.record(f"s{i}", "t", 1, i + 1, 0, 1)
    assert rec.spans(clear=True) and rec.dropped == 0


def test_perf_counter_and_now_us_read_one_clock():
    # benchmark spans (time.perf_counter) and ring spans (now_us) are
    # compared directly: they must be the same clock, not merely monotonic
    assert time.get_clock_info("perf_counter").implementation == \
        time.get_clock_info("monotonic").implementation
    for _ in range(100):
        a = time.perf_counter_ns() // 1000
        u = obs.now_us()
        b = time.perf_counter_ns() // 1000
        assert a <= u <= b


def test_span_off_path_records_nothing_and_frames_carry_no_envelope(
        tmp_path, monkeypatch):
    from repro.core import remote, wire
    from repro.core.backend import BackendService
    from repro.core.client import LocalServer
    from repro.core.posix import O_CREAT, O_RDWR
    from repro.core.runtime import FunctionRuntime
    from repro.core.server import BackendServer

    assert obs.profiler_annotation() is None
    ring = SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    frames = []
    real = wire.encode_frame_into

    def spy(out, msg_type, o, req_id=0, trace=None):
        at = len(out)
        n = real(out, msg_type, o, req_id, trace)
        frames.append((msg_type, o, req_id, trace, bytes(out[at:at + n])))
        return n

    monkeypatch.setattr(remote.wire, "encode_frame_into", spy)
    server = BackendServer(BackendService(block_size=64),
                           wal_path=str(tmp_path / "wal")).start()
    rb = remote.RemoteBackend("127.0.0.1", server.port)
    try:
        rt = FunctionRuntime(LocalServer(rb))

        def put(fs):
            fd = fs.open("/mnt/tsfs/f", O_CREAT | O_RDWR)
            fs.pwrite(fd, b"x" * 100, 0)

        with obs.span("outer", "test"):
            assert obs.current_trace() is None
            rt.invoke(put)
    finally:
        rb.close()
        server.shutdown()
    monkeypatch.undo()
    assert ring.spans() == [] and ring.dropped == 0
    assert frames and all(t is None for *_, t, _ in frames)
    for msg_type, o, req_id, _, raw in frames:
        _, _, _, flags = wire.decode_header_ex(raw)
        assert flags == 0
        assert raw == wire.encode_frame(msg_type, o, req_id)


def _xplane_host_events(log_dir):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]


def test_span_under_profiler_roots_a_trace_on_the_device_clock(
        tmp_path, monkeypatch):
    jax = pytest.importorskip("jax")

    ring = SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    assert obs.current_trace() is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert obs.profiler_annotation() is not None
        with obs.span("probe.outer", "test") as outer:
            ctx = obs.current_trace()
            assert ctx is not None and ctx[0] == outer.trace_id
            with obs.span("probe.inner", "test", args={"bytes": 8}):
                time.sleep(0.02)
        assert obs.current_trace() is None  # the root uninstalls itself
        with obs.span("probe.other", "test") as other:
            pass
    finally:
        jax.profiler.stop_trace()
    assert obs.profiler_annotation() is None

    by = {s["n"]: s for s in ring.spans()}
    o, i = by["probe.outer"], by["probe.inner"]
    assert o["pa"] == 0 and o["tr"] == outer.trace_id != 0
    assert i["tr"] == o["tr"] and i["pa"] == o["sp"] and i["ar"] == {"bytes": 8}
    assert by["probe.other"]["tr"] == other.trace_id != o["tr"]  # fresh root

    ev = {n: (t, d) for n, t, d in _xplane_host_events(str(tmp_path))
          if n.startswith("probe.")}
    (ot, od), (it, idur) = ev["probe.outer"], ev["probe.inner"]
    assert ot <= it and it + idur <= ot + od  # nested in the xplane
    assert abs(od / 1e3 - o["du"]) < 1000 and abs(idur / 1e3 - i["du"]) < 1000


def test_runtime_root_records_under_profiler_without_trace_flag(
        tmp_path, monkeypatch):
    jax = pytest.importorskip("jax")
    from repro.core.backend import BackendService
    from repro.core.client import LocalServer
    from repro.core.posix import O_CREAT, O_RDWR
    from repro.core.runtime import FunctionRuntime, InvocationStats

    ring = SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    rt = FunctionRuntime(LocalServer(BackendService(block_size=64)))
    assert not rt.trace

    def put(fs):
        fd = fs.open("/mnt/tsfs/g", O_CREAT | O_RDWR)
        fs.pwrite(fd, b"y" * 10, 0)

    rt.invoke(put)
    assert ring.spans() == []  # no context, no profiler: nothing
    stats = InvocationStats()
    jax.profiler.start_trace(str(tmp_path))
    try:
        rt.invoke(put, stats=stats)
    finally:
        jax.profiler.stop_trace()
    spans = ring.spans()
    root = next(s for s in spans if s["n"] == "invoke.put")
    assert root["pa"] == 0 and root["tr"] == stats.trace_id != 0
    attempt = next(s for s in spans if s["n"] == "invoke.attempt")
    commit = next(s for s in spans if s["n"] == "invoke.commit")
    assert attempt["pa"] == root["sp"] and commit["pa"] == attempt["sp"]
    assert {s["tr"] for s in spans} == {root["tr"]}


# ------------------------------------------------------------------------- #
# logger + slow-op ring
# ------------------------------------------------------------------------- #
def test_logger_levels_fields_and_trace_tag():
    out = io.StringIO()
    log = Logger("info", stream=out)
    log.debug("hidden")
    log.info("served", port=123, msg="two words")
    assert "hidden" not in out.getvalue()
    line = out.getvalue().strip()
    assert "level=info" in line and "event=served" in line
    assert "port=123" in line and "msg='two words'" in line
    assert "trace=" not in line
    prev = obs.set_trace((0xABC, 1))
    try:
        log.warn("slow")
    finally:
        obs.set_trace(prev)
    assert "trace=0000000000000abc" in out.getvalue()
    log.set_level("off")
    before = out.getvalue()
    log.error("nope")
    assert out.getvalue() == before


def test_slow_op_log_tags_active_trace():
    ring = SlowOpLog(capacity=4)
    prev = obs.set_trace((77, 1))
    try:
        ring.record("commit", 12345, detail="block:(1, 0)")
    finally:
        obs.set_trace(prev)
    ring.record("begin", 99)
    a, b = ring.entries()
    assert a["trace"] == 77 and a["op"] == "commit"
    assert b["trace"] == 0
    assert ring.entries(clear=True) and ring.entries() == []
