"""The readers of the program's spans (``harness/spans.py`` and the seven
metrics over it): on synthetic operations and spans, after a drop, against
a program that records no spans, and in a traced CPU run of each mix."""
import pytest

import tiny
from harness import cell
from repro.core import obs

#: metric, the operation it averages over, the benchmark span of that
#: operation, the program span it sums, and the save kind it keeps
READERS = [
    ("ckpt.detect_s.delta", "save", "save", "ckpt.detect", "delta"),
    ("ckpt.commit_s.delta", "save", "save", "ckpt.commit", "delta"),
    ("invoke.commit_s.full", "save", "save", "invoke.commit", "full"),
    ("tensor.land_s.restore", "restore", "restore", "tensor.land", None),
    ("tensor.land_s.refresh", "refresh", "refresh", "tensor.land", None),
    ("replica.upload_s", "request", "serve", "replica.upload", None),
    ("replica.decode_s", "request", "serve", "replica.decode", None),
]
DEADLINE = 35.0


def _run(op, bench, kind):
    """A traced run whose window holds four operations ``op``: two counted,
    one of another save kind, one cut by the window's end."""
    spec = cell.load_spec()
    w = spec["workloads"][0]
    r = cell.Run(spec, w, {}, {}, seed=1, seconds=1.0, trace=True)
    r.deadline = DEADLINE

    def add(t0, t1, kind_=kind):
        if op == "save":  # the pull is a benchmark span inside the save
            r.spans.append(("pull", t0, t0 + 0.01))
        r.spans.append((bench, t0, t1))
        rec = {"op": op, "end": t1 + 0.001}
        if kind_ is not None:
            rec["kind"] = kind_
        r.ops.append(rec)

    add(10.0, 11.0)
    add(20.0, 22.0)
    add(DEADLINE - 1, DEADLINE + 1)  # still in flight at the deadline
    if kind is not None:
        add(25.0, 26.0, "full" if kind == "delta" else "delta")
    return r


def _record(ring, name, t0, t1):
    ring.record(name, "test", 1, 1, int(t0 * 1e6), int((t1 - t0) * 1e6))


def _spans(ring, name):
    _record(ring, name, 10.1, 10.2)    # 0.1 s in the first operation
    _record(ring, "other.span", 10.2, 10.9)
    _record(ring, name, 10.5, 10.8)    # 0.3 s more
    _record(ring, name, 12.0, 12.5)    # between operations
    _record(ring, name, 20.5, 21.5)    # 1.0 s in the second
    _record(ring, name, DEADLINE - 0.5, DEADLINE + 0.5)  # the cut one's
    _record(ring, name, 25.1, 25.9)    # the other kind's


@pytest.mark.parametrize("metric,op,bench,name,kind", READERS)
def test_reader_sums_spans_inside_counted_operations(monkeypatch, metric, op,
                                                     bench, name, kind):
    ring = obs.SpanRecorder()
    monkeypatch.setattr(obs, "SPANS", ring)
    _spans(ring, name)
    got = cell.reader(metric)(_run(op, bench, kind))
    assert got == pytest.approx((0.1 + 0.3 + 1.0) / 2, abs=1e-5)


@pytest.mark.parametrize("metric,op,bench,name,kind", READERS)
def test_reader_returns_none_after_a_drop_in_the_window(monkeypatch, capsys, metric,
                                                        op, bench, name, kind):
    ring = obs.SpanRecorder(capacity=4)
    monkeypatch.setattr(obs, "SPANS", ring)
    _spans(ring, name)
    assert ring.dropped == 3
    assert cell.reader(metric)(_run(op, bench, kind)) is None
    assert "dropped" in capsys.readouterr().err


@pytest.mark.parametrize("metric,op,bench,name,kind", READERS)
def test_reader_ignores_drops_from_before_the_window(monkeypatch, metric, op,
                                                     bench, name, kind):
    ring = obs.SpanRecorder(capacity=8)
    monkeypatch.setattr(obs, "SPANS", ring)
    for i in range(5):
        _record(ring, name, 1.0 + i, 1.5 + i)  # four pushed out by the window's
    _spans(ring, name)
    assert ring.dropped == 4
    got = cell.reader(metric)(_run(op, bench, kind))
    assert got == pytest.approx((0.1 + 0.3 + 1.0) / 2, abs=1e-5)


@pytest.mark.parametrize("metric,op,bench,name,kind", READERS)
def test_reader_finds_nothing_in_a_program_without_spans(monkeypatch, metric, op,
                                                        bench, name, kind):
    monkeypatch.setattr(obs, "SPANS", obs.SpanRecorder())
    assert cell.reader(metric)(_run(op, bench, kind)) is None


#: per mix, its program-span metrics with the benchmark-span metric of the
#: call each is a part of (None where the cell reports no such metric), and
#: the mix's other per-layer metrics that a CPU run reports (it has no
#: peaks and no device plane, so rooflines and mfu stay out)
TRACED = [
    ("serve_refresh", {"tensor.land_s.refresh": "replica.refresh_s",
                       "replica.upload_s": "replica.serve_s",
                       "replica.decode_s": "replica.serve_s"},
     {"replica.refresh_s", "replica.serve_s"}),
    ("ckpt_delta", {"ckpt.detect_s.delta": None, "ckpt.commit_s.delta": None},
     {"ckpt.pull_s.delta", "server.write_amp.delta"}),
    ("ckpt_full", {"invoke.commit_s.full": None, "tensor.land_s.restore": None},
     {"server.commit_exec_s.full", "wal.fsync_s.full",
      "client.rpcs_per_GB.restore", "restore.put_s"}),
]


@pytest.mark.parametrize("traffic,new,others", TRACED)
def test_traced_run_reports_the_program_span_metrics(traffic, new, others):
    obs.SPANS.clear()
    out = tiny.run(traffic, trace=True)
    out.pop("_run")
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(got) == set(new) | others
    for name, outer in new.items():
        assert got.get(name) is not None and got[name] > 0, name
        if outer is not None:  # a part never exceeds the call it is part of
            assert got[name] <= got[outer], (name, got)
    assert obs.SPANS.dropped == 0
