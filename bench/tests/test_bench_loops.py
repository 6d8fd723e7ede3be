"""Each traffic mix rehearsed on the CPU at a tiny size: the whole run
(server process, trainer process, set-up, window, reference checks) with
the kernel in interpret mode, untraced and traced."""
import pytest

import tiny


@pytest.mark.parametrize("traffic,e2e", [
    ("serve_refresh", {"serve_tok_s", "setup_s"}),
    ("ckpt_delta", {"ckpt_delta_s", "setup_s"}),
    ("ckpt_full", {"ckpt_save_MBps", "restore_MBps", "setup_s"}),
])
def test_mix_runs_correct_with_its_end_to_end_metrics(traffic, e2e):
    out = tiny.run(traffic)
    r = out.pop("_run")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == e2e
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert list(out)[-1] == "checks"
    assert not r.compiles, "a program compiled inside the window"


@pytest.mark.parametrize("traffic,layer", [
    ("serve_refresh", {"replica.refresh_s", "replica.serve_s"}),
    ("ckpt_delta", {"ckpt.pull_s.delta", "server.write_amp.delta"}),
    ("ckpt_full", {"server.commit_exec_s.full", "wal.fsync_s.full",
                   "client.rpcs_per_GB.restore", "restore.put_s"}),
])
def test_traced_run_reports_per_layer_metrics(traffic, layer):
    out = tiny.run(traffic, trace=True)
    out.pop("_run")
    assert out["correct"], out["checks"]
    # the CPU has no peaks and no device plane: rooflines and mfu stay out
    assert set(out["metrics"]) == layer
    assert out["device"]["window_s"] > 0


def test_serve_window_refreshes_to_every_version_published():
    out = tiny.run("serve_refresh", seconds=3.5)
    r = out.pop("_run")
    assert out["correct"], out["checks"]
    published = [p["k"] for p in r.extra["publishes"]]
    assert published == [1, 2, 3]
    refreshed = [o["k"] for o in r.ops if o["op"] == "refresh"]
    assert refreshed and refreshed == sorted(refreshed) and refreshed[-1] == 3


def test_delta_saves_write_exactly_the_drawn_blocks():
    out = tiny.run("ckpt_delta")
    r = out.pop("_run")
    deltas = [o for o in r.ops if o["op"] == "save" and o["kind"] == "delta"]
    fulls = [o for o in r.ops if o["op"] == "save" and o["kind"] == "full"]
    assert len(deltas) >= 8 and fulls, "the chain cut forces a full save"
    for o in deltas:
        assert o["blocks_written"] == sum(len(b) for b in o["want"].values())


def test_work_in_flight_when_the_window_closes_is_cut_and_not_counted(monkeypatch):
    import time

    from repro.state import checkpoint

    real, calls = checkpoint.save_tree, []

    def slow(*a, **kw):
        calls.append(1)
        if len(calls) == 4:  # the third save of the window outlives it
            time.sleep(2.5)
        return real(*a, **kw)

    monkeypatch.setattr(checkpoint, "save_tree", slow)
    t0 = time.monotonic()
    out = tiny.run("ckpt_delta", seconds=1.5)
    r = out.pop("_run")
    assert out["correct"], out["checks"]
    assert r.aborted and len(r.done("save")) == 2
    assert time.monotonic() - t0 < 60
