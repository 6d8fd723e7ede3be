"""The harness around the loops: refusal without a chip, teardown, the
count functions, the layout, and finding cells, mixes and metrics by name."""
import json
import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import tiny
from harness import counts, weights

BENCH, REPO = tiny.BENCH, tiny.REPO


def _cpu_env(tmp: Path) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp))
    env.pop("PYTHONPATH", None)
    return env


def _no_result(stdout: str) -> bool:
    return not any(l.startswith("{") for l in stdout.splitlines())


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    r = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-3-2b.ckpt_full",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=_cpu_env(tmp_path), capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and _no_result(r.stdout), r.stderr
    assert "needs 1 tpu" in r.stderr
    assert not list(tmp_path.glob("faasfs-bench-*")), "the run left its directory"


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "qwen2-1.5b.ckpt_delta", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=_cpu_env(tmp_path), capture_output=True,
                       text=True, timeout=120)
    assert r.returncode != 0 and _no_result(r.stdout)


def _ours(tmp: Path):
    """Processes whose environment carries ``tmp`` as TMPDIR."""
    mark = f"TMPDIR={tmp}".encode() + b"\0"
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                env = f.read()
            with open(f"/proc/{pid}/stat") as f:
                zombie = f.read().rsplit(")", 1)[1].split()[0] == "Z"
        except OSError:
            continue
        if mark in env and not zombie:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                out.append(f.read().replace(b"\0", b" ").decode())
    return out


@pytest.mark.parametrize("sig", [signal.SIGKILL, signal.SIGTERM])
def test_a_run_killed_in_its_window_leaves_nothing_behind(tmp_path, sig):
    script = ("import sys; sys.path.insert(0, %r); import tiny; "
              "tiny.run('serve_refresh', seconds=120)" % str(BENCH / "tests"))
    proc = subprocess.Popen([sys.executable, "-c", script], cwd=REPO,
                            env=_cpu_env(tmp_path), stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 120
        while not any("trainer.py" in c for c in _ours(tmp_path)):
            assert proc.poll() is None and time.monotonic() < deadline
            time.sleep(0.2)
        time.sleep(5)  # the trainer publishes the base, the window opens
        assert list(tmp_path.glob("faasfs-bench-*/wal"))
        proc.send_signal(sig)
        proc.wait(timeout=120)
        deadline = time.monotonic() + 60
        while (_ours(tmp_path) or list(tmp_path.glob("faasfs-bench-*"))) \
                and time.monotonic() < deadline:
            time.sleep(0.2)
        assert _ours(tmp_path) == []
        assert list(tmp_path.glob("faasfs-bench-*")) == []
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_decode_flops_count_every_matrix_and_the_context():
    c = tiny.TINY  # D 64, F 128, 4 heads x 16, 2 kv heads, V 256, 2 layers
    per_layer = 2 * 64 * (64 + 32 + 32 + 64) + 2 * 3 * 64 * 128
    attn = 2 * 2 * 64 * 5  # scores and values over 5 positions
    assert counts.decode_flops(c, 5) == 2 * (per_layer + attn) + 2 * 64 * 256


def test_block_delta_cost_reads_two_inputs_and_writes_the_delta():
    flops, nbytes = counts.block_delta_cost(3, 65536)
    n = 3 * 65536
    assert flops == 10 * n
    assert nbytes == 8 * n + n + 3 * 3 * 128 * 4


@pytest.mark.parametrize("name", ["qwen2-1.5b", "granite-3-2b"])
def test_layout_is_the_programs_and_the_file_states_its_bytes(name):
    from harness.serve_loop import model_config
    from repro.models.model import abstract_params

    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    want = {k: tuple(v.shape) for k, v in weights.flatten(
        abstract_params(model_config(c))).items()}
    got = {n: tuple(s) for n, s, _ in weights.layout(c)}
    assert got == want
    assert weights.state_bytes(c) == c["state_bytes"] == 4 * c["params"]


def test_weights_are_the_same_for_a_seed_and_differ_across_seeds():
    a = weights.make_params(tiny.TINY, 2 ** 33 + 1)
    b = weights.make_params(tiny.TINY, 2 ** 33 + 1)
    c = weights.make_params(tiny.TINY, 1)
    assert np.array_equal(a["embed"], b["embed"])
    assert not np.array_equal(a["embed"], c["embed"])


def test_files_dropped_into_a_copy_are_found_by_name(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    b = tmp_path / "bench"
    (b / "configs" / "newmodel.json").write_text(json.dumps(dict(tiny.TINY, name="newmodel")))
    mix = json.loads((b / "traffic" / "ckpt_full.json").read_text())
    (b / "traffic" / "ckpt_twice.json").write_text(json.dumps(dict(mix, about="x")))
    (b / "metrics" / "new.metric.py").write_text("def read(r):\n    return 42.0\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "newmodel", "source": "x",
                            "file": "bench/configs/newmodel.json", "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "newmodel.ckpt_twice", "config": "newmodel",
                              "traffic": "ckpt_twice", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "new.metric", "unit": "s", "better": "lower",
                              "source": "host_clock", "layer": "x",
                              "moves": "restore_MBps"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    probe = ("import sys; sys.path[:0] = ['bench', %r]\n"
             "from harness import cell\n"
             "spec = cell.load_spec()\n"
             "w, c, m = cell.cell_inputs(spec, 'newmodel.ckpt_twice')\n"
             "print(c['name'], m['about'], cell.reader('new.metric')(None),\n"
             "      [x['name'] for x in spec['per_layer'] if cell.applies(x, w)])\n"
             % str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path,
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split()[:3] == ["newmodel", "x", "42.0"]
    assert "'new.metric'" in r.stdout
    for p, data in before.items():
        assert p.read_bytes() == data
