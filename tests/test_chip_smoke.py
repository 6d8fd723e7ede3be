"""``chip_smoke.py`` on the CPU: its phases and reference checks at a tiny
``reduced_config`` (kernels in interpret mode, passed in here), and its
refusal to report a result without a TPU or without the repo."""
import importlib.util
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs.base import get_config, reduced_config

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_pass_their_references_on_a_tiny_config(smoke, tmp_path, capsys):
    proc, port = smoke.start_server(str(tmp_path / "wal"), block_bytes=4096)
    try:
        out = smoke.run_phases(
            reduced_config(get_config("qwen2-1.5b")), port,
            impl="pallas_interpret", block_bytes=4096, dirty_frac=0.05,
            tokens=4, requests=2,
        )
    finally:
        smoke.stop_server(proc)
    lines = capsys.readouterr().out.splitlines()
    phases = [l.split()[0] for l in lines if l.startswith("phase=")]
    assert phases == ["phase=publish", "phase=serve", "phase=checkpoint",
                      "phase=refresh", "phase=restore"]
    assert all(" ok " in l for l in lines if l.startswith("phase="))
    assert out["dirty_blocks"] >= 2
    ckpt = next(l for l in lines if l.startswith("phase=checkpoint"))
    assert "detector=pallas_interpret" in ckpt and "kernel_leaves=0" not in ckpt


def test_a_perturbed_block_the_detector_misses_fails_the_phase(
        smoke, tmp_path, monkeypatch):
    from repro.state import checkpoint

    real = checkpoint.dirty_block_indices

    def blind(new, old, block_bytes, impl="auto"):
        idx = real(new, old, block_bytes, impl)
        return idx[1:] if idx else idx

    monkeypatch.setattr(checkpoint, "dirty_block_indices", blind)
    proc, port = smoke.start_server(str(tmp_path / "wal"), block_bytes=4096)
    try:
        with pytest.raises(smoke.SmokeFailure, match="detector"):
            smoke.run_phases(
                reduced_config(get_config("qwen2-1.5b")), port,
                impl="xla", block_bytes=4096, dirty_frac=0.05,
                tokens=2, requests=1,
            )
    finally:
        smoke.stop_server(proc)


def test_main_without_a_tpu_exits_nonzero_and_reports_nothing(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert not any(l.startswith("{") and json.loads(l).get("ok")
                   for l in r.stdout.splitlines())
