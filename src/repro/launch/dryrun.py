import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real ``train_step``/``prefill_step``/
``decode_step`` over the production mesh with ShapeDtypeStruct inputs (no
allocation), compiles it, and records:

  * ``memory_analysis()``  — proves the cell fits per-device HBM,
  * ``cost_analysis()``    — XLA's (while-body-once) FLOPs/bytes,
  * the trip-count-corrected per-device FLOPs / bytes / collective wire
    bytes from ``hlo_analysis`` (the roofline inputs),
  * the collective schedule (op kinds and counts).

Results are cached incrementally under ``experiments/dryrun/`` as one JSON
per cell (plus the gzipped HLO for offline re-analysis), so the sweep is
resumable and the roofline/perf tooling never needs to recompile.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k --mesh pod
"""
import argparse
import gzip
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import SHAPES, cell_applicable, get_config, list_configs
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import runtime

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def cell_id(arch: str, shape: str, mesh_name: str) -> str:
    return f"{arch}__{shape}__{mesh_name}"


def run_cell(arch: str, shape_name: str, mesh_name: str, *, overrides=None,
             out_dir: Path = OUT_DIR, tag: str = "", force: bool = False) -> dict:
    out_dir.mkdir(parents=True, exist_ok=True)
    cid = cell_id(arch, shape_name, mesh_name) + (f"__{tag}" if tag else "")
    jpath = out_dir / f"{cid}.json"
    if jpath.exists() and not force:
        return json.loads(jpath.read_text())

    cfg = get_config(arch)
    shape = next(s for s in SHAPES if s.name == shape_name)
    ok, why = cell_applicable(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "tag": tag,
        "applicable": ok,
    }
    if not ok:
        rec["skip_reason"] = why
        jpath.write_text(json.dumps(rec, indent=2))
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    n_dev = mesh.devices.size
    plan = runtime.plan_cell(cfg, shape, mesh, overrides=overrides)
    t0 = time.time()
    try:
        lowered = runtime.lower_cell(plan, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        text = compiled.as_text()
        ana = hlo_analysis.analyze_text(text)
        rec.update(
            ok=True,
            devices=n_dev,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_bytes_est": ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
                - ma.alias_size_in_bytes,
            },
            xla_cost={
                "flops": ca.get("flops", 0.0),
                "bytes_accessed": ca.get("bytes accessed", 0.0),
            },
            analysis=ana,
            model_params=cfg.param_count(),
            model_params_active=cfg.active_param_count(),
        )
        (out_dir / f"{cid}.hlo.txt.gz").write_bytes(gzip.compress(text.encode()))
    except Exception as e:  # record failures — they are bugs to fix
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-4000:])
    jpath.write_text(json.dumps(rec, indent=2))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=[None, "pod", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_configs()
    shapes = [args.shape] if args.shape else [s.name for s in SHAPES]
    meshes = [args.mesh] if args.mesh else ["pod", "multipod"]

    results = []
    for a in archs:
        for s in shapes:
            for m in meshes:
                t0 = time.time()
                rec = run_cell(a, s, m, force=args.force)
                status = (
                    "SKIP" if not rec.get("applicable", True)
                    else ("OK" if rec.get("ok") else "FAIL")
                )
                peak = rec.get("memory", {}).get("peak_bytes_est", 0)
                print(
                    f"[{status:4s}] {a:22s} {s:12s} {m:8s} "
                    f"peak={peak/2**30:7.2f}GiB wall={time.time()-t0:6.1f}s",
                    flush=True,
                )
                if not rec.get("ok", True) and rec.get("applicable", True):
                    print("       ", rec.get("error", ""), flush=True)
                results.append(rec)
    n_ok = sum(1 for r in results if r.get("ok"))
    n_skip = sum(1 for r in results if not r.get("applicable", True))
    n_fail = len(results) - n_ok - n_skip
    print(f"\ndone: {n_ok} ok, {n_skip} skip, {n_fail} fail / {len(results)}")


if __name__ == "__main__":
    main()
