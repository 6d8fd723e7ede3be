#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file and its traffic mix are found by name
through ``BENCHMARK.json``. The last line of standard output is the
result: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``
and, traced, ``breakdown``; then ``checks``, each number compared with
its limit, which also close standard error. A run that finds no TPU, or
fewer chips than the cell needs, or no ``src/repro`` beside this
directory, exits non-zero and prints no result.

``--control`` runs the lower-precision control in place of the checked
path (see PERF.md); the benchmark's own runs never pass it.
"""
import argparse
import json
import signal
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must not be negative")
    if not (REPO / "src" / "repro" / "core" / "server.py").is_file():
        print(f"run.py: no repro package under {REPO / 'src'}", file=sys.stderr)
        return 2
    for p in (str(BENCH), str(REPO / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import cell

    spec = cell.load_spec()
    workload, config, mix = cell.cell_inputs(spec, args.workload)
    r = cell.Run(spec, workload, config, mix, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), control=args.control)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        out = cell.execute(r, T_START)
    except cell.NoDevice as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    for name, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
