"""Tiny inputs for running the harness on the CPU.

``TINY`` is a configuration of the benchmark's layout at a few KB; the
mixes are the committed ones with their sizes cut to what a test can run
in seconds and the kernel in interpret mode.
"""
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import cell  # noqa: E402

TINY = {
    "name": "tiny", "source": "test", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_hidden_layers": 2, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "vocab_size": 256, "qkv_bias": True, "torch_dtype": "bfloat16",
    "master_dtype": "float32", "block_bytes": 4096, "server_shards": 2,
    "client_cache_blocks": 256,
}
CUTS = {
    "serve_refresh": {"batch": 2, "tokens": 4, "publish_period_s": 1.0,
                      "dirty_frac": 0.05, "sample_requests": 3},
    "ckpt_delta": {"detector": "pallas_interpret", "dirty_frac": 0.05},
    "ckpt_full": {},
}


def mix(name: str) -> dict:
    m = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    m.update(CUTS[name])
    return m


def run(traffic: str, *, seed: int = 7, seconds: float = 3.0, trace: bool = False,
        control: bool = False, config: dict = TINY) -> dict:
    spec = cell.load_spec()
    w = {"name": f"{config['name']}.{traffic}", "config": config["name"],
         "traffic": traffic, "chips": 1, "why": "test"}
    spec = dict(spec, workloads=spec["workloads"] + [w])
    for m in spec["end_to_end"] + spec["per_layer"]:
        for real in list(m.get("workloads", [])):
            if real.endswith("." + traffic):
                m["workloads"] = m["workloads"] + [w["name"]]
    r = cell.Run(spec, w, config, mix(traffic), seed=seed, seconds=seconds,
                 trace=trace, control=control)
    out = cell.execute(r, time.perf_counter(), platform=None)
    out["_run"] = r
    return out
