"""The ``serve_granite`` loop and what it brings: Granite's layout against
the program's, the program against the plain float32 reference at every
position, the float8 control against the logit-gap limit, the FLOP count
and its reader, and whole runs of both Granite mixes on the CPU."""
import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

import tiny
from harness import cell, granite_counts, granite_reference, granite_weights, weights
from harness import serve_granite_loop as loop
from harness.trace import Trace

CONFIGS = tiny.BENCH / "configs"
G3 = json.loads((CONFIGS / "granite-3-2b.json").read_text())
G4 = json.loads((CONFIGS / "granite-4.0-h-micro.json").read_text())
SIZES = ("params", "state_bytes", "published")
SMALL = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "vocab_size": 256, "block_bytes": 4096}
#: two periods of a Mamba-2 / NoPE attention pattern at a tiny width
TINY_H = dict({k: v for k, v in G4.items() if k not in SIZES}, name="tiny-h", **SMALL, shared_intermediate_size=128,
              num_hidden_layers=6, layer_types=["mamba", "mamba", "attention"] * 2,
              mamba_n_heads=8, mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8)
TINY_D = dict({k: v for k, v in G3.items() if k not in SIZES}, name="tiny-d", **SMALL, num_hidden_layers=2)
CUTS = {"batch": 2, "tokens": 5, "publish_period_s": 1.0, "dirty_frac": 0.05,
        "sample_requests": 3}


def _tree_shapes(tree):
    return {n: tuple(a.shape) for n, a in weights.flatten(tree).items()}


@pytest.mark.parametrize("c", [G4, G3, TINY_H], ids=lambda c: c["name"])
def test_layout_is_the_programs_in_names_shapes_and_bytes(c):
    from repro.models import model as M

    program = _tree_shapes(M.abstract_params(loop.model_config(c)))
    ours = {n: s for n, s, _ in granite_weights.layout(c)}
    assert ours == program
    n = sum(int(np.prod(s)) for s in ours.values())
    assert n == loop.model_config(c).param_count()
    if "params" in c:
        assert n == c["params"]
        assert granite_weights.state_bytes(c, c["torch_dtype"]) == 2 * c["params"]


def test_granite_4_file_is_one_period_of_the_published_pattern():
    assert G4["layer_types"] == G4["published"]["layer_types"][:10]
    assert G4["published"]["layer_types"] == G4["layer_types"] * 4
    assert G4["params"] == 951_991_232 and G4["state_bytes"] == 1_903_982_464


def _reference_logits(c, p, toks):
    import jax
    import jax.numpy as jnp

    h = jax.jit(lambda p, t: granite_reference.hidden(c, p, t))(p, jnp.asarray(toks))
    return np.asarray(h @ p["embed"].astype(jnp.float32).T) / c["logits_scaling"]


@pytest.mark.parametrize("c", [TINY_H, TINY_D], ids=lambda c: c["name"])
def test_prefill_and_cached_decode_match_the_reference_at_every_position(c):
    """The program in float32, prompts of every length from 1 to S - 1
    prefilled and the rest decoded through the cache (teacher-forced),
    against the reference's logits at the same positions. Both sides are
    float32 with float32 products on the CPU, so they differ by summation
    order alone: 2e-4 of the logits' own spread."""
    import jax
    import jax.numpy as jnp

    from repro.models import model as M

    cfg = loop.model_config(c)
    p = granite_weights.make_params(c, 2 ** 31 + 3, "float32")
    B, S = 2, 19
    toks = np.random.default_rng(1).integers(0, c["vocab_size"], (B, S), np.int32)
    ref = _reference_logits(c, p, toks)
    tol = 2e-4 * float(np.std(ref))
    f32 = dict(compute_dtype=jnp.float32)
    prefill = jax.jit(lambda p, t: M.prefill(cfg, p, t, max_seq=S, **f32))
    step = jax.jit(lambda p, c, t, i: M.decode_step(cfg, p, c, t, i, **f32))
    for P in (1, 5, 8, 9, 16):
        if P == 1:
            cache = M.make_decode_cache(cfg, B, S, jnp.float32)
            got = []
        else:
            logits, cache = prefill(p, jnp.asarray(toks[:, :P]))
            got = [logits]
        for i in range(P - 1 if P == 1 else P, S):
            logits, cache = step(p, cache, jnp.asarray(toks[:, i:i + 1]), jnp.int32(i))
            got.append(logits)
        got = np.stack([np.asarray(g) for g in got], 1)
        np.testing.assert_allclose(got, ref[:, P - 1:], rtol=0, atol=tol, err_msg=f"P={P}")


#: full widths, the layers cut to a period's two kinds (hybrid) or one
#: layer (dense), the vocabulary to 4,096 rows: a narrower model's logits
#: lie too close together for any precision to be told apart
WIDE = {
    "hybrid": dict(G4, num_hidden_layers=2, layer_types=["mamba", "attention"],
                   vocab_size=4096, block_bytes=65536),
    "dense": dict(G3, num_hidden_layers=1, vocab_size=4096, block_bytes=65536),
}


@pytest.mark.parametrize("kind,prompt", [("hybrid", 13), ("dense", 1)])
@pytest.mark.parametrize("seed", [2 ** 31 + 1, 2 ** 31 + 5])
def test_float8_reference_in_place_of_the_decode_fails_the_logit_gap(kind, prompt, seed):
    from repro.launch.serve import make_decode_fn

    c = WIDE[kind]
    p = granite_weights.make_params(c, seed, c["torch_dtype"])
    prompts = np.random.default_rng(seed).integers(0, c["vocab_size"], (8, prompt), np.int32)
    served = np.asarray(make_decode_fn(loop.model_config(c), 16)({"params": p}, prompts))
    sound = granite_reference.widest_gap(c, p, served, prompt)
    control = granite_reference.widest_gap(c, p, served, prompt, loop.CONTROL_CAST)
    assert sound <= loop.LOGIT_GAP_LIMIT < control


def test_decode_flops_count_every_weight_once():
    """At a context of one position, a decode step's FLOPs are twice the
    weights it multiplies by, within the state recurrence and the conv."""
    d = granite_weights.dims(G4)
    norms = (2 * d["L"] + 1) * d["D"] + d["Lm"] * (d["Di"] + 3 * d["Hs"] + d["C"])
    conv_w = d["Lm"] * d["C"] * d["K"]
    weights_flops = 2 * (G4["params"] - norms - conv_w)
    extra = d["Lm"] * (5 * d["Hs"] * d["P"] * d["N"] + 2 * d["Di"] + 2 * d["K"] * d["C"]) \
        + d["La"] * 4 * d["heads"] * d["hd"]
    assert granite_counts.decode_flops(G4, 1) == weights_flops + extra
    # a prefill's token skips the LM head (but the last) and adds its chunk's SSD terms
    per_token = granite_counts.prefill_flops(G4, 512, 256) / 512
    head = 2 * d["D"] * d["V"]
    assert granite_counts.decode_flops(G4, 1) - head < per_token
    assert per_token < granite_counts.decode_flops(G4, 512) - head + d["Lm"] * 2 * 256 * (
        d["N"] + d["Hs"] * d["P"])


def ev(plane, line, name, ts, dur):
    return {"plane": plane, "line": line, "name": name, "ts": float(ts),
            "dur": float(dur), "stats": {}}


@pytest.mark.parametrize("prompt,steps,first", [(512, 3, 513), (1, 4, 1)])
def test_hybrid_decode_mfu_counts_each_step_at_its_context(prompt, steps, first):
    dev = "/device:TPU:0"
    window = ev("/host:CPU", "main", "bench.window", 0, 1e9)
    runs = [ev(dev, "XLA Modules", "jit_decode_one(3)", 1e6 * (1 + 2 * i), 1e6)
            for i in range(5)]
    other = [ev(dev, "XLA Modules", "jit_prefill_prompt(4)", 2e6, 1e6)]
    mix = {"batch": 8, "prompt_tokens": prompt, "tokens": steps + (prompt > 1)}
    r = SimpleNamespace(trace_view=Trace([window] + runs + other), config=G4, mix=mix,
                        peaks=cell.peaks("TPU v5 lite"))
    f = [8 * granite_counts.decode_flops(G4, first + i % steps) for i in range(5)]
    assert cell.reader("hybrid.decode_mfu")(r) == pytest.approx(100 * sum(f) / (5e-3 * 197e12))
    r.trace_view = Trace([window])
    assert cell.reader("hybrid.decode_mfu")(r) is None


def _run(config, traffic, *, prompt, trace=False, control=False, seconds=3.0):
    spec = cell.load_spec()
    real = {"serve_refresh_granite": "granite-3-2b.serve_refresh",
            "serve_rollout": "granite-4.0-h-micro.serve_rollout"}[traffic]
    w = {"name": f"{config['name']}.{traffic}", "config": config["name"],
         "traffic": traffic, "chips": 1, "why": "test"}
    spec = dict(spec, workloads=spec["workloads"] + [w])
    for m in spec["end_to_end"] + spec["per_layer"]:
        if real in m.get("workloads", []):
            m["workloads"] = m["workloads"] + [w["name"]]
    mix = json.loads((tiny.BENCH / "traffic" / f"{traffic}.json").read_text())
    mix.update(CUTS, prompt_tokens=prompt)
    r = cell.Run(spec, w, config, mix, seed=2 ** 31 + 11, seconds=seconds, trace=trace,
                 control=control)
    out = cell.execute(r, time.perf_counter(), platform=None)
    out["_run"] = r
    return out


@pytest.mark.parametrize("config,traffic,prompt", [
    (TINY_D, "serve_refresh_granite", 1), (TINY_H, "serve_rollout", 13)])
def test_granite_mix_runs_correct_with_its_end_to_end_metrics(config, traffic, prompt):
    out = _run(config, traffic, prompt=prompt)
    r = out.pop("_run")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"serve_tok_s", "setup_s"}
    assert set(out["checks"]) == {"version_errors", "param_mismatch",
                                  "malformed_outputs", "logit_gap"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert [p["k"] for p in r.extra["publishes"]] == [1, 2]
    assert not r.compiles, "a program compiled inside the window"
    served = r.done("request")[0]["out"]
    assert served.shape == (CUTS["batch"], prompt + CUTS["tokens"])


def test_traced_rollout_reports_its_per_layer_metrics():
    from repro.core import obs

    obs.SPANS.clear()
    out = _run(TINY_H, "serve_rollout", prompt=13, trace=True)
    out.pop("_run")
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    # the CPU has no peaks and no device plane: hybrid.decode_mfu stays out
    assert set(got) == {"replica.refresh_s", "replica.serve_s", "tensor.land_s.refresh",
                        "replica.upload_s", "replica.decode_s", "replica.prefill_s"}
    assert all(v > 0 for v in got.values())
    assert got["replica.prefill_s"] + got["replica.decode_s"] <= got["replica.serve_s"]
    decode = [s for s in obs.SPANS.spans() if s["n"] == "replica.decode"]
    assert decode and all(s["ar"]["kv_bytes"] > 0 and s["ar"]["ssm_bytes"] > 0
                          for s in decode)


def test_a_changed_served_version_fails_the_parameter_check(monkeypatch):
    """A replica that serves weights the trainer never published: one
    element of the served state differs from every version."""
    real = loop.identify

    def off_by_one(r, flat):
        k = real(r, flat)
        return None if k == 1 else k

    monkeypatch.setattr(loop, "identify", off_by_one)
    out = _run(TINY_H, "serve_rollout", prompt=13, seconds=1.5)
    out.pop("_run")
    assert out["checks"]["version_errors"]["value"] > 0
    assert out["correct"] is False
