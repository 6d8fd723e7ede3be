"""Share of the chip's peak FLOP/s that the replica's decode program
reached while it ran, in %: the FLOPs of every decode step the device
trace shows (``harness.counts.decode_flops``; each request's steps come
in order, step ``i`` attending to ``i + 1`` positions) over the device
time of those executions times the peak. Refreshes and uploads are not
in its time, so it moves apart from ``serve_tok_s`` when the decode
program itself gets faster or slower. Moves ``serve_tok_s``."""
from harness.counts import decode_flops

#: the HLO module of ``make_decode_fn``'s jitted step
PROGRAM = "jit_decode_one"


def read(r):
    t = r.trace_view
    runs = [e for e in t.program_runs(PROGRAM) if t.t0 <= e["ts"] < t.t1] if t else []
    secs = sum(e["dur"] for e in runs) / 1e9
    if not runs or not r.peaks or secs <= 0:
        return None
    batch, tokens = r.mix["batch"], r.mix["tokens"]
    flops = sum(batch * decode_flops(r.config, i % tokens + 1) for i in range(len(runs)))
    return 100.0 * flops / (secs * r.peaks["flops_bf16"])
