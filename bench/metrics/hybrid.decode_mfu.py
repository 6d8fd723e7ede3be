"""Share of the chip's peak FLOP/s that the replica's decode program
reached on an interleaved Mamba-2 and attention model, in %: the FLOPs of
every decode step the device trace shows
(``harness.granite_counts.decode_flops``) over the device time of those
executions times the peak. A request of ``prompt_tokens`` prompt tokens
and ``tokens`` served tokens prefills the prompt (its own program, not
counted) and then runs ``tokens - 1`` decode steps, step ``j`` attending
to ``prompt_tokens + j + 1`` positions; a one-token prompt runs
``tokens`` steps, step ``j`` attending to ``j + 1``. Moves
``serve_tok_s``."""
from harness.granite_counts import decode_flops

#: the HLO module of ``make_decode_fn``'s jitted step
PROGRAM = "jit_decode_one"


def read(r):
    t = r.trace_view
    runs = [e for e in t.program_runs(PROGRAM) if t.t0 <= e["ts"] < t.t1] if t else []
    secs = sum(e["dur"] for e in runs) / 1e9
    if not runs or not r.peaks or secs <= 0:
        return None
    batch, prompt, tokens = r.mix["batch"], r.mix["prompt_tokens"], r.mix["tokens"]
    steps, first = (tokens - 1, prompt + 1) if prompt > 1 else (tokens, 1)
    flops = sum(batch * decode_flops(r.config, first + i % steps) for i in range(len(runs)))
    return 100.0 * flops / (secs * r.peaks["flops_bf16"])
