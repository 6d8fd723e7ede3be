"""Share of its roofline that the ``block_delta`` kernel reached in the
window, in %: over all of its events in the device trace, the least time
each call could take on this chip (the larger of bytes over peak
bandwidth and FLOPs over peak FLOP/s, from the call's operand shape by
``harness.counts.block_delta_cost``) over the device time the events
took. Moves ``ckpt_delta_s``."""
from harness.counts import block_delta_cost
from harness.trace import operand_shapes


def read(r):
    events = r.trace_view.kernel_events("block_delta") if r.trace_view else []
    if not events or not r.peaks:
        return None
    least = spent = 0.0
    for e in events:
        shapes = operand_shapes(e)
        if not shapes or len(shapes[0][1]) != 3:
            return None
        nb, rows, lanes = shapes[0][1]
        flops, nbytes = block_delta_cost(nb, rows * lanes)
        least += max(nbytes / r.peaks["hbm_bytes_per_s"], flops / r.peaks["flops_bf16"])
        spent += e["dur"] / 1e9
    return 100.0 * least / spent if spent > 0 else None
