"""The reduction from a device trace to numbers, on small synthetic
traces whose answers are worked out by hand."""
from types import SimpleNamespace

import pytest

import tiny  # noqa: F401  (puts the harness on the path)
from harness import cell, counts
from harness.trace import Trace

DEV = "/device:TPU:0"


def ev(plane, line, name, ts, dur, **stats):
    return {"plane": plane, "line": line, "name": name, "ts": float(ts),
            "dur": float(dur), "stats": stats}


SYNTH = [
    ev("/host:CPU", "main", "bench.window", 0, 1000),
    ev("/host:CPU", "main", "bench.save", 100, 600),
    ev("/host:CPU", "main", "bench.pull", 100, 100),
    ev(DEV, "XLA Ops", "fusion.1", 50, 50, hlo_module="jit_update"),  # [50, 100)
    ev(DEV, "XLA Ops", "block_delta", 300, 100, hlo_module="jit_compute_block_delta"),
    ev(DEV, "XLA Ops", "copy.2", 350, 100, hlo_module="jit_compute_block_delta"),
    ev(DEV, "XLA Ops", "fusion.1", 900, 200, hlo_module="jit_update"),  # clipped at 1000
    ev(DEV, "XLA Modules", "jit_update", 900, 200),
]


def test_busy_time_is_the_union_of_op_intervals_inside_the_window():
    t = Trace(SYNTH)
    assert t.window_s == 1000 / 1e9
    # [50,100) + [300,450) + [900,1000)
    assert t.busy_s() == pytest.approx(300 / 1e9)


def test_kernel_events_are_found_by_the_kernel_name():
    t = Trace(SYNTH)
    assert [e["ts"] for e in t.kernel_events("block_delta")] == [300.0]


def test_idle_gaps_are_named_by_the_innermost_open_span():
    gaps = dict(Trace(SYNTH).idle_gaps())
    # [0,50): no span; [100,300): middle 200, pull and save open -> pull;
    # [450,900): middle 675 -> save
    assert gaps == pytest.approx({"host": 50e-9, "bench.pull": 200e-9,
                                  "bench.save": 450e-9})


def test_top_ops_sum_by_program_and_op():
    top = dict(Trace(SYNTH).top_ops())
    assert top["jit_update/fusion.1"] == pytest.approx(150e-9)
    assert top["jit_compute_block_delta/block_delta"] == pytest.approx(100e-9)


#: a ``block_delta`` call over the embedding's 3,561 blocks, as the chip's
#: trace names it (the HLO text of the custom call, layouts and all)
KERNEL_HLO = (
    "%block_delta.1 = (s8[3561,512,128]{2,1,0:T(8,128)(4,1)}, "
    "f32[3561,1,128]{2,1,0:T(1,128)S(1)}, f32[3561,1,128]{2,1,0:T(1,128)S(1)}, "
    "f32[3561,1,128]{2,1,0:T(1,128)S(1)}) custom-call(f32[3561,512,128]{2,1,0:T(8,128)} "
    "%slice, f32[3561,512,128]{2,1,0:T(8,128)} %slice.1), "
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    "{f32[3561,512,128]{2,1,0}, f32[3561,512,128]{2,1,0}}")


def test_kernel_roofline_reads_the_operand_shapes_of_the_custom_call():
    n = 3561 * 512 * 128
    least = (9 * n + 3 * 3561 * 128 * 4) / 819e9  # bytes-bound on a v5e
    kernel = ev(DEV, "XLA Ops", "block_delta.1", 1e6, 2 * least * 1e9, hlo=KERNEL_HLO)
    window = ev("/host:CPU", "main", "bench.window", 0, 1e9)
    r = SimpleNamespace(trace_view=Trace([window, kernel]), peaks=cell.peaks("TPU v5 lite"))
    assert cell.reader("kernel.block_delta_roofline")(r) == pytest.approx(50.0)
    # a kernel event without its HLO text gives no shapes, and so no number
    r.trace_view = Trace(SYNTH)
    assert cell.reader("kernel.block_delta_roofline")(r) is None


def test_decode_mfu_counts_each_executed_step_over_its_device_time():
    c = tiny.TINY
    window = ev("/host:CPU", "main", "bench.window", 0, 1e9)
    # two requests of 3 steps, the second cut after 2; 1 ms each; an op
    # line beside them, and another program's run, which do not count
    runs = [ev(DEV, "XLA Modules", f"jit_decode_one({7 + i % 2})", 1e6 * (1 + 2 * i), 1e6)
            for i in range(5)]
    other = [ev(DEV, "XLA Ops", "fusion.3", 1e6, 1e6, hlo_module="jit_decode_one"),
             ev(DEV, "XLA Modules", "jit_argmax(3)", 2e6, 1e6)]
    r = SimpleNamespace(trace_view=Trace([window] + runs + other), config=c,
                        mix={"batch": 8, "tokens": 3}, peaks=cell.peaks("TPU v5 lite"))
    f = [8 * counts.decode_flops(c, k) for k in (1, 2, 3, 1, 2)]
    assert cell.reader("decode_mfu")(r) == pytest.approx(100 * sum(f) / (5e-3 * 197e12))
    # no decode program in the trace: nothing to read
    r.trace_view = Trace(SYNTH)
    assert cell.reader("decode_mfu")(r) is None
