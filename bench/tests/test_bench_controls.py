"""The lower-precision controls come out not correct, at sizes a test run
can hold. (At each cell's own size they ran on the chip; PERF.md gives
their readings beside the limits.)

Serving: the plain reference computed in float8 in place of the program;
at each position of the tokens the program served, the gap of the token
that it puts first has to exceed the limit, while the served tokens' own
gap stays under it. It is read at qwen2-1.5b's widths on one layer and a
sliced vocabulary, over 128 served tokens, from bfloat16 weights as the
replica gets them: a narrower model has logits too close together for any
precision to be told apart.
Checkpoints: every save given the state rounded to bfloat16."""
import numpy as np
import pytest

import tiny
from harness import reference, serve_loop, weights

WIDE = dict(tiny.TINY, name="wide", hidden_size=1536, intermediate_size=8960,
            num_attention_heads=12, num_key_value_heads=2, num_hidden_layers=1,
            vocab_size=4096, block_bytes=65536)


@pytest.mark.parametrize("seed", [2 ** 31 + 1, 2 ** 31 + 5])
def test_float8_reference_in_place_of_the_decode_fails_the_logit_gap(seed):
    from repro.launch.serve import make_decode_fn

    p = weights.make_params(WIDE, seed, WIDE["torch_dtype"])
    prompts = np.random.default_rng(seed).integers(
        0, WIDE["vocab_size"], (8, 1), dtype=np.int32)
    served = np.asarray(make_decode_fn(serve_loop.model_config(WIDE), 16)(
        {"params": p}, prompts))
    sound = reference.widest_gap(WIDE, p, served)
    control = reference.widest_gap(WIDE, p, served, serve_loop.CONTROL_CAST)
    assert sound <= serve_loop.LOGIT_GAP_LIMIT < control


@pytest.mark.parametrize("traffic", ["ckpt_delta", "ckpt_full"])
def test_bfloat16_checkpoints_fail_the_restore(traffic):
    out = tiny.run(traffic, seconds=1.0, control=True)
    out.pop("_run")
    assert out["checks"]["restore_mismatch"]["value"] > 0
    assert out["correct"] is False
