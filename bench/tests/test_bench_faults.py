"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole run on the CPU (the look for a chip skipped)
with one fault planted in the program: a step that leaves the state as
it was, half of the batch left out, an answer altered where it is made.
One chip has no exchange between chips to leave out. The
lower-precision controls are in ``test_bench_controls.py``."""
import numpy as np
import pytest

import tiny

#: wide enough that a wrong token's logit lies well below the best
MID = dict(tiny.TINY, name="mid", hidden_size=512, intermediate_size=1024,
           num_attention_heads=8, num_hidden_layers=1, vocab_size=1024)


def not_correct(out: dict) -> bool:
    out.pop("_run")
    return out["correct"] is False


# --------------------------------------------------------------- serving --
def test_a_token_altered_where_it_is_served(monkeypatch):
    from repro.serving.engine import SnapshotServer

    real = SnapshotServer.serve

    def serve(self, batch):
        out = np.array(real(self, batch))
        out[0, 2] = (out[0, 2] + 1 + out[0, 2] % 7) % MID["vocab_size"]
        return out

    monkeypatch.setattr(SnapshotServer, "serve", serve)
    out = tiny.run("serve_refresh", config=MID, seconds=2.0)
    assert out["checks"]["logit_gap"]["value"] > out["checks"]["logit_gap"]["limit"]
    assert not_correct(out)


def test_half_of_the_batch_left_out_of_serving(monkeypatch):
    from repro.serving.engine import SnapshotServer

    real = SnapshotServer.serve
    monkeypatch.setattr(SnapshotServer, "serve",
                        lambda self, batch: real(self, batch[: len(batch) // 2]))
    out = tiny.run("serve_refresh", seconds=2.0)
    assert out["checks"]["malformed_outputs"]["value"] > 0
    assert not_correct(out)


def test_a_refresh_that_keeps_the_old_weights(monkeypatch):
    from repro.serving.engine import SnapshotServer

    real = SnapshotServer.refresh

    def refresh(self):
        if self.params is None:
            return real(self)
        return self.version

    monkeypatch.setattr(SnapshotServer, "refresh", refresh)
    out = tiny.run("serve_refresh", seconds=2.5)
    assert out["checks"]["version_errors"]["value"] > 0
    assert not_correct(out)


# ----------------------------------------------------------- checkpoints --
@pytest.mark.parametrize("traffic", ["ckpt_delta", "ckpt_full"])
def test_a_save_that_writes_nothing(monkeypatch, traffic):
    from repro.core.tensorstate import TensorStore

    real = TensorStore.write_part
    calls = []

    def write_part(self, name, part):
        calls.append(name)
        if len(calls) > 1:  # the set-up's base save goes through
            return None
        return real(self, name, part)

    monkeypatch.setattr(TensorStore, "write_part", write_part)
    assert not_correct(tiny.run(traffic, seconds=1.0))


@pytest.mark.parametrize("traffic", ["ckpt_delta", "ckpt_full"])
def test_a_byte_altered_where_it_is_written(monkeypatch, traffic):
    from repro.core.tensorstate import TensorStore

    real = TensorStore.write_part

    def write_part(self, name, part):
        bad = []
        for lname, raw, lo, hi in part:
            raw = raw.copy()
            raw[lo] ^= 1
            bad.append((lname, raw, lo, hi))
        return real(self, name, bad)

    monkeypatch.setattr(TensorStore, "write_part", write_part)
    out = tiny.run(traffic, seconds=1.0)
    assert out["checks"]["restore_mismatch"]["value"] > 0
    assert not_correct(out)


def test_half_of_the_dirty_blocks_left_out(monkeypatch):
    from repro.state import checkpoint

    real = checkpoint.dirty_block_indices

    def half(new, old, block_bytes, impl="auto"):
        idx = real(new, old, block_bytes, impl)
        return idx[: len(idx) // 2] if idx else idx

    monkeypatch.setattr(checkpoint, "dirty_block_indices", half)
    out = tiny.run("ckpt_delta", seconds=1.0)
    assert out["checks"]["dirty_mismatch"]["value"] > 0
    assert not_correct(out)
