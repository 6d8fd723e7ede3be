"""Production training launcher: ``--arch <id>`` selects an architecture.

On this CPU container it runs the REDUCED same-family config through the
full transactional stack (FaaSFS-backed state, delta checkpoints, OCC
retry); on a real pod the same driver takes ``--full`` and the production
mesh (the step function and shardings ``lower_cell`` builds).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SHAPES_BY_NAME, ShapeCell, get_config, list_configs, reduced_config
from repro.core.backend import BackendService
from repro.core.client import LocalServer
from repro.core.types import CachePolicy
from repro.data.pipeline import DataConfig, synth_batch
from repro.models import model as M
from repro.models.runtime import CellPlan, make_train_step, plan_cell, lower_cell
from repro.optim import adamw
from repro.state.checkpoint import CheckpointManager
from repro.train.loop import TransactionalTrainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_configs())
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--full", action="store_true",
                    help="lower the FULL config on the production mesh "
                         "(requires --xla_force_host_platform_device_count "
                         "or real TPUs; compile-only on CPU)")
    args = ap.parse_args()

    if args.full:
        mesh_mod = __import__("repro.launch.mesh", fromlist=["make_production_mesh"])
        mesh = mesh_mod.make_production_mesh()
        cfg = get_config(args.arch)
        plan = plan_cell(cfg, SHAPES_BY_NAME["train_4k"], mesh)
        lowered = lower_cell(plan, mesh)
        compiled = lowered.compile()
        print(compiled.memory_analysis())
        print("full config compiled; attach real devices to execute")
        return

    cfg = reduced_config(get_config(args.arch))
    print(f"arch={args.arch} (reduced: {cfg.param_count():,} params, "
          f"family={cfg.family})")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    state0 = jax.tree.map(np.asarray,
                          {"params": params, "opt": adamw.init_opt_state(params)})
    plan = CellPlan(cfg, ShapeCell("t", "train", args.seq, args.batch),
                    None, {}, M.NO_SHARDING, 0, 32)
    jit_step = jax.jit(make_train_step(
        plan, adamw.AdamWConfig(lr_peak=1e-3, warmup_steps=5, decay_steps=args.steps)))

    backend = BackendService(block_size=1 << 18, policy=CachePolicy.EAGER)
    local = LocalServer(backend)

    def train_step(state, batch):
        s, m = jit_step(jax.tree.map(jnp.asarray, state),
                        {k: jnp.asarray(v) for k, v in batch.items()})
        return s, {k: float(v) for k, v in m.items()}

    trainer = TransactionalTrainer(local, train_step, state0)
    cm = CheckpointManager(local, block_bytes=1 << 18)
    try:
        restored, start = cm.restore(state0)
        trainer.init(restored)
        print(f"resumed @ step {start}")
    except FileNotFoundError:
        trainer.init(state0)
        start = 0

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)
    t0 = time.time()
    for step in range(start, args.steps):
        res = trainer.step(synth_batch(dcfg, step))
        if step % 5 == 0:
            print(f"step {step:4d} loss={res.metrics['loss']:.4f} "
                  f"attempts={res.attempts} bytes={res.bytes_written:,}")
        if (step + 1) % args.ckpt_every == 0:
            cm.save(step + 1, trainer.read_state())
    print(f"done in {time.time()-t0:.1f}s; {trainer.stats.aborts} occ aborts")


if __name__ == "__main__":
    main()
