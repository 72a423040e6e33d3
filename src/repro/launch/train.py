"""End-to-end training driver with checkpoint/restart fault tolerance.

Usage (CPU-scale example — see examples/train_lm.py for the ~100M run):

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt --global-batch 8 --seq-len 128
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_arch
from repro.data import SyntheticLM
from repro.launch import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import (
    TrainOptions,
    init_params,
    init_train_state,
    make_train_step,
)
from repro.models import build_model


def train(
    arch: str,
    *,
    smoke: bool = True,
    steps: int = 50,
    global_batch: int = 8,
    seq_len: int = 128,
    ckpt_dir: str = "/tmp/repro_ckpt",
    ckpt_every: int = 20,
    peak_lr: float | None = None,
    compress_grads: bool = False,
    resume: bool = True,
    log_every: int = 10,
    num_microbatches: int | None = None,
):
    cfg = get_arch(arch)
    if smoke:
        cfg = cfg.reduced()
    if num_microbatches is not None:
        cfg = dataclasses.replace(cfg, num_microbatches=num_microbatches)
    model = build_model(cfg)
    mesh = make_local_mesh()
    if peak_lr is None:
        # The reduced smoke models are a few hundred K params; at the
        # full-size default (3e-4) they move less per step than the
        # batch-to-batch loss noise of the synthetic stream, so short smoke
        # runs can't show descent.  Tiny models take a bigger step.
        peak_lr = 3e-3 if smoke else 3e-4
    opts = TrainOptions(
        peak_lr=peak_lr, warmup_steps=max(steps // 10, 1), total_steps=steps,
        compress_grads=compress_grads,
    )

    params = init_params(model, 0, mesh)
    opt_state, err = init_train_state(model, params, opts)

    ckpt = Checkpointer(Path(ckpt_dir))
    start_step = 0
    if resume and ckpt.latest_step() is not None:
        (params, opt_state), start_step = ckpt.restore((params, opt_state))
        print(f"resumed from step {start_step}")

    data = SyntheticLM(cfg.vocab_size, seq_len, global_batch, seed=17)
    step_fn = jax.jit(
        make_train_step(model, opts), donate_argnums=(0, 1),
    )

    losses = []
    t_start = time.time()
    for step in range(start_step, steps):
        batch = {k: jnp.asarray(v) for k, v in data.batch(step).items()}
        params, opt_state, err, metrics = step_fn(params, opt_state, err, batch)
        losses.append(float(metrics["loss"]))
        if step % log_every == 0 or step == steps - 1:
            dt = time.time() - t_start
            print(f"step {step:5d}  loss {losses[-1]:.4f}  ({dt:.1f}s)")
        if (step + 1) % ckpt_every == 0 or step == steps - 1:
            # async: snapshot now, write in background (one in flight)
            ckpt.save_async(step + 1, (params, opt_state))
    ckpt.wait()
    return losses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--lr", type=float, default=None)  # None: auto by scale
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--no-resume", dest="resume", action="store_false")
    args = ap.parse_args()
    use_compile_cache()
    train(
        args.arch,
        smoke=args.smoke,
        steps=args.steps,
        global_batch=args.global_batch,
        seq_len=args.seq_len,
        ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every,
        peak_lr=args.lr,
        compress_grads=args.compress_grads,
        resume=args.resume,
    )


if __name__ == "__main__":
    main()
