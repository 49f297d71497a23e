"""Production training driver.

It runs the same program on whatever devices exist: one CPU device in a
test, the chips of a TPU host.

  PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --smoke \
      --steps 50 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.ckpt import CheckpointManager
from repro.data import Prefetcher, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import get_config, get_smoke_config
from repro.models.transformer import Model
from repro.train import OptConfig, TrainConfig, make_train_step
from repro.train.step import init_train_state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--opt", default="adamw8", choices=["adamw", "adamw8"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    tcfg = TrainConfig(
        n_microbatches=args.microbatches,
        opt=OptConfig(name=args.opt, lr=args.lr, warmup=10,
                      total_steps=args.steps * 2),
    )
    data = SyntheticLM(cfg.vocab, args.seq, args.batch, seed=17)
    state = init_train_state(model, 0, tcfg)
    n_params = sum(p.size for p in jax.tree.leaves(state["params"]))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"opt={args.opt} batch={args.batch} seq={args.seq}")

    start = 0
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    if mgr and args.resume and mgr.latest_step() is not None:
        start = mgr.latest_step() + 1
        state, _ = mgr.restore(start - 1, jax.eval_shape(lambda: state))
        print(f"resumed from step {start - 1}")

    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
    pf = Prefetcher(data, start_step=start)
    t0 = time.time()
    try:
        for i in range(start, args.steps):
            step_idx, batch = pf.next()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, metrics = step_fn(state, batch)
            if i % 10 == 0 or i == args.steps - 1:
                dt = (time.time() - t0) / max(i - start + 1, 1)
                print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} [{dt:.2f}s/step]")
            if mgr and (i % args.ckpt_every == args.ckpt_every - 1):
                mgr.save(i, state)  # async
    finally:
        pf.close()
        if mgr:
            mgr.wait()
    print("done")


if __name__ == "__main__":
    main()
