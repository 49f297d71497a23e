"""End-to-end training driver example.

Presets:
  tiny  (default): ~0.4M-param stablelm-family model, 60 steps — finishes in
         ~a minute on this CPU container and shows a clear loss drop.
  100m : ~100M-param model, a few hundred steps — the deliverable-scale run
         (hours on CPU; the intended substrate is a TPU slice where the same
         program runs through repro.launch.train).

Includes async checkpointing and a mid-run restore to demonstrate
fault-tolerant restart.

Run:  PYTHONPATH=src python examples/train_lm.py [--preset 100m]
"""
import argparse
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.ckpt import CheckpointManager  # noqa: E402
from repro.data import Prefetcher, SyntheticLM  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.models.transformer import Model  # noqa: E402
from repro.train import OptConfig, TrainConfig, make_train_step  # noqa: E402
from repro.train.step import init_train_state  # noqa: E402

PRESETS = {
    "tiny": dict(
        cfg=ModelConfig(name="tiny-lm", kind="dense", n_layers=4, d_model=128,
                        n_heads=4, n_kv_heads=2, d_ff=384, vocab=512,
                        param_dtype="float32", compute_dtype="float32"),
        steps=60, batch=16, seq=64, lr=2e-3),
    "100m": dict(
        cfg=ModelConfig(name="lm-100m", kind="dense", n_layers=12,
                        d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
                        vocab=32000, param_dtype="float32",
                        compute_dtype="float32"),
        steps=300, batch=32, seq=256, lr=6e-4),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args()
    preset = PRESETS[args.preset]
    cfg = preset["cfg"]
    steps = args.steps or preset["steps"]

    model = Model(cfg)
    tcfg = TrainConfig(
        n_microbatches=2,
        opt=OptConfig(name="adamw8", lr=preset["lr"], warmup=10,
                      total_steps=steps))
    data = SyntheticLM(cfg.vocab, preset["seq"], preset["batch"], seed=17)
    state = init_train_state(model, 0, tcfg)
    n = sum(p.size for p in jax.tree.leaves(state["params"]))
    print(f"{cfg.name}: {n/1e6:.1f}M params, {steps} steps, "
          f"batch {preset['batch']} x seq {preset['seq']}")

    step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
    ckdir = tempfile.mkdtemp(prefix="repro_ckpt_")
    mgr = CheckpointManager(ckdir, keep=2)
    pf = Prefetcher(data)
    first = mid = None
    t0 = time.time()
    try:
        for i in range(steps):
            _, batch = pf.next()
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            state, m = step_fn(state, batch)
            loss = float(m["loss"])
            first = first if first is not None else loss
            if i == steps // 2:
                mid = loss
                mgr.save(i, state)  # async checkpoint mid-run
            if i % max(steps // 10, 1) == 0 or i == steps - 1:
                print(f"step {i:4d} loss {loss:.4f} "
                      f"({(time.time()-t0)/(i+1):.2f}s/step)")
    finally:
        pf.close()
        mgr.wait()

    # fault-tolerance: restore the mid-run checkpoint and take one step
    st = mgr.latest_step()
    restored, _ = mgr.restore(st, jax.eval_shape(lambda: state))
    _, m = step_fn(restored, batch)
    print(f"restored step {st}: next-step loss {float(m['loss']):.4f}")
    print(f"loss: start {first:.3f} -> mid {mid:.3f} -> end {loss:.3f}")
    assert loss < first, "training failed to reduce loss"
    print("OK")


if __name__ == "__main__":
    main()
