"""Serve stablelm-3b at full width and depth on one TPU chip.

The quickest proof that the system still starts on the chip. From the
root of a checkout:

    python3 chip_smoke.py

It builds the registry's ``stablelm-3b`` unchanged with random weights
from a seed, prints the planner's modelled period for it, and serves 16
seeded requests through ``ServeEngine`` (8 slots x 1024 positions, wall
clock, no admission planner). It checks that every request completes with
exactly its token budget inside the vocabulary, and that a request served
alone yields the same tokens as when it shared the batch with 7 others.

The served decode path runs XLA attention (``decode_attention_local``),
no Pallas kernel. This is a smoke run, not a benchmark: it prints no
roofline figure, and its times include a cold compile unless the
persistent compilation cache is warm.

On any platform other than a TPU it exits non-zero before serving. The
last line of a successful run is one JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.config import get_config  # noqa: E402
from repro.models.transformer import Model  # noqa: E402
from repro.obs import MetricsRegistry  # noqa: E402
from repro.pipeline import HeterogeneousSystem, plan_pipeline  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402

ARCH = "stablelm-3b"
SEED = 0
N_REQUESTS = 16
BATCH_SLOTS = 8
MAX_LEN = 1024
PROMPT_LENS = (16, 128)
NEW_TOKENS = 32


def make_requests(vocab: int, n: int = N_REQUESTS) -> list[Request]:
    """``n`` requests seeded by ``SEED``: prompt lengths uniform in
    ``PROMPT_LENS`` (inclusive), prompt tokens uniform over the vocabulary,
    ``NEW_TOKENS`` to generate."""
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=n)
    return [Request(rid=i, prompt=rng.integers(0, vocab, size=int(k)).tolist(),
                    max_new_tokens=NEW_TOKENS)
            for i, k in enumerate(lens)]


def serve(model: Model, params, requests: list[Request]) -> dict:
    """Queue every request at start and serve them on the wall clock until
    the engine is idle. Returns the engine's step and token counts and the
    wall seconds, first compile included."""
    metrics = MetricsRegistry()
    engine = ServeEngine(model, params, batch_slots=BATCH_SLOTS,
                         max_len=MAX_LEN, metrics=metrics)
    for req in requests:
        engine.submit(req)
    t0 = time.perf_counter()
    engine.run_until_idle()
    jax.block_until_ready(engine.cache)
    wall_s = time.perf_counter() - t0
    step_s = metrics.snapshot()["histograms"]["serve/step_s"]
    return {"steps": step_s["count"], "step_s_p50": step_s["p50"],
            "step_s_max": step_s["max"],
            "tokens": int(metrics.counter("serve/tokens")),
            "wall_s": wall_s}


def check_served(requests: list[Request], vocab: int) -> None:
    """Every request done, with exactly its budget of in-vocabulary
    tokens. Raises ``AssertionError`` naming the first that is not."""
    for req in requests:
        if not req.done or req.rejected:
            raise AssertionError(f"request {req.rid} did not complete")
        if len(req.out) != req.max_new_tokens:
            raise AssertionError(
                f"request {req.rid}: {len(req.out)} tokens, "
                f"expected {req.max_new_tokens}")
        bad = [t for t in req.out if not 0 <= t < vocab]
        if bad:
            raise AssertionError(
                f"request {req.rid}: tokens outside [0, {vocab}): {bad}")


def serve_and_check(model: Model, params, requests: list[Request]) -> dict:
    """The smoke's serve phase: serve ``requests`` together, check them,
    then serve the first one again alone (every other slot empty) and
    require the same tokens — one lane's output may not depend on what
    the other lanes hold."""
    vocab = model.cfg.vocab
    stats = serve(model, params, requests)
    check_served(requests, vocab)
    first = requests[0]
    solo = Request(rid=first.rid, prompt=list(first.prompt),
                   max_new_tokens=first.max_new_tokens)
    stats["solo"] = serve(model, params, [solo])
    check_served([solo], vocab)
    if solo.out != first.out:
        diverge = next(i for i, (a, b) in enumerate(zip(solo.out, first.out))
                       if a != b)
        raise AssertionError(
            f"lane isolation broken: request {first.rid} served alone "
            f"diverges from its batched run at output token {diverge}")
    return stats


def _gb(n: float) -> str:
    return f"{n / 1e9:.3f} GB"


def main() -> int:
    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    print(f"jax {jax.__version__}  device_kind {dev.device_kind}  "
          f"devices {len(jax.devices())}  compile cache {cache_dir}")

    cfg = get_config(ARCH)
    plan = plan_pipeline(cfg, system=HeterogeneousSystem.default(1, 1),
                         tokens_per_step=8, mode="decode")
    print(f"planner period (modelled, not measured): {plan.period_us:.1f} us "
          f"per step of 8 tokens")

    model = Model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(model.init(SEED))
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    print(f"model {cfg.name}: {n_params} params "
          f"({cfg.n_layers} layers, d_model {cfg.d_model}, {cfg.param_dtype}),"
          f" init {time.perf_counter() - t0:.2f} s")

    # the engine's step program, compiled here to report its size; the
    # engine compiles its own on its first step (in "max" below)
    t0 = time.perf_counter()
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, model.init_cache(BATCH_SLOTS, MAX_LEN, abstract=True),
        jax.ShapeDtypeStruct((BATCH_SLOTS,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    print(f"decode_step ({BATCH_SLOTS} slots x {MAX_LEN} positions): "
          f"compile {time.perf_counter() - t0:.2f} s, "
          f"arguments {_gb(mem.argument_size_in_bytes)}, "
          f"outputs {_gb(mem.output_size_in_bytes)}, "
          f"temporaries {_gb(mem.temp_size_in_bytes)}, "
          f"aliased {_gb(mem.alias_size_in_bytes)}")
    del compiled

    requests = make_requests(cfg.vocab)
    stats = serve_and_check(model, params, requests)
    solo = stats["solo"]
    print(f"served {sum(r.done for r in requests)}/{len(requests)} requests "
          f"({BATCH_SLOTS} slots): {stats['steps']} engine steps, "
          f"{stats['tokens']} tokens, {stats['wall_s']:.3f} s wall "
          f"(first compile included), step p50 {stats['step_s_p50']:.6f} s, "
          f"max {stats['step_s_max']:.6f} s")
    print(f"lane isolation held: request 0 alone ({solo['steps']} steps, "
          f"{solo['wall_s']:.3f} s) gives the same {NEW_TOKENS} tokens")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print("peak_bytes_in_use "
          + (_gb(peak) if peak is not None else "not reported"))

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
