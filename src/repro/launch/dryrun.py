import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ The dry-run (and ONLY the dry-run) builds the production meshes out of
# 512 host placeholder devices; these two lines must precede any jax import.

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the single-pod (16, 16) and multi-pod (2, 16, 16) production meshes.

Per cell this captures, into dryrun_out/<arch>__<shape>__<mesh>.json:
  - compiled.memory_analysis()  (per-device bytes: args/outputs/temps/code)
  - compiled.cost_analysis()    (per-device HLO FLOPs and bytes accessed)
  - per-kind collective bytes parsed from the post-SPMD optimized HLO
  - lower/compile wall times

Usage:
  python -m repro.launch.dryrun --arch gemma3-12b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs N]
"""
import argparse
import json
import re
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.launch import mesh as mesh_lib
from repro.launch.specs import (
    abstract_params_sharded,
    abstract_state_sharded,
    batch_specs,
    decode_specs,
)
from repro.models.config import SHAPES, get_config, list_archs, shape_cells
from repro.models.transformer import Model
from repro.sharding import use_ctx
from repro.train.step import TrainConfig, make_train_step
from repro.train.optimizer import OptConfig

OUT_DIR = Path(__file__).resolve().parents[3] / "dryrun_out"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of all shapes in an HLO result type string."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-device bytes moved by each collective kind, from post-SPMD HLO.

    Factors: all-reduce moves ~2x its payload (ring reduce+broadcast);
    all-gather / reduce-scatter / all-to-all / collective-permute ~1x. The
    payload is the op result size in the per-device (partitioned) module.
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if s.startswith("%") or s.startswith("ROOT"):
            for kind in _COLLECTIVES:
                if f" {kind}(" in s or f" {kind}-start(" in s:
                    lhs = s.split(" = ", 1)
                    if len(lhs) != 2:
                        continue
                    nbytes = _shape_bytes(lhs[1].split("(", 1)[0])
                    factor = 2 if kind == "all-reduce" else 1
                    out[kind] += nbytes * factor
                    out["count"] += 1
                    break
    return out


def _memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if not out:
        out["repr"] = repr(ma)
    return out


def _cost_analysis_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and not k.startswith("utilization")}


N_MICROBATCHES = 8
FSDP_THRESHOLD = 100e9  # params above this get FSDP + bf16 grad accumulation


def train_config(cfg) -> TrainConfig:
    big = cfg.param_count()[0] > FSDP_THRESHOLD
    return TrainConfig(
        n_microbatches=N_MICROBATCHES,
        opt=OptConfig(name="adamw8"),
        grad_accum_dtype="bfloat16" if big else "float32",
        fsdp_params=big,
    )


def analysis_points(cfg) -> list[tuple[str, object]]:
    """Reduced-depth configs for exact per-op analysis.

    Per-layer HLO cost is exactly linear in the layer count, so two (or,
    with a tail segment, three) shallow unrolled compiles determine the
    full-depth FLOPs / bytes / collectives: the roofline script solves
      cost(L) = fixed + n_super * c_super (+ c_tail).
    Unrolling the full 35-81 layer stacks would take tens of minutes per
    cell on this 1-core container; the shallow points compile in seconds.
    """
    import dataclasses as _dc

    pts = []
    if cfg.layer_period:
        per = cfg.layer_period
        tail = cfg.n_layers % per
        pts.append((f"L{per}", _dc.replace(cfg, n_layers=per)))
        pts.append((f"L{2 * per}", _dc.replace(cfg, n_layers=2 * per)))
        if tail:
            pts.append((f"L{per + tail}",
                        _dc.replace(cfg, n_layers=per + tail)))
    elif cfg.kind in ("encdec", "audio"):
        pts.append(("L2", _dc.replace(cfg, n_layers=2, n_enc_layers=2)))
        pts.append(("L4", _dc.replace(cfg, n_layers=4, n_enc_layers=4)))
    else:
        pts.append(("L2", _dc.replace(cfg, n_layers=2)))
        pts.append(("L4", _dc.replace(cfg, n_layers=4)))
    return pts


def build_lowerable(cfg, shape_name: str, variant: str = "true"):
    """Returns (fn, abstract_args, jit_kwargs) for the cell.

    Variants:
      'true' : the production program (scanned layers / microbatches) —
               this is the compile + memory_analysis gate.
      'grad' : one microbatch fwd+bwd — with unrolled scans this yields
               exact per-op FLOPs / bytes / collectives; scale x8.
      'opt'  : full train_step at n_microbatches=1 on one microbatch —
               ('opt' - 'grad') isolates the optimizer update.
      For prefill/decode the same step is simply re-lowered unrolled.
    """
    import dataclasses as _dc

    model = Model(cfg)
    shape = SHAPES[shape_name]
    if shape.mode == "train":
        if variant == "true":
            tcfg = train_config(cfg)
            state = abstract_state_sharded(model, tcfg)
            pshard = jax.tree.map(lambda s: getattr(s, "sharding", None),
                                  state["params"])
            step = make_train_step(model, tcfg, param_shardings=pshard)
            batch = batch_specs(cfg, shape)
            return step, (state, batch), dict(donate_argnums=(0,))
        micro = _dc.replace(shape,
                            global_batch=shape.global_batch // N_MICROBATCHES)
        if variant == "grad":
            def grad_step(params, batch):
                return jax.value_and_grad(model.loss)(params, batch)
            tcfg = train_config(cfg)
            if tcfg.fsdp_params:
                # params must carry their FSDP shardings here, else the
                # per-layer weight all-gathers are not counted
                params = abstract_state_sharded(model, tcfg)["params"]
            else:
                params = abstract_params_sharded(model)
            batch = batch_specs(cfg, micro)
            return grad_step, (params, batch), {}
        if variant == "opt":
            # The optimizer update lowered alone (abstract grads in) — its
            # cost adds to 8x the grad variant for the full-step totals.
            from repro.train.optimizer import apply_updates
            tcfg = train_config(cfg)
            state = abstract_state_sharded(model, tcfg)
            gdt = jnp.dtype(tcfg.grad_accum_dtype)
            grads = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, gdt,
                                               sharding=s.sharding
                                               if hasattr(s, "sharding")
                                               else None),
                state["params"])

            def opt_step(state, grads):
                p, o, metrics = apply_updates(state["params"], grads,
                                              state["opt"], tcfg.opt)
                return {"params": p, "opt": o}, metrics

            return opt_step, (state, grads), dict(donate_argnums=(0,))
        raise ValueError(variant)
    if shape.mode == "prefill":
        def prefill_step(params, batch):
            return model.prefill(params, batch, cache_len=shape.seq_len)
        tcfg = train_config(cfg)
        if tcfg.fsdp_params:
            # >100B archs: weights must stay FSDP-sharded in prefill too
            # (2 TB of bf16 params do not fit at model-axis-only sharding);
            # prefill is compute-heavy so the per-layer gathers amortize.
            params = abstract_state_sharded(model, tcfg)["params"]
        else:
            params = abstract_params_sharded(model)
        batch = batch_specs(cfg, shape)
        return prefill_step, (params, batch), {}
    # decode
    def serve_step(params, cache, tokens):
        return model.decode_step(params, cache, tokens)
    params = abstract_params_sharded(model)
    cache, tokens = decode_specs(model, shape)
    return serve_step, (params, cache, tokens), dict(donate_argnums=(1,))


def _decode_rules(cfg):
    """Rule overrides for decode cells: MoE giants use 2D expert sharding —
    experts over 'model', the expert FF dim over ('pod', 'data') — so 480B/1T
    weights fit per-device without per-token gathers (see moe._moe_decode_2d).
    """
    if cfg.kind == "moe":
        return {"batch": ("data",), "experts": ("model",),
                "expert_ff": ("pod", "data")}
    return None


def _lower_and_analyse(cfg, shape_name, mesh, variant, unroll):
    rec = {"n_layers": cfg.n_layers}
    mode = SHAPES[shape_name].mode
    rules = _decode_rules(cfg) if mode == "decode" else None
    with use_ctx(mesh, rules=rules, unroll=unroll):
        fn, args, jit_kw = build_lowerable(cfg, shape_name, variant)
        t0 = time.time()
        lowered = jax.jit(fn, **jit_kw).lower(*args)
        rec["lower_s"] = round(time.time() - t0, 2)
        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)
        rec["memory"] = _memory_analysis_dict(compiled)
        rec["cost"] = _cost_analysis_dict(compiled)
        hlo = compiled.as_text()
        rec["collectives"] = collective_bytes(hlo)
        rec["hlo_bytes"] = len(hlo)
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             verbose: bool = True, analysis: bool = True) -> dict:
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cfg = get_config(arch)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "devices": int(mesh.size), "n_microbatches": N_MICROBATCHES}
    mode = SHAPES[shape_name].mode
    # The production program at full depth: compile + memory gate.
    rec["true"] = _lower_and_analyse(cfg, shape_name, mesh, "true",
                                     unroll=False)
    if analysis:
        # Exact per-op accounting: shallow depth points, unrolled scans;
        # benchmarks/roofline.py extrapolates linearly in layer count.
        variants = ["grad", "opt"] if mode == "train" else ["true"]
        for variant in variants:
            key = {"true": "unrolled"}.get(variant, variant)
            rec[key + "_pts"] = [
                dict(label=lbl,
                     **_lower_and_analyse(rcfg, shape_name, mesh, variant,
                                          unroll=True))
                for lbl, rcfg in analysis_points(cfg)
            ]
    if verbose:
        t = rec["true"]
        pts = rec.get("grad_pts") or rec.get("unrolled_pts") or []
        ana = pts[-1] if pts else t
        print(f"[{arch} {shape_name} {mesh_name}] "
              f"compile={t['compile_s']}s "
              f"flops/dev(pt)={ana['cost'].get('flops', 0):.3e} "
              f"temp/dev={t['memory'].get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
              f"coll(pt)={sum(v for k, v in ana['collectives'].items() if k != 'count')/2**30:.2f}GiB",
              flush=True)
    return rec


def cell_path(arch: str, shape_name: str, multi_pod: bool) -> Path:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    return OUT_DIR / f"{arch}__{shape_name}__{mesh_name}.json"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()
    OUT_DIR.mkdir(exist_ok=True)

    cells: list[tuple[str, str, bool]] = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    for arch in archs:
        shapes = shape_cells(arch) if (args.all or args.shape is None) \
            else [args.shape]
        for sh in shapes:
            if args.both_meshes:
                cells.append((arch, sh, False))
                cells.append((arch, sh, True))
            else:
                cells.append((arch, sh, args.multi_pod))

    failures = []
    for arch, sh, mp in cells:
        path = cell_path(arch, sh, mp)
        if path.exists() and not args.force:
            print(f"[skip] {path.name} exists")
            continue
        try:
            rec = run_cell(arch, sh, mp)
            path.write_text(json.dumps(rec, indent=1))
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            failures.append((arch, sh, mp, f"{type(e).__name__}: {e}"))
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        return 1
    print(f"dry-run OK: {len(cells)} cells")
    return 0


if __name__ == "__main__":
    sys.exit(main())
