"""Roofline analysis from the dry-run artifacts (assignment §Roofline).

Per (arch x shape x mesh) cell, reconstruct full-depth per-device costs from
the shallow unrolled analysis points (exactly linear in layer count — see
repro.launch.dryrun.analysis_points) and derive the three roofline terms on
TPU v5e constants:

  compute_term    = HLO_FLOPs/device            / 197e12 FLOP/s
  memory_term     = analytic HBM traffic/device / 819e9  B/s
                    (see _analytic_memory_bytes; the raw HLO bytes-accessed
                    figure is reported separately as memory_hlo_s — on the
                    CPU backend it counts unfused op boundaries and
                    overstates TPU HBM traffic several-fold)
  collective_term = collective_bytes/device     / 50e9   B/s (ICI link)

plus MODEL_FLOPS = 6·N_active·tokens (train) / 2·N_active·tokens (inference)
and the usefulness ratio MODEL_FLOPS / HLO_FLOPs.

Train cells: total = 8 x grad-variant + optimizer-variant (the step has 8
microbatches). Decode/prefill cells: the unrolled variant is exact.

Also reports analytic per-kernel-variant roofline terms
(``print_variant_roofline``): structural MXU/VPU/HBM counts for each
selectable implementation in ``repro.kernels.registry``, as a sanity
anchor for the measured multipliers ``repro.control.calibrate`` fits
onto the scheduling variant axis.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16  # noqa: E402
from repro.models.config import SHAPES, get_config  # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[1] / "dryrun_out"

_COLL_KEYS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
              "collective-permute")


def _fields(rec: dict) -> dict:
    """Extract the extrapolatable numeric fields from one analysis point."""
    out = {"flops": rec["cost"].get("flops", 0.0),
           "bytes": rec["cost"].get("bytes accessed", 0.0)}
    for k in _COLL_KEYS:
        out[f"coll:{k}"] = float(rec["collectives"].get(k, 0))
    out["coll_total"] = sum(out[f"coll:{k}"] for k in _COLL_KEYS)
    return out


def _extrapolate(pts: list[dict], cfg) -> dict:
    """Reconstruct full-depth costs from shallow points (linear in depth)."""
    by_layers = {p["n_layers"]: _fields(p) for p in pts}
    Ls = sorted(by_layers)
    if cfg.layer_period:
        per = cfg.layer_period
        tail = cfg.n_layers % per
        n_super = cfg.n_layers // per
        c1, c2 = by_layers[per], by_layers[2 * per]
        out = {}
        for k in c1:
            sup = c2[k] - c1[k]
            fixed = c1[k] - sup
            t = (by_layers[per + tail][k] - c1[k]) if tail else 0.0
            out[k] = max(fixed + n_super * sup + t, 0.0)
        return out
    l1, l2 = Ls[0], Ls[1]
    c1, c2 = by_layers[l1], by_layers[l2]
    out = {}
    for k in c1:
        per_layer = (c2[k] - c1[k]) / (l2 - l1)
        fixed = c1[k] - l1 * per_layer
        out[k] = max(fixed + cfg.n_layers * per_layer, 0.0)
    return out


def _model_flops_per_device(cfg, shape, devices: int) -> float:
    _, n_active = cfg.param_count()
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens
    elif shape.mode == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.global_batch
    return total / devices


def _analytic_memory_bytes(cfg, shape, rec) -> float:
    """Required HBM traffic per device per step (fused-execution model).

    The XLA 'bytes accessed' statistic counts every HLO op boundary in the
    *CPU* module — without TPU fusion it overstates HBM traffic several-fold
    (it is reported as a diagnostic). This analytic model counts the traffic
    a well-fused TPU execution cannot avoid:

      train  : persistent state read+write (params/grad-accum/moments — the
               optimizer sweep), plus per-microbatch weight reads (gathered
               FSDP copies land in HBM) for fwd + remat + bwd, plus the
               residual-stream activation flow;
      prefill: weight reads + activation flow + KV cache writes;
      decode : weight reads (every step touches every live parameter shard)
               + KV/SSM cache read — the classic decode memory bound.
    """
    devices = rec["devices"]
    args = rec["true"]["memory"].get("argument_size_in_bytes", 0)
    total_params, active_params = cfg.param_count()
    p_bytes = 2.0  # bf16
    mode = shape.mode
    # per-device model-parallel shard of the weights (model axis = 16)
    w_local = total_params * p_bytes / 16.0
    if cfg.kind == "moe":
        # non-expert weights replicated-ish; experts dominate — use the full
        # sharded figure from the compiled args when available
        w_local = min(w_local, max(args, 1.0))
    tokens_local = shape.global_batch * shape.seq_len / devices
    act_flow = tokens_local * cfg.d_model * 2 * 12 * cfg.n_layers  # r/w x ops
    if mode == "train":
        n_mb = rec.get("n_microbatches", 8)
        state_sweep = 2.0 * args                      # read + write the state
        weight_reads = 3.0 * w_local * n_mb           # fwd + remat + bwd
        return state_sweep + weight_reads + 3 * act_flow
    if mode == "prefill":
        kv_write = tokens_local * cfg.n_kv_heads * cfg.hd * 2 * 2 \
            * cfg.n_layers
        return w_local + act_flow + kv_write
    # decode
    cache_read = args - min(w_local, args) if args > w_local else 0.0
    return min(w_local, args) + max(cache_read, 0.0) + act_flow / 100.0


def analyse_cell(path: Path) -> dict | None:
    rec = json.loads(path.read_text())
    cfg = get_config(rec["arch"])
    shape = SHAPES[rec["shape"]]
    mode = shape.mode
    if mode == "train":
        if "grad_pts" not in rec or "opt_pts" not in rec:
            return None
        grad = _extrapolate(rec["grad_pts"], cfg)
        opt = _extrapolate(rec["opt_pts"], cfg)
        total = {k: rec["n_microbatches"] * grad[k] + opt[k] for k in grad}
    else:
        if "unrolled_pts" not in rec:
            return None
        total = _extrapolate(rec["unrolled_pts"], cfg)

    devices = rec["devices"]
    compute_t = total["flops"] / PEAK_FLOPS_BF16
    mem_bytes = _analytic_memory_bytes(cfg, shape, rec)
    memory_t = mem_bytes / HBM_BW
    memory_hlo_t = total["bytes"] / HBM_BW  # diagnostic upper bound
    coll_t = total["coll_total"] / ICI_BW
    terms = {"compute": compute_t, "memory": memory_t, "collective": coll_t}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    mf = _model_flops_per_device(cfg, shape, devices)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "devices": devices,
        "flops_per_dev": total["flops"],
        "bytes_per_dev": mem_bytes,
        "bytes_hlo_per_dev": total["bytes"],
        "coll_bytes_per_dev": total["coll_total"],
        "coll_breakdown": {k.split(":", 1)[1]: total[k]
                           for k in total if k.startswith("coll:")},
        "compute_s": compute_t, "memory_s": memory_t,
        "memory_hlo_s": memory_hlo_t, "collective_s": coll_t,
        "dominant": dominant,
        "step_s_bound": bound,
        "model_flops_per_dev": mf,
        "useful_ratio": mf / total["flops"] if total["flops"] else 0.0,
        "roofline_fraction": (compute_t / bound) if bound else 0.0,
        "mem_args_gib": rec["true"]["memory"].get(
            "argument_size_in_bytes", 0) / 2**30,
        "mem_temp_gib": rec["true"]["memory"].get(
            "temp_size_in_bytes", 0) / 2**30,
    }


def all_cells() -> list[dict]:
    out = []
    for path in sorted(OUT_DIR.glob("*.json")):
        try:
            r = analyse_cell(path)
        except Exception as e:  # noqa: BLE001
            r = None
            print(f"# roofline: failed {path.name}: {e}", file=sys.stderr)
        if r:
            out.append(r)
    return out


def print_roofline() -> None:
    print("# roofline: three-term analysis per cell (seconds per step, "
          "per device; v5e constants)")
    print("roofline,arch,shape,mesh,compute_s,memory_s,collective_s,"
          "memory_hlo_s,dominant,useful_ratio,roofline_fraction,"
          "args_gib,temp_gib")
    for r in all_cells():
        print(f"roofline,{r['arch']},{r['shape']},{r['mesh']},"
              f"{r['compute_s']:.4g},{r['memory_s']:.4g},"
              f"{r['collective_s']:.4g},{r['memory_hlo_s']:.4g},"
              f"{r['dominant']},"
              f"{r['useful_ratio']:.3f},{r['roofline_fraction']:.3f},"
              f"{r['mem_args_gib']:.2f},{r['mem_temp_gib']:.2f}")


def markdown_table(mesh: str = "pod16x16") -> str:
    rows = [r for r in all_cells() if r["mesh"] == mesh]
    lines = [
        "| arch | shape | compute (s) | memory (s) | collective (s) | "
        "dominant | MODEL/HLO | roofline frac | args GiB/dev |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4g} | "
            f"{r['memory_s']:.4g} | {r['collective_s']:.4g} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.2f} | {r['mem_args_gib']:.1f} |")
    return "\n".join(lines)


# ---------------------------------------------------- per-kernel variants
# VPU throughput anchor: vector lanes issue far below MXU peak on v5e.
# The absolute figure is coarse; the per-variant RATIOS are the anchor —
# they use the same constant on both sides.
VPU_OPS = PEAK_FLOPS_BF16 / 64.0


def _flash_variant_counts(b, h, sq, skv, d, bk, dtype_bytes):
    """Structural MXU/VPU/HBM counts per flash-attention implementation.

    All three compute the same function; they differ in how often the
    score matrix is built (MXU), how much softmax bookkeeping runs on
    the VPU, and how often K/V cross HBM. Fused-execution lower bounds:

      base    — online softmax: one QK + one PV pass; every kv chunk
                rescales the (sq, d) accumulator and the running sum
                (the exp-correction traffic on the VPU); K/V read once.
      chunked — two-pass lazy softmax (Rabe & Staats): the score matrix
                is built TWICE (pass 1 for the final max, pass 2 for the
                exp-sum), so MXU work is ~1.5x — but the accumulator is
                never rescaled, dropping the per-chunk VPU correction;
                K is read twice.
      xla     — online softmax via lax.scan: base's counts, plus the
                per-chunk fp32 probability tensors that cross HLO
                boundaries when XLA does not fuse the chain (an upper
                bound on spill traffic).
    """
    qk = 2.0 * b * h * sq * skv * d
    pv = 2.0 * b * h * sq * skv * d
    nk = max(skv // bk, 1)
    exp_pass = b * h * sq * skv           # exp over every masked score
    rescale = b * h * sq * (d + 2) * nk   # acc/l/m corrections per chunk
    io_q = b * h * sq * d * dtype_bytes
    io_kv = b * h * skv * d * dtype_bytes
    io_o = b * h * sq * d * dtype_bytes
    spill = 2.0 * b * h * sq * skv * 4.0  # fp32 p write+read per chunk
    return {
        "base": {"mxu": qk + pv, "vpu": exp_pass + rescale,
                 "bytes": io_q + 2 * io_kv + io_o},
        "chunked": {"mxu": 2 * qk + pv, "vpu": exp_pass,
                    "bytes": io_q + 3 * io_kv + io_o},
        "xla": {"mxu": qk + pv, "vpu": exp_pass + rescale,
                "bytes": io_q + 2 * io_kv + io_o + spill},
    }


def _ssd_variant_counts(b, l, h, p, n, chunk, dtype_bytes):
    """Structural counts per SSD-scan implementation.

    base       — Pallas chunked scan: within-chunk parallel form plus
                 one inter-chunk state pass; states stay in VMEM.
      blocked  — pure-jnp block decomposition: the same math with the
                 per-chunk decay/cumsum tensors materialized through HBM.
      sequential — lax.scan over tokens: minimal arithmetic but the
                 (h, p, n) state crosses HBM every token — the classic
                 bandwidth wall that makes it the slow reference.
    """
    core = 6.0 * b * l * h * p * n        # B-expand + update + C-contract
    io = dtype_bytes * (2.0 * b * l * h * p + 2.0 * b * l * n) \
        + 4.0 * b * l * h                 # x/y + B/C + dt
    state = 4.0 * b * h * p * n           # one fp32 state snapshot
    n_chunks = max(l // chunk, 1)
    return {
        "base": {"mxu": core, "vpu": b * l * h * (p + n),
                 "bytes": io + state * n_chunks},
        "blocked": {"mxu": 1.5 * core, "vpu": 2.0 * b * l * h * (p + n),
                    "bytes": io + 3.0 * state * n_chunks},
        "sequential": {"mxu": core, "vpu": b * l * h * (p + n),
                       "bytes": io + 2.0 * state * l},
    }


def variant_roofline(*, b=1, h=16, sq=4096, skv=4096, d=128, bk=128,
                     ssd_l=4096, ssd_p=64, ssd_n=128, ssd_chunk=64,
                     dtype_bytes=2) -> list[dict]:
    """Per-(family, variant) roofline terms on v5e constants.

    Returns one row per selectable implementation with its MXU / VPU /
    HBM time terms, the dominant bound, and each term's ratio against
    the family's base implementation. The ratios are the analytic
    sanity anchor for measured multipliers (e.g. the DVB-S2 preset's
    chunked (big 1.30, little 0.82)): a bandwidth-bound core should see
    roughly the bytes ratio, a vector-bound core the vpu ratio — a
    fitted multiplier far outside [min, max] of the term ratios points
    at a calibration problem, not a real implementation gap.
    """
    families = {
        "flash_attention": _flash_variant_counts(b, h, sq, skv, d, bk,
                                                 dtype_bytes),
        "ssd_scan": _ssd_variant_counts(b, ssd_l, h, ssd_p, ssd_n,
                                        ssd_chunk, dtype_bytes),
    }
    rows = []
    for family, counts in families.items():
        base = counts["base"]
        for variant, c in counts.items():
            terms = {"mxu": c["mxu"] / PEAK_FLOPS_BF16,
                     "vpu": c["vpu"] / VPU_OPS,
                     "memory": c["bytes"] / HBM_BW}
            ratios = {k: c[k2] / base[k2] for k, k2 in
                      (("mxu", "mxu"), ("vpu", "vpu"),
                       ("memory", "bytes"))}
            rows.append({
                "family": family, "variant": variant,
                "mxu_s": terms["mxu"], "vpu_s": terms["vpu"],
                "memory_s": terms["memory"],
                "dominant": max(terms, key=terms.get),
                "mxu_vs_base": ratios["mxu"],
                "vpu_vs_base": ratios["vpu"],
                "memory_vs_base": ratios["memory"],
            })
    return rows


def print_variant_roofline() -> None:
    print("# variant-roofline: analytic per-implementation terms "
          "(v5e constants); *_vs_base ratios anchor calibrated "
          "scheduling multipliers")
    print("variant_roofline,family,variant,mxu_s,vpu_s,memory_s,"
          "dominant,mxu_vs_base,vpu_vs_base,memory_vs_base")
    for r in variant_roofline():
        print(f"variant_roofline,{r['family']},{r['variant']},"
              f"{r['mxu_s']:.4g},{r['vpu_s']:.4g},{r['memory_s']:.4g},"
              f"{r['dominant']},{r['mxu_vs_base']:.3f},"
              f"{r['vpu_vs_base']:.3f},{r['memory_vs_base']:.3f}")


if __name__ == "__main__":
    print_roofline()
    print_variant_roofline()


def write_markdown() -> None:
    """Generate ROOFLINE.md with tables for both meshes."""
    out = ["# Roofline tables (generated by benchmarks/roofline.py)", ""]
    for mesh in ("pod16x16", "pod2x16x16"):
        out.append(f"## mesh {mesh}")
        out.append("")
        out.append(markdown_table(mesh))
        out.append("")
    Path(__file__).resolve().parents[1].joinpath("ROOFLINE.md").write_text(
        "\n".join(out))
    print("wrote ROOFLINE.md")
