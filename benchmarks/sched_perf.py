"""Scheduling-layer host timings: plan latency, frontier sizes, and the
within-run A/B gates of the runtime, serving and observability layers.

Measures the energy/DVFS planning entry points the runtime governor sits
on (``repro.energy.pareto``) and the host-side machinery around them.
Every number is a host (CPU) timing, never a device one. ``--check``
gates only within-run, machine-independent ratios and counts, so it
needs no baseline from another machine; ``--out`` writes the entries as
JSON.

Seven measurement families:

- ``frontier``: ``pareto_frontier`` (nominal) and ``dvfs_frontier``
  (frequency-swept) end-to-end latency + frontier size, on the paper's
  four platform power models (DVB-S2 chains) and on synthetic chains up
  to n=32 tasks and 16+16 core budgets. Recorded, not gated.
- ``plan``: the governor's re-plan query ``min_period_under_power``
  against a prebuilt frontier (the cached-frontier fast path swapped at
  runtime). Recorded, not gated.
- ``control``: the runtime control layer — a steady-state governor
  ``observe`` tick (the per-window monitoring overhead, frontier cached)
  and a full ``StreamingPipelineRuntime.rebuild(mode="drain")`` swap
  (drain in-flight frames, join workers, re-materialize, restart — the
  stop-the-world path) on the DVB-S2 mac pipeline. Recorded, not gated.
- ``obs``: tracer overhead on the threaded runtime hot path — the
  steady-state period of the same pipeline with no tracer, a disabled
  tracer, and an enabled tracer recording one frame span per
  (frame, stage). CI-gated (``--check``): enabled tracing must inflate
  the period < 5%, disabled < 3% (measured live, machine-independent —
  the observability layer must stay cheap enough to leave on).
- ``serve``: the serving engine's admission machinery — the same request
  trace served with continuous (mid-run) admission vs the legacy
  step-0-only refill, on a stub model so the measurement is the engine
  loop, not the network. CI-gated (``--check``) with within-run,
  machine-independent invariants: continuous admission must not need
  more engine steps than step0 for the same work (deterministic), and
  its per-step admission overhead must not eat the batching win
  (requests/s ratio >= 0.9 live).
- ``runtime``: the worker-substrate A/B — process workers over
  shared-memory frame rings vs GIL-bound threads on a CPU-bound
  4-replica chain (throughput), and the rebuild traffic gap — live
  handoff mid-stream vs stop-the-world drain. CI-gated live
  (``--check``): exact delivery always; on multi-core hosts (``cores``
  recorded per entry) process throughput must reach >= 1.5x thread and
  the handoff gap must stay < 10% of the drain's.
- ``variant``: the kernel-variant axis — ``sweep_budgets_variant``'s
  stacked K x P table fill (V=3 variants) vs V sequential per-variant
  frequency sweeps producing the same points, CI-gated (``--check``)
  live at >= 1.5x; plus the ⊆-dominance invariant (every fixed-variant
  frontier point weakly dominated by the 4-axis frontier, zero
  violations allowed).

The vectorized sweeps are certified bit-identical to their scalar
references by tests/test_pareto_equiv.py, not here.

Usage:
    PYTHONPATH=src python benchmarks/sched_perf.py            # full grid
    PYTHONPATH=src python benchmarks/sched_perf.py --smoke    # CI subset
    PYTHONPATH=src python benchmarks/sched_perf.py --smoke --check \
        --out bench_sched.json     # gate, and keep the entries as JSON
"""
from __future__ import annotations

import argparse
import json
import math
import platform
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.configs.dvbs2 import RESOURCES, dvbs2_chain  # noqa: E402
from repro.control import ConstantBudget, Governor, Observation  # noqa: E402
from repro.control.sim import sleep_stage_builder  # noqa: E402
from repro.core.chain import make_chain  # noqa: E402
from repro.obs import Tracer  # noqa: E402
from repro.pipeline import StageSpec, StreamingPipelineRuntime  # noqa: E402
from repro.energy.model import DEFAULT_POWER, PLATFORM_POWER, PowerModel  # noqa: E402
from repro.core.variants import VariantRegistry  # noqa: E402
from repro.energy.pareto import (  # noqa: E402
    dvfs_frontier,
    min_period_under_power,
    pareto_frontier,
    sweep_budgets_freq,
    sweep_budgets_variant,
    variant_frontier,
)


# ------------------------------------------------------------- measurement
def _best_ms(fn, repeats: int) -> float:
    """Best-of-repeats wall latency in ms (first call warms caches).

    Minimum, not mean: scheduling noise on shared hosts only ever adds
    latency, so the minimum is the stable estimator of the code's cost —
    and it is applied to both arms of every comparison."""
    fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return min(times)


def _dvfs_model(power: PowerModel) -> PowerModel:
    if isinstance(power.freq_levels, tuple) and power.freq_levels == (1.0,):
        return PowerModel(power.name + "-dvfs", power.big, power.little,
                          freq_levels=(0.5, 0.75, 1.0))
    return power


def run(smoke: bool) -> dict:
    repeats = 3 if smoke else 5
    entries = []

    # the paper's four platform power models (Apple / Intel / ARM / AMD
    # presets, each with its own DVFS ladder) on the measured DVB-S2
    # chains: "mac"/"x7" use their native tables and machine budgets, the
    # other two run the mac chain at the mac half budgets
    plat_grid = [
        ("m1_ultra", "mac"), ("intel_185h", "x7"),
        ("arm", "mac"), ("amd", "mac"),
    ]
    if smoke:
        plat_grid = plat_grid[:2]
    for plat, table in plat_grid:
        chain = dvbs2_chain(table)
        power = PLATFORM_POWER[plat]
        budgets = sorted(RESOURCES[table].items()) \
            if plat in ("m1_ultra", "intel_185h") \
            else [("half", RESOURCES["mac"]["half"])]
        for cfg, (b, l) in budgets:
            if smoke and cfg != "half":
                continue
            front_n = pareto_frontier(chain, b, l, power)
            entries.append({
                "bench": "frontier", "mode": "nominal", "chain": f"dvbs2-{table}",
                "platform": plat, "n": chain.n, "b": b, "l": l,
                "frontier_size": len(front_n),
                "latency_ms": _best_ms(
                    lambda: pareto_frontier(chain, b, l, power), repeats),
            })
            front_d = dvfs_frontier(chain, b, l, power)
            entries.append({
                "bench": "frontier", "mode": "dvfs", "chain": f"dvbs2-{table}",
                "platform": plat, "n": chain.n, "b": b, "l": l,
                "frontier_size": len(front_d),
                "latency_ms": _best_ms(
                    lambda: dvfs_frontier(chain, b, l, power), repeats),
            })
            # governor re-plan: cached-frontier query at the median cap
            watts = sorted(pt.energy / pt.period for pt in front_d)
            cap = watts[len(watts) // 2]
            entries.append({
                "bench": "plan", "mode": "dvfs-cached", "chain": f"dvbs2-{table}",
                "platform": plat, "n": chain.n, "b": b, "l": l,
                "cap_w": cap,
                "latency_ms": _best_ms(
                    lambda: min_period_under_power(
                        chain, b, l, power, cap, dvfs=True,
                        frontier=front_d), repeats),
            })

    # synthetic scaling: chain sizes up to n=32, budgets up to 16+16
    grid = [(8, 4, 4), (16, 8, 8)] if smoke else \
        [(8, 4, 4), (16, 8, 8), (24, 12, 12), (32, 16, 16)]
    for n, b, l in grid:
        chain = make_chain(np.random.default_rng(7), n, 0.6)
        power = _dvfs_model(DEFAULT_POWER)
        front_n = pareto_frontier(chain, b, l, power)
        entries.append({
            "bench": "frontier", "mode": "nominal", "chain": f"synth-n{n}",
            "platform": "default", "n": n, "b": b, "l": l,
            "frontier_size": len(front_n),
            "latency_ms": _best_ms(
                lambda: pareto_frontier(chain, b, l, power), repeats),
        })
        front_d = dvfs_frontier(chain, b, l, power)
        entries.append({
            "bench": "frontier", "mode": "dvfs", "chain": f"synth-n{n}",
            "platform": "default", "n": n, "b": b, "l": l,
            "frontier_size": len(front_d),
            "latency_ms": _best_ms(
                lambda: dvfs_frontier(chain, b, l, power), repeats),
        })

    # control layer (ROADMAP PR 4 follow-up): governor tick cost and the
    # runtime rebuild (drain) latency on the DVB-S2 mac half pipeline
    ctl_chain = dvbs2_chain("mac")
    ctl_power = PLATFORM_POWER["m1_ultra"]
    ctl_b, ctl_l = RESOURCES["mac"]["half"]
    gov = Governor(ctl_chain, ctl_b, ctl_l, ctl_power,
                   ConstantBudget(1e9))
    gov.start()
    tick = Observation(t=1.0, period=gov.plan.predicted_period)
    entries.append({
        "bench": "control", "mode": "tick", "chain": "dvbs2-mac",
        "platform": "m1_ultra", "n": ctl_chain.n, "b": ctl_b, "l": ctl_l,
        "latency_ms": _best_ms(lambda: gov.observe(tick),
                               max(repeats, 20)),
    })
    # rebuild: real threads, the mode="drain" swap (drain the pipe, join
    # every worker, re-materialize, restart) — the default live handoff's
    # synchronous cost is just the fence and is covered by the runtime
    # family's rebuild-stall A/B below (time_scale keeps the
    # sleep-simulated stage work negligible next to the swap machinery)
    rt = StreamingPipelineRuntime.from_plan(
        gov.plan, sleep_stage_builder(ctl_chain, 1e-8, {}),
        power=ctl_power)
    rt.start()
    rt.run(list(range(8)))
    entries.append({
        "bench": "control", "mode": "rebuild", "chain": "dvbs2-mac",
        "platform": "m1_ultra", "n": ctl_chain.n, "b": ctl_b, "l": ctl_l,
        "latency_ms": _best_ms(lambda: rt.rebuild(gov.plan, mode="drain"),
                               repeats),
    })
    rt.stop()

    # observability: tracer overhead on the runtime hot path. Three arms
    # on the same 4-stage threaded pipeline — no tracer, disabled
    # tracer, enabled tracer — interleaved round-robin so slow host
    # noise hits every arm alike, best-of-12 steady-state period per arm
    # (min: scheduling noise only adds, same estimator as _best_ms).
    # Stage work is a 1 ms sleep: long enough that single-core wakeup
    # jitter is small relative to the period, short enough that the
    # per-frame tracer cost (~µs) would register if it regressed.
    def _obs_runtime(tr) -> StreamingPipelineRuntime:
        stages = [StageSpec(f"s{i}", lambda x: (time.sleep(1e-3), x)[1])
                  for i in range(4)]
        rt = StreamingPipelineRuntime(stages, tracer=tr)
        rt.start()
        rt.run(list(range(10)), warmup=3)   # warm the workers
        return rt

    obs_arms = [_obs_runtime(None), _obs_runtime(Tracer(enabled=False)),
                _obs_runtime(Tracer())]
    obs_best = [math.inf] * 3
    for _ in range(12):
        for i, obs_rt in enumerate(obs_arms):
            obs_best[i] = min(
                obs_best[i],
                obs_rt.run(list(range(60)), warmup=10)["period_s"])
            if obs_rt.tracer is not None:
                obs_rt.tracer.drain()  # bound memory, off the timed path
    for obs_rt in obs_arms:
        obs_rt.stop()
    p_base, p_off, p_on = (p * 1e3 for p in obs_best)
    entries.append({
        "bench": "obs", "mode": "tracer-overhead", "chain": "synth-4stage",
        "platform": "default", "n": 4, "b": 0, "l": 0,
        "latency_ms": p_on,
        "period_base_ms": p_base,
        "period_off_ms": p_off,
        "period_on_ms": p_on,
        "overhead_off_pct": 100.0 * (p_off - p_base) / p_base,
        "overhead_on_pct": 100.0 * (p_on - p_base) / p_base,
    })

    # runtime executor A/B: true-parallel process workers vs GIL-bound
    # threads on a CPU-bound pure-Python chain (4 replicas of a pure
    # bytecode loop — threads serialize on the GIL, processes don't),
    # plus the rebuild traffic-gap A/B: the worst sink inter-arrival gap
    # while a live handoff lands mid-stream vs the stop-the-world wall
    # of a drain rebuild (which IS its traffic gap: no workers run
    # inside it). CI-gated live (``--check``): delivery is exact on both
    # backends everywhere; the >= 1.5x process-over-thread throughput
    # and the handoff-gap < 10%-of-drain bars additionally require a
    # multi-core host (``cores`` is recorded per entry — a single-core
    # runner serializes process workers too, so the ratio measures the
    # host, not the code).
    import os
    import threading as _threading

    cores = os.cpu_count() or 1
    # ~1 ms of pure bytecode per frame: long enough that per-frame ring
    # overhead (~0.1 ms parent-side) can't mask the parallelism ratio
    spin_n = 20_000 if smoke else 35_000

    def _spin(x, _n=spin_n):
        acc = 0
        for i in range(_n):
            acc += i * i
        return x

    rt_frames = 80 if smoke else 240
    arm = {}
    for executor in ("thread", "process"):
        rrt = StreamingPipelineRuntime(
            [StageSpec("spin", _spin, replicas=4)], executor=executor)
        rrt.start()
        rrt.run(list(range(12)))                      # warm the workers
        best_fps, drops = 0.0, 0
        for _ in range(max(repeats, 3)):
            r = rrt.run(list(range(rt_frames)), warmup=8, timeout_s=120.0)
            best_fps = max(best_fps, r["throughput_fps"])
            drops += r["frames_dropped"]
        rrt.stop()
        arm[executor] = (best_fps, drops)
    entries.append({
        "bench": "runtime", "mode": "executor-throughput",
        "chain": "synth-spin4", "platform": "default",
        "n": 1, "b": 4, "l": 0, "cores": cores,
        "latency_ms": 1e3 / arm["process"][0],
        "thread_fps": arm["thread"][0],
        "process_fps": arm["process"][0],
        "speedup": arm["process"][0] / arm["thread"][0],
        "frames_dropped": arm["thread"][1] + arm["process"][1],
    })

    # rebuild traffic gap, process backend: handoff lands mid-stream
    # (max sink inter-arrival gap from the tracer's frame spans), drain
    # is timed between batches (its span duration == its gap)
    from repro.core.chain import TaskChain
    from repro.core.herad import herad as _herad

    gap_chain = TaskChain([2.0], [4.0], [True])

    class _GapPlan:
        # 4 process replicas: the drain arm pays 4 joins + 4 forks, the
        # handoff arm forks its new set before the fence, off-path
        solution = _herad(gap_chain, 4, 0)
        chain = gap_chain

    def _gap_builder(s, e):
        def fn(x):
            time.sleep(0.002)
            return x
        return fn

    def _stall_arm(mode: str) -> tuple[float, int]:
        tracer = Tracer()
        rrt = StreamingPipelineRuntime.from_plan(
            _GapPlan, _gap_builder, queue_depth=4,
            executor="process", tracer=tracer).start()
        rrt.run(list(range(10)))                      # warm
        tracer.drain()
        gap_frames = 120 if smoke else 200
        dropped = 0
        if mode == "handoff":
            box = {}

            def go():
                box["res"] = rrt.run(list(range(gap_frames)),
                                     timeout_s=60.0)

            th = _threading.Thread(target=go)
            th.start()
            time.sleep(0.06)
            rrt.rebuild(_GapPlan, mode="handoff")     # mid-stream
            th.join(120.0)
            dropped = box["res"]["frames_dropped"]
            rrt.stop()
            arrivals = sorted(
                ev.ts + ev.dur for ev in tracer.drain()
                if ev.ph == "X" and ev.cat == "frame")
            gap_s = float(np.diff(np.asarray(arrivals)).max())
        else:
            dropped += rrt.run(list(range(gap_frames // 2)),
                               timeout_s=60.0)["frames_dropped"]
            rrt.rebuild(_GapPlan, mode="drain")       # stop-the-world
            dropped += rrt.run(list(range(gap_frames // 2)),
                               timeout_s=60.0)["frames_dropped"]
            rrt.stop()
            spans = [ev for ev in tracer.drain()
                     if ev.ph == "X" and ev.name == "runtime/rebuild"]
            gap_s = float(spans[-1].args["stall_s"])
        return gap_s, dropped

    # min-of-2 gap per arm (noise only widens gaps); drops accumulate
    h_runs = [_stall_arm("handoff") for _ in range(2)]
    d_runs = [_stall_arm("drain") for _ in range(2)]
    handoff_gap_s = min(g for g, _ in h_runs)
    drain_gap_s = min(g for g, _ in d_runs)
    handoff_drops = sum(d for _, d in h_runs)
    drain_drops = sum(d for _, d in d_runs)
    entries.append({
        "bench": "runtime", "mode": "rebuild-stall",
        "chain": "synth-sleep1", "platform": "default",
        "n": 1, "b": 4, "l": 0, "cores": cores,
        "latency_ms": handoff_gap_s * 1e3,
        "handoff_gap_ms": handoff_gap_s * 1e3,
        "drain_gap_ms": drain_gap_s * 1e3,
        "stall_ratio": handoff_gap_s / drain_gap_s,
        "frames_dropped": handoff_drops + drain_drops,
    })

    # serving engine: continuous (mid-run) admission vs legacy step-0
    # refill, same trace, stub model (the engine loop is the measurand).
    # Steps are deterministic per arm; wall time is best-of-repeats on a
    # reused engine so jit compilation stays off the timed path.
    import jax.numpy as jnp
    from repro.serve import Request, ServeEngine

    class _StubServeModel:
        def init_cache(self, b, max_len):
            return {"pos": jnp.zeros((b,), jnp.int32)}

        def decode_step(self, params, cache, tok):
            return tok + 1, {"pos": cache["pos"] + 1}

        def reset_cache_lane(self, cache, slot):
            return {"pos": cache["pos"].at[slot].set(0)}

    n_req, slots = (16, 4) if smoke else (48, 4)

    def _serve_arm(admit_mode):
        engine = ServeEngine(_StubServeModel(), None, batch_slots=slots,
                             max_len=512, admit_mode=admit_mode)

        def load():
            rng = np.random.default_rng(11)
            for i in range(n_req):
                engine.submit(Request(
                    rid=i, prompt=[1] * int(rng.integers(2, 5)),
                    max_new_tokens=int(rng.integers(4, 17))))
            steps = 0
            while engine.queue or any(s is not None for s in engine.slots):
                engine.step()
                steps += 1
            return steps

        steps = load()                      # warm: compiles the stub step
        best = math.inf
        for _ in range(max(repeats, 3)):
            t0 = time.perf_counter()
            assert load() == steps          # same trace -> same step count
            best = min(best, time.perf_counter() - t0)
        return steps, best

    cont_steps, cont_s = _serve_arm("continuous")
    step0_steps, step0_s = _serve_arm("step0")
    entries.append({
        "bench": "serve", "mode": "admission-overhead", "chain": "stub-serve",
        "platform": "default", "n": n_req, "b": slots, "l": 0,
        "latency_ms": cont_s / cont_steps * 1e3,
        "continuous_steps": cont_steps,
        "step0_steps": step0_steps,
        "continuous_req_per_s": n_req / cont_s,
        "step0_req_per_s": n_req / step0_s,
        "throughput_ratio": step0_s / cont_s,
    })

    # kernel-variant axis: the stacked K x P sweep of
    # sweep_budgets_variant (all variant x profile tables in ONE
    # herad_tables fill) vs V sequential per-variant frequency sweeps —
    # the same cells, certified below to produce the same points. Also
    # the ⊆-dominance invariant the 4-axis frontier promises: every
    # fixed-variant frontier point is weakly (period, energy)-dominated
    # by the variant frontier. Both are live-gated (``--check``):
    # stacked >= 1.5x the sequential fills, zero dominance violations.
    # long chain, small budget planes: the regime where the per-fill
    # python loop overhead (what the stacking amortizes) dominates the
    # per-cell numeric work, so the batching win measures cleanly
    vchain = make_chain(np.random.default_rng(13), 16 if smoke else 20,
                        0.6)
    vb, vl = (4, 4)
    vrng = np.random.default_rng(17)
    vreg = VariantRegistry()
    for vname in ("chunked", "xla"):
        for task in vchain.names:
            vreg.register(task, vname,
                          big=float(vrng.uniform(0.7, 1.4)),
                          little=float(vrng.uniform(0.7, 1.4)))
    vspec = vreg.spec_for(vchain)
    vpower = _dvfs_model(DEFAULT_POWER)

    def _sequential_fills():
        pts = []
        for vname in vspec.names:
            pts.extend(sweep_budgets_freq(vspec.scaled(vchain, vname),
                                          vb, vl, vpower))
        return pts

    stacked_pts = sweep_budgets_variant(vchain, vb, vl, vpower,
                                        variants=vspec)
    assert sorted((p.period, p.energy) for p in stacked_pts) == \
        sorted((p.period, p.energy) for p in _sequential_fills()), \
        "stacked variant sweep disagrees with per-variant sweeps"
    stacked_ms = _best_ms(
        lambda: sweep_budgets_variant(vchain, vb, vl, vpower,
                                      variants=vspec), repeats)
    seq_ms = _best_ms(_sequential_fills, repeats)
    vfront = variant_frontier(vchain, vb, vl, vpower, vspec)
    violations = 0
    # dominance invariant holds at sweep level (the stacked grid is the
    # union of the per-variant grids, and refinement only lowers the
    # variant frontier); a *refined* fixed frontier can dip below by
    # re-running its exact DP at period levels the variant sweep pruned,
    # so the fixed side is compared unrefined
    for vname in vspec.names:
        for pt in dvfs_frontier(vspec.scaled(vchain, vname), vb, vl,
                                vpower, refine=False):
            if not any(q.period <= pt.period * (1 + 1e-9)
                       and q.energy <= pt.energy * (1 + 1e-9)
                       for q in vfront):
                violations += 1
    entries.append({
        "bench": "variant", "mode": "stacked-fill",
        "chain": f"synth-n{vchain.n}", "platform": "default",
        "n": vchain.n, "b": vb, "l": vl,
        "n_variants": vspec.n_variants,
        "latency_ms": stacked_ms,
        "sequential_ms": seq_ms,
        "speedup": seq_ms / stacked_ms,
        "frontier_size": len(vfront),
        "dominance_violations": violations,
    })

    return {
        "meta": {
            "bench": "sched_perf",
            "smoke": smoke,
            "python": platform.python_version(),
            "numpy": np.__version__,
            "machine": platform.machine(),
        },
        "entries": entries,
    }


def check(result: dict) -> int:
    """Fail (non-zero) when a within-run gate fails.

    Every gate is a ratio or a count taken inside this one run, so it
    holds on any host; the ``frontier``, ``plan`` and ``control`` latencies
    are recorded, not gated.

    The ``obs`` entry: the tracer-overhead percentages are within-run
    ratios on one host — enabled tracing must inflate the steady-state
    period < 5%, a disabled tracer < 3%.

    The ``runtime`` entries: frame delivery must be exact (zero drops)
    on both backends unconditionally, while the performance bars —
    process throughput >= 1.5x thread on the CPU-bound chain,
    live-handoff traffic gap < 10% of the stop-the-world drain's — apply
    only when the recorded ``cores`` is >= 2 (a single-core host
    serializes process workers exactly like the GIL serializes threads,
    so the ratio there measures the runner, not the runtime).

    The ``serve`` entry is gated the same way (within-run, one host):
    continuous admission must not take more engine steps than the
    step-0-only refill for the same trace (mid-run refill keeps slots
    busier — a deterministic count), and its requests/s must stay >= 0.9x
    the step0 arm's (the per-step queue scan and lane resets must not eat
    the batching win).

    The ``variant`` entry: the stacked fill must reach >= 1.5x the
    sequential per-variant sweeps, with zero dominance violations.
    """
    failures = []
    for e in result["entries"]:
        if e["bench"] == "obs":
            if e["overhead_on_pct"] > 5.0:
                failures.append(
                    f"tracer overhead (enabled) {e['overhead_on_pct']:.2f}% "
                    f"exceeds the 5% budget "
                    f"({e['period_base_ms']:.3f} -> "
                    f"{e['period_on_ms']:.3f} ms/frame)")
            if e["overhead_off_pct"] > 3.0:
                failures.append(
                    f"tracer overhead (disabled) "
                    f"{e['overhead_off_pct']:.2f}% exceeds the 3% budget "
                    f"({e['period_base_ms']:.3f} -> "
                    f"{e['period_off_ms']:.3f} ms/frame)")
            continue
        if e["bench"] == "runtime":
            if e["frames_dropped"] != 0:
                failures.append(
                    f"runtime/{e['mode']}: {e['frames_dropped']} frames "
                    f"dropped — delivery must be exact on both backends")
            multicore = e.get("cores", 1) >= 2
            if e["mode"] == "executor-throughput" and multicore \
                    and e["speedup"] < 1.5:
                failures.append(
                    f"process backend throughput is only "
                    f"{e['speedup']:.2f}x the thread backend's on a "
                    f"{e['cores']}-core host (< 1.5x): shared-memory "
                    f"workers are not escaping the GIL")
            if e["mode"] == "rebuild-stall" and multicore \
                    and e["stall_ratio"] >= 0.10:
                failures.append(
                    f"live-handoff traffic gap {e['handoff_gap_ms']:.1f} ms"
                    f" is {100 * e['stall_ratio']:.0f}% of the "
                    f"stop-the-world drain ({e['drain_gap_ms']:.1f} ms); "
                    f"must stay < 10%")
            continue
        if e["bench"] == "variant":
            # within-run ratios on one host: the stacked K x P fill must
            # beat V sequential per-variant sweeps, and the 4-axis
            # frontier must ⊆-dominate every fixed-variant frontier
            if e["speedup"] < 1.5:
                failures.append(
                    f"stacked variant sweep is only {e['speedup']:.2f}x "
                    f"the {e['n_variants']} sequential fills (< 1.5x): "
                    f"the K x P batching is not paying for itself")
            if e["dominance_violations"] != 0:
                failures.append(
                    f"{e['dominance_violations']} fixed-variant frontier "
                    f"points are not dominated by the variant frontier")
            continue
        if e["bench"] == "serve":
            if e["continuous_steps"] > e["step0_steps"]:
                failures.append(
                    f"continuous admission took {e['continuous_steps']} "
                    f"engine steps vs step0's {e['step0_steps']} — mid-run "
                    f"refill must not add steps")
            ratio = e["continuous_req_per_s"] / e["step0_req_per_s"]
            if ratio < 0.9:
                failures.append(
                    f"continuous admission requests/s is {ratio:.2f}x the "
                    f"step0 arm (< 0.9x): admission overhead ate the "
                    f"batching win")
    for f in failures:
        print("REGRESSION:", f)
    if not failures:
        print("within-run gates: all pass")
    return 1 if failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grid for CI")
    ap.add_argument("--out", type=Path, default=None,
                    help="write the entries as JSON to this path")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero when a within-run gate fails")
    args = ap.parse_args(argv)

    result = run(smoke=args.smoke)
    for e in result["entries"]:
        extra = f" speedup={e['speedup']:.1f}x" if "speedup" in e else ""
        if "overhead_on_pct" in e:
            extra = (f" on={e['overhead_on_pct']:+.2f}% "
                     f"off={e['overhead_off_pct']:+.2f}%")
        if "throughput_ratio" in e:
            extra = (f" steps={e['continuous_steps']}/{e['step0_steps']} "
                     f"req/s ratio={e['continuous_req_per_s'] / e['step0_req_per_s']:.2f}x")
        if "process_fps" in e:
            extra = (f" thread={e['thread_fps']:.0f} "
                     f"process={e['process_fps']:.0f} fps "
                     f"x{e['speedup']:.2f} (cores={e['cores']})")
        if "stall_ratio" in e:
            extra = (f" handoff={e['handoff_gap_ms']:.1f} ms "
                     f"drain={e['drain_gap_ms']:.1f} ms "
                     f"ratio={e['stall_ratio']:.3f}")
        print(f"{e['bench']:9s} {e['mode']:12s} {e['chain']:12s} "
              f"n={e['n']:3d} b={e['b']:2d} l={e['l']:2d} "
              f"{e['latency_ms']:9.3f} ms{extra}")

    rc = check(result) if args.check else 0
    if args.out is not None:
        args.out.write_text(json.dumps(result, indent=2) + "\n")
        print(f"wrote {args.out}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
