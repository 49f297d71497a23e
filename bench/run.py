"""Run one benchmark cell once on the chip and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic are found by name through
``BENCHMARK.json`` (see ``harness/spec.py``). The run makes the weights on
the device from the seed, builds the registry's model and a
``ServeEngine`` at the configuration's slots x positions, warms up the
engine's two programs (decode step and lane reset), then serves the
seeded schedule on the wall clock: a lead, which is not measured, and a
window of ``--seconds``. With ``--trace 1`` the first seconds of the
window are traced by the profiler and the run reports the cell's
per-layer metrics; with ``--trace 0`` its end-to-end metrics.

After the window the program's state is freed and a sample of the
finished requests is compared with the plain float32 reference
(``harness/check.py``). The numbers compared and their limits are the
last lines on standard error and the ``checks`` entry of the result.
The last line on standard output is the result's JSON object.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench.harness import check, serve, spec, trace, traffic  # noqa: E402

MARKS = {"imported": time.perf_counter()}   # host clock of set-up's parts

TRACE_S = 5.0   # traced part of the window, at its start
DRAIN_S = 60.0  # most time after an open-loop window to wait for the
                # first tokens of the requests due in it


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache() -> None:
    """The program's persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` in the checkout), holding every
    program, however quick its compile."""
    from repro.launch.compile_cache import enable_compile_cache as enable
    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def weight_key(seed: int):
    return jax.random.PRNGKey(int(traffic.rng_for(seed).integers(2**31)))


def make_params(cell: spec.Cell, seed: int, model):
    """The weights from the seed, made on the device in one program, and
    held to the program's own tree of shapes and dtypes."""
    make = jax.jit(functools.partial(spec.arch(cell.kind).make_params,
                                     cell.config["model"]))
    params = jax.block_until_ready(make(weight_key(seed)))
    want = jax.tree.map(lambda a: (a.shape, a.dtype), model.abstract_params())
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != got:
        raise ValueError(f"bench/arch/{cell.kind}.py makes a weight tree "
                         f"that is not the program's: {got} != {want}")
    return params


class CompileCounter:
    """Counts JAX compile events while ``on``; before that, sums the
    seconds of each of JAX's timed events (tracing, lowering, compiling,
    reading the compile cache) that set-up spends."""

    def __init__(self):
        self.on = False
        self.n = 0
        self.setup = {}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, *_args, **_kw):
        if self.on:
            self.n += "compile" in name
        else:
            n, t = self.setup.get(name, (0, 0.0))
            self.setup[name] = (n + 1, t + secs)


def pct(vals, q: float) -> float:
    return float(np.percentile(np.asarray(vals, float), q))


def end_to_end(cell, log, w0, w1, t_end, setup_s
               ) -> tuple[dict, dict, list]:
    """The cell's end-to-end metrics, attempted/failed, and lines that
    state each sample count. ``t_end``: when serving stopped, at or after
    the window's end ``w1``."""
    want = {m["name"]: m["unit"] for m in cell.end_to_end}
    vals = {"setup_s": setup_s}
    lines = []
    due = log.in_window()
    late = 0
    ttft = []
    for r in due:
        t_due = log.t0 + r.arrival.due_s
        if r.token_t:
            ttft.append(r.token_t[0] - t_due)
        else:
            ttft.append(t_end - t_due)
            late += 1
    if ttft:
        vals["ttft_p50_ms"] = pct(ttft, 50) * 1e3
        vals["ttft_p90_ms"] = pct(ttft, 90) * 1e3
    itl = [b - a for r in log.records for a, b in
           zip(r.token_t, r.token_t[1:]) if a >= w0 and b <= w1]
    if itl:
        vals["itl_p99_ms"] = pct(itl, 99) * 1e3
    steps = [s for s in log.steps if s.end <= w1]
    e0 = max((s.end for s in steps if s.end <= w0), default=w0)
    inside = [s for s in steps if s.end > e0]
    if inside:
        vals["output_tokens_per_s"] = sum(s.emitted for s in inside) / (
            inside[-1].end - e0)
    lines.append(f"samples: ttft over {len(ttft)} requests due in the "
                 f"window ({late} still without a first token "
                 f"{t_end - w1:.3f} s after it, counted at that time - due); "
                 f"itl over {len(itl)} gaps; "
                 f"{sum(s.emitted for s in inside)} output tokens in "
                 f"{len(inside)} steps over "
                 f"{(inside[-1].end - e0) if inside else 0.0:.6f} s")
    worked = {r.req.rid for r in log.records
              if any(w0 < t <= w1 for t in r.token_t)}
    attempted = len(due) if cell.traffic["arrival"] == "poisson" \
        else len(worked)
    failed = sum(r.req.rejected for r in log.records)
    metrics = {k: {"value": vals[k], "unit": u} for k, u in want.items()
               if k in vals}
    return metrics, {"attempted": attempted, "failed": failed}, lines


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        t_start: float, checker=None) -> dict:
    """One run of ``cell`` on ``jax.devices()[:cell.chips]``; returns the
    result object (the chip check is the caller's). ``checker`` takes the
    place of :func:`correctness`, with the same arguments."""
    from repro.models.transformer import Model
    from repro.obs import MetricsRegistry
    from repro.serve import Request, ServeEngine

    model_dict = cell.config["model"]
    cfg = spec.model_config(cell.config)
    slots = cell.config["serve"]["slots"]
    max_len = cell.config["serve"]["max_len"]
    arch = spec.arch(cell.kind)
    dev = jax.devices()[0]
    counter = CompileCounter()

    t_run = time.perf_counter()
    model = Model(cfg)
    params = make_params(cell, seed, model)
    t_weights = time.perf_counter()
    metrics = MetricsRegistry()
    engine = ServeEngine(model, params, batch_slots=slots, max_len=max_len,
                         metrics=metrics)
    # the engine's two programs at this cell's shapes, through its own path
    engine.submit(Request(rid=-1, prompt=[0], max_new_tokens=1))
    engine.step()
    jax.block_until_ready(engine.cache)
    t_warm = time.perf_counter()
    metrics.window_summary(reset=True)
    arrivals = traffic.generate(cell.traffic, seed, seconds, vocab=cfg.vocab,
                                max_len=max_len, slots=slots)
    log = serve.new_log(arrivals, lambda i, a: Request(
        rid=i, prompt=a.prompt, max_new_tokens=a.max_new_tokens))
    # what set-up left (JAX, the model, the schedule) is kept out of the
    # collector's full passes, which otherwise stall a step for tens of ms
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    counter.on = True
    log.t0 = time.perf_counter()
    w0 = log.t0 + float(cell.traffic.get("lead_s", 0.0))
    w1 = w0 + seconds
    serve.drive(engine, log, w0)
    traced_window = None
    if traced:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        n0 = len(log.steps)
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            metrics.window_summary(reset=True)
            serve.drive(engine, log, min(w1, w0 + TRACE_S))
            step_hist = metrics.window_summary(reset=True).get("serve/step_s")
        jax.profiler.stop_trace()
        tsteps = log.steps[n0:]
    serve.drive(engine, log, w1)
    if cell.traffic["arrival"] == "poisson":
        due = log.in_window()
        serve.drive(engine, log, w1 + DRAIN_S,
                    stop=lambda: all(r.token_t for r in due))
    t_end = max(w1, log.steps[-1].end if log.steps else w1)
    counter.on = False
    gc.unfreeze()
    if traced:
        weight_bytes = sum(a.size * a.dtype.itemsize
                           for a in jax.tree.leaves(params))
        traced_window = trace.TracedWindow(
            events=trace.load_events(tdir), steps=tsteps,
            engine_step_s=step_hist["mean"] if step_hist else None,
            step_cost=lambda st: arch.step_cost(
                model_dict, weight_bytes, st.active, st.ctx_sum),
            peaks=spec.peaks(dev.device_kind) if dev.platform == "tpu"
            else {})
        shutil.rmtree(tdir, ignore_errors=True)
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    e2e, counts, lines = end_to_end(cell, log, w0, w1, t_end, setup_s)
    steps_in = [s for s in log.steps if w0 < s.end <= w1]
    step_ms = sorted((s.end - s.start) * 1e3 for s in steps_in) or [0.0]
    lines.append(f"generator: worst lateness "
                 f"{log.max_lateness * 1e3:.3f} ms over "
                 f"{log.next_arrival} submissions; queued at window open "
                 f"{log.queued_at(w0)}, at its end {log.queued_at(w1)}; "
                 f"engine steps in window {len(steps_in)}; compiles in "
                 f"window {counter.n}; setup {setup_s:.3f} s")
    mid = step_ms[len(step_ms) // 2]
    lines.append(f"engine steps in window: median {mid:.3f} ms, max "
                 f"{step_ms[-1]:.3f} ms, "
                 f"{sum(step_ms) / 1e3:.3f} s of {seconds:.3f} s in steps")
    t_imp = MARKS.get("imported", t_start)
    t_dev = MARKS.get("devices", t_imp)
    lines.append(f"setup: {t_imp - t_start:.3f} s imports, "
                 f"{t_dev - t_imp:.3f} s to the device, "
                 f"{t_run - t_dev:.3f} s program imports, "
                 f"{t_weights - t_run:.3f} s weights, {t_warm - t_weights:.3f}"
                 f" s engine warm-up, {setup_s - (t_warm - t_start):.3f} s "
                 f"schedule; JAX events " + ", ".join(
                     f"{k.rsplit('/', 1)[-1]} {n} {t:.3f} s"
                     for k, (n, t) in sorted(counter.setup.items())))
    result = {"correct": False, **counts}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    if traced:
        result["metrics"], extra = per_layer(cell, traced_window, lines)
        device.update(extra)
        bd = traced_window.breakdown()
        if bd is not None:
            result["breakdown"] = bd
    else:
        result["metrics"] = e2e
    result["device"] = device

    # the program's state goes before the reference runs
    del engine, params
    t_check = time.perf_counter()
    checks = (checker or correctness)(cell, log, seed, model, arch)
    lines.append(f"reference check: {time.perf_counter() - t_check:.3f} s")
    result["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    result["checks"] = checks
    for line in lines:
        print(line, file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return result


def per_layer(cell, tw, lines) -> tuple[dict, dict]:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(tw)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    least = tw.least_step_s()
    mods = tw.module_s()
    lines.append(f"traced window: {tw.window_s()} s, {len(tw.steps)} engine "
                 f"steps, {len(mods)} decode_step modules on the device; "
                 f"least step {least[0] if least else None} s bound by "
                 f"{least[1] if least else None}")
    extra = {}
    if tw.busy_s() is not None:
        extra = {"busy_s": tw.busy_s(), "window_s": tw.window_s()}
    return out, extra


def correctness(cell, log, seed, model, arch) -> dict:
    cfg = model.cfg
    limits = cell.traffic["limits"]
    picked = check.sample(log, seed)
    params = make_params(cell, seed, model)
    gaps = check.logit_gaps(arch, cell.config["model"], params, picked,
                            cell.config["serve"]["max_len"])
    exact = check.exact_checks(log, cfg.vocab)
    return {
        "logit_gap": {"value": gaps["served"],
                      "limit": limits["logit_gap"]},
        "no_sample": {"value": int(not picked), "limit": 0},
        "wrong_length": {"value": exact["wrong_length"], "limit": 0},
        "out_of_vocab": {"value": exact["out_of_vocab"], "limit": 0},
    }


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    enable_compile_cache()
    devices = jax.devices()
    MARKS["devices"] = time.perf_counter()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); found "
              f"{len(devices)} {devices[0].platform} device(s) "
              f"({devices[0].device_kind})", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
