"""Observability layer: tracer rings, metrics registry, Perfetto export,
and the trace -> report round trip against runtime/scenario ground truth."""
import json
import math
import threading
import time

import jax.numpy as jnp
import pytest

from repro.control import (
    ConstantBudget,
    Governor,
    ScriptedBudget,
    bursty_arrivals,
    run_scenario,
    run_serve_scenario,
)
from repro.energy import CoreTypePower, PowerModel, pareto_frontier
from repro.obs import (
    MetricsRegistry,
    NULL_TRACER,
    Tracer,
    analyze_trace,
    load_trace,
    to_chrome_events,
    write_perfetto,
)
from repro.core import TaskChain
from repro.pipeline import StageSpec, StreamingPipelineRuntime


# ================================================================== tracer
def test_tracer_records_and_drains_in_order():
    tr = Tracer()
    t = tr.now()
    tr.complete("b", t + 1.0, 0.5, cat="frame", args={"seq": 1})
    tr.complete("a", t, 0.5)
    tr.instant("mark", cat="governor", ts=t + 2.0)
    tr.counter("cap_w", 12.5, ts=t + 3.0)
    events = tr.drain()
    assert [e.name for e in events] == ["a", "b", "mark", "cap_w"]
    assert events[1].args == {"seq": 1}
    assert events[0].ph == "X" and events[2].ph == "i" \
        and events[3].ph == "C"
    # drain cleared everything
    assert tr.drain() == []


def test_tracer_ring_bounded_drops_oldest():
    tr = Tracer(ring_size=4)
    t = tr.now()
    for i in range(10):
        tr.complete(f"s{i}", t + i, 0.1)
    assert tr.dropped_records == 6
    events = tr.drain()
    assert [e.name for e in events] == ["s6", "s7", "s8", "s9"]
    with pytest.raises(ValueError):
        Tracer(ring_size=0)


def test_disabled_tracer_records_nothing():
    for tr in (Tracer(enabled=False), NULL_TRACER):
        tr.complete("x", 0.0, 1.0)
        tr.instant("y")
        tr.counter("z", 1.0)
        tr.set_thread_name("w")
        assert tr.drain() == []
        assert tr.dropped_records == 0


def test_tracer_span_context_manager_times_block():
    tr = Tracer()
    with tr.span("work", cat="test", args={"k": 1}):
        time.sleep(0.002)
    (ev,) = tr.drain()
    assert ev.ph == "X" and ev.name == "work" and ev.cat == "test"
    assert ev.dur >= 0.002
    assert ev.args == {"k": 1}


def test_tracer_per_thread_rings_and_thread_names():
    tr = Tracer()
    barrier = threading.Barrier(3)  # overlap lifetimes: distinct idents

    def worker(name):
        tr.set_thread_name(name)
        tr.complete(name, tr.now(), 0.001)
        barrier.wait(timeout=5)

    threads = [threading.Thread(target=worker, args=(f"w{i}",))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = tr.drain()
    metas = {e.name: e.tid for e in events if e.ph == "M"}
    spans = {e.name: e.tid for e in events if e.ph == "X"}
    assert set(metas) == set(spans) == {"w0", "w1", "w2"}
    # each worker's span landed on its own named row
    assert all(metas[n] == spans[n] for n in metas)
    assert len(set(spans.values())) == 3


# ================================================================= metrics
def test_metrics_counters_gauges_snapshot():
    m = MetricsRegistry()
    m.inc("frames")
    m.inc("frames", 4)
    m.set_gauge("cap_w", 20.5)
    assert m.counter("frames") == 5
    assert m.counter("missing") == 0.0
    assert m.gauge("cap_w") == 20.5
    assert m.gauge("missing") is None
    snap = m.snapshot()
    assert snap["counters"] == {"frames": 5}
    assert snap["gauges"] == {"cap_w": 20.5}
    assert snap["histograms"] == {}


def test_metrics_histogram_percentiles():
    m = MetricsRegistry()
    for v in range(1, 101):
        m.observe("lat", float(v))
    h = m.snapshot()["histograms"]["lat"]
    assert h["count"] == 100
    assert h["mean"] == pytest.approx(50.5)
    assert (h["min"], h["max"]) == (1.0, 100.0)
    assert (h["p50"], h["p95"], h["p99"]) == (50.0, 95.0, 99.0)


def test_metrics_window_summary_resets():
    m = MetricsRegistry()
    for v in (1.0, 2.0, 3.0):
        m.observe("lat", v)
    w1 = m.window_summary(reset=True)["lat"]
    assert w1["count"] == 3 and w1["p50"] == 2.0
    w2 = m.window_summary()["lat"]
    assert w2["count"] == 0 and math.isnan(w2["p50"])
    m.observe("lat", 9.0)
    w3 = m.window_summary(reset=False)["lat"]
    assert w3["count"] == 1 and w3["p50"] == 9.0
    # cumulative stats survive window resets
    assert m.snapshot()["histograms"]["lat"]["count"] == 4


def test_metrics_histogram_reservoir_bounded():
    m = MetricsRegistry()
    n = 30_000
    for v in range(n):
        m.observe("lat", float(v))
    hist = m._hists["lat"]
    assert len(hist.samples) < 8192
    h = hist.summary()
    assert h["count"] == n
    assert (h["min"], h["max"]) == (0.0, float(n - 1))
    # thinned reservoir still spans the history
    assert h["p50"] == pytest.approx(n / 2, rel=0.05)


# ================================================================== export
def test_chrome_export_format():
    tr = Tracer()
    tr.set_thread_name("stage/r0")
    t = tr.now()
    tr.complete("frame0", t, 0.25, cat="frame", args={"seq": 0})
    tr.instant("governor/cap", cat="governor", args={"trigger": "cap"},
               ts=t + 1.0)
    tr.counter("cap_w", 18.0, ts=t + 1.0)
    tr.counter("multi", {"a": 1.0, "b": 2.0}, ts=t + 2.0)
    recs = to_chrome_events(tr.drain())
    by_ph = {}
    for r in recs:
        by_ph.setdefault(r["ph"], []).append(r)
    meta = by_ph["M"][0]
    assert meta["name"] == "thread_name"
    assert meta["args"] == {"name": "stage/r0"}
    span = by_ph["X"][0]
    assert span["cat"] == "frame" and span["dur"] == pytest.approx(0.25e6)
    assert span["args"] == {"seq": 0}
    inst = by_ph["i"][0]
    assert inst["s"] == "p" and inst["args"]["trigger"] == "cap"
    counters = {c["name"]: c for c in by_ph["C"]}
    assert counters["cap_w"]["args"] == {"value": 18.0}
    assert counters["multi"]["args"] == {"a": 1.0, "b": 2.0}
    # timestamps normalized to the earliest event, in µs
    assert min(r.get("ts", 0.0) for r in recs) == 0.0
    assert inst["ts"] - span["ts"] == pytest.approx(1e6, rel=1e-6)


def test_export_round_trip_mapping_counters_and_drop_metadata(tmp_path):
    """Regression guard for export fidelity: multi-series counter samples
    (numpy scalars included) and the tracer's ring-overflow count must
    survive write_perfetto -> load_trace -> analyze_trace unchanged —
    ring overflow would otherwise silently vanish between the tracer and
    the report."""
    import numpy as np

    tr = Tracer(ring_size=8)
    t = tr.now()
    for i in range(12):  # overflow the 8-slot ring
        tr.complete(f"f{i}", t + i * 0.1, 0.05, cat="frame")
    tr.counter("power_corrections", {"B": np.float64(1.5), "L": 1.0},
               ts=t + 2.0)
    tr.counter("power_corrections", {"B": 1.25, "L": 1.0}, ts=t + 3.0)
    tr.counter("cap_w", np.float32(18.0), ts=t + 2.0)
    events = tr.drain()
    assert tr.dropped_records > 0

    path = write_perfetto(events, tmp_path / "t.json",
                          dropped_records=tr.dropped_records,
                          metadata={"run": "unit"})
    loaded = load_trace(path)
    # mapping counters keep one arg per sub-series key, numpy coerced
    rows = [e for e in loaded if e.get("ph") == "C"
            and e["name"] == "power_corrections"]
    assert [r["args"] for r in rows] == [{"B": 1.5, "L": 1.0},
                                         {"B": 1.25, "L": 1.0}]
    (cap_row,) = [e for e in loaded if e.get("ph") == "C"
                  and e["name"] == "cap_w"]
    assert cap_row["args"] == {"value": 18.0}
    # the overflow count and extra metadata ride a metadata record...
    (meta,) = [e for e in loaded if e.get("ph") == "M"
               and e.get("name") == "trace_metadata"]
    assert meta["args"] == {"run": "unit",
                            "dropped_records": tr.dropped_records}
    # ...and land back on the report
    report = analyze_trace(loaded)
    assert report.dropped_records == tr.dropped_records


def test_write_and_load_round_trip(tmp_path):
    tr = Tracer()
    tr.complete("x", tr.now(), 0.001, cat="frame")
    path = write_perfetto(tr.drain(), tmp_path / "trace.json")
    data = json.loads(path.read_text())
    assert data["displayTimeUnit"] == "ms"
    events = load_trace(path)
    assert len(events) == 1 and events[0]["name"] == "x"
    # bare-array variant loads too
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(events))
    assert load_trace(bare) == events


# ====================================================== runtime round trip
def test_runtime_trace_matches_run_stats(tmp_path):
    """Perfetto round trip against ground truth: per-stage busy time and
    queue waits reconstructed from the exported trace must match what
    run() measured (same timestamps feed both paths)."""
    tracer = Tracer()
    stages = [
        StageSpec("fast", lambda x: x),
        StageSpec("slow", lambda x: (time.sleep(0.002), x)[1], replicas=2),
    ]
    rt = StreamingPipelineRuntime(stages, tracer=tracer).start()
    stats = rt.run(list(range(40)))
    rt.stop()

    path = write_perfetto(tracer.drain(), tmp_path / "rt.json")
    report = analyze_trace(load_trace(path))

    by_name = {s.name: s for s in report.stages}
    assert set(by_name) == {"fast", "slow"}
    assert by_name["fast"].frames == by_name["slow"].frames == 40
    assert by_name["slow"].replicas == 2
    for name in ("fast", "slow"):
        busy_stats = sum(v for (s, _), v in stats["busy_s"].items()
                         if s == name)
        assert by_name[name].busy_s == pytest.approx(busy_stats, rel=1e-3)
        wait_stats = sum(v for (s, _), v in stats["queue_wait_s"].items()
                         if s == name)
        assert by_name[name].mean_queue_wait_s * by_name[name].frames \
            == pytest.approx(wait_stats, rel=1e-3)
    # the sleeping stage dominates its rows; the pass-through one idles
    assert by_name["slow"].utilization > 5 * by_name["fast"].utilization
    assert report.rebuild_count == 0 and report.over_cap_windows == 0
    assert tracer.dropped_records == 0


# ===================================================== governed round trip
def test_governed_scenario_trace_round_trip(tmp_path):
    """The acceptance scenario shape: a reactive governor hit by a
    mid-window cap drop (window 1 straddles it -> over-cap) and a device
    loss. The exported trace must carry per-replica frame spans, trigger-
    labelled decision instants, cap/power counter tracks, and rebuild
    drain gaps — and trace_report's numbers must agree with the
    ScenarioResult the run itself measured."""
    chain = TaskChain(
        w_big=[10.0, 40.0, 40.0, 10.0],
        w_little=[25.0, 100.0, 100.0, 25.0],
        replicable=[False, True, True, False],
    )
    power = PowerModel("t", CoreTypePower(0.1, 0.9),
                       CoreTypePower(0.03, 0.32))
    front = pareto_frontier(chain, 3, 2, power)
    watts = [pt.energy / pt.period for pt in front]
    # drop lands mid-window at t=1.5: the reactive governor only adopts
    # at the next tick, so window 1's plan is over the new floor
    budget = ScriptedBudget(((0.0, watts[0] + 1.0), (1.5, watts[-1] * 1.001)))
    gov = Governor(chain, 3, 2, power, budget)
    tracer = Tracer()
    metrics = MetricsRegistry()
    res = run_scenario(gov, time_scale=2e-6, n_windows=5, window_dt=1.0,
                       frames_per_window=20,
                       device_loss_at={3: (0, 1)},
                       tracer=tracer, metrics=metrics)
    assert len(res.over_cap_windows) >= 1
    assert len(res.replans) >= 2     # the cap drop + the device loss

    path = write_perfetto(tracer.drain(), tmp_path / "gov.json")
    report = analyze_trace(load_trace(path))

    # over-cap windows: same definition, same count
    assert report.over_cap_windows == len(res.over_cap_windows)
    assert report.over_cap_s > 0
    # one rebuild drain gap per adopted re-plan, with real stall time
    assert report.rebuild_count == len(res.replans)
    assert report.rebuild_stall_s > 0
    # decision instants carry trigger labels; the governor's own event
    # log is reproduced verbatim (plus the "start" adoption)
    triggers = [d["trigger"] for d in report.decisions]
    assert triggers[0] == "start"
    assert triggers[1:] == [e.trigger for e in res.replans]
    assert "cap" in triggers and "device_loss" in triggers
    assert all("cap_w" in d for d in report.decisions)
    # frame spans landed on per-replica rows for every active plan's
    # stages (each fed frame crosses every stage of its plan)
    assert report.stages and all(s.frames > 0 for s in report.stages)
    assert sum(s.frames for s in report.stages) >= res.frames_fed
    # the cap/power counter tracks made it into the trace
    counters = {e["name"] for e in load_trace(path) if e.get("ph") == "C"}
    assert {"cap_w", "power_w"} <= counters

    # metrics registry agrees with the scenario result
    assert metrics.counter("scenario/frames_fed") == res.frames_fed
    assert metrics.counter("scenario/frames_dropped") == res.frames_dropped
    assert metrics.counter("scenario/replans") == len(res.replans)
    hist = metrics.snapshot()["histograms"]["scenario/period_us"]
    assert hist["count"] == len(res.windows)


# ====================================================== serving round trip
class _StubModel:
    """Duck-typed decode model: the serving obs round trip is about the
    metric/trace plumbing, not the network."""

    def init_cache(self, b, max_len):
        return {"pos": jnp.zeros((b,), jnp.int32)}

    def decode_step(self, params, cache, tok):
        return tok + 1, {"pos": cache["pos"] + 1}

    def reset_cache_lane(self, cache, slot):
        return {"pos": cache["pos"].at[slot].set(0)}


def test_serve_deadline_miss_counter():
    """A request that finishes past its deadline must be flagged on the
    request, counted in ``serve/deadline_miss``, and marked in the
    trace — the reconciliation anchor for the zero-miss claims (which
    assert this very counter stays 0)."""
    from repro.serve import Request, ServeEngine, SimClock

    tracer, metrics = Tracer(), MetricsRegistry()
    # no planner: the only miss path left is a pace collapse after
    # admission (the engine rejects guaranteed misses up front)
    engine = ServeEngine(_StubModel(), None, batch_slots=2, max_len=16,
                         clock=SimClock(), step_time_s=1.0,
                         tracer=tracer, metrics=metrics)
    late = Request(rid=0, prompt=[1], max_new_tokens=4, deadline_s=10.0)
    ok = Request(rid=1, prompt=[1], max_new_tokens=4, deadline_s=1000.0)
    engine.submit(late)
    engine.submit(ok)
    engine.step()                 # both admitted at the healthy pace...
    engine.step_time_s = 5.0      # ...then every step runs 5x slower
    engine.run_until_idle()
    assert late.done and late.missed and not ok.missed
    assert metrics.counter("serve/deadline_miss") == 1
    assert metrics.counter("serve/requests_done") == 2
    assert any(e.name == "serve/deadline_miss" for e in tracer.drain())


def test_served_scenario_metrics_and_trace_round_trip(tmp_path):
    """The SLO-governed serving scenario, end to end on the stub model:
    the metrics registry's serving counters must reconcile with the
    ServeScenarioResult, each window's recorded p99 must equal the
    previous window's paced step time (the registry's window summary is
    the governor's own input), and the exported trace must carry engine
    step spans, serving windows, and the "slo" decision instant."""
    from repro.core import make_chain
    from repro.serve import AdmissionPlanner, ServeEngine, SimClock
    import numpy as np

    chain = make_chain(np.random.default_rng(5), 4, 0.5)
    power = PowerModel("t", CoreTypePower(0.1, 0.9),
                       CoreTypePower(0.03, 0.32))
    front = pareto_frontier(chain, 3, 2, power)
    if len(front) < 3:
        pytest.skip("degenerate frontier")
    watts = [pt.energy / pt.period for pt in front]
    slo_period = front[len(front) // 3].period * 1.05
    ts = 1e-4
    gov = Governor(chain, 3, 2, power, ConstantBudget(watts[0] * 1.05),
                   slo_period=slo_period, upshift_margin=0.02)
    planner = AdmissionPlanner(frontier=gov.frontier(), time_scale=ts,
                               cap_w=watts[0] * 1.05, safety=1.5)
    tracer, metrics = Tracer(), MetricsRegistry()
    engine = ServeEngine(_StubModel(), None, batch_slots=4, max_len=32,
                         clock=SimClock(), planner=planner, pace="fixed",
                         tracer=tracer, metrics=metrics)
    arrivals = bursty_arrivals(8, window_dt=0.2, base_rate=1,
                               burst_rate=3, burst_windows=(2, 3),
                               latency_slo_s=0.5, max_new_tokens=6)
    res = run_serve_scenario(gov, engine, arrivals, time_scale=ts,
                             n_windows=8, window_dt=0.2,
                             inflation_at=((5, 1.2),),
                             tracer=tracer, metrics=metrics)

    # counters reconcile with the scenario result (and zero misses hold)
    assert res.deadline_misses == 0
    assert metrics.counter("serve/deadline_miss") == res.deadline_misses
    assert metrics.counter("serve/requests_done") == res.completed
    assert metrics.counter("serve/rejected") == res.rejected
    assert metrics.counter("serve/tokens") == res.tokens
    assert res.completed + res.rejected == len(res.requests)
    assert sum(w.completed for w in res.windows) <= res.completed
    # the backlog is a tracer counter track only (asserted below)
    assert metrics.gauge("serve/queue_depth") is None

    # each window's p99 is the previous window's paced step time — the
    # deterministic sim makes the histogram round trip exact
    for prev, cur in zip(res.windows, res.windows[1:]):
        if prev.steps:
            assert cur.p99_s == pytest.approx(prev.step_s)
    # the cumulative step histogram saw at least every in-window step
    hist = metrics.snapshot()["histograms"]["serve/step_s"]
    assert hist["count"] >= sum(w.steps for w in res.windows) > 0

    # the governed run actually exercised the serving objective
    assert any(e.trigger == "slo" for e in res.replans)

    # trace round trip: step spans, serving windows, decision instants
    path = write_perfetto(tracer.drain(), tmp_path / "serve.json")
    events = load_trace(path)
    steps = [e for e in events
             if e.get("ph") == "X" and e["name"] == "serve/step"]
    assert len(steps) == hist["count"]
    wins = [e for e in events
            if e.get("ph") == "X" and e["name"] == "serve/window"]
    assert len(wins) == len(res.windows)
    assert sum(w["args"]["steps"] for w in wins) \
        == sum(w.steps for w in res.windows)
    instants = [e for e in events if e.get("ph") == "i"
                and e["name"] == "governor/slo"]
    assert instants and all(d["args"]["trigger"] == "slo"
                            for d in instants)
    counters = {e["name"] for e in events if e.get("ph") == "C"}
    assert {"serve/active_slots", "serve/queue_depth"} <= counters
