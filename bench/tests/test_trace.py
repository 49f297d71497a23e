"""The reduction from trace events to busy time, idle share, module time
and labelled gaps."""
import json
from pathlib import Path

import pytest

from bench.harness import trace

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.json"


def test_union_busy_and_gaps():
    ivs = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (29, 31, "d"),
           (40, 50, "e")]
    assert trace.union(ivs) == [(0, 15), (20, 31), (40, 50)]
    assert trace.busy_ns(ivs, 0, 50) == 15 + 11 + 10
    assert trace.busy_ns(ivs, 8, 45) == 7 + 11 + 5
    assert trace.gaps(ivs, 8, 60) == [(15, 20), (31, 40), (50, 60)]


def test_top_ops_and_modules():
    ivs = [(0, 10, "fusion"), (10, 30, "copy"), (30, 35, "fusion")]
    assert trace.top_ops(ivs, 0, 100, k=1) == [["copy", 20e-9]]
    assert trace.top_ops(ivs, 5, 100) == [["copy", 20e-9],
                                          ["fusion", 10e-9]]
    mods = [(0, 40, "jit_decode_step"), (41, 45, "jit_reset_cache_lane"),
            (50, 90, "jit_decode_step"), (95, 200, "jit_decode_step")]
    assert trace.module_durations(mods, 0, 100, "decode_step") == \
        [40e-9, 40e-9]


def test_gaps_named_by_host_span():
    ops = [(0, 10, "x"), (30, 40, "y"), (41, 50, "z")]
    host = [(5, 25, "engine.step"), (26, 29, "load_generator"),
            (40, 41, "engine.step"), (0, 60, "bench.window")]
    out = trace.labelled_gaps(ops, host, 0, 60, trace.HOST_LABELS)
    assert out == [["engine.step", 20e-9], ["other", 10e-9],
                   ["engine.step", 1e-9]]


def _window(events):
    return trace.TracedWindow(events=events, steps=[], engine_step_s=None,
                              step_cost=None, peaks={})


def test_self_time_and_short_names():
    ivs = [(0, 100, "%while.1 = (s32[]) while(...)"),
           (10, 30, "%fusion.2 = bf16[8]{0:T(128)} fusion(%x)"),
           (40, 50, "%copy.3 = bf16[2,3]{1,0} copy(%y)"),
           (120, 130, "%fusion.2 = bf16[8]{0:T(128)} fusion(%x)")]
    top = trace.top_ops(ivs, 0, 200)
    assert [n for n, _ in top] == ["%while.1 (s32[])", "%fusion.2 bf16[8]",
                                   "%copy.3 bf16[2,3]"]
    assert [t for _, t in top] == pytest.approx([70e-9, 30e-9, 10e-9])


def test_recorded_trace():
    """One engine step of stablelm-3b.chat traced on a v5e."""
    rec = json.loads(FIXTURE.read_text())
    host = [tuple(h) for h in rec["host"]]
    ev = trace.Events(ops={k: [tuple(e) for e in v]
                           for k, v in rec["ops"].items()},
                      modules={k: [tuple(e) for e in v]
                               for k, v in rec["modules"].items()},
                      host=host)
    tw = _window(ev)
    lo, hi = tw.bounds
    assert hi > lo
    busy = tw.busy_s()
    assert 0 < busy <= tw.window_s()
    for k, v in rec["expect"].items():
        assert getattr(tw, k)() == pytest.approx(v, rel=1e-9)
    # the step module whole in the slice: 48.2 ms, as the chip measured
    assert max(tw.module_s()) == pytest.approx(0.048213041, rel=1e-9)
    bd = tw.breakdown()
    assert len(bd["device_ops"]) == 10
    assert bd["device_ops"][0][0].startswith("%copy")
    ops = next(iter(ev.ops.values()))
    assert sum(t for _, t in trace.self_times(trace.clip(ops, lo, hi))) \
        == pytest.approx(busy * 1e9, rel=1e-6)
    assert bd["idle_gaps"][0][0] == "engine.step"
