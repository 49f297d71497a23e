"""Engine spans and model task scopes as a profiler sees them, and the two
benchmark readers that split each step's device idle by them.

A tiny ``ServeEngine`` runs under ``jax.profiler.trace`` on the CPU; the
trace is read back with the benchmark's own ``load_events``. The readers
are checked on synthetic events with known answers."""
import glob
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.models.config import get_smoke_config
from repro.models.transformer import Model
from repro.serve import Request, ServeEngine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import spec, trace  # noqa: E402

PHASES = ["serve/admit", "serve/prepare", "serve/dispatch", "serve/sync",
          "serve/emit"]


@pytest.fixture(scope="module")
def dense():
    model = Model(get_smoke_config("stablelm-3b"))
    return model, model.init(0)


def _traced(tmp_path, engine, reqs, submit_at):
    """Step ``engine`` under the profiler until idle, submitting
    ``reqs[i]`` before step ``submit_at[i]``; returns the trace's events
    and the admit spans' ``rid`` stats."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        k = 0
        while k < 100:
            for r, at in zip(reqs, submit_at):
                if at == k:
                    engine.submit(r)
            if not engine.queue and all(s is None for s in engine.slots) \
                    and k > max(submit_at):
                break
            engine.step()
            k += 1
    finally:
        jax.profiler.stop_trace()
    ev = trace.load_events(str(tmp_path))
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    rids = [dict(e.stats).get("rid")
            for plane in jax.profiler.ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == "serve/admit"]
    return ev, rids


def _phases_by_step(host):
    steps = sorted((s, e) for s, e, n in host if n == "serve/step")
    phases = sorted((s, e, n) for s, e, n in host if n in PHASES)
    return steps, [[n for s, e, n in phases if s0 <= s and e <= e1]
                   for s0, e1 in steps]


def test_engine_spans_nest_in_order_in_the_profiler_trace(dense, tmp_path):
    model, params = dense
    engine = ServeEngine(model, params, batch_slots=2, max_len=32)
    reqs = [Request(rid=i, prompt=[i + 1, i + 2], max_new_tokens=2)
            for i in range(2)]
    ev, _ = _traced(tmp_path, engine, reqs, [0, 0])
    steps, per_step = _phases_by_step(ev.host)
    assert len(steps) == 3          # 2 prompt tokens + 1 further token
    assert per_step == [PHASES] * len(steps)
    assert sum(n in PHASES for _, _, n in ev.host) == 5 * len(steps)


def test_admit_span_carries_admitted_rids(dense, tmp_path):
    """One lane: rid 3 runs, rid 7 waits and is admitted mid-run into the
    freed lane; the two admitting steps name them, the others nobody."""
    model, params = dense
    engine = ServeEngine(model, params, batch_slots=1, max_len=32)
    reqs = [Request(rid=3, prompt=[5, 9], max_new_tokens=2),
            Request(rid=7, prompt=[4], max_new_tokens=1)]
    ev, rids = _traced(tmp_path, engine, reqs, [0, 1])
    steps, per_step = _phases_by_step(ev.host)
    assert per_step == [PHASES] * len(steps) and len(steps) == 4
    assert [None if r is None else str(r) for r in rids] == \
        ["3", None, None, "7"]
    assert all(r.done for r in reqs)


def _scope_paths(model, cache_len=32):
    cache = model.init_cache(2, cache_len, abstract=True)
    low = jax.jit(model.decode_step).lower(
        model.abstract_params(), cache,
        jax.ShapeDtypeStruct((2,), jnp.int32))
    names = re.findall(r'loc\("([^"]*)"', low.as_text(debug_info=True))
    return {"/".join(n.split("/")[:-1]) for n in names}


@pytest.mark.parametrize("arch,want,absent", [
    ("stablelm-3b", ["embed", "layer/attn", "layer/kv_write", "layer/ffn",
                     "head"], ["layer/ssm"]),
    ("mamba2-1.3b", ["embed", "layer/ssm", "layer/state_write", "head"],
     ["layer/attn", "layer/kv_write"]),
])
def test_decode_step_carries_task_scopes(arch, want, absent):
    paths = _scope_paths(Model(get_smoke_config(arch)))

    def has(scope):
        return any(p == scope or p.endswith("/" + scope) for p in paths)

    for scope in want:
        assert has(scope), (scope, sorted(paths))
    for scope in absent:
        assert not has(scope), scope


# ----------------------------------------------------------------- readers

def _tw(host, ops):
    ev = trace.Events(ops=ops, modules={}, host=host)
    return trace.TracedWindow(events=ev, steps=[], engine_step_s=None,
                              step_cost=None, peaks={})


def _read(name, tw):
    return spec.metric_reader(name)(tw)


# window [0, 100]. Step A [10, 30] syncs on [20, 28]; the device idles in
# it on [10, 12], [25, 26] and [29, 30]. Step B [40, 60] syncs on [45, 58]
# and the device never idles in it. The idle on [5, 10], [30, 38] and
# [61, 100] is outside every step. Step C crosses the window's end and
# step D lies after it: neither counts.
HOST = [(0, 100, "bench.window"),
        (10, 30, "serve/step"), (10, 11, "serve/admit"),
        (20, 28, "serve/sync"),
        (40, 60, "serve/step"), (45, 58, "serve/sync"),
        (95, 105, "serve/step"), (96, 104, "serve/sync"),
        (110, 120, "serve/step"), (111, 119, "serve/sync"),
        (38, 39, "engine.step")]
OPS = [(0, 5, "a"), (12, 25, "b"), (26, 29, "c"), (38, 61, "d"),
       (110, 115, "e")]


def test_engine_host_ms_reads_step_less_sync():
    tw = _tw(HOST, {"/device:TPU:0": OPS})
    # A: 20 - 8 = 12 ns, B: 20 - 13 = 7 ns
    assert _read("engine_host_ms.chat", tw) == pytest.approx(9.5e-6)


def test_idle_in_engine_ms_counts_only_idle_inside_steps():
    tw = _tw(HOST, {"/device:TPU:0": OPS})
    # A: 2 + 1 + 1 = 4 ns, B: 0 ns, over two steps
    assert _read("idle_in_engine_ms.chat", tw) == pytest.approx(2e-6)
    # a second device busy all through the window halves the mean
    tw = _tw(HOST, {"/device:TPU:0": OPS, "/device:TPU:1": [(0, 100, "z")]})
    assert _read("idle_in_engine_ms.chat", tw) == pytest.approx(1e-6)


@pytest.mark.parametrize("name,host,ops", [
    ("engine_host_ms.chat", [h for h in HOST if h[2] != "serve/step"],
     {"/device:TPU:0": OPS}),
    ("engine_host_ms.chat", [h for h in HOST if h[2] != "serve/sync"],
     {"/device:TPU:0": OPS}),
    ("engine_host_ms.chat", [h for h in HOST if h[2] != "bench.window"],
     {"/device:TPU:0": OPS}),
    ("idle_in_engine_ms.chat", [h for h in HOST if h[2] != "serve/step"],
     {"/device:TPU:0": OPS}),
    ("idle_in_engine_ms.chat", HOST, {}),
    ("idle_in_engine_ms.chat", [h for h in HOST if h[2] != "bench.window"],
     {"/device:TPU:0": OPS}),
])
def test_readers_read_nothing_without_their_events(name, host, ops):
    """The parent program writes no engine spans: its traced runs read
    None, and the line leaves the metric out."""
    assert _read(name, _tw(host, ops)) is None
