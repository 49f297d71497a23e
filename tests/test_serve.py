"""Serving engine: batched greedy decode matches the manual decode loop,
and mid-run admission is byte-identical to solo serving."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import get_smoke_config
from repro.models.transformer import Model
from repro.serve import Request, ServeEngine


def _solo(model, params, prompt, n_new, max_len=64):
    """Serve one request alone: the reference token stream."""
    cache = model.init_cache(1, max_len)
    step = jax.jit(model.decode_step)
    tok = None
    for t in prompt:
        tok, cache = step(params, cache, jnp.asarray([t], jnp.int32))
    out = [int(tok[0])]
    for _ in range(n_new - 1):
        tok, cache = step(params, cache, tok)
        out.append(int(tok[0]))
    return out


def test_engine_matches_manual_decode():
    cfg = get_smoke_config("stablelm-3b")
    model = Model(cfg)
    params = model.init(0)
    prompts = [[5, 9, 2], [7, 1, 3]]

    # manual: stream prompt tokens, then greedy-continue
    def manual(prompt, n_new):
        cache = model.init_cache(1, 64)
        step = jax.jit(model.decode_step)
        tok = None
        for t in prompt:
            tok, cache = step(params, cache, jnp.asarray([t], jnp.int32))
        out = [int(tok[0])]
        for _ in range(n_new - 1):
            tok, cache = step(params, cache, tok)
            out.append(int(tok[0]))
        return out

    expected = [manual(p, 5) for p in prompts]

    engine = ServeEngine(model, params, batch_slots=2, max_len=64)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=5)
            for i, p in enumerate(prompts)]
    for r in reqs:
        engine.submit(r)
    engine.run_until_idle()
    assert [r.out for r in reqs] == expected
    assert all(r.done for r in reqs)


def test_engine_batches_capacity():
    cfg = get_smoke_config("gemma3-1b")
    model = Model(cfg)
    params = model.init(0)
    engine = ServeEngine(model, params, batch_slots=4, max_len=64)
    reqs = [Request(rid=i, prompt=[i + 1, i + 2], max_new_tokens=3)
            for i in range(4)]
    for r in reqs:
        engine.submit(r)
    engine.run_until_idle()
    assert all(len(r.out) == 3 for r in reqs)


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["stablelm-3b", "gemma3-1b", "mamba2-1.3b",
                                  "zamba2-7b", "granite-4.0-h-micro"])
@pytest.mark.parametrize("offset", [1, 3, 6])
def test_mid_run_admission_byte_identical(arch, offset):
    """A request admitted while another is mid-decode must produce exactly
    the tokens it would produce served alone — per-slot cache positions
    plus lane reset make admission exact at any step, across transformer,
    windowed-attention, SSM, and hybrid families."""
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(0)
    long = Request(rid=0, prompt=[5, 9, 2, 4], max_new_tokens=12)
    late = Request(rid=1, prompt=[7, 1, 3], max_new_tokens=5)
    expected_long = _solo(model, params, long.prompt, long.max_new_tokens)
    expected_late = _solo(model, params, late.prompt, late.max_new_tokens)

    engine = ServeEngine(model, params, batch_slots=2, max_len=64)
    engine.submit(long)
    for _ in range(offset):          # the long request runs alone first...
        engine.step()
    engine.submit(late)              # ...then the late one joins mid-run
    engine.run_until_idle()
    assert long.out == expected_long
    assert late.out == expected_late


def test_slot_reuse_resets_lane():
    """A slot freed by a finished request and re-used by a later one must
    not leak stale cache state into the newcomer's tokens."""
    cfg = get_smoke_config("gemma3-1b")
    model = Model(cfg)
    params = model.init(0)
    a = Request(rid=0, prompt=[5, 9], max_new_tokens=3)
    b = Request(rid=1, prompt=[7, 1, 3], max_new_tokens=4)
    expected_b = _solo(model, params, b.prompt, b.max_new_tokens)

    engine = ServeEngine(model, params, batch_slots=1, max_len=64)
    engine.submit(a)
    engine.submit(b)                 # b waits for a's slot, then re-uses it
    engine.run_until_idle()
    assert a.done and b.done
    assert b.out == expected_b


PHASES = ("serve/admit", "serve/prepare", "serve/dispatch", "serve/sync",
          "serve/emit")


def test_engine_emits_trace_and_metrics():
    from repro.obs import MetricsRegistry, Tracer

    cfg = get_smoke_config("gemma3-1b")
    model = Model(cfg)
    params = model.init(0)
    tracer, metrics = Tracer(), MetricsRegistry()
    engine = ServeEngine(model, params, batch_slots=2, max_len=64,
                         tracer=tracer, metrics=metrics)
    reqs = [Request(rid=i, prompt=[i + 1, i + 2], max_new_tokens=3)
            for i in range(2)]
    for r in reqs:
        engine.submit(r)
    engine.run_until_idle()

    events = tracer.drain()
    steps = [e for e in events if e.name == "serve/step"]
    assert steps and all(e.ph == "X" and e.cat == "serve" for e in steps)
    assert steps[0].args["active"] == 2
    # the ring holds the phase spans the profiler gets, in order, in each
    # step; the first step admitted both requests
    phases = [e for e in events if e.ph == "X" and e.name in PHASES]
    for st in steps:
        inside = [e for e in phases
                  if st.ts <= e.ts and e.ts + e.dur <= st.ts + st.dur]
        assert [e.name for e in inside] == list(PHASES)
        assert all(e.cat == "serve" for e in inside)
    assert len(phases) == len(PHASES) * len(steps)
    assert phases[0].args == {"rid": "0 1"}
    assert any(e.name == "serve/active_slots" for e in events)
    assert metrics.counter("serve/tokens") == 6
    assert metrics.counter("serve/requests_done") == 2
    hist = metrics.snapshot()["histograms"]["serve/step_s"]
    assert hist["count"] == len(steps) and hist["p99"] > 0
    # the cache's bytes on the device, in its layout there
    assert metrics.gauge("serve/cache_bytes") == sum(
        a.on_device_size_in_bytes() for a in jax.tree.leaves(engine.cache))
    assert metrics.gauge("serve/cache_bytes") >= sum(
        a.nbytes for a in jax.tree.leaves(engine.cache))
