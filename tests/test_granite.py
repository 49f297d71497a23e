"""granite-4.0-h-micro: Mamba2 and GQA attention layers by pattern, each
with its own MLP, served through ``ServeEngine`` and held to the plain
float32 reference in ``bench/arch/hybrid.py`` at smoke size.

The smoke configuration has two periods of (Mamba2, attention, Mamba2),
four query heads over two KV heads, and every multiplier away from 1.
Logits are compared, not sampled tokens: with random weights the best
logit can change on rounding.

Tolerance: 1e-5 on logits whose spread is about 0.14. The program in
float32 reads within about 1e-6 of the reference (the order of float32
sums differs: the chunked SSD scan against the reference's token-by-token
recurrence, flash attention against a plain softmax). The same program
computing in bfloat16 misses by about 4e-3, so it fails by a wide margin
(``test_tolerance_fails_a_bfloat16_program``).
"""
import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BIG, LITTLE
from repro.models import embedloss
from repro.models.config import get_config, get_smoke_config
from repro.models.transformer import Model
from repro.pipeline import HeterogeneousSystem, model_chain
from repro.serve import Request, ServeEngine

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import spec  # noqa: E402

ARCH = "granite-4.0-h-micro"
TOL = 1e-5
MAX_LEN = 32


def _model_dict(cfg) -> dict:
    """``cfg`` as a configuration file's ``model`` entry."""
    model = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    model["ssm"] = dataclasses.asdict(model["ssm"])
    return model


@pytest.fixture(scope="module")
def smoke():
    """(model dict, weights from the reference's seed, the program)."""
    cfg = get_smoke_config(ARCH)
    model = _model_dict(cfg)
    ref = spec.arch("hybrid")
    params = jax.jit(functools.partial(ref.make_params, model))(
        jax.random.PRNGKey(3))
    return model, params, Model(cfg)


def _reference_logits(model, params, seq) -> np.ndarray:
    """The reference's logits at every position of ``seq``."""
    ref = spec.arch("hybrid")
    h = ref.forward(model, params, jnp.asarray([seq], jnp.int32))
    return np.asarray(ref.logits(model, params, h[0]))


def _program_logits(prog, params, tokens) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        x = prog.forward(params, {"tokens": tokens})
        return np.asarray(x.astype(jnp.float32)
                          @ params["embed"][:prog.cfg.vocab].T.astype(
                              jnp.float32))


def test_layout_follows_the_published_layer_types():
    cfg = get_config(ARCH)
    published = spec.load_json(spec.BENCH_DIR / "configs" / f"{ARCH}.json")
    assert cfg.layer_types() == tuple(published["layer_types"])
    hy = Model(cfg).hybrid
    assert (hy.n_mamba, hy.attn_after, hy.shared) == (36, (4, 13, 22, 31),
                                                     False)
    assert cfg.param_count()[0] == 3_191_239_424


def test_forward_matches_reference(smoke):
    model, params, prog = smoke
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 24), 0,
                              model["vocab"])
    want = _program_logits(prog, params, toks)
    ref = spec.arch("hybrid")
    h = ref.forward(model, params, toks)
    got = np.asarray(ref.logits(model, params, h.reshape(-1, h.shape[-1])))
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=TOL,
                               rtol=0)


def test_tolerance_fails_a_bfloat16_program(smoke):
    model, params, prog = smoke
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 24), 0,
                              model["vocab"])
    want = _program_logits(prog, params, toks)
    low = Model(dataclasses.replace(prog.cfg, param_dtype="bfloat16",
                                    compute_dtype="bfloat16"))
    got = _program_logits(
        low, jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), toks)
    assert np.abs(got - want).max() > 10 * TOL


def _serve_with_logits(prog, params, requests, submit_at, monkeypatch):
    """Serve ``requests`` through ``ServeEngine`` (2 lanes), submitting
    request ``i`` before step ``submit_at[i]``; returns each request's
    head logits by position (prompt, then decode), read at the tied head
    of the step the engine runs."""
    seen = []
    greedy = embedloss.greedy

    def spy(x, table, valid_vocab=None):
        logits = x.astype(jnp.float32) @ table[:valid_vocab].astype(
            jnp.float32).T
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), logits)
        return greedy(x, table, valid_vocab=valid_vocab)

    monkeypatch.setattr(embedloss, "greedy", spy)
    by_rid = {r.rid: [] for r in requests}
    engine = ServeEngine(prog, params, batch_slots=2, max_len=MAX_LEN)
    for step in range(200):
        if all(r.done for r in requests):
            break
        for r, at in zip(requests, submit_at):
            if at == step:
                engine.submit(r)
        before = list(engine.slots)
        engine.step()
        jax.effects_barrier()
        for lane, r in enumerate(before):
            # the lane's request before the step, or the one it admitted
            r = r or engine.slots[lane]
            if r is not None:
                by_rid[r.rid].append(seen[-1][lane])
    return by_rid


def test_engine_prompt_then_decode_matches_reference(smoke, monkeypatch):
    """Prompts streamed and answers decoded through ``ServeEngine`` (two
    lanes at different positions, one request admitted into a lane that
    another used) give the reference's full-forward logits at every
    position."""
    model, params, prog = smoke
    # a model of its own name, so that no step traced without the spy is
    # reused
    prog = Model(dataclasses.replace(prog.cfg, name=prog.cfg.name + "-spy"))
    reqs = [Request(rid=0, prompt=[5, 9, 2, 4, 7], max_new_tokens=9),
            Request(rid=1, prompt=[7, 1, 3], max_new_tokens=3),
            Request(rid=2, prompt=[11, 6, 8, 2], max_new_tokens=5)]
    by_rid = _serve_with_logits(prog, params, reqs, [0, 1, 6], monkeypatch)
    for r in reqs:
        assert r.done and len(r.out) == r.max_new_tokens
        seq = list(r.prompt) + list(r.out[:-1])
        got = np.stack(by_rid[r.rid])
        assert got.shape[0] == len(seq)
        want = _reference_logits(model, params, seq)
        np.testing.assert_allclose(got, want, atol=TOL, rtol=0,
                                   err_msg=f"request {r.rid}")


def _solo(prog, params, prompt, n_new):
    cache = prog.init_cache(1, MAX_LEN)
    step = jax.jit(prog.decode_step)
    for t in prompt:
        tok, cache = step(params, cache, jnp.asarray([t], jnp.int32))
    out = [int(tok[0])]
    for _ in range(n_new - 1):
        tok, cache = step(params, cache, tok)
        out.append(int(tok[0]))
    return out


@pytest.mark.parametrize("offset", [2, 5])
def test_mid_run_admission_into_reused_lane_is_exact(smoke, offset):
    """A request admitted mid-run into a lane a finished request used
    (its conv windows, SSM states and KV rows reset) gives the tokens it
    gives served alone, and so does the request running beside it."""
    _, params, prog = smoke
    long = Request(rid=0, prompt=[5, 9, 2, 4], max_new_tokens=14)
    short = Request(rid=1, prompt=[7, 1], max_new_tokens=2)
    late = Request(rid=2, prompt=[3, 8, 6], max_new_tokens=5)
    expected = {r.rid: _solo(prog, params, r.prompt, r.max_new_tokens)
                for r in (long, short, late)}
    engine = ServeEngine(prog, params, batch_slots=2, max_len=MAX_LEN)
    engine.submit(long)
    engine.submit(short)
    for _ in range(short.total_steps + offset):
        engine.step()
    assert short.done and engine.slots[1] is None and not long.done
    engine.submit(late)
    engine.step()
    assert engine.slots[1] is late
    engine.run_until_idle()
    for r in (long, short, late):
        assert r.out == expected[r.rid], r.rid


def test_planner_chain_weights_follow_layer_types():
    """The planner's chain gives granite two layer weights, one per mixer
    kind, in the published order."""
    cfg = get_config(ARCH)
    chain, blocks = model_chain(cfg, tokens_per_step=32, mode="decode",
                                system=HeterogeneousSystem.default(4, 4))
    layers = [b for b in blocks if b.name.startswith("layer")]
    assert [b.name for b in layers] == [f"layer{i}" for i in range(40)]
    by_kind = {}
    for kind, b in zip(cfg.layer_types(), layers):
        by_kind.setdefault(kind, set()).add((b.flops, b.bytes_moved))
    assert set(by_kind) == {"mamba", "attention"}
    assert all(len(v) == 1 for v in by_kind.values())
    (mamba,), (attn,) = by_kind["mamba"], by_kind["attention"]
    assert mamba != attn
    # both kinds carry the same MLP; the Mamba2 mixer's projections
    # outweigh the GQA projections
    assert mamba[0] > attn[0]
    first = chain.names.index("layer0")
    for v in (BIG, LITTLE):
        w = chain.w[v][first:first + 40]
        by_kind = {k: {float(x) for x, kk in zip(w, cfg.layer_types())
                       if kk == k} for k in ("mamba", "attention")}
        assert all(len(x) == 1 for x in by_kind.values())
        assert by_kind["mamba"] != by_kind["attention"]
