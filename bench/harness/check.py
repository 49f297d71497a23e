"""Whether what the timed path served is correct.

Once the window has closed and the program's state is freed, a sample of
the requests it finished, drawn from the seed and always holding the
longest, goes through the plain float32 reference of ``bench/arch/<kind>``:
once over each prompt followed by the served tokens. For each served token
the gap is the reference's best logit at that position minus the
reference's logit of the served token (0 where the program chose the
reference's best). The run's number is the widest gap over the sample.

The same positions give the control's reading: the reference in fp8 (see
``numerics``) puts its own token first at each position, and its gap is
read under the float32 reference in the same way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.traffic import rng_for

SAMPLE = 8          # requests compared per run: the reference's batch
ROW_CHUNK = 512     # logit rows per head call


def sample(log, seed: int, n: int = SAMPLE) -> list:
    """Up to ``n`` finished records: the one with the most served tokens,
    then a draw from ``seed`` among the rest that takes each lane once
    before any twice, and holds a request admitted into a reused lane
    where one finished (the lane reset)."""
    done = [r for r in log.records if r.req.done and not r.req.rejected]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.req.out), r.req.rid))
    rest = [done[i] for i in rng_for(seed + 1).permutation(len(done))
            if done[i] is not longest]
    picked, lanes = [longest], {longest.lane}
    for r in rest:
        if len(picked) < n and r.lane not in lanes:
            picked.append(r)
            lanes.add(r.lane)
    picked += [r for r in rest if r not in picked][:n - len(picked)]
    if not any(r.lane_reused for r in picked):
        reused = [r for r in rest if r.lane_reused]
        if reused:
            picked[-1 if len(picked) == n else len(picked):] = reused[:1]
    return picked


def _sequences(picked, max_len: int):
    """Token matrix (SAMPLE, max_len) of prompt + served[:-1], and for
    each served token its (row, position) and id."""
    toks = np.zeros((max(SAMPLE, len(picked)), max_len), np.int32)
    rows, pos, served = [], [], []
    for b, rec in enumerate(picked):
        p, out = rec.req.prompt, rec.req.out
        seq = list(p) + list(out[:-1])
        toks[b, :len(seq)] = seq
        for j, tok in enumerate(out):
            rows.append(b)
            pos.append(len(p) - 1 + j)
            served.append(tok)
    return toks, np.array(rows), np.array(pos), np.array(served)


@jax.jit
def _gaps(ref, other, served):
    """Per row: best reference logit minus the reference logit of the
    served token, and of the token ``other`` ranks first."""
    best = ref.max(axis=-1)
    at = lambda ids: jnp.take_along_axis(ref, ids[:, None], -1)[:, 0]  # noqa
    return best - at(served), best - at(jnp.argmax(other, axis=-1))


def logit_gaps(arch, model: dict, params, picked, max_len: int,
               control: bool = False) -> dict:
    """Widest gap of the served tokens (``served``) and, with ``control``,
    of the fp8 control's first choices (``control``), over ``picked``."""
    toks, rows, pos, served = _sequences(picked, max_len)
    toks = jnp.asarray(toks)
    h = arch.forward(model, params, toks, "f32")
    hq = arch.forward(model, params, toks, "fp8") if control else None
    n = len(served)
    pad = -n % ROW_CHUNK
    rows, pos = np.pad(rows, (0, pad)), np.pad(pos, (0, pad))
    served = np.pad(served, (0, pad))
    out = {"served": 0.0, "tokens": n}
    if control:
        out["control"] = 0.0
    for lo in range(0, n + pad, ROW_CHUNK):
        sl = slice(lo, lo + ROW_CHUNK)
        keep = min(ROW_CHUNK, n - lo)
        ref = arch.logits(model, params, h[rows[sl], pos[sl]], "f32")
        other = ref if hq is None else arch.logits(
            model, params, hq[rows[sl], pos[sl]], "fp8")
        g_served, g_other = (np.asarray(g)[:keep] for g in
                             _gaps(ref, other, jnp.asarray(served[sl])))
        out["served"] = max(out["served"], float(g_served.max()))
        if control:
            out["control"] = max(out["control"], float(g_other.max()))
    return out


def exact_checks(log, vocab: int) -> dict:
    """Counts that must be 0: finished requests whose output is not
    exactly their budget, and served tokens outside the vocabulary."""
    done = [r.req for r in log.records if r.req.done and not r.req.rejected]
    return {
        "wrong_length": sum(len(q.out) != q.max_new_tokens for q in done),
        "out_of_vocab": sum(not 0 <= t < vocab for q in done for t in q.out),
    }
