"""The one traffic generator: turns a mix's parameters and a seed into a
schedule of requests.

Every seed gets the same set of request sizes and of arrival gaps, in
another order, with other prompt tokens: prompt and output sizes are the
quantiles of the mix's distributions at evenly spaced probabilities, paired
in one fixed way, and gaps the quantiles of the exponential distribution.
So two seeds differ in order and content, not in the amount of work, and
runs of a cell stay comparable. Where a window holds too few requests for
the order to average out, a cell sets ``fixed_order``: the order and the
gaps are then one fixed draw, the same for every seed, and the seed draws
only the prompt tokens.

Arrival kinds:

- ``poisson``: an open loop at ``rate_per_s``. ``lead_s`` seconds of
  arrivals come before the measured window so that the window opens on a
  loaded engine, then ``seconds`` of arrivals inside it; each part has its
  own set of sizes.
- ``backlog``: ``backlog_per_slot`` x slots requests all due at 0, enough
  to keep every slot busy through the lead and the window.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Arrival:
    due_s: float            # seconds after the schedule starts
    prompt: list[int]
    max_new_tokens: int
    in_window: bool         # due inside the measured window


def rng_for(seed: int) -> np.random.Generator:
    """A generator for any whole ``seed``, negative or past 64 bits."""
    return np.random.default_rng(seed % 2**64)


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` integer sizes at probabilities (i + 0.5) / n of ``dist``,
    clipped to its ``min`` and ``max``."""
    p = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in p])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        vals = dist["min"] + p * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(int)


def _gaps(n: int, span: float, rng) -> np.ndarray:
    p = (np.arange(n) + 0.5) / n
    g = rng.permutation(-np.log1p(-p))
    return g * (span / g.sum())


def _requests(mix: dict, n: int, rng, order_rng, vocab: int, max_len: int):
    # one fixed pairing of prompt and output sizes for every seed;
    # ``order_rng`` orders the pairs, ``rng`` draws the tokens
    outs = np.random.default_rng(0).permutation(
        quantiles(mix["output_len"], n))
    order = order_rng.permutation(n)
    prompts, outs = quantiles(mix["prompt_len"], n)[order], outs[order]
    # a request fills prompt + output - 1 cache positions
    outs = np.minimum(outs, max_len + 1 - prompts)
    if (outs < 1).any():
        raise ValueError(f"prompts of up to {prompts.max()} tokens do not "
                         f"fit a {max_len}-position cache")
    return [(rng.integers(0, vocab, size=int(k)).tolist(), int(o))
            for k, o in zip(prompts, outs)]


def generate(mix: dict, seed: int, seconds: float, *, vocab: int,
             max_len: int, slots: int) -> list[Arrival]:
    """The schedule for one run, sorted by due time."""
    rng = rng_for(seed)
    order_rng = np.random.default_rng(0) if mix.get("fixed_order") else rng
    lead = float(mix.get("lead_s", 0.0))
    if mix["arrival"] == "backlog":
        n = int(mix["backlog_per_slot"]) * slots
        return [Arrival(0.0, p, o, True) for p, o in
                _requests(mix, n, rng, order_rng, vocab, max_len)]
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival kind {mix['arrival']!r}")
    rate = float(mix["rate_per_s"])
    out = []
    for start, span, in_window in ((0.0, lead, False),
                                   (lead, float(seconds), True)):
        n = max(1, math.floor(rate * span + 0.5)) if span > 0 else 0
        if not n:
            continue
        gaps = _gaps(n, span, order_rng)
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        out += [Arrival(float(t), p, o, in_window) for t, (p, o) in
                zip(due, _requests(mix, n, rng, order_rng, vocab, max_len))]
    return out
