"""Vectorized planning kernels vs their retained scalar reference oracles.

The energy/DVFS planning layer (repro.energy.pareto) was rebuilt around
numpy budget-plane kernels; this suite certifies the PR's exactness
contract: the vectorized DPs and sweeps produce BIT-IDENTICAL results —
period, energy, stage decomposition, frequency annotation, tie-breaking —
to the scalar ``*_reference`` implementations, on random chains
(hypothesis, n <= 6, budgets <= 4+4, <= 3 frequency levels per ladder),
on directed edge cases, and on the real DVB-S2 tables. Also covers the
lazy ``ParetoPoint.solution`` semantics, the ``min_period_under_power``
bisection (incl. the cap + 1e-9 admission boundary), candidate-table
rescaling, frequency-profile deduplication, and the stacked multi-chain
``herad_tables`` path against the scalar HeRAD pseudo-code.
"""
import math

import numpy as np
import pytest

from _hyp import example, given, settings, st
from repro.core import BIG, LITTLE, make_chain
from repro.core.chain import TaskChain
from repro.core.dvfs import dvfs_tables, scale_chain
from repro.core.herad import (
    herad,
    herad_reference,
    herad_table,
    herad_tables,
    plane_merged_stages,
)
from repro.energy import (
    CandidateTable,
    DEFAULT_POWER,
    ParetoPoint,
    PowerModel,
    dvfs_frontier,
    energy,
    min_energy_under_period,
    min_energy_under_period_freq,
    min_energy_under_period_freq_batch,
    min_energy_under_period_freq_reference,
    min_energy_under_period_reference,
    min_period_under_power,
    pareto_frontier,
    sweep_budgets,
    sweep_budgets_freq,
    sweep_budgets_freq_reference,
    sweep_budgets_reference,
    sweep_budgets_variant,
    sweep_budgets_variant_reference,
)
from repro.core.variants import VariantRegistry
from repro.energy.pareto import _non_dominated

LADDERS = [
    (1.0,),
    (0.6, 1.0),
    (0.5, 0.75, 1.0),
    {"big": (0.6, 0.8, 1.0), "little": (0.75, 1.0)},
]


def _chain(seed, n=6, sr=0.5):
    return make_chain(np.random.default_rng(seed), n, sr)


def _model(ladder):
    return PowerModel("equiv", DEFAULT_POWER.big, DEFAULT_POWER.little,
                      freq_levels=ladder)


def _vspec(chain, seed, k):
    """k random non-base variants covering every task (k=0: trivial)."""
    rng = np.random.default_rng(20_000 + seed)
    reg = VariantRegistry()
    for ki in range(k):
        for task in chain.names:
            reg.register(task, f"v{ki}",
                         big=float(rng.uniform(0.6, 1.5)),
                         little=float(rng.uniform(0.6, 1.5)))
    return reg.spec_for(chain)


def _assert_points_equal(fast, ref):
    assert len(fast) == len(ref)
    for a, r in zip(fast, ref):
        assert a.period == r.period          # bit-identical, no approx
        assert a.energy == r.energy
        assert a.budget == r.budget
        assert a.solution == r.solution      # decomposition + frequencies


# ------------------------------------------------------- hypothesis suites
@settings(deadline=None, max_examples=60)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(1, 6),
    sr=st.sampled_from([0.0, 0.25, 0.5, 0.75, 1.0]),
    b=st.integers(0, 4),
    l=st.integers(0, 4),
    ladder=st.sampled_from(LADDERS),
    stretch=st.sampled_from([0.5, 1.0, 1.5, 4.0]),
)
def test_min_energy_dp_matches_reference(seed, n, sr, b, l, ladder, stretch):
    chain = _chain(seed, n, sr)
    power = _model(ladder)
    if b + l == 0:
        p_max = 100.0
    else:
        opt = herad(chain, b, l)
        p_max = opt.period(chain) * stretch if not opt.is_empty() else 50.0
    fast = min_energy_under_period_freq(chain, b, l, p_max, power)
    ref = min_energy_under_period_freq_reference(chain, b, l, p_max, power)
    assert fast == ref  # stages, replicas, types, frequencies — exact
    if not fast.is_empty():
        # same objective value through the accounting layer
        assert energy(chain, fast, power, period=p_max) == \
            energy(chain, ref, power, period=p_max)


@settings(deadline=None, max_examples=40)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(1, 6),
    sr=st.sampled_from([0.0, 0.25, 0.5, 1.0]),
    b=st.integers(0, 4),
    l=st.integers(0, 4),
    ladder=st.sampled_from(LADDERS),
)
def test_min_energy_dp_batch_matches_scalar(seed, n, sr, b, l, ladder):
    """The batched refinement DP == S independent scalar DP calls, bit
    for bit — including guard slots (inf / non-positive bounds) and
    shared-CandidateTable reuse."""
    chain = _chain(seed, n, sr)
    power = _model(ladder)
    if b + l == 0:
        base = 100.0
    else:
        opt = herad(chain, b, l)
        base = opt.period(chain) if not opt.is_empty() else 50.0
    p_maxes = [base * s for s in (0.4, 0.8, 1.0, 1.0, 1.7, 3.0)] \
        + [math.inf, 0.0, -2.0]
    batch = min_energy_under_period_freq_batch(chain, b, l, p_maxes, power)
    assert len(batch) == len(p_maxes)
    cand = CandidateTable.build(chain, power)
    for p_max, fast in zip(p_maxes, batch):
        ref = min_energy_under_period_freq(chain, b, l, p_max, power,
                                           candidates=cand)
        assert fast == ref  # stages, replicas, types, frequencies — exact
        if not fast.is_empty():
            assert energy(chain, fast, power, period=p_max) == \
                energy(chain, ref, power, period=p_max)
    assert min_energy_under_period_freq_batch(chain, b, l, [], power) == []


@settings(deadline=None, max_examples=40)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(1, 6),
    sr=st.sampled_from([0.0, 0.5, 1.0]),
    b=st.integers(0, 4),
    l=st.integers(0, 4),
    stretch=st.sampled_from([1.0, 2.5]),
)
def test_min_energy_nominal_matches_reference(seed, n, sr, b, l, stretch):
    chain = _chain(seed, n, sr)
    if b + l == 0:
        p_max = 100.0
    else:
        opt = herad(chain, b, l)
        p_max = opt.period(chain) * stretch if not opt.is_empty() else 50.0
    assert min_energy_under_period(chain, b, l, p_max, DEFAULT_POWER) == \
        min_energy_under_period_reference(chain, b, l, p_max, DEFAULT_POWER)


@settings(deadline=None, max_examples=40)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(1, 6),
    sr=st.sampled_from([0.0, 0.25, 0.5, 1.0]),
    b=st.integers(0, 4),
    l=st.integers(0, 4),
)
def test_sweep_budgets_matches_reference(seed, n, sr, b, l):
    chain = _chain(seed, n, sr)
    _assert_points_equal(
        sweep_budgets(chain, b, l, DEFAULT_POWER),
        sweep_budgets_reference(chain, b, l, DEFAULT_POWER))


@settings(deadline=None, max_examples=40)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(1, 5),
    sr=st.sampled_from([0.0, 0.5, 1.0]),
    b=st.integers(0, 4),
    l=st.integers(0, 4),
    ladder=st.sampled_from(LADDERS),
)
@example(seed=0, n=3, sr=0.0, b=2, l=1, ladder=(0.6, 1.0))
def test_sweep_budgets_freq_matches_reference(seed, n, sr, b, l, ladder):
    chain = _chain(seed, n, sr)
    power = _model(ladder)
    _assert_points_equal(
        sweep_budgets_freq(chain, b, l, power),
        sweep_budgets_freq_reference(chain, b, l, power))


@settings(deadline=None, max_examples=40)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(1, 5),
    sr=st.sampled_from([0.0, 0.5, 1.0]),
    b=st.integers(0, 3),
    l=st.integers(0, 3),
    ladder=st.sampled_from(LADDERS),
    k=st.integers(0, 2),
    stretch=st.sampled_from([0.8, 1.0, 2.0]),
)
def test_min_energy_dp_variant_matches_reference(seed, n, sr, b, l,
                                                 ladder, k, stretch):
    """The 4-axis DP (kernel-variant candidates on top of the ladder) is
    bit-identical to its scalar reference; k=0 exercises the trivial
    spec, which must match the pre-variant path exactly."""
    chain = _chain(seed, n, sr)
    power = _model(ladder)
    spec = _vspec(chain, seed, k)
    if b + l == 0:
        p_max = 100.0
    else:
        opt = herad(chain, b, l)
        p_max = opt.period(chain) * stretch if not opt.is_empty() else 50.0
    fast = min_energy_under_period_freq(chain, b, l, p_max, power,
                                        variants=spec)
    ref = min_energy_under_period_freq_reference(chain, b, l, p_max,
                                                 power, variants=spec)
    assert fast == ref  # stages, replicas, types, freqs, variants
    if k == 0:
        assert fast == min_energy_under_period_freq(chain, b, l, p_max,
                                                    power)
    if not fast.is_empty():
        assert energy(chain, fast, power, period=p_max) == \
            energy(chain, ref, power, period=p_max)


@settings(deadline=None, max_examples=25)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(1, 4),
    sr=st.sampled_from([0.0, 0.5, 1.0]),
    b=st.integers(0, 3),
    l=st.integers(0, 3),
    ladder=st.sampled_from(LADDERS),
    k=st.integers(1, 2),
)
@example(seed=0, n=3, sr=0.0, b=2, l=1, ladder=(1.0,), k=2)
def test_sweep_budgets_variant_matches_reference(seed, n, sr, b, l,
                                                 ladder, k):
    chain = _chain(seed, n, sr)
    power = _model(ladder)
    spec = _vspec(chain, seed, k)
    _assert_points_equal(
        sweep_budgets_variant(chain, b, l, power, variants=spec),
        sweep_budgets_variant_reference(chain, b, l, power,
                                        variants=spec))


@settings(deadline=None, max_examples=25)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(1, 5),
    sr=st.sampled_from([0.0, 0.5, 1.0]),
    b=st.integers(1, 4),
    l=st.integers(0, 4),
    ladder=st.sampled_from(LADDERS),
)
@example(seed=3921, n=5, sr=0.0, b=1, l=2, ladder=(1.0,))
def test_frontiers_match_reference_composition(seed, n, sr, b, l, ladder):
    """pareto_frontier / dvfs_frontier == non-dominated reference sweep
    refined by the reference DP (the pre-PR composition)."""
    chain = _chain(seed, n, sr)
    power = _model(ladder)

    def ref_frontier(dvfs):
        pts = _non_dominated(
            sweep_budgets_freq_reference(chain, b, l, power) if dvfs
            else sweep_budgets_reference(chain, b, l, power))
        refined = []
        for pt in pts:
            if dvfs:
                sol = min_energy_under_period_freq_reference(
                    chain, b, l, pt.period, power)
            else:
                sol = min_energy_under_period_reference(
                    chain, b, l, pt.period, power)
            if sol.is_empty():
                refined.append(pt)
                continue
            e = energy(chain, sol, power, period=pt.period)
            refined.append(ParetoPoint(pt.period, e, sol, sol.core_usage())
                           if e < pt.energy else pt)
        return _non_dominated(refined)

    _assert_points_equal(pareto_frontier(chain, b, l, power),
                         ref_frontier(dvfs=False))
    _assert_points_equal(dvfs_frontier(chain, b, l, power),
                         ref_frontier(dvfs=True))


@settings(deadline=None, max_examples=40)
@given(
    seed=st.integers(0, 10_000),
    n=st.integers(1, 7),
    sr=st.sampled_from([0.0, 0.3, 0.6, 1.0]),
    b=st.integers(0, 4),
    l=st.integers(0, 4),
)
def test_stacked_herad_tables_match_scalar_pseudocode(seed, n, sr, b, l):
    """The batched table fill reproduces Algos 7-11 for every sub-budget
    and every chain of a profile grid."""
    if b + l == 0:
        return
    chain = _chain(seed, n, sr)
    chains = [chain, scale_chain(chain, 0.5, 1.0), scale_chain(chain, 1.0, 0.75)]
    tables = herad_tables(chains, b, l)
    for ch, table in zip(chains, tables):
        for bb in range(b + 1):
            for ll in range(l + 1):
                if bb + ll == 0:
                    continue
                from repro.core.herad import extract_solution
                assert extract_solution(table, ch, bb, ll) == \
                    herad_reference(ch, bb, ll)


# --------------------------------------------------------- directed cases
# A deterministic grid mirroring the hypothesis suites, so the exactness
# contract is certified even where hypothesis is unavailable (the _hyp
# shim skips @given tests there).
@pytest.mark.parametrize("seed", range(12))
def test_equivalence_grid_deterministic(seed):
    rng = np.random.default_rng(1000 + seed)
    n = int(rng.integers(1, 7))
    sr = float(rng.choice([0.0, 0.25, 0.5, 0.75, 1.0]))
    chain = make_chain(rng, n, sr)
    b, l = int(rng.integers(0, 5)), int(rng.integers(0, 5))
    power = _model(LADDERS[seed % len(LADDERS)])
    p_maxes = [math.inf, 0.0, 75.0]
    if b + l > 0:
        opt = herad(chain, b, l)
        if not opt.is_empty():
            p = opt.period(chain)
            p_maxes += [p, 0.5 * p, 1.5 * p, 4.0 * p]
    for p_max in p_maxes:
        assert min_energy_under_period_freq(chain, b, l, p_max, power) == \
            min_energy_under_period_freq_reference(chain, b, l, p_max, power)
        assert min_energy_under_period(chain, b, l, p_max, power) == \
            min_energy_under_period_reference(chain, b, l, p_max, power)
    _assert_points_equal(sweep_budgets(chain, b, l, power),
                         sweep_budgets_reference(chain, b, l, power))
    _assert_points_equal(sweep_budgets_freq(chain, b, l, power),
                         sweep_budgets_freq_reference(chain, b, l, power))
    if b + l > 0:
        for bb in range(b + 1):
            for ll in range(l + 1):
                if bb + ll == 0:
                    continue
                assert herad(chain, bb, ll) == herad_reference(chain, bb, ll)


def test_dvbs2_sweeps_and_dp_bit_identical():
    """Real float-weight tables (0.1 µs precision), both platforms."""
    from repro.configs.dvbs2 import RESOURCES, dvbs2_chain, platform_power

    for plat in RESOURCES:
        chain = dvbs2_chain(plat)
        power = platform_power(plat)
        b, l = (4, 3)
        _assert_points_equal(sweep_budgets(chain, b, l, power),
                             sweep_budgets_reference(chain, b, l, power))
        _assert_points_equal(
            sweep_budgets_freq(chain, b, l, power),
            sweep_budgets_freq_reference(chain, b, l, power))
        p_opt = herad(chain, b, l).period(chain)
        for p_max in (p_opt, 2.3 * p_opt):
            assert min_energy_under_period_freq(chain, b, l, p_max, power) \
                == min_energy_under_period_freq_reference(
                    chain, b, l, p_max, power)


def test_dvbs2_batch_dp_bit_identical():
    """Batched refinement DP == scalar DP on the real DVB-S2 tables:
    the exact bound vector a frontier refinement would issue, plus guard
    slots, answered in one shared budget volume."""
    from repro.configs.dvbs2 import RESOURCES, dvbs2_chain, platform_power

    for plat in RESOURCES:
        chain = dvbs2_chain(plat)
        power = platform_power(plat)
        b, l = (4, 3)
        periods = [pt.period
                   for pt in pareto_frontier(chain, b, l, power,
                                             refine=False)]
        p_maxes = periods + [math.inf, 0.0]
        batch = min_energy_under_period_freq_batch(
            chain, b, l, p_maxes, power)
        cand = CandidateTable.build(chain, power)
        for p_max, fast in zip(p_maxes, batch):
            assert fast == min_energy_under_period_freq(
                chain, b, l, p_max, power, candidates=cand)


def test_empty_and_infeasible_guards_match():
    chain = _chain(3, 5, 0.6)
    power = _model((0.5, 1.0))
    for args in ((chain, 0, 0, 10.0), (chain, 2, 2, math.inf),
                 (chain, 2, 2, 0.0), (chain, 2, 2, -1.0)):
        assert min_energy_under_period_freq(*args, power) == \
            min_energy_under_period_freq_reference(*args, power)
    assert sweep_budgets(chain, 0, 0, power) == \
        sweep_budgets_reference(chain, 0, 0, power) == []
    assert sweep_budgets_freq(chain, -1, 2, power) == []


def test_plane_merged_stages_matches_extraction():
    from repro.core.herad import extract_solution

    chain = _chain(11, 9, 0.6)
    b, l = 4, 3
    table = herad_table(chain, b, l)
    feasible, steps = plane_merged_stages(table, chain)
    for bb in range(b + 1):
        for ll in range(l + 1):
            sol = extract_solution(table, chain, bb, ll)
            if sol.is_empty():
                assert not feasible[bb, ll]
                continue
            recs = [
                (int(s[bb, ll]), int(e[bb, ll]), int(r[bb, ll]),
                 BIG if vb[bb, ll] else LITTLE)
                for s, e, r, vb, emit in steps if emit[bb, ll]]
            assert recs == [(st_.start, st_.end, st_.cores, st_.ctype)
                            for st_ in sol.stages]


# ------------------------------------------------------ lazy ParetoPoint
def test_pareto_point_lazy_extraction_and_equality():
    chain = _chain(5, 6, 0.6)
    pts = sweep_budgets(chain, 3, 2, DEFAULT_POWER)
    pt = pts[0]
    assert pt._solution is None            # nothing extracted yet
    calls = []
    lazy = ParetoPoint(1.0, 2.0, budget=(1, 0),
                       extract=lambda: calls.append(1) or pt.solution)
    assert lazy.solution is lazy.solution  # cached after first access
    assert calls == [1]
    # eq compares (period, energy, budget, solution)
    eager = ParetoPoint(pt.period, pt.energy, pt.solution, pt.budget)
    assert eager == pt
    assert ParetoPoint(pt.period + 1.0, pt.energy, pt.solution,
                       pt.budget) != pt
    with pytest.raises(ValueError):
        ParetoPoint(1.0, 2.0)              # neither solution nor extractor
    assert "lazy" not in repr(eager) and "budget" in repr(eager)


# --------------------------------------------- bisection power-cap query
def test_min_period_under_power_bisection_matches_linear_scan():
    chain = _chain(8, 8, 0.6)
    power = _model((0.5, 0.75, 1.0))
    front = dvfs_frontier(chain, 4, 4, power)
    assert len(front) >= 3
    watts = [pt.energy / pt.period for pt in front]
    caps = [watts[0] * 1.5, *watts, *(w - 1e-6 for w in watts),
            watts[-1] * 0.5, 0.0]
    for cap in caps:
        linear = next((pt for pt in front
                       if pt.period > 0
                       and pt.energy / pt.period <= cap + 1e-9), None)
        got = min_period_under_power(chain, 4, 4, power, cap,
                                     frontier=front)
        assert got == linear if linear is not None else got is None


def test_min_period_under_power_cap_epsilon_boundary():
    """Regression for the cap + 1e-9 admission edge: a point drawing
    exactly cap (or within the epsilon above it) is admitted; beyond the
    epsilon it is not."""
    sol = herad(_chain(2, 4, 1.0), 2, 0)
    mk = lambda p, e: ParetoPoint(p, e, sol, (2, 0))  # noqa: E731
    front = [mk(10.0, 100.0), mk(20.0, 100.0)]        # 10 W then 5 W
    # draw == cap exactly -> fastest point admitted
    assert min_period_under_power(None, 2, 0, DEFAULT_POWER, 10.0,
                                  frontier=front) is front[0]
    # within the documented epsilon above the cap: still admitted
    assert min_period_under_power(None, 2, 0, DEFAULT_POWER,
                                  10.0 - 5e-10, frontier=front) is front[0]
    # beyond the epsilon: falls through to the frugal point
    assert min_period_under_power(None, 2, 0, DEFAULT_POWER,
                                  10.0 - 1e-6, frontier=front) is front[1]
    # cap under every point's draw -> None
    assert min_period_under_power(None, 2, 0, DEFAULT_POWER, 4.0,
                                  frontier=front) is None


# ------------------------------------------------------- candidate table
def test_candidate_table_rescale_bit_identical_to_fresh_build():
    chain = _chain(4, 6, 0.5)
    power = _model((0.5, 0.75, 1.0))
    table = CandidateTable.build(chain, power, None)
    ratio = 1.37
    scaled = TaskChain(w_big=chain.w[BIG] * ratio,
                       w_little=chain.w[LITTLE] * ratio,
                       replicable=chain.replicable, names=chain.names)
    rescaled = table.rescale(scaled)
    fresh = CandidateTable.build(scaled, power, None)
    p_max = herad(scaled, 3, 2).period(scaled) * 1.4
    a = min_energy_under_period_freq(scaled, 3, 2, p_max, power,
                                     candidates=rescaled)
    b = min_energy_under_period_freq(scaled, 3, 2, p_max, power,
                                     candidates=fresh)
    c = min_energy_under_period_freq_reference(scaled, 3, 2, p_max, power)
    assert a == b == c
    with pytest.raises(ValueError):
        table.rescale(_chain(9, 7, 0.5))   # different structure


# ------------------------------------------------------ profile dedup
def test_dvfs_tables_dedupes_duplicate_profiles(monkeypatch):
    """Ladder specs with repeated levels fill and sweep each distinct
    (f_big, f_little) profile exactly once."""
    import repro.core.dvfs as dvfs_mod

    chain = _chain(6, 5, 0.8)
    calls = []
    real = dvfs_mod.herad_tables

    def counting(chains, b, l):
        calls.append(len(list(chains)))
        return real(chains, b, l)

    monkeypatch.setattr(dvfs_mod, "herad_tables", counting)
    tables = dvfs_mod.dvfs_tables(
        chain, 2, 2,
        {BIG: (0.5, 1.0, 1.0, 0.5), LITTLE: (0.75, 0.75, 1.0)})
    assert sorted(tables) == sorted(
        [(fb, fl) for fb in (0.5, 1.0) for fl in (0.75, 1.0)])
    assert calls == [4]                    # 2 x 2 distinct profiles, one pass
    # sweeping a deduplicated-model ladder yields one point per
    # (profile, sub-budget), not more
    power = _model((0.5, 0.5, 1.0))
    pts = sweep_budgets_freq(chain, 2, 2, power)
    per_cell = {}
    for pt in pts:
        per_cell[pt.budget] = per_cell.get(pt.budget, 0) + 1
    assert all(cnt == 4 for cnt in per_cell.values())  # 2x2 profiles
