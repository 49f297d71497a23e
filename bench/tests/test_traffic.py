from collections import Counter

import numpy as np
import pytest

from bench.harness import spec, traffic


def _mix(name):
    mix = spec.load_json(spec.BENCH_DIR / "traffic" / f"{name}.json")
    mix.setdefault("rate_per_s", 0.6)
    return mix


def _sizes(arrivals):
    return Counter((len(a.prompt), a.max_new_tokens) for a in arrivals)


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_same_seed_same_schedule(mix):
    kw = dict(vocab=50304, max_len=1024, slots=8)
    a = traffic.generate(_mix(mix), 2**33 + 5, 30, **kw)
    b = traffic.generate(_mix(mix), 2**33 + 5, 30, **kw)
    assert [(x.due_s, x.prompt, x.max_new_tokens) for x in a] == \
        [(x.due_s, x.prompt, x.max_new_tokens) for x in b]
    c = traffic.generate(_mix(mix), 2**33 + 6, 30, **kw)
    assert [x.prompt for x in a] != [x.prompt for x in c]


@pytest.mark.parametrize("mix", ["chat", "batch"])
def test_seeds_share_sizes_and_gaps(mix):
    """Seeds reorder one multiset of sizes (and of gaps): same work."""
    kw = dict(vocab=50304, max_len=1024, slots=8)
    a = traffic.generate(_mix(mix), 1, 30, **kw)
    b = traffic.generate(_mix(mix), -(2**40), 30, **kw)
    assert _sizes(a) == _sizes(b)
    end = _mix(mix).get("lead_s", 0) + 30

    def win(arr):
        due = [x.due_s for x in arr if x.in_window]
        return sorted(np.diff(due + [end]))
    np.testing.assert_allclose(win(a), win(b), rtol=1e-9, atol=1e-12)


def test_fixed_order_same_schedule_other_tokens():
    """With ``fixed_order`` seeds share the order of sizes and the due
    times; only the prompt tokens differ."""
    mix = {**_mix("chat"), "fixed_order": True}
    kw = dict(vocab=50304, max_len=1024, slots=8)
    a = traffic.generate(mix, 2**33 + 5, 51, **kw)
    b = traffic.generate(mix, 17, 51, **kw)
    assert [(x.due_s, len(x.prompt), x.max_new_tokens, x.in_window)
            for x in a] == [(x.due_s, len(x.prompt), x.max_new_tokens,
                             x.in_window) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in b]
    shuffled = traffic.generate(_mix("chat"), 17, 51, **kw)
    assert [x.due_s for x in shuffled] != [x.due_s for x in b]


def test_chat_clipping_and_window():
    mix = _mix("chat")
    arr = traffic.generate(mix, 7, 51, vocab=50304, max_len=1024, slots=8)
    lead = mix["lead_s"]
    p = [len(a.prompt) for a in arr]
    o = [a.max_new_tokens for a in arr]
    assert min(p) >= 8 and max(p) <= 768
    assert min(o) >= 4 and max(o) <= 256
    assert all(len(a.prompt) + a.max_new_tokens - 1 <= 1024 for a in arr)
    inside = [a for a in arr if a.in_window]
    assert len(inside) == round(0.6 * 51)
    assert all(lead <= a.due_s < lead + 51 for a in inside)
    assert all(a.due_s < lead for a in arr if not a.in_window)
    assert all(0 <= t < 50304 for a in arr for t in a.prompt)


def test_quantiles_clip_and_cache_fit():
    q = traffic.quantiles({"dist": "lognormal", "median": 96, "sigma": 1.0,
                           "min": 8, "max": 768}, 1000)
    assert q.min() == 8 and q.max() == 768 and np.median(q) == 96
    u = traffic.quantiles({"dist": "uniform", "min": 256, "max": 768}, 512)
    assert u.min() >= 256 and u.max() <= 768
    mix = {"arrival": "backlog", "backlog_per_slot": 4,
           "prompt_len": {"dist": "uniform", "min": 40, "max": 60},
           "output_len": {"dist": "uniform", "min": 30, "max": 40}}
    arr = traffic.generate(mix, 3, 1, vocab=100, max_len=64, slots=2)
    assert all(len(a.prompt) + a.max_new_tokens - 1 <= 64 for a in arr)
    with pytest.raises(ValueError):
        traffic.generate({**mix, "prompt_len": {"dist": "uniform", "min": 70,
                                               "max": 80}},
                         3, 1, vocab=100, max_len=64, slots=2)
