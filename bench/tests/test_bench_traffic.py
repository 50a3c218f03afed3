"""The traffic generator: seeded, the same work for every seed (one
replayed schedule, the seed's own token ids), and inside each cell's
length ranges."""
from __future__ import annotations

import itertools
import json

import numpy as np
import pytest

from benchlib import spec
from benchlib import traffic as T

MIXES = sorted(p.stem for p in (spec.BENCH / "traffic").glob("*.json"))


def mix(name):
    return json.loads((spec.BENCH / "traffic" / f"{name}.json").read_text())


def schedule(m, seed, seconds=30.0):
    if m["loop"] == "open":
        return T.open_loop(m, seed, seconds, 32000)
    return list(itertools.islice(T.closed_loop_specs(m, seed, 32000),
                                 4 * m["clients"]))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a, b = schedule(mix(name), 2**33 + 5), schedule(mix(name), 2**33 + 5)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)
        assert x.due_s == y.due_s or (np.isnan(x.due_s) and np.isnan(y.due_s))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work_in_another_order(name):
    """Every seed replays the same lengths and gaps in the same order;
    only the token ids are the seed's own."""
    a, b = schedule(mix(name), 1), schedule(mix(name), 2**33 + 1)
    la = [(len(r.prompt), r.max_new_tokens) for r in a]
    lb = [(len(r.prompt), r.max_new_tokens) for r in b]
    assert la == lb
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.due_s for r in a] == [r.due_s for r in b] or \
        mix(name)["loop"] == "closed"


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_the_mix_ranges(name):
    m = mix(name)
    p_lo, p_hi, o_lo, o_hi = T.length_ranges(m)
    reqs = schedule(m, 7)
    assert all(p_lo <= len(r.prompt) <= p_hi for r in reqs)
    assert all(o_lo <= r.max_new_tokens <= o_hi for r in reqs)
    assert all(len(r.prompt) + r.max_new_tokens <= m["max_len"]
               for r in reqs)
    assert all(1 <= int(t) < 32000 for r in reqs for t in r.prompt)


def test_open_loop_is_due_inside_the_window():
    m = dict(mix("chat"), rate_per_s=5.0)
    reqs = T.open_loop(m, 3, 20.0, 32000)
    assert len(reqs) == 100
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and 0.0 < due[0] and due[-1] < 20.0


def test_quantiles_follow_the_distribution():
    d = {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 1,
         "max": 10**9}
    v = T.quantile_lengths(d, 1001)
    assert np.median(v) == 256
    u = T.quantile_lengths({"dist": "uniform", "min": 8, "max": 32}, 2500)
    assert u.min() == 8 and u.max() == 32
    assert np.bincount(u)[8:].min() >= 99
