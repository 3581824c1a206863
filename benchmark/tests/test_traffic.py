"""The general generator: every seed gets the same sizes and arrivals in
another order."""
import json
import os

import numpy as np
import pytest

from benchmark.harness.traffic import Traffic, quantile_grid

WORKLOADS = os.path.join(os.path.dirname(__file__), "..", "workloads")
SERVE_CELLS = [f[:-5] for f in sorted(os.listdir(WORKLOADS))
               if json.load(open(os.path.join(WORKLOADS, f)))["kind"]
               == "serve"]


def test_quantile_grid_is_clipped_and_centred():
    d = {"dist": "lognormal", "median": 256, "sigma": 0.8, "min": 32,
         "max": 1024}
    g = quantile_grid(d, 128)
    assert g.min() >= 32 and g.max() <= 1024
    assert abs(np.median(g) - 256) <= 4
    assert (np.diff(g) >= 0).all()


def test_an_open_loop_may_not_take_its_arrivals_from_a_grid():
    spec = json.load(open(os.path.join(
        WORKLOADS, "cgpt590m-serve-prefill-open.json")))
    with pytest.raises(ValueError):
        Traffic(dict(spec, draw="grid"), 1, 50257)


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_each_round_holds_the_same_sizes_whatever_the_seed(cell):
    spec = json.load(open(os.path.join(WORKLOADS, cell + ".json")))
    n = spec["round"]
    rounds = []
    for seed in (1, 2**31 + 12345):
        t = Traffic(spec, seed, 50257)
        for r in range(2):
            reqs = [t.request(i) for i in range(r * n, (r + 1) * n)]
            rounds.append(sorted((len(p), o, round(g, 9))
                                 for p, o, g in reqs))
            assert all(len(p) + o <= spec["engine"]["max_seq_len"]
                       for p, o, _ in reqs)
            assert all(1 <= p.min() and p.max() < 50257 for p, _, _ in reqs)
    assert all(r == rounds[0] for r in rounds)
    a = [Traffic(spec, 1, 50257).request(i)[1] for i in range(n)]
    b = [Traffic(spec, 2, 50257).request(i)[1] for i in range(n)]
    assert a != b                       # ... in another order


def test_same_seed_same_requests():
    spec = json.load(open(os.path.join(WORKLOADS, SERVE_CELLS[0] + ".json")))
    a, b = Traffic(spec, 5, 1000), Traffic(spec, 5, 1000)
    for i in (0, 3, 200):
        pa, oa, ga = a.request(i)
        pb, ob, gb = b.request(i)
        assert (pa == pb).all() and (oa, ga) == (ob, gb)
    assert 0.0 < a.phase(3) < 1.0 and a.phase(3) == b.phase(3)


def test_a_closed_loop_starts_with_the_same_requests_whatever_the_seed():
    """Each client's first request, and the point of its life at which
    the client starts, are one fixed set in another order."""
    spec = json.load(open(os.path.join(
        WORKLOADS, "cgpt590m-serve-decode-closed128.json")))
    firsts = []
    for seed in (1, 2**31 + 12345):
        t = Traffic(spec, seed, 50257)
        firsts.append([(len(t.request(c)[0]), t.request(c)[1], t.phase(c))
                       for c in range(spec["clients"])])
    assert firsts[0] != firsts[1]
    assert sorted(firsts[0]) == sorted(firsts[1])
    phases = sorted(p for _, _, p in firsts[0])
    assert phases == pytest.approx(
        (np.arange(spec["clients"]) + 0.5) / spec["clients"])


def test_a_rotated_trace_is_one_draw_and_the_seed_chooses_where_it_starts():
    spec = json.load(open(os.path.join(
        WORKLOADS, "cgpt590m-serve-prefill-open.json")))
    n = spec["round"]
    assert (spec["draw"], spec["order"]) == ("iid", "rotate")

    def shapes(t, first, count=n):
        return [(len(p), o, round(g, 9)) for p, o, g in
                (t.request(i) for i in range(first, first + count))]

    a, b = Traffic(spec, 1, 50257), Traffic(spec, 2**31 + 12345, 50257)
    assert a.first != b.first
    shift = (a.first - b.first) % n
    assert shapes(a, 0) == shapes(b, shift)         # same trace, rotated
    assert shapes(a, 0) == shapes(a, n)             # and it comes round
    assert (a.request(0)[0] != b.request(shift)[0]).any()       # other ids
    # one trace lasts the window, at the cell's rate
    assert a.gaps.sum() == pytest.approx(n / spec["rate_per_s"])
    assert (a.gaps > 0).all()


def test_poisson_gaps_are_bursty_where_a_grid_is_not():
    """Independent arrival times leave some fifths of a second empty and
    put three or more into others; the mid-quantile grid of gaps, paired
    at random, has the same mean and a thinner tail."""
    from benchmark.harness.traffic import poisson_gaps

    rng = np.random.default_rng(3)
    gaps = poisson_gaps(4.8, 240, rng)
    assert gaps.sum() == pytest.approx(50.0)
    per_bin = np.histogram(np.cumsum(gaps) % 50.0,
                           bins=np.arange(0, 50.2, 0.2))[0]
    assert per_bin.max() >= 3 and (per_bin == 0).sum() > 80
    assert per_bin.var() == pytest.approx(per_bin.mean(), rel=0.35)
