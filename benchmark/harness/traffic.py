"""The one general traffic generator. A traffic mix is a data file of
parameters (the cell's file); this reads it.

Every seed gets the same set of sizes and arrivals in another order. A
round of `round` requests holds one draw of each distribution (prompt
lengths, output lengths and, in an open loop, the gaps between arrivals at
`rate_per_s`), made by the cell's fixed `shape_seed`; the run's seed only
orders it and draws the token ids. So a window of any seed does the same
work, and a round of an open loop lasts `round / rate_per_s` seconds.

`draw` says how a round is drawn:

- `grid` (the default): the mid-quantile grid of each distribution, a
  stratified sample, paired at random. Right where a round is short beside
  the window and the lengths alone are the work: a closed loop, and only
  a closed loop.
- `iid`: `round` independent draws of each length, and the arrivals of a
  Poisson process at `rate_per_s` that has `round` arrivals in
  `round / rate_per_s` seconds (their times independent and uniform over
  the round, as a Poisson process's are once their number is known): the
  bursts and lulls of real independent users, which a grid of gaps would
  iron out. Right for an open loop near its capacity, where the queue's
  tail is made by the bursts.

`order` says what the run's seed does with a round:

- `shuffle` (the default): every round in an order of its own.
- `rotate`: the round keeps the order it was drawn in, which for `iid`
  arrivals is part of the draw, and comes round again and again; the seed
  chooses the request at which the run starts. A cell whose round lasts as
  long as the window then replays one drawn trace from a seeded point.

A closed loop starts each client inside its first request's life (the
first outputs shortened): those points are a stratified grid, tied by
`shape_seed` to the place (round, slot) of the request they shorten, so
the first requests are the same set for every seed as well.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def quantile_grid(dist: dict, n: int) -> np.ndarray:
    """n whole numbers at the mid-quantiles of `dist`, clipped."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        x = np.array([dist["median"] * math.exp(
            dist["sigma"] * _NORMAL.inv_cdf(q)) for q in u])
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif dist["dist"] == "fixed":
        x = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist.get("min", 1),
                   dist.get("max", np.inf)).astype(np.int64)


def draw_lengths(dist: dict, n: int, rng) -> np.ndarray:
    """n independent whole numbers from `dist`, clipped."""
    if dist["dist"] == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    elif dist["dist"] == "uniform":
        x = rng.uniform(dist["min"], dist["max"], n)
    elif dist["dist"] == "fixed":
        x = np.full(n, dist["value"], float)
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist.get("min", 1),
                   dist.get("max", np.inf)).astype(np.int64)


def poisson_gaps(rate: float, n: int, rng) -> np.ndarray:
    """The n gaps of a Poisson process at `rate` that has n arrivals in a
    round of n/rate seconds: arrival times independent and uniform over
    the round; the first gap runs from the round before's last arrival."""
    length = n / rate
    t = np.sort(rng.uniform(0.0, length, n))
    return np.diff(t, prepend=t[-1] - length)


class Traffic:
    """Request i of a run: (prompt ids, output length, gap before it)."""

    def __init__(self, cell: dict, seed: int, vocab: int):
        n = self.n = int(cell["round"])
        fixed = np.random.default_rng(int(cell["shape_seed"]))
        rate = cell.get("rate_per_s")
        draw = cell.get("draw", "grid")
        if draw == "grid":
            self.prompts = quantile_grid(cell["prompt_len"], n)
            self.outputs = quantile_grid(cell["output_len"], n)[
                fixed.permutation(n)]
            if rate:
                # a grid of gaps irons out the bursts that make a queue's
                # tail, whatever it is called
                raise ValueError("an open loop's arrivals are drawn: "
                                 "`draw` has to be `iid`")
            self.gaps = np.zeros(n)
        elif draw == "iid":
            self.prompts = draw_lengths(cell["prompt_len"], n, fixed)
            self.outputs = draw_lengths(cell["output_len"], n, fixed)
            self.gaps = (poisson_gaps(rate, n, fixed) if rate
                         else np.zeros(n))
        else:
            raise ValueError(f"unknown draw {draw!r}")
        clients = int(cell.get("clients") or 0)
        self.phases = ((np.arange(clients) + 0.5) / clients)[
            fixed.permutation(clients)]
        self.seed, self.vocab = int(seed), int(vocab)
        self._orders = {}
        self.order = cell.get("order", "shuffle")
        if self.order not in ("shuffle", "rotate"):
            raise ValueError(f"unknown order {self.order!r}")
        self.first = (int(self._rng(4).integers(n))
                      if self.order == "rotate" else 0)

    def _rng(self, *tag):
        return np.random.default_rng(
            [self.seed & 0xFFFFFFFF, self.seed >> 32, *tag])

    def _slot(self, i: int) -> int:
        if self.order == "rotate":
            return (i + self.first) % self.n
        r = i // self.n
        if r not in self._orders:
            self._orders[r] = self._rng(1, r).permutation(self.n)
        return int(self._orders[r][i % self.n])

    def request(self, i: int):
        j = self._slot(i)
        ids = self._rng(2, i).integers(1, self.vocab, int(self.prompts[j]),
                                       dtype=np.int64)
        return ids.astype(np.int32), int(self.outputs[j]), float(self.gaps[j])

    def phase(self, i: int) -> float:
        """The share of request i's outputs that is still to come when
        its client starts with it, in (0, 1): request i is one of the
        `clients` first requests of a closed loop."""
        return float(self.phases[((i // self.n) * self.n + self._slot(i))
                                 % len(self.phases)])
