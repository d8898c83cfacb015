"""One general traffic generator; each mix is a JSON file of parameters
under ``flamebench/traffic/``.

Sizes, users and arrivals come from fixed quantile grids, and the seed only
orders them (and draws the item ids and which id each user rank gets), so
every seed sends the same amount of work in another order.  A run's
requests are one sequence: the warm requests first, then the window's.
The sequence is cut into blocks of ``BLOCK`` consecutive requests; every
block holds one stratum of each grid (every ``n``-th value of the sorted
grid) and the seed orders requests only within a block, so any number of
requests sends the same users, slates and growth on every seed, up to the
one block in progress.  An open loop gives the window's requests due
times: exactly ``rate_per_s x seconds`` of them fall in the window on
every seed, their gaps the quantile grid scaled to span the window.

Parameters of a mix (all lengths in items):

``loop``            ``"open"`` (Poisson arrivals at ``rate_per_s``) or
                    ``"closed"`` (``concurrency`` requests in flight)
``users``           repeat-user population with ``zipf`` popularity, or 0:
                    every request is a new user with a fresh history
``history_extra``   ids past the model window in a fresh history
``grow_share``      share of each block's requests whose user's history grew
                    first, by ``grow_items`` = [lo, hi] ids appended past
                    the window, taken among the block's repeat visits
``slate``           ``{"median", "sigma", "min", "max"}``: a lognormal
                    slate size, clipped
``warm``            untimed requests sent before the window, closed loop at
                    ``warm_concurrency``: the first of the sequence, which
                    the window then continues
``drain_s``         open loop: seconds of arrivals scheduled past the window
                    so its last requests still meet load
``check``           requests the correctness check samples after the window
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
GRID = 4096             # size of the fixed quantile grids
BLOCK = 64              # requests per block of the grids


def load(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Request:
    history: np.ndarray
    candidates: np.ndarray
    user_id: int
    new_user: bool            # first time this run sends the user's history
    grew: bool                # the history grew since the user's last send
    due: float = 0.0          # open loop: seconds after the window opens


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def slate_sizes(mix: dict, n: int) -> np.ndarray:
    s = mix["slate"]
    nd = NormalDist(math.log(s["median"]), s["sigma"])
    m = np.exp([nd.inv_cdf(q) for q in _quantiles(n)])
    return np.clip(np.rint(m), s["min"], s["max"]).astype(np.int64)


def user_ranks(mix: dict, n: int) -> np.ndarray:
    w = np.arange(1, mix["users"] + 1, dtype=np.float64) ** -mix["zipf"]
    cdf = np.cumsum(w) / w.sum()
    return np.minimum(np.searchsorted(cdf, _quantiles(n)), mix["users"] - 1)


def growth(mix: dict, n: int) -> np.ndarray:
    """Items appended before each of ``n`` requests: ``grow_share`` of them
    grow by ``grow_items`` = [lo, hi] in turn, the rest by 0."""
    lo, hi = mix.get("grow_items", [0, 0])
    n_grow = int(round(mix.get("grow_share", 0.0) * n))
    g = np.zeros(n, np.int64)
    g[:n_grow] = lo + np.arange(n_grow) % (hi - lo + 1)
    return g


def arrival_gaps(rate: float, n: int) -> np.ndarray:
    return -np.log1p(-_quantiles(n)) / rate


def blocked(grid: np.ndarray, rng) -> np.ndarray:
    """``grid`` laid out in blocks of BLOCK: block ``b`` holds every
    ``n``-th value of the sorted grid from the ``b``-th, in seed order."""
    v = np.sort(grid)
    nb = len(v) // BLOCK
    return np.concatenate([rng.permutation(v[b::nb]) for b in range(nb)])


class Traffic:
    """The requests of one run: ``warm`` (a list) and the window's, either
    a list with due times (open loop) or :meth:`closed` by index."""

    def __init__(self, mix: dict, *, n_history: int, vocab: int, seed: int,
                 seconds: float):
        self.mix = mix
        self.n_history = n_history
        self.vocab = vocab
        self.seed = int(seed) % 2**64
        self.seconds = seconds
        self.rng = np.random.default_rng([self.seed, 0x7E57])
        self.loop = mix["loop"]
        self._hist: dict = {}
        self._seen: set = set()
        if mix["users"]:
            self._uid = self.rng.permutation(mix["users"])
        self._grid_m = blocked(slate_sizes(mix, GRID), self.rng)
        if mix["users"]:
            self._grid_u = blocked(user_ranks(mix, GRID), self.rng)
            self._grid_g = self._growth_plan()
        self._first = int(mix["warm"])
        self.warm: List[Request] = [self._request(j)
                                    for j in range(self._first)]
        self.window: List[Request] = []
        if self.loop == "open":
            # the window and the drain after it each get their own gaps, so
            # every seed sends the window the same requests at the same gaps
            for t0, span in ((0.0, seconds), (seconds, mix["drain_s"])):
                n = int(round(mix["rate_per_s"] * span))
                if n == 0:
                    continue
                gaps = arrival_gaps(mix["rate_per_s"], n)
                gaps = self.rng.permutation(gaps * (span / gaps.sum()))
                due = t0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
                for t in due:
                    r = self.closed(len(self.window))
                    r.due = float(t)
                    self.window.append(r)

    def _growth_plan(self) -> np.ndarray:
        """Items appended before each request of the first two passes over
        the user grid (the second and later passes visit only users seen
        before).  Each block grows the histories of ``grow_share x BLOCK``
        of its repeat visits, by ``grow_items`` in turn, chosen in seed
        order; the number of repeat visits in a block does not depend on
        the order inside it, so neither does the growth."""
        n = 2 * GRID
        seen: set = set()
        repeat = np.zeros(n, bool)
        for j in range(n):
            u = int(self._grid_u[j % GRID])
            repeat[j] = u in seen
            seen.add(u)
        amounts = [a for a in growth(self.mix, BLOCK) if a]
        g = np.zeros(n, np.int64)
        for b in range(0, n, BLOCK):
            idx = self.rng.permutation(np.flatnonzero(repeat[b:b + BLOCK]))
            k = min(len(idx), len(amounts))
            g[b + idx[:k]] = amounts[:k]
        return g

    def _fresh_history(self, rng) -> np.ndarray:
        n = self.n_history + int(self.mix.get("history_extra", 0))
        return rng.integers(0, self.vocab, n).astype(np.int32)

    def _slate(self, rng, m: int) -> np.ndarray:
        return rng.integers(0, self.vocab, m).astype(np.int32)

    def _request(self, j: int) -> Request:
        """The ``j``-th request of the run's sequence (call in order)."""
        rng = np.random.default_rng([self.seed, 0xC105ED, j])
        m = int(self._grid_m[j % GRID])
        if not self.mix["users"]:
            hist = self._fresh_history(rng)
            return Request(hist, self._slate(rng, m), 1_000_000_000 + j,
                           True, False)
        uid = int(self._uid[self._grid_u[j % GRID]])
        grow = int(self._grid_g[j if j < 2 * GRID else GRID + j % GRID])
        new = uid not in self._seen
        if new:
            self._hist[uid] = self._fresh_history(rng)
            self._seen.add(uid)
        grew = bool(grow) and not new
        if grew:
            self._hist[uid] = np.concatenate(
                [self._hist[uid], rng.integers(0, self.vocab, grow)
                 .astype(np.int32)])
        return Request(self._hist[uid], self._slate(rng, m), uid, new, grew)

    def closed(self, i: int) -> Request:
        """The window's ``i``-th request (call in order): the sequence goes
        on from the warm requests."""
        return self._request(self._first + i)
