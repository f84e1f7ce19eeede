"""Seeded traffic for one cell, generated from a mix file ``bench/traffic/<mix>.json``.

A mix is open-loop sessions at a fixed rate. Each session sends
``requests_per_session`` requests (a ``[lo, hi]`` range) spaced by
exponential think times. Each request asks every table of the
configuration for the same keys: ``session_keys`` drawn once per session
and ``fresh_keys`` drawn per request. Keys are item ranks drawn zipfian with
exponent ``zipf`` over the configuration's items; a ``miss_share`` of them
is replaced by keys that no table holds. An ``update_share`` above 0 adds
YCSB-style record updates: for each record read, ``share / (1 - share)``
records are updated, due uniformly over the window and published as one
delta every ``publish_interval_s``.

Fixed work per seed: the session gaps, session lengths, think times and
update due times are drawn once from the mix's ``shape_seed``; ``--seed``
only reorders them and draws the keys. Every session that starts in the
window sends all its requests, even those its think times carry past the
window's end, so every seed offers the same number of sessions, requests,
keys and updates. The zipf law is the one
``repro.traffic.loadgen.ZipfianPopularity`` implements, copied here so that
the yardstick cannot move with the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

MISS_LO = np.uint64(1 << 63)     # generated table keys all lie below 2**63


class Zipf:
    """Bounded zipf law over ranks ``0..vocab-1``: p(r) ∝ (r + 1)**-skew,
    sampled by inverse CDF."""

    def __init__(self, vocab: int, skew: float):
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -float(skew)
        self.cdf = np.cumsum(w / w.sum())
        self.cdf[-1] = 1.0

    def sample(self, rng: np.random.Generator, size) -> np.ndarray:
        return np.searchsorted(self.cdf, rng.random(size), side="right")


@dataclasses.dataclass
class Schedule:
    """What one run offers. Times are seconds from the window's start."""
    read_due: np.ndarray          # float64 [R], sorted
    read_ranks: list              # R int64 arrays of item ranks, -1 = miss
    miss_keys: list               # R uint64 arrays, the keys for the -1s
    update_due: np.ndarray        # float64 [U], sorted
    update_ranks: np.ndarray      # int64 [U]
    publish_interval_s: float
    tables: list                  # the tables every request asks

    def keys_asked(self, i: int) -> int:
        """Keys request ``i`` asks for, over every table and round."""
        return len(self.read_ranks[i]) * len(self.tables)

    def keys_of(self, i: int, item_keys: np.ndarray) -> np.ndarray:
        """Request ``i``'s keys, in the order they are sent."""
        ranks = self.read_ranks[i]
        keys = item_keys[np.maximum(ranks, 0)]
        keys[ranks < 0] = self.miss_keys[i]
        return keys

    def deltas(self) -> list:
        """Per publish ``k`` (due at ``(k + 1) * interval``): the indices of
        the updates due in ``(k * interval, (k + 1) * interval]``."""
        if not len(self.update_due):
            return []
        slot = np.ceil(self.update_due / self.publish_interval_s) - 1
        slot = np.maximum(slot, 0).astype(np.int64)
        n = int(slot.max()) + 1
        order = np.argsort(slot, kind="stable")
        bounds = np.searchsorted(slot[order], np.arange(n + 1))
        return [order[bounds[k]:bounds[k + 1]] for k in range(n)]


def _shaped(shape_rng, order_rng, draw, n):
    """``n`` values drawn once from the shape stream, in the order stream's
    permutation."""
    vals = draw(shape_rng, n)
    return vals[order_rng.permutation(n)]


def generate(mix: dict, n_items: int, seconds: float, seed: int,
             tables: list) -> Schedule:
    shape = np.random.default_rng(int(mix["shape_seed"]))
    order = np.random.default_rng([seed, 11])
    keys_rng = np.random.default_rng([seed, 12])
    zipf = Zipf(n_items, mix["zipf"])

    rate = float(mix["sessions_per_s"])
    n_sess = max(int(round(rate * seconds)), 1)
    gaps = _shaped(shape, order,
                   lambda r, n: r.exponential(1.0 / rate, n + 1), n_sess + 1)
    starts = np.cumsum(gaps)[:-1] * (seconds / gaps.sum())
    lo, hi = mix["requests_per_session"]
    n_req = _shaped(shape, order, lambda r, n: r.integers(lo, hi + 1, n),
                    n_sess)
    think = _shaped(shape, order,
                    lambda r, n: r.exponential(mix["think_time_s"], n)
                    if mix["think_time_s"] > 0 else np.zeros(n),
                    int(n_req.sum()))

    due, ranks = [], []
    n_sk, n_fk = int(mix["session_keys"]), int(mix["fresh_keys"])
    j = 0
    for s in range(n_sess):
        sk = zipf.sample(keys_rng, n_sk)
        t = starts[s]
        for r in range(int(n_req[s])):
            if r:
                t += think[j]
            j += 1
            due.append(t)
            ranks.append(np.concatenate([sk, zipf.sample(keys_rng, n_fk)]))
    order_idx = np.argsort(np.asarray(due), kind="stable")
    due = np.asarray(due, dtype=np.float64)[order_idx]
    ranks = [ranks[i] for i in order_idx]
    misses = []
    for r in ranks:
        miss = keys_rng.random(len(r)) < mix["miss_share"]
        r[miss] = -1
        misses.append(keys_rng.integers(MISS_LO, np.iinfo(np.uint64).max,
                                        int(miss.sum()), dtype=np.uint64))

    share = float(mix.get("update_share", 0.0))
    n_upd = int(round(len(due) * (n_sk + n_fk) * share / (1.0 - share)))
    upd_due = np.sort(shape.uniform(0.0, seconds, n_upd))
    upd_ranks = zipf.sample(keys_rng, n_upd)
    return Schedule(read_due=due, read_ranks=ranks, miss_keys=misses,
                    update_due=upd_due, update_ranks=upd_ranks,
                    publish_interval_s=float(mix.get("publish_interval_s",
                                                     1.0)),
                    tables=list(tables))
