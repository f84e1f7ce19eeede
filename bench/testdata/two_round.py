"""A fixture deployment, in no cell of ``BENCHMARK.json``: it proves that a
deployment with its own key sets, request shape, build and reference comes
as new files only (``bench/test_two_round.py`` runs it).

Two tables over different key sets: ``node_nbr`` maps each node key to the
rank of one feature (a scalar payload); ``nbr_feat`` maps each feature key
to a row (an embedding table). A request reads in two dependent rounds:
round 1 asks ``node_nbr`` for the request's node keys under the window's
consistency; round 2 asks ``nbr_feat`` for the features of the nodes round
1 found, pinned to round 1's version. Each delta re-points some nodes and
rewrites the rows of as many features. An answer is wrong where either
round differs from the reference at round 1's version, or round 2 was
answered at another version.
"""
from __future__ import annotations

import dataclasses
import time
from types import SimpleNamespace

import numpy as np

import deploy
import faults
import reference
import traffic
import window
from deployments import default
from deployments.default import serve  # noqa: F401

NODES, FEATS = "node_nbr", "nbr_feat"


@dataclasses.dataclass
class Data:
    nodes: deploy.Dataset         # node keys, NODES payloads
    feats: deploy.Dataset         # feature keys, FEATS rows


@dataclasses.dataclass
class Delta:
    version: int
    due_s: float
    records: np.ndarray
    node: deploy.Delta            # positions over nodes, NODES payloads
    feat: deploy.Delta            # positions over features, FEATS rows


@dataclasses.dataclass
class Schedule(traffic.Schedule):
    def keys_asked(self, i: int) -> int:
        """Round 1's keys, and round 2's one feature per node found (every
        node key is held; the misses are not)."""
        ranks = self.read_ranks[i]
        return len(ranks) + int((ranks >= 0).sum())


def small(cfg: dict, mix: dict) -> tuple[dict, dict]:
    return dict(cfg), dict(mix)       # the fixture is small already


def _nodes(cfg: dict) -> dict:
    return dict(cfg, tables=deploy.tables(cfg, "scalar"))


def feature_keys(data: Data, ranks: np.ndarray) -> np.ndarray:
    return data.feats.keys[ranks % np.uint64(len(data.feats.keys))]


def generate(cfg: dict, seed: int) -> Data:
    rng = np.random.default_rng([seed, 21])
    n, width = int(cfg["n_feats"]), int(cfg["value_bytes"])
    keys = np.unique(rng.integers(0, 1 << 63, 2 * n, dtype=np.uint64))[:n]
    rows = rng.integers(0, 256, (n, width), dtype=np.uint8)
    keys.flags.writeable = rows.flags.writeable = False
    return Data(nodes=deploy.generate(_nodes(cfg), seed),
                feats=deploy.Dataset(keys=keys, payloads={},
                                     rows={FEATS: rows}))


def schedule(mix, cfg, data, seconds, seed) -> Schedule:
    sched = traffic.generate(mix, len(data.nodes.keys), seconds, seed,
                             [NODES])
    return Schedule(**vars(sched))


def make_deltas(cfg, sched, seed, first_version: int = 2):
    node_deltas, _ = deploy.make_deltas(_nodes(cfg), sched, seed,
                                        first_version)
    out, offset = [], 0
    for d in node_deltas:
        pos = np.unique(d.positions % int(cfg["n_feats"]))
        feat = deploy.Delta(d.version, d.due_s, d.records, pos, {}, offset)
        out.append(Delta(d.version, d.due_s, d.records, d, feat))
        offset += len(pos)
    rng = np.random.default_rng([seed, 22])
    rows = rng.integers(0, 256, (offset, int(cfg["value_bytes"])),
                        dtype=np.uint8)
    return out, {FEATS: rows}


def upserts(data: Data, delta: Delta, update_rows: dict) -> dict:
    return {**deploy.upserts(data.nodes, delta.node, {}),
            **deploy.upserts(data.feats, delta.feat, update_rows)}


def build_engine(cfg: dict, data: Data):
    from repro.core.engine import (EmbeddingTable, MultiTableEngine,
                                   ScalarTable)
    return MultiTableEngine(
        [ScalarTable(NODES, data.nodes.keys, data.nodes.payloads[NODES],
                     load_factor=cfg["load_factor"])],
        [EmbeddingTable(FEATS, data.feats.keys, data.feats.rows[FEATS],
                        hot_fraction=cfg["hot_fraction"])],
        max_shard_bytes=int(cfg["max_shard_bytes"]),
        retain=int(cfg["retain"]), version=1)


def warm_up(engine, server, client, cfg, mix, data, keys_rng) -> None:
    default.warm_up(engine, server, client, _nodes(cfg), mix, data.nodes,
                    keys_rng)


def send(client, sched, data, i, consistency, timeout, sampled):
    from repro.api.types import Consistency
    first = client.query({NODES: sched.keys_of(i, data.nodes.keys)},
                         consistency=consistency, timeout=timeout)
    found = np.array(first.tables[NODES].found)
    nbr = np.array(first.tables[NODES].payloads)
    second = client.query({FEATS: feature_keys(data, nbr[found])},
                          consistency=Consistency.pinned(first.version),
                          timeout=timeout)
    done = time.monotonic()
    t = second.tables[FEATS]
    rows = np.array(t.values) if sampled else None
    return window.Answer(done, first.version,
                         (found, nbr, second.version, np.array(t.found),
                          rows))


def compare(win, data, deltas, update_rows, cfg) -> dict:
    nodes = reference.Reference(data.nodes, [d.node for d in deltas])
    feats = reference.Reference(data.feats, [d.feat for d in deltas])
    ok = np.flatnonzero(win.outcome == window.OK)
    wrong = np.zeros(len(win.outcome), dtype=bool)
    for i in ok[np.argsort(win.version[ok], kind="stable")]:
        v = int(win.version[i])
        nodes.advance(v)
        feats.advance(v)
        found, nbr, v2, found2, rows2 = win.kept[i]
        want_f, want_p = nodes.scalar(
            NODES, win.sched.keys_of(int(i), data.nodes.keys))
        want_f2, want_rows = feats.rows(
            FEATS, feature_keys(data, want_p[want_f]), update_rows)
        wrong[i] = v2 != v or not (
            np.array_equal(found, want_f) and np.array_equal(nbr, want_p)
            and np.array_equal(found2, want_f2)
            and (rows2 is None or np.array_equal(rows2, want_rows)))
    return default.checks(win, wrong)


class _Control:
    """One ``reference.ControlClient`` per key set, each table routed to
    its own."""

    def __init__(self, parts: dict):
        self.parts = parts

    def query(self, tables, consistency=None, timeout=None):
        out, version = {}, None
        for name, keys in tables.items():
            resp = self.parts[name].query({name: keys})
            out[name], version = resp.tables[name], resp.version
        return SimpleNamespace(version=version, tables=out)

    def update(self, version, upserts=None):
        for part in self.parts.values():
            part.update(version)


def control_client(data, deltas, update_rows, cfg):
    kind = cfg["control"]
    return _Control({
        NODES: reference.ControlClient(data.nodes, [d.node for d in deltas],
                                       {}, kind),
        FEATS: reference.ControlClient(data.feats, [d.feat for d in deltas],
                                       update_rows, kind)})


def _off_version(monkeypatch):
    """A pinned query answered at the build before the one it names,
    wherever that build is retained: round 2 at another version than
    round 1, whenever round 1 read a version after the first."""
    from repro.core.engine import MultiTableEngine
    orig = MultiTableEngine._pin

    def pin(self, version, strict=False):
        if version is not None:
            ok, v, build = self.window.get(version - 1)
            if ok:
                return v, build
        return orig(self, version, strict)
    monkeypatch.setattr(MultiTableEngine, "_pin", pin)


FAULTS = default.FAULTS + [
    faults.Fault("drop_half_round2", faults.drop_half_of({FEATS}),
                 lambda cfg, mix: True),
    faults.Fault("round2_off_version", _off_version, faults.has_updates),
]
