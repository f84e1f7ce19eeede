"""The default deployment's data and build, from its configuration file
``bench/configs/<config>.json`` (hooks: ``bench/deployments/default.py``).

``generate`` makes the tables' data from ``--seed`` (the benchmark's data,
which both the program and the reference receive); ``make_deltas`` turns the
schedule's update records into the deltas the window publishes;
``build_engine`` and ``serve`` hand the data to the program under test
through its public constructors.

The file's ``tables`` list each table by ``name`` and ``kind``: a
``scalar`` table maps every item key to a payload drawn below
``payload_vocab``; an ``embedding`` table maps it to a ``value_bytes`` row
with ``hot_fraction`` of the rows, the most popular first, in the hot tier.
Item ``i`` is the ``i``-th most popular; its key is the ``i``-th smallest.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Dataset:
    keys: np.ndarray              # uint64 [n], sorted, all below 2**63
    payloads: dict                # scalar table -> uint64 [n]
    rows: dict                    # embedding table -> uint8 [n, value_bytes]


@dataclasses.dataclass
class Delta:
    """One publish: every table's new value for the items at ``positions``
    (the last update of each item among the delta's records)."""
    version: int
    due_s: float                  # from the window's start
    records: np.ndarray           # indices of the schedule's update records
    positions: np.ndarray         # item ranks, unique, sorted
    payloads: dict                # scalar table -> uint64 [len(positions)]
    row_offset: int               # first row of this delta in update_rows


def _readonly(a: np.ndarray) -> np.ndarray:
    a.flags.writeable = False
    return a


def _raw_rows(rng: np.random.Generator, n: int, width: int) -> np.ndarray:
    words = -(-n * width // 8)
    return rng.bit_generator.random_raw(words).view(np.uint8)[
        :n * width].reshape(n, width)


def tables(cfg: dict, kind: str) -> list:
    return [t for t in cfg["tables"] if t["kind"] == kind]


def generate(cfg: dict, seed: int) -> Dataset:
    """Keys, payloads and rows from ``seed``; every array read-only, so the
    program cannot change what the reference compares against."""
    rng = np.random.default_rng([seed, 1])
    n = int(cfg["n_items"])
    keys = np.unique(rng.integers(0, 1 << 63, n + n // 64 + 64,
                                  dtype=np.uint64))
    while len(keys) < n:        # pragma: no cover: needs ~n/64 collisions
        keys = np.unique(np.concatenate(
            [keys, rng.integers(0, 1 << 63, n, dtype=np.uint64)]))
    keys = keys[:n]
    payloads = {t["name"]: _readonly(rng.integers(
        0, int(t["payload_vocab"]), n, dtype=np.uint64))
        for t in tables(cfg, "scalar")}
    rows = {t["name"]: _readonly(_raw_rows(rng, n, int(cfg["value_bytes"])))
            for t in tables(cfg, "embedding")}
    return Dataset(keys=_readonly(keys), payloads=payloads, rows=rows)


def make_deltas(cfg: dict, sched, seed: int, first_version: int = 2
                ) -> tuple[list, dict]:
    """The window's deltas, versions ``first_version``, ... in due order,
    and each embedding table's update rows (all deltas' rows, one after
    another)."""
    rng = np.random.default_rng([seed, 13])
    deltas, offset = [], 0
    for k, idx in enumerate(sched.deltas()):
        if not len(idx):
            continue
        positions = np.unique(sched.update_ranks[idx])
        deltas.append(Delta(
            version=len(deltas) + first_version,
            due_s=(k + 1) * sched.publish_interval_s, records=idx,
            positions=positions,
            payloads={t["name"]: rng.integers(0, int(t["payload_vocab"]),
                                              len(positions),
                                              dtype=np.uint64)
                      for t in tables(cfg, "scalar")},
            row_offset=offset))
        offset += len(positions)
    update_rows = {t["name"]: _readonly(_raw_rows(
        rng, offset, int(cfg["value_bytes"])))
        for t in tables(cfg, "embedding")}
    return deltas, update_rows


def upserts(data: Dataset, delta: Delta, update_rows: dict) -> dict:
    """The ``client.update`` argument for one delta."""
    keys = data.keys[delta.positions]
    out = {name: (keys, p) for name, p in delta.payloads.items()}
    end = delta.row_offset + len(delta.positions)
    for name, rows in update_rows.items():
        out[name] = (keys, rows[delta.row_offset:end])
    return out


def build_engine(cfg: dict, data: Dataset):
    from repro.core.engine import (EmbeddingTable, MultiTableEngine,
                                   ScalarTable)
    scalars = [ScalarTable(t["name"], data.keys, data.payloads[t["name"]],
                           load_factor=cfg["load_factor"])
               for t in tables(cfg, "scalar")]
    embeddings = [EmbeddingTable(t["name"], data.keys, data.rows[t["name"]],
                                 hot_fraction=cfg["hot_fraction"])
                  for t in tables(cfg, "embedding")]
    return MultiTableEngine(scalars, embeddings,
                            max_shard_bytes=int(cfg["max_shard_bytes"]),
                            retain=int(cfg["retain"]), version=1)


def serve(cfg: dict, engine, tracer=None):
    """``(server, client)`` over ``engine`` with the configuration's
    batching policy."""
    from repro.api import FeatureClient
    from repro.serve.scheduler import BatchPolicy
    from repro.serve.server import QueryServer
    srv = cfg["server"]
    server = QueryServer(engine, BatchPolicy(**srv["policy"]),
                         workers=int(srv["workers"]),
                         pipeline_depth=int(srv["pipeline_depth"]),
                         tracer=tracer)
    return server, FeatureClient(server)
