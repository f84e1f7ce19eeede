"""The default deployment's cells offer the work they offered before the
harness took its hooks from a deployment module.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_golden.py

For each cell at its ``small`` cut and a fixed seed, a digest of: the data
(keys, payloads, rows); the schedule (due times, ranks, miss keys, update
due times and ranks); the deltas, their ``client.update`` arguments and the
update rows; ``keys_asked`` of every request; the request dicts and
consistency that ``send`` passes to ``client.query`` for the first 32
requests, driven through the window; and the calls ``warm_up`` makes. The
constants were recorded from the harness as it was before its hooks moved
into ``bench/deployments/default.py``, with the same digest.
"""
from __future__ import annotations

import hashlib
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
import window  # noqa: E402

SEED = 2**31 + 5
SECONDS = 2.0
N_REQUESTS = 32

GOLDEN = {
    "bili.ycsb-b.zipf99": {
        "data": "a937738de7170ed2", "schedule": "f850626abf90b0a4",
        "deltas": "fee155b06f55914c", "keys_asked": "0101bd65088cd30a",
        "requests": "b1fb80ca667616c0", "warm_up": "e0bf0b8f544574c1"},
    "bili.ycsb-b.above-knee": {
        "data": "a937738de7170ed2", "schedule": "6dbb295bcb0e45bc",
        "deltas": "21b3bed895170ccc", "keys_asked": "f6379964ecb04393",
        "requests": "41dc88ac6be9060a", "warm_up": "e0bf0b8f544574c1"},
    "din.attr.sessions": {
        "data": "114f59cb55b45183", "schedule": "5757b16aaf91848c",
        "deltas": "e3b0c44298fc1c14", "keys_asked": "2daf927d92b87a2b",
        "requests": "4b1bb69ddbd509cc", "warm_up": "e790e217ffed5ae3"},
}


class Digest:
    def __init__(self):
        self.h = hashlib.sha256()

    def add(self, label: str, value) -> None:
        a = np.ascontiguousarray(value)
        self.h.update(f"{label}|{a.dtype.str}|{a.shape}|".encode())
        self.h.update(a.tobytes())

    def hex(self) -> str:
        return self.h.hexdigest()[:16]


class Recorder:
    """A client (or engine) that records each query and answers every key
    as absent at version 1."""

    def __init__(self, value_bytes: int):
        self.calls, self.vb = [], value_bytes

    def query(self, tables, consistency=None, timeout=None, **_):
        self.calls.append(({t: np.array(k) for t, k in tables.items()},
                           consistency))
        n = len(next(iter(tables.values())))
        t = SimpleNamespace(found=np.zeros(n, bool),
                            payloads=np.zeros(n, np.uint64),
                            values=np.zeros((n, self.vb), np.uint8))
        return SimpleNamespace(version=1,
                               tables={name: t for name in tables})


def _calls(d: Digest, calls: list) -> None:
    for k, (request, consistency) in enumerate(calls):
        c = (None, None) if consistency is None else \
            (consistency.mode, consistency.version)
        d.add(f"call{k}/consistency/{c}", np.zeros(0))
        for t, keys in request.items():
            d.add(f"call{k}/{t}", keys)


def digests(cfg: dict, mix: dict, seconds: float, seed: int) -> dict:
    """The digest of each part of what the cell offers."""
    from repro.serve.scheduler import BatchPolicy
    dep = run.deployment(cfg)
    out = {}
    data = dep.generate(cfg, seed)
    d = Digest()
    d.add("keys", data.keys)
    for t, p in data.payloads.items():
        d.add(f"payload/{t}", p)
    for t, r in data.rows.items():
        d.add(f"rows/{t}", r)
    out["data"] = d.hex()

    sched = dep.schedule(mix, cfg, data, seconds, seed)
    n = len(sched.read_due)
    d = Digest()
    d.add("read_due", sched.read_due)
    for i in range(n):
        d.add(f"ranks{i}", sched.read_ranks[i])
        d.add(f"miss{i}", sched.miss_keys[i])
    d.add("update_due", sched.update_due)
    d.add("update_ranks", sched.update_ranks)
    d.add("interval", np.float64(sched.publish_interval_s))
    out["schedule"] = d.hex()

    deltas, update_rows = dep.make_deltas(cfg, sched, seed)
    d = Digest()
    for k, x in enumerate(deltas):
        d.add(f"d{k}/head", np.array([x.version, x.row_offset]))
        d.add(f"d{k}/due", np.float64(x.due_s))
        d.add(f"d{k}/records", x.records)
        d.add(f"d{k}/positions", x.positions)
        for t, p in x.payloads.items():
            d.add(f"d{k}/payload/{t}", p)
        for t, (keys, vals) in dep.upserts(data, x, update_rows).items():
            d.add(f"d{k}/upsert/{t}/keys", keys)
            d.add(f"d{k}/upsert/{t}/values", vals)
    for t, r in update_rows.items():
        d.add(f"update_rows/{t}", r)
    out["deltas"] = d.hex()

    d = Digest()
    d.add("keys_asked", np.array([sched.keys_asked(i) for i in range(n)],
                                 dtype=np.int64))
    out["keys_asked"] = d.hex()

    vb = int(cfg.get("value_bytes", 1))
    rec = Recorder(vb)
    win = window.Window(rec, sched, data, deltas,
                        lambda x: dep.upserts(data, x, update_rows),
                        dep.send, clients=1, sample={0, 1})
    win._read(-1e9, float("inf"))       # every request due long ago
    assert (win.outcome == window.OK).all(), win.errors[:3]
    d = Digest()
    _calls(d, rec.calls[:N_REQUESTS])
    out["requests"] = d.hex()

    engine, client = Recorder(vb), Recorder(vb)
    server = SimpleNamespace(policy=BatchPolicy(**cfg["server"]["policy"]))
    dep.warm_up(engine, server, client, cfg, mix, data,
                np.random.default_rng([seed, 15]))
    d = Digest()
    _calls(d, engine.calls + client.calls)
    out["warm_up"] = d.hex()
    return out


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_cell_offers_the_recorded_work(name):
    spec = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    _, cfg, mix = run.cell_files(spec, name)
    cfg, mix = run.deployment(cfg).small(cfg, mix)
    assert digests(cfg, mix, SECONDS, SEED) == GOLDEN[name]
