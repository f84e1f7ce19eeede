"""Plain answers for the generated tables at every published version.

Sorted-key search over the benchmark's own generated data (no hashing, no
tiers, no device), with each version's upserts laid over the base in
version order. It imports nothing of the program under test.

``ControlClient`` puts this reference in the program's place with one
guarantee of the configuration broken, as the configuration's ``control``
names it. Its answers must come out as not correct.
"""
from __future__ import annotations

import threading
from types import SimpleNamespace

import numpy as np


class Reference:
    """Answers at one version at a time; ``advance(v)`` moves forward."""

    def __init__(self, data, deltas):
        self.keys = data.keys                       # sorted, read-only
        self.base_rows = data.rows                  # table -> uint8 [n, vb]
        self.payloads = {t: p.copy() for t, p in data.payloads.items()}
        self.row_src = {t: np.full(len(self.keys), -1, dtype=np.int64)
                        for t in data.rows}
        self.deltas = {d.version: d for d in deltas}
        self.version = 1

    def advance(self, version: int) -> None:
        """Lay every delta up to ``version`` over the base, in order."""
        if version < self.version:
            raise ValueError(f"reference is at {self.version}, asked for "
                             f"the earlier {version}")
        for v in range(self.version + 1, version + 1):
            d = self.deltas.get(v)
            if d is None:
                raise ValueError(f"no version {v} was ever published")
            for t, p in d.payloads.items():
                self.payloads[t][d.positions] = p
            for t in self.row_src:
                self.row_src[t][d.positions] = d.row_offset + \
                    np.arange(len(d.positions))
        self.version = version

    def find(self, keys: np.ndarray):
        pos = np.minimum(np.searchsorted(self.keys, keys),
                         len(self.keys) - 1)
        return self.keys[pos] == keys, pos

    def scalar(self, table: str, keys: np.ndarray):
        found, pos = self.find(keys)
        return found, np.where(found, self.payloads[table][pos],
                               np.uint64(0))

    def rows(self, table: str, keys: np.ndarray, update_rows: dict):
        found, pos = self.find(keys)
        src = self.row_src[table][pos]
        out = self.base_rows[table][pos]
        fresh = found & (src >= 0)
        if fresh.any():
            out[fresh] = update_rows[table][src[fresh]]
        out[~found] = 0
        return found, out


class ControlClient:
    """The reference serving the window in the program's place, with the
    configuration's ``control`` guarantee broken:

    ``stale_version``      an update is acknowledged and its version
                           reported, but the rows served are those of the
                           version before it (read-your-writes broken);
    ``absent_as_default``  a key the table does not hold reads as found,
                           with payload 0 (exact membership broken).
    """

    def __init__(self, data, deltas, update_rows, kind: str):
        if kind not in ("stale_version", "absent_as_default"):
            raise ValueError(f"unknown control {kind!r}")
        self.kind = kind
        self.ref = Reference(data, deltas)
        self.update_rows = update_rows
        self.scalar_tables = set(data.payloads)
        self.latest = 1
        self._lock = threading.Lock()

    def query(self, tables: dict, consistency=None, timeout=None):
        with self._lock:
            out = {}
            for name, keys in tables.items():
                if name in self.scalar_tables:
                    found, p = self.ref.scalar(name, keys)
                    if self.kind == "absent_as_default":
                        found = np.ones_like(found)
                    out[name] = SimpleNamespace(found=found, payloads=p,
                                                values=None)
                else:
                    found, rows = self.ref.rows(name, keys, self.update_rows)
                    out[name] = SimpleNamespace(found=found, payloads=None,
                                                values=rows)
            return SimpleNamespace(version=self.latest, tables=out)

    def update(self, version: int, upserts=None) -> None:
        with self._lock:
            if self.kind == "stale_version":
                self.ref.advance(self.latest)
            else:
                self.ref.advance(version)
            self.latest = version
