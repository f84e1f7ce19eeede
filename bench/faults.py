"""Faults planted in the program underneath a run, each of which the
comparison that decides ``correct`` must catch.

A ``Fault`` patches the program through pytest's ``monkeypatch`` and says
to which cells it applies. ``GENERIC`` holds the faults that apply wherever
a configuration's tables have the part they alter: every table's answers
(``drop_half``), the device probe of scalar tables (``alter_probe``), the
hybrid store's rows of embedding tables (``alter_rows``). A deployment
lists its own further faults in its ``FAULTS``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np


@dataclasses.dataclass(frozen=True)
class Fault:
    name: str
    plant: Callable                 # (monkeypatch) -> None
    applies: Callable               # (cfg, mix) -> bool


def has_kind(kind: str):
    return lambda cfg, mix: any(t["kind"] == kind for t in cfg["tables"])


def drop_half_of(tables=None):
    """Half of each batch left out: the engine answers the second half of
    each table's keys (of ``tables``, or of every table) as absent."""
    def plant(monkeypatch):
        from repro.core.engine import MultiTableEngine
        orig = MultiTableEngine._finish

        def finish(self, inflight):
            out = orig(self, inflight)
            for name, t in out.tables.items():
                if tables is not None and name not in tables:
                    continue
                half = len(t.found) // 2
                t.found[half:] = False
                if t.payloads is not None:
                    t.payloads[half:] = 0
                if t.values is not None:
                    t.values[half:] = 0
            return out
        monkeypatch.setattr(MultiTableEngine, "_finish", finish)
    return plant


def _alter_probe(monkeypatch):
    """An answer altered where it is produced: the device probe flips the
    low bit of every payload it returns."""
    from repro.core import lookup as lk
    orig = lk.lookup

    def lookup(*args, **kw):
        found, p_hi, p_lo = orig(*args, **kw)
        return found, p_hi, p_lo ^ np.uint32(1)
    monkeypatch.setattr(lk, "lookup", lookup)


def _alter_rows(monkeypatch):
    """An answer altered where it is produced: the hybrid store's gather
    flips one byte of every row it returns."""
    from repro.core.hybrid_store import HybridKVStore
    orig = HybridKVStore.get_batch

    def get_batch(self, keys, admit=True):
        found, rows = orig(self, keys, admit)
        rows[:, 0] ^= 1
        return found, rows
    monkeypatch.setattr(HybridKVStore, "get_batch", get_batch)


def _unchanged_state(monkeypatch):
    """A step that returns its state unchanged: a delta publish installs its
    version over the previous build, the delta never applied. It takes the
    ``spans`` that ``EngineBackend.apply_update`` passes."""
    from repro.core.engine import MultiTableEngine

    def publish_delta(self, version, upserts=None, deletes=None, spans=None):
        with self._publish_lock:
            _, _, prev = self.window.get(None)
            self.window.publish(version, prev)
    monkeypatch.setattr(MultiTableEngine, "publish_delta", publish_delta)


def has_updates(cfg, mix) -> bool:
    return float(mix.get("update_share", 0.0)) > 0


GENERIC = [
    Fault("drop_half", drop_half_of(), lambda cfg, mix: True),
    Fault("alter_probe", _alter_probe, has_kind("scalar")),
    Fault("alter_rows", _alter_rows, has_kind("embedding")),
]
UNCHANGED_STATE = Fault("unchanged_state", _unchanged_state, has_updates)
