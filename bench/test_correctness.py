"""The comparison that decides ``correct``, driven on the CPU at a small size.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_correctness.py

Each cell's whole run (data, build, warm-up, window, comparison) goes
through ``run.run_cell``, skipping only the harness's look for a chip. A
sound run must come out correct; the control (the reference in the
program's place with the configuration's ``control`` guarantee broken)
and each fault planted in the program underneath must come out not
correct. The cells run on one chip, so no exchange between chips exists
to leave out.
"""
from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402

CELLS = ["bili.ycsb-b.zipf99", "bili.ycsb-b.above-knee", "din.attr.sessions"]


def small(name: str):
    """The cell's own configuration and mix at a size the CPU runs in
    seconds."""
    spec = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, cfg, mix = run.cell_files(spec, name)
    cfg["n_items"] = 20_000
    mix["fresh_keys"] = max(mix["fresh_keys"] // 16, 8)
    mix["session_keys"] //= 4
    mix["sessions_per_s"] = min(mix["sessions_per_s"], 20.0)
    return cell, cfg, mix, run.cell_metrics(spec, name, False)


def run_small(name: str, seed: int = 2**31 + 5, control: bool = False):
    cell, cfg, mix, metrics = small(name)
    return run.run_cell(cell, cfg, mix, metrics, seed=seed, seconds=2.0,
                        trace=False, control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run_small(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["wrong_answers"]["value"] == 0
    assert set(res["metrics"]) == {m["name"] for m in small(name)[3]}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    res = run_small(name, control=True)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def _drop_half(monkeypatch):
    """Half of each batch left out: the engine answers the second half of
    every table's keys as absent."""
    from repro.core.engine import MultiTableEngine
    orig = MultiTableEngine._finish

    def finish(self, inflight):
        out = orig(self, inflight)
        for t in out.tables.values():
            half = len(t.found) // 2
            t.found[half:] = False
            if t.payloads is not None:
                t.payloads[half:] = 0
            if t.values is not None:
                t.values[half:] = 0
        return out
    monkeypatch.setattr(MultiTableEngine, "_finish", finish)


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
    version over the previous build, the delta never applied."""
    from repro.core.engine import MultiTableEngine

    def publish_delta(self, version, upserts=None, deletes=None):
        with self._publish_lock:
            _, _, prev = self.window.get(None)
            self.window.publish(version, prev)
    monkeypatch.setattr(MultiTableEngine, "publish_delta", publish_delta)


FAULTS = [
    ("bili.ycsb-b.zipf99", _drop_half), ("din.attr.sessions", _drop_half),
    ("bili.ycsb-b.zipf99", _alter_probe), ("din.attr.sessions", _alter_probe),
    ("bili.ycsb-b.zipf99", _alter_rows),
    ("bili.ycsb-b.zipf99", _unchanged_state),
    ("bili.ycsb-b.above-knee", _drop_half),
    ("bili.ycsb-b.above-knee", _alter_rows),
    ("bili.ycsb-b.above-knee", _unchanged_state),
]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.__name__[1:]}" for n, f in FAULTS])
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    res = run_small(name)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_no_tpu_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[-1], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""


def test_every_cell_and_metric_has_its_files():
    spec = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        cell, cfg, mix = run.cell_files(spec, w["name"])
        assert cfg["name"] == cell["config"]
        for trace in (False, True):
            for m in run.cell_metrics(spec, w["name"], trace):
                assert os.path.exists(run.metric_reader(m["name"])), \
                    m["name"]


def test_split_metric_takes_its_quantitys_reader():
    reader = os.path.join(BENCH, "metrics", "engine.finish_ms.py")
    assert run.metric_reader("engine.finish_ms.throughput") == reader
    assert run.metric_reader("engine.finish_ms") == reader
