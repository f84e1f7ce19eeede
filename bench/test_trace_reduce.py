"""The trace reduction and the peaks table.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_trace_reduce.py

``testdata/din_small.xplane.pb`` is a profiler trace recorded on one TPU v5e
chip by ``bench/run.py --trace 1`` on the ``din.attr.sessions`` cell cut to
20,000 keys, with a 0.3 s traced window. Its ``SYNC`` annotation ties the
trace's clock to the monotonic clock as ``SYNC_MONO`` below.
"""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(BENCH, "testdata", "din_small.xplane.pb")


def test_union_and_gaps():
    merged = tr.union([(5, 6), (0, 2), (1, 3), (3, 4)])
    assert merged == [[0, 4], [5, 6]]
    assert tr.clip(merged, 1, 5.5) == [[1, 4], [5, 5.5]]
    assert tr.gaps(merged, -1, 8) == [(-1, 0), (4, 5), (6, 8)]
    assert tr.gaps([], 0, 2) == [(0, 2)]


def test_gap_named_by_most_covering_host_span():
    spans = [("finish", 0.0, 1.0), ("begin", 0.9, 3.0), ("publish", 5, 6)]
    assert tr.name_gap((0.5, 2.0), spans) == "begin"
    assert tr.name_gap((3.5, 4.5), spans) == "host_idle"


def test_short_op_names():
    assert tr.short_name(
        "%fusion.83 = u32[2048]{0:T(1024)S(1)} fusion(u32[25000]{0:T(1024)} "
        "%get-tuple-element.477), kind=kCustom") == "%fusion.83 fusion u32[2048]"
    assert tr.short_name(
        "%while.24 = (s32[]{:T(128)}, pred[4096]{0:T(1024)(128)(4,1)}) "
        "while((s32[]{:T(128)}, pred[4096]) %tuple.73), condition=%c") == \
        "%while.24 while pred[4096]"
    assert tr.short_name("copy") == "copy"


def test_recorded_trace_reduces():
    devices, sync = tr.read_events(FIXTURE)
    assert sync is not None and len(devices) == 1
    ops = next(iter(devices.values()))
    lo = min(s for _, s, _ in ops)
    hi = max(e for _, _, e in ops)
    # the whole recorded span, on the monotonic clock of an arbitrary tie
    out = tr.reduce(FIXTURE, sync_mono=100.0, lo_mono=100.0 + lo - sync,
                    hi_mono=100.0 + hi - sync,
                    host_spans=[("finish", 100.0 + lo - sync,
                                 100.0 + hi - sync)])
    assert out["n_devices"] == 1
    assert 0 < out["busy_s"] <= out["window_s"]
    busy = sum(e - s for s, e in tr.union((s, e) for _, s, e in ops))
    assert out["busy_s"] == pytest.approx(busy)
    assert 0 < len(out["device_ops"]) <= tr.TOP
    times = [t for _, t in out["device_ops"]]
    assert times == sorted(times, reverse=True)
    gaps = [g for _, g in out["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert all(name == "finish" for name, _ in out["idle_gaps"])
    assert sum(gaps) <= out["window_s"] - out["busy_s"] + 1e-9


def test_peaks_by_device_kind():
    peaks = tr.load_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        tr.load_peaks("TPU v9 imaginary")
