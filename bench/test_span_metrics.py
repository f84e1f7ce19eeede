"""The readers of the program's own spans (``bench/span_stats.py`` and the
metrics that use it), fed a synthetic ``run.spans`` with known answers.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_span_metrics.py
"""
from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
import span_stats  # noqa: E402

PER_BATCH = {
    "begin.stage_ms": "begin.stage", "begin.upload_ms": "begin.upload",
    "begin.dispatch_ms": "begin.dispatch",
    "finish.device_wait_ms": "finish.device_wait",
    "finish.fetch_ms": "finish.fetch",
    "finish.scalar_gather_ms": "finish.scalar_gather",
    "server.finish_wait_ms": "finish_wait",
    "store.probe_ms": "store.probe",
    "store.hot_gather_ms": "store.hot_gather",
    "store.cold_read_ms": "store.cold_read", "store.admit_ms": "store.admit",
}
PER_PUBLISH = {
    "publish.index_ms": "publish.index",
    "publish.upload_ms": "publish.upload",
    "publish.store_clone_ms": "publish.store_clone",
    "publish.store_upsert_ms": "publish.store_upsert",
}


def synthetic_run() -> SimpleNamespace:
    """Two batches in the window: every step lasts 1 ms in the first and
    3 ms in the second, so each per-batch mean is 2 ms. A sampled
    request's chain repeats the second batch's server spans, and a warm-up
    batch falls before the window's start. Three publishes: the index
    takes 4, 6 and 3 + 5 ms (two shards), so its mean is 6 ms; every other
    step takes 5 ms."""
    spans = []
    for t, d in ((10.0, 1e-3), (20.0, 3e-3)):
        spans += [("begin", t, t + 0.1), ("finish", t + 0.2, t + 0.3)]
        spans += [(name, t + 0.1, t + 0.1 + d)
                  for name in PER_BATCH.values()]
    spans += [s for s in spans if s[1] >= 20.0
              and s[0] in ("begin", "finish_wait", "finish")]
    spans += [("finish", 1.0, 1.5), ("begin.stage", 0.5, 0.9)]
    for t, index in ((30.0, [4e-3]), (31.0, [6e-3]), (32.0, [3e-3, 5e-3])):
        spans.append(("publish_delta", t, t + 0.5))
        spans += [("publish.index", t + 0.1 + k * 1e-4,
                   t + 0.1 + k * 1e-4 + d) for k, d in enumerate(index)]
        spans += [(name, t + 0.2, t + 0.205) for name in PER_PUBLISH.values()
                  if name != "publish.index"]
    return SimpleNamespace(t0=5.0, spans=spans)


@pytest.mark.parametrize("metric", sorted(PER_BATCH))
def test_per_batch_readers(metric):
    assert run.read_metric(metric, synthetic_run()) == pytest.approx(2.0)


@pytest.mark.parametrize("metric,want", [
    ("publish.index_ms", 6.0), ("publish.upload_ms", 5.0),
    ("publish.store_clone_ms", 5.0), ("publish.store_upsert_ms", 5.0)])
def test_per_publish_readers(metric, want):
    assert run.read_metric(metric, synthetic_run()) == pytest.approx(want)


def test_repeated_spans_count_once():
    r = synthetic_run()
    assert len(span_stats.distinct(r, "finish")) == 2
    assert len(span_stats.distinct(r, "publish_delta")) == 3


@pytest.mark.parametrize("metric", sorted(PER_BATCH) + sorted(PER_PUBLISH))
def test_program_without_the_spans_reads_nothing(metric):
    """A program that records only the server's sampled chains (no engine
    or store steps, no publish traces) gives no reading and no error."""
    r = SimpleNamespace(t0=0.0, spans=[("begin", 1.0, 1.1),
                                       ("finish", 1.2, 1.3)])
    if metric == "server.finish_wait_ms":
        r.spans.append(("device", 1.1, 1.2))
    assert run.read_metric(metric, r) is None
    assert run.read_metric(metric, SimpleNamespace(t0=0.0, spans=None)) \
        is None


def test_every_new_metric_has_a_reader_and_an_entry():
    spec = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    entries = {m["name"]: m for m in spec["per_layer"]}
    for metric in list(PER_BATCH) + list(PER_PUBLISH):
        assert run.metric_reader(metric) == os.path.join(
            BENCH, "metrics", metric + ".py")
        m = entries[metric]
        assert (m["source"], m["unit"], m["better"]) == \
            ("program_span", "ms", "lower")
