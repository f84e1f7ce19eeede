"""Every micro-batch's and every delta publish's own spans, recorded from
inside the program whenever a ``Tracer`` is attached to the ``QueryServer``
(obs/trace.py's batch taxonomy, core/spans.py), the profiler mirror of
those spans, and the device program's stable names.

The server here samples no request (rate 0), so each span comes from the
batch and publish traces alone.
"""
from __future__ import annotations

import sys
import threading

import numpy as np
import pytest

from repro.core import spans as spans_mod
from repro.obs import trace as trace_mod
from repro.obs.trace import Tracer

N = 600
VB = 16

BEGIN_STEPS = {"begin.stage", "begin.upload", "begin.dispatch"}
FINISH_STEPS = {"finish.device_wait", "finish.fetch", "finish.scalar_gather",
                "finish.row_gather", "store.probe", "store.hot_gather",
                "store.cold_read", "store.admit"}
PUBLISH_STEPS = {"publish.index", "publish.upload", "publish.retrace",
                 "publish.store_clone", "publish.store_upsert",
                 "publish.install"}


def _engine():
    from repro.core.engine import EmbeddingTable, MultiTableEngine, \
        ScalarTable
    keys = np.arange(1, N + 1, dtype=np.uint64)
    rows = np.random.default_rng(0).integers(0, 256, (N, VB),
                                             dtype=np.uint8)
    eng = MultiTableEngine(
        [ScalarTable("attr", keys, keys * 3)],
        [EmbeddingTable("feats", keys, rows, hot_fraction=0.25)])
    return eng, keys, rows


def _serve(tracer, n_queries=24, publish=False):
    """Drive a server over a fresh engine from eight client threads, with a
    delta published midway when ``publish``; returns the server's stats
    snapshot after close."""
    from repro.api import FeatureClient
    from repro.serve.scheduler import BatchPolicy
    from repro.serve.server import QueryServer
    eng, keys, rows = _engine()
    server = QueryServer(eng, BatchPolicy(max_wait_s=0.002), tracer=tracer)
    client = FeatureClient(server)
    rng = np.random.default_rng(1)
    batches = [rng.choice(keys, 64) for _ in range(n_queries)]
    wrong = []

    def worker(mine):
        for ids in mine:
            res = client.query({"attr": ids, "feats": ids})
            want = np.where((ids <= 8) & (res.version >= 2), ids * 5,
                            ids * 3)
            if not np.array_equal(res.tables["attr"].payloads, want):
                wrong.append(ids)

    threads = [threading.Thread(target=worker, args=(batches[i::8],))
               for i in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # interleave the recording threads
    try:
        for t in threads:
            t.start()
        if publish:
            client.update(2, upserts={
                "attr": (keys[:8], keys[:8] * 5),
                "feats": (keys[:8], rows[:8] ^ np.uint8(1))})
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    snap = server.stats_snapshot()
    server.close()
    assert not wrong
    return snap


def _all_spans(tracer):
    return [s for tid in tracer.trace_ids() for s in tracer.peek(tid)]


def _contained(child, parent):
    return parent.t0 <= child.t0 and child.t1 <= parent.t1


@pytest.fixture(scope="module")
def traced():
    tracer = Tracer(sample_rate=0.0, capacity=1 << 16)
    snap = _serve(tracer, publish=True)
    return tracer, snap


def test_every_batch_records_one_begin_and_one_finish(traced):
    tracer, snap = traced
    assert snap.batches > 0
    for name in ("coalesce", "version_pin", "begin", "finish_wait",
                 "finish"):
        per_trace = [sum(s.name == name for s in tracer.peek(tid))
                     for tid in tracer.trace_ids()
                     if any(s.name == "begin" for s in tracer.peek(tid))]
        assert per_trace and set(per_trace) == {1}, name
        assert len(per_trace) == snap.batches, name


def test_begin_carries_the_batch_tags(traced):
    tracer, snap = traced
    begins = [s for s in _all_spans(tracer) if s.name == "begin"]
    assert sum(s.tags["n_requests"] for s in begins) == snap.completed
    assert len({s.tags["batch_id"] for s in begins}) == len(begins)
    assert sum(s.tags["keys_requested"] for s in begins) == \
        snap.keys_requested
    assert sum(s.tags["keys_deviceside"] for s in begins) == \
        snap.keys_deviceside


def test_child_spans_lie_inside_their_parents(traced):
    tracer, _ = traced
    seen = set()
    for tid in tracer.trace_ids():
        spans = tracer.peek(tid)
        by_id = {s.span_id: s for s in spans}
        for s in spans:
            if s.parent_id is None:
                continue
            parent = by_id[s.parent_id]
            assert _contained(s, parent), (s, parent)
            seen.add((parent.name, s.name))
            if s.name in BEGIN_STEPS:
                assert parent.name == "begin"
            elif s.name in FINISH_STEPS:
                assert parent.name == "finish"
            else:
                assert s.name in PUBLISH_STEPS and \
                    parent.name == "publish_delta", s
    children = {c for _, c in seen}
    assert BEGIN_STEPS | {"finish.device_wait", "finish.fetch",
                          "finish.scalar_gather", "finish.row_gather",
                          "store.probe", "store.hot_gather"} <= children
    # a quarter of the rows start hot: some batch reads the cold file and
    # admits what it read
    assert {"store.cold_read", "store.admit"} <= children


def test_batch_spans_follow_each_other(traced):
    tracer, _ = traced
    for tid in tracer.trace_ids():
        by_name = {s.name: s for s in tracer.peek(tid)}
        if "begin" not in by_name:
            continue
        chain = [by_name[n] for n in ("coalesce", "version_pin", "begin",
                                      "finish_wait", "finish")]
        for a, b in zip(chain, chain[1:]):
            assert a.t1 == b.t0, (a, b)


def test_publish_delta_records_its_steps(traced):
    tracer, _ = traced
    traces = [tracer.peek(tid) for tid in tracer.trace_ids()
              if any(s.name == "publish_delta" for s in tracer.peek(tid))]
    assert len(traces) == 1
    spans = traces[0]
    root = next(s for s in spans if s.name == "publish_delta")
    assert root.parent_id is None and root.tags == {"version": 2}
    assert {s.name for s in spans} == PUBLISH_STEPS | {"publish_delta"}
    for s in spans:
        if s is not root:
            assert s.parent_id == root.span_id and _contained(s, root)
    retrace = next(s for s in spans if s.name == "publish.retrace")
    assert retrace.tags["rebuilt"] in (0, 1)
    index = [s for s in spans if s.name == "publish.index"]
    assert [s.tags["shard"] for s in index] == [0]


def test_no_tracer_records_nothing(monkeypatch):
    """With no tracer no span, sink or step is ever made: their
    construction raises here, and serving and publishing still work."""
    _refuse_tracing_objects(monkeypatch)
    snap = _serve(None, n_queries=8, publish=True)
    assert snap.completed == 8 and snap.failed == 0


def _refuse_tracing_objects(monkeypatch):
    def refuse(*_a, **_k):
        raise AssertionError("tracing object built")
    monkeypatch.setattr(trace_mod.Span, "__init__", refuse)
    monkeypatch.setattr(trace_mod.SpanSink, "__init__", refuse)
    monkeypatch.setattr(spans_mod._Step, "__init__", refuse)


def _store_server(tracer):
    from repro.api.backends import StoreBackend
    from repro.core.hybrid_store import HybridKVStore
    from repro.serve.scheduler import BatchPolicy
    from repro.serve.server import QueryServer
    keys = np.arange(1, N + 1, dtype=np.uint64)
    rows = np.random.default_rng(0).integers(0, 256, (N, VB),
                                             dtype=np.uint8)
    backend = StoreBackend({"feats": HybridKVStore(keys, rows,
                                                   hot_fraction=0.5)})
    return QueryServer(backend, BatchPolicy(max_wait_s=0.001),
                       tracer=tracer), keys


def test_rate_zero_on_a_store_backend_records_nothing(monkeypatch):
    """A backend that times no steps of its own (a fabric shard's store)
    traces a batch only for a rider that carries a trace context: at rate
    0 no span, sink or step is made."""
    from repro.api.types import QueryRequest
    tracer = Tracer(sample_rate=0.0)
    server, keys = _store_server(tracer)
    _refuse_tracing_objects(monkeypatch)
    with server:
        for i in range(6):
            resp = server.query(QueryRequest(
                tables={"feats": keys[i * 32:(i + 1) * 32]}))
            assert resp.trace is None and resp.tables["feats"].found.all()
    assert tracer.trace_ids() == []


def test_traced_rider_on_a_store_backend_traces_its_batch():
    """The same server records the batch of a request that arrives with a
    trace context, and the request's chain names that batch's trace."""
    from repro.api.types import QueryRequest
    tracer = Tracer(sample_rate=0.0)
    server, keys = _store_server(tracer)
    with server:
        resp = server.query(QueryRequest(tables={"feats": keys[:32]},
                                         trace={"trace_id": "edge"}))
    chain = {d["name"]: d for d in resp.trace}
    batch = {s.name for s in tracer.peek(chain["serve"]["tags"]
                                         ["batch_trace"])}
    assert batch == {"coalesce", "version_pin", "begin", "finish_wait",
                     "finish"}
    assert set(tracer.trace_ids()) == {"edge",
                                       chain["serve"]["tags"]["batch_trace"]}


def test_untraced_step_is_one_shared_object():
    assert spans_mod.step(None, "a") is spans_mod.step(None, "b")
    with spans_mod.step(None, "a") as st:
        assert st.sink is None


def test_sampled_chain_repeats_its_batch_spans():
    """A sampled request's chain names its batch's trace, and its batch
    spans have the batch trace's own times."""
    from repro.api.types import QueryRequest
    from repro.serve.scheduler import BatchPolicy
    from repro.serve.server import QueryServer
    eng, keys, _ = _engine()
    tracer = Tracer(sample_rate=1.0)
    with QueryServer(eng, BatchPolicy(max_wait_s=0.001),
                     tracer=tracer) as server:
        resp = server.query(QueryRequest(tables={"attr": keys[:32]}))
    chain = {d["name"]: d for d in resp.trace}
    assert "device" not in chain
    batch = {s.name: s for s in tracer.peek(chain["serve"]["tags"]
                                            ["batch_trace"])}
    for name in ("coalesce", "version_pin", "begin", "finish_wait",
                 "finish"):
        assert (chain[name]["t0"], chain[name]["t1"]) == \
            (batch[name].t0, batch[name].t1), name
    assert batch["begin"].tags["batch_id"] == chain["serve"]["tags"][
        "batch_id"]


def test_steps_enter_profiler_annotations(monkeypatch):
    """Each step is also a ``jax.profiler.TraceAnnotation`` of its name, so
    a profiler trace shows it on the host thread."""
    import jax
    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tracer = Tracer(sample_rate=0.0)
    _serve(tracer, n_queries=4)
    recorded = {s.name for s in _all_spans(tracer)}
    assert {"coalesce", "begin", "finish", "begin.stage",
            "finish.device_wait", "store.probe"} <= set(entered)
    # spans that cover no code of one thread are recorded, not annotated
    assert set(entered) == recorded - {"version_pin", "finish_wait"}


def test_probe_program_has_stable_names():
    """The fused probe's module is ``jit_probe_fused`` and each table's
    probe sits in the scope ``probe/<table>``, whatever the compile."""
    import jax.numpy as jnp
    from repro.core.engine import MultiTableEngine, ScalarTable
    keys = np.arange(1, 201, dtype=np.uint64)
    eng = MultiTableEngine([ScalarTable("item_cate", keys, keys),
                            ScalarTable("item_shop", keys, keys * 7)])
    _, _, build = eng.window.get(None)
    q = [jnp.zeros(8, jnp.uint32)] * 2
    text = build._fused_fns[0].lower(build.shard_arrays[0], q, q).as_text(
        debug_info=True)
    assert "module @jit_probe_fused" in text
    assert "probe/item_cate" in text and "probe/item_shop" in text
