"""Observability: metrics registry, Prometheus exposition, silo bridges,
the metrics-catalog checker, and cross-process request tracing.

The fabric tests spawn real shard processes (same regime as
tests/test_fabric.py — kept small, CI boxes are thin).  The launcher
scrape test is ``slow``: it subprocess-runs ``repro.launch.fabric
--smoke --metrics-port`` and scrapes ``/metrics`` mid-run — the
acceptance path for serving live metrics out of the process tree.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from repro.obs import exporter
from repro.obs.bridge import (CLASS_STATS_METRICS, FABRIC_METRICS,
                              SERVER_STATS_METRICS, TIER_STATS_METRICS,
                              WINDOW_METRICS, bridge_router,
                              bridge_server_stats, bridge_tier_stats,
                              bridge_version_window)
from repro.obs.metrics import Counter, Gauge, Histogram, Registry
from repro.obs.trace import Span, Tracer, sort_timeline

from conftest import subprocess_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
class TestRegistry:
    def test_counter_inc_and_set_total(self):
        reg = Registry()
        c = reg.counter("repro_x_total", "x")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5
        c.set_total(10)
        assert c.value() == 10
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_labels_must_match_declaration(self):
        reg = Registry()
        c = reg.counter("repro_l_total", "x", labelnames=("qos",))
        c.inc(qos="RANKING")
        with pytest.raises(ValueError):
            c.inc()                        # missing label
        with pytest.raises(ValueError):
            c.inc(qos="A", extra="B")      # unknown label
        with pytest.raises(ValueError):
            reg.counter("repro_l_total", "x", labelnames=("other",))
        with pytest.raises(ValueError):
            reg.gauge("repro_l_total", "x", labelnames=("qos",))

    def test_histogram_buckets_sorted_and_deduped(self):
        reg = Registry()
        with pytest.raises(ValueError):
            reg.histogram("repro_h", "h", buckets=(1.0, 1.0))
        h = reg.histogram("repro_h", "h", buckets=(2.0, 0.5))
        h.observe(1.0)
        sample_les = [dict(lp)["le"] for suffix, lp, _ in h.samples()
                      if suffix == "_bucket"]
        assert sample_les == ["0.5", "2", "+Inf"]

    def test_collectors_run_outside_lock(self):
        # a collector that itself creates metrics must not deadlock
        reg = Registry()

        def collect():
            reg.gauge("repro_from_collector", "g").set(1.0)

        reg.register_collector(collect)
        names = [m.name for m in reg.collect()]
        assert "repro_from_collector" in names

    def test_concurrent_writers_exact_totals(self):
        reg = Registry()
        c = reg.counter("repro_stress_total", "s", labelnames=("w",))
        h = reg.histogram("repro_stress_lat", "s", buckets=(0.5,))
        n_threads, n_iter = 8, 2000
        barrier = threading.Barrier(n_threads)

        def worker(w: int):
            barrier.wait()
            for i in range(n_iter):
                c.inc(w=str(w % 2))
                h.observe(0.25 if i % 2 else 0.75)

        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        total = n_threads * n_iter
        assert c.value(w="0") + c.value(w="1") == total
        flat = {f"{s}{dict(lp).get('le', '')}": v
                for s, lp, v in h.samples()}
        assert flat["_count"] == total
        assert flat["_bucket0.5"] == total // 2


# ---------------------------------------------------------------------------
# exposition round-trip
# ---------------------------------------------------------------------------
class TestExposition:
    def test_label_escaping_round_trips(self):
        reg = Registry()
        nasty = 'a\\b"c\nd'
        reg.counter("repro_esc_total", "e", labelnames=("k",)) \
            .inc(3, k=nasty)
        text = exporter.render_text(reg)
        parsed = exporter.parse_text(text)
        assert parsed[("repro_esc_total", (("k", nasty),))] == 3.0

    def test_histogram_buckets_cumulative_and_consistent(self):
        reg = Registry()
        h = reg.histogram("repro_lat_seconds", "lat",
                          buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        parsed = exporter.parse_text(exporter.render_text(reg))

        def bucket(le):
            return parsed[("repro_lat_seconds_bucket", (("le", le),))]

        counts = [bucket("0.1"), bucket("1"), bucket("10"), bucket("+Inf")]
        assert counts == sorted(counts)          # monotone
        assert counts == [1, 3, 4, 5]            # cumulative
        assert counts[-1] == parsed[("repro_lat_seconds_count", ())]
        assert parsed[("repro_lat_seconds_sum", ())] == \
            pytest.approx(56.05)

    def test_special_values_render(self):
        reg = Registry()
        reg.gauge("repro_nan", "n").set(float("nan"))
        reg.gauge("repro_inf", "i").set(float("inf"))
        parsed = exporter.parse_text(exporter.render_text(reg))
        assert math.isnan(parsed[("repro_nan", ())])
        assert parsed[("repro_inf", ())] == float("inf")

    def test_http_endpoint_serves_and_404s(self):
        reg = Registry()
        reg.counter("repro_served_total", "s").inc(7)
        with exporter.MetricsServer(reg) as srv:
            body = urllib.request.urlopen(srv.url, timeout=5).read()
            assert b"repro_served_total 7" in body
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/nope", timeout=5)

    def test_snapshot_flattens_for_records(self):
        reg = Registry()
        reg.counter("repro_s_total", "s", labelnames=("q",)).inc(2, q="A")
        flat = exporter.snapshot(reg)
        assert flat['repro_s_total{q="A"}'] == 2.0
        json.dumps(flat)                         # JSON-able by contract


# ---------------------------------------------------------------------------
# bridges + the metrics-catalog checker
# ---------------------------------------------------------------------------
class TestBridges:
    def test_server_and_class_stats_bridge(self):
        from repro.api.types import QoSClass
        from repro.serve.scheduler import BatchPolicy, ServerStats
        stats = ServerStats(BatchPolicy())
        stats.on_submit(QoSClass.RANKING)
        stats.on_complete(0.010, True, QoSClass.RANKING)
        reg = Registry()
        bridge_server_stats(reg, stats.snapshot, labels={"shard": "s0"})
        parsed = exporter.parse_text(exporter.render_text(reg))
        assert parsed[("repro_server_requests_submitted_total",
                       (("shard", "s0"),))] == 1.0
        assert parsed[("repro_server_class_requests_completed_total",
                       (("qos", "RANKING"), ("shard", "s0")))] == 1.0

    def test_tier_bridge_with_derived_ratios(self):
        tiers = {"emb": {"lookups": 100, "hot_hits": 80, "cold_misses": 15,
                         "garbage_bytes": 30, "cold_file_bytes": 120}}
        reg = Registry()
        bridge_tier_stats(reg, lambda: tiers)
        parsed = exporter.parse_text(exporter.render_text(reg))
        key = (("table", "emb"),)
        assert parsed[("repro_tier_hot_hits_total", key)] == 80.0
        assert parsed[("repro_tier_hot_hit_rate", key)] == 0.8
        assert parsed[("repro_tier_garbage_fraction", key)] == 0.25

    def test_version_window_bridge(self):
        from repro.core.versioning import VersionWindow
        w = VersionWindow(retain=1)
        w.publish(1, "a")
        w.publish(2, "b")                        # evicts v1
        w.get(2)
        w.get(1)                                 # NACK
        reg = Registry()
        bridge_version_window(reg, w)
        parsed = exporter.parse_text(exporter.render_text(reg))
        assert parsed[("repro_version_pin_served_total", ())] == 1.0
        assert parsed[("repro_version_pin_nacks_total", ())] == 1.0
        assert parsed[("repro_version_window_publishes_total", ())] == 2.0
        assert parsed[("repro_version_window_evictions_total", ())] == 1.0

    def test_catalog_names_unique_and_wellformed(self):
        import re
        all_names = []
        for mapping in (SERVER_STATS_METRICS, CLASS_STATS_METRICS,
                        FABRIC_METRICS, TIER_STATS_METRICS, WINDOW_METRICS):
            all_names.extend(mapping.values())
        assert len(all_names) == len(set(all_names))
        for name in all_names:
            assert re.match(r"^repro_[a-z][a-z0-9_]*$", name), name

    def test_checker_clean_on_this_repo(self):
        from tools.analyze import metrics as checker
        assert checker.check_repo(REPO) == []

    def test_checker_flags_unbridged_field_and_undocumented_name(
            self, tmp_path):
        # clone the checker's inputs, then break them both ways
        fake = tmp_path / "repo"
        for rel in ("src/repro/obs/bridge.py", "src/repro/serve/scheduler.py",
                    "src/repro/serve/fabric.py", "src/repro/core/tiering.py",
                    "src/repro/core/versioning.py",
                    "src/repro/stream/pipeline.py",
                    "src/repro/traffic/driver.py",
                    "src/repro/traffic/controller.py",
                    "docs/observability.md"):
            dst = fake / rel
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(os.path.join(REPO, rel), dst)
        from tools.analyze import metrics as checker
        assert checker.check_repo(str(fake)) == []

        bridge_path = fake / "src/repro/obs/bridge.py"
        text = bridge_path.read_text()
        # drop a mapped field -> "has no metric name" violation
        broken = text.replace(
            '    "failovers": "repro_fabric_failovers_total",\n', "")
        bridge_path.write_text(broken)
        msgs = [v.message for v in checker.check_repo(str(fake))]
        assert any("FabricCounts.failovers" in m for m in msgs)

        # undocumented name -> "not documented" violation
        bridge_path.write_text(text.replace(
            "repro_fabric_failovers_total",
            "repro_fabric_failovers_renamed_total"))
        msgs = [v.message for v in checker.check_repo(str(fake))]
        assert any("not documented" in m for m in msgs)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------
class TestTracer:
    def test_rate_zero_never_samples(self):
        t = Tracer(sample_rate=0.0)
        assert all(t.sample() is None for _ in range(1000))

    def test_rate_one_always_samples_unique(self):
        t = Tracer(sample_rate=1.0)
        ids = {t.sample() for _ in range(100)}
        assert None not in ids and len(ids) == 100

    def test_record_take_and_capacity_eviction(self):
        t = Tracer(sample_rate=1.0, capacity=2)
        tids = [t.sample() for _ in range(3)]
        for tid in tids:
            t.record([Span(tid, "serve", 0.0, 1.0)])
        assert t.take(tids[0]) == []             # evicted (oldest)
        assert len(t.take(tids[2])) == 1
        assert t.take(tids[2]) == []             # take pops

    def test_span_wire_round_trip(self):
        s = Span("tid", "device", 1.5, 2.5, parent_id="pid",
                 proc="shard0/r1", tags={"version": 3})
        back = Span.from_wire(s.to_wire())
        assert (back.trace_id, back.name, back.t0, back.t1, back.parent_id,
                back.proc, back.tags) == \
            ("tid", "device", 1.5, 2.5, "pid", "shard0/r1", {"version": 3})
        assert back.duration_s == pytest.approx(1.0)

    def test_sort_timeline_orders_by_start(self):
        spans = [Span("t", "b", 2.0, 3.0), Span("t", "a", 1.0, 4.0)]
        assert [s.name for s in sort_timeline(spans)] == ["a", "b"]


def _small_engine(n=2000):
    from repro.core.engine import MultiTableEngine, ScalarTable
    keys = np.arange(1, n + 1, dtype=np.uint64)
    vals = np.arange(1, n + 1, dtype=np.uint64) * 3
    return MultiTableEngine([ScalarTable("item_attr", keys, vals)]), keys


class TestServerTracing:
    def test_sampled_request_yields_full_span_chain(self):
        from repro.serve.scheduler import BatchPolicy
        from repro.serve.server import QueryServer
        from repro.api.types import QueryRequest

        engine, keys = _small_engine()
        tracer = Tracer(sample_rate=1.0, proc="server")
        with QueryServer(engine, BatchPolicy(max_wait_s=0.001),
                         tracer=tracer) as server:
            resp = server.query(QueryRequest(tables={"item_attr": keys[:64]}))
        assert resp.trace, "sampled request returned no trace"
        names = [d["name"] for d in resp.trace]
        for want in ("serve", "admission", "lane_wait", "coalesce",
                     "version_pin", "begin", "finish_wait", "finish",
                     "scatter"):
            assert want in names, f"missing span {want!r}"
        tids = {d["trace_id"] for d in resp.trace}
        assert len(tids) == 1
        # server-side tracer retained the same trace
        assert tracer.take(tids.pop())

    def test_unsampled_request_has_no_trace(self):
        from repro.serve.scheduler import BatchPolicy
        from repro.serve.server import QueryServer
        from repro.api.types import QueryRequest

        engine, keys = _small_engine()
        with QueryServer(engine, BatchPolicy(max_wait_s=0.001),
                         tracer=Tracer(sample_rate=0.0)) as server:
            resp = server.query(QueryRequest(tables={"item_attr": keys[:64]}))
        assert resp.trace is None

    def test_tracing_disabled_adds_no_measurable_overhead(self):
        """Rate-0 tracing must cost ~nothing on the serving hot path.

        Generous bound: min-of-trials wall time within 1.6x of the
        no-tracer baseline (the sample() short-circuit is one float
        compare; anything past the bound means work leaked onto the
        untraced path)."""
        from repro.serve.scheduler import BatchPolicy
        from repro.serve.server import QueryServer
        from repro.api.types import QueryRequest

        engine, keys = _small_engine()
        reqs = [QueryRequest(tables={"item_attr": keys[i % 32::32][:64]})
                for i in range(120)]

        def run(tracer):
            with QueryServer(engine, BatchPolicy(max_wait_s=0.0005),
                             tracer=tracer) as server:
                for r in reqs[:20]:                       # warm
                    server.query(r)
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    for r in reqs:
                        server.query(r)
                    best = min(best, time.perf_counter() - t0)
            return best

        base = run(None)
        traced_off = run(Tracer(sample_rate=0.0))
        assert traced_off < base * 1.6, (
            f"rate-0 tracing overhead: {traced_off:.4f}s vs {base:.4f}s")


# ---------------------------------------------------------------------------
# fabric: merged cross-process traces + stats RPC + /metrics endpoint
# ---------------------------------------------------------------------------
def _build_fabric(tmp_path, *, trace_rate=0.0, n_shards=2, n_replicas=1):
    from repro.core.query_types import EmbeddingTable
    from repro.serve.fabric import FabricConfig, Router
    rng = np.random.default_rng(0)
    keys = np.unique(rng.integers(1, 1 << 62, 4000,
                                  dtype=np.uint64))[:2000]
    vals = rng.integers(0, 256, size=(len(keys), 16), dtype=np.uint8)
    cfg = FabricConfig(n_shards=n_shards, n_replicas=n_replicas,
                       snapshot_root=str(tmp_path / "snaps"),
                       respawn=False, trace_sample_rate=trace_rate)
    table = EmbeddingTable("emb", keys, vals, hot_fraction=0.5,
                           variant="neighborhash")
    return Router.build([table], cfg), keys


class TestFabricObservability:
    def test_sampled_query_merges_one_cross_process_trace(self, tmp_path):
        """The acceptance trace: one sampled query through a 2-shard
        fabric yields ONE trace covering admission -> scatter-back,
        including shard-side time."""
        from repro.api.types import QueryRequest
        router, keys = _build_fabric(tmp_path, trace_rate=1.0)
        try:
            resp = router.query_ex(QueryRequest(tables={"emb": keys[:256]}))
            if isinstance(resp, tuple):
                resp = resp[0]
            assert resp.trace, "sampled fabric query returned no trace"
            names = [d["name"] for d in resp.trace]
            procs = {d["proc"] for d in resp.trace}
            tids = {d["trace_id"] for d in resp.trace}
            assert len(tids) == 1, f"trace ids fragmented: {tids}"
            for want in ("route", "shard_rpc", "serve", "admission",
                         "lane_wait", "coalesce", "version_pin", "begin",
                         "finish_wait", "finish", "scatter"):
                assert want in names, f"missing span {want!r}"
            shard_procs = {p for p in procs if p.startswith("shard")}
            assert len(shard_procs) == 2, procs    # both shards contributed
            assert "router" in procs
            # router tracer holds the merged timeline; spans sort by start
            spans = router.tracer.take(resp.trace[0]["trace_id"])
            assert spans
            ordered = sort_timeline(spans)
            assert ordered[0].name == "route"
        finally:
            router.close()

    def test_unsampled_fabric_query_carries_no_trace(self, tmp_path):
        from repro.api.types import QueryRequest
        router, keys = _build_fabric(tmp_path, trace_rate=0.0)
        try:
            resp = router.query_ex(QueryRequest(tables={"emb": keys[:64]}))
            if isinstance(resp, tuple):
                resp = resp[0]
            assert resp.trace is None
        finally:
            router.close()

    def test_stats_rpc_and_router_bridge(self, tmp_path):
        from repro.api.types import QueryRequest
        router, keys = _build_fabric(tmp_path)
        try:
            for i in range(4):
                router.query_ex(QueryRequest(
                    tables={"emb": keys[64 * i:64 * (i + 1)]}))
            shards = router.collect_shard_stats()
            assert set(shards) == {"shard0/r0", "shard1/r0"}
            for silo in shards.values():
                assert silo["server"]["submitted"] >= 1
                assert silo["tiers"]["emb"]["lookups"] >= 1

            reg = Registry()
            bridge_router(reg, router)
            parsed = exporter.parse_text(exporter.render_text(reg))
            assert parsed[("repro_fabric_queries_total", ())] == 4.0
            key = (("shard", "shard0/r0"),)
            assert parsed[("repro_server_requests_submitted_total",
                           key)] >= 1.0
            assert ("repro_tier_hot_hit_rate",
                    (("shard", "shard0/r0"), ("table", "emb"))) in parsed
        finally:
            router.close()


@pytest.mark.slow
def test_launcher_serves_metrics_and_emits_record(tmp_path):
    """The CI smoke acceptance: ``repro.launch.fabric --smoke`` serves
    ``/metrics`` which a mid-run scrape can read — hot-tier hit rate,
    per-QoS p99, shed counts, version-pin retries, failover counts —
    and the exit record carries the final snapshot."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    record = tmp_path / "BENCH_fabric_smoke.json"
    env = subprocess_env(inherit=True)
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.fabric", "--smoke",
         "--metrics-port", str(port), "--trace-sample", "0.2",
         "--record", str(record)],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    url = f"http://127.0.0.1:{port}/metrics"
    parsed = None
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and proc.poll() is None:
            try:
                body = urllib.request.urlopen(url, timeout=5).read().decode()
                got = exporter.parse_text(body)
                if any(k[0] == "repro_fabric_queries_total" and v > 0
                       for k, v in got.items()):
                    parsed = got
                    break                         # a real mid-run scrape
            except (urllib.error.URLError, ConnectionError, OSError):
                pass
            time.sleep(0.2)
        assert parsed is not None, (
            "never scraped a live /metrics with traffic; launcher output:\n"
            + (proc.communicate(timeout=10)[0] if proc.poll() is not None
               else "<still running>"))
        names = {k[0] for k in parsed}
        # the acceptance series, by family
        assert "repro_tier_hot_hit_rate" in names
        assert "repro_server_class_latency_p99_ms" in names
        assert "repro_server_shed_queue_full_total" in names
        assert "repro_fabric_version_retries_total" in names
        assert "repro_fabric_failovers_total" in names
        # traffic actually flowed: per-shard submits and tier lookups
        assert sum(v for k, v in parsed.items()
                   if k[0] == "repro_server_requests_submitted_total") > 0
        assert sum(v for k, v in parsed.items()
                   if k[0] == "repro_tier_lookups_total") > 0
        # drive() queries the built keyset, so hot hits are real
        assert sum(v for k, v in parsed.items()
                   if k[0] == "repro_tier_hot_hits_total") > 0
        out, _ = proc.communicate(timeout=150)
        assert proc.returncode == 0, out
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=10)
    rec = json.loads(record.read_text())
    assert rec["ok"] is True
    assert rec["alias"] == "fabric_smoke"
    assert any(k.startswith("repro_fabric_queries_total")
               for k in rec["metrics"])
