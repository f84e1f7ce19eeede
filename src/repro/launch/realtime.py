"""Realtime launcher: the streaming online-learning loop, end to end.

    PYTHONPATH=src python -m repro.launch.realtime --smoke

One process runs the whole lambda loop the paper's serving architecture
assumes, concurrently:

  - sessionized traffic threads append impression/click events to the
    in-process event log (``repro.stream``) and query features through
    ``FeatureClient`` -> ``QueryServer`` on the RANKING lane — every
    N-th query demands ``min_version`` read-your-writes against the
    newest published version;
  - a streaming trainer consumes the events in micro-batches, runs the
    real DIN ``train_step`` (delta emission), and publishes the touched
    embedding rows as incremental deltas;
  - a windowed-EMA updater maintains ``user_profile`` rows; a trending
    aggregator keeps the cold-start fallback row fresh (users with no
    profile yet are served the decayed top-k);
  - a rolling batch layer republishes the full tables every few seconds
    through the same serialized version sequence.

Event-append -> servable-version latency lands in the obs registry as
the ``repro_stream_freshness_seconds`` histogram (with ``--trace-sample``
above 0, the server's tracer samples requests at that rate and records
every delta publish as a ``publish_delta`` trace), and the run exits with
a per-run SLO report:
freshness p50/p99, staleness violations, updates/s, qps.  Exit is
nonzero on any ``min_version`` violation, served-version regression, or
pipeline-stage crash.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import threading
import time

import jax

from repro.core import compat
import jax.numpy as jnp
import numpy as np

from repro.api import Consistency, ConsistencyError, FeatureClient
from repro.configs import registry
from repro.core.engine import EmbeddingTable, MultiTableEngine
from repro.data import synthetic
from repro.launch import mesh as mesh_mod
from repro.models import common as cm
from repro.models import recsys as rec_mod
from repro.obs.bridge import bridge_server_stats, bridge_stream_stats
from repro.obs.exporter import MetricsServer, snapshot
from repro.obs.metrics import Registry
from repro.obs.trace import Tracer
from repro.serve.scheduler import BatchPolicy, ShedError
from repro.serve.server import QueryServer
from repro.stream import (EventLog, ProfileEMAUpdater, SessionizedSource,
                          StreamStats, StreamingTrainer, TrendingAggregator,
                          VersionedPublisher)
from repro.train import optimizer as opt
from repro.train import train_step as ts

EVENTS_TOPIC = "events"
TRENDING_TOPIC = "trending"
PROFILE_DIM = 8


def _rows_as_bytes(table: np.ndarray, rows: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(
        table[rows].astype(np.float32)).view(np.uint8)


def build_engine(args, item_table: np.ndarray) -> MultiTableEngine:
    """Seed the serving tier: trained item rows, zeroed user profiles,
    an empty trending fallback row."""
    item_keys = np.arange(1, args.n_items + 1, dtype=np.uint64)
    item_vals = _rows_as_bytes(item_table,
                               np.arange(args.n_items, dtype=np.int64))
    user_keys = np.arange(1, args.n_users + 1, dtype=np.uint64)
    user_vals = np.zeros((args.n_users, PROFILE_DIM * 4), dtype=np.uint8)
    trend_vals = np.zeros((1, args.top_k * 8), dtype=np.uint8)
    return MultiTableEngine(embeddings=[
        EmbeddingTable("item_table", item_keys, item_vals,
                       hot_fraction=0.5),
        EmbeddingTable("user_profile", user_keys, user_vals,
                       hot_fraction=0.5),
        EmbeddingTable(TRENDING_TOPIC,
                       np.asarray([1], dtype=np.uint64), trend_vals),
    ], max_shard_bytes=1 << 18, version=1)


def make_step_fn(args, cfg, mesh, mi, params):
    """The streaming trainer's ``step_fn``: fold the micro-batch's events
    into a DIN batch frame (static shapes — one compile), run the real
    ``train_step`` with delta emission, return the touched rows as an
    upsert."""
    ocfg = opt.OptConfig(lr=0.003)
    state = opt.init_opt_state(params, ocfg)
    jit_step = jax.jit(ts.make_train_step(
        lambda p, b: rec_mod.recsys_loss(p, cfg, b, mi), ocfg,
        delta_ids_fn=lambda b: {"item_table": jnp.concatenate(
            [b["hist_items"].reshape(-1), b["target_item"].reshape(-1)])}))
    rng = np.random.default_rng(1234)
    holder = {"params": params, "state": state, "step": jnp.int32(0)}

    def step_fn(events):
        batch = synthetic.recsys_batch(rng, cfg, args.train_batch)
        items = np.asarray([(ev.payload or {}).get("item", 0)
                            for ev in events], dtype=np.int64)
        clicks = np.asarray([ev.kind == "click" for ev in events],
                            dtype=np.float32)
        n = min(len(items), args.train_batch)
        batch["target_item"][:n] = items[:n] % cfg.item_vocab
        batch["label"][:n] = clicks[:n]
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        with compat.set_mesh(mesh):
            p, s, st, metrics = jit_step(holder["params"], holder["state"],
                                         holder["step"], jb)
        holder.update(params=p, state=s, step=st)
        ids = np.asarray(metrics["delta_ids"]["item_table"]).reshape(-1)
        rows = np.unique(ids[(ids >= 0) & (ids < args.n_items)])
        if not len(rows):
            return None
        return {"item_table": (
            rows.astype(np.uint64) + np.uint64(1),
            _rows_as_bytes(np.asarray(holder["params"]["item_table"]),
                           rows))}

    return step_fn, holder


def drive(args, registry_obj, tracer) -> tuple[int, dict]:
    cfg = dataclasses.replace(registry.get("din").smoke,
                              item_vocab=args.n_items, seq_len=10)
    mesh = mesh_mod.make_local_mesh()
    mi = cm.MeshInfo.from_mesh(mesh)
    params, _ = cm.unbox(rec_mod.recsys_init(jax.random.key(0), cfg))

    engine = build_engine(args, np.asarray(params["item_table"]))
    server = QueryServer(engine, BatchPolicy(max_batch_keys=4096),
                         tracer=tracer)
    client = FeatureClient(server, default_budget_s=2.0)

    log = EventLog()
    log.create_topic(EVENTS_TOPIC, partitions=4, retention=args.retention)
    log.create_topic(TRENDING_TOPIC, partitions=1, retention=64)

    stats = StreamStats(slo_budget_s=args.slo_s)
    bridge_stream_stats(registry_obj, stats)
    bridge_server_stats(registry_obj, server.stats_snapshot)
    # a traced server hands its tracer to the engine's backend, which
    # records every delta publish as a ``publish_delta`` trace of its own
    publisher = VersionedPublisher(client, engine.latest_version, stats)

    step_fn, holder = make_step_fn(args, cfg, mesh, mi, params)
    # pay the jit compiles before any event's clock starts (the second
    # call re-specializes on the returned step counter's dtype)
    step_fn([])
    step_fn([])
    trainer = StreamingTrainer(log, EVENTS_TOPIC, publisher, stats, step_fn,
                               batch_events=args.train_batch,
                               max_backlog=args.max_backlog)
    profiles = ProfileEMAUpdater(log, EVENTS_TOPIC, publisher, stats,
                                 dim=PROFILE_DIM)
    trending = TrendingAggregator(log, EVENTS_TOPIC, publisher, stats,
                                  out_topic=TRENDING_TOPIC,
                                  top_k=args.top_k)
    stages = [trainer, profiles, trending]

    qlat: list[float] = []
    counters = {"queries": 0, "shed": 0, "fallback_served": 0,
                "ryw_checked": 0, "version_regressions": 0}
    clock = threading.Lock()
    stop = threading.Event()

    def traffic(cid: int):
        src = SessionizedSource(log, EVENTS_TOPIC, n_users=args.n_users,
                                n_items=args.n_items, seed=500 + cid)
        last_version = 0
        for i in range(args.requests):
            if stop.is_set():
                return
            user = src.pick_user()
            events = src.emit_session(user)
            item_keys = np.unique(np.asarray(
                [(ev.payload or {}).get("item", 0) for ev in events],
                dtype=np.uint64) + np.uint64(1))
            q = {"user_profile": np.asarray([user + 1], dtype=np.uint64),
                 TRENDING_TOPIC: np.asarray([1], dtype=np.uint64),
                 "item_table": item_keys}
            consistency = None
            if args.ryw_every and i % args.ryw_every == 0:
                # read-your-writes: demand at least the newest version
                # this process knows to be servable
                consistency = Consistency.min_version(publisher.version)
            t0 = time.perf_counter()
            try:
                res = client.query(q, qos="RANKING",
                                   consistency=consistency)
            except ConsistencyError:
                stats.inc("min_version_violations")
                continue
            except ShedError:
                with clock:
                    counters["shed"] += 1
                continue
            with clock:
                qlat.append((time.perf_counter() - t0) * 1e3)
                counters["queries"] += 1
                if consistency is not None:
                    counters["ryw_checked"] += 1
                if res.version < last_version:
                    counters["version_regressions"] += 1
            last_version = res.version
            prof = res.tables["user_profile"]
            if not prof.found[0] or not prof.values[0].any():
                # cold-start: no profile signal yet -> trending fallback
                trow = res.tables[TRENDING_TOPIC]
                if trow.found[0]:
                    TrendingAggregator.decode_row(trow.values[0])
                    with clock:
                        counters["fallback_served"] += 1

    def batch_layer():
        """Rolling full republish: the lambda batch layer, sharing the
        speed layer's serialized version sequence."""
        while not stop.wait(args.batch_publish_s):
            item_tab = np.asarray(holder["params"]["item_table"])
            item_keys = np.arange(1, args.n_items + 1, dtype=np.uint64)
            user_vals = np.zeros((args.n_users, PROFILE_DIM * 4), np.uint8)
            for u, vec in profiles.all_profiles().items():
                if 0 <= u < args.n_users:
                    user_vals[u] = vec.astype(np.float32).view(np.uint8)
            top = (trending.top() + [0] * args.top_k)[:args.top_k]
            publisher.publish_full(embeddings=[
                EmbeddingTable("item_table", item_keys, _rows_as_bytes(
                    item_tab, np.arange(args.n_items, dtype=np.int64)),
                    hot_fraction=0.5),
                EmbeddingTable("user_profile",
                               np.arange(1, args.n_users + 1,
                                         dtype=np.uint64),
                               user_vals, hot_fraction=0.5),
                EmbeddingTable(TRENDING_TOPIC,
                               np.asarray([1], dtype=np.uint64),
                               np.asarray(top, dtype=np.uint64)
                               .view(np.uint8).reshape(1, -1)),
            ])

    t_run = time.perf_counter()
    for s in stages:
        s.start()
    batcher = threading.Thread(target=batch_layer, daemon=True)
    batcher.start()
    workers = [threading.Thread(target=traffic, args=(c,))
               for c in range(args.clients)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    # drain: let the pipeline catch up on the tail of the event stream
    deadline = time.monotonic() + args.drain_s
    while time.monotonic() < deadline \
            and log.backlog(EVENTS_TOPIC, "trainer") > 0 \
            and all(s.error is None for s in stages):
        time.sleep(0.02)
    stop.set()
    for s in stages:
        s.stop()
    batcher.join(timeout=5.0)
    elapsed = time.perf_counter() - t_run
    server.close()

    snap = stats.snapshot()
    stage_errors = {s.name: repr(s.error) for s in stages
                    if s.error is not None}
    report = {
        "freshness_p50_ms": round(snap.freshness_p50_ms, 3),
        "freshness_p99_ms": round(snap.freshness_p99_ms, 3),
        "freshness_samples": snap.freshness_samples,
        "staleness_violations": snap.staleness_violations,
        "updates_per_s": round(snap.updates_per_s, 2),
        "qps": round(counters["queries"] / max(elapsed, 1e-9), 2),
        "query_p50_ms": round(float(np.percentile(qlat, 50)), 3)
        if qlat else 0.0,
        "query_p99_ms": round(float(np.percentile(qlat, 99)), 3)
        if qlat else 0.0,
        "queries": counters["queries"],
        "shed": counters["shed"],
        "ryw_checked": counters["ryw_checked"],
        "min_version_violations": snap.min_version_violations,
        "version_regressions": counters["version_regressions"],
        "fallback_served": counters["fallback_served"],
        "deltas_published": snap.deltas_published,
        "trainer_steps": snap.trainer_steps,
        "events_consumed": snap.events_consumed,
        "events_shed": snap.events_shed,
        "final_version": publisher.version,
        "stage_errors": stage_errors,
    }
    rc = 0
    if snap.min_version_violations or counters["version_regressions"]:
        print("FAIL: consistency violated under concurrent publishing")
        rc = 1
    if stage_errors:
        print(f"FAIL: pipeline stage crashed: {stage_errors}")
        rc = 1
    if not snap.deltas_published or not counters["queries"]:
        print("FAIL: the loop did not actually run "
              f"(deltas={snap.deltas_published} "
              f"queries={counters['queries']})")
        rc = 1
    return rc, report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: few users/requests, small model")
    ap.add_argument("--n-items", type=int, default=2000)
    ap.add_argument("--n-users", type=int, default=256)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--requests", type=int, default=60,
                    help="sessions (each: events appended + one query) "
                         "per client thread")
    ap.add_argument("--train-batch", type=int, default=32)
    ap.add_argument("--retention", type=int, default=50_000)
    ap.add_argument("--max-backlog", type=int, default=4096)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--ryw-every", type=int, default=2,
                    help="every N-th query demands min_version "
                         "read-your-writes (0 disables)")
    ap.add_argument("--batch-publish-s", type=float, default=2.0,
                    help="rolling full-republish period (the batch layer)")
    ap.add_argument("--drain-s", type=float, default=5.0,
                    help="max seconds to let the pipeline drain the tail")
    ap.add_argument("--slo-s", type=float, default=2.0,
                    help="freshness SLO budget: event-append -> servable "
                         "above this counts as a staleness violation")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics on this port while "
                         "driving (0 = ephemeral; the bound URL is printed)")
    ap.add_argument("--trace-sample", type=float, default=0.0,
                    help="fraction of queries to trace [0,1]; above 0 "
                         "the server also records every micro-batch and "
                         "every delta publish as a trace")
    ap.add_argument("--record", default=None,
                    help="write a BENCH-style JSON record (SLO report + "
                         "metrics snapshot) to this path on exit")
    args = ap.parse_args()
    mesh_mod.enable_compile_cache()
    if args.smoke:
        args.n_items = min(args.n_items, 500)
        args.n_users = min(args.n_users, 64)
        args.clients = min(args.clients, 2)
        args.requests = min(args.requests, 12)

    registry_obj = Registry()
    # a server with a tracer records every micro-batch and every delta
    # publish: only an operator who asked for traces pays for them
    tracer = (Tracer(sample_rate=args.trace_sample, proc="realtime")
              if args.trace_sample > 0 else None)
    metrics_srv = None
    if args.metrics_port is not None:
        metrics_srv = MetricsServer(registry_obj,
                                    port=args.metrics_port).start()
        print(f"metrics: serving {metrics_srv.url}", flush=True)
    t_start = time.time()
    try:
        rc, report = drive(args, registry_obj, tracer)
        print("realtime SLO report: "
              + json.dumps(report, sort_keys=True), flush=True)
        if args.record:
            record = {
                "alias": "realtime",
                "unix_time": int(t_start),
                "duration_s": round(time.time() - t_start, 3),
                "ok": rc == 0,
                "report": report,
                "metrics": snapshot(registry_obj),
            }
            with open(args.record, "w") as f:
                json.dump(record, f, indent=1)
            print(f"record: wrote {args.record}", flush=True)
    finally:
        if metrics_srv is not None:
            metrics_srv.close()
    raise SystemExit(rc)


if __name__ == "__main__":
    main()
