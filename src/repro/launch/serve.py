"""Serving launcher: run a serve/decode cell with request batching on the
devices present.

    PYTHONPATH=src python -m repro.launch.serve --arch deepfm [--smoke] \
        [--requests 20]

Recsys archs can additionally serve their feature columns through the
concurrent QueryServer (serve/server.py) — concurrent client threads score
batches whose table lookups coalesce into deadline-aware micro-batches:

    PYTHONPATH=src python -m repro.launch.serve --arch deepfm [--smoke] \
        --feature-server --clients 8 --requests 10

--smoke runs the reduced configs (CPU tests).  Without it the full arch
config runs, and --feature-server builds the full bili-feature-store
deployment.  ``chip_smoke.py`` drives the same functions at cut sizes.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax

from repro.core import compat
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch import cells as cells_mod
from repro.launch import mesh as mesh_mod
from repro.launch.materialize import materialize, materialize_bundle

# (table, batch field) pairs the scoring step resolves in one fused query
FEATURE_FIELDS = [("item_feats", "item_id"), ("item_attr", "item_id")]
# YCSB's zipfian constant: request keys follow the item popularity law
ITEM_SKEW = 0.99


def feature_tables(fs_cfg, *, seed: int = 0, n_items: Optional[int] = None,
                   n_embed_rows: Optional[int] = None):
    """The bili-feature-store schema generated from ``seed``: ``item_attr``
    (item key -> 52-bit attribute payload) over ``n_items`` items, and
    ``item_feats`` (item key -> ``value_bytes`` row of float32 features,
    ``hot_fraction`` of it in the hot tier) over the first
    ``n_embed_rows`` items.  Item ``i`` is the ``i``-th most popular;
    the sizes default to the config's ``n_items``.
    Returns ``(ScalarTable, EmbeddingTable)``."""
    from repro.core import neighborhash as nh
    from repro.core.engine import EmbeddingTable, ScalarTable

    n_items = n_items or fs_cfg.n_items
    n_embed = n_embed_rows or n_items
    keys, payloads = nh.random_kv(n_items, seed=seed)
    rng = np.random.default_rng(seed + 1)
    values = rng.standard_normal((n_embed, fs_cfg.value_bytes // 4),
                                 dtype=np.float32).view(np.uint8)
    return (ScalarTable("item_attr", keys, payloads,
                        load_factor=fs_cfg.load_factor),
            EmbeddingTable("item_feats", keys[:n_embed], values,
                           hot_fraction=fs_cfg.hot_fraction))


def build_feature_engine(fs_cfg, attr, feats):
    """One ``MultiTableEngine`` version holding both tables: the attribute
    index goes to the device, the feature rows to the hybrid store."""
    from repro.core.engine import MultiTableEngine
    return MultiTableEngine([attr], [feats],
                            max_shard_bytes=fs_cfg.max_shard_bytes,
                            version=1)


def request_keys(rng: np.random.Generator, popularity, item_keys: np.ndarray,
                 n: int, miss_share: float) -> np.ndarray:
    """``n`` request keys: items drawn by ``popularity`` rank, a
    ``miss_share`` of them replaced by keys no table holds (the item keys
    all lie below 2^63)."""
    keys = item_keys[popularity.sample(rng, n)]
    miss = rng.random(n) < miss_share
    keys[miss] = rng.integers(1 << 63, (1 << 64) - 1, int(miss.sum()),
                              dtype=np.uint64)
    return keys


def feature_scoring(cfg, client, *, seed: int = 0,
                    budget_s: Optional[float] = None):
    """Recsys scoring whose ``item_id`` features come through ``client``
    (RANKING lane): ``(params, step)`` with random params from ``seed``
    on the local mesh."""
    from repro.models import common as cm
    from repro.models import recsys as rec_mod
    from repro.serve import serve_step

    mesh = mesh_mod.make_local_mesh()
    mi = cm.MeshInfo.from_mesh(mesh)
    params = jax.jit(lambda k: cm.unbox(rec_mod.recsys_init(k, cfg))[0])(
        jax.random.key(seed))
    step = serve_step.recsys_score_fn(
        cfg, mesh, mi, feature_client=client, feature_budget_s=budget_s,
        feature_fields=FEATURE_FIELDS)
    return params, step


def serve_with_feature_server(args, spec):
    """Recsys serving through the QueryServer: ``--clients`` threads score
    request batches concurrently; each batch's feature lookups ride the
    RANKING lane of the API-v2 FeatureClient with a latency budget and
    coalesce with the other clients' lookups into fused micro-batches,
    while a publisher ships a delta mid-traffic.  ``--prefetch-clients``
    adds background PREFETCH-lane lookup threads, exercising the QoS
    weighted service/shed order under real scoring load."""
    import threading

    from repro.api import FeatureClient
    from repro.data import synthetic
    from repro.serve.scheduler import BatchPolicy, ShedError
    from repro.serve.server import QueryServer
    from repro.traffic.loadgen import ZipfianPopularity

    fs_spec = registry.get("bili-feature-store")
    fs_cfg = fs_spec.smoke if args.smoke else fs_spec.config
    cfg = spec.smoke if args.smoke else spec.config
    attr, feats = feature_tables(fs_cfg, seed=0)
    engine = build_feature_engine(fs_cfg, attr, feats)
    popularity = ZipfianPopularity(len(attr.keys), ITEM_SKEW)

    server = QueryServer(engine, BatchPolicy(max_batch_keys=4096))
    client_session = FeatureClient(server, default_budget_s=2.0)
    params, step = feature_scoring(cfg, client_session, budget_s=2.0)

    lat, shed = [], [0]
    lat_lock = threading.Lock()
    prefetch_stop = threading.Event()

    def prefetch_client(cid: int):
        """Speculative cache-warming traffic on the PREFETCH lane — first
        to shed under backpressure, never allowed to crowd out scoring."""
        prng = np.random.default_rng(900 + cid)
        while not prefetch_stop.is_set():
            ids = request_keys(prng, popularity, attr.keys, 256, 0.0)
            try:
                client_session.query({"item_feats": ids}, qos="PREFETCH",
                                     budget_s=0.5)
            except ShedError:
                pass

    def client(cid: int):
        crng = np.random.default_rng(100 + cid)
        for i in range(args.requests):
            batch = synthetic.recsys_batch(crng, cfg, 64)
            batch.pop("label", None)
            batch["item_id"] = request_keys(crng, popularity, attr.keys, 64,
                                            0.1)
            t0 = time.perf_counter()
            try:
                jax.block_until_ready(step(params, batch))
            except ShedError:
                with lat_lock:
                    shed[0] += 1
                continue
            with lat_lock:
                lat.append((time.perf_counter() - t0) * 1e3)

    client(0)                                      # warmup/compile lane
    with lat_lock:                                 # fresh measurement
        lat.clear()
        shed[0] = 0
    server.reset_stats()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(args.clients)]
    prefetchers = [threading.Thread(target=prefetch_client, args=(p,),
                                    daemon=True)
                   for p in range(args.prefetch_clients)]
    for t in threads + prefetchers:
        t.start()
    # a delta publish lands mid-traffic; micro-batches stay one-version
    client_session.update(2, upserts={
        "item_attr": (attr.keys[:64], attr.payloads[:64] + np.uint64(1))})
    for t in threads:
        t.join()
    prefetch_stop.set()
    for t in prefetchers:
        t.join()
    snap = server.stats_snapshot()
    server.close()
    if lat:
        lat_line = (f"p50={np.percentile(lat, 50):.2f}ms "
                    f"p99={np.percentile(lat, 99):.2f}ms")
    else:
        lat_line = "no requests served"
    print(f"{args.arch}/feature-server: {args.clients} clients x "
          f"{args.requests} requests, {lat_line} shed={shed[0]}")
    print(f"  server: {snap.summary()}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--feature-server", action="store_true",
                    help="recsys only: serve feature tables through the "
                         "concurrent QueryServer")
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent client threads for --feature-server")
    ap.add_argument("--prefetch-clients", type=int, default=2,
                    help="background PREFETCH-lane lookup threads for "
                         "--feature-server (QoS lanes under load)")
    args = ap.parse_args()
    mesh_mod.enable_compile_cache()

    spec = registry.get(args.arch)
    if args.feature_server:
        if spec.family != "recsys":
            raise SystemExit("--feature-server needs a recsys arch")
        serve_with_feature_server(args, spec)
        return
    shape = args.shape or {"lm": "decode_32k", "gnn": "molecule",
                           "recsys": "serve_p99"}[spec.family]
    mesh = mesh_mod.make_local_mesh()
    bundle = cells_mod.build_cell(args.arch, shape, mesh, smoke=args.smoke)
    fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                 out_shardings=bundle.out_shardings)
    base_args = materialize_bundle(bundle, seed=0)
    lat = []
    with compat.set_mesh(mesh):
        out = jax.block_until_ready(fn(*base_args))       # warmup/compile
        for i in range(args.requests):
            req = materialize(bundle.args[1:], seed=i + 1,
                              int_high=bundle.meta.get("int_high"))
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(base_args[0], *req))
            lat.append((time.perf_counter() - t0) * 1e3)
    print(f"{args.arch}/{shape}: {args.requests} requests, "
          f"p50={np.percentile(lat, 50):.2f}ms "
          f"p99={np.percentile(lat, 99):.2f}ms")


if __name__ == "__main__":
    main()
