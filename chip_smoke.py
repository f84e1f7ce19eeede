"""Bring-up smoke of the served feature-store path on one accelerator chip.

    python chip_smoke.py                              # the TPU machine
    JAX_PLATFORMS=cpu python chip_smoke.py --small    # rehearsal on the CPU

One process drives the path a user calls, end to end (the chip belongs to
one process, so nothing here starts a child that imports JAX):

  store    the bili-feature-store deployment built from ``--seed`` through
           ``launch/serve.py``: the ``item_attr`` NeighborHash index on the
           device and the 1 KB ``item_feats`` rows in the hybrid store;
  serve    client threads query both tables through ``FeatureClient`` ->
           ``QueryServer`` -> ``MultiTableEngine`` with zipfian keys and a
           miss share, twice (warm-up, then a warm repeat); every answer is
           checked bit for bit against a numpy reference;
  delta    one published delta is read back under ``min_version``;
  score    full-width DeepFM scores ``serve_p99`` batches whose features
           come through the same client, and its native FM kernel is
           compared with the pure-jnp oracle on the same rows.

Sizes are the deployment's except where the run's 1200 s limit forces a
cut; each cut is printed.  Latencies printed are a smoke reading, not a
benchmark.  The last stdout line is one JSON object
``{"ok": ..., "device": {"platform", "kind", "count"}}``; ``ok`` is true only
on a TPU at real size with every check passed.  Without ``--small`` a run
that finds no TPU exits 2 before any phase; any failed check or exception
exits nonzero without that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# The item_feats rows are cut: 40M x 1 KB is 40 GB of host rows plus their
# cold file, more than a one-chip host holds, and the hybrid store indexes
# them with the same per-key host build as item_attr (~14 s per million
# keys measured on a TPU v5e host; the 40M-key item_attr index takes ~9 min
# of the run's 1200 s).
EMBED_ROWS = 2_000_000
REQUEST_KEYS = {False: 4096, True: 512}       # keyed by --small
MISS_SHARE = 0.1
CLIENTS = 4
REQUESTS_PER_CLIENT = 8
SCORE_BATCHES = 3
DELTA_UPDATES, DELTA_NEW_KEYS = 512, 64


class Reference:
    """Numpy answers for the generated tables: sorted-key search, no hash.
    ``upsert`` applies a delta's rows the way the store must."""

    def __init__(self, attr, feats):
        order = np.argsort(attr.keys)
        self.attr_keys = attr.keys[order]
        self.attr_payloads = attr.payloads[order]
        order = np.argsort(feats.keys)
        self.feat_keys = feats.keys[order]
        self.feat_values = feats.values[order]

    @staticmethod
    def _find(sorted_keys, q):
        pos = np.minimum(np.searchsorted(sorted_keys, q),
                         len(sorted_keys) - 1)
        return sorted_keys[pos] == q, pos

    def attr(self, q):
        found, pos = self._find(self.attr_keys, q)
        return found, np.where(found, self.attr_payloads[pos], 0)

    def feats(self, q):
        found, pos = self._find(self.feat_keys, q)
        vals = self.feat_values[pos]
        vals[~found] = 0
        return found, vals

    def upsert(self, attr_keys, attr_payloads, feat_keys, feat_values):
        keys = np.concatenate([attr_keys, self.attr_keys])
        payloads = np.concatenate([attr_payloads, self.attr_payloads])
        keys, first = np.unique(keys, return_index=True)
        self.attr_keys, self.attr_payloads = keys, payloads[first]
        found, pos = self._find(self.feat_keys, feat_keys)
        if not found.all():
            raise ValueError("the smoke's delta rewrites existing rows only")
        self.feat_values = self.feat_values.copy()
        self.feat_values[pos] = feat_values


def check_response(ref: Reference, keys, resp, min_version: int) -> None:
    if resp.version < min_version:
        raise AssertionError(f"served version {resp.version} < "
                             f"{min_version}")
    found, payloads = ref.attr(keys)
    got = resp["item_attr"]
    if not (np.array_equal(got.found, found)
            and np.array_equal(got.payloads, payloads)):
        raise AssertionError("item_attr answer differs from the reference")
    found, values = ref.feats(keys)
    got = resp["item_feats"]
    if not (np.array_equal(got.found, found)
            and np.array_equal(got.values, values)):
        raise AssertionError("item_feats answer differs from the reference")


def device_bytes(jax) -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats:
        return "not reported"
    return (f"{stats.get('bytes_in_use', 0)} in use, "
            f"{stats.get('peak_bytes_in_use', 0)} peak")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--small", action="store_true",
                    help="smoke configs at CPU size (rehearsal)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.api import Consistency, FeatureClient
    from repro.configs import registry
    from repro.data import synthetic
    from repro.kernels import ops
    from repro.launch import mesh as mesh_mod
    from repro.launch import serve
    from repro.models import common as cm
    from repro.models import recsys as rec_mod
    from repro.serve.scheduler import BatchPolicy
    from repro.serve.server import QueryServer
    from repro.traffic.loadgen import ZipfianPopularity

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.small:
        print(f"no TPU: JAX found {device}", file=sys.stderr)
        return 2
    cache_dir = mesh_mod.enable_compile_cache()
    compiles = {"n": 0, "s": 0.0}

    def on_event(name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    print(f"device: {device} compile cache: {cache_dir}", flush=True)

    fs_spec, fm_spec = registry.get("bili-feature-store"), \
        registry.get("deepfm")
    fs_cfg = fs_spec.smoke if args.small else fs_spec.config
    cfg = fm_spec.smoke if args.small else fm_spec.config
    n_items = n_embed = fs_cfg.n_items
    if not args.small:
        n_embed = EMBED_ROWS
        print(f"cut: item_feats {fs_cfg.n_items} -> {n_embed} rows of "
              f"{fs_cfg.value_bytes} B (host memory and build time); the "
              f"item_attr index keeps all {n_items} keys", flush=True)
    req_keys = REQUEST_KEYS[args.small]
    # traffic draws from its own stream: the tables' generator is seeded
    # with the bare seed, and sharing it would replay their keys
    rng = np.random.default_rng([args.seed, 1])

    # ---- store ---------------------------------------------------------
    t0 = time.perf_counter()
    attr, feats = serve.feature_tables(fs_cfg, seed=args.seed,
                                       n_items=n_items, n_embed_rows=n_embed)
    t_gen = time.perf_counter() - t0
    engine = serve.build_feature_engine(fs_cfg, attr, feats)
    t_build = time.perf_counter() - t0 - t_gen
    ref = Reference(attr, feats)
    _, _, build = engine.window.get(None)
    arrays = [a for shard in build.shard_arrays for tbl in shard
              for a in tbl.values()]
    on_device = {d.platform for a in arrays for d in a.devices()}
    if on_device != {dev.platform}:
        raise AssertionError(f"index arrays live on {on_device}")
    capacity = sum(t.capacity for shard in build.shard_tables for t in shard)
    print(f"store: {n_items} keys in {build.n_shards} shard(s), "
          f"{capacity} buckets, {sum(a.nbytes for a in arrays)} B of index "
          f"on {dev.platform}; {n_embed} x {fs_cfg.value_bytes} B rows "
          f"(hot fraction {fs_cfg.hot_fraction}); generate {t_gen:.1f} s, "
          f"build {t_build:.1f} s; device bytes {device_bytes(jax)}",
          flush=True)

    popularity = ZipfianPopularity(n_items, serve.ITEM_SKEW)
    server = QueryServer(engine, BatchPolicy(
        max_batch_keys=2 * CLIENTS * req_keys))
    try:
        client = FeatureClient(server)

        # ---- serve -----------------------------------------------------
        def serve_round(tag: str) -> None:
            keys = [[serve.request_keys(rng, popularity, attr.keys,
                                        req_keys, MISS_SHARE)
                     for _ in range(REQUESTS_PER_CLIENT)]
                    for _ in range(CLIENTS)]
            out = [[] for _ in range(CLIENTS)]
            errors: list[BaseException] = []

            def run(c: int):
                try:
                    for k in keys[c]:
                        t = time.perf_counter()
                        resp = client.query({"item_attr": k,
                                             "item_feats": k})
                        out[c].append((k, resp, time.perf_counter() - t))
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    errors.append(e)

            before = dict(compiles)
            t = time.perf_counter()
            threads = [threading.Thread(target=run, args=(c,))
                       for c in range(CLIENTS)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t
            if errors:
                raise errors[0]
            done = [r for rows in out for r in rows]
            for k, resp, _ in done:
                check_response(ref, k, resp, min_version=1)
            lat = np.array([s for *_, s in done]) * 1e3
            print(f"serve {tag}: {len(done)} requests x {req_keys} keys x "
                  f"2 tables from {CLIENTS} clients in {wall:.2f} s, "
                  f"all match the reference; smoke reading, not a "
                  f"benchmark: p50 {np.percentile(lat, 50):.2f} ms, p99 "
                  f"{np.percentile(lat, 99):.2f} ms; compiles "
                  f"{compiles['n'] - before['n']} "
                  f"({compiles['s'] - before['s']:.1f} s)", flush=True)

        serve_round("warm-up")
        serve_round("warm repeat")

        # ---- delta -----------------------------------------------------
        upd = rng.choice(n_items, DELTA_UPDATES, replace=False)
        new = rng.integers(0, 1 << 63, DELTA_NEW_KEYS, dtype=np.uint64)
        new = new[~ref.attr(new)[0]]
        d_keys = np.concatenate([attr.keys[upd], new])
        d_payloads = rng.integers(0, 1 << 52, len(d_keys), dtype=np.uint64)
        f_keys = attr.keys[rng.choice(n_embed, DELTA_UPDATES // 2,
                                      replace=False)]
        f_values = rng.standard_normal(
            (len(f_keys), fs_cfg.value_bytes // 4),
            dtype=np.float32).view(np.uint8)
        t = time.perf_counter()
        client.update(2, upserts={"item_attr": (d_keys, d_payloads),
                                  "item_feats": (f_keys, f_values)})
        t_delta = time.perf_counter() - t
        ref.upsert(d_keys, d_payloads, f_keys, f_values)
        q = np.concatenate([d_keys, f_keys, serve.request_keys(
            rng, popularity, attr.keys, req_keys, MISS_SHARE)])
        resp = client.query({"item_attr": q, "item_feats": q},
                            consistency=Consistency.min_version(2))
        check_response(ref, q, resp, min_version=2)
        print(f"delta: v2 upserts {len(d_keys)} item_attr keys "
              f"({len(new)} new) and {len(f_keys)} item_feats rows, "
              f"published in {t_delta:.2f} s; min_version(2) read-back of "
              f"{len(q)} keys matches", flush=True)

        # ---- score -----------------------------------------------------
        t = time.perf_counter()
        params, step = serve.feature_scoring(cfg, client, seed=args.seed)
        jax.block_until_ready(params)
        t_init = time.perf_counter() - t
        batches = []
        for _ in range(SCORE_BATCHES):
            b = synthetic.recsys_batch(rng, cfg, 512)
            b.pop("label")
            b["item_id"] = serve.request_keys(rng, popularity, attr.keys,
                                              512, MISS_SHARE)
            batches.append(b)
        t = time.perf_counter()
        probs = jax.block_until_ready(step(params, batches[0]))
        t_first = time.perf_counter() - t
        before = compiles["n"]
        for b in batches:
            probs = np.asarray(step(params, b))
            if probs.shape != (512,) or not np.all(np.isfinite(probs)) \
                    or probs.min() < 0 or probs.max() > 1:
                raise AssertionError(f"bad scores: shape {probs.shape}, "
                                     f"range [{probs.min()}, {probs.max()}]")
        score_compiles = compiles["n"] - before

        model_batch = {k: jnp.asarray(v) for k, v in batches[0].items()
                       if k != "item_id"}
        mi = cm.MeshInfo.from_mesh(mesh_mod.make_local_mesh())
        hlo = jax.jit(lambda p, b: rec_mod.recsys_score(p, cfg, b, mi)) \
            .lower(params, model_batch).as_text()
        native_fm = "tpu_custom_call" in hlo
        if native_fm != (dev.platform == "tpu"):
            raise AssertionError(f"scoring step on {dev.platform} has a "
                                 f"native kernel: {native_fm}")
        flat = model_batch["sparse_ids"] + \
            jnp.arange(cfg.n_sparse_fields, dtype=jnp.int32) * cfg.field_vocab
        emb = jnp.take(params["field_table"], flat, axis=0)
        fm = np.asarray(ops.fm_interaction(emb))
        fm_ref = np.asarray(ops.fm_interaction(emb, impl="ref"))
        np.testing.assert_allclose(fm, fm_ref, rtol=1e-5, atol=1e-6)
        print(f"score: {cfg.name} ({cfg.n_sparse_fields} fields x "
              f"{cfg.field_vocab} x {cfg.embed_dim}) params in "
              f"{t_init:.1f} s; first batch (compile) {t_first:.1f} s; "
              f"{SCORE_BATCHES} x 512 rows finite in [0, 1]; compiles in the "
              f"warm repeat {score_compiles}; all compiles so far "
              f"{compiles['n']} ({compiles['s']:.1f} s); native FM kernel "
              f"{native_fm}, max |fm - ref| "
              f"{float(np.abs(fm - fm_ref).max()):.3g}; device bytes "
              f"{device_bytes(jax)}", flush=True)

        snap = server.stats_snapshot()
        print(f"server: {snap.summary()}; failed {snap.failed}", flush=True)
        if snap.shed_queue_full or snap.shed_deadline or snap.failed:
            raise AssertionError("the server shed or failed requests")
    finally:
        server.close()

    print(json.dumps({"ok": dev.platform == "tpu" and not args.small,
                      "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
