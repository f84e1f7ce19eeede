"""Find a cell's knee: the highest offered rate it sustains with no growing
backlog. The cell's traffic file then takes about 0.8 of it as its rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 20,40,60

One process builds the deployment once and offers each rate of the cell's
mix (``sessions_per_s``) for ``--seconds`` in turn, deltas flowing where the
mix has them. For each rate it prints one JSON line: the read p50/p99, how
late the generator sent, the median latency of the window's first and last
quarter, how long after the window's end the last answer came (``drain_s``),
the requests served per second up to that answer, failures, and the
freshness p99. A backlog that grows shows as a last
quarter well above the first and a drain of seconds. Answers are not
compared here; ``bench/run.py`` does that.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
import window  # noqa: E402


def offer(dep, engine, client, cfg, mix, data, rate, seconds, seed) -> dict:
    mix = dict(mix, sessions_per_s=rate)
    sched = dep.schedule(mix, cfg, data, seconds, seed)
    first = engine.latest_version + 1
    deltas, update_rows = dep.make_deltas(cfg, sched, seed, first)
    win = window.Window(
        client, sched, data, deltas,
        lambda d: dep.upserts(data, d, update_rows), dep.send,
        clients=int(mix["clients"]), sample=set(), acked=first - 1)
    t0 = time.monotonic() + 0.05
    win.start(t0, seconds, grace_s=20.0)
    win.join()
    ok = win.outcome == window.OK
    took = float(np.nanmax(win.done) - t0) if ok.any() else np.nan
    lat = (win.done - t0 - sched.read_due)[ok] * 1e3
    q = max(len(lat) // 4, 1)
    fresh = win.update_visible() - t0 - sched.update_due
    fresh = fresh[~np.isnan(fresh)] * 1e3
    lag = (win.sent - t0 - sched.read_due) * 1e3
    return {
        "rate": rate, "requests": len(ok), "failed": int((~ok).sum()),
        "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
        "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
        "first_quarter_p50_ms": float(np.median(lat[:q])) if len(lat) else None,
        "last_quarter_p50_ms": float(np.median(lat[-q:])) if len(lat) else None,
        "drain_s": took - seconds if ok.any() else None,
        "served_per_s": float(ok.sum() / took) if ok.any() else None,
        "gen_lag_p99_ms": float(np.nanpercentile(lag, 99)),
        "freshness_p99_ms": float(np.percentile(fresh, 99))
        if len(fresh) else None,
        "publishes": int((~np.isnan(win.pub_end)).sum()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, cfg, mix = run.cell_files(spec, args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    run.use_compile_cache()
    dep = run.deployment(cfg)
    data = dep.generate(cfg, args.seed)
    engine = dep.build_engine(cfg, data)
    server, client = dep.serve(cfg, engine)
    try:
        dep.warm_up(engine, server, client, cfg, mix, data,
                    np.random.default_rng([args.seed, 15]))
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            print(json.dumps(offer(dep, engine, client, cfg, mix, data, rate,
                                   args.seconds, args.seed + k)), flush=True)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
