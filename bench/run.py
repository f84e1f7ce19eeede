"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; it names a
configuration (``bench/configs/<config>.json``, per its ``file``) and a
traffic mix (``bench/traffic/<traffic>.json``). The configuration's
``deployment_module`` names the module of hooks that generates, builds,
sends and compares for it (``bench/deployments/default.py`` where it names
none; see ``deployment``). Each metric the cell reports is read by its own
reader, ``bench/metrics/<metric>.py``, whose ``read(run)`` returns a number
or None (nothing to read); see ``metric_reader`` for a quantity split by
what it moves.

One process does everything, because the chip belongs to one process:
it makes the data from ``--seed``, builds the deployment through the
program's own constructors, warms up every shape the cell's traffic
reaches, then drives the window (``bench/window.py``) with client threads
and, where the mix has updates, a publisher thread. With ``--trace 1`` the
server records spans and a profiler trace is taken of a few seconds inside
the window. Once the window has closed, device memory has been read and
the program is freed, every answer is compared with the deployment's plain
reference at the version it reports.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result. ``--control`` serves the window from the reference with
the configuration's ``control`` guarantee broken; its result must come out
as not correct (``bench/test_correctness.py``).
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()     # set-up is counted from here

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402
import window  # noqa: E402

COMPILE_CACHE = os.path.join(ROOT, "artifacts", "jax_cache")
ROW_SAMPLE = 64                   # requests whose rows are compared
TRACE_S = 4.0                     # profiled part of a --trace 1 window
SPAN_SAMPLE = 0.1                 # share of requests the server traces
BATCH_SPANS = ("coalesce", "version_pin", "begin", "finish", "scatter")
DEFAULT_DEPLOYMENT = "deployments/default"
MODULE_PATH = re.compile(r"[A-Za-z_][A-Za-z0-9_]*(/[A-Za-z_][A-Za-z0-9_]*)*")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(spec: dict, name: str):
    """``(cell, config, mix)`` for the cell ``name`` of ``BENCHMARK.json``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return (cell, load_json(os.path.join(ROOT, cfg["file"])),
            load_json(os.path.join(BENCH, "traffic",
                                   cell["traffic"] + ".json")))


def deployment(cfg: dict):
    """The module of hooks for configuration ``cfg``: ``bench/<m>.py`` for
    its ``"deployment_module": "<m>"``, or the default deployment."""
    name = cfg.get("deployment_module", DEFAULT_DEPLOYMENT)
    if not MODULE_PATH.fullmatch(name) or \
            not os.path.isfile(os.path.join(BENCH, name + ".py")):
        raise ValueError(f"deployment_module {name!r} names no module "
                         f"bench/<path>.py")
    return importlib.import_module(name.replace("/", "."))


def cell_metrics(spec: dict, name: str, trace: bool) -> list:
    """The metrics this cell reports: its end-to-end ones, or with
    ``trace`` its per-layer ones."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def metric_reader(name: str) -> str:
    """Path of the reader of ``name``. A metric ``<quantity>.<suffix>`` with
    no reader of its own is read by ``<quantity>``'s: the suffix only splits
    a quantity whose cells report different end-to-end metrics."""
    base = name
    while True:
        path = os.path.join(BENCH, "metrics", base + ".py")
        if os.path.exists(path) or "." not in base:
            return path
        base = base.rsplit(".", 1)[0]


def read_metric(name: str, run) -> float | None:
    path = metric_reader(name)
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


# ---------------------------------------------------------------------------
class CompileLog:
    """Times at which this process obtained a compiled program: every one
    raises JAX's backend-compile event, also when it was loaded from the
    persistent cache, which raises a cache-hit event besides."""

    def __init__(self):
        import jax
        self.times, self.hits = [], []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.monotonic())

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.monotonic())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.times)


class GcLog:
    """Pauses of Python's garbage collector, per generation: a full pass
    over the objects set-up left behind stops every thread at once."""

    def __init__(self):
        self.pauses = []                # (generation, start, end)
        self._start = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._start = time.monotonic()
        elif self._start is not None:
            self.pauses.append((info["generation"], self._start,
                                time.monotonic()))
            self._start = None

    def close(self):
        gc.callbacks.remove(self._callback)

    def between(self, lo: float, hi: float) -> dict:
        """Per generation, the count and the longest pause in ms."""
        out = {}
        for g, s, e in self.pauses:
            if lo <= s <= hi:
                n, most = out.get(g, (0, 0.0))
                out[g] = (n + 1, max(most, (e - s) * 1e3))
        return {f"gen{g}": [n, round(most, 3)]
                for g, (n, most) in sorted(out.items())}


class StoreTally:
    """Hybrid-store tier counters summed over every store that served in
    the window (each delta publishes a cloned store with fresh counters).
    At most three builds are held, the engine retaining two."""

    def __init__(self, engine):
        self.engine = engine
        self.live = collections.OrderedDict()     # id -> store
        self.base = {}
        self.total = collections.Counter()
        self._add_current(baseline=True)

    def _add_current(self, baseline=False):
        ok, _, build = self.engine.window.get(None)
        for store in build.stores.values():
            if id(store) not in self.live:
                self.live[id(store)] = store
                if baseline:
                    self.base[id(store)] = store.stats_snapshot()

    def _settle(self, key):
        store = self.live.pop(key)
        snap, base = store.stats_snapshot(), self.base.get(key)
        for f in ("lookups", "hot_hits", "cold_misses", "not_found"):
            self.total[f] += getattr(snap, f) - \
                (getattr(base, f) if base else 0)

    def on_publish(self, _version):
        self._add_current()
        while len(self.live) > 3:
            self._settle(next(iter(self.live)))

    def finish(self) -> dict:
        for key in list(self.live):
            self._settle(key)
        return dict(self.total)


# ---------------------------------------------------------------------------
def use_compile_cache() -> None:
    """Keep every compiled program in the checkout's cache, none evicted: a
    size limit from the environment turns on eviction, which needs a stamp
    file beside every entry and drops entries the next run would load."""
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell: dict, cfg: dict, mix: dict, metrics: list, *, seed: int,
             seconds: float, trace: bool, control: bool = False) -> dict:
    use_compile_cache()
    compiles = CompileLog()
    pauses = GcLog()
    tmp = tempfile.mkdtemp(prefix="bench-")
    tempfile.tempdir = tmp          # the cold tier's files go here
    try:
        return _run(cell, cfg, mix, metrics, seed=seed, seconds=seconds,
                    trace=trace, control=control, compiles=compiles,
                    pauses=pauses, tmp=tmp)
    finally:
        pauses.close()
        tempfile.tempdir = None
        shutil.rmtree(tmp, ignore_errors=True)


def _run(cell, cfg, mix, metrics, *, seed, seconds, trace, control,
         compiles, pauses, tmp) -> dict:
    import jax
    dep = deployment(cfg)
    split = {}
    t = time.monotonic()
    data = dep.generate(cfg, seed)
    sched = dep.schedule(mix, cfg, data, seconds, seed)
    deltas, update_rows = dep.make_deltas(cfg, sched, seed)
    split["generate"] = time.monotonic() - t

    engine = server = tally = tracer = None
    t = time.monotonic()
    if control:
        client = dep.control_client(data, deltas, update_rows, cfg)
        split["build"] = time.monotonic() - t
    else:
        from repro.obs.trace import Tracer
        engine = dep.build_engine(cfg, data)
        split["build"] = time.monotonic() - t
        t = time.monotonic()
        _, _, build = engine.window.get(None)
        jax.block_until_ready(build.shard_arrays)
        split["upload"] = time.monotonic() - t
        if trace:
            tracer = Tracer(sample_rate=SPAN_SAMPLE, capacity=1 << 20)
        server, client = dep.serve(cfg, engine, tracer)
        t = time.monotonic()
        dep.warm_up(engine, server, client, cfg, mix, data,
                    np.random.default_rng([seed, 15]))
        split["warmup"] = time.monotonic() - t
        if trace:
            tally = StoreTally(engine)

    n_reads = len(sched.read_due)
    sample_rng = np.random.default_rng([seed, 14])
    sample = set(sample_rng.choice(n_reads, min(ROW_SAMPLE, n_reads),
                                   replace=False).tolist())
    sample.add(n_reads - 1)
    win = window.Window(
        client, sched, data, deltas,
        lambda d: dep.upserts(data, d, update_rows), dep.send,
        clients=int(mix["clients"]), sample=sample,
        on_publish=tally.on_publish if tally else None)

    split["compiled"] = len(compiles.times)
    split["cache_hits"] = len(compiles.hits)
    t0 = time.monotonic() + 0.05
    setup_s = t0 - T_PROCESS
    server0 = server.stats_snapshot() if server else None
    win.start(t0, seconds)
    traced = None
    if trace and not control:
        traced = _trace_window(win, server, t0, seconds, tmp)
    time.sleep(max(t0 + seconds - time.monotonic(), 0.0))
    joined = win.join()
    in_window = compiles.between(t0, time.monotonic())
    gc_window = pauses.between(t0, t0 + seconds)

    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in jax.local_devices()[:int(cell["chips"])])
    server1 = server.stats_snapshot() if server else None
    tier = tally.finish() if tally else None
    spans = _spans(tracer) if tracer else None
    if server is not None:
        server.close()
    del engine, server, client, tally, tracer
    gc.collect()

    if traced is not None:
        host = [s for s in spans if s[0] in BATCH_SPANS]
        host = list(set(host)) + [
            ("publish", s, e) for s, e in zip(win.pub_start, win.pub_end)
            if not np.isnan(e)]
        traced.update(trace_reduce.reduce(
            traced.pop("path"), sync_mono=traced["sync"],
            lo_mono=traced["lo"], hi_mono=traced["hi"], host_spans=host))
        shutil.rmtree(os.path.join(tmp, "trace"), ignore_errors=True)

    checks = dep.compare(win, data, deltas, update_rows, cfg)
    if not joined:
        checks["unanswered"][0] += 1

    dev = jax.devices()[0]
    run = SimpleNamespace(
        t0=t0, setup_s=setup_s,
        read_due=t0 + sched.read_due, read_sent=win.sent,
        read_done=win.done, read_ok=win.outcome == window.OK,
        read_keys=np.array([sched.keys_asked(i) for i in range(n_reads)],
                           dtype=np.int64),
        update_due=t0 + sched.update_due,
        update_visible=win.update_visible(),
        pub_start=win.pub_start, pub_end=win.pub_end,
        server0=server0, server1=server1, tier=tier, spans=spans,
        trace=traced,
        peaks=trace_reduce.load_peaks(dev.device_kind)
        if dev.platform == "tpu" else None)
    values = {}
    for m in metrics:
        v = read_metric(m["name"], run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    correct = all(v <= lim for v, lim in checks.values()) and \
        bool((win.outcome == window.OK).any())
    n_failed = int((win.outcome != window.OK).sum()) + \
        checks["failed_publishes"][0]
    result = {
        "correct": bool(correct),
        "attempted": n_reads + len(deltas),
        "failed": n_failed,
        "metrics": values,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": int(memory_peak)},
    }
    if traced is not None:
        result["device"]["busy_s"] = traced["busy_s"]
        result["device"]["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    ok = win.outcome == window.OK
    lat = (win.done - t0 - sched.read_due)[ok] * 1e3
    lag = (win.sent - t0 - sched.read_due)[~np.isnan(win.sent)] * 1e3
    result["_window"] = {
        "p50_ms_by_quarter": [round(float(np.median(q)), 3)
                              for q in np.array_split(lat, 4) if len(q)],
        "lag_p99_ms": round(float(np.percentile(lag, 99)), 3)
        if len(lag) else None,
        "lag_max_ms": round(float(lag.max()), 3) if len(lag) else None,
        "gc_passes": gc_window}
    result["_setup"] = dict(split, setup_s=setup_s,
                            compiles_in_window=in_window,
                            errors=win.errors[:5])
    return result


def _trace_window(win, server, t0, seconds, tmp) -> dict:
    """Profile ``TRACE_S`` seconds inside the window: counters at both
    ends, the clock tie, and the trace's path."""
    import jax
    lo = t0 + min(1.0, seconds / 4)
    time.sleep(max(lo - time.monotonic(), 0.0))
    # no Python tracer: it records every Python call of every thread, which
    # slows the host path it would be measuring by orders of magnitude
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(tmp, "trace"),
                             profiler_options=options)
    sync = time.monotonic()
    with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
        pass
    s0 = server.stats_snapshot()
    hi = min(sync + TRACE_S, t0 + seconds * 0.9)
    time.sleep(max(hi - time.monotonic(), 0.0))
    s1 = server.stats_snapshot()
    hi = time.monotonic()
    jax.profiler.stop_trace()
    return {"sync": sync, "lo": sync, "hi": hi,
            "keys_deviceside": s1.keys_deviceside - s0.keys_deviceside,
            "path": trace_reduce.find_xplane(os.path.join(tmp, "trace"))}


def _spans(tracer) -> list:
    return [(s.name, s.t0, s.t1) for tid in tracer.trace_ids()
            for s in tracer.peek(tid)]


def emit(result: dict) -> None:
    setup = result.pop("_setup")
    print("window: " + json.dumps(result.pop("_window")), flush=True)
    errors = setup.pop("errors")
    counts = {k: setup.pop(k) for k in ("compiled", "cache_hits",
                                         "compiles_in_window") if k in setup}
    print("setup: " + ", ".join(f"{k} {v:.3f} s" for k, v in setup.items())
          + f"; programs built in set-up: {counts.get('compiled', 0)} "
          + f"({counts.get('cache_hits', 0)} of them from the compile "
          + f"cache); built in the window: {counts['compiles_in_window']}",
          flush=True)
    for e in errors:
        print(f"error: {e}", file=sys.stderr)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve from the reference with the configuration's "
                         "control guarantee broken")
    args = ap.parse_args(argv)
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, mix = cell_files(spec, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"no TPU with {cell['chips']} chip(s): JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = run_cell(cell, cfg, mix,
                      cell_metrics(spec, args.workload, bool(args.trace)),
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), control=args.control)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
