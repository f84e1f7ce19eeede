"""The default deployment: one key set shared by every table, each request
one ``client.query`` that asks every table for the same keys, answers
compared with ``bench/reference.py``.

A configuration file without a ``deployment_module`` key runs this one.
A configuration whose deployment differs (its own key sets, request shape,
build or reference) names a module of its own, ``bench/<module>.py``,
which defines what differs and imports the rest from here. The hooks that
``bench/run.py``, ``bench/window.py``, ``bench/sweep.py`` and the CPU tests
call per cell:

``generate(cfg, seed)``                      the data, made from the seed
``schedule(mix, cfg, data, seconds, seed)``  what the window offers: an
    object with ``read_due``, ``update_due``, ``publish_interval_s``,
    ``deltas()`` and ``keys_asked(i)``, the keys request ``i`` asks for
    over every table and round (``read_keys_per_s`` sums them)
``make_deltas(cfg, sched, seed, first_version=2)``  the deltas (versions
    from ``first_version`` on) and update rows
``upserts(data, delta, update_rows)``        one delta's ``client.update``
``build_engine(cfg, data)``, ``serve(cfg, engine, tracer)``  the program
``warm_up(engine, server, client, cfg, mix, data, rng)``     set-up's
    compiles of every shape the cell's traffic reaches
``send(client, sched, data, i, consistency, timeout, sampled)``  the
    window's one call per request; returns a ``window.Answer``
``compare(win, data, deltas, update_rows, cfg)``  the checks, each
    ``[value, limit]``: at least ``wrong_answers``, ``stale_answers``,
    ``unanswered`` and ``failed_publishes``, each with limit 0
``control_client(data, deltas, update_rows, cfg)``  the reference in the
    program's place with the configuration's ``control`` guarantee broken
``small(cfg, mix)``                          the CPU tests' cut
``FAULTS``                                   planted faults (``bench/
    faults.py``) this deployment's cells must catch, besides the generic
"""
from __future__ import annotations

import time

import numpy as np

import deploy
import faults
import reference
import traffic
import window
from deploy import build_engine, generate, make_deltas, serve, upserts  # noqa: F401

FAULTS = [faults.UNCHANGED_STATE]


def schedule(mix: dict, cfg: dict, data, seconds: float, seed: int):
    return traffic.generate(mix, len(data.keys), seconds, seed,
                            [t["name"] for t in cfg["tables"]])


def warm_up(engine, server, client, cfg, mix, data, keys_rng) -> None:
    """Compile every padded probe shape the cell's micro-batches can reach
    (the engine pads each table's unique keys to a power of two, at least
    8), then send a few requests through the client."""
    per_table = int(mix["session_keys"]) + int(mix["fresh_keys"])
    tables = [t["name"] for t in cfg["tables"]]
    scalars = [t["name"] for t in deploy.tables(cfg, "scalar")]
    pol = server.policy
    riders = max(1, min(pol.max_batch_requests,
                        pol.max_batch_keys // (per_table * len(tables))))
    most = min(riders * per_table, len(data.keys))
    p = 8
    while True:
        engine.query({t: data.keys[:p] for t in scalars})
        if p >= most:
            break
        p <<= 1
    zipf = traffic.Zipf(len(data.keys), mix["zipf"])
    for _ in range(4):
        ranks = zipf.sample(keys_rng, per_table)
        client.query({t: data.keys[ranks] for t in tables})


def send(client, sched, data, i, consistency, timeout, sampled):
    """Every table asked for request ``i``'s keys in one query. Kept: each
    scalar table's found flags and payloads, and where ``sampled`` each
    embedding table's rows."""
    keys = sched.keys_of(i, data.keys)
    resp = client.query({t: keys for t in sched.tables},
                        consistency=consistency, timeout=timeout)
    done = time.monotonic()
    scalars = {t: (np.array(resp.tables[t].found),
                   np.array(resp.tables[t].payloads))
               for t in data.payloads}
    rows = {t: np.array(resp.tables[t].values) for t in data.rows} \
        if sampled else None
    return window.Answer(done, resp.version, (scalars, rows))


def compare(win, data, deltas, update_rows, cfg) -> dict:
    """Every answer's scalar tables, and the sampled answers' rows, against
    the reference at the version the answer reports. Returns the counts
    compared, each beside its limit."""
    ref = reference.Reference(data, deltas)
    ok = win.outcome == window.OK
    wrong = np.zeros(len(ok), dtype=bool)
    for v in np.unique(win.version[ok]):
        ref.advance(int(v))
        idx = np.flatnonzero(ok & (win.version == v))
        keys = [win.sched.keys_of(int(i), data.keys) for i in idx]
        cut = np.cumsum([0] + [len(k) for k in keys])
        flat = np.concatenate(keys)
        for t in data.payloads:
            found, pay = ref.scalar(t, flat)
            got_f = np.concatenate([win.kept[i][0][t][0] for i in idx])
            got_p = np.concatenate([win.kept[i][0][t][1] for i in idx])
            bad = (got_f != found) | (got_p != pay)
            wrong[idx] |= np.add.reduceat(bad, cut[:-1]) > 0
        for j, i in enumerate(idx):
            for t, values in (win.kept[i][1] or {}).items():
                found, rows = ref.rows(t, keys[j], update_rows)
                if not np.array_equal(values, rows):
                    wrong[i] = True
    return checks(win, wrong)


def checks(win, wrong: np.ndarray) -> dict:
    """The four checks every deployment reports, limits 0, from its own
    per-request ``wrong`` flags and the window's outcomes."""
    ok = win.outcome == window.OK
    stale = (win.outcome == window.STALE) | \
        (ok & (win.version < win.min_version))
    return {
        "wrong_answers": [int(wrong.sum()), 0],
        "stale_answers": [int(stale.sum()), 0],
        "unanswered": [int((~ok & (win.outcome != window.STALE)).sum()), 0],
        "failed_publishes": [int(np.isnan(win.pub_end).sum()), 0],
    }


def control_client(data, deltas, update_rows, cfg):
    return reference.ControlClient(data, deltas, update_rows, cfg["control"])


def small(cfg: dict, mix: dict) -> tuple[dict, dict]:
    """The configuration and mix at a size the CPU runs in seconds."""
    cfg, mix = dict(cfg), dict(mix)
    cfg["n_items"] = 20_000
    mix["fresh_keys"] = max(mix["fresh_keys"] // 16, 8)
    mix["session_keys"] //= 4
    mix["sessions_per_s"] = min(mix["sessions_per_s"], 20.0)
    return cfg, mix
