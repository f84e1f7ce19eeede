"""Batched admission of cold rows into the hot tier
(``HybridKVStore.get_batch``'s ``store.admit`` step).

The admission step takes a batch's distinct cold keys in first-occurrence
order and fills free hot slots with them under one lock hold, one index
probe and one seqlock bump pair.  It must make exactly the choices the
per-key loop it replaced made: ``_reference_admit`` below is that loop,
kept here as the oracle, and twin stores built from one seed must end in
the same state whichever of the two admitted.

Each test runs under a time limit of its own (``_time_limit``), so a
deadlock in the admission path fails the test instead of hanging the
suite.
"""
from __future__ import annotations

import functools
import sys
import threading

import numpy as np
import pytest

from repro.core import hashcore as hc
from repro.core.hybrid_store import SLOT_MASK, TIER_MASK, HybridKVStore
from repro.obs.trace import SpanSink, Tracer

N = 400
VB = 16


def _time_limit(seconds):
    """Run the test body on a daemon thread; fail if it outlives
    ``seconds``."""
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            box = {}

            def body():
                try:
                    fn(*args, **kwargs)
                except BaseException as exc:     # re-raised on the caller
                    box["exc"] = exc
            t = threading.Thread(target=body, daemon=True)
            t.start()
            t.join(seconds)
            if t.is_alive():
                pytest.fail(f"{fn.__name__} did not finish in {seconds} s")
            if "exc" in box:
                raise box["exc"]
        return run
    return wrap


def _data(seed):
    rng = np.random.default_rng(seed)
    keys = rng.permutation(np.unique(
        rng.integers(1, 1 << 40, 2 * N, dtype=np.uint64)))[:N]
    rows = rng.integers(0, 256, (N, VB), dtype=np.uint8)
    return keys, rows


def _store(seed, tmp_path, name):
    keys, rows = _data(seed)
    (tmp_path / name).mkdir()
    return HybridKVStore(keys, rows, hot_fraction=0.1,
                         cold_dir=str(tmp_path / name))


def _reference_admit(store, key):
    """The per-key admission the batched step replaced, verbatim in its
    choices: re-probe under the lock, skip a key no longer cold, stop when
    no slot is free, else pop a slot and repoint the key."""
    with store._lock:
        ok, payload, _, _ = store.index.probe_trace(key)
        if not ok or not (payload & TIER_MASK):
            return
        if not store._hot_free:
            return
        store._write_seq += 1
        try:
            cold_slot = int(payload & np.uint64(SLOT_MASK))
            hot_slot = store._hot_free.pop()
            store._hot_values[hot_slot] = store._cold[cold_slot]
            store._hot_key[hot_slot] = key
            store._hot_last_access[hot_slot] = store._clock
            store.index.update(key, hot_slot)
            with store._stats_lock:
                store.stats.admissions += 1
        finally:
            store._write_seq += 1


def _reference_get_batch(store, keys, between=None):
    """``get_batch`` with the per-key admission loop over the batch's
    distinct cold keys in first-occurrence order; ``between(store)`` runs
    after the read and before admission, as another thread's writes
    would."""
    keys = np.asarray(keys, dtype=np.uint64)
    found, out = store.get_batch(keys, admit=False)
    f, payloads = store.index.lookup_host_batch(keys)
    cold = f & ((payloads & np.uint64(TIER_MASK)) != 0)
    cand = list(dict.fromkeys(keys[cold].tolist()))
    with store._stats_lock:
        store.stats.admit_candidates += len(cand)
    if between is not None:
        between(store)
    for k in cand:
        _reference_admit(store, int(k))
    return found, out


def _get_batch(store, keys, between=None):
    """``store.get_batch``, with ``between(store)`` run just before its
    admission step."""
    if between is None:
        return store.get_batch(keys)
    admit_batch = store._admit_batch

    def hooked(cold_keys):
        between(store)
        return admit_batch(cold_keys)
    store._admit_batch = hooked
    try:
        return store.get_batch(keys)
    finally:
        del store._admit_batch


def _all_keys(store):
    return np.fromiter(store._cold_slot_of_key_order.keys(),
                       dtype=np.uint64)


def _assert_same_state(ref, got):
    assert np.array_equal(ref._hot_key, got._hot_key)
    assert ref._hot_free == got._hot_free
    assert np.array_equal(ref._hot_values, got._hot_values)
    assert np.array_equal(ref._hot_last_access, got._hot_last_access)
    keys = _all_keys(ref)
    assert np.array_equal(keys, _all_keys(got))
    f_ref, p_ref = ref.index.lookup_host_batch(keys)
    f_got, p_got = got.index.lookup_host_batch(keys)
    assert np.array_equal(f_ref, f_got)
    assert np.array_equal(p_ref, p_got)
    assert ref.stats.admissions == got.stats.admissions
    assert ref.stats.admit_candidates == got.stats.admit_candidates
    assert ref._write_seq % 2 == 0 and got._write_seq % 2 == 0


def _cold_keys(store, keys):
    f, p = store.index.lookup_host_batch(keys)
    return keys[f & ((p & np.uint64(TIER_MASK)) != 0)]


def _hot_keys(store, keys):
    f, p = store.index.lookup_host_batch(keys)
    return keys[f & ((p & np.uint64(TIER_MASK)) == 0)]


# -- cases: each prepares one twin (by the real ways free slots appear)
# and returns (store, batches, between): the store to read, the batches,
# and what another thread writes between the first batch's read and its
# admission (or None).  Both twins take the same calls ---------------------

def _case_tier_full(store, keys, rng):
    assert not store._hot_free
    return store, [rng.choice(keys, 200)], None


def _case_fewer_free_than_candidates(store, keys, rng):
    store.delete_batch(_hot_keys(store, keys)[:5])
    cold = _cold_keys(store, keys)
    return store, [rng.permutation(np.concatenate([cold[:60], keys[:20]]))], \
        None


def _case_more_free_than_candidates(store, keys, rng):
    store.maintain(target_free_fraction=0.5)
    return store, [rng.permutation(np.concatenate(
        [_cold_keys(store, keys)[:6], _hot_keys(store, keys)[:6]]))], None


def _case_duplicates(store, keys, rng):
    store.maintain(target_free_fraction=0.2)
    cold = _cold_keys(store, keys)[:12]
    return store, [rng.permutation(np.concatenate([cold, cold, cold[:5]]))], \
        None


def _case_admitted_by_earlier_batch(store, keys, rng):
    store.maintain(target_free_fraction=0.3)
    cold = _cold_keys(store, keys)
    first = cold[:8]
    return store, [first,
                   rng.permutation(np.concatenate([first, cold[8:16]]))], None


def _case_absent_keys(store, keys, rng):
    store.maintain(target_free_fraction=0.2)
    absent = np.arange(1 << 41, (1 << 41) + 10, dtype=np.uint64)
    return store, [rng.permutation(np.concatenate(
        [absent, _cold_keys(store, keys)[:10], keys[:10]]))], None


def _case_moved_since_read(store, keys, rng):
    """Candidates another thread admitted or deleted after the read: the
    admission re-derives them under the lock and skips both."""
    store.maintain(target_free_fraction=0.3)
    cold = _cold_keys(store, keys)[:12]

    def between(s):
        _reference_admit(s, int(cold[2]))
        s.delete_batch(cold[5:7])
    return store, [rng.permutation(cold)], between


def _case_clone_after_cow_upsert(store, keys, rng):
    store.maintain(target_free_fraction=0.2)
    clone = store.clone()
    changed = np.concatenate([_cold_keys(clone, keys)[:10],
                              _hot_keys(clone, keys)[:5],
                              np.arange(1 << 42, (1 << 42) + 5,
                                        dtype=np.uint64)])
    clone.upsert_batch(changed,
                       rng.integers(0, 256, (len(changed), VB),
                                    dtype=np.uint8),
                       copy_on_write=True)
    return clone, [rng.permutation(np.concatenate(
        [changed, _cold_keys(clone, keys)[10:30]]))], None


CASES = {
    "tier_full": _case_tier_full,
    "fewer_free_than_candidates": _case_fewer_free_than_candidates,
    "more_free_than_candidates": _case_more_free_than_candidates,
    "duplicates": _case_duplicates,
    "admitted_by_earlier_batch": _case_admitted_by_earlier_batch,
    "absent_keys": _case_absent_keys,
    "moved_since_read": _case_moved_since_read,
    "clone_after_cow_upsert": _case_clone_after_cow_upsert,
}


@pytest.mark.parametrize("case", sorted(CASES))
@_time_limit(60)
def test_batched_admission_matches_per_key_loop(case, tmp_path):
    seed = 20261018
    keys, _ = _data(seed)
    ref, batches, between = CASES[case](_store(seed, tmp_path, "ref"), keys,
                                        np.random.default_rng(7))
    got, batches_got, between_got = CASES[case](
        _store(seed, tmp_path, "got"), keys, np.random.default_rng(7))
    assert all(np.array_equal(a, b) for a, b in zip(batches, batches_got))
    _assert_same_state(ref, got)
    for i, batch in enumerate(batches):
        f_ref, v_ref = _reference_get_batch(ref, batch,
                                            between if i == 0 else None)
        f_got, v_got = _get_batch(got, batch,
                                  between_got if i == 0 else None)
        assert np.array_equal(f_ref, f_got)
        assert np.array_equal(v_ref, v_got)
        _assert_same_state(ref, got)
    if case == "tier_full":
        assert got.stats.admissions == 0
    elif case != "admitted_by_earlier_batch":
        assert got.stats.admissions > 0
    assert got.stats.admit_candidates > 0


class _Counting:
    """Wraps the store's index methods and counts their calls; records
    ``_write_seq`` as ``update_batch`` sees it."""

    def __init__(self, store):
        self.calls = {"lookup_host_batch": 0, "probe_trace": 0}
        self.seq_in_update = []
        index = store.index
        for name in self.calls:
            setattr(index, name, self._count(name, getattr(index, name)))
        update_batch = index.update_batch

        def seen_update_batch(*args, **kwargs):
            self.seq_in_update.append(store._write_seq)
            return update_batch(*args, **kwargs)
        index.update_batch = seen_update_batch

    def _count(self, name, fn):
        def counted(*args, **kwargs):
            self.calls[name] += 1
            return fn(*args, **kwargs)
        return counted


def _admit_tags(tracer, sink):
    sink.flush()
    spans = [s for s in tracer.peek(sink.trace_id) if s.name == "store.admit"]
    assert len(spans) == 1
    return spans[0].tags


@_time_limit(60)
def test_full_tier_admission_makes_no_probe_and_no_seqlock_bump(tmp_path):
    store = _store(3, tmp_path, "s")
    keys, _ = _data(3)
    cold = _cold_keys(store, keys)[:50]
    assert not store._hot_free and len(cold) == 50
    counting = _Counting(store)
    seq0 = store._write_seq
    tracer = Tracer()
    sink = SpanSink(tracer)
    found, _ = store.get_batch(np.concatenate([cold, cold[:10]]),
                               spans=sink)
    assert found.all()
    # the one lookup is the read's own probe; admission made none
    assert counting.calls == {"lookup_host_batch": 1, "probe_trace": 0}
    assert counting.seq_in_update == []
    assert store._write_seq == seq0
    assert store.stats.admissions == 0
    assert store.stats.admit_candidates == 50
    assert _admit_tags(tracer, sink) == {"candidates": 50, "admitted": 0}


@_time_limit(60)
def test_admission_with_free_slots_is_one_probe_one_bump_pair(tmp_path):
    store = _store(4, tmp_path, "s")
    keys, _ = _data(4)
    store.maintain(target_free_fraction=0.5)
    n_free = len(store._hot_free)
    cold = _cold_keys(store, keys)[:n_free + 7]
    assert len(cold) == n_free + 7
    counting = _Counting(store)
    seq0 = store._write_seq
    assert seq0 % 2 == 0
    tracer = Tracer()
    sink = SpanSink(tracer)
    store.get_batch(cold, spans=sink)
    # the read's probe, then exactly one for the admission
    assert counting.calls == {"lookup_host_batch": 2, "probe_trace": 0}
    # one odd window around the writes: even -> odd -> even
    assert counting.seq_in_update == [seq0 + 1]
    assert store._write_seq == seq0 + 2
    assert store.stats.admissions == n_free and not store._hot_free
    assert _admit_tags(tracer, sink) == {"candidates": n_free + 7,
                                         "admitted": n_free}
    # the admitted keys now read hot, the same bytes
    hits = store.stats.hot_hits
    found, out = store.get_batch(cold[:n_free], admit=False)
    assert found.all()
    assert store.stats.hot_hits == hits + n_free
    assert np.array_equal(out, store._cold[[store._cold_slot_of_key_order[
        int(k)] for k in cold[:n_free]]])


@_time_limit(120)
def test_concurrent_admission_and_async_eviction_match_oracle(tmp_path):
    keys, rows = _data(5)
    store = _store(5, tmp_path, "s")
    oracle = {int(k): rows[i] for i, k in enumerate(keys)}
    absent = np.arange(1 << 41, (1 << 41) + 50, dtype=np.uint64)
    pool = np.concatenate([keys, absent])
    errors = []

    def reader(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(300):
                batch = rng.choice(pool, 64)
                found, out = store.get_batch(batch)
                for k, f, row in zip(batch.tolist(), found, out):
                    want = oracle.get(k)
                    if (want is None) == bool(f) or (
                            f and not np.array_equal(row, want)):
                        errors.append((k, bool(f)))
        except Exception as exc:                 # surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=reader, args=(s,), daemon=True)
               for s in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    store.start_async_eviction(period_s=0.001)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=90)
    finally:
        store.stop_async_eviction()
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert store._write_seq % 2 == 0
    assert store.stats.admissions > 0 and store.stats.evictions > 0
    # no hot slot orphaned or handed out twice
    occupied = np.flatnonzero(store._hot_key != np.uint64(hc.EMPTY_KEY))
    assert len(occupied) + len(store._hot_free) == store.hot_capacity
    assert len(set(store._hot_free)) == len(store._hot_free)
    f, p = store.index.lookup_host_batch(store._hot_key[occupied])
    assert f.all() and np.array_equal(p, occupied.astype(np.uint64))
    f, out = store.get_batch(keys, admit=False)
    assert f.all() and np.array_equal(out, rows)
