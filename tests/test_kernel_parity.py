"""Interpret-mode parity: Pallas ``lookup_vec`` / ``lookup_amac`` vs the
pure-jnp oracle (kernels/ref.py) on the regimes the sweep tests don't pin
down — skewed hit/miss mixes, batch sizes that don't divide the tile, and
empty-chain / lodger edge cases."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hashcore as hc
from repro.core import neighborhash as nh
from repro.kernels import neighbor_lookup as nlk
from repro.kernels import ops


def _build(n, seed, lf=0.8):
    keys, payloads = nh.random_kv(n, seed=seed)
    return keys, payloads, nh.build(keys, payloads, variant="neighborhash",
                                    load_factor=lf)


def _queries(keys, n_q, hit_rate, seed):
    rng = np.random.default_rng(seed)
    n_hit = int(round(n_q * hit_rate))
    q = np.concatenate([
        keys[rng.integers(0, len(keys), n_hit)],
        rng.integers(2**62, 2**63, n_q - n_hit).astype(np.uint64)])
    rng.shuffle(q)
    return q


def _run_both(t, q, impl, block_q=256, **kw):
    qh, ql = hc.key_split_np(q)
    qh, ql = jnp.asarray(qh), jnp.asarray(ql)
    args = [jnp.asarray(x) for x in (t.key_hi, t.key_lo, t.val_hi, t.val_lo)]
    mp = t.max_probe_len() + 1
    ref = ops.neighbor_lookup(*args, qh, ql, max_probes=mp, impl="ref")
    got = ops.neighbor_lookup(*args, qh, ql, max_probes=mp, impl=impl,
                              block_q=block_q, **kw)
    for r, g, what in zip(ref, got, ("found", "p_hi", "p_lo")):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r), what)


@pytest.mark.parametrize("impl", ["vec", "amac"])
@pytest.mark.parametrize("hit_rate", [0.0, 0.5, 1.0])
def test_hit_miss_mixes(impl, hit_rate):
    keys, _, t = _build(3000, seed=17)
    q = _queries(keys, 512, hit_rate, seed=3)
    _run_both(t, q, impl)


@pytest.mark.parametrize("impl", ["vec", "amac"])
@pytest.mark.parametrize("n_q", [1, 100, 255, 257, 777])
def test_batch_not_multiple_of_tile(impl, n_q):
    """ops pads to block_q and slices back; results must be exact for any N,
    including N < block and N = block ± 1."""
    keys, _, t = _build(2000, seed=n_q)
    q = _queries(keys, n_q, 0.7, seed=n_q)
    _run_both(t, q, impl, block_q=256)


@pytest.mark.parametrize("impl",
                         [("vec", nlk.lookup_vec), ("amac", nlk.lookup_amac)])
def test_raw_kernels_reject_undivisible_batch(impl):
    name, fn = impl
    keys, _, t = _build(600, seed=9)
    qh, ql = hc.key_split_np(keys[:100])
    args = dict(capacity=t.capacity, max_probes=3, block_q=64)
    with pytest.raises(ValueError, match="pad at call site"):
        if name == "vec":
            fn(jnp.asarray(t.key_hi), jnp.asarray(t.key_lo),
               jnp.asarray(t.val_hi), jnp.asarray(t.val_lo),
               jnp.asarray(qh), jnp.asarray(ql), **args)
        else:
            lines = jnp.asarray(nlk.pack_lines(t.key_hi, t.key_lo,
                                               t.val_hi, t.val_lo, 8))
            fn(lines, jnp.asarray(qh), jnp.asarray(ql), bpl=8, **args)


@pytest.mark.parametrize("impl", ["vec", "amac"])
def test_sparse_table_empty_buckets(impl):
    """LF 0.25: most probes land on EMPTY buckets (immediate miss, no
    chain) — the empty-chain fast path."""
    keys, _, t = _build(400, seed=23, lf=0.25)
    q = _queries(keys, 256, 0.3, seed=5)
    _run_both(t, q, impl, block_q=64)


@pytest.mark.parametrize("impl", ["vec", "amac"])
def test_lodger_resident_is_a_miss(impl):
    """A query whose home bucket holds a lodger (resident homed elsewhere)
    must miss WITHOUT following that resident's chain — the home-purity
    check in the kernels."""
    keys, payloads, t = _build(1500, seed=31, lf=0.95)
    # find occupied buckets whose resident is a lodger, then synthesize
    # query keys homing exactly there
    occ = np.flatnonzero(t.key_hi != np.uint32(hc.EMPTY_HI))
    lodger_buckets = [
        int(i) for i in occ
        if hc.bucket_of_int(int(t.key_hi[i]), int(t.key_lo[i]),
                            t.home_capacity) != int(i)]
    assert lodger_buckets, "LF 0.95 build produced no lodgers?"
    targets = set(lodger_buckets[:8])
    inserted = set(int(k) for k in keys)
    found_q = []
    cand = np.arange(2**40, 2**40 + 2_000_000, dtype=np.uint64)
    hi, lo = hc.key_split_np(cand)
    homes = hc.bucket_of_np(hi, lo, t.home_capacity)
    for k, h in zip(cand.tolist(), homes.tolist()):
        if h in targets and k not in inserted:
            found_q.append(k)
        if len(found_q) >= 64:
            break
    q = np.array(found_q, dtype=np.uint64)
    # host oracle agrees these are misses
    fh, _ = t.lookup_host(q)
    assert not fh.any()
    _run_both(t, q, impl, block_q=64)


@pytest.mark.parametrize("impl", ["vec", "amac"])
def test_single_entry_table(impl):
    keys = np.array([12345], dtype=np.uint64)
    payloads = np.array([777], dtype=np.uint64)
    t = nh.build(keys, payloads, variant="neighborhash")
    q = np.array([12345, 54321, 12345], dtype=np.uint64)
    _run_both(t, q, impl, block_q=64)


# ---------------------------------------------------------------------------
# embedding_bag / fm_interaction: Pallas (interpret off-TPU) vs ref oracle.
# tools.analyze's kernel-oracle gate requires every public ops kernel to be
# exercised here by name.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_bags", [1, 7, 8, 9, 100])
@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_embedding_bag_parity(n_bags, mode):
    rng = np.random.default_rng(n_bags)
    table = jnp.asarray(rng.normal(size=(512, 16)).astype(np.float32))
    idx = rng.integers(-1, 512, size=(n_bags, 12)).astype(np.int32)
    idx[0, :] = -1                       # fully-padded bag -> zeros / safe mean
    indices = jnp.asarray(idx)
    ref = ops.embedding_bag(table, indices, mode=mode, impl="ref")
    got = ops.embedding_bag(table, indices, mode=mode, impl="pallas",
                            bags_per_block=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_embedding_bag_weighted_parity():
    rng = np.random.default_rng(7)
    table = jnp.asarray(rng.normal(size=(256, 8)).astype(np.float32))
    indices = jnp.asarray(
        rng.integers(-1, 256, size=(33, 6)).astype(np.int32))
    weights = jnp.asarray(rng.normal(size=(33, 6)).astype(np.float32))
    ref = ops.embedding_bag(table, indices, weights, impl="ref")
    got = ops.embedding_bag(table, indices, weights, impl="pallas",
                            bags_per_block=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n_b", [1, 127, 128, 129])
def test_fm_interaction_parity(n_b):
    rng = np.random.default_rng(n_b)
    emb = jnp.asarray(rng.normal(size=(n_b, 13, 8)).astype(np.float32))
    ref = ops.fm_interaction(emb, impl="ref")
    got = ops.fm_interaction(emb, impl="pallas", block_b=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
