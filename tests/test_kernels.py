"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import hashcore as hc
from repro.core import neighborhash as nh
from repro.kernels import ops, ref as kref
from repro.kernels import neighbor_lookup as nlk


def _table_and_queries(n, seed, lf=0.8, sqr=0.9):
    keys, payloads = nh.random_kv(n, seed=seed)
    t = nh.build(keys, payloads, variant="neighborhash", load_factor=lf)
    rng = np.random.default_rng(seed)
    n_hit = int(512 * sqr)
    q = np.concatenate([keys[rng.choice(len(keys), n_hit)],
                        rng.integers(2**62, 2**63,
                                     512 - n_hit).astype(np.uint64)])
    qh, ql = hc.key_split_np(q)
    return t, jnp.asarray(qh), jnp.asarray(ql)


@pytest.mark.parametrize("n,lf", [(512, 0.5), (2000, 0.8), (6000, 0.85)])
@pytest.mark.parametrize("impl", ["vec", "amac"])
def test_neighbor_lookup_matches_ref(n, lf, impl):
    t, qh, ql = _table_and_queries(n, seed=n, lf=lf)
    args = [jnp.asarray(x) for x in (t.key_hi, t.key_lo, t.val_hi, t.val_lo)]
    mp = t.max_probe_len() + 1
    rf, rph, rpl = ops.neighbor_lookup(*args, qh, ql, max_probes=mp,
                                       impl="ref")
    f, ph, pl = ops.neighbor_lookup(*args, qh, ql, max_probes=mp, impl=impl,
                                    block_q=128)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(rf))
    np.testing.assert_array_equal(np.asarray(ph), np.asarray(rph))
    np.testing.assert_array_equal(np.asarray(pl), np.asarray(rpl))


@pytest.mark.parametrize("bpl", [4, 8, 32])
@pytest.mark.parametrize("n_slots", [2, 8])
def test_amac_line_sizes_and_slots(bpl, n_slots):
    t, qh, ql = _table_and_queries(1500, seed=bpl * 100 + n_slots)
    args = [jnp.asarray(x) for x in (t.key_hi, t.key_lo, t.val_hi, t.val_lo)]
    mp = t.max_probe_len() + 1
    rf, rph, rpl = ops.neighbor_lookup(*args, qh, ql, max_probes=mp,
                                       impl="ref")
    lines = jnp.asarray(nlk.pack_lines(t.key_hi, t.key_lo, t.val_hi,
                                       t.val_lo, bpl))
    f, ph, pl = ops.neighbor_lookup(*args, qh, ql, max_probes=mp,
                                    impl="amac", lines=lines, bpl=bpl,
                                    block_q=64, n_slots=n_slots)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(rf))
    np.testing.assert_array_equal(np.asarray(pl), np.asarray(rpl))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["sum", "mean"])
@pytest.mark.parametrize("shape", [(16, 4, 8), (37, 9, 32), (8, 1, 128)])
def test_embedding_bag_sweep(dtype, mode, shape):
    b, l, d = shape
    v = 300
    rng = np.random.default_rng(b * l)
    table = jnp.asarray(rng.normal(size=(v, d)), dtype)
    idx = jnp.asarray(rng.integers(-1, v, size=(b, l)), jnp.int32)
    w = jnp.asarray(np.abs(rng.normal(size=(b, l))), jnp.float32)
    for weights in (None, w):
        r = kref.embedding_bag(table, idx, weights, mode)
        k = ops.embedding_bag(table, idx, weights, mode=mode, impl="pallas",
                              bags_per_block=4)
        tol = 1e-5 if dtype == jnp.float32 else 6e-2   # bf16: sum-order noise
        np.testing.assert_allclose(np.asarray(k, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=tol, atol=tol)


def test_embedding_bag_all_padded_bag():
    table = jnp.ones((10, 8), jnp.float32)
    idx = jnp.full((4, 3), -1, jnp.int32)
    out = ops.embedding_bag(table, idx, None, mode="mean", impl="pallas",
                            bags_per_block=4)
    np.testing.assert_allclose(np.asarray(out), 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(64, 39, 10), (130, 7, 16), (8, 2, 4)])
def test_fused_fm_sweep(dtype, shape):
    rng = np.random.default_rng(shape[0])
    emb = jnp.asarray(rng.normal(size=shape), dtype)
    r = kref.fused_fm(emb)
    k = ops.fm_interaction(emb, impl="pallas", block_b=32)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(k), np.asarray(r), rtol=tol,
                               atol=tol)


def test_pack_lines_layout():
    keys, payloads = nh.random_kv(100, seed=5)
    t = nh.build(keys, payloads, variant="neighborhash", capacity=130)
    lines = nlk.pack_lines(t.key_hi, t.key_lo, t.val_hi, t.val_lo, 32)
    assert lines.shape == (-(-130 // 32), 4, 32)
    # bucket 7 lives at line 0, lane 7
    assert lines[0, 0, 7] == t.key_hi[7]
    assert lines[0, 3, 7] == t.val_lo[7]
    # padding is EMPTY
    assert lines[-1, 0, -1] == hc.EMPTY_HI


@pytest.mark.parametrize("shape", [((1000, 10), (64, 4)),       # D % 128
                                   ((1000, 128), (1024, 20))])  # SMEM
def test_embedding_bag_native_shape_checks(shape):
    """Shapes the TPU compiler would refuse raise a ValueError naming the
    cause, before any compile."""
    from repro.kernels import embedding_bag as bag_mod
    table, idx = (jax.ShapeDtypeStruct(s, d) for s, d in
                  zip(shape, (jnp.float32, jnp.int32)))
    with pytest.raises(ValueError, match="multiple of 128|SMEM"):
        jax.eval_shape(lambda t, i: bag_mod.embedding_bag(t, i), table, idx)
    assert bag_mod.smem_bytes(1016, 20) < (1 << 20) - (4 << 10)


def test_fused_fm_native_needs_lane_multiple():
    from repro.kernels import fused_fm as fm_mod
    emb = jax.ShapeDtypeStruct((64, 39, 10), jnp.float32)
    with pytest.raises(ValueError, match="lanes"):
        jax.eval_shape(lambda e: fm_mod.fused_fm(e, block_b=32), emb)


@pytest.mark.parametrize("platform,impl,expect", [
    ("tpu", "auto", ("pallas", False)),
    ("tpu", "ref", ("ref", False)),
    ("cpu", "auto", ("ref", True)),
    ("cpu", "pallas", ("pallas", True)),
    ("gpu", "auto", RuntimeError),
])
def test_ops_platform_policy(monkeypatch, platform, impl, expect):
    """auto runs the native kernel on TPU and ref on CPU; a forced impl
    interprets only on CPU; any other platform raises."""
    class _Dev:
        pass
    dev = _Dev()
    dev.platform = platform
    monkeypatch.setattr(ops.jax, "devices", lambda *a: [dev])
    if expect is RuntimeError:
        with pytest.raises(RuntimeError, match="no kernel policy"):
            ops._resolve(impl, "pallas")
    else:
        assert ops._resolve(impl, "pallas") == expect
    if platform == "tpu":
        assert ops._resolve("auto", "ref") == ("ref", False)
