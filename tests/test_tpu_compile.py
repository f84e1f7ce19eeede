"""Compile the served path for a described TPU v5e chip — no chip needed.

Interpret mode cannot show what the TPU compiler refuses (tile alignment,
SMEM/VMEM capacity, unsupported gathers), so each kernel and jitted step on
the served path is compiled here at its real size for one chip of a
described ``v5e:2x2`` topology.  The topology is described inside a module
fixture, never at import: one process at a time may load the TPU library,
and every xdist worker imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core import neighborhash as nh
from repro.core.engine import _FusedBuild
from repro.kernels import embedding_bag as bag_mod
from repro.kernels import fused_fm as fm_mod
from repro.kernels import ops

# the bili-feature-store index: 40M keys at load factor 0.8
INDEX_BUCKETS = 50_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but never read back without one; keep this file's compiles out
    of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_fused_fm_compiles_at_deepfm_width(one_chip):
    emb = _sds(one_chip, (512, 39, 10), jnp.float32)
    compiled = _compile(lambda e: fm_mod.fused_fm(e, block_b=128), emb)
    assert "tpu_custom_call" in compiled.as_text()


def test_embedding_bag_compiles_at_lane_width(one_chip):
    table = _sds(one_chip, (1_000_000, 128), jnp.float32)
    idx = _sds(one_chip, (512, 20), jnp.int32)
    compiled = _compile(lambda t, i: bag_mod.embedding_bag(t, i), table, idx)
    assert "tpu_custom_call" in compiled.as_text()


def test_deepfm_serve_step_compiles_with_native_fm(topo, monkeypatch):
    """The whole full-width deepfm/serve_p99 step, as the launcher jits
    it, on one described chip; ops sees a TPU, so FM is the kernel."""
    from repro.launch import cells as cells_mod
    monkeypatch.setattr(ops, "_platform", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    bundle = cells_mod.build_cell("deepfm", "serve_p99", mesh)
    compiled = jax.jit(bundle.fn, in_shardings=bundle.in_shardings).lower(
        *bundle.args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 1.5e9     # 39M x 10 fp32 table


def test_engine_probe_compiles_at_index_size(one_chip):
    """The engine's fused scalar-probe program over a 50M-bucket shard —
    the device half of every served query."""
    table = nh.HashTable(
        variant="neighborhash", capacity=INDEX_BUCKETS, buckets_per_line=8,
        key_hi=None, key_lo=None, val_hi=None, val_lo=None, next_idx=None,
        home_capacity=INDEX_BUCKETS, stats=nh.BuildStats(max_chain_len=8))
    build = _FusedBuild.__new__(_FusedBuild)
    build.shard_tables = [[table]]
    build.scalar_names = ["item_attr"]
    fused = build._make_fused_fn(0)
    arrays = {k: _sds(one_chip, (INDEX_BUCKETS,), jnp.uint32)
              for k in ("key_hi", "key_lo", "val_hi", "val_lo")}
    q = _sds(one_chip, (8192,), jnp.uint32)
    compiled = fused.lower([arrays], [q], [q]).compile()
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        16 * INDEX_BUCKETS
    assert "jit_probe_fused" in compiled.as_text()
