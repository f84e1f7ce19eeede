"""Delta publishing end-to-end + launcher (train/serve CLI) integration."""
import json
import subprocess
import sys

import numpy as np
import pytest

from repro.core.publish import DeltaPublisher
from repro.core.sharding import TableSpec, plan_shards
from repro.core.versioning import ConsistentBatchClient, Generation, \
    ShardReplica

from conftest import subprocess_env


class TestDeltaPublisher:
    def _fleet(self, n_rows=500, n_shards_bytes=2048):
        plan = plan_shards(TableSpec("emb", n_rows, 16), n_shards_bytes)
        reps = [[ShardReplica(s, r) for r in range(2)]
                for s in range(plan.n_shards)]
        keys = np.arange(n_rows, dtype=np.uint64)
        table = np.arange(n_rows, dtype=np.float32)[:, None] * np.ones(4)
        parts = plan.partition(keys)
        for s, rows in enumerate(parts):
            for rep in reps[s]:
                rep.publish(Generation(1, keys[rows], table[rows]))
        return plan, reps, keys, table

    def test_touched_rows_reach_serving(self):
        plan, reps, keys, table = self._fleet()
        pub = DeltaPublisher(plan, reps)
        client = ConsistentBatchClient(reps, plan.shard_of, enforce=True)
        # "train": rows 10..40 change
        table[10:40] += 1000.0
        pub.touch(np.arange(10, 40))
        v = pub.publish(lambda rows: table[rows])
        assert v == 2 and pub.stats.rows_published == 30
        f, vals, versions = client.query(keys[10:40])
        assert f.all() and set(versions) == {2}
        assert (vals[:, 0] >= 1000).all()

    def test_consistency_during_publish(self):
        plan, reps, keys, table = self._fleet()
        pub = DeltaPublisher(plan, reps)
        client = ConsistentBatchClient(reps, plan.shard_of, enforce=True)
        pub.touch(np.arange(0, 200))

        def interleave(ev):
            f, _, versions = client.query(keys[:64])
            assert f.all()
            assert len(set(versions)) == 1, ev

        pub.publish(lambda rows: table[rows], interleave=interleave)
        assert pub.stats.rolling_steps > 0

    def test_empty_publish_is_noop(self):
        plan, reps, keys, table = self._fleet()
        pub = DeltaPublisher(plan, reps)
        assert pub.publish(lambda rows: table[rows]) == 1
        assert pub.stats.publishes == 0


def _run(mod, *args):
    return subprocess.run(
        [sys.executable, "-m", mod, *args],
        capture_output=True, text=True, timeout=900,
        env=subprocess_env())


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["deepfm", "graphsage-reddit"])
def test_train_launcher_smoke(arch):
    r = _run("repro.launch.train", "--arch", arch, "--smoke", "--steps", "3")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout


@pytest.mark.slow
def test_serve_launcher_smoke():
    r = _run("repro.launch.serve", "--arch", "deepfm", "--smoke",
             "--requests", "3")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "p99" in r.stdout


@pytest.mark.slow
def test_serve_launcher_feature_server_smoke():
    """Scoring batches through the QoS-laned FeatureClient (RANKING lane)
    with background PREFETCH traffic riding the same server."""
    r = _run("repro.launch.serve", "--arch", "deepfm", "--smoke",
             "--feature-server", "--clients", "2", "--requests", "2",
             "--prefetch-clients", "1")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "feature-server" in r.stdout and "p99" in r.stdout


def test_dryrun_cli_help():
    r = _run("repro.launch.dryrun", "--help")
    assert r.returncode == 0 and "--multi-pod" in r.stdout


def test_compile_cache_dir_from_env_or_checkout(monkeypatch, tmp_path):
    """The env var wins untouched; otherwise the fixed checkout path."""
    import jax
    from repro.launch import mesh as mesh_mod
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert mesh_mod.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = str(mesh_mod.COMPILE_CACHE_DIR)
        assert want.endswith("artifacts/jax_cache")
        assert mesh_mod.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def _chip_smoke(*args):
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args],
        capture_output=True, text=True, timeout=600,
        env=subprocess_env(pythonpath=None))


@pytest.mark.slow
def test_chip_smoke_rehearsal_on_cpu():
    """--small runs every phase on the CPU and checks every answer; its
    last line reports the CPU and can never claim ok."""
    r = _chip_smoke("--small")
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu",
                                "count": 1}}
    out = r.stdout
    assert out.count("all match the reference") == 2
    assert "min_version(2) read-back" in out and "matches" in out
    assert "native FM kernel False" in out


def test_chip_smoke_refuses_without_tpu():
    r = _chip_smoke()
    assert r.returncode == 2
    assert "no TPU" in r.stderr and r.stdout.strip() == ""
