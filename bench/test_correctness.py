"""The comparison that decides ``correct``, driven on the CPU at a small size.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_correctness.py

Every cell of ``BENCHMARK.json``, at its deployment's ``small`` cut, goes
through ``run.run_cell`` whole (data, build, warm-up, window, comparison),
skipping only the harness's look for a chip. A sound run must come out
correct; the control (the reference in the program's place with the
configuration's ``control`` guarantee broken) and each fault planted in the
program underneath must come out not correct: the generic faults of
``bench/faults.py`` wherever the cell's tables have the part they alter,
and the deployment's own ``FAULTS``. The cells run on one chip, so no
exchange between chips exists to leave out.
"""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import faults  # noqa: E402
import run  # noqa: E402

SPEC = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in SPEC["workloads"]]


def small(name: str):
    """The cell's own configuration and mix at its deployment's CPU cut."""
    cell, cfg, mix = run.cell_files(SPEC, name)
    cfg, mix = run.deployment(cfg).small(cfg, mix)
    return cell, cfg, mix, run.cell_metrics(SPEC, name, False)


def cell_faults(name: str) -> list:
    """The generic faults whose part the cell's tables have, and its
    deployment's own."""
    _, cfg, mix, _ = small(name)
    return [f for f in faults.GENERIC + run.deployment(cfg).FAULTS
            if f.applies(cfg, mix)]


def run_small(name: str, seed: int = 2**31 + 5, control: bool = False):
    cell, cfg, mix, metrics = small(name)
    return run.run_cell(cell, cfg, mix, metrics, seed=seed, seconds=2.0,
                        trace=False, control=control)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = run_small(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["wrong_answers"]["value"] == 0
    assert set(res["metrics"]) == {m["name"] for m in small(name)[3]}


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    res = run_small(name, control=True)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


FAULTS = [(name, f) for name in CELLS for f in cell_faults(name)]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.name}" for n, f in FAULTS])
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault.plant(monkeypatch)
    res = run_small(name)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_no_tpu_exits_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[-1], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""


def test_every_cell_and_metric_has_its_files():
    for name in CELLS:
        cell, cfg, mix = run.cell_files(SPEC, name)
        assert cfg["name"] == cell["config"]
        assert run.deployment(cfg) is not None
        for trace in (False, True):
            for m in run.cell_metrics(SPEC, name, trace):
                assert os.path.exists(run.metric_reader(m["name"])), \
                    m["name"]


def test_split_metric_takes_its_quantitys_reader():
    reader = os.path.join(BENCH, "metrics", "engine.finish_ms.py")
    assert run.metric_reader("engine.finish_ms.throughput") == reader
    assert run.metric_reader("engine.finish_ms") == reader


def test_config_without_module_runs_the_default():
    default = run.deployment({})
    assert default.__name__ == "deployments.default"
    assert run.deployment({"deployment_module": "deployments/default"}) \
        is default
    for name in ("../run", "/etc/passwd", "deployments/none", "a.b"):
        with pytest.raises(ValueError):
            run.deployment({"deployment_module": name})
