"""The fixture deployment ``bench/testdata/two_round.py`` driven through
``run.run_cell`` on the CPU, from a spec built here: two tables over
different key sets, and a second round whose keys come from the first
round's answers, pinned to its version.

    JAX_PLATFORMS=cpu python -m pytest -q bench/test_two_round.py

It comes as new files only (a configuration, a mix, a deployment module),
and the harness takes it as it takes the cells of ``BENCHMARK.json``: a
sound run is correct; the control and each planted fault are not, among
them round 2 half left out and round 2 answered at another version.
"""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import faults  # noqa: E402
import run  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata")
SPEC = {
    "configs": [{"name": "two-round-fixture",
                 "file": "bench/testdata/two_round.json"}],
    "workloads": [{"name": "two-round", "config": "two-round-fixture",
                   "traffic": "two_round.traffic", "chips": 1}],
    "end_to_end": [
        {"name": "setup_s", "unit": "s"},
        {"name": "read_keys_per_s", "unit": "keys/s",
         "workloads": ["two-round"]},
        {"name": "freshness_p99_ms", "unit": "ms",
         "workloads": ["two-round"]}],
}
CFG = run.load_json(os.path.join(TESTDATA, "two_round.json"))
MIX = run.load_json(os.path.join(TESTDATA, "two_round.traffic.json"))
DEP = run.deployment(CFG)
FAULTS = [f for f in faults.GENERIC + DEP.FAULTS if f.applies(CFG, MIX)]


def run_fixture(seed: int = 2**31 + 7, control: bool = False):
    cell = SPEC["workloads"][0]
    return run.run_cell(cell, CFG, MIX,
                        run.cell_metrics(SPEC, cell["name"], False),
                        seed=seed, seconds=2.0, trace=False,
                        control=control)


def test_fixture_names_its_own_module():
    assert DEP.__name__ == "testdata.two_round"
    assert {"drop_half_round2", "round2_off_version"} <= \
        {f.name for f in FAULTS}


def test_sound_run_is_correct():
    res = run_fixture()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "read_keys_per_s",
                                   "freshness_p99_ms"}


def test_control_is_not_correct():
    res = run_fixture(control=True)
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault", FAULTS, ids=[f.name for f in FAULTS])
def test_fault_is_not_correct(fault, monkeypatch):
    fault.plant(monkeypatch)
    res = run_fixture()
    assert not res["correct"]
    assert res["checks"]["wrong_answers"]["value"] > 0
