"""Whole runs on the CPU at a small budget: sound runs are correct, and
every fault the cell can have makes ``correct`` false."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

import drive

HERE = pathlib.Path(__file__).resolve().parent

CASES = [
    ("ideal_sweep", "state_unchanged"),
    ("ideal_sweep", "half_batch"),
    ("ideal_sweep", "answer_altered"),
    ("living_drift", "state_unchanged"),
    ("living_drift", "half_batch"),
    ("living_drift", "answer_altered"),
    ("ideal_point", "state_unchanged"),
    ("ideal_point", "answer_altered"),
    ("ideal_sweep", "traffic_altered"),
    ("ideal_point", "latency_altered"),
]


@pytest.mark.parametrize("workload", ["ideal_sweep", "living_drift",
                                      "ideal_point"])
def test_sound_run_is_correct(workload):
    res = drive.run(workload, "none", 2**31 + 12345)
    assert res["correct"], res["checks"]
    assert res["checks"]["int_mismatches"]["value"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(workload, fault):
    res = drive.run(workload, fault, 4242)
    assert not res["correct"], res["checks"]


def _four_devices(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, str(HERE / "drive.py"), "ideal_sweep_4chip", fault,
         "99"], env=env, capture_output=True, text=True, timeout=900,
        check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["none", "exchange_dropped"])
def test_four_chip_exchange(fault):
    res = _four_devices(fault)
    assert res["device"]["count"] == 4
    assert res["correct"] == (fault == "none"), res["checks"]
