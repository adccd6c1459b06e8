"""Whole runs of the harness on the CPU, in a throwaway checkout."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest
from conftest import BENCH, ROOT, drive

import compare
import fleetgen
import reference


def test_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "campus1k.plan", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "campus1k.plan",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _sound(result):
    assert result is not None
    assert result["correct"] is True, result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    return result


@pytest.mark.parametrize("workload", ["tiny.plan", "tiny.rollout",
                                      "calm.plan"])
def test_cell_added_as_files_is_found_by_name(checkout, workload):
    rc, result, err = drive(checkout, workload, seconds=1.5)
    assert rc == 0, err[-3000:]
    _sound(result)
    names = set(result["metrics"])
    assert {"setup_s", "period_ms"} <= names
    assert ("period_ms_p95" in names) == workload.endswith(".plan")
    assert ("compared=4 " in err) == (workload == "calm.plan")
    assert result["device"]["count"] == 1
    assert "check acc_gap" in err.strip().splitlines()[-2]


def test_traced_run_reports_per_layer_metrics(checkout):
    rc, result, err = drive(checkout, "tiny.plan", trace=1, seconds=1.5)
    assert rc == 0, err[-3000:]
    _sound(result)
    # the CPU trace has no TPU ops: only the host spans have something
    assert set(result["metrics"]) == {"dispatch_ms", "fetch_ms"}
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("workload", ["tiny.plan", "calm.plan"])
@pytest.mark.parametrize("fault", ["stale_state", "half_fleet",
                                   "altered_plan"])
def test_a_broken_program_is_not_correct(checkout, fault, workload):
    rc, result, err = drive(checkout, workload, fault=fault, seconds=1.0)
    assert rc == 0, err[-3000:]
    assert result is not None and result["correct"] is False, \
        result["checks"]


def test_float32_control_fails_the_limits():
    """The reference computed in float32, in the program's place, fails
    at least one of the limits that float64 keeps (cell size: see
    bench/control.py)."""
    with open(os.path.join(BENCH, "configs", "campus1k.json")) as f:
        config = {**json.load(f), "n_devices": 256, "n_servers": 16}
    with open(os.path.join(BENCH, "traffic", "frames.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "cells", "campus1k.plan.json")) as f:
        limits = json.load(f)["limits"]
    fleet = fleetgen.make_fleet(config, traffic, 24, 2**31 + 5)
    ref = reference.run(fleet, config, 24)
    low = reference.run(fleet, config, 24, np.float32)
    got = compare.compare(compare.reference_as_program(low), ref)
    assert any(got[k] > limits[k] for k in limits), got
    same = compare.compare(compare.reference_as_program(ref), ref)
    assert all(same[k] <= limits[k] for k in limits), same
