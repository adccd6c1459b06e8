"""Shared fixtures of the benchmark's own tests (CPU only).

``checkout`` builds a throwaway copy of the benchmark: ``BENCHMARK.json``
and ``bench/`` copied, the program's ``src/`` linked, and two tiny
configurations (one with stragglers, outages and per-device jitter), a
Poisson traffic mix in two deliveries (one period and four periods per
call), three cells and a per-layer metric added as files plus manifest
entries, exactly as a later change would add them."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

TINY_CELLS = {
    "tiny.plan": {"chips": 1},
    "tiny.rollout": {"chips": 1, "traffic": "busy4"},
    # the paper's frames on a calm fleet, a sample of its periods checked
    "calm.plan": {"chips": 1, "config": "calm", "traffic": "frames",
                  "check_sample": 4},
}
# what the generator can also draw, though no configuration of the
# manifest asks for it: per-device jitter, stragglers and outages
CHAOS = {"straggler_frac": 0.25, "drift_mag": 3.0, "outage_frac": 0.1,
         "outage_prob": 0.2, "schedule_periods": 64}


def add_files(root):
    """Add the tiny configurations, a traffic mix and their cells."""
    man_path = os.path.join(root, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "campus1k.json")) as f:
        base = json.load(f)
    profile = {**base["paper_profile"], "jitter_ed": [0.8, 1.3],
               "jitter_es": [0.9, 1.2]}
    with open(os.path.join(bench, "configs", "tiny.json"), "w") as f:
        json.dump({**base, **CHAOS, "name": "tiny", "n_devices": 48,
                   "n_servers": 3, "paper_profile": profile}, f)
    with open(os.path.join(bench, "configs", "calm.json"), "w") as f:
        json.dump({**base, "name": "calm", "n_devices": 48,
                   "n_servers": 3}, f)
    for name in ("tiny", "calm"):
        man["configs"].append({"name": name, "source": "test",
                               "file": f"bench/configs/{name}.json",
                               "reduced": ["n_devices", "n_servers"],
                               "why": "test"})
    with open(os.path.join(bench, "traffic", "frames.json")) as f:
        traffic = json.load(f)
    busy = {**traffic, "name": "busy", "arrivals": "poisson", "rate": 14.0}
    with open(os.path.join(bench, "traffic", "busy.json"), "w") as f:
        json.dump(busy, f)
    with open(os.path.join(bench, "traffic", "busy4.json"), "w") as f:
        json.dump({**busy, "name": "busy4", "entry": "rollout",
                   "periods_per_call": 4}, f)
    for cell, p in TINY_CELLS.items():
        params = {"warmup_calls": 1, "trace_periods": 4096,
                  "trace_calls": 2, "check_periods_max": 64,
                  "limits": {"count_miss": 0, "acc_gap": 1e-9,
                             "time_gap": 1e-9}}
        if "check_sample" in p:
            params["check_sample"] = p["check_sample"]
        with open(os.path.join(bench, "cells", f"{cell}.json"), "w") as f:
            json.dump(params, f)
        man["workloads"].append({"name": cell,
                                 "config": p.get("config", "tiny"),
                                 "traffic": p.get("traffic", "busy"),
                                 "chips": p["chips"], "why": "test"})
    man["end_to_end"][2].setdefault("workloads", []).extend(
        ["tiny.plan", "calm.plan"])
    # a per-layer metric served by an existing reader
    with open(os.path.join(bench, "metrics", "fetch_ms.json"), "w") as f:
        json.dump({"reader": "host_span", "args": {"span": "fetch"}}, f)
    man["per_layer"].append({"name": "fetch_ms", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "entry", "moves": "period_ms",
                             "workloads": list(TINY_CELLS)})
    with open(man_path, "w") as f:
        json.dump(man, f)


@pytest.fixture(scope="session")
def checkout(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    add_files(root)
    return root


def drive(root, workload, *, fault="", trace=0, seconds=1.0, seed=5,
          timeout=600):
    """Run the harness in ``root`` on the CPU in a fresh process; returns
    ``(returncode, result dict or None, stderr)``."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": os.path.join(root, ".jax_cache")}
    env.pop("XLA_FLAGS", None)
    cmd = [sys.executable, os.path.join(root, "bench", "tests", "drive.py")]
    if fault:
        cmd += ["--fault", fault]
    cmd += ["--", "--workload", workload, "--seed", str(seed),
            "--seconds", str(seconds), "--trace", str(trace)]
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    result = None
    if lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return p.returncode, result, p.stderr
