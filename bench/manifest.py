"""`BENCHMARK.json` and the data files it names, found by name."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _data(kind: str, name: str, bench_dir: str = HERE) -> dict:
    path = os.path.join(bench_dir, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def cell(workload: str, root: str = ROOT) -> dict:
    """Everything one cell needs: its manifest entry, configuration,
    traffic mix, cell parameters, and its per-layer and end-to-end
    metrics (each per-layer metric with its own file's reader)."""
    man = load(root)
    bench_dir = os.path.join(root, "bench")
    by_name = {w["name"]: w for w in man["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(by_name)}")
    w = by_name[workload]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    config = _data("configs", w["config"], bench_dir)
    if os.path.join(root, conf["file"]) != os.path.join(
            bench_dir, "configs", f"{w['config']}.json"):
        raise ValueError(f"config {w['config']}: file {conf['file']} is "
                         f"not bench/configs/{w['config']}.json")

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    per_layer = []
    for m in man["per_layer"]:
        if applies(m):
            per_layer.append({**m, **_data("metrics", m["name"], bench_dir)})
    return {
        "workload": w,
        "config": config,
        "traffic": _data("traffic", w["traffic"], bench_dir),
        "params": _data("cells", workload, bench_dir),
        "end_to_end": [m for m in man["end_to_end"] if applies(m)],
        "per_layer": per_layer,
    }
