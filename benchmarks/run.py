"""Benchmark harness — one section per paper table/figure plus the fleet
engine's benches.  Prints ``name,us_per_call,derived`` CSV (the format
tests/CI consume)."""
from __future__ import annotations

import os
import sys

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path; make the `benchmarks` package importable either way.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from benchmarks import fleet_bench, paper_figs

    sections = paper_figs.ALL + fleet_bench.ALL
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for fn in sections:
        if only and only not in fn.__module__ + "." + fn.__name__:
            continue
        for name, us, derived in fn():
            print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
