"""AMDP for identical jobs (paper §VI): optimal DP schedule vs AMR^2 and
Greedy-RRA when every request is the same shape — the periodic-sensing
workload (e.g. fixed-resolution frames every period).

Also demos the §VI-B remark: identical processing but heterogeneous
communication times (sort-by-c_j greedy ES fill + CCKP), and the Pallas
TPU kernel path for the DP (interpret mode on CPU).

    PYTHONPATH=src python examples/amdp_identical.py
"""
import time

import numpy as np

from repro.core import (OffloadInstance, amdp, amdp_hetero_comm, amr2,
                        brute_force, greedy_rra)
from repro.core.types import enable_compile_cache


def main():
    enable_compile_cache()
    # ladder timings in the paper's range (Table II-like), identical jobs
    p_ed = np.array([0.010, 0.045])        # two ED models
    p_es = 0.35                            # comm + ES compute
    acc = np.array([0.395, 0.559, 0.771])  # Table I

    print(f"{'n':>5} {'T':>6} {'A_amdp':>8} {'A_amr2':>8} {'A_greedy':>9} "
          f"{'amdp_ms':>8} {'amr2_ms':>8}")
    for n, T in [(30, 2.0), (100, 4.0), (300, 8.0)]:
        inst = OffloadInstance(p_ed=np.tile(p_ed, (n, 1)),
                               p_es=np.full(n, p_es), acc=acc, T=T)
        t0 = time.perf_counter()
        d = amdp(inst)
        t1 = time.perf_counter()
        a = amr2(inst)
        t2 = time.perf_counter()
        g = greedy_rra(inst)
        print(f"{n:5d} {T:6.1f} {d.total_accuracy:8.2f} "
              f"{a.total_accuracy:8.2f} {g.total_accuracy:9.2f} "
              f"{1e3*(t1-t0):8.1f} {1e3*(t2-t1):8.1f}"
              + (f"   (amr2 viol {100*a.violation:.0f}%)"
                 if a.violation > 0 else ""))
        # AMDP is optimal among T-FEASIBLE schedules; AMR^2 may beat it
        # only by exceeding T (its 2T allowance, Thm 1).
        if a.violation == 0:
            assert d.total_accuracy >= a.total_accuracy - 1e-6
        assert d.violation == 0

    # optimality spot-check vs brute force
    inst = OffloadInstance(p_ed=np.tile(p_ed, (7, 1)),
                           p_es=np.full(7, p_es), acc=acc, T=1.0)
    opt = brute_force(inst)
    d = amdp(inst)
    print(f"\nn=7 brute force: {opt.total_accuracy:.3f} == "
          f"AMDP {d.total_accuracy:.3f}")

    # Pallas kernel path for the DP (the paper's C reimplementation,
    # TPU-style; interpret mode on CPU)
    inst = OffloadInstance(p_ed=np.tile(p_ed, (50, 1)),
                           p_es=np.full(50, p_es), acc=acc, T=2.0)
    d_pallas = amdp(inst, impl="pallas")
    d_jnp = amdp(inst)
    print(f"pallas CCKP kernel: A={d_pallas.total_accuracy:.3f} "
          f"(jnp path {d_jnp.total_accuracy:.3f})")

    # heterogeneous comm times (paper §VI-B remark)
    rng = np.random.default_rng(0)
    comm = rng.uniform(0.05, 0.6, size=40)
    h = amdp_hetero_comm(p_ed, p_es_proc=0.3, comm=comm, acc=acc, T=3.0)
    print(f"hetero-comm: A={h.total_accuracy:.2f} "
          f"offloaded={int((h.assignment == 2).sum())}/40 "
          f"ed={h.ed_makespan:.2f}s es={h.es_makespan:.2f}s (T=3.0)")


if __name__ == "__main__":
    main()
