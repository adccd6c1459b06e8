"""The comparison that decides `correct`: the program's per-period
metrics against the reference's ranges (`reference.FleetReference`)."""
from __future__ import annotations

import numpy as np

COUNTS = ("n_jobs", "n_violations", "n_offloading", "n_backpressured",
          "n_outage", "n_straggler_updates", "backlog")
# float metrics, each with the scale its gap is measured against: its
# own size, or 1 where the metric is a share of T that may be 0
FLOATS = {"total_accuracy": None, "realized_makespan": None,
          "es_utilization": None, "worst_violation": 1.0}


def _outside(p, lo, hi):
    return np.maximum(0.0, np.maximum(lo - p, p - hi))


def compare(prog: dict, ref: list) -> dict:
    """``prog``: metric -> (P,) array of the program's periods 0..P-1;
    ``ref``: the reference's P periods, None where a period is not
    compared.  Returns the numbers compared:

    * ``count_miss``: period-counts (integers) outside the reference's
      range; an exact comparison;
    * ``acc_gap``: the largest distance of a period's total accuracy
      outside the reference's range, relative to the reference's value;
    * ``time_gap``: the same for the worst wall time, the ES pool's
      utilization and the worst violation (in units of T).
    """
    keep = [t for t, r in enumerate(ref) if r is not None]
    ref = [ref[t] for t in keep]
    got = {k: np.asarray(prog[k], np.float64)[keep] for k in prog}
    lo = {k: np.array([r[k][1] for r in ref]) for k in ref[0]}
    hi = {k: np.array([r[k][2] for r in ref]) for k in ref[0]}
    val = {k: np.array([r[k][0] for r in ref]) for k in ref[0]}
    miss = sum(int((_outside(got[k], lo[k], hi[k]) > 0).sum())
               for k in COUNTS)
    gaps = {}
    for k, scale in FLOATS.items():
        s = np.abs(val[k]) if scale is None else scale
        gaps[k] = float((_outside(got[k], lo[k], hi[k])
                         / np.maximum(s, 1e-300)).max(initial=0.0))
    return {"count_miss": miss,
            "acc_gap": gaps["total_accuracy"],
            "time_gap": max(gaps["realized_makespan"],
                            gaps["es_utilization"],
                            gaps["worst_violation"])}


def reference_as_program(ref: list) -> dict:
    """A reference run's values in the program's layout, so that a second
    reference (the lower-precision control) can stand in the program's
    place."""
    return {k: np.array([r[k][0] for r in ref]) for k in ref[0]}
