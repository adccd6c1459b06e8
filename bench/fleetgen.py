"""Fleet and arrival-trace generator: the benchmark's own copy.

Copied (vectorized) from the program's fleet builder and request queue so
that a change to the program cannot change what a cell feeds it.  The
distributions are the program's; the order of the random draws is not, so
the fleet for a seed differs from the program's own generator.

Everything is a NumPy array drawn from one `numpy.random.Generator`
seeded with the run's ``--seed``:

* per-device latency/accuracy tables: the paper's testbed numbers
  (``paper_profile``), the same on every device unless the configuration
  gives a per-device ``jitter_ed`` / ``jitter_es`` range;
* per-device drift (a ``straggler_frac`` of devices slowing to
  ``drift_mag`` x from an onset period) and ES-link outages (an
  ``outage_frac`` of devices, each down in ``outage_prob`` of periods),
  ``schedule_periods`` long and cycled; none unless the configuration
  asks for them;
* a replayed arrival trace: counts per device-period, exactly ``rate``
  (``arrivals: fixed``, the paper's n jobs per frame) or Poisson with
  mean ``rate`` (``arrivals: poisson``), and each device's stream of job
  classes in arrival order.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Fleet:
    """One deployment drawn from a seed (all NumPy, float64 / int32)."""

    classes: np.ndarray      # (c,) job size classes (image dimension)
    p_ed: np.ndarray         # (D, c, m) local-model seconds per class
    p_es: np.ndarray         # (D, c) ES seconds per class (comm + compute)
    acc: np.ndarray          # (D, m+1) accuracy of each local model, then ES
    drift: np.ndarray        # (D, Hs) true slowdown of the local models
    outage: np.ndarray       # (D, Hs) ES link down
    counts: np.ndarray       # (H, D) int32 arrivals per period
    stream: np.ndarray       # (D, S) int32 class index of each arrival

    @property
    def n_devices(self) -> int:
        return self.p_ed.shape[0]


def _paper_tables(rng, D, prof, classes):
    c = len(classes)
    p_ed0 = np.asarray(prof["p_ed"], np.float64)            # (c, m)
    es0 = (np.asarray(prof["comm"], np.float64)
           + np.asarray(prof["p_es_proc"], np.float64))     # (c,)
    lo, hi = prof.get("jitter_ed", (1.0, 1.0))
    jit_ed = rng.uniform(lo, hi, size=(D, c, p_ed0.shape[1]))
    lo, hi = prof.get("jitter_es", (1.0, 1.0))
    jit_es = rng.uniform(lo, hi, size=(D, c))
    acc = np.broadcast_to(np.asarray(prof["acc"], np.float64),
                          (D, p_ed0.shape[1] + 1))
    return p_ed0[None] * jit_ed, es0[None] * jit_es, acc


def make_fleet(config: dict, traffic: dict, trace_periods: int,
               seed: int) -> Fleet:
    """Draw the fleet of ``config`` and ``trace_periods`` periods of the
    ``traffic`` mix's arrivals from ``seed``.

    The stream holds ``batch_max * trace_periods`` arrivals per device:
    no device can be released more jobs than that, so the shape is fixed
    by the cell and never by the draw (one compiled program per cell)."""
    rng = np.random.default_rng(seed)
    D = int(config["n_devices"])
    classes = np.asarray(config["classes"], np.int64)
    p_ed, p_es, acc = _paper_tables(rng, D, config["paper_profile"], classes)

    Hs = int(config.get("schedule_periods", 1))
    straggler = rng.uniform(size=D) < config.get("straggler_frac", 0.0)
    onset = rng.integers(1, max(2, Hs // 2), size=D)
    late = np.arange(Hs)[None, :] >= onset[:, None]
    drift = np.where(straggler[:, None] & late,
                     config.get("drift_mag", 1.0), 1.0)
    flaky = rng.uniform(size=D) < config.get("outage_frac", 0.0)
    outage = flaky[:, None] & (rng.uniform(size=(D, Hs))
                               < config.get("outage_prob", 0.0))

    H = int(trace_periods)
    rate = float(traffic["rate"])
    if traffic["arrivals"] == "fixed":
        counts = np.full((H, D), int(rate), np.int32)
    elif traffic["arrivals"] == "poisson":
        counts = rng.poisson(rate, size=(H, D)).astype(np.int32)
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    probs = traffic.get("class_probs")
    S = int(config["batch_max"]) * H
    if probs is None:
        stream = rng.integers(0, len(classes), size=(D, S), dtype=np.int32)
    else:
        cdf = np.cumsum(np.asarray(probs, np.float64))
        stream = np.searchsorted(cdf / cdf[-1], rng.uniform(size=(D, S)),
                                 side="right").astype(np.int32)
    return Fleet(classes=classes, p_ed=np.ascontiguousarray(p_ed),
                 p_es=np.ascontiguousarray(p_es),
                 acc=np.ascontiguousarray(acc),
                 drift=drift.astype(np.float64), outage=outage,
                 counts=counts, stream=stream)
