"""Problem/solution containers for the offloading problem `P` (paper §III).

Notation follows the paper:
  - n jobs, m models on the ED, one model (index m, 0-based; `m+1` in the
    paper's 1-based notation) on the ES.
  - ``p_ed[j, i]``  : processing time of job j on ED model i  (paper p_{ij}).
  - ``p_es[j]``     : *total* time of job j on the ES, communication included
                      (paper p_{(m+1)j} = c_j + p'_{(m+1)j}).
  - ``acc[i]``      : average test accuracy a_i, i = 0..m (acc[m] is the ES
                      model, the paper's a_{m+1}).
  - ``T``           : makespan budget for each of the two capacity
                      constraints (1) and (2).

Assignments are stored dense: ``assignment[j] in {0..m}`` where value ``m``
means "offload to the ES".
"""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Optional

import numpy as np

ES = -1  # sentinel alias: instance.es_index == m


def next_pow2(x: int) -> int:
    """Smallest power of two >= x.

    The shared bucketing primitive for jit-trace reuse: batch axes, DP grid
    extents, and shape-derived static args (e.g. simplex maxiter) are all
    rounded up with this so fluctuating sizes reuse O(log) compiled
    programs instead of retracing per distinct value."""
    return 1 << (max(int(x), 1) - 1).bit_length()


def x64_scope():
    """Context manager that runs its body in float64 (``jax.enable_x64``).

    Every float64 entry point (the LP/dual/HI solvers and the engine's
    step, rollout and sharded entries) traces and transfers inside this
    scope, so they stay float64 whatever jax's global x64 flag says."""
    import jax
    return jax.enable_x64(True)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself).  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
    fixed path, so a second run of the same program in the same checkout
    finds the first run's executables.  Called from the scripts' and
    examples' mains, never on import."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass(frozen=True)
class OffloadInstance:
    """One instance of problem P."""

    p_ed: np.ndarray   # (n, m) float
    p_es: np.ndarray   # (n,)  float  (comm + server compute)
    acc: np.ndarray    # (m+1,) float, ascending on the ED part by convention
    T: float

    def __post_init__(self):
        object.__setattr__(self, "p_ed", np.asarray(self.p_ed, dtype=np.float64))
        object.__setattr__(self, "p_es", np.asarray(self.p_es, dtype=np.float64))
        object.__setattr__(self, "acc", np.asarray(self.acc, dtype=np.float64))
        if self.p_ed.ndim != 2:
            raise ValueError("p_ed must be (n, m)")
        if self.p_es.shape != (self.n,):
            raise ValueError("p_es must be (n,)")
        if self.acc.shape != (self.m + 1,):
            raise ValueError("acc must be (m+1,)")

    @property
    def n(self) -> int:
        return self.p_ed.shape[0]

    @property
    def m(self) -> int:
        return self.p_ed.shape[1]

    @property
    def es_index(self) -> int:
        return self.m

    def p(self, j: int, i: int) -> float:
        """Unified p_{ij} with i == m meaning the ES."""
        return float(self.p_es[j]) if i == self.m else float(self.p_ed[j, i])

    def is_identical(self, rtol: float = 1e-9) -> bool:
        """True when all jobs share processing times (paper §VI setting)."""
        return bool(
            np.allclose(self.p_ed, self.p_ed[:1], rtol=rtol)
            and np.allclose(self.p_es, self.p_es[:1], rtol=rtol)
        )


@dataclasses.dataclass(frozen=True)
class InstanceBatch:
    """Array-of-instances: B problems sharing (n, m), stored stacked so the
    batched planner can `jax.vmap` one LP solve over the whole fleet.

    Per-instance `T` and `acc` may differ (heterogeneous fleets); only the
    job/model *counts* must agree across the batch."""

    p_ed: np.ndarray   # (B, n, m) float
    p_es: np.ndarray   # (B, n)  float
    acc: np.ndarray    # (B, m+1) float
    T: np.ndarray      # (B,)  float

    def __post_init__(self):
        object.__setattr__(self, "p_ed", np.asarray(self.p_ed, np.float64))
        object.__setattr__(self, "p_es", np.asarray(self.p_es, np.float64))
        object.__setattr__(self, "acc", np.asarray(self.acc, np.float64))
        object.__setattr__(self, "T", np.asarray(self.T, np.float64))
        if self.p_ed.ndim != 3:
            raise ValueError("p_ed must be (B, n, m)")
        B, n, m = self.p_ed.shape
        if self.p_es.shape != (B, n):
            raise ValueError("p_es must be (B, n)")
        if self.acc.shape != (B, m + 1):
            raise ValueError("acc must be (B, m+1)")
        if self.T.shape != (B,):
            raise ValueError("T must be (B,)")

    @classmethod
    def stack(cls, instances: "list[OffloadInstance]") -> "InstanceBatch":
        if not instances:
            raise ValueError("cannot stack an empty instance list")
        n, m = instances[0].n, instances[0].m
        for inst in instances[1:]:
            if (inst.n, inst.m) != (n, m):
                raise ValueError(
                    f"instances must share (n, m); got ({inst.n}, {inst.m}) "
                    f"vs ({n}, {m})")
        return cls(p_ed=np.stack([i.p_ed for i in instances]),
                   p_es=np.stack([i.p_es for i in instances]),
                   acc=np.stack([i.acc for i in instances]),
                   T=np.array([i.T for i in instances]))

    def __len__(self) -> int:
        return self.p_ed.shape[0]

    def __getitem__(self, b: int) -> OffloadInstance:
        return OffloadInstance(p_ed=self.p_ed[b], p_es=self.p_es[b],
                               acc=self.acc[b], T=float(self.T[b]))

    def identical_mask(self, rtol: float = 1e-9) -> np.ndarray:
        """(B,) bool: `OffloadInstance.is_identical` vectorized over the
        batch — the single criterion every batched planner dispatch uses."""
        return (np.isclose(self.p_ed, self.p_ed[:, :1], rtol=rtol)
                .all(axis=(1, 2))
                & np.isclose(self.p_es, self.p_es[:, :1], rtol=rtol)
                .all(axis=1))

    @property
    def n(self) -> int:
        return self.p_ed.shape[1]

    @property
    def m(self) -> int:
        return self.p_ed.shape[2]


@dataclasses.dataclass
class Schedule:
    """A (possibly constraint-violating) solution to P."""

    assignment: np.ndarray          # (n,) int in [0, m]; m == ES
    instance: OffloadInstance
    lp_accuracy: Optional[float] = None    # A*_LP upper bound when available
    n_fractional: Optional[int] = None     # fractional jobs seen by AMR^2
    status: str = "ok"                     # ok | infeasible | fallback
    solver: str = ""

    # ---- derived metrics -------------------------------------------------
    @property
    def total_accuracy(self) -> float:
        return float(self.instance.acc[self.assignment].sum())

    @property
    def ed_makespan(self) -> float:
        inst = self.instance
        mask = self.assignment < inst.m
        if not mask.any():
            return 0.0
        j = np.nonzero(mask)[0]
        return float(inst.p_ed[j, self.assignment[j]].sum())

    @property
    def es_makespan(self) -> float:
        inst = self.instance
        mask = self.assignment == inst.m
        return float(inst.p_es[mask].sum())

    @property
    def makespan(self) -> float:
        # Both tiers run in parallel; makespan is the later finisher.
        return max(self.ed_makespan, self.es_makespan)

    @property
    def violation(self) -> float:
        """makespan / T - 1 (0 when within budget)."""
        return max(0.0, self.makespan / self.instance.T - 1.0)

    def counts(self) -> np.ndarray:
        """(m+1,) number of jobs per model."""
        return np.bincount(self.assignment, minlength=self.instance.m + 1)

    def summary(self) -> str:
        return (
            f"[{self.solver}] A={self.total_accuracy:.3f} "
            f"(LP bound {self.lp_accuracy if self.lp_accuracy is None else round(self.lp_accuracy, 3)}) "
            f"makespan ed={self.ed_makespan:.3f} es={self.es_makespan:.3f} "
            f"T={self.instance.T} viol={100 * self.violation:.1f}% status={self.status}"
        )
