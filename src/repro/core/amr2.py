"""AMR^2 — Accuracy Maximization using LP-Relaxation and Rounding (paper §IV).

Pipeline (Algorithm 1):
  1. Solve the LP relaxation of P with a *basic* solver (simplex, `lp.py`).
     Lemma 1: a basic optimal solution has at most two fractional jobs.
  2. Keep the integer part of the LP solution verbatim.
  3. Round the <=2 fractional jobs:
       * one fractional  -> argmax_{i in M} { a_i : p_{i,j} <= T }   (line 4)
       * two fractional  -> exact 2-job sub-ILP (Algorithm 2 / Lemma 2);
         we solve it by exhaustive (m+1)^2 enumeration, which *is* optimal
         for the sub-ILP (the paper's case tree computes the same optimum).

Guarantees (validated in tests/test_amr2.py):
  Thm 1:  makespan(x†) <= 2T        whenever P is feasible.
  Thm 2:  A* <= A† + 2(a_{m+1} - a_1).
  Cor 1:  A* <= A† + (a_{m+1} - a_1) when all p_{(m+1)j} <= T.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from .lp import INFEASIBLE, OPTIMAL, solve_lp, solve_lp_batch
from .types import InstanceBatch, OffloadInstance, Schedule

_FRAC_TOL = 1e-4


# --------------------------------------------------------------------------
# LP relaxation of P
# --------------------------------------------------------------------------
def _unsolved(status: int) -> bool:
    """Statuses that mean the LP solver did not finish (iteration limit,
    unbounded — anything that is neither a solution nor an infeasibility
    certificate)."""
    return status not in (OPTIMAL, INFEASIBLE)


def build_lp_arrays(inst: OffloadInstance):
    """Variables x[j, i] flattened j-major, i in 0..m (i == m is the ES)."""
    n, m = inst.n, inst.m
    mp1 = m + 1
    nv = n * mp1
    c = -np.tile(inst.acc, n)                      # maximize -> minimize -A

    A_ub = np.zeros((2, nv))
    for j in range(n):
        A_ub[0, j * mp1: j * mp1 + m] = inst.p_ed[j]   # constraint (1): ED budget
        A_ub[1, j * mp1 + m] = inst.p_es[j]            # constraint (2): ES budget
    b_ub = np.array([inst.T, inst.T])

    A_eq = np.zeros((n, nv))
    for j in range(n):
        A_eq[j, j * mp1: (j + 1) * mp1] = 1.0          # constraint (3)
    b_eq = np.ones(n)
    return c, A_ub, b_ub, A_eq, b_eq


def solve_lp_relaxation(inst: OffloadInstance, *, backend: str = "numpy",
                        maxiter: Optional[int] = None,
                        warm_basis: Optional[np.ndarray] = None):
    """Returns (xbar (n, m+1), A*_LP, status, basis).

    ``warm_basis`` (the basis returned by a previous call on a
    structurally identical instance) starts the simplex from that vertex;
    see `solve_lp`."""
    c, A_ub, b_ub, A_eq, b_eq = build_lp_arrays(inst)
    res = solve_lp(c, A_ub, b_ub, A_eq, b_eq, backend=backend,
                   maxiter=maxiter, warm_basis=warm_basis)
    xbar = res.x.reshape(inst.n, inst.m + 1)
    return xbar, -res.fun, res.status, res.basis


# --------------------------------------------------------------------------
# Fractional-job bookkeeping (Lemma 1)
# --------------------------------------------------------------------------
def fractional_jobs(xbar: np.ndarray, tol: float = _FRAC_TOL) -> np.ndarray:
    """Indices j whose row has any entry strictly inside (tol, 1-tol)."""
    frac = (xbar > tol) & (xbar < 1.0 - tol)
    return np.nonzero(frac.any(axis=1))[0]


# --------------------------------------------------------------------------
# sub-ILP (Algorithm 2) — exact enumeration over (m+1)^2 assignments
# --------------------------------------------------------------------------
def solve_sub_ilp(inst: OffloadInstance, j1: int, j2: int
                  ) -> Optional[Tuple[int, int]]:
    """Optimal assignment of two jobs under fresh budgets T on ED and ES.

    Returns (i1, i2) or None when even the 2-job problem is infeasible.
    Vectorised over the (m+1) x (m+1) assignment grid.
    """
    m, T = inst.m, inst.T
    mp1 = m + 1
    # time contributed to the ED budget by assigning job -> model i (0 if ES)
    ed1 = np.concatenate([inst.p_ed[j1], [0.0]])       # (m+1,)
    ed2 = np.concatenate([inst.p_ed[j2], [0.0]])
    es1 = np.concatenate([np.zeros(m), [inst.p_es[j1]]])
    es2 = np.concatenate([np.zeros(m), [inst.p_es[j2]]])

    ed_load = ed1[:, None] + ed2[None, :]              # (m+1, m+1)
    es_load = es1[:, None] + es2[None, :]
    feas = (ed_load <= T + 1e-12) & (es_load <= T + 1e-12)
    if not feas.any():
        return None
    val = inst.acc[:, None] + inst.acc[None, :]
    val = np.where(feas, val, -np.inf)
    flat = int(np.argmax(val))
    return flat // mp1, flat % mp1


def algorithm2_case_tree(inst: OffloadInstance, j1: int, j2: int
                         ) -> Optional[Tuple[int, int]]:
    """The paper's literal Algorithm 2 case analysis (for cross-validation).

    Line 13's "models on the ES" is a typo for "on the ED" — with both
    p_{(m+1)j} > T neither job fits the ES budget.
    """
    m, T = inst.m, inst.T

    def best_fit(j):  # argmax_{i in M} {a_i : p_{ij} <= T}; None if empty
        ok = [i for i in range(m) if inst.p_ed[j, i] <= T]
        if inst.p_es[j] <= T:
            ok.append(m)
        if not ok:
            return None
        return max(ok, key=lambda i: inst.acc[i])

    def best_fit_ed(j):
        ok = [i for i in range(m) if inst.p_ed[j, i] <= T]
        if not ok:
            return None
        return max(ok, key=lambda i: inst.acc[i])

    if inst.p_es[j1] <= T or inst.p_es[j2] <= T:           # line 2
        if inst.p_es[j1] + inst.p_es[j2] <= T:             # line 3
            return m, m
        b1, b2 = best_fit_ed(j1), best_fit_ed(j2)
        a1 = -np.inf if b1 is None else inst.acc[b1]
        a2 = -np.inf if b2 is None else inst.acc[b2]
        if a1 >= a2 and b1 is not None and inst.p_es[j2] <= T:  # line 6
            return b1, m
        if b2 is not None and inst.p_es[j1] <= T:               # line 9
            return m, b2
        # degenerate corners the paper's tree leaves implicit
        return solve_sub_ilp(inst, j1, j2)
    # line 12: both exceed the ES budget -> both on the ED (line 13)
    best = None
    for i1 in range(m):
        for i2 in range(m):
            if inst.p_ed[j1, i1] + inst.p_ed[j2, i2] <= T:
                v = inst.acc[i1] + inst.acc[i2]
                if best is None or v > best[0]:
                    best = (v, i1, i2)
    if best is None:
        return None
    return best[1], best[2]


# --------------------------------------------------------------------------
# AMR^2 (Algorithm 1)
# --------------------------------------------------------------------------
def amr2(inst: OffloadInstance, *, backend: str = "numpy",
         frac_tol: float = _FRAC_TOL, maxiter: Optional[int] = None,
         warm_basis: Optional[np.ndarray] = None,
         on_error: str = "raise") -> Schedule:
    xbar, a_lp, status, _ = solve_lp_relaxation(
        inst, backend=backend, maxiter=maxiter, warm_basis=warm_basis)
    return round_relaxation(inst, xbar, a_lp, status, frac_tol=frac_tol,
                            on_error=on_error)


def round_relaxation(inst: OffloadInstance, xbar: np.ndarray, a_lp: float,
                     status: int, *, frac_tol: float = _FRAC_TOL,
                     solver: str = "amr2",
                     on_error: str = "raise") -> Schedule:
    """Algorithm 1 lines 2-11: turn a basic LP-relaxation solution into an
    integral schedule.  Shared by the scalar and vmapped-batch AMR^2 paths.

    A non-converged LP (iteration limit / unbounded — a capped ``maxiter``)
    must never be rounded as if optimal: ``on_error="raise"`` (default)
    raises, ``on_error="mark"`` returns a best-effort schedule tagged
    ``status="unsolved"`` so callers (the `repro.api` front door) can
    surface it per their ``strict`` setting."""
    if status == INFEASIBLE:
        # P infeasible (its relaxation already is): best-effort everything on
        # the fastest ED model so the caller still gets a schedule object.
        assignment = np.argmin(inst.p_ed, axis=1)
        return Schedule(assignment=assignment, instance=inst,
                        lp_accuracy=None, n_fractional=0,
                        status="infeasible", solver=solver)
    if status != OPTIMAL:
        if on_error != "mark":
            raise RuntimeError(
                f"LP relaxation did not converge (status={status})")
        return Schedule(assignment=np.argmax(xbar, axis=1).astype(np.int64),
                        instance=inst, lp_accuracy=None, n_fractional=0,
                        status="unsolved", solver=solver)

    frac = fractional_jobs(xbar, frac_tol)
    assignment = np.argmax(xbar, axis=1).astype(np.int64)
    sched_status = "ok"

    if len(frac) > 2:
        # Lemma 1 guarantees <=2 for an exact basic optimum; numerically we
        # keep the two most fractional rows and integer-round the rest.
        fractionality = 1.0 - xbar[frac].max(axis=1)
        order = frac[np.argsort(-fractionality)]
        frac = np.sort(order[:2])
        sched_status = "fallback"

    if len(frac) == 1:
        j = int(frac[0])
        i = _best_fit_any(inst, j)
        if i is None:                       # P was integrally infeasible
            i = int(np.argmin(inst.p_ed[j]))
            sched_status = "fallback"
        assignment[j] = i
    elif len(frac) == 2:
        j1, j2 = int(frac[0]), int(frac[1])
        pair = solve_sub_ilp(inst, j1, j2)
        if pair is None:                    # P was integrally infeasible
            pair = (int(np.argmin(inst.p_ed[j1])),
                    int(np.argmin(inst.p_ed[j2])))
            sched_status = "fallback"
        assignment[j1], assignment[j2] = pair

    return Schedule(assignment=assignment, instance=inst, lp_accuracy=a_lp,
                    n_fractional=int(len(frac)), status=sched_status,
                    solver=solver)


# --------------------------------------------------------------------------
# Batched AMR^2 — one vmapped LP solve for a whole fleet
# --------------------------------------------------------------------------
def build_lp_arrays_batch(batch: InstanceBatch):
    """Batched `build_lp_arrays`: (B, ...) arrays sharing the (n, m) shape."""
    B, n, m = batch.p_ed.shape
    mp1 = m + 1
    nv = n * mp1
    c = -np.tile(batch.acc, (1, n))                      # (B, nv)

    ed_rows = np.zeros((B, n, mp1))
    ed_rows[:, :, :m] = batch.p_ed                       # constraint (1)
    es_rows = np.zeros((B, n, mp1))
    es_rows[:, :, m] = batch.p_es                        # constraint (2)
    A_ub = np.stack([ed_rows.reshape(B, nv), es_rows.reshape(B, nv)], axis=1)
    b_ub = np.stack([batch.T, batch.T], axis=1)

    A_eq = np.broadcast_to(np.kron(np.eye(n), np.ones(mp1)), (B, n, nv))
    b_eq = np.ones((B, n))                               # constraint (3)
    return c, A_ub, b_ub, A_eq, b_eq


# status codes shared by the vectorized rounding and the fleet arrays path;
# the numbering matches `problem.SOLUTION_STATUS_NAMES` (3 is the api-level
# "bound" pseudo-status, never produced here)
ST_OK, ST_FALLBACK, ST_INFEASIBLE = 0, 1, 2
ST_UNSOLVED = 4
STATUS_NAMES = ("ok", "fallback", "infeasible", "bound", "unsolved")


def round_relaxation_batch(batch: InstanceBatch, xbar: np.ndarray,
                           status: np.ndarray, *,
                           frac_tol: float = _FRAC_TOL,
                           on_error: str = "raise"):
    """Vectorized `round_relaxation` across a whole batch.

    Algorithm 1's rounding cases run as array ops over the devices that hit
    them — one-fractional best-fit and the two-job sub-ILP enumeration both
    vectorize; only the rare numeric >2-fractional fallback drops to the
    scalar path.  Tie-breaks (first-max argmax everywhere) are identical to
    the scalar code, so assignments match it exactly.

    Returns ``(assignment (B, n) int64, sched_status (B,) int with
    ST_OK/ST_FALLBACK/ST_INFEASIBLE, n_fractional (B,) int)``.
    """
    B, n, mp1 = xbar.shape
    m = mp1 - 1
    status = np.asarray(status)
    bad = (status != OPTIMAL) & (status != INFEASIBLE)
    if bad.any() and on_error != "mark":
        raise RuntimeError(
            f"LP relaxation did not converge (status={int(status[bad][0])})")

    assignment = np.argmax(xbar, axis=2).astype(np.int64)
    sched_status = np.zeros(B, dtype=np.int64)
    n_frac = np.zeros(B, dtype=np.int64)
    sched_status[bad] = ST_UNSOLVED     # best-effort argmax, never rounded

    infeas = status == INFEASIBLE
    if infeas.any():
        assignment[infeas] = np.argmin(batch.p_ed[infeas], axis=2)
        sched_status[infeas] = ST_INFEASIBLE

    ok = ~infeas & ~bad
    frac_rows = (((xbar > frac_tol) & (xbar < 1.0 - frac_tol)).any(axis=2)
                 & ok[:, None])
    fc = frac_rows.sum(axis=1)
    n_frac[ok] = np.minimum(fc[ok], 2)

    many = ok & (fc > 2)              # numeric fallback: scalar path, rare
    for b in np.nonzero(many)[0]:
        sched = round_relaxation(batch[b], xbar[b], 0.0, OPTIMAL,
                                 frac_tol=frac_tol)
        assignment[b] = sched.assignment
        sched_status[b] = STATUS_NAMES.index(sched.status)
        n_frac[b] = sched.n_fractional

    one = ok & (fc == 1)              # Algorithm 1 line 4, vectorized
    if one.any():
        bs = np.nonzero(one)[0]
        js = np.argmax(frac_rows[bs], axis=1)
        Tb = batch.T[bs]
        feas = np.concatenate(
            [batch.p_ed[bs, js] <= Tb[:, None],
             (batch.p_es[bs, js] <= Tb)[:, None]], axis=1)   # (k, m+1)
        val = np.where(feas, batch.acc[bs], -np.inf)
        pick = np.argmax(val, axis=1)
        none = ~feas.any(axis=1)      # P integrally infeasible
        if none.any():
            pick[none] = np.argmin(batch.p_ed[bs[none], js[none]], axis=1)
            sched_status[bs[none]] = ST_FALLBACK
        assignment[bs, js] = pick

    two = ok & (fc == 2)              # Algorithm 2, vectorized enumeration
    if two.any():
        bs = np.nonzero(two)[0]
        k = len(bs)
        j1 = np.argmax(frac_rows[bs], axis=1)
        masked = frac_rows[bs].copy()
        masked[np.arange(k), j1] = False
        j2 = np.argmax(masked, axis=1)
        Tb = batch.T[bs]
        zed = np.zeros((k, 1))
        zes = np.zeros((k, m))
        ed1 = np.concatenate([batch.p_ed[bs, j1], zed], axis=1)  # (k, m+1)
        ed2 = np.concatenate([batch.p_ed[bs, j2], zed], axis=1)
        es1 = np.concatenate([zes, batch.p_es[bs, j1][:, None]], axis=1)
        es2 = np.concatenate([zes, batch.p_es[bs, j2][:, None]], axis=1)
        ed_load = ed1[:, :, None] + ed2[:, None, :]              # (k,m+1,m+1)
        es_load = es1[:, :, None] + es2[:, None, :]
        feas = ((ed_load <= Tb[:, None, None] + 1e-12)
                & (es_load <= Tb[:, None, None] + 1e-12))
        val = batch.acc[bs][:, :, None] + batch.acc[bs][:, None, :]
        val = np.where(feas, val, -np.inf)
        flat = np.argmax(val.reshape(k, -1), axis=1)
        i1, i2 = flat // mp1, flat % mp1
        none = ~feas.any(axis=(1, 2))
        if none.any():
            i1[none] = np.argmin(batch.p_ed[bs[none], j1[none]], axis=1)
            i2[none] = np.argmin(batch.p_ed[bs[none], j2[none]], axis=1)
            sched_status[bs[none]] = ST_FALLBACK
        assignment[bs, j1] = i1
        assignment[bs, j2] = i2

    return assignment, sched_status, n_frac


def amr2_batch_arrays(batch: InstanceBatch, *, frac_tol: float = _FRAC_TOL,
                      maxiter: Optional[int] = None,
                      warm_basis: Optional[np.ndarray] = None,
                      impl: str = "jnp", on_error: str = "raise"):
    """Array-level batched AMR^2 for the fleet hot path: ONE vmapped LP
    solve + vectorized rounding, no per-device Schedule objects.

    ``warm_basis`` (B, R) feeds the revised-simplex warm start — the basis
    each device's LP ended on last period (`solve_lp_batch`); rows of -1
    force a cold solve for that device.  ``impl="pallas"`` runs the warm
    pivots through the `kernels/simplex_pivot` kernel.

    Returns ``(assignment (B, n), sched_status (B,), n_fractional (B,),
    lp_accuracy (B,), basis (B, R))``."""
    c, A_ub, b_ub, A_eq, b_eq = build_lp_arrays_batch(batch)
    res = solve_lp_batch(c, A_ub, b_ub, A_eq, b_eq, maxiter=maxiter,
                         warm_basis=warm_basis, impl=impl)
    B, n = batch.p_es.shape
    xbar = res.x.reshape(B, n, batch.m + 1)
    assignment, sched_status, n_frac = round_relaxation_batch(
        batch, xbar, res.status, frac_tol=frac_tol, on_error=on_error)
    return assignment, sched_status, n_frac, -res.fun, res.basis


def build_lp_arrays_jnp(p_ed, p_es, acc, T):
    """Traceable `build_lp_arrays_batch` + `_canonicalize_batch` in one:
    canonicalised ``(A (B, R, C0), b (B, R), c_full (B, C0))`` with
    R = n + 2 rows (ED budget, ES budget, n assignment rows) and
    C0 = n(m+1) + 2 columns (variables + 2 slack).  ``b`` is already
    nonnegative (T > 0, assignment rhs = 1), so no row flips are needed —
    the output feeds `lp.simplex_batch_core` directly inside jit/scan."""
    import jax.numpy as jnp
    B, n, m = p_ed.shape
    mp1 = m + 1
    nv = n * mp1
    dtype = p_ed.dtype
    ed = jnp.zeros((B, n, mp1), dtype).at[:, :, :m].set(p_ed)
    es = jnp.zeros((B, n, mp1), dtype).at[:, :, m].set(p_es)
    eq = jnp.broadcast_to(
        jnp.asarray(np.kron(np.eye(n), np.ones(mp1)), dtype), (B, n, nv))
    slack = jnp.broadcast_to(
        jnp.asarray(np.concatenate([np.eye(2), np.zeros((n, 2))]), dtype),
        (B, n + 2, 2))
    A = jnp.concatenate([
        jnp.stack([ed.reshape(B, nv), es.reshape(B, nv)], axis=1),
        eq], axis=1)
    A = jnp.concatenate([A, slack], axis=2)
    Tb = jnp.broadcast_to(jnp.asarray(T, dtype).reshape(-1, 1), (B, 1))
    b = jnp.concatenate([Tb, Tb, jnp.ones((B, n), dtype)], axis=1)
    c_full = jnp.concatenate(
        [-jnp.tile(acc, (1, n)), jnp.zeros((B, 2), dtype)], axis=1)
    return A, b, c_full


def lp_column_rows(n: int, m: int) -> np.ndarray:
    """The rows of the (at most two) nonzeros of each column that
    `build_lp_arrays_jnp` builds for ``(n, m)``: a (2, C0) int32 array,
    static at trace time (`lp.simplex_batch_core`'s ``col_rows``).

    Variable ``(job j, model k)`` has its latency in the ED budget row 0
    (k < m) or the ES budget row 1 (k = m), and a 1 in assignment row
    ``2 + j``; slack ``s`` (s = 0, 1) has its 1 in budget row ``s`` and a
    0 in the other budget row."""
    mp1 = m + 1
    k = np.tile(np.arange(mp1), n)
    ra = np.concatenate([(k == m).astype(np.int32), [0, 1]])
    rb = np.concatenate([2 + np.repeat(np.arange(n), mp1), [1, 0]])
    return np.stack([ra, rb]).astype(np.int32)


def round_relaxation_jnp(p_ed, p_es, acc, T, xbar, status, *,
                         frac_tol: float = _FRAC_TOL):
    """Traceable `round_relaxation_batch`: Algorithm 1's rounding as pure
    jnp, usable inside `jax.jit` / `lax.scan` (the `repro.api.engine`
    period step).  Semantics match the NumPy batched path case for case —
    first-max argmaxes, the one-fractional best-fit, the two-job sub-ILP
    enumeration, and the infeasible / non-converged markings — except the
    rare >2-fractional numeric fallback, where the two most fractional
    rows are picked by a STABLE descending sort (NumPy's introsort leaves
    equal-fractionality ties unspecified; on real float data ties are
    measure-zero).

    Returns ``(assignment (B, n) int64-compatible ints, sched_status (B,),
    n_fractional (B,))``.
    """
    import jax.numpy as jnp
    B, n, mp1 = xbar.shape
    m = mp1 - 1
    status = jnp.asarray(status)
    bad = (status != OPTIMAL) & (status != INFEASIBLE)
    infeas = status == INFEASIBLE
    ok = ~infeas & ~bad

    assignment = jnp.argmax(xbar, axis=2).astype(jnp.int32)
    assignment = jnp.where(infeas[:, None],
                           jnp.argmin(p_ed, axis=2).astype(jnp.int32),
                           assignment)
    sched_status = jnp.where(bad, ST_UNSOLVED,
                             jnp.where(infeas, ST_INFEASIBLE, ST_OK)
                             ).astype(jnp.int32)

    frac_rows = (((xbar > frac_tol) & (xbar < 1.0 - frac_tol)).any(axis=2)
                 & ok[:, None])
    fc = frac_rows.sum(axis=1)
    n_frac = jnp.where(ok, jnp.minimum(fc, 2), 0).astype(jnp.int32)

    # candidate job pair: first two fractional rows (fc <= 2) or the two
    # most fractional rows (fc > 2, the scalar fallback's selection)
    j1_first = jnp.argmax(frac_rows, axis=1)
    masked = frac_rows.at[jnp.arange(B), j1_first].set(False)
    j2_first = jnp.argmax(masked, axis=1)
    fractionality = jnp.where(frac_rows, 1.0 - xbar.max(axis=2), -jnp.inf)
    top = jnp.argsort(-fractionality, axis=1)[:, :2]
    j1_many = jnp.min(top, axis=1)
    j2_many = jnp.max(top, axis=1)
    many = ok & (fc > 2)
    j1 = jnp.where(many, j1_many, j1_first)
    j2 = jnp.where(many, j2_many, j2_first)
    sched_status = jnp.where(many, ST_FALLBACK, sched_status)

    rows = jnp.arange(B)
    Tb = jnp.broadcast_to(jnp.asarray(T, xbar.dtype).reshape(-1), (B,))

    # ---- one fractional job: best-fit (Algorithm 1 line 4) -------------
    one = ok & (fc == 1)
    feas1 = jnp.concatenate(
        [p_ed[rows, j1] <= Tb[:, None],
         (p_es[rows, j1] <= Tb)[:, None]], axis=1)          # (B, m+1)
    val1 = jnp.where(feas1, acc, -jnp.inf)
    pick1 = jnp.argmax(val1, axis=1)
    none1 = ~feas1.any(axis=1)
    pick1 = jnp.where(none1, jnp.argmin(p_ed[rows, j1], axis=1), pick1)
    sched_status = jnp.where(one & none1, ST_FALLBACK, sched_status)
    assignment = jnp.where(
        (one[:, None]) & (jnp.arange(n)[None, :] == j1[:, None]),
        pick1[:, None].astype(jnp.int32), assignment)

    # ---- two (or >2, truncated) fractional jobs: sub-ILP ---------------
    two = ok & (fc >= 2)
    zed = jnp.zeros((B, 1), xbar.dtype)
    zes = jnp.zeros((B, m), xbar.dtype)
    ed1 = jnp.concatenate([p_ed[rows, j1], zed], axis=1)    # (B, m+1)
    ed2 = jnp.concatenate([p_ed[rows, j2], zed], axis=1)
    es1 = jnp.concatenate([zes, p_es[rows, j1][:, None]], axis=1)
    es2 = jnp.concatenate([zes, p_es[rows, j2][:, None]], axis=1)
    ed_load = ed1[:, :, None] + ed2[:, None, :]
    es_load = es1[:, :, None] + es2[:, None, :]
    feas2 = ((ed_load <= Tb[:, None, None] + 1e-12)
             & (es_load <= Tb[:, None, None] + 1e-12))
    val2 = acc[:, :, None] + acc[:, None, :]
    val2 = jnp.where(feas2, val2, -jnp.inf)
    flat = jnp.argmax(val2.reshape(B, -1), axis=1)
    i1, i2 = flat // mp1, flat % mp1
    none2 = ~feas2.reshape(B, -1).any(axis=1)
    i1 = jnp.where(none2, jnp.argmin(p_ed[rows, j1], axis=1), i1)
    i2 = jnp.where(none2, jnp.argmin(p_ed[rows, j2], axis=1), i2)
    sched_status = jnp.where(two & none2, ST_FALLBACK, sched_status)
    cols = jnp.arange(n)[None, :]
    assignment = jnp.where(two[:, None] & (cols == j1[:, None]),
                           i1[:, None].astype(jnp.int32), assignment)
    assignment = jnp.where(two[:, None] & (cols == j2[:, None]),
                           i2[:, None].astype(jnp.int32), assignment)
    return assignment, sched_status, n_frac


def soft_assignment_weights(xbar, *, tau: float = 0.25):
    """Smoothed twin of Algorithm 2's rounding: temperature-sharpened
    assignment weights ``w (B, n, m+1)`` from the LP relaxation ``xbar``.

    ``softmax(log(clip(xbar)) / tau)`` — at ``tau=1`` this is exactly
    ``xbar`` renormalized (softmax of a log is the identity on the
    simplex); as ``tau -> 0`` it hardens to the same argmax the hard
    rounding takes on integral rows.  Rows the LP left fractional (<= 2
    per lane, Lemma 1) keep mass on both candidates, which is what makes
    the relaxation differentiable where `round_relaxation_jnp`'s case
    tree is piecewise constant.  Gradients flow w.r.t. ``xbar`` only —
    the clip floor (1e-12) zeroes them where the LP put exactly no mass."""
    import jax
    import jax.numpy as jnp
    lx = jnp.log(jnp.clip(xbar, 1e-12, 1.0))
    return jax.nn.softmax(lx / tau, axis=2)


def straight_through_weights(xbar, assignment, *, tau: float = 0.25):
    """Straight-through twin: FORWARD is the exact one-hot of the hard
    Algorithm-2 ``assignment`` (including its sub-ILP fix-ups), BACKWARD
    is `soft_assignment_weights`' Jacobian — the classic ST estimator, so
    a differentiable rollout can keep the served accuracy numbers of the
    hard path while still producing a usable gradient signal."""
    import jax
    import jax.numpy as jnp
    soft = soft_assignment_weights(xbar, tau=tau)
    hard = jax.nn.one_hot(assignment, xbar.shape[2], dtype=xbar.dtype)
    return soft + jax.lax.stop_gradient(hard - soft)


def amr2_batch(batch: InstanceBatch, *,
               frac_tol: float = _FRAC_TOL) -> "list[Schedule]":
    """AMR^2 over a fleet of B same-shape instances.

    The expensive step — the basic LP-relaxation solve — runs as ONE jitted
    `vmap` over the batch (float64, so it matches the per-instance NumPy
    oracle to rounding-identical assignments); the rounding of at most two
    fractional jobs per instance is vectorized across the batch
    (`round_relaxation_batch`)."""
    assignment, sched_status, n_frac, lp_acc, _ = amr2_batch_arrays(
        batch, frac_tol=frac_tol)
    return [Schedule(assignment=assignment[b], instance=batch[b],
                     lp_accuracy=(None if sched_status[b] in
                                  (ST_INFEASIBLE, ST_UNSOLVED)
                                  else float(lp_acc[b])),
                     n_fractional=int(n_frac[b]),
                     status=STATUS_NAMES[sched_status[b]], solver="amr2")
            for b in range(len(batch))]


def _best_fit_any(inst: OffloadInstance, j: int) -> Optional[int]:
    """argmax_{i in M} { a_i : p_{ij} <= T } (Algorithm 1, line 4)."""
    ok = [i for i in range(inst.m) if inst.p_ed[j, i] <= inst.T]
    if inst.p_es[j] <= inst.T:
        ok.append(inst.m)
    if not ok:
        return None
    return int(max(ok, key=lambda i: inst.acc[i]))
