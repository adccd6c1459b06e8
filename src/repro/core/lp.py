"""Dense two-phase primal simplex, implemented twice from one design:

  * ``backend="jax"``   — fully jittable (`lax.while_loop` pivots, fixed-shape
    tableau).  This is the production path: the scheduler can run on-device
    next to the serving loop, and AMR^2 needs a *basic* optimal solution
    (Lemma 1 counts basic variables), which simplex — unlike interior-point —
    guarantees.
  * ``backend="numpy"`` — the same algorithm in float64 NumPy, used as the
    reference/oracle in tests and for very ill-conditioned instances.

Problem form:   minimize    c @ x
                subject to  A_ub @ x <= b_ub
                            A_eq @ x == b_eq
                            x >= 0

Phase 1 gives every row an artificial variable (initial basis), minimizes
their sum, and "drives out" artificials that linger in the basis at level 0
by prioritising their rows in the ratio test.  Phase 2 masks artificial
columns from ever re-entering.

Anti-cycling: the entering rule is Dantzig's (most negative reduced cost)
until ``bland_after`` consecutive degenerate (zero-improvement) pivots have
run, then Bland's rule (smallest eligible index) takes over until a
non-degenerate pivot resets the counter.  Together with the leaving
tie-break (smallest basic-variable index among min-ratio ties) this makes
every stall finite — Bland's theorem — in both backends.

Warm starts: consecutive fleet periods solve near-identical instances, so
`solve_lp` / `solve_lp_batch` accept the previous period's optimal basis
(``warm_basis``).  The warm path factors the basis once (one batched
``jnp.linalg.solve``), prices the full tableau out of it, skips phase 1
entirely when the basis is still primal feasible, and runs phase-2 pivots
from there — a revised-simplex start, typically 0–4 pivots instead of the
~R phase-1 + phase-2 pivots of a cold solve.  Lanes whose basis is rejected
(stale indices, singular/ill-conditioned factor, primal infeasible) fall
back to the existing two-phase path.  The batched pivot itself is a rank-1
update across the fleet dimension: ``impl="jnp"`` (default) uses the shared
`kernels/simplex_pivot/ref.py` update, ``impl="pallas"`` routes through the
`kernels/simplex_pivot` TPU kernel.

Reduced-tableau revised simplex (``method="revised"`` on
`simplex_batch_core` / `solve_lp_batch`): for the few-constraint /
many-column fleet LP (R = n+2 rows vs C0 = n(m+1)+2 columns) the dense
(R+1, C0+1) tableau is mostly dead weight — each lane only ever needs the
(R, R) basis inverse.  The revised path (`_revised_core`) carries exactly
that factor plus the basic solution, prices entering columns on demand
from the ORIGINAL column data (one BTRAN + a (R, C0) product per
iteration), and maintains the factor across pivots with product-form (eta)
rank-1 updates — `_batched_inverse` runs once per warm start, never per
pivot, and the C0-wide tableau is never materialized.  A caller that knows
its LP's columns hold at most two nonzeros (the engine, for AMR²) passes
their static rows (``col_rows``): pricing and FTRAN then read the two
values per column, and no pivot contracts against the (R, C0) data —
on a TPU, which emulates float64, those contractions dominated the
pivot.  Selection rules,
warm/cold/rejection semantics, statuses and pivot counts match the tableau
path (the parity tests pin status/basis/niter exactly and x/fun to solver
tolerance); summation orders differ, so results are not bit-identical.

Iteration budget: ``maxiter`` caps the TWO-PHASE TOTAL — phase 2 resumes
phase 1's counter — so an explicit user cap is respected exactly (shape-
derived defaults are pow2-bucketed for trace reuse; user values never
are).

Statuses: 0 optimal, 1 iteration limit, 2 infeasible, 3 unbounded.  Phase-1
non-convergence propagates (a maxiter-capped phase 1 can neither certify
feasibility nor hand phase 2 a valid basis, so the result is reported as
ITERATION_LIMIT rather than silently "optimal").
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .types import next_pow2, x64_scope

OPTIMAL, ITERATION_LIMIT, INFEASIBLE, UNBOUNDED = 0, 1, 2, 3

# Consecutive degenerate pivots tolerated before the entering rule switches
# from Dantzig to Bland.  Degenerate stalls shorter than this are common and
# harmless; a genuine cycle never improves the objective, so it cannot
# outlive the switch.
BLAND_AFTER = 8


def _bucket_maxiter(maxiter: int) -> int:
    """Round a shape-derived default maxiter UP to a power of two.

    `maxiter` is a static argname of the jitted solvers, so leaving it as
    the raw `50 * (rows + 2)` makes every distinct padded job count retrace
    the (vmapped) simplex; bucketing keeps the trace-key count at O(log)
    — mirroring `plan_batch`'s batch-axis bucketing — and only ever raises
    the iteration budget."""
    return next_pow2(maxiter)


@dataclasses.dataclass
class LPResult:
    x: np.ndarray
    fun: float
    status: int
    niter: int
    basis: np.ndarray  # row -> basic variable index
    warm: bool = False  # True when a warm_basis start was accepted

    @property
    def success(self) -> bool:
        return self.status == OPTIMAL


@dataclasses.dataclass
class BatchLPResult:
    """`solve_lp_batch` output: leading batch axis on every field."""
    x: np.ndarray        # (B, nv)
    fun: np.ndarray      # (B,)
    status: np.ndarray   # (B,) int
    niter: np.ndarray    # (B,) int
    basis: np.ndarray    # (B, R) int
    warm: Optional[np.ndarray] = None  # (B,) bool: warm start accepted

    def __len__(self) -> int:
        return self.x.shape[0]

    def __getitem__(self, b: int) -> LPResult:
        return LPResult(x=self.x[b], fun=float(self.fun[b]),
                        status=int(self.status[b]), niter=int(self.niter[b]),
                        basis=self.basis[b],
                        warm=bool(self.warm[b]) if self.warm is not None
                        else False)


# --------------------------------------------------------------------------
# Canonicalisation shared by both backends
# --------------------------------------------------------------------------
def _canonicalize(c, A_ub, b_ub, A_eq, b_eq):
    c = np.asarray(c, dtype=np.float64)
    nv = c.shape[0]
    rows = []
    rhs = []
    n_ub = 0
    if A_ub is not None:
        A_ub = np.asarray(A_ub, dtype=np.float64)
        b_ub = np.asarray(b_ub, dtype=np.float64)
        n_ub = A_ub.shape[0]
        rows.append(np.concatenate([A_ub, np.eye(n_ub)], axis=1))
        rhs.append(b_ub)
    if A_eq is not None:
        A_eq = np.asarray(A_eq, dtype=np.float64)
        b_eq = np.asarray(b_eq, dtype=np.float64)
        pad = np.zeros((A_eq.shape[0], n_ub))
        rows.append(np.concatenate([A_eq, pad], axis=1))
        rhs.append(b_eq)
    A = np.concatenate(rows, axis=0)
    b = np.concatenate(rhs, axis=0)
    # b >= 0 by row flips
    neg = b < 0
    A[neg] *= -1.0
    b[neg] *= -1.0
    c_full = np.concatenate([c, np.zeros(n_ub)])
    return A, b, c_full, nv, n_ub


# --------------------------------------------------------------------------
# JAX backend
# --------------------------------------------------------------------------
def _simplex_phase(tableau, basis, art_start, *, maxiter: int,
                   tol: float = 1e-7, bland_after: int = BLAND_AFTER,
                   it0=None):
    """Run pivots until optimal / maxiter / unbounded.

    tableau: (R+1, C+1); last row = objective (reduced costs | -obj value),
    last col = rhs.  basis: (R,) int32.  art_start: first artificial column
    (artificials may never enter; in phase 2 their rows get ratio priority
    so any basic artificial is driven out before it could turn positive).
    ``it0`` (scalar int32) seeds the iteration counter: phase 2 resumes
    phase 1's count so ``maxiter`` caps the two-phase TOTAL — an explicit
    user cap is respected exactly, never doubled.  The returned count is
    cumulative.
    """
    R = tableau.shape[0] - 1
    C = tableau.shape[1] - 1
    cols = jnp.arange(C)

    def cond(state):
        tab, basis, it, status, degen = state
        rc = tab[-1, :C]
        can_enter = (rc < -tol) & (cols < art_start)
        return (status == ITERATION_LIMIT) & jnp.any(can_enter) & (it < maxiter)

    def body(state):
        tab, basis, it, status, degen = state
        rc = tab[-1, :C]
        enter_mask = (rc < -tol) & (cols < art_start)
        # Dantzig rule while pivots improve the objective; after
        # `bland_after` consecutive degenerate pivots switch to Bland's
        # smallest-index rule (with the smallest-basis-index leaving
        # tie-break below, Bland's theorem rules out cycling).
        score = jnp.where(enter_mask, rc, jnp.inf)
        j_dantzig = jnp.argmin(score)
        j_bland = jnp.argmax(enter_mask)          # first eligible index
        j = jnp.where(degen >= bland_after, j_bland, j_dantzig)

        col = tab[:R, j]
        rhsv = tab[:R, -1]
        pos = col > tol
        ratio = jnp.where(pos, rhsv / jnp.where(pos, col, 1.0), jnp.inf)
        # Drive-out rule: a basic artificial sitting at level ~0 with a
        # nonzero pivot coefficient gets ratio 0 so it leaves the basis
        # first (it must not be allowed to turn positive again).
        art_basic = ((basis >= art_start) & (jnp.abs(col) > tol)
                     & (rhsv <= tol))
        ratio = jnp.where(art_basic, 0.0, ratio)
        unbounded = ~jnp.any(ratio < jnp.inf)
        # lexicographic-ish tie-break: smallest basis index among min ratios
        rmin = jnp.min(ratio)
        tie = ratio <= rmin + jnp.maximum(jnp.abs(rmin) * 1e-9, 1e-12)
        r = jnp.argmin(jnp.where(tie, basis, jnp.iinfo(jnp.int32).max))

        piv = tab[r, j]
        piv_row = tab[r] / piv
        tab2 = tab - jnp.outer(tab[:, j], piv_row)
        tab2 = tab2.at[r].set(piv_row)
        basis2 = basis.at[r].set(j.astype(basis.dtype))

        tab2 = jnp.where(unbounded, tab, tab2)
        basis2 = jnp.where(unbounded, basis, basis2)
        status2 = jnp.where(unbounded, UNBOUNDED, status)
        degen2 = jnp.where(unbounded, degen,
                           jnp.where(rmin <= tol, degen + 1,
                                     jnp.zeros_like(degen)))
        return tab2, basis2, it + 1, status2, degen2

    init = (tableau, basis,
            jnp.array(0, jnp.int32) if it0 is None else it0,
            jnp.array(ITERATION_LIMIT, jnp.int32), jnp.array(0, jnp.int32))
    tab, basis, it, status, _ = jax.lax.while_loop(cond, body, init)
    rc = tab[-1, :C]
    done = ~jnp.any((rc < -tol) & (cols < art_start))
    status = jnp.where((status == ITERATION_LIMIT) & done, OPTIMAL, status)
    return tab, basis, it, status


def _solve_core(A_j, b_j, c_j, nv, maxiter, tol, bland_after=BLAND_AFTER):
    """Pure-jnp two-phase simplex on one canonicalised instance.

    Shapes are static given (R, C0), so this traces once per problem shape
    and is `jax.vmap`-able over a leading batch axis (see `solve_lp_batch`).
    """
    R, C0 = A_j.shape         # C0 = nv + n_slack
    C = C0 + R                # + artificials
    dtype = A_j.dtype
    tab = jnp.zeros((R + 1, C + 1), dtype)
    tab = tab.at[:R, :C0].set(A_j)
    tab = tab.at[:R, C0:C].set(jnp.eye(R, dtype=dtype))
    tab = tab.at[:R, -1].set(b_j)
    # phase-1 objective: sum of artificials, expressed in reduced-cost form
    tab = tab.at[-1, :].set(-jnp.sum(tab[:R, :], axis=0))
    tab = tab.at[-1, C0:C].set(0.0)
    basis = jnp.arange(C0, C, dtype=jnp.int32)

    tab, basis, it1, status1 = _simplex_phase(
        tab, basis, jnp.array(C0, jnp.int32), maxiter=maxiter, tol=tol,
        bland_after=bland_after)
    phase1_obj = tab[-1, -1]  # = -(sum of artificials)
    infeasible = phase1_obj < -max(tol, 1e-5) * (1.0 + jnp.abs(b_j).sum())

    # phase 2: swap in the real objective
    obj = jnp.zeros((C + 1,), dtype)
    obj = obj.at[:C0].set(c_j)
    # make reduced costs of basic columns zero
    cb = obj[basis]                       # cost of basic vars
    obj = obj - cb @ tab[:R, :]
    tab = tab.at[-1, :].set(obj)
    # phase 2 resumes phase 1's iteration count: one shared maxiter budget
    tab, basis, it2, status2 = _simplex_phase(
        tab, basis, jnp.array(C0, jnp.int32), maxiter=maxiter, tol=tol,
        bland_after=bland_after, it0=it1)

    x = jnp.zeros((C,), dtype).at[basis].set(tab[:R, -1])
    fun = -tab[-1, -1]
    # A capped phase 1 can neither certify infeasibility nor hand phase 2 a
    # valid starting basis: propagate its status instead of trusting the
    # phase-2 verdict built on top of it.
    status = jnp.where(status1 != OPTIMAL, status1,
                       jnp.where(infeasible, INFEASIBLE, status2))
    return x[:nv], fun, status, it2, basis


def _solve_jax(A, b, c_full, nv, n_slack, maxiter, tol, bland_after):
    dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    return _solve_single_jit(jnp.asarray(A, dtype), jnp.asarray(b, dtype),
                             jnp.asarray(c_full, dtype), nv=nv,
                             maxiter=maxiter, tol=tol,
                             bland_after=bland_after)


@partial(jax.jit, static_argnames=("nv", "maxiter", "tol", "bland_after"))
def _solve_single_jit(A_j, b_j, c_j, *, nv, maxiter, tol,
                      bland_after=BLAND_AFTER):
    return _solve_core(A_j, b_j, c_j, nv, maxiter, tol, bland_after)


@partial(jax.jit, static_argnames=("nv", "maxiter", "tol", "bland_after"))
def _solve_batch_jit(A_j, b_j, c_j, *, nv, maxiter, tol,
                     bland_after=BLAND_AFTER):
    return jax.vmap(
        lambda A1, b1, c1: _solve_core(A1, b1, c1, nv, maxiter, tol,
                                       bland_after)
    )(A_j, b_j, c_j)


# --------------------------------------------------------------------------
# Warm-started revised simplex (batched)
# --------------------------------------------------------------------------
def _pivot_update_batch(tabs, r, j, mask, impl: str):
    """One rank-1 pivot across the whole lane stack.

    ``impl="jnp"`` uses the shared reference update; ``impl="pallas"``
    routes through the `kernels/simplex_pivot` TPU kernel (interpret mode
    off-TPU, like `cckp_dp`)."""
    if impl == "pallas":
        from ..kernels.simplex_pivot import ops as _pivot_ops
        return _pivot_ops.pivot_update(tabs, r, j, mask)
    from ..kernels.simplex_pivot.ref import pivot_update_ref
    return pivot_update_ref(tabs, r, j, mask)


def _phase_batched(tabs, bases, art_start: int, *, maxiter: int, tol: float,
                   bland_after: int, impl: str, it0=None):
    """Masked batched simplex phase over stacked tableaus (B, R+1, C+1).

    Per-lane semantics match `_simplex_phase` (Dantzig entering with the
    Bland fallback, smallest-basis-index leaving tie-break, artificial
    drive-out) but every iteration pivots ALL still-active lanes at once —
    the rank-1 update runs across the fleet dimension in one call
    (`_pivot_update_batch`), which is what the `simplex_pivot` Pallas
    kernel accelerates.  ``it0`` (B,) int32 seeds the per-lane iteration
    counters (shared two-phase maxiter budget; see `_simplex_phase`)."""
    B, R1, C1 = tabs.shape
    R, C = R1 - 1, C1 - 1
    cols = jnp.arange(C)
    intmax = jnp.iinfo(jnp.int32).max

    def cond(state):
        tabs, bases, it, status, degen = state
        return jnp.any((status == ITERATION_LIMIT) & (it < maxiter))

    def body(state):
        tabs, bases, it, status, degen = state
        rc = tabs[:, -1, :C]                              # (B, C)
        enter_mask = (rc < -tol) & (cols[None, :] < art_start)
        has_enter = enter_mask.any(axis=1)
        running = status == ITERATION_LIMIT
        status = jnp.where(running & ~has_enter, OPTIMAL, status)
        active = running & has_enter & (it < maxiter)

        score = jnp.where(enter_mask, rc, jnp.inf)
        j_dantzig = jnp.argmin(score, axis=1)
        j_bland = jnp.argmax(enter_mask, axis=1)
        j = jnp.where(degen >= bland_after, j_bland,
                      j_dantzig).astype(jnp.int32)

        col = jnp.take_along_axis(tabs[:, :R, :], j[:, None, None],
                                  axis=2)[..., 0]         # (B, R)
        rhsv = tabs[:, :R, -1]
        pos = col > tol
        ratio = jnp.where(pos, rhsv / jnp.where(pos, col, 1.0), jnp.inf)
        art_basic = ((bases >= art_start) & (jnp.abs(col) > tol)
                     & (rhsv <= tol))
        ratio = jnp.where(art_basic, 0.0, ratio)
        unbounded = ~jnp.any(ratio < jnp.inf, axis=1)
        rmin = jnp.min(ratio, axis=1)
        tie = ratio <= (rmin + jnp.maximum(jnp.abs(rmin) * 1e-9,
                                           1e-12))[:, None]
        r = jnp.argmin(jnp.where(tie, bases, intmax),
                       axis=1).astype(jnp.int32)

        do_pivot = active & ~unbounded
        tabs = _pivot_update_batch(tabs, r, j, do_pivot, impl)
        is_r = jnp.arange(R)[None, :] == r[:, None]
        bases = jnp.where(do_pivot[:, None] & is_r, j[:, None], bases)
        status = jnp.where(active & unbounded, UNBOUNDED, status)
        degen = jnp.where(do_pivot,
                          jnp.where(rmin <= tol, degen + 1,
                                    jnp.zeros_like(degen)), degen)
        return tabs, bases, it + active.astype(it.dtype), status, degen

    init = (tabs, bases,
            jnp.zeros(B, jnp.int32) if it0 is None else it0,
            jnp.full(B, ITERATION_LIMIT, jnp.int32), jnp.zeros(B, jnp.int32))
    tabs, bases, it, status, _ = jax.lax.while_loop(cond, body, init)
    rc = tabs[:, -1, :C]
    done = ~((rc < -tol) & (cols[None, :] < art_start)).any(axis=1)
    status = jnp.where((status == ITERATION_LIMIT) & done, OPTIMAL, status)
    return tabs, bases, it, status


def _batched_inverse(Bmat):
    """Gauss-Jordan inverse with partial pivoting, vectorized across the
    lane axis: (B, R, R) -> (B, R, R).

    XLA:CPU's batched `jnp.linalg.solve` costs ~4 ms for 256 14x14 lanes
    (it serializes the per-lane LAPACK calls) — an R-step fori_loop of
    whole-batch rank-1 eliminations is ~5x cheaper at fleet sizes and is
    exactly the same shaped work as the simplex pivots that follow.
    Singular lanes come out inf/nan and are caught by the caller's
    residual check."""
    B, R, _ = Bmat.shape
    dtype = Bmat.dtype
    eye = jnp.broadcast_to(jnp.eye(R, dtype=dtype), (B, R, R))
    aug = jnp.concatenate([Bmat, eye], axis=2)             # (B, R, 2R)
    rows = jnp.arange(R)

    def body(k, aug):
        col = jax.lax.dynamic_index_in_dim(aug, k, axis=2, keepdims=False)
        cand = jnp.where(rows[None, :] >= k, jnp.abs(col), -1.0)
        p = jnp.argmax(cand, axis=1)                       # pivot row
        row_p = jnp.take_along_axis(aug, p[:, None, None], axis=1)[:, 0]
        row_k = jax.lax.dynamic_index_in_dim(aug, k, axis=1,
                                             keepdims=False)
        is_k = rows[None, :] == k
        is_p = rows[None, :] == p[:, None]
        aug = jnp.where(is_k[:, :, None], row_p[:, None, :], aug)
        aug = jnp.where((is_p & ~is_k)[:, :, None], row_k[:, None, :], aug)
        piv_row = jax.lax.dynamic_index_in_dim(aug, k, axis=1,
                                               keepdims=False)
        piv = jax.lax.dynamic_index_in_dim(piv_row, k, axis=1,
                                           keepdims=True)
        piv_row = piv_row / piv
        colv = jax.lax.dynamic_index_in_dim(aug, k, axis=2,
                                            keepdims=False)
        new = aug - colv[:, :, None] * piv_row[:, None, :]
        return jnp.where(is_k[:, :, None], piv_row[:, None, :], new)

    aug = jax.lax.fori_loop(0, R, body, aug)
    return aug[:, :, R:]


def _warm_init_reduced(A, b, basis0):
    """Factor each lane's previous basis and repair primal infeasibility,
    in REDUCED (basis-inverse) form.

    One batched factor (`_batched_inverse`) per lane; rows the basis
    leaves infeasible on the new data (negative transformed rhs) are
    sign-flipped — the flip is applied to the Binv ROW, which distributes
    exactly over the later ``Binv @ A`` / pricing products — and handed a
    VIRTUAL tableau-space artificial (basis label C0 + row, column never
    materialized), so phase 1 shrinks to ~#violated-rows repair pivots —
    zero when the basis is still feasible.

    Returns ``(Binv (B, R, R), rhs (B, R), bas (B, R) int32, ok (B,))``;
    lanes with ``ok`` False (out-of-range / -1 basis rows — a device that
    switched solver or sat out an outage — or a singular/ill-conditioned
    factor) hold garbage and must run cold.  Shared by `_warm_init` (the
    dense-tableau paths) and `_revised_core` so the accept thresholds and
    repair semantics cannot drift apart."""
    B, R, C0 = A.shape
    dtype = A.dtype
    bas = jnp.clip(basis0, 0, C0 - 1).astype(jnp.int32)
    in_range = (basis0 >= 0).all(axis=1) & (basis0 < C0).all(axis=1)

    Bmat = jnp.take_along_axis(A, bas[:, None, :], axis=2)     # (B, R, R)
    Binv = _batched_inverse(Bmat)
    resid = jnp.max(jnp.abs(Bmat @ Binv - jnp.eye(R, dtype=dtype)),
                    axis=(1, 2))
    rhs = (Binv @ b[..., None])[..., 0]                        # (B, R)

    # f32 (global x64 off, single-instance path) carries ~1e-7 relative
    # noise through the factor-solve: loosen the accept thresholds so a
    # basic variable sitting numerically at 0 does not bounce the basis
    feas_tol, resid_tol = (1e-9, 1e-6) if dtype == jnp.float64 \
        else (1e-5, 1e-3)
    ok = in_range & jnp.isfinite(resid) & (resid < resid_tol)

    # feasibility repair: flip violated rows; each flipped row's virtual
    # artificial goes basic (label C0 + row)
    flip = rhs < -feas_tol                                     # (B, R)
    sgn = jnp.where(flip, -1.0, 1.0)
    Binv = Binv * sgn[:, :, None]
    rhs = jnp.maximum(rhs * sgn, 0.0)      # clamp -feas_tol..0 dust to 0
    rows = jnp.arange(R, dtype=jnp.int32)
    bas = jnp.where(flip, C0 + rows[None, :], bas)
    return Binv, rhs, bas.astype(jnp.int32), ok


def _warm_init(A, b, basis0):
    """`_warm_init_reduced` expanded to dense-tableau form: the repaired
    factor prices the full tableau (``tabA = Binv @ A``) for the
    `_phase_batched` paths.  Because the repair sign-flips distribute
    exactly over the row sums (IEEE negation is exact), this is
    bit-identical to flipping the priced tableau's rows directly.

    Returns ``(tabA (B, R, C0), rhs (B, R), bas (B, R) int32, ok (B,))``;
    shared by `_warm_batch_jit` (host dispatch) and `simplex_batch_core`
    (the traced engine path)."""
    Binv, rhs, bas, ok = _warm_init_reduced(A, b, basis0)
    return Binv @ A, rhs, bas, ok


def _two_phase_virtual(tabA, rhs, bas, b, c_full, *, nv, maxiter, tol,
                       bland_after, impl, lane_mask=None):
    """Both simplex phases over virtual-artificial tableaus.

    Builds the (B, R+1, C0+1) tableau stack from per-lane rows/rhs and a
    basis whose artificial members are LABELS >= C0 (columns never
    materialized — they may never enter, and drive-out/pricing only read
    labels), runs phase 1 (minimize the sum of artificial-basis rows, in
    reduced-cost form), swaps in the real objective priced out over the
    resulting basis, runs phase 2, and extracts the solution by
    scatter-add (clipped virtual labels contribute 0, so they cannot
    clobber a real basic variable's slot).  ``lane_mask`` False zeroes a
    lane's tableau — no entering column, 0 pivots, garbage x.

    The ONE definition of the warm/cold two-phase pipeline, shared by
    `_warm_batch_jit` and `simplex_batch_core`: the phase-1 infeasibility
    certificate and status propagation live here only.

    Returns ``(x (B, nv), fun, status, niter, bases)``."""
    B, R, C0 = tabA.shape
    dtype = tabA.dtype
    tabs = jnp.zeros((B, R + 1, C0 + 1), dtype)
    tabs = tabs.at[:, :R, :C0].set(tabA)
    tabs = tabs.at[:, :R, -1].set(rhs)
    # phase-1 objective: -(sum of artificial-basis rows) — for a cold lane
    # (every row's basis virtual) this is `_solve_core`'s -sum(rows)
    art_row = (bas >= C0).astype(dtype)
    p1 = -jnp.einsum("br,brc->bc", art_row, tabs[:, :R, :])
    tabs = tabs.at[:, -1, :].set(p1)
    if lane_mask is not None:
        tabs = jnp.where(lane_mask[:, None, None], tabs, 0.0)

    tabs, bases, it1, status1 = _phase_batched(
        tabs, bas, C0, maxiter=maxiter, tol=tol, bland_after=bland_after,
        impl=impl)
    phase1_obj = tabs[:, -1, -1]           # = -(sum of basic artificials)
    infeasible = phase1_obj < -max(tol, 1e-5) * (
        1.0 + jnp.abs(b).sum(axis=1))

    # phase 2: swap in the real objective, priced out over the basis
    # (virtual artificial labels price at cost 0)
    obj = jnp.zeros((B, C0 + 1), dtype)
    obj = obj.at[:, :C0].set(c_full)
    cb = jnp.where(bases < C0,
                   jnp.take_along_axis(obj[:, :C0],
                                       jnp.clip(bases, 0, C0 - 1), axis=1),
                   0.0)                                        # (B, R)
    obj = obj - jnp.einsum("br,brc->bc", cb, tabs[:, :R, :])
    if lane_mask is not None:
        # keep masked lanes inert in phase 2 too: a real objective row on
        # a zeroed tableau would otherwise spend one "unbounded" pivot
        obj = jnp.where(lane_mask[:, None], obj, 0.0)
    tabs = tabs.at[:, -1, :].set(obj)
    # phase 2 resumes phase 1's per-lane counts: one shared maxiter budget
    tabs, bases, it2, status2 = _phase_batched(
        tabs, bases, C0, maxiter=maxiter, tol=tol, bland_after=bland_after,
        impl=impl, it0=it1)

    vals = jnp.where(bases < C0, tabs[:, :R, -1], 0.0)
    x = jnp.zeros((B, C0), dtype)
    x = x.at[jnp.arange(B)[:, None], jnp.clip(bases, 0, C0 - 1)].add(vals)
    fun = -tabs[:, -1, -1]
    status = jnp.where(status1 != OPTIMAL, status1,
                       jnp.where(infeasible, INFEASIBLE, status2))
    return x[:, :nv], fun, status, it2, bases


# --------------------------------------------------------------------------
# Reduced-tableau revised simplex (batched)
# --------------------------------------------------------------------------
def _reduced_pivot_batch(A, c_phase, Binv, xB, bas, use_bland, may_pivot,
                         lane_ok, art_cost, tol, impl: str, cols=None):
    """One fused revised-simplex iteration across the whole lane stack.

    ``impl="jnp"`` uses the shared reference op; ``impl="pallas"`` routes
    through the fused `kernels/simplex_pivot.reduced_pivot` TPU kernel
    (interpret mode off-TPU, like the dense pivot).  ``cols`` (the column
    form of `_column_form`) prices and runs FTRAN from it instead of
    contracting against ``A`` (jnp only)."""
    if cols is not None:
        from ..kernels.simplex_pivot.ref import reduced_pivot_columns_ref
        return reduced_pivot_columns_ref(cols, c_phase, Binv, xB, bas,
                                         use_bland, may_pivot, lane_ok,
                                         art_cost=art_cost, tol=tol)
    if impl == "pallas":
        from ..kernels.simplex_pivot import ops as _pivot_ops
        return _pivot_ops.reduced_pivot(A, c_phase, Binv, xB, bas,
                                        use_bland, may_pivot, lane_ok,
                                        art_cost=art_cost, tol=tol)
    from ..kernels.simplex_pivot.ref import reduced_pivot_ref
    return reduced_pivot_ref(A, c_phase, Binv, xB, bas, use_bland,
                             may_pivot, lane_ok, art_cost=art_cost,
                             tol=tol)


def _revised_phase(A, c_phase, Binv, xB, bas, *, art_cost: float,
                   maxiter: int, tol: float, bland_after: int, impl: str,
                   lane_ok, it0=None, cols=None):
    """Masked batched simplex phase in REDUCED form: only the (R, R)
    basis-inverse factor and the basic solution are carried per lane;
    every iteration prices all C0 columns on demand out of the factor and
    applies the product-form (eta) rank-1 update — the C0-wide tableau of
    `_phase_batched` is never materialized.

    Per-lane selection rules (Dantzig entering with the Bland fallback,
    smallest-basis-index leaving tie-break, artificial drive-out) and the
    status/iteration bookkeeping match `_phase_batched`; ``art_cost`` is
    the phase cost of virtual artificial labels (1 in phase 1, 0 in
    phase 2) and ``it0`` seeds the per-lane counters (shared two-phase
    maxiter budget).  ``cols`` (see `_column_form`) selects the column
    pricer for the pivots and the final optimality check."""
    from ..kernels.simplex_pivot.ref import (price_columns_ref,
                                            price_reduced_ref)
    B = A.shape[0]
    lane_ok = (jnp.ones(B, dtype=bool) if lane_ok is None
               else jnp.asarray(lane_ok, dtype=bool))

    def cond(state):
        Binv, xB, bas, it, status, degen = state
        return jnp.any((status == ITERATION_LIMIT) & (it < maxiter))

    def body(state):
        Binv, xB, bas, it, status, degen = state
        running = status == ITERATION_LIMIT
        Binv2, xB2, bas2, has_enter, unbounded, degen_piv = \
            _reduced_pivot_batch(A, c_phase, Binv, xB, bas,
                                 degen >= bland_after,
                                 running & (it < maxiter), lane_ok,
                                 art_cost, tol, impl, cols)
        status = jnp.where(running & ~has_enter, OPTIMAL, status)
        active = running & has_enter & (it < maxiter)
        status = jnp.where(active & unbounded, UNBOUNDED, status)
        do_pivot = active & ~unbounded
        degen = jnp.where(do_pivot,
                          jnp.where(degen_piv, degen + 1,
                                    jnp.zeros_like(degen)), degen)
        return (Binv2, xB2, bas2, it + active.astype(it.dtype), status,
                degen)

    init = (Binv, xB, bas,
            jnp.zeros(B, jnp.int32) if it0 is None else it0,
            jnp.full(B, ITERATION_LIMIT, jnp.int32), jnp.zeros(B, jnp.int32))
    Binv, xB, bas, it, status, _ = jax.lax.while_loop(cond, body, init)
    rc = (price_reduced_ref(A, c_phase, Binv, bas, art_cost)
          if cols is None else
          price_columns_ref(cols, c_phase, Binv, bas, art_cost))
    done = ~((rc < -tol) & lane_ok[:, None]).any(axis=1)
    status = jnp.where((status == ITERATION_LIMIT) & done, OPTIMAL, status)
    return Binv, xB, bas, it, status


def _revised_two_phase(A, b, c_full, Binv, xB, bas, *, nv, maxiter, tol,
                       bland_after, impl, lane_mask=None, cols=None):
    """Both simplex phases in reduced form (`_two_phase_virtual`'s twin).

    Phase 1 minimizes the sum of basic virtual artificials (real columns
    cost 0, artificial labels cost 1), phase 2 prices the real objective;
    the infeasibility certificate reads the basic-artificial levels off
    ``xB`` directly (the reduced form of the tableau's phase-1 objective
    cell).  ``lane_mask`` False lanes never produce an entering column —
    0 pivots, OPTIMAL status, x = 0 — matching the zeroed-tableau
    contract.  Returns ``(x (B, nv), fun, status, niter, bases)``."""
    B, R, C0 = A.shape
    dtype = A.dtype
    Binv, xB, bas, it1, status1 = _revised_phase(
        A, jnp.zeros_like(c_full), Binv, xB, bas, art_cost=1.0,
        maxiter=maxiter, tol=tol, bland_after=bland_after, impl=impl,
        lane_ok=lane_mask, cols=cols)
    art_sum = jnp.sum(jnp.where(bas >= C0, xB, 0.0), axis=1)
    infeasible = art_sum > max(tol, 1e-5) * (1.0 + jnp.abs(b).sum(axis=1))
    if lane_mask is not None:
        infeasible = infeasible & lane_mask

    # phase 2 resumes phase 1's per-lane counts: one shared maxiter budget
    Binv, xB, bas, it2, status2 = _revised_phase(
        A, c_full, Binv, xB, bas, art_cost=0.0, maxiter=maxiter, tol=tol,
        bland_after=bland_after, impl=impl, lane_ok=lane_mask, it0=it1,
        cols=cols)

    vals = jnp.where(bas < C0, xB, 0.0)
    x = jnp.zeros((B, C0), dtype)
    x = x.at[jnp.arange(B)[:, None], jnp.clip(bas, 0, C0 - 1)].add(vals)
    cb = jnp.where(bas < C0,
                   jnp.take_along_axis(c_full, jnp.clip(bas, 0, C0 - 1),
                                       axis=1), 0.0)
    fun = jnp.sum(cb * vals, axis=1)
    if lane_mask is not None:
        fun = jnp.where(lane_mask, fun, 0.0)
    status = jnp.where(status1 != OPTIMAL, status1,
                       jnp.where(infeasible, INFEASIBLE, status2))
    return x[:, :nv], fun, status, it2, bas


def _column_form(A, col_rows):
    """The column form of an LP whose every column has at most two
    nonzeros: ``(ra, rb, va, vb)`` with the static row pair ``col_rows``
    (2, C0) — for a column with one nonzero, ``rb`` names any row where
    it is 0 — and the values gathered once from ``A`` (B, R, C0), so
    there is no second copy of the coefficients to drift from it."""
    B, R, C0 = A.shape
    rows = np.asarray(col_rows)
    if rows.shape != (2, C0) or rows.min() < 0 or rows.max() >= R:
        raise ValueError(f"col_rows must be (2, {C0}) row indices in "
                         f"[0, {R}); got shape {rows.shape}")
    ra, rb = rows.astype(np.int32)
    cidx = np.arange(C0)
    return ra, rb, A[:, ra, cidx], A[:, rb, cidx]


def _revised_core(A, b, c_full, basis0, *, nv, maxiter, tol,
                  bland_after=BLAND_AFTER, impl="jnp", lane_mask=None,
                  col_rows=None):
    """Traceable warm-OR-cold batched revised simplex — the
    ``method="revised"`` body of `simplex_batch_core`, with the same
    start/rejection semantics: a cold lane's factor is the identity
    (xB = b, every row basic on its virtual artificial) and a warm lane
    reuses its repaired `_warm_init_reduced` factor; rejected lanes start
    cold in the same call.  ``col_rows`` prices from the column form
    (`_column_form`).  Returns the `simplex_batch_core` tuple."""
    B, R, C0 = A.shape
    dtype = A.dtype
    rows = jnp.arange(R, dtype=jnp.int32)
    bas_c = jnp.broadcast_to(C0 + rows[None, :], (B, R)).astype(jnp.int32)
    eye = jnp.broadcast_to(jnp.eye(R, dtype=dtype), (B, R, R))

    if basis0 is None:
        warm_ok = jnp.zeros(B, dtype=bool)
        Binv, xB, bas = eye, b, bas_c
    else:
        Binv_w, rhs_w, bas_w, warm_ok = _warm_init_reduced(A, b, basis0)
        Binv = jnp.where(warm_ok[:, None, None], Binv_w, eye)
        xB = jnp.where(warm_ok[:, None], rhs_w, b)
        bas = jnp.where(warm_ok[:, None], bas_w, bas_c)

    cols = None if col_rows is None else _column_form(A, col_rows)
    x, fun, status, niter, bases = _revised_two_phase(
        A, b, c_full, Binv, xB, bas, nv=nv, maxiter=maxiter, tol=tol,
        bland_after=bland_after, impl=impl, lane_mask=lane_mask, cols=cols)
    return x, fun, status, niter, bases, warm_ok


@partial(jax.jit,
         static_argnames=("nv", "maxiter", "tol", "bland_after", "impl"))
def _revised_batch_jit(A_j, b_j, c_j, basis0, *, nv, maxiter, tol,
                       bland_after=BLAND_AFTER, impl="jnp"):
    """Jitted `_revised_core` for the `solve_lp_batch(method="revised")`
    host dispatch (warm and cold lanes resolve in ONE call — no separate
    rejected-subset re-solve)."""
    return _revised_core(A_j, b_j, c_j, basis0, nv=nv, maxiter=maxiter,
                         tol=tol, bland_after=bland_after, impl=impl)


@partial(jax.jit,
         static_argnames=("nv", "maxiter", "tol", "bland_after", "impl"))
def _warm_batch_jit(A_j, b_j, c_j, basis0, *, nv, maxiter, tol,
                    bland_after=BLAND_AFTER, impl="jnp"):
    """Revised-simplex warm start from a previous optimal basis
    (`_warm_init` + `_two_phase_virtual`).

    Returns ``(x, fun, status, niter, basis, ok)``; lanes with ``ok``
    False (out-of-range basis indices or a singular/ill-conditioned
    factor) hold garbage and must be re-solved by the cold two-phase
    path — `solve_lp_batch` dispatches them to `_solve_batch_jit` on a
    pow2-padded subset (`simplex_batch_core` is the traced alternative
    that runs them cold in the same call)."""
    tabA, rhs, bas, ok = _warm_init(A_j, b_j, basis0)
    # rejected lanes: zero tableau -> no entering column -> 0 pivots spent
    x, fun, status, niter, bases = _two_phase_virtual(
        tabA, rhs, bas, b_j, c_j, nv=nv, maxiter=maxiter, tol=tol,
        bland_after=bland_after, impl=impl, lane_mask=ok)
    return x, fun, status, niter, bases, ok


def simplex_batch_core(A, b, c_full, basis0, *, nv: int, maxiter: int,
                       tol: float = 1e-7, bland_after: int = BLAND_AFTER,
                       impl: str = "jnp", lane_mask=None,
                       method: str = "tableau", col_rows=None):
    """Traceable warm-OR-cold batched two-phase simplex (the scan path).

    Unlike `solve_lp_batch` — which accepts warm lanes via `_warm_batch_jit`
    and re-solves rejected lanes with a second host-dispatched cold call —
    this is ONE pure-jnp function usable inside `jax.jit` / `lax.scan` /
    `shard_map` (the `repro.api.engine` period step): every lane starts
    either from its previous basis (accepted: factor once, sign-flip and
    virtually repair infeasible rows) or from the cold all-artificial
    tableau (rejected / ``basis0`` rows of -1 / ``basis0=None``), and a
    single `_phase_batched` pass runs phase 1 + phase 2 for the whole
    stack.  A warm-feasible lane spends 0 phase-1 pivots; a cold lane runs
    the same pivots `_solve_core` would, so per-lane results are
    bit-comparable with the host `solve_lp_batch` dispatch.

    ALL artificials are virtual (basis LABELS >= C0, columns never
    materialized — the `_warm_batch_jit` trick extended to the cold path:
    a cold lane's initial basis is simply every row's virtual label and
    phase 1 minimizes -sum(rows), exactly `_solve_core`'s start): the
    tableau stays (R+1, C0+1) wide, ~40% less pivot traffic than
    materialized artificial columns, with identical pivot sequences —
    artificials may never enter, and the drive-out/pricing rules only read
    their labels.

    ``basis0=None`` skips the warm factorization entirely (every lane
    cold) — the engine's backpressure replan path.  ``lane_mask`` (B,)
    bool: lanes marked False get a zeroed tableau — no entering column, 0
    pivots, garbage x — for masked sub-batch solves without a host-side
    subset.

    ``method`` selects the pivot representation: ``"tableau"`` (default)
    is the dense (R+1, C0+1) path above, bit-compatible with the existing
    dispatch; ``"revised"`` carries only the (R, R) basis inverse per lane
    (`_revised_core`) — same warm/cold/rejection semantics and selection
    rules, entering columns priced on demand, eta-factor updates instead
    of wide-tableau pivots.  The paths agree on status/basis/pivot counts
    and to solver tolerance on x/fun (pinned by the parity tests), but not
    bit-for-bit — their floating-point summation orders differ.

    ``col_rows`` (revised, ``impl="jnp"`` only): for an LP whose every
    column has at most two nonzeros, a static (2, C0) int array naming
    each column's two rows (for a one-nonzero column, any row where it
    is 0; `amr2.lp_column_rows` for the AMR² relaxation).  The revised
    pivots then price and run FTRAN from the two gathered values per
    column (`kernels.simplex_pivot.ref.reduced_pivot_columns_ref`): no
    float64 contraction against ``A`` per pivot, the same terms a dense
    one would add save exact zeros, and the same selection and update.
    ``None`` (default) contracts against ``A``.

    Expects canonicalised inputs (``b >= 0``; see `_canonicalize_batch`).
    Returns ``(x (B, nv), fun, status, niter, basis, warm_ok)``.
    """
    if col_rows is not None and (method, impl) != ("revised", "jnp"):
        raise ValueError("col_rows needs method='revised' and impl='jnp' "
                         f"(got method={method!r}, impl={impl!r})")
    if method == "revised":
        return _revised_core(A, b, c_full, basis0, nv=nv, maxiter=maxiter,
                             tol=tol, bland_after=bland_after, impl=impl,
                             lane_mask=lane_mask, col_rows=col_rows)
    if method != "tableau":
        raise ValueError(f"unknown simplex method {method!r}; expected "
                         f"'tableau' or 'revised'")
    B, R, C0 = A.shape
    rows = jnp.arange(R, dtype=jnp.int32)
    # cold init: every row basic on its virtual artificial (`_solve_core`)
    bas_c = jnp.broadcast_to(C0 + rows[None, :], (B, R)).astype(jnp.int32)

    if basis0 is None:
        warm_ok = jnp.zeros(B, dtype=bool)
        tabA, rhs, bas = A, b, bas_c
    else:
        tabA_w, rhs_w, bas_w, warm_ok = _warm_init(A, b, basis0)
        # rejected lanes start cold IN the same call (the host dispatch
        # instead zeroes them and re-solves a pow2 subset; _warm_batch_jit)
        tabA = jnp.where(warm_ok[:, None, None], tabA_w, A)
        rhs = jnp.where(warm_ok[:, None], rhs_w, b)
        bas = jnp.where(warm_ok[:, None], bas_w, bas_c)

    x, fun, status, niter, bases = _two_phase_virtual(
        tabA, rhs, bas, b, c_full, nv=nv, maxiter=maxiter, tol=tol,
        bland_after=bland_after, impl=impl, lane_mask=lane_mask)
    return x, fun, status, niter, bases, warm_ok


# --------------------------------------------------------------------------
# Implicit differentiation: custom VJP at the converged basis
# --------------------------------------------------------------------------
class _ImplicitCfg(NamedTuple):
    """Hashable static config for `_simplex_implicit` (nondiff argnum 0)."""
    nv: int
    maxiter: int
    tol: float
    bland_after: int
    impl: str
    method: str


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _simplex_implicit(cfg: _ImplicitCfg, A, b, c_full, basis0, lane_mask):
    return simplex_batch_core(
        A, b, c_full, basis0, nv=cfg.nv, maxiter=cfg.maxiter, tol=cfg.tol,
        bland_after=cfg.bland_after, impl=cfg.impl, lane_mask=lane_mask,
        method=cfg.method)


def _simplex_implicit_fwd(cfg, A, b, c_full, basis0, lane_mask):
    # The pivot loops run UNdifferentiated (they are `lax.while_loop`s and
    # could not be reverse-differentiated anyway); only their *fixed point*
    # — the converged basis — feeds the backward pass.
    out = _simplex_implicit(cfg, A, b, c_full, basis0, lane_mask)
    _, _, status, _, bases, _ = out
    return out, (A, b, c_full, bases, status, basis0, lane_mask)


def _simplex_implicit_bwd(cfg, res, cts):
    from ..kernels.simplex_pivot.ref import kkt_vjp_ref
    gx, gfun = cts[0], cts[1]        # status/niter/bases/warm_ok: int/bool
    A, b, c_full, bases, status, basis0, lane_mask = res
    valid = status == OPTIMAL
    if lane_mask is not None:
        valid = valid & lane_mask
    A_bar, b_bar, c_bar = kkt_vjp_ref(
        A, b, c_full, bases, gx, gfun, valid, nv=cfg.nv)
    f0 = jax.dtypes.float0
    b0_bar = None if basis0 is None else np.zeros(basis0.shape, f0)
    lm_bar = None if lane_mask is None else np.zeros(lane_mask.shape, f0)
    return A_bar, b_bar, c_bar, b0_bar, lm_bar


_simplex_implicit.defvjp(_simplex_implicit_fwd, _simplex_implicit_bwd)


def simplex_batch_grad(A, b, c_full, basis0, *, nv: int, maxiter: int,
                       tol: float = 1e-7, bland_after: int = BLAND_AFTER,
                       impl: str = "jnp", lane_mask=None,
                       method: str = "tableau"):
    """`simplex_batch_core` with an implicit-function VJP attached.

    Forward pass is the SAME traced warm-or-cold two-phase simplex (bitwise
    identical outputs); the backward pass never differentiates the pivot
    loops.  Instead, at the converged basis ``B`` the optimum is locally
    ``x_B = B^{-1} b`` (active-set / KKT view), so cotangents w.r.t.
    ``(A, b, c_full)`` come from one adjoint (R, R) solve per lane
    (`kernels.simplex_pivot.ref.kkt_vjp_ref`) against the SAME basis factor
    the revised method carries.  Integer bookkeeping — ``basis0`` warm
    labels, ``lane_mask`` — gets symbolic-zero (float0) cotangents, and the
    ``status``/``niter``/``bases``/``warm_ok`` outputs are gradient fences:
    nothing differentiable flows through them.

    Caveats (documented, by design):
      * Non-OPTIMAL or masked lanes contribute exactly-zero cotangents
        (their basis is meaningless; the engine layer must not rely on
        gradients through failed lanes).
      * At a DEGENERATE optimal basis the optimum is not differentiable;
        the VJP returns the subgradient selected by the converged basis —
        fine for optimization, not for exact sensitivity audits.
      * The host-dispatched `solve_lp_batch` (NumPy boundary) is NOT
        covered: differentiable callers must stay on this traced path.
    """
    cfg = _ImplicitCfg(nv=nv, maxiter=maxiter, tol=tol,
                       bland_after=bland_after, impl=impl, method=method)
    return _simplex_implicit(cfg, A, b, c_full, basis0, lane_mask)


def _warm_np(A, b, c_full, nv, basis0, maxiter, tol, bland_after):
    """NumPy warm start: same algorithm as `_warm_batch_jit` (basis
    factorization, sign-flip + tableau-space-artificial feasibility
    repair, warm phase 1 + phase 2), one instance.  The oracle path keeps
    the artificial columns materialized — clarity over the batched path's
    virtual-label trick.  Returns an LPResult-tuple or None on basis
    rejection."""
    R, C0 = A.shape
    C = C0 + R
    basis0 = np.asarray(basis0)
    if basis0.shape != (R,) or (basis0 < 0).any() or (basis0 >= C0).any():
        return None
    Bmat = A[:, basis0]
    try:
        Binv = np.linalg.solve(Bmat, np.eye(R))
    except np.linalg.LinAlgError:
        return None
    resid = np.max(np.abs(Bmat @ Binv - np.eye(R)))
    if not np.isfinite(resid) or resid >= 1e-6:
        return None
    rhs = Binv @ b
    tabA = Binv @ A

    flip = rhs < -1e-9                       # feasibility-repair rows
    sgn = np.where(flip, -1.0, 1.0)
    tabA = tabA * sgn[:, None]
    rhs = np.maximum(rhs * sgn, 0.0)
    basis = basis0.astype(np.int64).copy()
    basis[flip] = C0 + np.nonzero(flip)[0]

    tab = np.zeros((R + 1, C + 1))
    tab[:R, :C0] = tabA
    tab[:R, C0:C] = np.eye(R)
    tab[:R, -1] = rhs
    tab[-1, :] = -tab[:R, :][flip].sum(axis=0)
    tab[-1, C0:C] = 0.0
    tab, basis, it1, st1 = _phase_np(tab, basis, C0, maxiter, tol,
                                     bland_after)
    infeasible = tab[-1, -1] < -max(tol, 1e-8) * (1.0 + np.abs(b).sum())

    obj = np.zeros(C + 1)
    obj[:C0] = c_full
    obj = obj - obj[basis] @ tab[:R, :]
    tab[-1, :] = obj
    tab, basis, it2, st2 = _phase_np(tab, basis, C0, maxiter, tol,
                                     bland_after, it0=it1)
    x = np.zeros(C)
    x[basis] = tab[:R, -1]
    if st1 != OPTIMAL:
        status = st1
    else:
        status = INFEASIBLE if infeasible else st2
    return x[:nv], -tab[-1, -1], status, it2, basis


# --------------------------------------------------------------------------
# NumPy backend (float64 reference)
# --------------------------------------------------------------------------
def _phase_np(tab, basis, art_start, maxiter, tol,
              bland_after=BLAND_AFTER, it0=0):
    """``it0`` seeds the iteration counter (cumulative across phases, so
    an explicit ``maxiter`` caps the two-phase total; see
    `_simplex_phase`).  Optimality is checked before the cap — matching
    the jax path's post-loop upgrade."""
    R = tab.shape[0] - 1
    C = tab.shape[1] - 1
    it = it0
    degen = 0
    while True:
        rc = tab[-1, :C]
        enter = np.where((rc < -tol) & (np.arange(C) < art_start))[0]
        if enter.size == 0:
            return tab, basis, it, OPTIMAL
        if it >= maxiter:
            return tab, basis, it, ITERATION_LIMIT
        if degen >= bland_after:
            j = enter[0]                  # Bland: smallest eligible index
        else:
            j = enter[np.argmin(rc[enter])]
        col = tab[:R, j]
        rhs = tab[:R, -1]
        ratio = np.full(R, np.inf)
        pos = col > tol
        ratio[pos] = rhs[pos] / col[pos]
        art_basic = (basis >= art_start) & (np.abs(col) > tol) & (rhs <= tol)
        ratio[art_basic] = 0.0
        if not np.any(ratio < np.inf):
            return tab, basis, it, UNBOUNDED
        rmin = ratio.min()
        tie = ratio <= rmin + max(abs(rmin) * 1e-9, 1e-12)
        cand = np.where(tie)[0]
        r = cand[np.argmin(basis[cand])]
        piv = tab[r, j]
        tab[r] = tab[r] / piv
        for k in range(tab.shape[0]):
            if k != r and abs(tab[k, j]) > 0:
                tab[k] -= tab[k, j] * tab[r]
        basis[r] = j
        degen = degen + 1 if rmin <= tol else 0
        it += 1


def _solve_np(A, b, c_full, nv, n_slack, maxiter, tol,
              bland_after=BLAND_AFTER):
    R, C0 = A.shape
    C = C0 + R
    tab = np.zeros((R + 1, C + 1))
    tab[:R, :C0] = A
    tab[:R, C0:C] = np.eye(R)
    tab[:R, -1] = b
    tab[-1, :] = -tab[:R, :].sum(axis=0)
    tab[-1, C0:C] = 0.0
    basis = np.arange(C0, C, dtype=np.int64)

    tab, basis, it1, st1 = _phase_np(tab, basis, C0, maxiter, tol,
                                     bland_after)
    infeasible = tab[-1, -1] < -max(tol, 1e-8) * (1.0 + np.abs(b).sum())

    obj = np.zeros(C + 1)
    obj[:C0] = c_full
    obj = obj - obj[basis] @ tab[:R, :]
    tab[-1, :] = obj
    tab, basis, it2, st2 = _phase_np(tab, basis, C0, maxiter, tol,
                                     bland_after, it0=it1)

    x = np.zeros(C)
    x[basis] = tab[:R, -1]
    fun = -tab[-1, -1]
    # mirror the jax path: an unconverged phase 1 invalidates both the
    # infeasibility certificate and the phase-2 result
    if st1 != OPTIMAL:
        status = st1
    else:
        status = INFEASIBLE if infeasible else st2
    return x[:nv], fun, status, it2, basis


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------
def solve_lp(c, A_ub=None, b_ub=None, A_eq=None, b_eq=None, *,
             backend: str = "numpy", maxiter: Optional[int] = None,
             tol: float = 1e-7, warm_basis: Optional[np.ndarray] = None,
             bland_after: int = BLAND_AFTER) -> LPResult:
    """Minimize c@x s.t. A_ub x <= b_ub, A_eq x == b_eq, x >= 0.

    ``warm_basis`` (a previous `LPResult.basis` for a structurally
    identical instance) starts the solve from that basis, skipping phase 1
    when it is still feasible; a rejected basis falls back to the cold
    two-phase solve (``LPResult.warm`` reports which path ran)."""
    A, b, c_full, nv, n_slack = _canonicalize(c, A_ub, b_ub, A_eq, b_eq)
    if warm_basis is not None \
            and np.asarray(warm_basis).shape != (A.shape[0],):
        raise ValueError(
            f"warm_basis must be ({A.shape[0]},) — one basic column per "
            f"constraint row; got {np.asarray(warm_basis).shape}")
    if maxiter is None:
        maxiter = 50 * (A.shape[0] + 2)
        if backend == "jax":          # static argname: bucket the trace key
            maxiter = _bucket_maxiter(maxiter)
    if backend == "jax":
        if not jax.config.jax_enable_x64:
            tol = max(tol, 1e-5)
        if warm_basis is not None:       # shape validated above
            wb = np.asarray(warm_basis, np.int64)
            dtype = jnp.float64 if jax.config.jax_enable_x64 \
                else jnp.float32
            xw, funw, stw, itw, basw, okw = jax.tree_util.tree_map(
                np.asarray,
                _warm_batch_jit(jnp.asarray(A[None], dtype),
                                jnp.asarray(b[None], dtype),
                                jnp.asarray(c_full[None], dtype),
                                jnp.asarray(wb[None]),
                                nv=nv, maxiter=maxiter, tol=tol,
                                bland_after=bland_after))
            if bool(okw[0]):
                return LPResult(x=np.asarray(xw[0], np.float64),
                                fun=float(funw[0]), status=int(stw[0]),
                                niter=int(itw[0]),
                                basis=np.asarray(basw[0], np.int64),
                                warm=True)
        x, fun, status, niter, basis = jax.tree_util.tree_map(
            np.asarray,
            _solve_jax(A, b, c_full, nv, n_slack, maxiter, tol,
                       bland_after))
        return LPResult(x=np.asarray(x, np.float64), fun=float(fun),
                        status=int(status), niter=int(niter),
                        basis=np.asarray(basis))
    elif backend == "numpy":
        if warm_basis is not None:
            got = _warm_np(A, b, c_full, nv, warm_basis, maxiter, tol,
                           bland_after)
            if got is not None:
                x, fun, status, niter, basis = got
                return LPResult(x=x, fun=float(fun), status=int(status),
                                niter=int(niter), basis=basis, warm=True)
        x, fun, status, niter, basis = _solve_np(A, b, c_full, nv, n_slack,
                                                 maxiter, tol, bland_after)
        return LPResult(x=x, fun=float(fun), status=int(status),
                        niter=int(niter), basis=basis)
    raise ValueError(f"unknown backend {backend!r}")


def _canonicalize_batch(c, A_ub, b_ub, A_eq, b_eq):
    """Batched `_canonicalize`: every input carries a leading batch axis and
    all batch elements share constraint structure (shapes)."""
    c = np.asarray(c, dtype=np.float64)
    B, nv = c.shape
    rows = []
    rhs = []
    n_ub = 0
    if A_ub is not None:
        A_ub = np.asarray(A_ub, dtype=np.float64)
        b_ub = np.asarray(b_ub, dtype=np.float64)
        n_ub = A_ub.shape[1]
        eye = np.broadcast_to(np.eye(n_ub), (B, n_ub, n_ub))
        rows.append(np.concatenate([A_ub, eye], axis=2))
        rhs.append(b_ub)
    if A_eq is not None:
        A_eq = np.asarray(A_eq, dtype=np.float64)
        b_eq = np.asarray(b_eq, dtype=np.float64)
        pad = np.zeros((B, A_eq.shape[1], n_ub))
        rows.append(np.concatenate([A_eq, pad], axis=2))
        rhs.append(b_eq)
    A = np.concatenate(rows, axis=1)
    b = np.concatenate(rhs, axis=1)
    neg = b < 0
    A = np.where(neg[:, :, None], -A, A)
    b = np.where(neg, -b, b)
    c_full = np.concatenate([c, np.zeros((B, n_ub))], axis=1)
    return A, b, c_full, nv, n_ub


def solve_lp_batch(c, A_ub=None, b_ub=None, A_eq=None, b_eq=None, *,
                   maxiter: Optional[int] = None, tol: float = 1e-7,
                   warm_basis: Optional[np.ndarray] = None,
                   impl: str = "jnp", bland_after: int = BLAND_AFTER,
                   method: str = "tableau") -> BatchLPResult:
    """Solve B structurally-identical LPs in one jitted `vmap` of the simplex.

    Inputs mirror `solve_lp` with a leading batch axis on every array.  Runs
    in float64 (via a local `enable_x64` scope) regardless of the global jax
    precision mode so the batched path stays bit-comparable with the NumPy
    oracle; the schedulable fleet sizes here make the 2x memory irrelevant.

    ``warm_basis`` (B, R) starts every lane from that basis via the
    revised-simplex warm path; rejected lanes (stale / singular / primal
    infeasible bases — pass -1 rows to force a cold solve) are re-solved by
    the two-phase path in one extra jitted call over the rejected subset.
    ``impl="pallas"`` runs the batched pivot through the
    `kernels/simplex_pivot` TPU kernels.

    ``method="revised"`` dispatches to the reduced-tableau revised simplex
    (`simplex_batch_core`'s revised path): warm and cold lanes resolve in
    ONE jitted call, only (R, R) factors are carried, and the bucketed
    default maxiter / float64 scope / result contract are identical.  The
    default ``"tableau"`` keeps the existing dispatch bit-for-bit.
    """
    if method not in ("tableau", "revised"):
        raise ValueError(f"unknown simplex method {method!r}; expected "
                         f"'tableau' or 'revised'")
    A, b, c_full, nv, _ = _canonicalize_batch(c, A_ub, b_ub, A_eq, b_eq)
    if maxiter is None:
        maxiter = _bucket_maxiter(50 * (A.shape[1] + 2))
    if method == "revised":
        basis0 = None
        if warm_basis is not None:
            wb = np.asarray(warm_basis, np.int64)
            if wb.shape != A.shape[:2]:
                raise ValueError(
                    f"warm_basis must be (B, R) = {A.shape[:2]}; "
                    f"got {wb.shape}")
            basis0 = jnp.asarray(wb)
        with x64_scope():
            x, fun, status, niter, basis, ok = jax.tree_util.tree_map(
                np.asarray,
                _revised_batch_jit(jnp.asarray(A, jnp.float64),
                                   jnp.asarray(b, jnp.float64),
                                   jnp.asarray(c_full, jnp.float64),
                                   basis0, nv=nv, maxiter=maxiter, tol=tol,
                                   bland_after=bland_after, impl=impl))
        return BatchLPResult(x=np.asarray(x, np.float64),
                             fun=np.asarray(fun, np.float64),
                             status=np.asarray(status, np.int64),
                             niter=np.asarray(niter, np.int64),
                             basis=np.asarray(basis, np.int64),
                             warm=np.asarray(ok, bool))
    with x64_scope():
        if warm_basis is not None:
            wb = np.asarray(warm_basis, np.int64)
            if wb.shape != A.shape[:2]:
                raise ValueError(
                    f"warm_basis must be (B, R) = {A.shape[:2]}; "
                    f"got {wb.shape}")
            x, fun, status, niter, basis, ok = jax.tree_util.tree_map(
                np.asarray,
                _warm_batch_jit(jnp.asarray(A, jnp.float64),
                                jnp.asarray(b, jnp.float64),
                                jnp.asarray(c_full, jnp.float64),
                                jnp.asarray(wb),
                                nv=nv, maxiter=maxiter, tol=tol,
                                bland_after=bland_after, impl=impl))
            x, fun = x.copy(), fun.copy()
            status, niter, basis = status.copy(), niter.copy(), basis.copy()
            cold = np.nonzero(~ok)[0]
            if len(cold):
                # pow2-pad the rejected subset (repeat the last row) so
                # fluctuating rejection counts reuse O(log B) traces
                sel = np.concatenate(
                    [cold, np.full(next_pow2(len(cold)) - len(cold),
                                   cold[-1], dtype=np.int64)])
                xc, func, stc, nitc, basc = jax.tree_util.tree_map(
                    np.asarray,
                    _solve_batch_jit(jnp.asarray(A[sel], jnp.float64),
                                     jnp.asarray(b[sel], jnp.float64),
                                     jnp.asarray(c_full[sel], jnp.float64),
                                     nv=nv, maxiter=maxiter, tol=tol,
                                     bland_after=bland_after))
                k = len(cold)
                x[cold], fun[cold] = xc[:k], func[:k]
                status[cold], niter[cold] = stc[:k], nitc[:k]
                basis[cold] = basc[:k]
            return BatchLPResult(x=np.asarray(x, np.float64),
                                 fun=np.asarray(fun, np.float64),
                                 status=np.asarray(status, np.int64),
                                 niter=np.asarray(niter, np.int64),
                                 basis=np.asarray(basis, np.int64),
                                 warm=np.asarray(ok, bool))
        x, fun, status, niter, basis = jax.tree_util.tree_map(
            np.asarray,
            _solve_batch_jit(jnp.asarray(A, jnp.float64),
                             jnp.asarray(b, jnp.float64),
                             jnp.asarray(c_full, jnp.float64),
                             nv=nv, maxiter=maxiter, tol=tol,
                             bland_after=bland_after))
    return BatchLPResult(x=np.asarray(x, np.float64),
                         fun=np.asarray(fun, np.float64),
                         status=np.asarray(status, np.int64),
                         niter=np.asarray(niter, np.int64),
                         basis=np.asarray(basis),
                         warm=np.zeros(len(x), dtype=bool))
