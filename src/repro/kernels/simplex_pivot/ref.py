"""Pure-jnp references for the batched simplex pivot kernels.

Three ops live here, each the oracle its Pallas kernel is tested against
AND the default (``impl="jnp"``) implementation the fleet LP path uses —
there is ONE definition of each update, shared by `core.lp` and the kernel
tests:

  * `pivot_update_ref` — the dense rank-1 tableau update used by
    `core.lp._phase_batched` (the legacy full-tableau path).
  * `reduced_pivot_ref` — one FUSED revised-simplex iteration (BTRAN
    pricing + entering/leaving selection + product-form eta update of the
    basis-inverse factors) used by `core.lp._revised_phase`.  Only the
    (R, R) basis inverse and the basic solution are updated; entering
    columns are priced on demand from the original (R, C0) column data, so
    the C0-wide tableau is never materialized.
    `reduced_pivot_columns_ref` is its twin for an LP whose columns hold
    at most two nonzeros (the AMR² relaxation): it prices and runs FTRAN
    from that column form instead of contracting against ``A``, and
    shares the selection and eta update (`select_update_ref`).  It has no
    Pallas kernel.
  * `basis_columns_ref` / `kkt_vjp_ref` — the per-lane basis gather and
    the KKT adjoint solve behind the implicit-gradient simplex
    (`core.lp` ``differentiable=True``): at a converged basis the optimum
    is ``x_B = B^{-1} b``, so the whole VJP is two (R, R) triangular-ish
    solves per lane against the same basis factor the revised method
    carries — no differentiation through the pivot loops.
"""
from __future__ import annotations

import jax.numpy as jnp


def pivot_update_ref(tabs: jnp.ndarray, r: jnp.ndarray, j: jnp.ndarray,
                     mask: jnp.ndarray) -> jnp.ndarray:
    """One simplex pivot on every active lane of a tableau stack.

    tabs: (B, R+1, C+1) tableaus (last row = reduced costs | -obj, last col
    = rhs); r, j: (B,) pivot row/column per lane; mask: (B,) bool — lanes
    with mask False pass through unchanged (their r/j may be garbage).

    Row/column selection uses `take_along_axis`: on XLA:CPU the gather
    lowering measures ~2x faster per pivot than the one-hot einsum
    formulation the Pallas kernel uses (one-hot is the right shape for the
    TPU VPU, gathers for CPU).
    """
    colv = jnp.take_along_axis(tabs, j[:, None, None], axis=2)[..., 0]
    prow = jnp.take_along_axis(tabs, r[:, None, None], axis=1)[:, 0, :]
    piv = jnp.take_along_axis(colv, r[:, None], axis=1)[:, 0]
    piv = jnp.where(mask, piv, 1.0)         # masked lanes: avoid 0-divide
    prow = prow / piv[:, None]
    new = tabs - colv[:, :, None] * prow[:, None, :]
    is_r = jnp.arange(tabs.shape[1])[None, :] == r[:, None]
    new = jnp.where(is_r[:, :, None], prow[:, None, :], new)
    return jnp.where(mask[:, None, None], new, tabs)


def _multipliers_ref(c_phase, Binv, basis, art_cost):
    """BTRAN: the simplex multipliers ``y = c_B Binv`` (B, R), with virtual
    artificial labels (>= C0) priced at ``art_cost``."""
    C0 = c_phase.shape[1]
    cB = jnp.where(
        basis >= C0, jnp.asarray(art_cost, c_phase.dtype),
        jnp.take_along_axis(c_phase, jnp.clip(basis, 0, C0 - 1), axis=1))
    return jnp.einsum("br,brk->bk", cB, Binv)


def price_reduced_ref(A, c_phase, Binv, basis, art_cost):
    """Reduced costs out of the basis-inverse factor (one BTRAN + pricing).

    A: (B, R, C0) original columns; c_phase: (B, C0) phase costs; Binv:
    (B, R, R); basis: (B, R) labels — entries >= C0 are VIRTUAL artificials
    (no column exists; they price at ``art_cost``: 1 in phase 1, 0 in
    phase 2).  Returns rc (B, C0)."""
    y = _multipliers_ref(c_phase, Binv, basis, art_cost)
    return c_phase - jnp.einsum("bk,bkc->bc", y, A)


def price_columns_ref(cols, c_phase, Binv, basis, art_cost):
    """`price_reduced_ref` for an LP whose every column has at most two
    nonzeros, given in column form ``cols = (ra, rb, va, vb)``: column
    ``c`` holds ``va[:, c]`` in row ``ra[c]`` and ``vb[:, c]`` in row
    ``rb[c]`` and zeros elsewhere (``ra``, ``rb``: static (C0,) int
    arrays; ``va``, ``vb``: (B, C0)).  ``y·A`` is then two gathers of the
    multipliers, two products and one add per column — the terms a dense
    contraction would add for the other rows are exact zeros."""
    ra, rb, va, vb = cols
    y = _multipliers_ref(c_phase, Binv, basis, art_cost)
    return c_phase - (y[:, ra] * va + y[:, rb] * vb)


def ftran_columns_ref(cols, Binv, j):
    """FTRAN ``Binv A_j`` (B, R) of each lane's entering column ``j`` (B,)
    from the column form of `price_columns_ref`: one per-lane gather of
    the two ``Binv`` columns the entering column's rows select, and one
    axpy."""
    ra, rb, va, vb = cols
    rows = jnp.stack([jnp.asarray(ra)[j], jnp.asarray(rb)[j]], axis=1)
    g = jnp.take_along_axis(Binv, rows[:, None, :], axis=2)     # (B, R, 2)
    vaj = jnp.take_along_axis(va, j[:, None], axis=1)           # (B, 1)
    vbj = jnp.take_along_axis(vb, j[:, None], axis=1)
    return g[..., 0] * vaj + g[..., 1] * vbj


def select_update_ref(rc, ftran, Binv, xB, basis, use_bland, may_pivot,
                      lane_ok, *, tol: float):
    """Entering/leaving selection and the eta update of one revised-simplex
    iteration, given the reduced costs ``rc`` (B, C0) and ``ftran``, a
    function of the entering columns ``j`` (B,) that returns ``Binv A_j``
    (B, R).  The one body behind both pricers (`reduced_pivot_ref`,
    `reduced_pivot_columns_ref`); the arguments and the return value are
    theirs."""
    C0 = rc.shape[1]
    R = Binv.shape[1]
    dtype = Binv.dtype
    intmax = jnp.iinfo(jnp.int32).max

    enter = (rc < -tol) & lane_ok[:, None]
    has_enter = enter.any(axis=1)
    score = jnp.where(enter, rc, jnp.inf)
    j_dantzig = jnp.argmin(score, axis=1)
    j_bland = jnp.argmax(enter, axis=1)             # first eligible index
    j = jnp.where(use_bland, j_bland, j_dantzig).astype(jnp.int32)
    j = jnp.where(has_enter, j, 0)                  # safe gather index

    d = ftran(j)            # entering column in basis coordinates
    pos = d > tol
    ratio = jnp.where(pos, xB / jnp.where(pos, d, 1.0), jnp.inf)
    art_basic = (basis >= C0) & (jnp.abs(d) > tol) & (xB <= tol)
    ratio = jnp.where(art_basic, 0.0, ratio)
    unbounded = ~jnp.any(ratio < jnp.inf, axis=1)
    rmin = jnp.min(ratio, axis=1)
    tie = ratio <= (rmin + jnp.maximum(jnp.abs(rmin) * 1e-9,
                                       1e-12))[:, None]
    r = jnp.argmin(jnp.where(tie, basis, intmax), axis=1).astype(jnp.int32)

    do = may_pivot & has_enter & ~unbounded
    # product-form update of the augmented factor [Binv | xB]
    F = jnp.concatenate([Binv, xB[..., None]], axis=2)     # (B, R, R+1)
    prow = jnp.take_along_axis(F, r[:, None, None], axis=1)[:, 0, :]
    piv = jnp.take_along_axis(d, r[:, None], axis=1)[:, 0]
    piv = jnp.where(do, piv, jnp.ones((), dtype))          # no 0-divide
    prow = prow / piv[:, None]
    Fnew = F - d[:, :, None] * prow[:, None, :]
    is_r = jnp.arange(R)[None, :] == r[:, None]
    Fnew = jnp.where(is_r[:, :, None], prow[:, None, :], Fnew)
    F = jnp.where(do[:, None, None], Fnew, F)
    basis = jnp.where(do[:, None] & is_r, j[:, None], basis)
    return (F[:, :, :R], F[:, :, R], basis.astype(jnp.int32),
            has_enter, unbounded, rmin <= tol)


def reduced_pivot_ref(A, c_phase, Binv, xB, basis, use_bland, may_pivot,
                      lane_ok, *, art_cost: float, tol: float):
    """One fused revised-simplex iteration across the whole lane stack.

    Prices every column out of the current factor (`price_reduced_ref`),
    picks the entering column (Dantzig, or Bland's smallest index where
    ``use_bland``), runs the ratio test on the FTRAN-transformed entering
    column (with `core.lp`'s artificial drive-out rule and
    smallest-basis-index tie-break), and applies the product-form (eta)
    rank-1 update to ``[Binv | xB]`` — the revised-simplex replacement for
    the dense (R+1, C0+1) tableau pivot of `pivot_update_ref`.

    A: (B, R, C0); c_phase: (B, C0); Binv: (B, R, R); xB: (B, R) basic
    solution; basis: (B, R) labels (>= C0 virtual artificial);
    use_bland / may_pivot / lane_ok: (B,) bool — ``lane_ok`` False lanes
    never produce an entering column (the masked-lane contract), and the
    update is applied only where ``may_pivot & has_enter & ~unbounded``.

    Returns ``(Binv', xB', basis', has_enter, unbounded, degenerate)``
    with the three flags (B,) bool (``degenerate``: min ratio <= tol,
    meaningful only on lanes that pivoted).
    """
    def ftran(j):
        Aj = jnp.take_along_axis(A, j[:, None, None], axis=2)[..., 0]
        return jnp.einsum("brk,bk->br", Binv, Aj)

    rc = price_reduced_ref(A, c_phase, Binv, basis, art_cost)
    return select_update_ref(rc, ftran, Binv, xB, basis, use_bland,
                             may_pivot, lane_ok, tol=tol)


def reduced_pivot_columns_ref(cols, c_phase, Binv, xB, basis, use_bland,
                              may_pivot, lane_ok, *, art_cost: float,
                              tol: float):
    """`reduced_pivot_ref` priced and FTRAN-ed from the column form of
    `price_columns_ref` (``cols`` in place of ``A``): no contraction
    against the columns, the same selection and update."""
    rc = price_columns_ref(cols, c_phase, Binv, basis, art_cost)
    return select_update_ref(
        rc, lambda j: ftran_columns_ref(cols, Binv, j), Binv, xB, basis,
        use_bland, may_pivot, lane_ok, tol=tol)


def basis_columns_ref(A, basis):
    """Gather each lane's basis matrix out of the original column data.

    A: (B, R, C0); basis: (B, R) labels.  Labels >= C0 are VIRTUAL
    artificials (the `core.lp` convention: the column for label ``C0 + r``
    is the unit vector ``e_r``, never materialized) — they come back as
    unit columns here.  Returns ``(Bmat (B, R, R), real (B, R) bool)``
    with ``real`` marking non-artificial basis members.

    The sign a warm-repair flip gave an artificial's virtual column is
    deliberately dropped: the KKT adjoint zeroes artificial cotangent
    entries (`kkt_vjp_ref`), and flipping column ``j`` of ``Bmat`` only
    rescales the adjoint component that multiplies that zero.
    """
    B, R, C0 = A.shape
    real = basis < C0
    basJ = jnp.clip(basis, 0, C0 - 1)
    cols = jnp.take_along_axis(A, basJ[:, None, :], axis=2)     # (B, R, R)
    art_row = jnp.clip(basis - C0, 0, R - 1)
    unit = (jnp.arange(R)[None, :, None]
            == art_row[:, None, :]).astype(A.dtype)             # e_{b-C0}
    return jnp.where(real[:, None, :], cols, unit), real


def kkt_vjp_ref(A, b, c_full, basis, gx, gfun, valid, *, nv: int):
    """The implicit-function VJP of a converged simplex optimum.

    At an optimal basis ``B`` the active-set system is ``B x_B = b`` with
    every nonbasic variable pinned at 0, so (away from degenerate bases,
    where any subgradient is returned) the solution map is locally
    ``x_B = B^{-1} b`` and ``fun = c_B^T x_B``.  Given output cotangents
    ``gx`` (B, nv) and ``gfun`` (B,), one adjoint solve per lane yields
    every input cotangent:

        g_B   = gather(gx)[basis] + gfun * c_B          (artificials: 0)
        y     = B^{-T} g_B                              (KKT adjoint)
        b-bar = y
        A-bar = -y (x_B scattered to basic columns)^T   (rank-1 per lane)
        c-bar = gfun * x_B scattered to basic columns

    ``valid`` (B,) bool gates lanes whose basis is meaningful (status
    OPTIMAL, lane unmasked): invalid lanes get an identity factor BEFORE
    the solve — gating after it would leak ``NaN * 0`` from singular
    garbage factors — and exactly-zero cotangents.

    A: (B, R, C0); b: (B, R); c_full: (B, C0); basis: (B, R) labels
    (>= C0 virtual); gx: (B, nv); gfun: (B,).  Returns ``(A_bar, b_bar,
    c_bar)`` with the primal shapes.
    """
    B, R, C0 = A.shape
    dtype = A.dtype
    Bmat, real = basis_columns_ref(A, basis)
    eye = jnp.broadcast_to(jnp.eye(R, dtype=dtype), (B, R, R))
    Bsafe = jnp.where(valid[:, None, None], Bmat, eye)
    basJ = jnp.clip(basis, 0, C0 - 1)

    xB = jnp.linalg.solve(Bsafe, b[..., None])[..., 0]          # (B, R)
    gxp = jnp.concatenate(
        [gx, jnp.zeros((B, C0 - nv), dtype)], axis=1)           # slack: 0
    gB = jnp.take_along_axis(gxp, basJ, axis=1) \
        + gfun[:, None] * jnp.take_along_axis(c_full, basJ, axis=1)
    gB = jnp.where(real & valid[:, None], gB, 0.0)
    y = jnp.linalg.solve(jnp.swapaxes(Bsafe, 1, 2),
                         gB[..., None])[..., 0]                  # (B, R)

    w = jnp.where(real & valid[:, None], xB, 0.0)
    b_bar = jnp.where(valid[:, None], y, 0.0)
    lanes = jnp.arange(B)[:, None]
    wcol = jnp.zeros((B, C0), dtype).at[lanes, basJ].add(w)      # (B, C0)
    A_bar = -b_bar[:, :, None] * wcol[:, None, :]
    c_bar = jnp.zeros((B, C0), dtype).at[lanes, basJ].add(
        gfun[:, None] * w)
    return A_bar, b_bar, c_bar
