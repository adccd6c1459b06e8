"""Batched simplex pivot kernels — TPU Pallas.

Two kernels, both gridded over the lane (device) axis with per-lane flags
as scalar-prefetch operands and every dynamic row/column selection done
with broadcasted-iota one-hot masks (no gathers, pure VPU work):

  * ``simplex_pivot`` — the dense rank-1 tableau update

        tab' = tab - tab[:, j] (x) (tab[r, :] / tab[r, j])

    that `core.lp._phase_batched` performs across B device tableaus per
    iteration; pivot coordinates (r, j) are chosen by the caller.

  * ``reduced_pivot`` — one FUSED revised-simplex iteration for
    `core.lp._revised_phase`: BTRAN pricing out of the (R, R) basis
    inverse, entering-column selection (Dantzig / Bland), the ratio test
    with the artificial drive-out rule, and the product-form (eta) rank-1
    update of ``[Binv | xB]`` — all in one kernel launch per iteration,
    with the original (R, C0) column data streamed per lane instead of a
    materialized C0-wide tableau.

Both mirror the jnp references in ``ref.py`` and, like `cckp_dp`, run in
interpret mode off-TPU; fleet factors are float64 on CPU (the LP parity
contract), so on a real TPU the caller must run the float32 LP mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lane_block(*tail):
    """BlockSpec of lane ``b``'s ``(1, *tail)`` slab of a ``(B, *tail)``
    array.  Every block spans its array's full last two dims (the TPU's
    tiling rule), and the index map returns int32 zeros: under x64 a
    Python ``0`` traces as int64, which Mosaic cannot legalize."""
    return pl.BlockSpec((1, *tail),
                        lambda b, *_: (b,) + (jnp.int32(0),) * len(tail))


def _any(x):
    """``jnp.any`` as a float32 max: Pallas lowers ``reduce_or`` through
    a max over Python-float 1.0/0.0, which is float64 under x64."""
    return jnp.max(jnp.where(x, np.float32(1), np.float32(0))) > 0


def _imin(x):
    """Min of a small non-negative int32 vector, reduced in float32 (exact
    below 2**24): Mosaic reduces floats natively, and its integer
    fallback recurses forever under x64."""
    return jnp.min(x.astype(jnp.float32)).astype(jnp.int32)


def _kernel(r_ref, j_ref, mask_ref, tab_ref, out_ref):
    b = pl.program_id(0)
    tab = tab_ref[0]                       # (R1, C1) lane block
    R1, C1 = tab.shape
    r = r_ref[b]
    j = j_ref[b]
    active = mask_ref[b] != 0
    rows = jax.lax.broadcasted_iota(jnp.int32, (R1, C1), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (R1, C1), 1)
    is_r = rows == r
    is_j = cols == j
    piv = jnp.sum(jnp.where(is_r & is_j, tab, 0.0))
    piv = jnp.where(active, piv, jnp.ones((), tab.dtype))
    prow = jnp.sum(jnp.where(is_r, tab, 0.0), axis=0) / piv    # (C1,)
    colv = jnp.sum(jnp.where(is_j, tab, 0.0), axis=1)          # (R1,)
    upd = tab - colv[:, None] * prow[None, :]
    upd = jnp.where(is_r, prow[None, :], upd)
    out_ref[0] = jnp.where(active, upd, tab)


@functools.partial(jax.jit, static_argnames=("interpret",))
def simplex_pivot(tabs: jnp.ndarray, r: jnp.ndarray, j: jnp.ndarray,
                  mask: jnp.ndarray, *, interpret: bool = False):
    """Pivot every active lane of a (B, R+1, C+1) tableau stack.

    r, j: (B,) int pivot coordinates; mask: (B,) bool/int lane-active flags
    (inactive lanes pass through, their r/j may be garbage).
    """
    B, R1, C1 = tabs.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[_lane_block(R1, C1)],
        out_specs=_lane_block(R1, C1),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, R1, C1), tabs.dtype),
        interpret=interpret,
    )(r.astype(jnp.int32), j.astype(jnp.int32), mask.astype(jnp.int32),
      tabs)


def _reduced_kernel(bland_ref, may_ref, ok_ref, A_ref, c_ref, binv_ref,
                    xb_ref, bas_ref, binv_out, xb_out, bas_out, flag_out,
                    *, art_cost: float, tol: float):
    """Every vector is 2-D with a fixed orientation — per-row quantities
    are (R, 1) columns, per-column ones (1, C0) rows — since Mosaic
    cannot relayout a 1-D vector between the two.  Where one is needed in
    the other orientation it is read off an identity mask (a sum with
    zeros, so exact)."""
    b = pl.program_id(0)
    A = A_ref[0]                           # (R, C0) original columns
    c = c_ref[0]                           # (1, C0) phase costs
    Binv = binv_ref[0]                     # (R, R) basis inverse
    xB = xb_ref[0]                         # (R, 1) basic solution
    bas = bas_ref[0]                       # (R, 1) labels (>= C0 virtual)
    dtype = A.dtype
    # constants typed as the operands: under x64 a Python float or int is
    # a 64-bit scalar, which Mosaic cannot hold
    R, C0 = (np.int32(n) for n in A.shape)
    zero, one = jnp.zeros((), dtype), jnp.ones((), dtype)
    tol = jnp.asarray(tol, dtype)
    use_bland = bland_ref[b] != 0
    may = may_ref[b] != 0
    ok = ok_ref[b] != 0
    inf = jnp.asarray(jnp.inf, dtype)
    cols = jax.lax.broadcasted_iota(jnp.int32, (R, C0), 1)
    cols1 = jax.lax.broadcasted_iota(jnp.int32, (1, C0), 1)
    rows1 = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (R, R), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (R, R), 1))

    # BTRAN + pricing: rc = c - (cB Binv) A
    cB = jnp.sum(jnp.where(cols == bas, c, zero), axis=1, keepdims=True)
    cB = jnp.where(bas >= C0, jnp.asarray(art_cost, dtype), cB)
    y = jnp.sum(cB * Binv, axis=0, keepdims=True)             # (1, R)
    y = jnp.sum(jnp.where(eye, y, zero), axis=1, keepdims=True)  # (R, 1)
    rc = c - jnp.sum(y * A, axis=0, keepdims=True)            # (1, C0)

    enter = (rc < -tol) & ok
    has_enter = _any(enter)
    score = jnp.where(enter, rc, inf)
    smin = jnp.min(score)
    j_dantzig = _imin(jnp.where(score == smin, cols1, C0))
    j_bland = _imin(jnp.where(enter, cols1, C0))
    j = jnp.where(use_bland, j_bland, j_dantzig)
    j = jnp.where(has_enter, j, np.int32(0))

    # FTRAN + ratio test (drive-out rule, smallest-basis-index tie-break)
    Aj = jnp.sum(jnp.where(cols == j, A, zero), axis=1, keepdims=True)
    Aj = jnp.sum(jnp.where(eye, Aj, zero), axis=0, keepdims=True)  # (1, R)
    d = jnp.sum(Binv * Aj, axis=1, keepdims=True)              # (R, 1)
    pos = d > tol
    ratio = jnp.where(pos, xB / jnp.where(pos, d, one), inf)
    art_basic = (bas >= C0) & (jnp.abs(d) > tol) & (xB <= tol)
    ratio = jnp.where(art_basic, zero, ratio)
    unbounded = ~_any(ratio < inf)
    rmin = jnp.min(ratio)
    tie = ratio <= rmin + jnp.maximum(jnp.abs(rmin) * jnp.asarray(1e-9, dtype),
                                      jnp.asarray(1e-12, dtype))
    bmin = _imin(jnp.where(tie, bas, C0 + R))    # labels are < C0 + R and
    r = _imin(jnp.where(tie & (bas == bmin), rows1, R))  # unique per lane

    do = may & has_enter & ~unbounded
    is_r = rows1 == r                                          # (R, 1)
    piv = jnp.sum(jnp.where(is_r, d, zero))
    piv = jnp.where(do, piv, one)
    brow = jnp.sum(jnp.where(is_r, Binv, zero), axis=0, keepdims=True) / piv
    xr = jnp.sum(jnp.where(is_r, xB, zero)) / piv
    Binv2 = Binv - d * brow
    Binv2 = jnp.where(is_r, brow, Binv2)
    xB2 = jnp.where(is_r, xr, xB - d * xr)
    binv_out[0] = jnp.where(do, Binv2, Binv)
    xb_out[0] = jnp.where(do, xB2, xB)
    bas_out[0] = jnp.where(do & is_r, j, bas)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, 3), 1)
    flag = lambda x: x.astype(jnp.int32)    # no i1 vectors in Mosaic
    flag_out[0] = jnp.where(slot == 0, flag(has_enter),
                            jnp.where(slot == 1, flag(unbounded),
                                      flag(rmin <= tol)))


@functools.partial(jax.jit,
                   static_argnames=("art_cost", "tol", "interpret"))
def reduced_pivot(A: jnp.ndarray, c_phase: jnp.ndarray, Binv: jnp.ndarray,
                  xB: jnp.ndarray, basis: jnp.ndarray,
                  use_bland: jnp.ndarray, may_pivot: jnp.ndarray,
                  lane_ok: jnp.ndarray, *, art_cost: float, tol: float,
                  interpret: bool = False):
    """One fused revised-simplex iteration on every lane of the stack.

    Signature and semantics match `ref.reduced_pivot_ref`: per lane, price
    all C0 columns out of the (R, R) basis inverse, select the pivot, and
    apply the eta update — lanes where ``may_pivot & has_enter &
    ~unbounded`` is False pass their factors through unchanged.  Returns
    ``(Binv', xB', basis', has_enter, unbounded, degenerate)``.
    """
    B, R, C0 = A.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[_lane_block(R, C0), _lane_block(1, C0),
                  _lane_block(R, R), _lane_block(R, 1), _lane_block(R, 1)],
        out_specs=[_lane_block(R, R), _lane_block(R, 1), _lane_block(R, 1),
                   _lane_block(1, 3)],
    )
    binv2, xb2, bas2, flags = pl.pallas_call(
        functools.partial(_reduced_kernel, art_cost=art_cost, tol=tol),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, R, R), Binv.dtype),
                   jax.ShapeDtypeStruct((B, R, 1), xB.dtype),
                   jax.ShapeDtypeStruct((B, R, 1), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, 3), jnp.int32)],
        interpret=interpret,
    )(use_bland.astype(jnp.int32), may_pivot.astype(jnp.int32),
      lane_ok.astype(jnp.int32), A, c_phase[:, None], Binv, xB[..., None],
      basis.astype(jnp.int32)[..., None])
    return (binv2, xb2[..., 0], bas2[..., 0], flags[:, 0, 0] != 0,
            flags[:, 0, 1] != 0, flags[:, 0, 2] != 0)
