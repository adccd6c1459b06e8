"""jit'd wrappers exposing the kernels with `core.lp`'s batched pivot
signatures (so both simplex paths drop them in as ``impl="pallas"``,
mirroring how `cckp_dp` is wired into AMDP)."""
from __future__ import annotations

from .. import interpret_mode
from .simplex_pivot import reduced_pivot as _reduced_pivot
from .simplex_pivot import simplex_pivot


def pivot_update(tabs, r, j, mask):
    interpret = interpret_mode("simplex_pivot", tabs)
    return simplex_pivot(tabs, r, j, mask, interpret=interpret)


def reduced_pivot(A, c_phase, Binv, xB, basis, use_bland, may_pivot,
                  lane_ok, *, art_cost, tol):
    interpret = interpret_mode("reduced_pivot", A, c_phase, Binv, xB)
    return _reduced_pivot(A, c_phase, Binv, xB, basis, use_bland,
                          may_pivot, lane_ok, art_cost=float(art_cost),
                          tol=float(tol), interpret=interpret)
