"""Plain reference for the fleet planner: the same period, device by
device semantics, written from the paper and the engine's documented
rules without importing the program.

One period of one device (arXiv 2112.11413, Algorithm 1 "AMR^2"):

1. release up to ``batch_max`` queued jobs, oldest first;
2. solve the LP relaxation: maximise the summed accuracy of assigning
   each job to one of the m local models or the ES, with the local
   models' seconds and the ES seconds each within the period T;
3. keep the integral part, round one fractional job to the most accurate
   tier that fits T, and two fractional jobs by enumerating their
   (m+1)^2 assignments under fresh budgets (Algorithm 2);

and then, across the fleet:

4. ES-pool admission: devices that offload, in ascending order of ES
   demand (device id on ties), each to the least-loaded server (lowest
   index on ties) if it fits in T;
5. a device that was refused is re-planned with the ES disabled;
6. price each device's period at its true speed (``drift``), count the
   devices whose wall time passes T, and run the straggler audit: a
   device whose local models ran slower than believed by more than
   ``straggler_threshold`` has its believed latencies EMA-rescaled.

The LP is solved by a textbook dense two-phase simplex (Dantzig's rule,
Bland's rule after a run of degenerate pivots) over a batch of devices,
in whatever floating dtype the caller asks for: float64 is the reference,
float32 the control that a sound comparison must tell apart from it.
"""
from __future__ import annotations

import heapq

import numpy as np

# the ES column of a job whose device has no ES link this period, and of
# every job when a refused device is re-planned (seconds)
ES_DISABLED = 1e9
FRAC_TOL = 1e-4
FIT_SLACK = 1e-12
AUDIT_RTOL = 1e-9
# demands this close, relative, are equal but for rounding
TIE_RTOL = 1e-9
DEGENERATE_BEFORE_BLAND = 50
MAX_PIVOTS = 2000
# lanes per block of the batched LP when it runs on several threads
BLOCK = 1024


class ReferenceFailure(RuntimeError):
    """The reference itself failed (a pivot cap, a lost basis)."""


# --------------------------------------------------------------------------
# LP relaxation: batched dense simplex
# --------------------------------------------------------------------------
def _optimize(tab, basis, allowed, tol):
    """Pivot every lane of ``tab`` (L, R+1, C+1) to optimality of its
    objective row (the last row: reduced costs of a minimisation, the
    negated objective in the last column), in place.  ``allowed`` (C,)
    marks the columns that may enter; ``basis`` (L, R) labels each row's
    basic column.  Dantzig's rule, Bland's rule after a run of degenerate
    pivots; the leaving row is the smallest basic label among min-ratio
    ties."""
    L, R1, _ = tab.shape
    R = R1 - 1
    lanes = np.arange(L)
    W, Bs = tab, basis
    stall = np.zeros(L, np.int64)
    last = W[:, R, -1].copy()
    big = np.iinfo(np.int64).max
    for _ in range(MAX_PIVOTS):
        red = np.where(allowed[None, :], W[:, R, :-1], np.inf)
        neg = red < -tol
        going = neg.any(axis=1)
        if not going.all():
            done = ~going
            if W is not tab:
                tab[lanes[done]] = W[done]
                basis[lanes[done]] = Bs[done]
            if not going.any():
                return
            if done.sum() * 4 >= len(lanes) or W is tab:
                W, Bs = W[going], Bs[going]
                lanes, stall, last = lanes[going], stall[going], last[going]
                red, neg = red[going], neg[going]
                going = np.ones(len(lanes), bool)
        k = np.arange(len(lanes))
        enter = np.where(stall >= DEGENERATE_BEFORE_BLAND,
                         np.argmax(neg, axis=1), np.argmin(red, axis=1))
        col = W[k, :, enter]                                # (L, R+1)
        a = col[:, :R]
        ok = a > tol
        if not ok[going].any(axis=1).all():
            raise ReferenceFailure("unbounded LP: the fleet LP is bounded")
        rhs = W[:, :R, -1]
        ratio = np.where(ok, rhs / np.where(ok, a, 1.0), np.inf)
        best = ratio.min(axis=1, keepdims=True)
        tie = ok & (ratio <= best + tol * np.maximum(1.0, np.abs(best)))
        leave = np.argmin(np.where(tie, Bs, big), axis=1)
        prow = W[k, leave, :] / col[k, leave][:, None]
        col = np.where(going[:, None], col, 0.0)
        col[k, leave] = 0.0
        W -= col[:, :, None] * prow[:, None, :]
        g = np.nonzero(going)[0]
        W[g, leave[g], :] = prow[g]
        Bs[g, leave[g]] = enter[g]
        obj = W[:, R, -1]
        stall = np.where(np.abs(obj - last) <= tol, stall + 1, 0)
        last = obj.copy()
    raise ReferenceFailure(f"simplex did not converge in {MAX_PIVOTS} pivots")


def _tableau(p_ed, p_es, T, dtype, artificials):
    """Standard-form tableau of every lane: rows local seconds <= T, ES
    seconds <= T, one assignment row per job; columns x[j, i] (j-major,
    i = m the ES), the two slacks, optionally one artificial per
    assignment row, then the right-hand side."""
    L, n, m = p_ed.shape
    mp1 = m + 1
    nv = n * mp1
    R = n + 2
    C = nv + 2 + (n if artificials else 0)
    tab = np.zeros((L, R + 1, C + 1), dtype)
    tab[:, 0, :nv].reshape(L, n, mp1)[:, :, :m] = p_ed
    tab[:, 1, :nv].reshape(L, n, mp1)[:, :, m] = p_es
    tab[:, 0, nv] = 1.0
    tab[:, 1, nv + 1] = 1.0
    for j in range(n):
        tab[:, 2 + j, j * mp1:(j + 1) * mp1] = 1.0
        if artificials:
            tab[:, 2 + j, nv + 2 + j] = 1.0
    tab[:, 0, -1] = T
    tab[:, 1, -1] = T
    tab[:, 2:R, -1] = 1.0
    return tab


def _price(tab, basis, cost):
    """Objective row for ``cost`` (L, C) given the basis."""
    R = basis.shape[1]
    cb = np.take_along_axis(cost, basis, axis=1)
    tab[:, R, :-1] = cost - np.einsum("lr,lrc->lc", cb, tab[:, :R, :-1])
    tab[:, R, -1] = -np.einsum("lr,lr->l", cb, tab[:, :R, -1])


def _two_phase(p_ed, p_es, acc, T, dtype, tol):
    """Cold two-phase simplex: phase 1 from the slacks and one artificial
    per assignment row.  Returns ``(x (L, nv), feasible (L,))``."""
    L, n, m = p_ed.shape
    nv = n * (m + 1)
    R = n + 2
    tab = _tableau(p_ed, p_es, T, dtype, artificials=True)
    C = tab.shape[2] - 1
    basis = np.empty((L, R), np.int64)
    basis[:, 0] = nv
    basis[:, 1] = nv + 1
    basis[:, 2:] = nv + 2 + np.arange(n)
    cost = np.zeros((L, C), dtype)
    cost[:, nv + 2:] = 1.0
    _price(tab, basis, cost)
    _optimize(tab, basis, np.ones(C, bool), tol)
    feasible = tab[:, R, -1] >= -tol * n
    structural = np.arange(C) < nv + 2
    for _ in range(n):          # drive zero-level artificials out
        art = (basis >= nv + 2) & feasible[:, None]
        lanes = np.nonzero(art.any(axis=1))[0]
        if len(lanes) == 0:
            break
        rows = np.argmax(art[lanes], axis=1)
        entries = np.abs(tab[lanes, rows, :C]) * structural[None, :]
        cols = np.argmax(entries > tol, axis=1)
        if not (entries[np.arange(len(lanes)), cols] > tol).all():
            raise ReferenceFailure("an assignment row lost its variables")
        sub, bsub = tab[lanes], basis[lanes]
        k = np.arange(len(lanes))
        prow = sub[k, rows, :] / sub[k, rows, cols][:, None]
        colv = sub[k, :, cols]
        colv[k, rows] = 0.0
        sub -= colv[:, :, None] * prow[:, None, :]
        sub[k, rows, :] = prow
        bsub[k, rows] = cols
        tab[lanes], basis[lanes] = sub, bsub
    cost = np.zeros((L, C), dtype)
    cost[:, :nv] = -np.tile(acc, (1, n))
    _price(tab, basis, cost)
    _optimize(tab, basis, structural, tol)
    x = np.zeros((L, C), dtype)
    np.put_along_axis(x, basis, tab[:, :R, -1], axis=1)
    return x[:, :nv], feasible


def solve_lp(p_ed, p_es, acc, T, dtype):
    """LP relaxation of every lane: ``p_ed`` (L, n, m) and ``p_es`` (L, n)
    job seconds, ``acc`` (L, m+1), ``T`` scalar.  Returns ``(xbar (L, n,
    m+1), feasible (L,))``.

    Phase 2 starts from the basis that runs every job on its fastest
    local model, where that fits T; other lanes take the cold two-phase
    path."""
    L, n, m = p_ed.shape
    mp1 = m + 1
    nv = n * mp1
    R = n + 2
    tol = float(np.finfo(dtype).eps) ** 0.6 * 10.0
    fast = np.argmin(p_ed, axis=2)                        # (L, n)
    fast_t = np.take_along_axis(p_ed, fast[..., None], 2)[..., 0]
    crash = T - fast_t.sum(axis=1) >= 0
    xbar = np.zeros((L, nv), dtype)
    feasible = np.ones(L, bool)
    if (~crash).any():
        c = np.nonzero(~crash)[0]
        xbar[c], feasible[c] = _two_phase(p_ed[c], p_es[c], acc[c], T,
                                          dtype, tol)
    lanes = np.nonzero(crash)[0]
    if len(lanes):
        pe, fa, fi = p_ed[lanes], acc[lanes], fast[lanes]
        Lc = len(lanes)
        tab = _tableau(pe, p_es[lanes], T, dtype, artificials=False)
        basis = np.empty((Lc, R), np.int64)
        basis[:, 0] = nv
        basis[:, 1] = nv + 1
        basis[:, 2:] = np.arange(n)[None, :] * mp1 + fi
        # eliminate the basic local columns from the local-seconds row
        tab[:, 0, :] -= np.einsum("lj,ljc->lc", fast_t[lanes],
                                  tab[:, 2:R, :])
        C = tab.shape[2] - 1
        cost = np.zeros((Lc, C), dtype)
        cost[:, :nv] = -np.tile(fa, (1, n))
        _price(tab, basis, cost)
        _optimize(tab, basis, np.ones(C, bool), tol)
        x = np.zeros((Lc, C), dtype)
        np.put_along_axis(x, basis, tab[:, :R, -1], axis=1)
        xbar[lanes] = x[:, :nv]
    return xbar.reshape(L, n, mp1), feasible


# --------------------------------------------------------------------------
# AMR^2 rounding (Algorithm 1 lines 3-5, Algorithm 2)
# --------------------------------------------------------------------------
def round_lp(p_ed, p_es, acc, T, xbar, feasible):
    """Integral assignment (L, n) from the relaxation: 0..m-1 local
    models, m the ES.  An infeasible lane runs every job on its fastest
    local model."""
    L, n, mp1 = xbar.shape
    m = mp1 - 1
    lanes = np.arange(L)
    assign = np.argmax(xbar, axis=2)
    assign = np.where(feasible[:, None], assign, np.argmin(p_ed, axis=2))
    frac = ((xbar > FRAC_TOL) & (xbar < 1.0 - FRAC_TOL)).any(axis=2)
    frac &= feasible[:, None]
    fc = frac.sum(axis=1)
    j1 = np.argmax(frac, axis=1)
    rest = frac.copy()
    rest[lanes, j1] = False
    j2 = np.argmax(rest, axis=1)
    many = fc > 2
    if many.any():
        # more fractional jobs than a vertex has: the two most fractional
        fracness = np.where(frac, 1.0 - xbar.max(axis=2), -np.inf)
        top = np.argsort(-fracness, axis=1, kind="stable")[:, :2]
        j1 = np.where(many, top.min(axis=1), j1)
        j2 = np.where(many, top.max(axis=1), j2)
    dt = xbar.dtype
    e1, e2 = p_ed[lanes, j1], p_ed[lanes, j2]           # (L, m)
    s1, s2 = p_es[lanes, j1], p_es[lanes, j2]           # (L,)
    # one fractional job: the most accurate tier on which it fits T
    fit1 = np.concatenate([e1 <= T, (s1 <= T)[:, None]], axis=1)
    pick1 = np.argmax(np.where(fit1, acc, -np.inf), axis=1)
    pick1 = np.where(fit1.any(axis=1), pick1, np.argmin(e1, axis=1))
    one = fc == 1
    assign[one, j1[one]] = pick1[one]
    # two fractional jobs: best feasible pair under fresh budgets
    zed = np.zeros((L, 1), dt)
    ed1 = np.concatenate([e1, zed], axis=1)
    ed2 = np.concatenate([e2, zed], axis=1)
    es1 = np.concatenate([np.zeros((L, m), dt), s1[:, None]], axis=1)
    es2 = np.concatenate([np.zeros((L, m), dt), s2[:, None]], axis=1)
    fit2 = (((ed1[:, :, None] + ed2[:, None, :]) <= T + FIT_SLACK)
            & ((es1[:, :, None] + es2[:, None, :]) <= T + FIT_SLACK))
    val = np.where(fit2, acc[:, :, None] + acc[:, None, :], -np.inf)
    flat = np.argmax(val.reshape(L, -1), axis=1)
    i1, i2 = flat // mp1, flat % mp1
    none2 = ~fit2.reshape(L, -1).any(axis=1)
    i1 = np.where(none2, np.argmin(e1, axis=1), i1)
    i2 = np.where(none2, np.argmin(e2, axis=1), i2)
    two = fc >= 2
    assign[two, j1[two]] = i1[two]
    assign[two, j2[two]] = i2[two]
    return assign


def _plan_block(p_ed, p_es, acc, T, dtype):
    xbar, feasible = solve_lp(p_ed, p_es, acc, T, dtype)
    return round_lp(p_ed, p_es, acc, T, xbar, feasible)


def plan(p_ed, p_es, acc, T, dtype, pool=None):
    """LP relaxation + rounding for a batch of devices, in blocks of
    ``BLOCK`` lanes over ``pool`` (a `concurrent.futures` executor) when
    one is given: NumPy leaves the interpreter lock in its loops, so the
    blocks run side by side."""
    L = p_ed.shape[0]
    if L == 0:
        return np.zeros(p_es.shape, np.int64)
    if pool is None or L <= BLOCK:
        return _plan_block(p_ed, p_es, acc, T, dtype)
    cuts = range(0, L, BLOCK)
    futs = [pool.submit(_plan_block, p_ed[i:i + BLOCK], p_es[i:i + BLOCK],
                        acc[i:i + BLOCK], T, dtype) for i in cuts]
    return np.concatenate([f.result() for f in futs])


# --------------------------------------------------------------------------
# ES-pool admission
# --------------------------------------------------------------------------
def admit(demand, T, n_servers):
    """First fit in ascending demand (device id on ties) onto the least
    loaded server (lowest index on ties).  Returns ``(admitted (D,),
    loads (n_servers,), order, cut)``: the devices in the order they were
    offered, and the position in it of the first refusal (len(order) if
    none).  A refusal ends admission: every later demand is at least as
    large and the loads no longer change."""
    active = np.nonzero(demand > 0)[0]
    order = active[np.argsort(demand[active], kind="stable")]
    admitted = np.zeros(demand.shape[0], bool)
    heap = [(demand.dtype.type(0.0), s) for s in range(n_servers)]
    cut = len(order)
    for pos, d in enumerate(order):
        need = demand[d]
        load, s = heap[0]
        if load + need <= T + FIT_SLACK:
            heapq.heapreplace(heap, (load + need, s))
            admitted[d] = True
        else:
            cut = pos
            break
    loads = np.zeros(n_servers, demand.dtype)
    for load, s in heap:
        loads[s] = load
    return admitted, loads, order, cut


def split_tie(demand, order, cut):
    """Devices whose demands equal, to rounding (``TIE_RTOL``), the demand
    of the first refused device, when some of them were admitted: the
    program sums each device's demand in its own order, so which of them
    it admits is decided by rounding, and any choice of as many is a
    correct plan."""
    if cut == 0 or cut >= len(order):
        return np.zeros(0, np.int64)
    v = demand[order]
    lo = cut
    while lo > 0 and v[cut] - v[lo - 1] <= TIE_RTOL * v[cut]:
        lo -= 1
    if lo == cut:
        return np.zeros(0, np.int64)
    hi = cut + 1
    while hi < len(v) and v[hi] - v[cut] <= TIE_RTOL * v[cut]:
        hi += 1
    return order[lo:hi]


# --------------------------------------------------------------------------
# The fleet, period by period
# --------------------------------------------------------------------------
METRICS = ("n_jobs", "total_accuracy", "n_violations", "worst_violation",
           "n_offloading", "n_backpressured", "n_outage",
           "n_straggler_updates", "es_utilization", "realized_makespan",
           "backlog")


class FleetReference:
    """The deployment's state and one `period()` at a time.

    ``fleet`` is the generator's `Fleet`; ``config`` the configuration's
    parameters; ``dtype`` the precision of every float computed; ``pool``
    an optional executor for the LP blocks.

    Each period returns, for every metric, ``(value, lo, hi)``: the value
    of this reference's own plan and the range of values that a correct
    plan can give.  The range is wider than the value only where devices
    tie, to rounding, at the ES pool's admission cut (`split_tie`).  A
    device on either side of such a cut may then hold another believed
    latency table than this reference does; those alternatives are kept
    (`alts`) until the audit makes them agree, and a plan that would
    depend on them stops the reference."""

    def __init__(self, fleet, config, dtype=np.float64, pool=None):
        self.dtype = np.dtype(dtype)
        f = self.dtype.type
        self.f = fleet
        self.pool = pool
        self.T = f(config["T"])
        self.n = int(config["batch_max"])
        self.n_servers = int(config["n_servers"])
        self.bar = f(float(config["straggler_threshold"]) * (1 + AUDIT_RTOL))
        self.ema = f(config["ema"])
        self.base = fleet.p_ed.astype(self.dtype)
        self.p_es = fleet.p_es.astype(self.dtype)
        self.acc = fleet.acc.astype(self.dtype)
        self.drift = fleet.drift.astype(self.dtype)
        self.belief = self.base.copy()
        D = fleet.n_devices
        self.pending = np.zeros(D, np.int64)
        self.head = np.zeros(D, np.int64)
        self.alts = {}          # device -> alternative belief tables
        self.t = 0

    def _plan(self, p_ed, p_es, acc):
        return plan(p_ed, p_es, acc, self.T, self.dtype, self.pool)

    def _audit(self, ed_pred, ed_wall, belief):
        """Straggler audit: ``(updated, new belief)`` (vectorized over a
        leading device axis)."""
        dt = self.dtype
        ratio = ed_wall / np.maximum(ed_pred, dt.type(1e-9))
        upd = (ed_pred > 0) & (ratio > self.bar)
        factor = (1.0 - self.ema) + self.ema * ratio
        new = np.where(upd[..., None, None], belief * factor[..., None, None],
                       belief).astype(dt)
        return upd, new

    def _price(self, assign, mask, p_ed_jobs, base_jobs, drift_t, acc):
        """Per device: accuracy, local predicted and wall seconds."""
        dt = self.dtype
        m = self.base.shape[2]
        acc_jobs = np.take_along_axis(acc, assign, axis=1)
        on_ed = mask & (assign < m)
        pick = np.minimum(assign, m - 1)[..., None]
        ed_pred = np.where(on_ed, np.take_along_axis(p_ed_jobs, pick, 2)[..., 0],
                           0.0).astype(dt).sum(axis=-1)
        ed_wall = np.where(on_ed, np.take_along_axis(base_jobs, pick, 2)[..., 0],
                           0.0).astype(dt).sum(axis=-1) * drift_t
        acc_dev = np.where(mask, acc_jobs, 0.0).astype(dt).sum(axis=-1)
        return acc_dev, ed_pred, ed_wall

    def _release(self):
        """Release this period's jobs: ``(take (D,), class index (D, n))``,
        advancing each device's queue."""
        fl, n = self.f, self.n
        t = self.t
        if t >= fl.counts.shape[0]:
            raise ReferenceFailure(f"the arrival trace ends before period {t}")
        avail = self.pending + fl.counts[t]
        take = np.minimum(avail, n)
        idx = self.head[:, None] + np.arange(n)[None, :]
        ci = np.take_along_axis(fl.stream,
                                np.minimum(idx, fl.stream.shape[1] - 1),
                                axis=1)
        self.head = self.head + take
        self.pending = avail - take
        return take, ci

    def advance(self) -> bool:
        """Move one period on without planning it, where no plan can
        change the state it leaves: every device runs at its believed
        speed (no drift, beliefs as measured, none left open by a tie),
        so the audit cannot fire and only the queues move.  Returns
        False, and moves nothing, otherwise."""
        drift_t = self.drift[:, self.t % self.drift.shape[1]]
        if self.alts or not (drift_t == 1).all() or not np.array_equal(
                self.belief, self.base):
            return False
        self._release()
        self.t += 1
        return True

    def period(self) -> dict:
        fl, n, T, dt = self.f, self.n, self.T, self.dtype
        D = fl.n_devices
        m = self.base.shape[2]
        t = self.t
        Hs = fl.drift.shape[1]
        drift_t = self.drift[:, t % Hs]
        outage_t = fl.outage[:, t % Hs]
        take, ci = self._release()
        mask = np.arange(n)[None, :] < take[:, None]
        rows = np.arange(D)[:, None]
        p_ed_jobs = np.where(mask[..., None], self.belief[rows, ci], 0.0
                             ).astype(dt)
        base_jobs = np.where(mask[..., None], self.base[rows, ci], 0.0
                             ).astype(dt)
        es = np.where(mask, self.p_es[rows, ci], 0.0).astype(dt)
        p_es_jobs = np.where(outage_t[:, None] & mask, dt.type(ES_DISABLED),
                             es).astype(dt)
        crippled = np.where(mask, dt.type(ES_DISABLED), 0.0).astype(dt)

        primary = self._plan(p_ed_jobs, p_es_jobs, self.acc)
        demand = np.where(mask & (primary == m), p_es_jobs, 0.0
                          ).astype(dt).sum(axis=1)
        admitted, loads, order, cut = admit(demand, T, self.n_servers)
        offl = demand > 0
        bumped = offl & ~admitted
        tie = split_tie(demand, order, cut)
        replan = np.nonzero(bumped)[0]
        replan = np.union1d(replan, tie)
        backup = primary.copy()
        if len(replan):
            backup[replan] = self._plan(p_ed_jobs[replan], crippled[replan],
                                        self.acc[replan])
        assign = np.where(bumped[:, None], backup, primary)

        acc_dev, ed_pred, ed_wall = self._price(assign, mask, p_ed_jobs,
                                                base_jobs, drift_t, self.acc)
        es_wall = np.where(admitted, demand, 0.0).astype(dt)
        wall = np.maximum(ed_wall, es_wall)
        viol = np.maximum(0.0, wall / T - 1.0).astype(dt)
        upd, new_belief = self._audit(ed_pred, ed_wall, self.belief)

        out = {
            "n_jobs": int(take.sum()),
            "total_accuracy": float(acc_dev.sum()),
            "n_violations": int((viol > 0).sum()),
            "worst_violation": float(viol.max(initial=0.0)),
            "n_offloading": int(offl.sum()),
            "n_backpressured": int(bumped.sum()),
            "n_outage": int(outage_t.sum()),
            "n_straggler_updates": int(upd.sum()),
            "es_utilization": float(loads.sum() / (self.n_servers * T)),
            "realized_makespan": float(wall.max(initial=0.0)),
            "backlog": int(self.pending.sum()),
        }
        rng = {k: [v, v] for k, v in out.items()}

        # ---- devices whose admission or belief a correct plan may differ in
        per_dev = {"total_accuracy": acc_dev, "n_violations": viol > 0}
        if len(tie):
            def branch(admit_it):
                a = np.where(admit_it[:, None], primary[tie], backup[tie])
                ad, ep, ew = self._price(a, mask[tie], p_ed_jobs[tie],
                                         base_jobs[tie], drift_t[tie],
                                         self.acc[tie])
                w = np.maximum(ew, np.where(admit_it, demand[tie], 0.0))
                return ad, w, np.maximum(0.0, w / T - 1.0)
            yes = np.ones(len(tie), bool)
            acc_a, wall_a, viol_a = branch(yes)
            acc_b, wall_b, viol_b = branch(~yes)
            nb = int(bumped[tie].sum())
            for key, xa, xb in (("total_accuracy", acc_a, acc_b),
                                ("n_violations", viol_a > 0, viol_b > 0)):
                xa, xb = xa.astype(np.float64), xb.astype(np.float64)
                delta = np.sort(xb - xa)
                mine = per_dev[key][tie].astype(np.float64).sum()
                rest = float(out[key]) - mine
                rng[key] = [rest + xa.sum() + delta[:nb].sum(),
                            rest + xa.sum() + delta[len(delta) - nb:].sum()]
            others = np.ones(D, bool)
            others[tie] = False
            for key, xa, xb, whole in (
                    ("realized_makespan", wall_a, wall_b, wall),
                    ("worst_violation", viol_a, viol_b, viol)):
                rest = float(whole[others].max(initial=0.0))
                rng[key] = [max(rest, float(np.minimum(xa, xb).max())),
                            max(rest, float(np.maximum(xa, xb).max()))]
        tied = set(tie.tolist())
        unsure = set(self.alts) | tied
        lo_upd = hi_upd = int(upd.sum())
        new_alts = {}
        for d in sorted(unsure):
            choices = [True, False] if d in tied else [bool(admitted[d])]
            mine_upd = bool(upd[d])
            outcomes = []
            for bi, belief in enumerate([self.belief[d]]
                                        + self.alts.get(d, [])):
                pe = np.where(mask[d][:, None], belief[ci[d]], 0.0).astype(dt)
                for ok in choices:
                    want = primary[d] if ok else backup[d]
                    if bi:
                        got = self._plan(pe[None], (p_es_jobs if ok else
                                                    crippled)[d][None],
                                         self.acc[d][None])[0]
                        if not np.array_equal(got, want):
                            raise ReferenceFailure(
                                f"device {d}: its plan depends on a belief "
                                f"that an admission tie left open")
                    _, ep, ew = self._price(want[None], mask[d][None], pe[None],
                                            base_jobs[d][None],
                                            drift_t[d][None],
                                            self.acc[d][None])
                    u, nb_ = self._audit(ep, ew, belief[None])
                    outcomes.append((bool(u[0]), nb_[0]))
            lo_upd += min(u for u, _ in outcomes) - mine_upd
            hi_upd += max(u for u, _ in outcomes) - mine_upd
            keep = []
            for _, b in outcomes:
                if np.allclose(b, new_belief[d], rtol=1e-12, atol=0.0):
                    continue
                if not any(np.allclose(b, k, rtol=1e-12, atol=0.0)
                           for k in keep):
                    keep.append(b)
            if len(keep) > 4:
                raise ReferenceFailure(f"device {d}: too many open beliefs")
            if keep:
                new_alts[d] = keep
        rng["n_straggler_updates"] = [lo_upd, hi_upd]
        self.alts = new_alts
        self.belief = new_belief
        self.t += 1
        return {k: (out[k], float(rng[k][0]), float(rng[k][1]))
                for k in METRICS}


def run(fleet, config, periods, dtype=np.float64, pool=None, sample=None):
    """``(value, lo, hi)`` of every metric of periods ``0 .. periods-1``:
    a list of dicts.  With ``sample`` (a set of periods), only those are
    returned; the entry of any other period is None, and it is moved on
    without planning where `FleetReference.advance` can, else planned."""
    ref = FleetReference(fleet, config, dtype, pool)
    out = []
    for t in range(periods):
        if sample is not None and t not in sample:
            if not ref.advance():
                ref.period()
            out.append(None)
        else:
            out.append(ref.period())
    return out
