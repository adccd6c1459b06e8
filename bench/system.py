"""The system under test, driven through its public entry points.

`build` hands a generated `fleetgen.Fleet` to the program: devices and a
request queue through `EngineParams.from_fleet`, then the benchmark's own
replayed arrival trace through `dataclasses.replace` on the ``counts`` /
``stream`` leaves.  Parameters and state are put on the device once, in
float64, before anything is timed.

`ENTRIES` maps a traffic mix's ``entry`` to a callable ``(state) -> (state,
metrics)`` that plans ``periods_per_call`` periods; the harness times the
call and then fetches the metrics to the host.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def build(fleet, config: dict, traffic: dict):
    """Return ``(params, state)`` on the device."""
    import jax

    from repro.api import engine as E
    from repro.core.types import x64_scope
    from repro.serving import DeviceSpec, RequestQueue
    from repro.serving.profile import TierProfile

    classes = [int(c) for c in fleet.classes]
    devices = [
        DeviceSpec(profile=TierProfile(name="bench", p_ed=fleet.p_ed[d],
                                       p_es=fleet.p_es[d],
                                       acc=fleet.acc[d], classes=classes),
                   drift=fleet.drift[d], outage=fleet.outage[d])
        for d in range(fleet.n_devices)]
    queue = RequestQueue(fleet.n_devices, classes,
                         rate=float(traffic["rate"]),
                         batch_max=int(config["batch_max"]),
                         class_probs=traffic.get("class_probs"))
    params = E.EngineParams.from_fleet(
        devices, queue, T=float(config["T"]),
        n_servers=int(config["n_servers"]), policy=config["policy"],
        horizon=fleet.drift.shape[1], arrivals="poisson",
        straggler_threshold=float(config["straggler_threshold"]),
        ema=float(config["ema"]), lp_method=config["lp_method"])
    params = dataclasses.replace(params, arrivals="replay",
                                 counts=fleet.counts, stream=fleet.stream)
    state = E.init_state(params)
    with x64_scope():
        params, state = jax.device_put((params, state))
    return params, state


def _step(params, periods):
    from repro.api import engine as E
    return lambda state: E.step(state, params)


def _rollout(params, periods):
    from repro.api import engine as E
    return lambda state: E.rollout(state, params, periods, donate=True)


ENTRIES = {"step": _step, "rollout": _rollout}


def metrics_to_host(metrics) -> dict:
    """`PeriodMetrics` (scalars or stacked) as host arrays, one row per
    period."""
    import jax
    host = jax.device_get(metrics)
    return {f.name: np.atleast_1d(np.asarray(getattr(host, f.name)))
            for f in dataclasses.fields(host)}
