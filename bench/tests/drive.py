"""Run `bench/run.py`'s main on the CPU, as the tests need it: the look
for a TPU is skipped, and optionally one fault is planted in the program
underneath the timed path.

    python drive.py [--fault NAME] -- <run.py arguments>

Faults (each makes the program's answers wrong in one way):

* ``stale_state``: every call returns the state it was given, so the
  fleet never advances a period;
* ``half_fleet``: the second half of the devices is released no jobs;
* ``altered_plan``: device 0's first job is moved to another tier where
  the rounding produces it.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    import system
    from repro.api import engine as E

    if fault == "stale_state":
        real = system.ENTRIES["step"]

        def stale(params, periods):
            call = real(params, periods)

            def f(state):
                _, m = call(state)
                return state, m
            return f
        system.ENTRIES["step"] = stale
    elif fault == "half_fleet":
        real_arrivals = E._arrivals

        def arrivals(state, params, axis_name=None):
            ci, take, pending, head, key = real_arrivals(state, params,
                                                         axis_name)
            D = take.shape[0]
            take = jnp.where(jnp.arange(D) < D // 2, take, 0)
            return ci, take, pending, head, key
        E._arrivals = arrivals
    elif fault == "altered_plan":
        real_round = E.round_relaxation_jnp

        def altered(*a, **k):
            assign, status, nf = real_round(*a, **k)
            m = a[0].shape[2]
            assign = assign.at[0, 0].set((assign[0, 0] + 1) % (m + 1))
            return assign, status, nf
        E.round_relaxation_jnp = altered
    elif fault:
        raise SystemExit(f"unknown fault {fault!r}")
    jax.clear_caches()


def main(argv):
    fault = ""
    if argv[:1] == ["--fault"]:
        fault, argv = argv[1], argv[2:]
    if argv[:1] == ["--"]:
        argv = argv[1:]
    bench = os.path.dirname(HERE)
    sys.path.insert(0, bench)
    sys.path.insert(0, os.path.join(os.path.dirname(bench), "src"))
    import jax

    import run
    run.accelerator = lambda chips: jax.devices()[:chips]
    if fault:
        plant(fault)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
