"""Chip benchmark of the fleet offloading planner.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json``: draw the cell's deployment
(``bench/configs/<config>.json``) and arrival trace
(``bench/traffic/<traffic>.json``) from ``--seed``, hand them to the
planner's public API, compile and warm up the entry point that the
traffic mix names (``step`` or ``rollout``, with its periods per call),
then plan period after period in a closed loop for ``--seconds`` seconds.
The cell's own file (``bench/cells/<cell>.json``) holds its warm-up,
trace length, traced calls and the limits of its checks.  With ``--trace 0`` the result line carries the
cell's end-to-end metrics; with ``--trace 1`` the profiler records the
first calls of the window and the result line carries the per-layer
metrics, each read by its reader (``bench/readers/<reader>.py``).

Every run then replays the same periods through the plain NumPy reference
(``bench/reference.py``) and compares (``bench/compare.py``): ``correct``
is true when every number compared is within its limit.

The run refuses to measure anything but a TPU: without one (or with fewer
chips than the cell asks for) it exits with code 2 and prints no result.
Its last line on standard output is the result, one JSON object.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import compare  # noqa: E402
import fleetgen  # noqa: E402
import manifest  # noqa: E402
import reference  # noqa: E402
import tracing  # noqa: E402

NO_ACCELERATOR = 2


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileMonitor:
    """JAX's own compile events: backend compiles (or persistent-cache
    retrievals) and the persistent cache's hits and misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.counts = Counter()
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1
            self.counts["compile_s"] += secs

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.counts[event.rsplit("/", 1)[1]] += 1

    def snapshot(self) -> Counter:
        return Counter(self.counts)


def accelerator(chips: int):
    """The devices to run on, or None (with a reason on stderr) when JAX
    finds no TPU or fewer chips than the cell needs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: JAX found no TPU (platform {devs[0].platform}); "
            f"refusing to measure it")
        return None
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips, JAX found {len(devs)}")
        return None
    return devs[:chips]


def compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` in the checkout (a fixed path: the path
    is part of the cache key).  Every program is cached, however quickly
    it compiled, so only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def run_window(call, state, seconds, trace_calls=0, trace_dir=None):
    """Closed loop: call, fetch that call's metrics to the host, repeat
    until ``seconds`` have passed and the first ``trace_calls`` calls,
    traced by the profiler, are done.  Returns ``(state, rows, spans,
    window_s, traced)``: one metrics dict per call, host spans ``(name,
    start, end)`` in seconds from the window's start, and the traced
    calls' ``(start, end)``."""
    import jax

    import system
    rows, spans = [], []
    traced = None
    t_open = time.perf_counter()
    k = 0
    while True:
        tracing_now = k < trace_calls
        if tracing_now and k == 0:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        if tracing_now:
            with jax.profiler.TraceAnnotation("bench:dispatch"):
                state, m = call(state)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:fetch"):
                rows.append(system.metrics_to_host(m))
        else:
            state, m = call(state)
            t1 = time.perf_counter()
            rows.append(system.metrics_to_host(m))
        t2 = time.perf_counter()
        spans.append(("dispatch", t0 - t_open, t1 - t_open))
        spans.append(("fetch", t1 - t_open, t2 - t_open))
        k += 1
        if tracing_now and k == trace_calls:
            traced = (spans[0][1], t2 - t_open)
            jax.profiler.stop_trace()
        if time.perf_counter() - t_open >= seconds and k >= trace_calls:
            break
    window_s = time.perf_counter() - t_open
    return state, rows, spans, window_s, traced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = manifest.cell(args.workload)
    w, config, traffic, cp = (spec["workload"], spec["config"],
                              spec["traffic"], spec["params"])
    chips = int(w["chips"])
    sys.path.insert(0, os.path.join(ROOT, "src"))

    devs = accelerator(chips)
    if devs is None:
        return NO_ACCELERATOR
    import jax

    import system
    cache = compile_cache()
    mon = CompileMonitor()
    log(f"device kind={devs[0].device_kind!r} count={len(jax.devices())} "
        f"used={chips} cache={cache}")

    t_jax = time.perf_counter() - T_START
    fleet = fleetgen.make_fleet(config, traffic, int(cp["trace_periods"]),
                                args.seed)
    t_gen = time.perf_counter() - T_START
    params, state = system.build(fleet, config, traffic)
    t_build = time.perf_counter() - T_START
    ppc = int(traffic["periods_per_call"])
    call = system.ENTRIES[traffic["entry"]](params, ppc)
    rows = []
    for _ in range(int(cp["warmup_calls"])):
        state, m = call(state)
        rows.append(system.metrics_to_host(m))
    c_setup = mon.snapshot()
    setup_s = time.perf_counter() - T_START
    log(f"setup phases: jax_ready={t_jax!r} generated={t_gen!r} "
        f"built={t_build!r} warm={setup_s!r}")
    log(f"setup setup_s={setup_s!r} compiles={c_setup['compiles']} "
        f"compile_s={c_setup['compile_s']!r} "
        f"cache_hits={c_setup['cache_hits']} "
        f"cache_misses={c_setup['cache_misses']}")

    trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
    trace_calls = int(cp["trace_calls"]) if args.trace else 0
    if trace_calls:
        shutil.rmtree(trace_dir, ignore_errors=True)
    state, wrows, spans, window_s, traced = run_window(
        call, state, args.seconds, trace_calls, trace_dir)
    c_win = mon.snapshot()
    c_win.subtract(c_setup)
    periods = len(wrows) * ppc
    rows += wrows
    log(f"window seconds={window_s!r} calls={len(wrows)} periods={periods} "
        f"compiles_in_window={c_win['compiles']}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips, "memory_peak_bytes": int(peak)}
    prog = {k: np.concatenate([r[k] for r in rows]) for k in rows[0]}
    n_win = slice(len(prog["period"]) - periods, None)
    attempted = periods * fleet.n_devices
    failed = int(prog["n_unsolved"][n_win].sum())

    metrics = {}
    breakdown = None
    if args.trace:
        ctx = tracing.Context.from_run(
            trace_dir, spans=spans, traced=traced,
            periods=trace_calls * ppc, devices=devs)
        metrics, extra = tracing.per_layer(ctx, spec["per_layer"])
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        breakdown = ctx.breakdown()
        log(extra)
    else:
        per_call = np.array([e - s for n, s, e in spans if n == "fetch"])
        call_s = np.array([spans[i + 1][2] - spans[i][1]
                           for i in range(0, len(spans), 2)])
        values = {"setup_s": setup_s,
                  "period_ms": 1000.0 * window_s / periods}
        if ppc == 1 and len(call_s):
            values["period_ms_p95"] = 1000.0 * float(
                np.percentile(call_s, 95))
        log(f"calls median_ms={1000 * float(np.median(call_s))!r} "
            f"fetch_median_ms={1000 * float(np.median(per_call))!r}")
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # ---- free the program's state, then the reference --------------------
    del state, params, call
    gc.collect()
    n_check = min(len(prog["period"]), int(cp["check_periods_max"]))
    sample = None
    if "check_sample" in cp:
        # a sample of the periods run, drawn from the seed: the reference
        # plans these and moves through the others without planning them
        # where it can, so that it ends well inside the window's length
        rng = np.random.default_rng([args.seed, 1])
        sample = set(rng.choice(n_check, min(n_check, int(cp["check_sample"])),
                                replace=False).tolist())
        n_check = max(sample) + 1
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max(1, min(8, (os.cpu_count() or 2) - 1))) as pool:
        try:
            ref = reference.run(fleet, config, n_check, pool=pool,
                                sample=sample)
            got = compare.compare(prog, ref)
            ref_error = None
        except reference.ReferenceFailure as e:
            got, ref_error = None, str(e)
    compared = n_check if sample is None else len(sample)
    log(f"reference periods={n_check} of {len(prog['period'])} "
        f"compared={compared} seconds={time.perf_counter() - t0!r}")
    limits = cp["limits"]
    if got is None:
        log(f"reference failed: {ref_error}")
        correct = False
        checks = {k: {"value": None, "limit": v} for k, v in limits.items()}
    else:
        checks = {k: {"value": got[k], "limit": limits[k]} for k in limits}
        correct = all(c["value"] <= c["limit"] for c in checks.values())
    for k, c in checks.items():
        log(f"check {k} value={c['value']!r} limit={c['limit']!r}")
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
