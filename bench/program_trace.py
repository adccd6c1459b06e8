"""The planner's own spans and scopes, read from a run's profiler trace.

The program marks its work itself (``src/repro/api/engine.py``):

* host spans (``jax.profiler.TraceAnnotation``) in each public entry
  point: ``repro.step`` / ``repro.rollout`` / ``repro.step_sharded`` /
  ``repro.rollout_sharded``, each holding ``repro.validate`` (the float64
  checks), ``repro.horizon`` (the replay-horizon check, with its
  device-to-host read of ``state.period``) and ``repro.launch`` (the
  jitted call until it returns);
* device scopes (``jax.named_scope``), which reach every op's HLO
  metadata as a component of its ``op_name``: each op of a period sits
  under one top-level scope of `SCOPES`, and ``plan`` / ``replan`` nest
  ``lp`` and ``round``.

`tracing.Context` reads neither, so the readers ``scope_time`` and
``program_span`` take them from the same trace file through
`for_context`: the newest trace under ``.bench_trace/``, the one the run
has just written, accepted only when its ``bench:dispatch`` spans are the
context's traced calls.  A trace of a program without these marks gives
nothing to read, and the readers return None.

Run as a script after a ``--trace 1`` run of a cell, it prints one JSON
line: each top-level scope's device ms per period, the ``lp`` / ``round``
split inside ``plan`` and ``replan``, the share of leaf-op time under no
scope, each program span's ms per period, and the device's idle gaps, each
named by the innermost host span (the benchmark's or the program's) that
covers it::

    python3 bench/program_trace.py --workload campus1k.plan
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import functools
import glob
import json
import os
import re
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACES = os.path.join(ROOT, ".bench_trace")    # where bench/run.py traces

SCOPES = ("arrivals", "route", "plan", "hi_gate", "admission", "replan",
          "ladder", "pricing")
NESTED = ("lp", "round")
ENTRIES = ("repro.step", "repro.rollout", "repro.step_sharded",
           "repro.rollout_sharded")
PHASES = ("repro.validate", "repro.horizon", "repro.launch")


def scope_path(op_name: str) -> str:
    """``<top>`` or ``<top>/<nested>`` (``plan/lp``) of an ``op_name``, ""
    when it names no scope.  An op the compiler merged from several keeps
    their paths joined by ``;``: the first that names a scope counts."""
    for path in op_name.split(";"):
        parts = path.split("/")
        for i, part in enumerate(parts):
            if part in SCOPES:
                inner = [p for p in parts[i + 1:] if p in NESTED]
                return part + ("/" + inner[0] if inner else "")
    return ""


def top_scope(op_name: str) -> str:
    """The top-level scope an ``op_name`` names, "" when none."""
    return scope_path(op_name).split("/")[0]


# --------------------------------------------------------------------------
# HLO text: instruction -> op_name
# --------------------------------------------------------------------------
# a computation's header: ``[ENTRY] %name (params) -> shape {`` (its
# parameter list may hold ``/*index=5*/`` comments, so no test on "=")
_COMP = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTR = re.compile(
    r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(")
_OPNAME = re.compile(r'op_name="([^"]+)"')
_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")


def op_names(text: str) -> dict:
    """``instruction -> op_name`` for every instruction of an HLO module's
    text.  An instruction with no ``op_name`` of its own takes, in turn:
    if it is a fusion, the first one inside its fused computation (root
    first, one under a scope before one under none); the one of the first
    operand that has one (a copy or an emulated-float64 combine the
    compiler inserted belongs with what it moves); the one of the
    instruction that calls its computation (a loop body's copy belongs
    where the loop does); else ""."""
    own, opcode, comp_of, calls, args = {}, {}, {}, {}, {}
    body = defaultdict(list)            # computation -> instrs, root first
    caller = {}                         # computation -> calling instruction
    comp = None
    for line in text.splitlines():
        mc = _COMP.match(line)
        if mc:
            comp = mc.group(2)
            continue
        mi = _INSTR.match(line)
        if not mi:
            continue
        name = mi.group(2)
        mo = _OPNAME.search(line)
        own[name] = mo.group(1) if mo else ""
        opcode[name] = mi.group(3)
        comp_of[name] = comp
        args[name] = _OPERAND.findall(line[mi.end():].split(")", 1)[0])
        called = _CALLED.findall(line)
        mb = _BRANCHES.search(line)
        if mb:
            called += [c.strip().lstrip("%") for c in mb.group(1).split(",")]
        calls[name] = called
        for c in called:
            caller.setdefault(c, name)
        if mi.group(1):
            body[comp].insert(0, name)
        else:
            body[comp].append(name)

    def inside(c, depth):
        names = []
        for n in body.get(c, ()):
            names.append(own[n])
            if opcode[n] == "fusion" and depth < 8:
                names += [inside(sub, depth + 1) for sub in calls[n]]
        scoped = [x for x in names if top_scope(x)]
        return (scoped or [x for x in names if x] or [""])[0]

    @functools.lru_cache(maxsize=None)
    def resolve(name, depth=0):
        if own[name]:
            return own[name]
        if opcode[name] == "fusion":
            for sub in calls[name]:
                got = inside(sub, 0)
                if got:
                    return got
        if depth >= 32:
            return ""
        for a in args[name]:
            got = resolve(a, depth + 1) if a in own else ""
            if top_scope(got):
                return got
        up = caller.get(comp_of[name])
        return resolve(up, depth + 1) if up else ""

    return {name: resolve(name) for name in own}


# --------------------------------------------------------------------------
# a run's trace
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Trace:
    path: str
    spans: list         # (name, start, end): bench and program host spans
    op_names: dict      # HLO module -> {instruction: op_name}

    def op_name(self, op) -> str:
        return self.op_names.get(op.module, {}).get(op.name, "")

    def dispatches(self) -> list:
        return sorted((s, e) for n, s, e in self.spans
                      if n == "bench:dispatch")

    def span_seconds(self, names) -> float:
        """Total seconds of the program spans named ``names`` that lie
        inside one of the benchmark's traced calls."""
        calls = self.dispatches()
        starts = [s for s, _ in calls]
        total = 0.0
        for n, s, e in self.spans:
            if n not in names:
                continue
            k = bisect.bisect_right(starts, s) - 1
            if k >= 0 and e <= calls[k][1]:
                total += e - s
        return total


def host_spans(pd) -> list:
    """Every ``bench:`` and ``repro.`` host span of a
    `jax.profiler.ProfileData`, in seconds on the profiler's clock."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("bench:", "repro.")):
                    out.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime: float) -> Trace:
    import tracing
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    names = {mod: op_names(text)
             for mod, text in tracing.hlo_texts(path).items()}
    return Trace(path=path, spans=host_spans(pd), op_names=names)


def load(path: str) -> Trace:
    return _load(path, os.path.getmtime(path))


def newest(directory: str = TRACES):
    """The newest trace file under ``directory``, or None."""
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def for_context(ctx):
    """The trace ``ctx`` was read from, or None when the newest trace is
    not it (its count of ``bench:dispatch`` spans is not the context's
    count of traced calls)."""
    path = newest()
    if path is None:
        return None
    tr = load(path)
    calls = sum(1 for n, _, _ in ctx.spans if n == "dispatch")
    return tr if calls and len(tr.dispatches()) == calls else None


# --------------------------------------------------------------------------
# the script: a traced run's program-side breakdown
# --------------------------------------------------------------------------
def innermost(spans, t: float) -> str:
    """Name of the shortest span that covers ``t``, or "between calls"."""
    best, name = None, "between calls"
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best):
            best, name = e - s, n
    return name


def idle_gaps(ops, spans, window, n_chips) -> list:
    """Each idle gap of the chips' op intervals inside ``window``, as
    ``[name, seconds]`` longest first, named by `innermost`."""
    import tracing
    w0, w1 = window
    named = []
    for d in range(n_chips):
        iv = [(max(o.start, w0), min(o.start + o.dur, w1)) for o in ops
              if o.device == d and o.start + o.dur > w0 and o.start < w1]
        gaps = tracing._union(iv)[1]
        if iv:
            gaps += [(w0, min(s for s, _ in iv)), (max(e for _, e in iv), w1)]
        else:
            gaps = [window]
        for s, e in gaps:
            if e > s:
                named.append([innermost(spans, 0.5 * (s + e)), e - s])
    named.sort(key=lambda kv: -kv[1])
    return named


def breakdown(path: str, n_chips: int, periods_per_call: int) -> dict:
    import tracing
    from jax.profiler import ProfileData
    tr = load(path)
    calls = tr.dispatches()
    periods = len(calls) * periods_per_call
    fetches = [e for n, _, e in tr.spans if n == "bench:fetch"]
    window = (calls[0][0], max(fetches + [calls[-1][1]]))
    ops, _ = tracing.read_planes(ProfileData.from_file(path), path, n_chips)
    per_path = defaultdict(float)
    for o in tracing.Context(ops, [], window, periods, n_chips).leaf_ops():
        per_path[scope_path(tr.op_name(o))] += o.dur
    total = sum(per_path.values())
    scale = 1000.0 / n_chips / max(periods, 1)
    top = defaultdict(float)
    for p, s in per_path.items():
        if p:
            top[p.split("/")[0]] += s
    spans = {n: 1000.0 * tr.span_seconds((n,)) / max(periods, 1)
             for n in ENTRIES + PHASES}
    return {
        "trace": os.path.relpath(path, ROOT), "periods": periods,
        "scopes_ms": {k: v * scale for k, v in sorted(top.items())},
        "nested_ms": {k: v * scale for k, v in sorted(per_path.items())
                      if "/" in k},
        "unscoped_share": per_path.get("", 0.0) / total if total else None,
        "spans_ms": {k: v for k, v in spans.items() if v > 0},
        "idle_gaps": idle_gaps(ops, tr.spans, window, n_chips)[:12],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import manifest
    spec = manifest.cell(args.workload)
    path = newest(os.path.join(TRACES, args.workload))
    if path is None:
        print(f"no trace of {args.workload} under .bench_trace/",
              file=sys.stderr)
        return 1
    out = breakdown(path, int(spec["workload"]["chips"]),
                    int(spec["traffic"]["periods_per_call"]))
    print(json.dumps({"workload": args.workload, **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
