"""From a profiler trace to the per-layer metrics.

`Context.from_run` reads the XPlane file that ``jax.profiler`` wrote for
the traced calls of a window:

* the device ops of each chip used, from its ``/device:TPU:<i>`` plane
  (the line that carries XLA ops), each with its HLO module and op name;
* the HLO modules themselves, where the trace carries them, whose
  metadata names the source file that made each op: an op belongs to
  that file, a fusion to the file of its root instruction, and an op
  with no file to the JAX name stack it was traced under;
* the benchmark's own host spans (``bench:dispatch``, ``bench:fetch``),
  which fix the traced window and name the device's idle gaps.

`per_layer` runs each per-layer metric's reader (``readers/<name>.py``,
a function ``read(ctx, **args)`` returning a number, or None when it
finds nothing to read).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import importlib
import os
import re
from collections import defaultdict

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Op:
    name: str          # HLO instruction (e.g. "fusion.12")
    module: str        # HLO module
    device: int        # index among the chips used
    start: float       # seconds since the traced window opened
    dur: float         # seconds
    opcode: str        # HLO opcode, "" when unknown
    source: str        # source file that made it, "" when unknown


# --------------------------------------------------------------------------
# HLO text: instruction -> (opcode, source file)
# --------------------------------------------------------------------------
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_SRCFILE = re.compile(r'source_file="([^"]+)"')
_OPNAME = re.compile(r'op_name="([^"]+)"')
_COMP = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")


def parse_hlo(text: str) -> dict:
    """``instruction name -> (opcode, source file or "", op_name)`` for
    every instruction of an HLO module's text.  A fusion gets the file of
    its fused computation's root instruction, or, where the root carries
    none, of the first instruction there that does."""
    files, locs, frames = {}, {}, {}
    section = None
    instrs = {}
    comps = {}           # computation -> [instruction names], root first
    comp = None
    for line in text.splitlines():
        s = line.strip()
        if s in ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames"):
            section = s
            continue
        if section and s and s[0].isdigit():
            key, rest = s.split(" ", 1)
            if section == "FileNames":
                files[int(key)] = rest.strip().strip('"')
            elif section == "FileLocations":
                m = re.search(r"file_name_id=(\d+)", rest)
                if m:
                    locs[int(key)] = int(m.group(1))
            elif section == "StackFrames":
                m = re.search(r"file_location_id=(\d+)", rest)
                if m:
                    frames[int(key)] = int(m.group(1))
            continue
        section = None
        if not s:
            continue
        mc = _COMP.match(line)
        if mc and "=" not in line.split("{")[0]:
            comp = mc.group(2)
            comps[comp] = []
            continue
        mi = _INSTR.match(line)
        if not mi:
            continue
        name, opcode = mi.group(2), mi.group(3)
        src = ""
        mf = _SRCFILE.search(line)
        if mf:
            src = mf.group(1)
        else:
            fr = _FRAME.search(line)
            if fr:
                src = files.get(locs.get(frames.get(int(fr.group(1)), -1),
                                         -1), "")
        mo = _OPNAME.search(line)
        calls = _CALLS.search(line)
        instrs[name] = (opcode, src, mo.group(1) if mo else "",
                        calls.group(1) if calls else None)
        if comp is not None:
            if mi.group(1):
                comps[comp].insert(0, name)
            else:
                comps[comp].append(name)

    def fused_source(called, depth=0):
        body = comps.get(called, [])
        if not body or depth > 8:
            return ""
        for n in body:
            op, src, _, sub = instrs[n]
            if op == "fusion" and sub:
                src = fused_source(sub, depth + 1) or src
            if src:
                return src
        return ""

    out = {}
    for name, (opcode, src, opname, calls) in instrs.items():
        if opcode == "fusion" and calls:
            src = fused_source(calls) or src
        out[name] = (opcode, src, opname)
    return out


def hlo_module_text(module_proto: bytes) -> tuple:
    """``(name, text)`` of a serialized ``xla.HloModuleProto``.

    JAX has no public call for this: it is the one use of its private
    XLA bindings in the benchmark, and it fails loudly when they move."""
    try:
        from jax._src.lib import _jax
        mod = _jax.HloModule.from_serialized_hlo_module_proto(module_proto)
    except (ImportError, AttributeError) as e:
        raise RuntimeError(
            "this JAX has no jax._src.lib._jax.HloModule."
            "from_serialized_hlo_module_proto: the trace's HLO modules "
            "cannot be read, so no op can be attributed to a module") from e
    return mod.name, mod.to_string()


def hlo_texts(path) -> dict:
    """HLO module name -> text, for every module the trace carries (the
    ``Hlo Proto`` stat of the metadata plane's event metadata, an
    ``xla.HloProto`` whose field 1 is the module)."""
    import xspace
    out = {}
    for plane in xspace.planes(path):
        if plane["name"] != "/host:metadata":
            continue
        for md in plane["event_metadata"].values():
            proto = md["stats"].get("Hlo Proto")
            if not isinstance(proto, bytes):
                continue
            inner = [v for n, _, v in xspace._fields(memoryview(proto))
                     if n == 1]
            if not inner:
                continue
            name, text = hlo_module_text(bytes(inner[0]))
            out[name] = text
    return out


# --------------------------------------------------------------------------
def _union(intervals):
    """Total length of the union of ``(start, end)`` intervals, and the
    gaps between them as ``(start, end)``."""
    if not intervals:
        return 0.0, []
    iv = sorted(intervals)
    total = 0.0
    gaps = []
    cs, ce = iv[0]
    for s, e in iv[1:]:
        if s > ce:
            total += ce - cs
            gaps.append((ce, s))
            cs, ce = s, e
        else:
            ce = max(ce, e)
    total += ce - cs
    return total, gaps


class Context:
    """What the readers read: the traced window's device ops and host
    spans, and the number of periods planned in it."""

    def __init__(self, ops, spans, window, periods, n_chips):
        self.ops = ops                  # list of Op
        self.spans = spans              # (name, start, end), window secs
        self.window = window            # (start, end) of the traced calls
        self.periods = periods
        self.n_chips = n_chips

    # ---- construction from a run ---------------------------------------
    @classmethod
    def from_run(cls, trace_dir, *, spans, traced, periods, devices):
        from jax.profiler import ProfileData
        files = sorted(glob.glob(os.path.join(trace_dir, "**",
                                              "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no trace under {trace_dir}")
        pd = ProfileData.from_file(files[-1])
        ops, host = read_planes(pd, files[-1], len(devices))
        # host spans and the device ops share the profiler's clock; the
        # benchmark's annotations tie it to the window's clock
        marks = sorted(s for n, s, e in host if n == "bench:dispatch")
        if not marks:
            raise ValueError("the trace has no bench:dispatch span")
        shift = marks[0] - traced[0]
        for op in ops:
            op.start -= shift
        traced_spans = [(n, s, e) for n, s, e in spans
                        if s >= traced[0] - 1e-9 and e <= traced[1] + 1e-6]
        return cls(ops, traced_spans, traced, periods, len(devices))

    # ---- shared quantities ----------------------------------------------
    def _intervals(self, device):
        w0, w1 = self.window
        return [(max(o.start, w0), min(o.start + o.dur, w1))
                for o in self.ops
                if o.device == device and o.start + o.dur > w0
                and o.start < w1]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips used."""
        return float(np.mean([_union(self._intervals(d))[0]
                              for d in range(self.n_chips)]))

    def leaf_ops(self):
        """Ops that are not containers of other ops (a while loop, a
        conditional, a call span their bodies' ops on the same line)."""
        return [o for o in self.ops
                if o.opcode not in ("while", "conditional", "call")]

    def breakdown(self) -> dict:
        tot = defaultdict(float)
        for o in self.leaf_ops():
            src = os.path.basename(o.source) if o.source else "?"
            tot[f"{o.name} [{src}]"] += o.dur / self.n_chips
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
        gaps = []
        for d in range(self.n_chips):
            gaps += _union(self._intervals(d))[1]
        w0, w1 = self.window
        edges = []
        if self.ops:
            first = min(o.start for o in self.ops if o.device == 0)
            last = max(o.start + o.dur for o in self.ops if o.device == 0)
            edges = [(w0, max(w0, first)), (min(w1, last), w1)]
        named = []
        for s, e in gaps + edges:
            if e - s <= 0:
                continue
            mid = 0.5 * (s + e)
            what = "between calls"
            for n, a, b in self.spans:
                if a <= mid <= b:
                    what = n
                    break
            named.append((what, e - s))
        named.sort(key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in named[:10]]}


_EVENT = re.compile(r"^%?([\w.\-]+)(?:\s*=\s*(.*))?$", re.S)
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def _name_and_opcode(text):
    """An XLA op event's name is its HLO instruction, printed whole on a
    TPU (``%fusion.12 = f32[8]{0} fusion(...), calls=...``) or bare."""
    m = _EVENT.match(text)
    if not m:
        return text, ""
    rest = m.group(2) or ""
    op = _OPCODE.search(" " + rest)
    return m.group(1), op.group(1) if op else ""


def read_planes(pd, path, n_chips):
    """``(ops, host_spans)``: every XLA op on the first ``n_chips`` TPU
    planes, with times in seconds on the profiler's clock, attributed to
    the source file its HLO module names; and every ``bench:`` host span
    as ``(name, start, end)``."""
    hlo = {name: parse_hlo(text) for name, text in hlo_texts(path).items()}
    ops, host = [], []
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) < n_chips:
            dev = int(m.group(1))
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
                 ev.name.split("(")[0])
                for ev in (lines["XLA Modules"].events
                           if "XLA Modules" in lines else ()))
            starts = [a for a, _, _ in modules]
            parsed = {}
            for ev in (lines["XLA Ops"].events if "XLA Ops" in lines
                       else ()):
                t = ev.start_ns * 1e-9
                k = bisect.bisect_right(starts, t) - 1
                module = modules[k][2] if k >= 0 else ""
                key = (module, ev.name)
                if key not in parsed:
                    name, opcode = _name_and_opcode(ev.name)
                    hop, src, _ = hlo.get(module, {}).get(name, ("", "", ""))
                    parsed[key] = (name, hop or opcode, src)
                name, opcode, src = parsed[key]
                ops.append(Op(name=name, module=module, device=dev,
                              start=t, dur=ev.duration_ns * 1e-9,
                              opcode=opcode, source=src))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench:"):
                        host.append((ev.name, ev.start_ns * 1e-9,
                                     (ev.start_ns + ev.duration_ns) * 1e-9))
    return ops, host


def per_layer(ctx, metrics):
    """``({name: {"value", "unit"}}, note)``: each metric's reader on
    ``ctx``, leaving out those that find nothing to read; ``note`` gives
    the share of leaf-op time that no module metric claims."""
    claimed = []
    for m in metrics:
        claimed += m.get("args", {}).get("modules", [])
    out = {}
    for m in metrics:
        reader = importlib.import_module(f"readers.{m['reader']}")
        v = reader.read(ctx, **m.get("args", {}))
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    leaf = ctx.leaf_ops()
    total = sum(o.dur for o in leaf)
    unclaimed = sum(o.dur for o in leaf
                    if not any(o.source.endswith(c) for c in claimed))
    no_src = sum(o.dur for o in leaf if not o.source)
    note = (f"trace ops={len(ctx.ops)} leaf_s={total!r} "
            f"unattributed_share={(unclaimed / total if total else 0.0)!r} "
            f"no_source_share={(no_src / total if total else 0.0)!r}")
    return out, note
