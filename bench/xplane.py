"""Reduction of a JAX profiler trace (`.xplane.pb`) to device metrics.

`load` reads the trace with `jax.profiler.ProfileData` into plain lists:
for each device plane (`/device:<KIND>:<n>`) the operations of its
"XLA Ops" line and the program runs of its "XLA Modules" line, and every
event of the host planes' threads. `reduce` turns those into

* `busy_s`: per device, the union of its operation intervals within the
  traced window, so overlapping operations count once;
* `programs_s`: device time per program, summed over devices, under the
  program's name without the "(id)" the trace appends;
* `device_ops`: the ten operations with most device time, summed over
  devices, each as its HLO name and output shape;
* `idle_gaps`: the ten longest intervals in which a device ran nothing,
  each named by what the host was doing then: the shortest host event
  that covers at least half of the gap, else the one that overlaps it
  most.

Times in the trace are nanoseconds from the moment the profiler
started; the window is [0, `window_s`].
"""
from __future__ import annotations

import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
TOP = 10


def load(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    [...]}, each event as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is not None:
                    dev[key] += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.duration_ns > 0]
    return {"devices": devices, "host": host}


def union(intervals, lo: float, hi: float) -> list:
    """Disjoint, sorted [start, end) intervals covering `intervals`
    clipped to [lo, hi]."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of disjoint sorted `busy` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_activity(host: list, s: float, e: float) -> str:
    """What the host was doing in [s, e] (see the module docstring)."""
    length = e - s
    best_cover, best_overlap = None, None
    for name, hs, he in host:
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        if ov >= length / 2 and (best_cover is None
                                 or he - hs < best_cover[1]):
            best_cover = (name, he - hs)
        if best_overlap is None or ov > best_overlap[1]:
            best_overlap = (name, ov)
    if best_cover is not None:
        return best_cover[0]
    return best_overlap[0] if best_overlap is not None else "no host event"


def program_name(module: str) -> str:
    return re.sub(r"\(\d+\)$", "", module)


def op_name(op: str) -> str:
    """An operation as "name shape": the trace names it by its whole HLO
    text, "%fusion.3 = bf16[16,56,56,256]{3,0,2,1:...} fusion(...)"."""
    lhs, _, rhs = op.partition(" = ")
    shape = re.match(r"[^{ ]*", rhs).group(0) if rhs else ""
    return f"{lhs.lstrip('%')} {shape}".strip()


def reduce(trace: dict, window_s: float) -> dict:
    """Device metrics of `trace` (from `load`) over [0, window_s]."""
    hi = window_s * 1e9
    busy, programs, ops, idle = {}, {}, {}, []
    for dev, ev in sorted(trace["devices"].items()):
        spans = union([(s, e) for _, s, e in (ev["ops"] or ev["modules"])],
                      0.0, hi)
        busy[dev] = sum(e - s for s, e in spans) / 1e9
        for name, s, e in ev["modules"]:
            d = max(0.0, min(e, hi) - max(s, 0.0))
            programs[program_name(name)] = programs.get(
                program_name(name), 0.0) + d / 1e9
        for name, s, e in ev["ops"]:
            d = max(0.0, min(e, hi) - max(s, 0.0))
            ops[op_name(name)] = ops.get(op_name(name), 0.0) + d / 1e9
        idle += [(e - s, s, e) for s, e in gaps(spans, 0.0, hi)]
    idle.sort(reverse=True)
    return {
        "window_s": window_s,
        "busy_s": busy,
        "programs_s": programs,
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": [[host_activity(trace["host"], s, e), d / 1e9]
                      for d, s, e in idle[:TOP]],
    }
