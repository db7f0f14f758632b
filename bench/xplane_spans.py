"""The worker's EXEC phases in a JAX profiler trace, and each device's idle
time put down to them.

The program's EXEC lanes mark each phase of an EXEC with a profiler span
named "exec/<phase>" (`repro.serving.engine.EXEC_PHASES`: input,
dispatch, wait) whose `gpu` argument names the lane's device. `load` reads
those spans from a `.xplane.pb`; `idle_by_span` takes `xplane.load`'s
device lines and gives, per device, the seconds of its idle time within
the traced window [0, `window_s`] under each phase of its own lane's
spans, and under "none" where its lane ran no phase. Per device they add
up to the window minus `xplane.reduce`'s `busy_s`.

A trace of a program without these spans holds none: every idle second
then falls under "none".
"""
from __future__ import annotations

import re

import xplane

PREFIX = "exec/"
NONE = "none"
DEVICE_ORDINAL = re.compile(r":(\d+)$")


def load(path: str) -> list:
    """[(phase, gpu, start_ns, end_ns)] of every "exec/<phase>" span on
    the trace's host planes, sorted by start."""
    from jax.profiler import ProfileData
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                gpu = dict(e.stats).get("gpu")
                if gpu is not None:
                    spans.append((e.name[len(PREFIX):], int(gpu),
                                  e.start_ns, e.start_ns + e.duration_ns))
    return sorted(spans, key=lambda s: s[2])


def disjoint(spans: list, lo: float, hi: float) -> list:
    """[(start, end, phase)] of `spans` (one lane's, sorted by start)
    clipped to [lo, hi]; where two overlap, the later starts where the
    earlier ends, so no instant counts twice."""
    out, t = [], lo
    for phase, _, s, e in spans:
        s, e = max(s, t), min(e, hi)
        if e > s:
            out.append((s, e, phase))
            t = e
    return out


def idle_by_span(trace: dict, spans: list, window_s: float) -> dict:
    """{device plane: {phase or "none": idle seconds}} over [0, window_s]
    (see the module docstring). `trace` is `xplane.load`'s; a device is
    matched to the spans whose `gpu` is its plane's ordinal."""
    hi = window_s * 1e9
    out = {}
    for dev, ev in sorted(trace["devices"].items()):
        busy = xplane.union([(s, e) for _, s, e in (ev["ops"]
                                                      or ev["modules"])],
                            0.0, hi)
        gpu = int(DEVICE_ORDINAL.search(dev).group(1))
        lane = disjoint([s for s in spans if s[1] == gpu], 0.0, hi)
        split = {NONE: 0.0}
        i = 0
        for gs, ge in xplane.gaps(busy, 0.0, hi):
            covered = 0.0
            while i < len(lane) and lane[i][1] <= gs:
                i += 1
            j = i
            while j < len(lane) and lane[j][0] < ge:
                s, e, phase = lane[j]
                d = min(e, ge) - max(s, gs)
                split[phase] = split.get(phase, 0.0) + d / 1e9
                covered += d
                j += 1
            split[NONE] += (ge - gs - covered) / 1e9
        out[dev] = split
    return out
