"""The trace reduction, on a trace recorded on the CPU (data/cpu.xplane.pb:
three runs of a jitted 256x256 product and tanh under
`jax.profiler.start_trace` with the Python tracer off) and on device
lines laid over its host events."""
from pathlib import Path

import pytest

import xplane

TRACE = str(Path(__file__).parent / "data" / "cpu.xplane.pb")


@pytest.fixture(scope="module")
def cpu_trace():
    return xplane.load(TRACE)


def test_cpu_trace_has_host_events_and_no_device(cpu_trace):
    names = {n for n, _, _ in cpu_trace["host"]}
    assert "PjRtCpuExecutable::Execute" in names
    assert cpu_trace["devices"] == {}
    r = xplane.reduce(cpu_trace, 0.05)
    # nothing ran on a device: no device metric can be read from it
    assert r["busy_s"] == {} and r["programs_s"] == {}
    assert r["device_ops"] == [] and r["idle_gaps"] == []


def test_reduce_over_device_lines(cpu_trace):
    execs = sorted((s, e) for n, s, e in cpu_trace["host"]
                   if n == "PjRtCpuExecutable::Execute")
    assert len(execs) == 3
    # one device running two overlapping ops inside each host execute
    ops = []
    for s, e in execs:
        mid = (s + e) / 2
        ops += [("fusion.1", s, (mid + e) / 2), ("convolution.2", mid, e)]
    mods = [("jit_step(7)", s, e) for s, e in execs]
    trace = {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
             "host": cpu_trace["host"]}
    window = execs[-1][1] / 1e9 + 0.001
    r = xplane.reduce(trace, window)
    busy = sum(e - s for s, e in execs) / 1e9
    assert r["busy_s"]["/device:TPU:0"] == pytest.approx(busy)
    assert r["programs_s"] == {"jit_step": pytest.approx(busy)}
    assert [n for n, _ in r["device_ops"]] == ["fusion.1", "convolution.2"] \
        or [n for n, _ in r["device_ops"]] == ["convolution.2", "fusion.1"]
    gaps = r["idle_gaps"]
    assert sum(d for _, d in gaps) == pytest.approx(window - busy)
    assert all(d > 0 for _, d in gaps)
    # each gap is named by a host event recorded while the device idled
    host_names = {n for n, _, _ in cpu_trace["host"]} | {"no host event"}
    assert all(n in host_names for n, _ in gaps)


def test_union_and_gaps_clip_to_the_window():
    u = xplane.union([(5, 8), (-2, 1), (0, 3), (7, 12), (20, 30)], 0, 25)
    assert u == [[0, 3], [5, 12], [20, 25]]
    assert xplane.gaps(u, 0, 25) == [(3, 5), (12, 20)]


def test_host_activity_names_the_innermost_covering_event():
    host = [("outer", 0, 100), ("inner", 10, 30), ("brief", 12, 13)]
    assert xplane.host_activity(host, 12, 28) == "inner"
    assert xplane.host_activity(host, 50, 60) == "outer"
    assert xplane.host_activity(host, 200, 300) == "no host event"
