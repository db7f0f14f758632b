"""Device idle time put down to the EXEC lanes' phases, on a trace recorded
on the CPU (data/cpu_exec.xplane.pb: three EXECs, at buckets 1, 2 and 1,
of a `JaxModel` whose forward is tanh(x W), served by the worker's
realtime EXEC lane under `jax.profiler.start_trace` with bench/daemon.py's
Tracer options) with device lines laid over its host events, and on
spans made by hand."""
from pathlib import Path

import pytest

import xplane
import xplane_spans

DATA = Path(__file__).parent / "data"
TRACE = str(DATA / "cpu_exec.xplane.pb")
PHASES = ("input", "dispatch", "wait")


@pytest.fixture(scope="module")
def cpu_trace():
    return xplane.load(TRACE), xplane_spans.load(TRACE)


def test_the_lanes_spans_are_read_with_their_device(cpu_trace):
    _, spans = cpu_trace
    assert [(p, g) for p, g, _, _ in spans] == [(p, 0) for p in PHASES] * 3
    assert all(s < e for _, _, s, e in spans)
    # one lane runs its phases one after another
    assert all(a[3] <= b[2] for a, b in zip(spans, spans[1:]))


def _brute_force(ops, spans, gpu, hi, step=1000):
    """Idle nanoseconds per phase, sampled every `step` ns."""
    out: dict = {}
    for t in range(0, int(hi), step):
        if any(s <= t < e for _, s, e in ops):
            continue
        phase = next((p for p, g, s, e in spans if g == gpu and s <= t < e),
                     "none")
        out[phase] = out.get(phase, 0) + step
    return {k: v / 1e9 for k, v in out.items()}


def test_idle_by_span_over_device_lines(cpu_trace):
    host, spans = cpu_trace
    execs = sorted((s, e) for n, s, e in host["host"]
                   if n == "PjRtCpuExecutable::Execute")
    assert len(execs) == 3
    # device 0 runs two overlapping ops inside each host execute; device 1
    # runs the same, but no lane of gpu 1 left spans in the trace
    ops = []
    for s, e in execs:
        mid = (s + e) / 2
        ops += [("fusion.1", s, (mid + e) / 2), ("convolution.2", mid, e)]
    mods = [("jit_step(7)", s, e) for s, e in execs]
    trace = {"devices": {d: {"ops": ops, "modules": mods}
                         for d in ("/device:TPU:0", "/device:TPU:1")},
             "host": host["host"]}
    window = spans[-1][3] / 1e9 + 0.002
    busy = xplane.reduce(trace, window)["busy_s"]
    split = xplane_spans.idle_by_span(trace, spans, window)
    for dev in trace["devices"]:
        assert sum(split[dev].values()) == pytest.approx(
            window - busy[dev], rel=1e-9)
    # the device idles through each input copy and between the EXECs
    assert split["/device:TPU:0"]["input"] > 0
    assert split["/device:TPU:0"]["none"] > 0
    assert split["/device:TPU:1"] == {"none": pytest.approx(
        window - busy["/device:TPU:1"])}
    expected = _brute_force(ops, spans, 0, window * 1e9)
    assert set(split["/device:TPU:0"]) == set(expected)
    for phase, secs in expected.items():
        # each boundary can move a sample by one step
        assert split["/device:TPU:0"][phase] == pytest.approx(
            secs, abs=2 * len(spans) * 1e-6)


def test_idle_by_span_clips_and_never_counts_an_instant_twice():
    trace = {"devices": {"/device:TPU:2": {"ops": [("op", 40, 60)],
                                           "modules": []}},
             "host": []}
    spans = [("input", 2, -10, 30), ("dispatch", 2, 20, 50),
             ("wait", 2, 50, 130), ("input", 3, 0, 100)]
    split = xplane_spans.idle_by_span(trace, spans, 100e-9)
    # idle [0, 40) and [60, 100): input [0, 30), dispatch [30, 40),
    # wait [60, 100); gpu 3's span is another device's
    assert split == {"/device:TPU:2": {
        "none": pytest.approx(0.0), "input": pytest.approx(30e-9),
        "dispatch": pytest.approx(10e-9), "wait": pytest.approx(40e-9)}}


def test_a_trace_without_spans_puts_all_idle_time_under_none():
    assert xplane_spans.load(str(DATA / "cpu.xplane.pb")) == []
    trace = {"devices": {"/device:TPU:0": {"ops": [("op", 10, 30)],
                                           "modules": []}},
             "host": []}
    assert xplane_spans.idle_by_span(trace, [], 50e-9) == {
        "/device:TPU:0": {"none": pytest.approx(30e-9)}}
