"""An EXEC's input crosses to the device as a lane-dense slab where it is
one floating-point array whose per-example element count is a multiple of
128 (engine.slab_shape): the same host bytes, reshaped back inside the one
compiled program of its bucket, with the same logits. Anything else crosses
as it is. Each EXEC lane counts its slab EXECs (`EXEC/slab_n`), and the
`exec/input` span names the layout. CPU, reduced width."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.actions import Request
from repro.core.clock import EventLoop, RealClock, RealtimePump
from repro.core.controller import Controller
from repro.core.scheduler import ClockworkScheduler
from repro.core.worker import Worker
from repro.models.resnet import resnet50_forward
from repro.runtime.controller import ControllerServer
from repro.runtime.transport import LoopbackLink
from repro.runtime.worker import WorkerHost
from repro.serving.engine import (REDUCED, JaxBackend, JaxModel,
                                  make_lm_decode_model, make_resnet_model,
                                  resnet_fleet, resnet_fleet_defs, slab_shape,
                                  to_wire)

ROWS = REDUCED["img"] ** 2 * 3 // 128        # slab rows per 64-px image


@pytest.fixture(scope="module")
def copy():
    """A reduced ResNet-50 copy with buckets 1 and 4, loaded on the CPU."""
    m = make_resnet_model("m0", batches=(1, 4), seed=3)
    dev = jax.devices()[0]
    m.compile([dev])
    m.load(dev)
    return m, dev


@pytest.mark.parametrize("b", [1, 4])
def test_slab_logits_equal_the_forward_on_nhwc(copy, b):
    m, dev = copy
    x = m._input(b)
    got = np.asarray(m.execute(b, x, dev))
    want = np.asarray(jax.jit(resnet50_forward)(m.device_params[dev], x))
    assert got.shape == (b, 1000)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("form", ["nhwc", "slab"])
def test_execute_takes_an_input_already_on_the_device(copy, form):
    """`execute` runs an input placed on the device beforehand, as the
    bucket's input is shaped or as it crosses, with the same logits."""
    m, dev = copy
    x = m._input(4)
    on_dev = jax.device_put(x if form == "nhwc" else to_wire(x), dev)
    got = np.asarray(m.execute(4, on_dev, dev))
    assert np.array_equal(got, np.asarray(m.execute(4, x, dev)))


@pytest.mark.parametrize("b", [1, 4])
def test_slab_is_a_view_of_the_nhwc_input(copy, b):
    m, _ = copy
    x = m._input(b)
    assert x.shape == (b, REDUCED["img"], REDUCED["img"], 3)
    slab = to_wire(x)
    assert slab.shape == slab_shape(x) == (b, ROWS, 128)
    assert np.shares_memory(slab, x)


@pytest.mark.parametrize("b", [1, 4])
def test_slab_program_keeps_the_forwards_module_name(copy, b):
    """The benchmark reads the forward's device time under this name."""
    m, dev = copy
    hlo = m.programs.get(dev, b, m.host_params, m._input(b)).as_text()
    assert hlo.startswith("HloModule jit_resnet50_forward,")
    assert f"f32[{b},{ROWS},128]" in hlo.split("\n")[0]
    assert set(m.programs.compile_s) == {(dev, 1), (dev, 4)}


def _tanh_model(width, dtype):
    rng = np.random.default_rng(0)
    w = rng.standard_normal((width, 8)).astype(np.float32)
    return JaxModel("t", lambda p, x: jnp.tanh(x.astype(jnp.float32) @ p["w"]),
                    {"w": w}, lambda b: np.ones((b, width), dtype),
                    weights_bytes=w.nbytes, batches=(1, 2))


@pytest.mark.parametrize("make", [
    lambda: make_lm_decode_model("lm", batches=(1, 2), ctx=16),
    lambda: _tanh_model(10, np.float32),
    lambda: _tanh_model(128, np.int32),
], ids=["lm-decode-tuple", "float-not-128", "int-128"])
def test_other_inputs_cross_as_they_are(make):
    m = make()
    dev = jax.devices()[0]
    m.compile([dev])
    assert set(m.programs.compile_s) == {(dev, b) for b in m.batches}
    m.load(dev)
    for b in m.batches:
        x = m._input(b)
        assert slab_shape(x) is None and to_wire(x) is x
        phases = {}
        m.run(b, dev, phases)
        assert phases["slab_n"] == 0
    assert len(m.programs.compile_s) == len(m.batches)


def test_lanes_count_slab_execs_and_spans_name_the_layout(tmp_path):
    """A reduced fleet served by the controller through a realtime lane:
    every EXEC crossed as a slab, the lane's `slab_n` gauge says so, and
    every `exec/input` span in the trace carries layout "slab"."""
    from jax.profiler import ProfileData
    engines = resnet_fleet(2, **REDUCED)
    dev = jax.devices()[0]
    for e in engines.values():
        e.compile([dev])
    next(iter(engines.values())).warm([dev])
    defs = resnet_fleet_defs(2, REDUCED["scale"])
    loop = EventLoop(RealClock())
    pump = RealtimePump(loop, max_poll=0.002)
    w = Worker("w0", loop, JaxBackend(engines, [dev]), defs, post=pump.post)
    controller = Controller(loop, defs, ClockworkScheduler(),
                            default_slo=30.0)
    server = ControllerServer(controller, estimate_net_delay=False)
    link = LoopbackLink(loop)
    server.adopt(link.a)
    host = WorkerHost(w, link.b, telemetry_interval=None)
    host.register()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(8):
            controller.on_request(Request(model_id=f"m{i % 2}",
                                          arrival=loop.now(), slo=30.0))
        assert pump.run(until=lambda: len(controller.completed) == 8,
                        timeout=120)
    finally:
        jax.profiler.stop_trace()
    host.flush_telemetry(sample_first=True)
    w.close()

    assert {r.status for r in controller.completed} == {"ok"}
    execs = [a for a in controller.recorder.iter_actions()
             if a.action_type == "INFER" and a.status == "SUCCESS"]
    assert execs
    ex = w.execs[(0, "EXEC")]
    assert ex.slab_n == len(execs)
    assert w.execs[(0, "LOAD")].slab_n is None
    gauges = {g.name: g.value for g in controller.recorder.iter_gauges()}
    assert gauges["worker/w0/gpu0/EXEC/slab_n"] == len(execs)
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    layouts = [dict(e.stats).get("layout")
               for plane in ProfileData.from_file(str(path)).planes
               for line in plane.lines for e in line.events
               if e.name == "exec/input"]
    assert layouts == ["slab"] * len(execs)
