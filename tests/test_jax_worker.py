"""The worker's JAX backend on CPU at reduced width: LOAD places a copy's
weights on the device its gpu_id names, EXEC runs there and never loads,
UNLOAD frees the buffers, copies share compiled programs, lanes run on
threads of their own, and the daemon serves through the TCP runtime."""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.core.actions import Action, ActionType, Request, ResultStatus
from repro.core.clock import EventLoop, RealClock, RealtimePump
from repro.core.controller import Controller
from repro.core.scheduler import ClockworkScheduler
from repro.core.worker import Worker
from repro.runtime.controller import ControllerServer
from repro.runtime.transport import LoopbackLink
from repro.runtime.worker import WorkerHost
from repro.serving import engine
from repro.serving.engine import (REDUCED, JaxBackend, NotLoadedError,
                                  check_logits, make_resnet_model,
                                  resnet_fleet, resnet_fleet_defs)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(tmp_path, **extra):
    env = dict(os.environ, **extra)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    return env


def _worker(n_models=1, batches=(1,)):
    engines = {f"m{i}": make_resnet_model(f"m{i}", batches=batches, seed=i)
               for i in range(n_models)}
    w, pump, results = _laned(JaxBackend(engines, jax.devices()[:1]), 1,
                              n_models)
    return w, pump, engines, results


def _serve(w, pump, results, *actions):
    """Hand `actions` to the worker and pump until each has a result."""
    n = len(results) + len(actions)
    for a in actions:
        w.receive(a)
    assert pump.run(until=lambda: len(results) == n, timeout=60)


def _act(w, kind, mid, gpu=0):
    now = w.loop.now()
    return Action(type=kind, model_id=mid, worker_id="w0", gpu_id=gpu,
                  earliest=now, latest=now + 60.0, expected_duration=1e-3)


class _SleepBackend:
    """Realtime backend whose EXEC takes 20 ms but reports 1 ms; LOAD takes
    `load_s`. EXEC raises `fail` when one is given."""
    realtime = True

    def __init__(self, load_s=0.0, fail=None):
        self.load_s, self.fail = load_s, fail

    def load_duration(self, model, gpu_id):
        time.sleep(self.load_s)
        return 1e-3

    def unload(self, model, gpu_id):
        pass

    def exec_duration(self, model, action):
        if self.fail is not None:
            raise self.fail
        time.sleep(0.02)
        return 1e-3


def _laned(backend, n_gpus, n_models=2):
    """A worker whose lanes run on threads, driven by a pump."""
    loop = EventLoop(RealClock())
    pump = RealtimePump(loop, max_poll=0.002)
    w = Worker("w0", loop, backend,
               resnet_fleet_defs(n_models, REDUCED["scale"]), n_gpus=n_gpus,
               post=pump.post)
    results = []
    w.on_result = results.append
    return w, pump, results


def test_lanes_run_on_their_own_threads_and_overlap():
    """Two devices' EXECs and a LOAD beside one of them run at once, as
    the controller plans them, each stamped with its own start and end."""
    w, pump, results = _laned(_SleepBackend(load_s=0.02), n_gpus=2)
    _serve(w, pump, results,
           *[_act(w, ActionType.LOAD, "m0", g) for g in (0, 1)])
    _serve(w, pump, results, _act(w, ActionType.INFER, "m0", 0),
           _act(w, ActionType.INFER, "m0", 1),
           _act(w, ActionType.LOAD, "m1", 0))
    ran = results[2:]
    assert sorted((r.action_type.value, r.gpu_id) for r in ran) == [
        ("INFER", 0), ("INFER", 1), ("LOAD", 0)]
    assert all(r.status is ResultStatus.SUCCESS for r in ran)
    assert max(r.t_start for r in ran) < min(r.t_end for r in ran)
    assert all(ex.total_busy > 0 for ex in w.execs.values())
    w.close()
    assert not any(ex.lane.is_alive() for ex in w.execs.values())


def test_lane_reports_exec_without_weights_and_raises_other_failures():
    w, pump, results = _laned(_SleepBackend(fail=NotLoadedError("m0")), 1)
    _serve(w, pump, results, _act(w, ActionType.LOAD, "m0"),
           _act(w, ActionType.INFER, "m0"))
    assert {r.action_type: r.status for r in results} == {
        ActionType.LOAD: ResultStatus.SUCCESS,
        ActionType.INFER: ResultStatus.ERROR_NOT_LOADED}
    assert not w.execs[(0, "EXEC")].busy
    w.backend.fail = ValueError("device lost")
    w.receive(_act(w, ActionType.INFER, "m0"))
    with pytest.raises(ValueError, match="device lost"):
        pump.run(timeout=10)


def test_realtime_results_time_the_whole_action_and_only_it():
    """Each device's EXEC result carries the wall time the action held its
    executor (20 ms), not the 1 ms the backend reports, and is stamped
    with its own start and end."""
    w, pump, results = _laned(_SleepBackend(), n_gpus=2, n_models=1)
    _serve(w, pump, results,
           *[_act(w, ActionType.LOAD, "m0", g) for g in (0, 1)])
    _serve(w, pump, results,
           *[_act(w, ActionType.INFER, "m0", g) for g in (0, 1)])
    assert sorted(r.gpu_id for r in results[2:]) == [0, 1]
    for r in results[2:]:
        assert r.status is ResultStatus.SUCCESS
        assert r.duration >= 0.02
        assert r.t_end - r.t_start == pytest.approx(r.duration)


def test_exec_phases_add_up_to_the_lanes_busy_time_at_the_controller():
    """A reduced-width copy served by the controller through a realtime
    lane and a loopback channel: the EXEC lane's input, dispatch, wait and
    other seconds add up to its busy time, and reach the controller's
    Recorder as gauges beside `busy_s`. The LOAD lane times no phases."""
    engines = {"m0": make_resnet_model("m0", batches=(1, 2), seed=0)}
    loop = EventLoop(RealClock())
    pump = RealtimePump(loop, max_poll=0.002)
    w = Worker("w0", loop, JaxBackend(engines, jax.devices()[:1]),
               resnet_fleet_defs(1, REDUCED["scale"]), post=pump.post)
    controller = Controller(loop, resnet_fleet_defs(1, REDUCED["scale"]),
                            ClockworkScheduler(), default_slo=30.0)
    server = ControllerServer(controller, estimate_net_delay=False)
    link = LoopbackLink(loop)
    server.adopt(link.a)
    host = WorkerHost(w, link.b, telemetry_interval=None)
    host.register()
    for _ in range(6):
        controller.on_request(Request(model_id="m0", arrival=loop.now(),
                                      slo=30.0))
    assert pump.run(until=lambda: len(controller.completed) == 6,
                    timeout=120)
    assert {r.status for r in controller.completed} == {"ok"}
    host.flush_telemetry(sample_first=True)
    w.close()

    ex = w.execs[(0, "EXEC")]
    assert set(ex.phase_s) == {"input", "dispatch", "wait", "other"}
    assert all(ex.phase_s[p] > 0 for p in engine.EXEC_PHASES)
    assert ex.phase_s["other"] >= 0
    assert sum(ex.phase_s.values()) == pytest.approx(ex.total_busy,
                                                     abs=1e-9)
    assert w.execs[(0, "LOAD")].phase_s is None
    rec = controller.recorder
    got = {g.name: g.value for g in rec.iter_gauges()
           if g.name.startswith("worker/")}
    lane = "worker/w0/gpu0/EXEC"
    assert got[f"{lane}/busy_s"] == ex.total_busy
    assert {p: got[f"{lane}/{p}_s"] for p in ex.phase_s} == ex.phase_s
    assert sorted(n for n in got if "/LOAD/" in n) == [
        "worker/w0/gpu0/LOAD/busy_s"]


def test_exec_phases_are_profiler_spans_naming_their_lane(tmp_path):
    """Each phase of an EXEC is a span "exec/<phase>" in the profiler's
    trace, in order, carrying the lane's gpu, the copy and the bucket."""
    from jax.profiler import ProfileData
    m = make_resnet_model("m3", batches=(1, 2), seed=0)
    dev = jax.devices()[0]
    m.load(dev)
    m.run(2, dev)                # compiled and warm before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        m.run(2, dev, gpu=5)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = sorted((e.start_ns, e.name, dict(e.stats))
                   for plane in ProfileData.from_file(str(path)).planes
                   for line in plane.lines for e in line.events
                   if e.name.startswith("exec/"))
    assert [n for _, n, _ in spans] == [
        f"exec/{p}" for p in engine.EXEC_PHASES]
    lane = {"gpu": 5, "copy": "m3", "bucket": 2}
    assert [a for _, _, a in spans] == [{**lane, "layout": "slab"}, lane,
                                        lane]


def test_realtime_backend_needs_a_way_onto_the_loop_thread():
    with pytest.raises(ValueError, match="post"):
        Worker("w0", EventLoop(RealClock()), _SleepBackend(),
               resnet_fleet_defs(1, REDUCED["scale"]))


def test_exec_without_load_is_an_error_result_not_a_silent_load():
    w, pump, engines, results = _worker()
    _serve(w, pump, results, _act(w, ActionType.INFER, "m0"))
    assert [r.status for r in results] == [ResultStatus.ERROR_NOT_LOADED]
    assert engines["m0"].device_params == {}
    with pytest.raises(NotLoadedError):
        engines["m0"].run(1, jax.devices()[0])


def test_unload_frees_the_device_buffers():
    w, pump, engines, results = _worker()
    m = engines["m0"]
    _serve(w, pump, results, _act(w, ActionType.LOAD, "m0"))
    _serve(w, pump, results, _act(w, ActionType.INFER, "m0"))
    assert [r.status for r in results] == [ResultStatus.SUCCESS] * 2
    held = jax.tree.leaves(m.device_params[jax.devices()[0]])
    assert held and not any(a.is_deleted() for a in held)
    _serve(w, pump, results, _act(w, ActionType.UNLOAD, "m0"))
    assert results[-1].status is ResultStatus.SUCCESS
    assert m.device_params == {}
    assert all(a.is_deleted() for a in held)
    assert not w.pagecaches[0].contains("m0")


def test_copies_share_one_executable_per_bucket():
    fleet = resnet_fleet(3, **REDUCED)
    dev = jax.devices()[0]
    for m in fleet.values():
        m.compile([dev])
    programs = {id(m.programs) for m in fleet.values()}
    assert len(programs) == 1
    assert set(fleet["m0"].programs.compile_s) == {
        (dev, b) for b in engine.BUCKETS}
    # each copy has its own seeded weights
    assert not np.array_equal(fleet["m0"].host_params["stem"],
                              fleet["m1"].host_params["stem"])


def test_reduced_resnet_logits_match_float32_reference():
    r = check_logits(make_resnet_model("m0", batches=(1,)),
                     jax.devices()[0])
    assert r["ok"] and r["finite"] and r["shape"] == [1, 1000], r
    assert r["rel_l2"] <= engine.LOGITS_RTOL


def test_compile_cache_honours_the_environment(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", "/elsewhere")
        engine.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == "/elsewhere"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        engine.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == \
            os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_controller_side_fleet_needs_no_jax_backend():
    code = textwrap.dedent("""
        from jax._src import xla_bridge
        from repro.serving.engine import resnet_fleet_defs
        defs = resnet_fleet_defs(8, 1)
        assert not xla_bridge.backends_are_initialized()
        print(sorted(defs), defs["m0"].weights_bytes)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, PYTHONPATH="src"), cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split()[-1] == "51112064"
    assert resnet_fleet_defs(2, REDUCED["scale"])["m1"].weights_bytes == \
        make_resnet_model("m1", batches=(1,)).weights_bytes


def test_each_gpu_id_places_weights_and_runs_on_its_own_device():
    """Four forced host devices (own process: the device count is fixed
    at JAX start-up)."""
    code = textwrap.dedent("""
        import json, os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.core.actions import Action, ActionType
        from repro.core.clock import EventLoop, RealClock, RealtimePump
        from repro.core.worker import Worker
        from repro.serving.engine import (JaxBackend, resnet_fleet,
                                          resnet_fleet_defs)
        devs = jax.devices()
        engines = resnet_fleet(4, scale=16, img=64)
        loop = EventLoop(RealClock())
        pump = RealtimePump(loop)
        w = Worker("w0", loop, JaxBackend(engines, devs),
                   resnet_fleet_defs(4, 16), n_gpus=4, post=pump.post)
        res = []
        w.on_result = res.append
        for g in range(4):
            for kind in (ActionType.LOAD, ActionType.INFER):
                now = loop.now()
                w.receive(Action(type=kind, model_id=f"m{g}",
                                 worker_id="w0", gpu_id=g, earliest=now,
                                 latest=now + 60, expected_duration=1e-3))
                n = len(res) + 1
                pump.run(until=lambda: len(res) == n, timeout=60)
        where = {mid: [sorted({d.id for a in jax.tree.leaves(p)
                               for d in a.devices()})
                       for p in e.device_params.values()]
                 for mid, e in engines.items()}
        ran = sorted({d.id for d, _ in engines["m0"].programs.compile_s})
        print(json.dumps({"n": len(devs), "where": where, "ran": ran,
                          "status": [r.status.value for r in res],
                          "busy": [w.execs[(g, "EXEC")].total_busy > 0
                                   for g in range(4)]}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         env=dict(os.environ, PYTHONPATH="src"), cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n"] == 4
    assert res["where"] == {f"m{g}": [[g]] for g in range(4)}
    assert res["ran"] == [0, 1, 2, 3]
    assert res["status"] == ["SUCCESS"] * 8
    assert res["busy"] == [True] * 4


def test_tcp_demo_with_jax_backend_daemon(tmp_path):
    """serve_distributed.py --backend jax: the daemon compiles, measures
    and registers, then serves with LOADs and on-time responses."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "serve_distributed.py"),
         "--smoke", "--backend", "jax", "--reduced", "--workers", "1",
         "--n-models", "4", "--duration", "2.0", "--slo", "2.0",
         "--loadgen", "--loadgen-processes", "1"],
        env=_env(tmp_path), capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr[-4000:]}"
    assert "SMOKE OK" in proc.stdout
    out = json.loads(proc.stdout[proc.stdout.index("{"):
                                 proc.stdout.rindex("}") + 1])
    assert out["goodput"] > 0 and out["loads"] > 0 and out["execs"] > 0
    assert out["client"]["goodput"] == out["goodput"]
    assert out["worker_returncodes"] == [0]
    assert out["busy_s"]["w0/gpu0/EXEC"] > 0
    assert list(out["infer_profile_s"]) == ["1", "2", "4", "8", "16"]
    assert all(v > 0 for v in out["infer_profile_s"].values())
    assert out["devices"] == {"w0": [{"platform": "cpu", "kind": "cpu"}]}


def test_daemon_and_demo_refuse_impossible_device_requests(tmp_path):
    worker = subprocess.run(
        [sys.executable, "-m", "repro.runtime.worker", "--controller",
         "127.0.0.1:9", "--worker-id", "w0", "--backend", "jax",
         "--gpus", str(len(jax.devices()) + 1)],
        env=_env(tmp_path), capture_output=True, text=True, timeout=120)
    assert worker.returncode == 2
    assert "--gpus" in worker.stderr
    demo = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "serve_distributed.py"),
         "--backend", "jax", "--workers", "2"],
        env=_env(tmp_path), capture_output=True, text=True, timeout=120)
    assert demo.returncode == 2
    assert "one daemon per host" in demo.stderr
