"""Telemetry subsystem tests: ActionProfiler estimates, ProfileStore
round-trip, Recorder spans/records, the missed-result failure-detector fix,
and the e2e acceptance path (profiler CLI store -> serving run with zero
warmup re-measurements)."""
import json
import math

import pytest

from repro.core.actions import Action, ActionType, Request
from repro.core.clock import (EventLoop, RealClock, RealtimePump,
                              VirtualClock)
from repro.core.controller import Controller
from repro.core.predictor import ActionProfiler
from repro.core.scheduler import ClockworkScheduler
from repro.core.worker import ModelDef, SimBackend, Worker
from repro.serving.simulator import build_cluster, table1_modeldef
from repro.serving.workload import ClosedLoopClient
from repro.telemetry import (LatencyProfile, ProfileStore, Recorder,
                             latency_breakdown, prediction_error_report)
from repro.telemetry.events import ActionRecord


# ---------------------------------------------------------- ActionProfiler

def test_profiler_window_max_estimate():
    p = ActionProfiler(window=5)
    for d in (0.002, 0.003, 0.001):
        p.observe("INFER", "m", 1, d)
    assert p.estimate("INFER", "m", 1) == pytest.approx(0.003)
    # window slides: the old max falls out
    for d in (0.001,) * 5:
        p.observe("INFER", "m", 1, d)
    assert p.estimate("INFER", "m", 1) == pytest.approx(0.001)


def test_profiler_seed_fallback_until_first_observation():
    p = ActionProfiler()
    p.seed("INFER", "m", 1, 0.010)
    assert p.estimate("INFER", "m", 1) == pytest.approx(0.010)
    p.observe("INFER", "m", 1, 0.002)
    assert p.estimate("INFER", "m", 1) == pytest.approx(0.002)
    assert p.estimate("INFER", "m", 2) is None
    assert p.estimate_or("INFER", "m", 2, 0.007) == pytest.approx(0.007)


def test_profiler_over_under_error_accounting():
    """Each result's record holds the profiler's estimate beside the
    measured duration; prediction_error_report splits their errors into
    over- and under-predictions."""
    p = ActionProfiler()
    p.seed("INFER", "m", 1, 0.010)
    records = []
    for d in (0.004,    # pred 0.010 -> over by 0.006
              0.003,    # pred 0.004 -> over by 0.001
              0.009):   # pred 0.004 -> under by 0.005
        records.append(ActionRecord(
            len(records), "INFER", "m", "w0", 0, 1, "SUCCESS", 0.0, 0.0,
            d, d, predicted=p.estimate("INFER", "m", 1)))
        p.observe("INFER", "m", 1, d)
    assert [a.error for a in records] == pytest.approx(
        [0.006, 0.001, -0.005])
    rep = prediction_error_report(records)
    assert (rep["over"]["n"], rep["under"]["n"]) == (2, 1)
    assert rep["over"]["max_us"] == pytest.approx(6000)
    assert rep["under"]["max_us"] == pytest.approx(5000)


def test_profiler_history_snapshot():
    p = ActionProfiler(window=3)
    for d in (0.1, 0.2, 0.3, 0.4):
        p.observe("INFER", "m", 1, d)
    assert p.history() == {("INFER", "m", 1): [0.2, 0.3, 0.4]}


# ------------------------------------------------------------ ProfileStore

def test_profile_store_roundtrip_identical_estimates(tmp_path):
    src = ActionProfiler()
    for d in (0.002, 0.005, 0.003):
        src.observe("INFER", "m0", 1, d)
    for d in (0.011, 0.010):
        src.observe("LOAD", "m0", 1, d)
    store = ProfileStore()
    store.update_from_profiler(src)
    path = store.save(str(tmp_path / "profiles.json"))

    loaded = ProfileStore.load(path)
    dst = ActionProfiler()
    loaded.seed_profiler(dst)
    # seeded estimates equal the source's window-max estimates
    assert dst.estimate("INFER", "m0", 1) == \
        pytest.approx(src.estimate("INFER", "m0", 1))
    assert dst.estimate("LOAD", "m0", 1) == \
        pytest.approx(src.estimate("LOAD", "m0", 1))
    assert loaded.seed_dict() == store.seed_dict()


def test_profile_store_merge_and_version_check(tmp_path):
    store = ProfileStore()
    store.update("INFER", "m", 1, [0.002, 0.004])
    store.update("INFER", "m", 1, [0.003])
    p = store.get("INFER", "m", 1)
    assert p.count == 3
    assert p.max_s == pytest.approx(0.004)

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 99, "entries": []}))
    with pytest.raises(ValueError, match="version"):
        ProfileStore.load(str(bad))


def test_latency_profile_from_durations():
    p = LatencyProfile.from_durations([0.001, 0.002, 0.003, 0.010])
    assert p.count == 4
    assert p.median_s == pytest.approx(0.002)
    assert p.max_s == pytest.approx(0.010)
    assert p.estimate == p.max_s


# ------------------------------------------------- Recorder (via simulator)

def _loaded_run(dur=2.0, **kw):
    models = {"m0": table1_modeldef("m0")}
    cl = build_cluster(models, scheduler=ClockworkScheduler(), **kw)
    client = ClosedLoopClient(cl.loop, cl.submit, "m0", 0.100, concurrency=4)
    cl.attach_clients([client])
    cl.run(dur)
    return cl


def test_recorder_spans_have_full_breakdown():
    cl = _loaded_run()
    spans = [s for s in cl.recorder.iter_spans() if s.status == "ok"]
    assert spans
    for s in spans:
        assert s.response >= s.dispatched >= s.queued >= s.arrival
        assert s.exec_end >= s.exec_start >= s.dispatched
        assert s.worker_id == "w0" and s.batch_size >= 1 and s.attempts >= 1
    # the first request of a cold model is attributed a LOAD phase
    assert any(s.cold_start and s.load_end >= s.load_start for s in spans)
    bd = latency_breakdown(cl.recorder.iter_spans())
    assert bd["total"]["count"] == len(spans)
    assert bd["exec"]["median"] > 0
    assert bd["statuses"].get("ok", 0) == len(spans)


def test_recorder_action_records_feed_prediction_error_report():
    cl = _loaded_run()
    recs = list(cl.recorder.iter_actions())
    assert recs
    succ = [a for a in recs if a.status == "SUCCESS" and
            a.predicted is not None]
    assert succ, "no predicted-vs-actual records"
    rep = prediction_error_report(recs)
    assert rep["over"]["n"] + rep["under"]["n"] == \
        len([a for a in succ if a.actual > 0])
    # paper Fig 9 scale: errors are micro-second scale under low noise
    assert rep["over"]["p99_us"] < 2000
    # worker-side stamps made it through
    assert all(a.t_start >= a.t_received >= 0 for a in succ)


def test_recorder_jsonl_export(tmp_path):
    cl = _loaded_run(dur=1.0)
    path = tmp_path / "telemetry.jsonl"
    n = cl.recorder.export_jsonl(str(path))
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert len(lines) == n > 0
    kinds = {l["kind"] for l in lines}
    assert kinds == {"span", "action", "gauge"}


def test_recorder_ring_buffer_bounds_memory():
    rec = Recorder(capacity=16)
    cl = _loaded_run(dur=1.0, recorder=rec)
    assert cl.recorder is rec
    assert len(rec.spans) <= 16 and len(rec.actions) <= 16
    assert rec.dropped_spans > 0 or rec.dropped_actions > 0


def test_simulator_runs_from_profile_store():
    # a store written by one run seeds the next cluster's profiler
    cl1 = _loaded_run()
    store = cl1.export_profile_store()
    assert len(store) > 0
    models = {"m0": table1_modeldef("m0")}
    cl2 = build_cluster(models, scheduler=ClockworkScheduler(),
                        profile_store=store)
    assert cl2.controller.profiler.estimate("INFER", "m0", 1) is not None
    client = ClosedLoopClient(cl2.loop, cl2.submit, "m0", 0.100,
                              concurrency=4)
    cl2.attach_clients([client])
    s = cl2.run(1.0)
    assert s["goodput"] > 0 and s["timeout"] == 0


# ------------------------------------------- missed-result failure detector

def _controller_with_worker(threshold=2):
    loop = EventLoop(VirtualClock())
    models = {"m": ModelDef("m", int(100e6), {("INFER", 1): 0.003})}
    w = Worker("w0", loop, SimBackend(noise=0.0), models, n_gpus=1)
    c = Controller(loop, models, ClockworkScheduler(),
                   missed_result_threshold=threshold)
    c.add_worker(w)
    w.pagecaches[0].alloc("m", 7)
    c.workers["w0"].gpus[0].pagecache.alloc("m", 7)
    return loop, w, c


def _infer_action(now):
    return Action(type=ActionType.INFER, model_id="m", worker_id="w0",
                  gpu_id=0, earliest=now, latest=now + 1.0,
                  expected_duration=0.003)


def test_single_missed_result_does_not_kill_worker():
    loop, w, c = _controller_with_worker(threshold=2)
    w.receive = lambda a: None          # swallow the action: no result
    c.send_action(_infer_action(loop.now()))
    loop.run_until(5.0)
    assert "w0" in c.workers            # survived one late result
    assert c.workers["w0"].missed_results == 1
    assert c.stats["dead_workers"] == 0


def test_missed_result_threshold_kills_worker():
    loop, w, c = _controller_with_worker(threshold=2)
    w.receive = lambda a: None
    c.send_action(_infer_action(loop.now()))
    c.send_action(_infer_action(loop.now()))
    loop.run_until(5.0)
    assert "w0" not in c.workers
    assert c.stats["dead_workers"] == 1


def test_successful_result_resets_missed_counter():
    loop, w, c = _controller_with_worker(threshold=2)
    w.receive = lambda a: None
    c.send_action(_infer_action(loop.now()))
    loop.run_until(5.0)
    assert c.workers["w0"].missed_results == 1
    del w.receive                       # restore the real method
    c.send_action(_infer_action(loop.now()))
    loop.run_until(10.0)
    assert "w0" in c.workers
    assert c.workers["w0"].missed_results == 0
    # a later lone miss still doesn't kill it: the counter restarted
    w.receive = lambda a: None
    c.send_action(_infer_action(loop.now()))
    loop.run_until(15.0)
    assert "w0" in c.workers


# --------------------------------------------- e2e: offline profile -> serve

def test_offline_profile_store_enables_zero_warmup_serving(tmp_path):
    """Acceptance: profiler CLI writes a store; a second serving run seeded
    from it performs zero warmup re-measurements and still serves."""
    import jax
    from repro.serving.engine import (JaxBackend, make_resnet_model,
                                      seed_engines)
    from repro.telemetry import profiler as profcli

    mk = lambda: make_resnet_model("rt", scale=8, img=32, batches=(1,))
    store_path = str(tmp_path / "profiles.json")

    # --- run 1: offline profiling via the CLI plumbing
    store = profcli.build_store([("rt", mk)], reps=1)
    assert {k for k, _ in store.items()} == {("INFER", "rt", 1),
                                             ("LOAD", "rt", 1)}
    store.save(store_path)

    # --- run 2: fresh process state, seeded from the store
    store2 = ProfileStore.load(store_path)
    jm = mk()
    assert jm.warmup_count == 0
    dev = jax.devices()[0]
    profiles = seed_engines({"rt": jm}, dev, store2)
    models = {"rt": jm.modeldef()}
    jm.compile([dev])   # AOT compile (untimed) — not a re-measurement
    assert jm.warmup_count == 0, "modeldef() re-measured despite store"
    assert profiles[("INFER", "rt", 1)] == \
        pytest.approx(store2.get("INFER", "rt", 1).estimate)

    loop = EventLoop(RealClock())
    pump = RealtimePump(loop)
    w = Worker("w0", loop, JaxBackend({"rt": jm}, [dev]), models,
               n_gpus=1, post=pump.post)
    c = Controller(loop, models, ClockworkScheduler(), action_delay=1e-4)
    c.add_worker(w, profiles)
    done = []
    c.on_response = done.append
    for _ in range(4):
        c.on_request(Request(model_id="rt", arrival=loop.now(), slo=10.0))
        pump.run(timeout=0.05)
    pump.run(timeout=3.0)
    ok = [r for r in done if r.status == "ok"]
    assert len(ok) >= 3, [r.status for r in done]
    assert jm.warmup_count == 0, "serving run re-measured the model"
    # live telemetry flowed: spans closed with exec stamps
    spans = [s for s in c.recorder.iter_spans() if s.status == "ok"]
    assert spans and all(not math.isnan(s.exec_end) for s in spans)


def test_update_store_never_recycles_seeded_estimates(tmp_path):
    """A store covering INFER but missing LOAD forces one load measurement;
    the INFER estimates it seeded must still not be folded back as if they
    were fresh samples."""
    import jax
    from repro.serving.engine import make_resnet_model, seed_engines, \
        update_store

    mk = lambda: make_resnet_model("rt", scale=8, img=32, batches=(1,))
    store = ProfileStore()
    store.update("INFER", "rt", 1, [0.004])   # no ("LOAD", "rt", 1) entry

    jm = mk()
    seed_engines({"rt": jm}, jax.devices()[0], store)
    assert jm.warmup_count > 0                # it had to measure LOAD
    fresh = jm.fresh_profiles()
    assert ("LOAD", "rt", 1) in fresh
    assert ("INFER", "rt", 1) not in fresh    # seeded, not measured

    before = store.get("INFER", "rt", 1)
    update_store({"rt": jm}, store)
    after = store.get("INFER", "rt", 1)
    assert after.count == before.count == 1   # no echo folded back
    assert store.get("LOAD", "rt", 1) is not None


def test_profiler_cli_main_writes_store(tmp_path):
    from repro.telemetry.profiler import main
    out = str(tmp_path / "cli_profiles.json")
    rc = main(["--quick", "--reps", "1", "--batches", "1", "--out", out])
    assert rc == 0
    store = ProfileStore.load(out)
    assert store.get("INFER", "resnet_tiny", 1) is not None
    assert store.get("LOAD", "resnet_tiny", 1) is not None


# ------------------------------------------------------ Recorder streaming

def _stream_some(rec, n):
    for i in range(n):
        rec.record_gauge("g", float(i), float(i) * 2.0)


def test_stream_to_writes_records_continuously(tmp_path):
    path = str(tmp_path / "stream.jsonl")
    rec = Recorder()
    rec.stream_to(path)
    req = Request(model_id="m", arrival=0.0, slo=0.1)
    rec.span_open(req, queued=0.001)
    req.status = "ok"
    rec.span_close(req, 0.02)
    _stream_some(rec, 3)
    rec.close_stream()
    lines = [json.loads(l) for l in open(path)]
    kinds = [l["kind"] for l in lines]
    assert kinds == ["span", "gauge", "gauge", "gauge"]
    assert rec.stream_lines == 4
    # the ring buffers are unaffected by streaming
    assert len(list(rec.iter_spans())) == 1


def test_stream_to_rotates_and_preserves_every_line(tmp_path):
    import os
    path = str(tmp_path / "rot.jsonl")
    rec = Recorder()
    rec.stream_to(path, rotate_bytes=2_000, rotate_keep=3)
    n = 500
    _stream_some(rec, n)
    rec.close_stream()
    assert rec.stream_rotations > 0
    files = sorted(p for p in os.listdir(tmp_path) if p.startswith("rot"))
    assert len(files) > 1                       # rotation happened
    assert len(files) <= 4                      # live + rotate_keep
    total = sum(1 for p in files
                for _ in open(os.path.join(tmp_path, p)))
    if len(files) < 4:
        assert total == n                       # nothing lost pre-evict
    # every surviving file holds valid JSONL gauge lines
    for p in files:
        for l in open(os.path.join(tmp_path, p)):
            assert json.loads(l)["kind"] == "gauge"
    # live file stays under the rotation bound (+ one record of slack)
    assert os.path.getsize(path) < 2_000 + 200


def test_stream_to_drops_oldest_beyond_keep(tmp_path):
    import os
    path = str(tmp_path / "keep.jsonl")
    rec = Recorder()
    rec.stream_to(path, rotate_bytes=500, rotate_keep=2)
    _stream_some(rec, 400)
    rec.close_stream()
    files = sorted(p for p in os.listdir(tmp_path) if p.startswith("keep"))
    assert set(files) <= {"keep.jsonl", "keep.jsonl.1", "keep.jsonl.2"}
    assert rec.stream_rotations > 2             # old generations evicted


def test_streamed_jsonl_reloads_into_typed_records(tmp_path):
    """load_jsonl is the offline-analysis inverse of stream_to: spans,
    actions, and gauges come back as typed records that feed the same
    report functions."""
    from repro.telemetry import load_jsonl
    path = str(tmp_path / "reload.jsonl")
    rec = Recorder()
    rec.stream_to(path)
    req = Request(model_id="m", arrival=0.5, slo=0.1)
    rec.span_open(req, queued=0.501)
    req.status = "ok"
    span = rec.span_close(req, 0.52)
    rec.record_gauge("g", 1.0, 2.5)
    rec.close_stream()
    got = load_jsonl(path)
    assert len(got["spans"]) == 1 and len(got["gauges"]) == 1
    s = got["spans"][0]
    assert s == span                      # NaN-free fields round-trip...
    assert math.isnan(s.dispatched)       # ...and null stamps back to NaN
    assert got["gauges"][0].value == 2.5
    # reloaded records feed the standard reports unchanged
    assert latency_breakdown(got["spans"])["statuses"] == {"ok": 1}
