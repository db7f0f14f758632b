"""Unit + property tests for the Clockwork core (scheduler invariants)."""
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.actions import Action, ActionType, Request, ResultStatus
from repro.core.clock import EventLoop, VirtualClock
from repro.core.pagecache import PageCache
from repro.core.predictor import ActionProfiler
from repro.core.scheduler import ClockworkScheduler
from repro.core.worker import ModelDef, SimBackend, Worker
from repro.serving.simulator import build_cluster, table1_modeldef
from repro.serving.workload import ClosedLoopClient, OpenLoopClient
from repro.telemetry import prediction_error_report
from repro.telemetry.events import ActionRecord


# ------------------------------------------------------------- PageCache

@given(st.lists(st.tuples(st.integers(1, 50), st.booleans()), min_size=1,
                max_size=60))
@settings(max_examples=100, deadline=None)
def test_pagecache_accounting_invariant(ops):
    """free + sum(resident) == total, always; alloc never over-commits."""
    pc = PageCache(64 * pc_page(), pc_page())
    live = {}
    for i, (pages, do_free) in enumerate(ops):
        mid = f"m{i % 7}"
        if do_free and mid in live:
            pc.free(mid)
            live.pop(mid)
        elif mid not in live:
            ok = pc.alloc(mid, pages)
            assert ok == (pages <= 64 - sum(live.values()))
            if ok:
                live[mid] = pages
        assert pc.free_pages == pc.total_pages - sum(live.values())
        assert pc.free_pages >= 0
        assert set(pc.resident) == set(live)


def pc_page():
    return 16 * 1024 * 1024


def test_pagecache_lru_order():
    pc = PageCache(10 * pc_page(), pc_page())
    for m in ("a", "b", "c"):
        pc.alloc(m, 2)
    pc.touch("a")
    assert pc.lru_candidate() == "b"
    assert pc.lru_candidate(exclude=("b",)) == "c"


# ------------------------------------------------------------- predictor

def test_profiler_rolling_max_prediction():
    p = ActionProfiler(window=5)
    p.seed("INFER", "m", 1, 0.010)
    assert p.estimate("INFER", "m", 1) == pytest.approx(0.010)
    records = []

    def observe(d):
        # as the controller records each result: the estimate beside the
        # measured duration
        records.append(ActionRecord(
            len(records), "INFER", "m", "w0", 0, 1, "SUCCESS", 0.0, 0.0,
            d, d, predicted=p.estimate("INFER", "m", 1)))
        p.observe("INFER", "m", 1, d)

    for d in (0.002, 0.003, 0.001):
        observe(d)
    assert p.estimate("INFER", "m", 1) == pytest.approx(0.003)
    # window slides: old max falls out
    for d in (0.001,) * 5:
        observe(d)
    assert p.estimate("INFER", "m", 1) == pytest.approx(0.001)
    # every prediction's error is in the records
    rep = prediction_error_report(records)
    assert rep["over"]["n"] + rep["under"]["n"] == 8


# ------------------------------------------------------------- worker

def _one_worker_loop():
    loop = EventLoop(VirtualClock())
    models = {"m": ModelDef("m", int(100e6),
                            {("INFER", 1): 0.003, ("INFER", 2): 0.004})}
    w = Worker("w0", loop, SimBackend(noise=0.0), models, n_gpus=1)
    results = []
    w.on_result = results.append
    return loop, w, results


def test_worker_rejects_late_actions():
    loop, w, results = _one_worker_loop()
    w.pagecaches[0].alloc("m", 7)
    # latest already passed at delivery
    a = Action(type=ActionType.INFER, model_id="m", worker_id="w0", gpu_id=0,
               earliest=0.0, latest=-1.0, expected_duration=0.003)
    w.receive(a)
    loop.run_until(1.0)
    assert results[0].status is ResultStatus.REJECTED_LATE


def test_worker_waits_for_earliest():
    loop, w, results = _one_worker_loop()
    w.pagecaches[0].alloc("m", 7)
    a = Action(type=ActionType.INFER, model_id="m", worker_id="w0", gpu_id=0,
               earliest=0.5, latest=1.0, expected_duration=0.003)
    w.receive(a)
    loop.run_until(2.0)
    assert results[0].status is ResultStatus.SUCCESS
    assert results[0].t_start >= 0.5


def test_worker_infer_requires_residency():
    loop, w, results = _one_worker_loop()
    a = Action(type=ActionType.INFER, model_id="m", worker_id="w0", gpu_id=0,
               earliest=0.0, latest=1.0, expected_duration=0.003)
    w.receive(a)
    loop.run_until(1.0)
    assert results[0].status is ResultStatus.ERROR_NOT_LOADED


def test_worker_load_then_infer_and_one_at_a_time():
    loop, w, results = _one_worker_loop()
    load = Action(type=ActionType.LOAD, model_id="m", worker_id="w0",
                  gpu_id=0, earliest=0.0, latest=1.0,
                  expected_duration=0.009)
    w.receive(load)
    for _ in range(3):
        w.receive(Action(type=ActionType.INFER, model_id="m",
                         worker_id="w0", gpu_id=0, earliest=0.02,
                         latest=10.0, expected_duration=0.003))
    loop.run_until(5.0)
    ok = [r for r in results if r.status is ResultStatus.SUCCESS]
    assert len(ok) == 4
    infers = [r for r in ok if r.action_type is ActionType.INFER]
    # serial EXEC: no overlap between inference executions
    spans = sorted((r.t_start, r.t_end) for r in infers)
    for (s1, e1), (s2, e2) in zip(spans, spans[1:]):
        assert s2 >= e1 - 1e-9


# --------------------------------------------------- end-to-end invariants

@given(slo_ms=st.sampled_from([10, 25, 50, 100, 250]),
       n_models=st.integers(1, 6), conc=st.integers(1, 8),
       seed=st.integers(0, 5))
@settings(max_examples=12, deadline=None)
def test_clockwork_never_violates_slo_property(slo_ms, n_models, conc, seed):
    """Property (paper's headline): completed requests meet their SLO; the
    only failure mode is *proactive rejection*, never a late response —
    modulo the action-delay margin on external factors (C3)."""
    models = {f"m{i}": table1_modeldef(f"m{i}") for i in range(n_models)}
    cl = build_cluster(models, scheduler=ClockworkScheduler(), seed=seed)
    clients = [ClosedLoopClient(cl.loop, cl.submit, mid, slo_ms / 1e3,
                                concurrency=conc) for mid in models]
    cl.attach_clients(clients)
    s = cl.run(3.0)
    assert s["timeout"] <= 0.01 * max(s["goodput"], 1)
    for r in cl.controller.completed:
        if r.status == "ok":
            assert r.completion <= r.deadline + 1e-6


def test_failed_worker_detected_and_traffic_rerouted():
    models = {"m0": table1_modeldef("m0")}
    cl = build_cluster(models, n_workers=2, scheduler=ClockworkScheduler(),
                       preload=["m0", "m0"])
    # preload m0 on both workers' gpu0 (round-robin placed)
    client = ClosedLoopClient(cl.loop, cl.submit, "m0", 0.100, concurrency=8)
    cl.attach_clients([client])
    cl.controller.start_heartbeats()
    cl.loop.schedule(1.0, cl.workers[0].fail)
    s = cl.run(4.0)
    assert cl.controller.stats["dead_workers"] == 1
    assert "w0" not in cl.controller.workers
    # goodput continues after the failure window
    late = [r for r in cl.controller.completed
            if r.status == "ok" and r.arrival > 2.5]
    assert len(late) > 50


def test_elastic_add_worker_increases_capacity():
    # saturating load: one worker is the bottleneck, so elastic scale-out
    # must raise goodput
    models = {f"m{i}": table1_modeldef(f"m{i}") for i in range(8)}

    def run(two_workers: bool):
        cl = build_cluster(models, n_workers=1,
                           scheduler=ClockworkScheduler())
        clients = [ClosedLoopClient(cl.loop, cl.submit, mid, 0.030,
                                    concurrency=16) for mid in models]
        cl.attach_clients(clients)
        if two_workers:
            def add():
                from repro.core.worker import SimBackend, Worker
                w = Worker("w_new", cl.loop, SimBackend(noise=0.0),
                           models, n_gpus=1)
                cl.workers.append(w)
                cl.controller.add_worker(w)
            cl.loop.schedule(0.5, add)
        s = cl.run(3.0)
        return s["goodput"]

    assert run(True) > run(False) * 1.3
