"""Why the controller refuses requests and drops workers: each refusal
carries `estimate` or `late` (in `stats`, in the closed span and in the
`controller.refused.<cause>` gauge), each dropped worker the detector that
gave up on it (in `stats` and in a logged warning)."""
import logging

import pytest

from repro.core.controller import FAILURE_CAUSES, REFUSAL_CAUSES
from repro.core.scheduler import ClockworkScheduler
from repro.serving.simulator import build_cluster, table1_modeldef
from repro.serving.workload import ClosedLoopClient, OpenLoopClient

STRETCH_AT = 1.0        # virtual seconds
STRETCHED_S = 0.115     # one host freeze, past the 100-ms SLO


def _fleet(n):
    return {f"m{i}": table1_modeldef(f"m{i}") for i in range(n)}


def _refusals(cl):
    spans = [s for s in cl.controller.recorder.iter_spans()
             if s.status == "rejected"]
    for c in REFUSAL_CAUSES:
        gauge = [g.value for g in cl.controller.recorder.iter_gauges(
            f"controller.refused.{c}")]
        # the running count starts at 0 and rises by one per refusal
        assert gauge == list(range(len(gauge)))
        assert gauge[-1] == cl.controller.stats[f"rejected_{c}"] \
            == sum(s.cause == c for s in spans)
    return spans


def test_one_stretched_exec_refuses_its_copy_by_estimate():
    """PERF.md fault 2's witness: one EXEC of m0 at batch 1 stretched past
    the SLO leaves m0's batch-1 estimate above the SLO, and every later
    request of m0 is refused for it; the other copies keep being
    served."""
    models = _fleet(3)
    cl = build_cluster(models, scheduler=ClockworkScheduler(),
                       preload=list(models))
    backend = cl.workers[0].backend
    exec_duration = backend.exec_duration
    stretched = []

    def freeze_once(model, action):
        d = exec_duration(model, action)
        if model.model_id == "m0" and not stretched \
                and cl.loop.now() >= STRETCH_AT:
            stretched.append(action.batch_size)
            return STRETCHED_S
        return d

    backend.exec_duration = freeze_once
    cl.attach_clients([OpenLoopClient(cl.loop, cl.submit, mid, 0.100,
                                      rate=20.0, stop=3.0, seed=i)
                       for i, mid in enumerate(models)])
    cl.run(3.5)
    assert stretched == [1]
    assert cl.controller.profiler.estimate("INFER", "m0", 1) == STRETCHED_S
    refused = _refusals(cl)
    after = [s for s in refused if s.arrival > STRETCH_AT + STRETCHED_S]
    assert after and all(s.model_id == "m0" and s.cause == "estimate"
                         for s in after)
    assert cl.controller.stats["rejected_estimate"] == len(after)
    served = {s.model_id for s in cl.controller.recorder.iter_spans()
              if s.status == "ok" and s.arrival > 2.0}
    assert served == {"m1", "m2"}


def test_a_queue_that_waited_too_long_is_refused_as_late():
    """16 closed-loop users of one copy under a 25-ms SLO: requests queue
    behind batches of up to 17 ms, and those left waiting too long are
    refused as late while the copy keeps serving."""
    cl = build_cluster(_fleet(1), scheduler=ClockworkScheduler(),
                       preload=["m0"])
    cl.attach_clients([ClosedLoopClient(cl.loop, cl.submit, "m0", 0.025,
                                        concurrency=16)])
    s = cl.run(1.0)
    refused = _refusals(cl)
    assert refused and {s.cause for s in refused} == {"late"}
    assert s["goodput"] > 0 and s["rejected_estimate"] == 0
    assert cl.controller.profiler.estimate("INFER", "m0", 1) < 0.025


def _silent_worker():
    """Actions vanish in the worker: no result ever comes back."""
    cl = build_cluster(_fleet(1), scheduler=ClockworkScheduler(),
                       preload=["m0"])
    cl.workers[0].receive = lambda action: None
    cl.attach_clients([ClosedLoopClient(cl.loop, cl.submit, "m0", 0.100,
                                        concurrency=4)])
    return cl


def _unanswered_heartbeat():
    """An idle worker stops: nothing is outstanding, only the heartbeat
    can tell."""
    cl = build_cluster(_fleet(1), scheduler=ClockworkScheduler())
    cl.controller.start_heartbeats()
    cl.loop.schedule(0.5, cl.workers[0].fail)
    return cl


def _yanked_cable():
    cl = build_cluster(_fleet(1), scheduler=ClockworkScheduler(),
                       transport="loopback", preload=["m0"])
    cl.loop.schedule(0.5, cl.runtime.links[0].close)
    return cl


@pytest.mark.parametrize("cause, make", [
    ("heartbeat", _unanswered_heartbeat),
    ("missed_results", _silent_worker),
    ("disconnected", _yanked_cable),
], ids=FAILURE_CAUSES)
def test_each_failure_detector_names_its_cause(cause, make, caplog):
    cl = make()
    with caplog.at_level(logging.WARNING, logger="repro.core"):
        cl.run(3.0)
    assert "w0" not in cl.controller.workers
    stats = cl.controller.stats
    assert stats["dead_workers"] == 1
    assert {c: stats[f"dead_{c}"] for c in FAILURE_CAUSES} == {
        c: int(c == cause) for c in FAILURE_CAUSES}
    assert [r.getMessage() for r in caplog.records] == [
        f"worker w0 dropped: {cause}"]


def test_a_worker_the_controller_told_to_leave_is_not_dropped(caplog):
    """After the controller's GOODBYE a daemon stops answering heartbeats
    while it winds down; its mirror is retired at once, so no detector
    counts it as failed."""
    cl = build_cluster(_fleet(1), scheduler=ClockworkScheduler(),
                       transport="loopback", preload=["m0"])
    cl.controller.start_heartbeats()
    cl.run(1.2)
    cl.runtime.server.shutdown()
    for w in cl.workers:
        w.fail()                    # its loop has stopped: no more PONGs
    with caplog.at_level(logging.WARNING, logger="repro.core"):
        cl.loop.run_until(cl.loop.now() + 3.0)
    assert "w0" not in cl.controller.workers
    assert cl.controller.stats["dead_workers"] == 0
    assert not caplog.records
