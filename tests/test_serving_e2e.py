"""End-to-end serving behaviour: Clockwork vs baselines, isolation, cold
starts, predictability (system-level integration tests)."""
import pytest

from repro.core.baselines import ClipperScheduler, InfaasScheduler
from repro.core.scheduler import ClockworkScheduler
from repro.serving.simulator import TimeSeries, build_cluster, table1_modeldef
from repro.serving.workload import (ClosedLoopClient, OpenLoopClient,
                                    VariableRateClient, maf_like_rates)


def _fig5_run(sched_cls, slo, dur=10.0, n_models=8, conc=8):
    models = {f"m{i}": table1_modeldef(f"m{i}") for i in range(n_models)}
    cl = build_cluster(models, scheduler=sched_cls())
    clients = [ClosedLoopClient(cl.loop, cl.submit, mid, slo,
                                concurrency=conc) for mid in models]
    cl.attach_clients(clients)
    return cl.run(dur), cl


def test_clockwork_beats_baselines_at_tight_slo():
    s_cw, _ = _fig5_run(ClockworkScheduler, 0.025)
    s_cl, _ = _fig5_run(ClipperScheduler, 0.025)
    s_in, _ = _fig5_run(InfaasScheduler, 0.025)
    # Clockwork: zero timeouts (never responds late)
    assert s_cw["timeout"] == 0
    assert s_cw["goodput"] > 0
    # baselines either time out requests or underperform
    assert s_cl["timeout"] + s_in["timeout"] > 0 or \
        s_cw["goodput"] >= 0.8 * max(s_cl["goodput"], s_in["goodput"])


def test_clockwork_tail_latency_within_slo_under_overload():
    s, cl = _fig5_run(ClockworkScheduler, 0.100, n_models=10, conc=16)
    assert s["timeout"] == 0
    assert s["p99"] <= 0.100 + 1e-6


def test_cold_start_scale_up_shifts_bottleneck():
    """Fig-6 miniature: more active models than fit in device memory —
    the system keeps serving via LOAD/UNLOAD churn (PCIe-bound regime)."""
    n = 60
    models = {f"m{i}": table1_modeldef(f"m{i}") for i in range(n)}
    # small device memory: only ~20 models fit (102.2MB each -> 7 pages)
    cl = build_cluster(models, device_memory=2.2e9,
                       scheduler=ClockworkScheduler())
    clients = [OpenLoopClient(cl.loop, cl.submit, mid, 0.200, rate=8.0,
                              stop=6.0, seed=i)
               for i, mid in enumerate(models)]
    cl.attach_clients(clients)
    s = cl.run(7.0)
    assert s["goodput"] > 0
    loads = [r for r in cl.controller.results_log
             if r.action_type.value == "LOAD" and
             r.status.value == "SUCCESS"]
    # eviction churn: more loads than fit simultaneously
    assert len(loads) > 25
    assert s["timeout"] == 0


def test_isolation_ls_vs_batch_clients():
    """Fig-7-right miniature: latency-sensitive clients keep their goodput
    when saturating batch clients share the cluster."""
    models = {f"ls{i}": table1_modeldef(f"ls{i}") for i in range(2)}
    models.update({f"bc{i}": table1_modeldef(f"bc{i}") for i in range(4)})

    def run(with_bc):
        cl = build_cluster(models, n_workers=2,
                           scheduler=ClockworkScheduler())
        ls = [OpenLoopClient(cl.loop, cl.submit, f"ls{i}", 0.050,
                             rate=100.0, stop=5.0, seed=i)
              for i in range(2)]
        clients = list(ls)
        if with_bc:
            clients += [ClosedLoopClient(cl.loop, cl.submit, f"bc{i}", 10.0,
                                         concurrency=16) for i in range(4)]
        cl.attach_clients(clients)
        cl.run(5.0)
        ls_ok = sum(1 for r in cl.controller.completed
                    if r.model_id.startswith("ls") and r.status == "ok")
        ls_all = sum(1 for r in cl.controller.completed
                     if r.model_id.startswith("ls"))
        bc_ok = sum(1 for r in cl.controller.completed
                    if r.model_id.startswith("bc") and r.status == "ok")
        return ls_ok / max(ls_all, 1), bc_ok

    sat_alone, _ = run(False)
    sat_shared, bc_goodput = run(True)
    assert sat_shared > 0.85 * sat_alone     # LS isolation holds
    assert bc_goodput > 0                    # BC still make progress


def test_maf_like_trace_replay_meets_slo():
    rates = maf_like_rates(30, total_rate=400.0, duration=6.0, seed=1)
    models = {mid: table1_modeldef(mid) for mid in rates}
    cl = build_cluster(models, n_workers=2, scheduler=ClockworkScheduler())
    clients = [VariableRateClient(cl.loop, cl.submit, mid, 0.100, fn,
                                  stop=6.0, seed=i, max_rate=500.0)
               for i, (mid, fn) in enumerate(rates.items())]
    cl.attach_clients(clients)
    ts = TimeSeries(cl, dt=1.0)
    s = cl.run(7.0)
    assert s["timeout"] == 0
    assert s["goodput"] > 0
    assert len(ts.samples) >= 6


def test_prediction_errors_are_small():
    models = {"m0": table1_modeldef("m0")}
    cl = build_cluster(models, scheduler=ClockworkScheduler(), noise=0.0003)
    client = ClosedLoopClient(cl.loop, cl.submit, "m0", 0.100, concurrency=8)
    cl.attach_clients([client])
    cl.run(5.0)
    errs = sorted(abs(a.error) for a in cl.controller.recorder.iter_actions()
                  if a.status == "SUCCESS" and a.predicted is not None)
    assert errs, "no predictions recorded"
    p99 = errs[int(0.99 * (len(errs) - 1))]
    assert p99 < 0.002  # paper Fig 9: ~250us at v100 scale
