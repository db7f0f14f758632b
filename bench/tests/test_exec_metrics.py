"""The readers of the EXEC lanes' input share and of the controller's
refusals by estimate: on recorded gauges, on a run whose program keeps
neither (they report nothing), and through a whole traced CPU run."""
import pytest

import run as bench_run
from record import Run

CELL = "rn50-x15-1chip.poisson80"
LANE = "worker/w0/gpu{}/EXEC"


def _run(gauges, attempted=100, chips=1):
    return Run(seconds=10.0, chips=chips, window=(5.0, 15.0),
               client={"attempted": attempted}, actions=[], gauges=gauges,
               model=None, size={}, peak=None)


def test_exec_input_share_is_its_rise_over_the_busy_rise():
    read = bench_run.reader("exec_input_pct")
    gauges = {}
    for g, share in ((0, 0.5), (1, 0.25)):
        gauges[LANE.format(g) + "/busy_s"] = [(t, 2.0 * t) for t in
                                              (4, 6, 10, 14, 16)]
        gauges[LANE.format(g) + "/input_s"] = [(t, share * 2.0 * t + 1.0)
                                               for t in (4, 6, 10, 14, 16)]
    # samples at 6 and 14 s lie in the window: (8 * share) / 16
    assert read(_run(gauges, chips=2)) == pytest.approx(37.5)
    del gauges[LANE.format(1) + "/input_s"]
    assert read(_run(gauges, chips=2)) is None
    assert read(_run({})) is None


def test_refusals_by_estimate_are_the_counts_rise_in_the_window():
    read = bench_run.reader("refused_estimate_pct")
    name = "controller.refused.estimate"
    counts = [(0.0, 0), (3.0, 1), (4.0, 2), (6.0, 3), (9.0, 4), (16.0, 5)]
    assert read(_run({name: counts}, attempted=200)) == pytest.approx(1.0)
    # a running count with no refusal reads 0, a program without one none
    assert read(_run({name: [(0.0, 0)]})) == 0.0
    assert read(_run({})) is None


def test_a_traced_cpu_run_reads_both(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    r = bench_run.run(CELL, 2**31 + 25, 3.0, True, rehearsal=True,
                      rate=300.0)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert 0 < m["exec_input_pct"]["value"] < 100
    assert 0 <= m["refused_estimate_pct"]["value"] <= 100
