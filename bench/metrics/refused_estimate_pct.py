"""Controller and scheduler: share of the window's requests that the
controller refused with cause `estimate`, because the copy's batch-1
estimate alone exceeded the SLO (fault 2's signature), over the requests
due in the window. Read from the controller's running count of such
refusals, the `controller.refused.estimate` gauge: its rise across the
window. A controller that keeps no such count reports none."""
GAUGE = "controller.refused.estimate"


def read(run):
    samples = run.gauges.get(GAUGE)
    if not samples or not run.client["attempted"]:
        return None
    lo, hi = run.window
    before = max((v for t, v in samples if t < lo), default=0)
    by_end = max((v for t, v in samples if t <= hi), default=0)
    return 100.0 * (by_end - before) / run.client["attempted"]
