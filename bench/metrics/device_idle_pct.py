"""Device: share of the traced window in which no operation ran, 1 minus
the union of each device's operation intervals over the window, mean over
devices."""


def read(run):
    if run.trace is None or len(run.trace["busy_s"]) != run.chips:
        return None
    busy = run.trace["busy_s"].values()
    return 100.0 * (1.0 - sum(busy) / len(busy) / run.trace["window_s"])
