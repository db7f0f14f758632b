"""Controller and scheduler: 99th percentile of the controller's
`scheduler.tick_latency_s` gauge over the window's ticks."""
from record import quantile


def read(run):
    q = quantile([v for _, v in run.gauge("scheduler.tick_latency_s")], 0.99)
    return None if q is None else q * 1e3
