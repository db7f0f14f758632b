"""Load generator: 99th percentile of how late the window's requests were
sent, send time minus due time (the benchmark's own host clock)."""
from record import quantile


def read(run):
    q = quantile(run.client["lag_s"], 0.99)
    return None if q is None else q * 1e3
