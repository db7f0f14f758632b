"""Served path, client view: the 0.99 quantile of latency, due time to
receipt, over every ok response of the window (nearest rank). Not an
end-to-end metric with a bound while copies refused after a host freeze
make it swing from run to run (PERF.md, section 2)."""
from record import quantile


def read(run):
    q = quantile(run.client["latency_ok_s"], 0.99)
    return None if q is None else q * 1e3
