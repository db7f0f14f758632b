"""Client and protocol: median of `RequestSpan.net_overhead` over the
window's ok requests, the client's send-to-receipt time minus the
controller's admission-to-completion time."""
from record import quantile


def read(run):
    q = quantile(run.client["net_overhead_s"], 0.5)
    return None if q is None else q * 1e3
