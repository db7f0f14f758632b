"""Engine: 99th percentile of each successful EXEC's actual duration minus
the duration the scheduler predicted for it, over the window."""
from record import quantile


def read(run):
    q = quantile([a.actual - a.predicted for a in run.execs()
                  if a.predicted is not None], 0.99)
    return None if q is None else q * 1e3
