"""What one run recorded, as the per-layer metric readers see it.

A reader (metrics/<name>.py) defines `read(run: Run) -> float | None`
and returns None where the run holds nothing for it to read. Times on the
controller's loop clock are seconds; `window` is the measured window on
that clock, and `trace_window` the traced part of it, when there is one.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from counts import BUCKETS


def quantile(values, q: float) -> Optional[float]:
    """Nearest-rank quantile of all `values` (None when there are none)."""
    xs = sorted(values)
    if not xs:
        return None
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]


def bucket(batch: int) -> int:
    """The compiled batch bucket an EXEC of `batch` requests runs."""
    return next((b for b in BUCKETS if b >= batch), BUCKETS[-1])


@dataclasses.dataclass
class Run:
    seconds: float                  # length of the measured window
    chips: int
    window: tuple                   # (start, end), controller loop clock
    client: dict                    # merged generator reports
    actions: list                   # every ActionRecord of the run
    gauges: dict                    # gauge name -> [(t, value)]
    size: dict                      # {"img", "scale", "num_classes"}
    peak: Optional[dict]            # peaks.py entry of the device kind
    trace: Optional[dict] = None    # xplane.reduce output
    trace_window: Optional[tuple] = None

    def execs(self, window: Optional[tuple] = None) -> list:
        """Successful EXEC (INFER) records that started in `window`
        (default: the measured window)."""
        lo, hi = window or self.window
        return [a for a in self.actions
                if a.action_type == "INFER" and a.status == "SUCCESS"
                and lo <= a.t_start < hi]

    def gauge(self, name: str) -> list:
        """[(t, value)] of gauge `name` inside the measured window."""
        lo, hi = self.window
        return [(t, v) for t, v in self.gauges.get(name, ()) if lo <= t <= hi]
