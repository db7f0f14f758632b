"""The program's worker daemon, run for one benchmark run.

    python bench/daemon.py --out FILE [--trace-dir DIR --trace-at S
        --trace-s S] -- <arguments of python -m repro.runtime.worker>

Runs `repro.runtime.worker.main` unchanged, the served path itself, and
around it what only the process that holds the chips can do:

* SIGUSR1 from the harness opens the measured window. From then on the
  first and the last output of each (copy, bucket, device) that an EXEC
  returns are kept, as the timed path produced them; the EXEC is not
  slowed, the arrays stay on the device until the daemon has left the
  controller.
* With `--trace-dir`, a profiler trace of `--trace-s` seconds starts
  `--trace-at` seconds after the window opened.
* When the daemon has left, it reads the chips' peak memory, frees the
  program's state, reduces the trace (xplane.py) and compares the kept
  outputs with the plain reference (reference.py), and writes all of it
  as one JSON object to `--out`.
"""
from __future__ import annotations

import argparse
import gc
import glob
import json
import signal
import sys
import threading
import time


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", required=True)
    p.add_argument("--trace-dir", default=None)
    p.add_argument("--trace-at", type=float, default=0.0)
    p.add_argument("--trace-s", type=float, default=0.0)
    p.add_argument("--scale", type=int, default=1,
                   help="width divisor of the served copies, for the "
                        "reference")
    p.add_argument("--img", type=int, default=224,
                   help="image side of the served copies' inputs")
    own, worker_argv = p.parse_args(argv[:argv.index("--")]), \
        argv[argv.index("--") + 1:]
    w = argparse.ArgumentParser(add_help=False)
    w.add_argument("--gpus", type=int, default=1)
    w.add_argument("--seed", type=int, default=0)
    served, _ = w.parse_known_args(worker_argv)
    return own, served, worker_argv


class Capture:
    """Keeps the first and the last output of each (copy, bucket, device)
    that an EXEC returns once the window is open."""

    def __init__(self):
        self.open = threading.Event()
        self.t_open = None
        self.first: dict = {}
        self.last: dict = {}

    def wrap(self, execute):
        first, last, is_open = self.first, self.last, self.open.is_set

        def captured(model, b, x, device):
            out = execute(model, b, x, device)
            if is_open():
                key = (model.model_id, b, device.id)
                first.setdefault(key, out)
                last[key] = out
            return out
        return captured

    def outputs(self) -> dict:
        """{(copy index, bucket): [host arrays]}, and forgets the device
        arrays."""
        import numpy as np
        out: dict = {}
        for key, arr in self.first.items():
            arrays = [arr] if self.last[key] is arr else [arr, self.last[key]]
            out.setdefault((int(key[0][1:]), key[1]), []).extend(
                np.asarray(a, np.float32) for a in arrays)
        self.first.clear()
        self.last.clear()
        return out


class Tracer:
    """One profiler trace of `length` seconds, `at` seconds after start()."""

    def __init__(self, trace_dir: str, at: float, length: float):
        self.dir, self.at, self.length = trace_dir, at, length
        self.t_on = self.t_off = None
        self.error = None
        self._thread = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        # Python frames, and the runtime's verbose host events (a million
        # input-transpose chunks a second), would swamp the traced host
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        try:
            time.sleep(self.at)
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_on = time.monotonic()
            time.sleep(self.length)
            self.t_off = time.monotonic()
            jax.profiler.stop_trace()
        except Exception as e:  # reported in the result, never hidden
            self.error = repr(e)

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    own, served, worker_argv = parse(argv)
    capture = Capture()
    tracer = Tracer(own.trace_dir, own.trace_at, own.trace_s) \
        if own.trace_dir else None

    def window_open(*_):
        capture.t_open = time.monotonic()
        capture.open.set()
        if tracer is not None:
            tracer.start()

    signal.signal(signal.SIGUSR1, window_open)
    from repro.runtime import worker
    from repro.serving import engine
    engine.JaxModel.execute = capture.wrap(engine.JaxModel.execute)
    rc = worker.main(worker_argv)
    for sig in (signal.SIGUSR1, signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, signal.SIG_DFL)
    if tracer is not None:
        tracer.join()

    import jax
    devices = jax.devices()[:served.gpus]
    out = {"rc": rc, "t_open": capture.t_open,
           "memory_peak_bytes": max(
               (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)}
    outputs = capture.outputs()
    gc.collect()            # the program's weights and programs go here

    if tracer is not None:
        import xplane
        out["trace"] = {"t_on": tracer.t_on, "t_off": tracer.t_off,
                        "error": tracer.error}
        files = glob.glob(f"{own.trace_dir}/plugins/profile/*/*.xplane.pb")
        if files and tracer.t_on is not None:
            out["trace"].update(xplane.reduce(
                xplane.load(files[0]), tracer.t_off - tracer.t_on))

    import reference
    t0 = time.monotonic()
    out["check"] = reference.compare(outputs, served.seed, scale=own.scale,
                                     img=own.img)
    out["check"]["buckets"] = sorted({b for _, b in outputs})
    out["check"]["reference_s"] = time.monotonic() - t0
    with open(own.out, "w") as f:
        json.dump(out, f)
    return rc


if __name__ == "__main__":
    sys.exit(main())
