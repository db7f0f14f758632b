"""Runs one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (BENCHMARK.json `workloads`) names a configuration, whose file
gives the deployment (model, copies, SLO, controller settings), and a
traffic mix, found as bench/traffic/<traffic>.json, which gives the
arrivals, the popularity over the copies and the total rate. The served path runs as users see it:

* this process runs the program's controller (`Controller` with the
  `ClockworkScheduler` behind a `ControllerServer` on localhost TCP), as
  examples/serve_distributed.py does, and never imports JAX;
* one child runs the program's worker daemon with its JAX backend on the
  cell's chips (daemon.py);
* generator children (loadgen.py) send the seeded open-loop schedule
  through the program's `RemoteClient` and time each request from when
  it was due.

Set-up is everything before the window, warm-up traffic at the cell's
rate included. The window lasts `--seconds`; every request due in it is
counted, and the generators wait for each one's answer. Then the daemon
leaves, compares the outputs its EXECs produced in the window with the
plain reference, and exits.

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics` (the cell's end-to-end metrics, or with `--trace 1`
its per-layer ones), `device`, with `--trace 1` `breakdown`, and last
`checks`, each compared number beside its limit; the same checks are the
last lines of stderr. `failed` counts the window's requests that failed
as operations: never answered, answered more than once, or answered under
another model. A request that the controller refused, or answered after
its SLO, was answered: it is an SLO miss, counted against `goodput_rps`
and printed on stderr, not a failure. A run whose daemon finds no TPU, or fewer chips
than the cell asks for, prints no result and exits 1. `--rehearsal` runs
everything at 1/16 width on whatever JAX finds, for a CPU, and prints no
result either.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
REHEARSAL_SIZE = {"scale": 16, "img": 64}   # the daemon's --reduced copies
LEAD_S = 2.0                  # generators start and connect in this time
MAX_RATE_PER_GENERATOR = 1000.0
REGISTER_S = 1100.0           # a first run compiles every program
ANSWER_WAIT_S = 60.0          # matches loadgen.ANSWER_WAIT_S
DAEMON_EXIT_S = 240.0         # the daemon's reference check runs in this
TRACE_S = 3.0                 # length of the traced part of the window
# Widest gap by which a served row of logits may lie from the float32
# reference's, relative to the reference's norm (reference.row_gaps); set
# from the sound runs' readings and the float8 control's, see PERF.md.
LOGIT_GAP_LIMIT = 0.02


class RunError(RuntimeError):
    """The run could not be measured: no result is printed."""


def load_cell(name: str):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise RunError(f"no cell {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in reported]
    return cell, config, traffic, e2e, per_layer


def reader(metric: str):
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def child_env() -> dict:
    """The children's environment: the program on the path, JAX's
    compilation cache at one fixed directory of the checkout (never the
    machine's own), and a named TPU platform, so a chip that fails to
    start is an error and never a quiet CPU run. The CPU stays beside it:
    the program makes its host weights there."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [str(ROOT / "src"), str(BENCH),
                      os.environ.get("PYTHONPATH")]))
    env["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    platforms = env.get("JAX_PLATFORMS") or "tpu"
    if "cpu" not in platforms.split(","):
        platforms += ",cpu"
    env["JAX_PLATFORMS"] = platforms
    return env


def stop(procs) -> None:
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        p.wait()


def merge(reports: list) -> dict:
    out = {k: sum(r[k] for r in reports)
           for k in ("attempted", "ok_in_slo", "unanswered", "duplicates",
                     "wrong_model")}
    for k in ("latency_ok_s", "lag_s", "net_overhead_s"):
        out[k] = [x for r in reports for x in r[k]]
    out["status"] = {}
    misses: dict = {}
    for r in reports:
        for k, v in r["status"].items():
            out["status"][k] = out["status"].get(k, 0) + v
        for k, v in r["misses"]:
            misses[k] = misses.get(k, 0) + v
    out["misses"] = sorted(misses.items())
    return out


def episodes(misses, gap: int = 3) -> list:
    """The window's misses as episodes: [start s, length s, misses] of
    runs of 100-ms bins with misses, no more than `gap` bins apart, most
    misses first."""
    runs: list = []
    for k, n in misses:
        if runs and k - runs[-1][1] <= gap:
            runs[-1][1] = k
            runs[-1][2] += n
        else:
            runs.append([k, k, n])
    return sorted(([round(a / 10, 1), round((b - a + 1) / 10, 1), n]
                   for a, b, n in runs), key=lambda e: -e[2])


class GcPauses:
    """This process's garbage collections of 10 ms or more: the
    controller runs here, and a pause of its loop is a pause of the
    served path."""

    def __init__(self):
        self.pauses: list = []      # (start, generation, seconds)
        self._t0 = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        elif self._t0 is not None:
            took = time.monotonic() - self._t0
            if took >= 0.01:
                self.pauses.append((self._t0, info["generation"], took))


def passes(check: dict) -> bool:
    """A compared number against its limit: at most `limit`, or at least
    `min`."""
    if "min" in check:
        return check["value"] >= check["min"]
    return check["value"] <= check["limit"]


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        rehearsal: bool = False, rate: float = None) -> dict:
    """One run of a cell; returns the result object (see the module
    docstring) with the run's diagnostics under "diag"."""
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import arrivals
    import counts
    from peaks import peaks
    from record import Run, bucket, quantile
    from repro.core.clock import EventLoop, RealClock, RealtimePump
    from repro.core.controller import Controller
    from repro.core.scheduler import ClockworkScheduler
    from repro.core.worker import ModelDef
    from repro.runtime.controller import ControllerServer

    cell, config, traffic, e2e, per_layer = load_cell(cell_name)
    size = dict(REHEARSAL_SIZE) if rehearsal else {
        "scale": config["width_divisor"], "img": config["image_size"]}
    size["num_classes"] = config["num_classes"]
    weights_bytes = 2 * counts.param_count(size["scale"], size["num_classes"])
    # what the daemon serves, which the configuration has to state as it is
    served = {"dtype": "bfloat16", "clients": "open-loop",
              "batch_buckets": list(counts.BUCKETS)}
    if not rehearsal:
        served["weights_bytes_per_copy"] = weights_bytes
    for key, value in served.items():
        if config[key] != value:
            raise RunError(f"the configuration states {key} {config[key]!r};"
                           f" the served path runs {value!r}")
    ctl = config["controller"]
    if ctl["scheduler"] != "ClockworkScheduler":
        raise RunError(f"the configuration states scheduler "
                       f"{ctl['scheduler']!r}; the served path runs "
                       f"ClockworkScheduler")
    copies, chips = config["copies"], cell["chips"]
    slo = config["slo_ms"] / 1e3
    rate = rate or arrivals.rate_of(traffic)
    warm_s = traffic["warm_s"]
    if traffic["arrivals"] != "poisson":
        raise RunError(f"unknown arrivals {traffic['arrivals']!r}")

    tmp = Path(tempfile.mkdtemp(prefix="bench-"))
    env = child_env()
    procs: list = []
    loop = EventLoop(RealClock())
    pump = RealtimePump(loop, max_poll=0.005)
    models = {f"m{i}": ModelDef(f"m{i}", weights_bytes=weights_bytes,
                                exec_latency={}) for i in range(copies)}
    controller = Controller(loop, models, ClockworkScheduler(),
                            action_delay=ctl["action_delay_s"],
                            result_grace=ctl["result_grace_s"],
                            default_slo=slo)
    recorder = controller.recorder      # the program's own, as deployed
    gc_pauses = GcPauses()
    gc.callbacks.append(gc_pauses)
    hello_profiles: dict = {}           # (type, model, batch) from HELLO
    add_worker = controller.add_worker

    def registered(worker, profiles=None):
        hello_profiles.update(profiles or {})
        return add_worker(worker, profiles)

    controller.add_worker = registered
    server = ControllerServer(controller)
    diag: dict = {"rate_rps": rate}
    try:
        port = server.listen_tcp("127.0.0.1", 0, pump.post)
        cmd = [sys.executable, str(BENCH / "daemon.py"),
               "--out", str(tmp / "daemon.json"),
               "--scale", str(size["scale"]), "--img", str(size["img"])]
        if trace:
            cmd += ["--trace-dir", str(tmp / "trace"),
                    "--trace-at", str(max(0.0, (seconds - TRACE_S) / 2)),
                    "--trace-s", str(min(TRACE_S, seconds))]
        cmd += ["--", "--controller", f"127.0.0.1:{port}", "--worker-id",
                "w0", "--backend", "jax", "--n-models", str(copies),
                "--gpus", str(chips), "--seed", str(seed)]
        if rehearsal:
            cmd.append("--reduced")
        daemon = subprocess.Popen(cmd, env=env, stdout=sys.stderr)
        procs.append(daemon)
        pump.run(until=lambda: "w0" in controller.workers
                 or daemon.poll() is not None, timeout=REGISTER_S)
        if "w0" not in controller.workers:
            raise RunError(f"the daemon never registered (exit code "
                           f"{daemon.poll()})")
        stub = server.stubs["w0"]
        print(f"[bench] daemon registered {time.monotonic() - T_PROCESS:.1f}"
              f" s after start", file=sys.stderr, flush=True)
        devices = stub.devices
        kinds = {(d["platform"], d["kind"]) for d in devices}
        if len(kinds) != 1:
            raise RunError(f"the daemon's devices differ: {devices}")
        platform, kind = kinds.pop()
        if platform != "tpu" and not rehearsal:
            raise RunError(f"no TPU: the daemon runs on {platform} ({kind})")
        if len(devices) != chips:
            raise RunError(f"the daemon drives {len(devices)} devices, the "
                           f"cell asks for {chips}")
        profiled = {(mid, b) for t, mid, b in hello_profiles if t == "INFER"}
        if profiled != {(m, b) for m in models for b in counts.BUCKETS}:
            raise RunError(f"the daemon profiled (copy, batch) "
                           f"{sorted(profiled)}, not every copy at buckets "
                           f"{counts.BUCKETS}")
        peak = None if rehearsal and platform != "tpu" else peaks(kind)
        controller.start_heartbeats()

        base = time.monotonic() - loop.now()     # loop time 0 on monotonic
        start = time.monotonic() + LEAD_S
        window = (start + warm_s - base, start + warm_s + seconds - base)
        n_gen = max(1, math.ceil(rate / MAX_RATE_PER_GENERATOR))
        for k in range(n_gen):
            procs.append(subprocess.Popen(
                [sys.executable, str(BENCH / "loadgen.py"),
                 "--controller", f"127.0.0.1:{port}", "--index", str(k),
                 "--count", str(n_gen), "--seed", str(seed),
                 "--rate", repr(rate), "--n-models", str(copies),
                 "--popularity", traffic["popularity"],
                 "--slo-s", repr(slo), "--warm-s", repr(warm_s),
                 "--window-s", repr(float(seconds)), "--start", repr(start),
                 "--out", str(tmp / f"gen{k}.json")],
                env=env, stdout=sys.stderr))
        gens = procs[1:]

        def open_window():
            daemon.send_signal(signal.SIGUSR1)
            diag["loaded_at_window"] = len(
                {a.model_id for a in recorder.iter_actions()
                 if a.action_type == "LOAD" and a.status == "SUCCESS"})

        loop.schedule(window[0], open_window)
        pump.run(until=lambda: all(g.poll() is not None for g in gens)
                 or daemon.poll() is not None,
                 timeout=LEAD_S + warm_s + seconds + ANSWER_WAIT_S + 30)
        setup_s = start + warm_s - T_PROCESS
        rcs = [g.poll() for g in gens]
        if rcs != [0] * n_gen:
            raise RunError(f"generator exit codes {rcs}, daemon "
                           f"{daemon.poll()}")
        client = merge([json.loads((tmp / f"gen{k}.json").read_text())
                        for k in range(n_gen)])

        alive_at_end = stub.alive and "w0" in controller.workers
        server.shutdown()
        pump.run(until=lambda: not stub.alive, timeout=30.0)
        try:
            rc = daemon.wait(timeout=DAEMON_EXIT_S)
        except subprocess.TimeoutExpired:
            raise RunError("the daemon did not exit") from None
        if rc != 0:
            raise RunError(f"the daemon exited {rc}")
        out = json.loads((tmp / "daemon.json").read_text())
    finally:
        gc.callbacks.remove(gc_pauses)
        server.shutdown()
        pump.stop()
        stop(procs)
        shutil.rmtree(tmp, ignore_errors=True)

    actions = list(recorder.iter_actions())
    gauges: dict = {}
    for g in recorder.iter_gauges():
        gauges.setdefault(g.name, []).append((g.t, g.value))
    tr = out.get("trace")
    if trace and (tr is None or "busy_s" not in tr):
        raise RunError(f"no trace was read: {tr}")
    record = Run(seconds=seconds, chips=chips, window=window, client=client,
                 actions=actions, gauges=gauges, size=size, peak=peak,
                 trace=tr, trace_window=None if tr is None else
                 (tr["t_on"] - base, tr["t_off"] - base))

    lat = client["latency_ok_s"]
    if trace:
        values = {m["name"]: reader(m["name"])(record) for m in per_layer}
        metrics_def = per_layer
    else:
        values = {"goodput_rps": client["ok_in_slo"] / seconds,
                  "setup_s": setup_s}
        metrics_def = e2e
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in metrics_def if values.get(m["name"]) is not None}

    ran = {bucket(a.batch_size) for a in record.execs()}
    chk = out["check"]
    checks = {
        "rows_compared": {"value": chk["rows"], "min": 1},
        "logit_gap": {"value": chk["logit_gap_max"],
                      "limit": LOGIT_GAP_LIMIT},
        "unchecked_buckets": {"value": len(ran - set(chk["buckets"])),
                              "limit": 0},
        "unanswered": {"value": client["unanswered"], "limit": 0},
        "duplicates": {"value": client["duplicates"], "limit": 0},
        "wrong_model": {"value": client["wrong_model"], "limit": 0},
    }
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": all(passes(c) for c in checks.values()),
              "attempted": client["attempted"],
              "failed": client["unanswered"] + client["duplicates"]
              + client["wrong_model"],
              "metrics": metrics, "device": device}
    if trace:
        busy = list(tr["busy_s"].values())
        if len(busy) != chips and not rehearsal:
            raise RunError(f"the trace holds {len(busy)} devices' "
                           f"operations, the cell has {chips} chips")
        if busy:        # a CPU's operations run on host threads
            device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = checks
    diag.update(
        samples_ok=len(lat), status=client["status"],
        slo_missed=client["attempted"] - client["ok_in_slo"],
        latency_ms=[None if q is None else q * 1e3 for q in
                    (quantile(lat, p) for p in (0.5, 0.99, 0.999))],
        buckets_run=sorted(ran), compared=chk, seed=seed,
        generators=n_gen, setup_s=setup_s, execs=len(record.execs()),
        execs_per_device=[sum(a.gpu_id == g for a in record.execs())
                          for g in range(chips)],
        lag_max_ms=max(client["lag_s"], default=0.0) * 1e3,
        miss_episodes=episodes(client["misses"])[:8],
        gc_pauses_ms=[(round(t - base - window[0], 2), gen, round(d * 1e3, 1))
                      for t, gen, d in gc_pauses.pauses
                      if window[0] <= t - base <= window[1]],
        worker_alive=alive_at_end)
    result["diag"] = diag
    return result


def report(result: dict) -> None:
    """The run's diagnostics, then each compared number beside its limit,
    as the last lines of stderr."""
    d = result["diag"]
    print(f"[bench] {d['samples_ok']} ok responses of {result['attempted']} "
          f"requests due in the window, {d['slo_missed']} of them not ok "
          f"within the SLO; outcomes {d['status']}; "
          f"{d['execs']} EXECs at buckets {d['buckets_run']}, per device "
          f"{d['execs_per_device']}; "
          f"{d['generators']} generators, latest send "
          f"{d['lag_max_ms']:.3f} ms after its due time; offered "
          f"{d['rate_rps']} r/s; {d.get('loaded_at_window')} copies loaded "
          f"when the window opened", file=sys.stderr)
    print(f"[bench] latency from due time, p50/p99/p999 ms over the ok "
          f"responses: {d['latency_ms']}", file=sys.stderr)
    print(f"[bench] misses by episode [start s, length s, misses]: "
          f"{d['miss_episodes']}; controller GC pauses in the window "
          f"[s, generation, ms]: {d['gc_pauses_ms']}; worker still "
          f"registered at the window's end: {d['worker_alive']}",
          file=sys.stderr)
    print(f"[bench] compared {d['compared']}", file=sys.stderr)
    for name, c in result["checks"].items():
        bound = f"min {c['min']!r}" if "min" in c else f"limit {c['limit']!r}"
        print(f"[check] {name} {c['value']!r} {bound}", file=sys.stderr)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="1/16 width on any device; prints no result")
    p.add_argument("--rate-rps", type=float, default=None,
                   help="offer this total rate instead of the cell's (the "
                        "knee sweep)")
    args = p.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench/run.py runs from a checkout of the repository: "
              "src/repro is missing", file=sys.stderr)
        return 2
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), rehearsal=args.rehearsal,
                     rate=args.rate_rps)
    except (RunError, KeyError) as e:
        print(f"[bench] no result: {e}", file=sys.stderr)
        return 1
    report(result)
    if args.rehearsal:
        # a rehearsal's numbers are not the chip's: name what was read
        print(f"[bench] rehearsal read {sorted(result['metrics'])}; correct "
              f"{result['correct']}", file=sys.stderr)
        return 0 if result["correct"] else 1
    del result["diag"]
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
