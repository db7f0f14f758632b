"""Distributed serving demo: a controller and N worker daemons in
separate OS processes, talking the runtime wire protocol over TCP.

    PYTHONPATH=src python examples/serve_distributed.py --workers 2

spawns `python -m repro.runtime.worker` subprocesses, waits for them to
register, serves a short open-loop workload under real time, prints a
JSON summary (goodput, latency percentiles, per-worker network-delay
estimates, telemetry counts), then winds the daemons down gracefully —
each flushes its buffered telemetry before leaving.

`--loadgen` completes the paper's three-tier topology: instead of
in-process clients, a `python -m repro.runtime.loadgen` subprocess (with
`--loadgen-processes` child generators) drives the controller over its
own TCP connections and reports *client-observed* goodput and latency —
the summary then carries both the controller's and the clients' view.

`--backend jax` makes the daemon serve `--n-models` seeded ResNet-50
copies on its JAX devices (`--gpus` of them; `--reduced` for CPU-sized
ones). One daemon owns the host's accelerators, so it runs with
`--workers 1`, and the controller waits for it to compile and profile
before it registers.

`--smoke` makes the run assert (goodput > 0, zero timeouts' spirit —
completed-late must be 0 by construction, workers exit 0, LOADs ran under
`--backend jax`, and with `--loadgen` nonzero client-observed goodput) so
CI can use it as the distributed smoke job.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from repro.core.actions import EXEC_TYPES, ActionType, ResultStatus
from repro.core.clock import EventLoop, RealClock, RealtimePump
from repro.core.controller import Controller
from repro.core.scheduler import ClockworkScheduler
from repro.runtime.controller import ControllerServer
from repro.runtime.worker import demo_models
from repro.serving.workload import OpenLoopClient
from repro.telemetry.reports import quantile

# covers a daemon's cold compile and profiling of every batch bucket; a
# daemon that exits earlier ends the wait at once
REGISTER_TIMEOUT_S = 600.0
# covers a daemon's exit after GOODBYE, accelerator runtime teardown
# included
EXIT_TIMEOUT_S = 60.0


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--backend", choices=("sim", "jax"), default="sim",
                    help="daemon backend (see python -m repro.runtime."
                         "worker --help)")
    ap.add_argument("--gpus", type=int, default=1,
                    help="devices per daemon")
    ap.add_argument("--reduced", action="store_true",
                    help="jax: 1/16-width ResNet-50 on 64x64 inputs")
    ap.add_argument("--n-models", type=int, default=4)
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="open-loop request rate per model (r/s)")
    ap.add_argument("--slo", type=float, default=0.25)
    ap.add_argument("--port", type=int, default=0,
                    help="controller TCP port (0 = ephemeral)")
    ap.add_argument("--smoke", action="store_true",
                    help="assert goodput/clean-shutdown (CI smoke job)")
    ap.add_argument("--telemetry-jsonl", default=None,
                    help="daemons stream telemetry JSONL next to this "
                         "prefix (one file per worker)")
    ap.add_argument("--loadgen", action="store_true",
                    help="drive the workload from a separate loadgen "
                         "process (full three-tier topology) instead of "
                         "in-process clients")
    ap.add_argument("--loadgen-processes", type=int, default=2,
                    help="child generator processes under --loadgen")
    ap.add_argument("--workload", default="open",
                    choices=("open", "closed", "maf"),
                    help="workload shape for --loadgen")
    ap.add_argument("--concurrency", type=int, default=4,
                    help="closed: outstanding requests per model")
    args = ap.parse_args(argv)
    if args.backend == "jax" and args.workers > 1:
        ap.error("--backend jax runs one daemon per host: it owns every "
                 "accelerator there")
    return args


def serve(args) -> dict:
    """Run controller, daemons and workload; returns the summary. Raises
    RuntimeError when the cluster never comes up."""
    if args.backend == "jax":
        from repro.serving.engine import PUBLISHED, REDUCED, \
            resnet_fleet_defs
        models = resnet_fleet_defs(
            args.n_models, (REDUCED if args.reduced else PUBLISHED)["scale"])
    else:
        models = demo_models(args.n_models)
    loop = EventLoop(RealClock())
    pump = RealtimePump(loop, max_poll=0.005)
    # generous result grace: wall-clock scheduling slop must not look like
    # a missed result (virtual-clock defaults are tighter)
    controller = Controller(loop, models, ClockworkScheduler(),
                            action_delay=0.002, result_grace=0.25,
                            default_slo=args.slo)
    server = ControllerServer(controller)
    port = server.listen_tcp("127.0.0.1", args.port, pump.post)
    print(f"[controller] listening on 127.0.0.1:{port}", flush=True)

    env = dict(os.environ)
    procs = []
    lg = None
    for i in range(args.workers):
        cmd = [sys.executable, "-m", "repro.runtime.worker",
               "--controller", f"127.0.0.1:{port}",
               "--worker-id", f"w{i}", "--n-models", str(args.n_models),
               "--backend", args.backend, "--gpus", str(args.gpus),
               "--seed", str(i),
               "--duration", str(args.duration + 30.0)]
        if args.reduced:
            cmd.append("--reduced")
        if args.telemetry_jsonl:
            cmd += ["--telemetry-jsonl", f"{args.telemetry_jsonl}.w{i}"]
        procs.append(subprocess.Popen(cmd, env=env))

    try:
        pump.run(until=lambda: len(controller.workers) >= args.workers
                 or any(pr.poll() is not None for pr in procs),
                 timeout=REGISTER_TIMEOUT_S)
        if len(controller.workers) < args.workers:
            raise RuntimeError("workers never registered")
        devices = {wid: stub.devices for wid, stub in server.stubs.items()}
        print(f"[controller] {len(controller.workers)} workers registered",
              flush=True)

        clients, client_out = [], None
        controller.start_heartbeats()
        if args.loadgen:
            # third tier: the workload lives in its own process(es) and
            # measures latency on its side of the network
            lg_cmd = [sys.executable, "-m", "repro.runtime.loadgen",
                      "--controller", f"127.0.0.1:{port}",
                      "--workload", args.workload,
                      "--n-models", str(args.n_models),
                      "--rate", str(args.rate), "--slo", str(args.slo),
                      "--concurrency", str(args.concurrency),
                      "--duration", str(args.duration),
                      "--processes", str(args.loadgen_processes)]
            lg = subprocess.Popen(lg_cmd, env=env, stdout=subprocess.PIPE,
                                  text=True)
            pump.run(until=lambda: lg.poll() is not None,
                     timeout=args.duration + 90.0)
            try:
                lg_stdout, _ = lg.communicate(timeout=10.0)
            except subprocess.TimeoutExpired:
                lg.kill()
                lg_stdout, _ = lg.communicate()
            if not lg_stdout.strip():
                raise RuntimeError("loadgen produced no output")
            client_out = json.loads(lg_stdout)
            client_out["returncode"] = lg.returncode
        else:
            now = loop.now()
            clients = [OpenLoopClient(loop, controller.on_request, mid,
                                      args.slo, rate=args.rate, start=now,
                                      stop=now + args.duration, seed=i)
                       for i, mid in enumerate(models)]
            pump.run(timeout=args.duration + 0.5)

        summary = controller.summary()
        net = {wid: round(m.net_delay * 1e6)
               for wid, m in controller.workers.items()}
        # what the scheduler now plans with, per batch bucket the models
        # were profiled at (median over the models)
        prof = controller.profiler
        buckets = sorted({b for mid in models
                          for b in prof.known_batches("INFER", mid)})
        profile = {b: quantile([e for mid in models if (e := prof.estimate(
                       "INFER", mid, b)) is not None], 0.5)
                   for b in buckets}
    finally:
        if lg is not None and lg.poll() is None:
            lg.kill()              # never orphan the loadgen tree
        server.shutdown()          # daemons flush telemetry and leave
        pump.run(timeout=1.0)      # let final TELEMETRY/GOODBYE frames land
        pump.stop()
        report = controller.telemetry_report()
        worker_gauges = sorted(k for k in report["gauges"]
                               if k.startswith("worker/"))
        busy = {k[len("worker/"):-len("/busy_s")]: report["gauges"][k]["max"]
                for k in worker_gauges if k.endswith("/busy_s")}
        rcs = []
        for pr in procs:
            try:
                rcs.append(pr.wait(timeout=EXIT_TIMEOUT_S))
            except subprocess.TimeoutExpired:
                pr.kill()
                rcs.append(-9)

    sent = client_out["sent"] if client_out is not None \
        else sum(c.sent for c in clients)
    done = [r for r in controller.results_log
            if r.status is ResultStatus.SUCCESS]
    out = {"sent": sent, **summary,
           "loads": sum(r.action_type is ActionType.LOAD for r in done),
           "execs": sum(r.action_type in EXEC_TYPES for r in done),
           "infer_profile_s": profile, "busy_s": busy, "devices": devices,
           "net_delay_us": net, "worker_returncodes": rcs,
           "worker_gauges": worker_gauges}
    if client_out is not None:
        out["client"] = client_out
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        out = serve(args)
    except RuntimeError as e:
        print(f"FATAL: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out, indent=2, default=str))

    if args.smoke:
        assert out["goodput"] > 0, "no requests served"
        if args.backend == "jax":
            assert out["loads"] > 0, "no weights were loaded"
        assert out["timeout"] == 0, "Clockwork must never respond late"
        rcs = out["worker_returncodes"]
        assert all(rc == 0 for rc in rcs), f"unclean worker exit: {rcs}"
        assert out["dead_workers"] == 0, "worker falsely declared dead"
        assert out["worker_gauges"], \
            "daemon telemetry never reached controller"
        client_out = out.get("client")
        if client_out is not None:
            assert client_out["returncode"] == 0, "loadgen exited unclean"
            assert client_out["goodput"] > 0, \
                "no client-observed completions"
            assert client_out["timeout"] == 0, \
                "client observed a late response"
            assert client_out["goodput"] == out["goodput"], \
                "client/controller goodput disagree"
        print("SMOKE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
