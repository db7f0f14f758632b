"""Chip smoke check: full-width ResNet-50 served through the worker daemon.

    python chip_smoke.py              # one chip: phase A, then phase B
    python chip_smoke.py --chips 4    # one daemon on four chips, and the
                                      # same traffic on one, nothing else

Phase A runs in a child process that exits before phase B starts. It names
the device, compiles every batch bucket of ResNet-50 at its published
widths (224x224 inputs, bf16 weights), and checks the bucket-1 logits
served on the chip against a float32 forward of the same seeded weights on
the host CPU (`repro.serving.engine.check_logits`, relative L2 error at
most `LOGITS_RTOL`).

Phase B serves real traffic. This process runs the controller, as
`examples/serve_distributed.py` does; one `--backend jax` worker daemon
owns the chip; a loadgen process sends open-loop requests at 10 r/s to
each of 8 ResNet-50 copies for 3 s under the paper's 100 ms SLO. No copy
is preloaded, so the controller must LOAD before it can EXEC.

This process never touches JAX, since a chip belongs to one process at a
time. Its children run with `JAX_PLATFORMS` naming `tpu` (and `cpu`), so
a chip that fails to start is an error, never a quiet CPU run, and phase
B fails unless every device the daemon names in its HELLO is a TPU. The
last line of stdout is one JSON object: `{"ok": true, "device":
{...}}` when every check passed on a TPU, otherwise `{"ok": false, ...}`
with a non-zero exit. `--cpu-rehearsal` runs the same phases at 1/16
width on whatever devices JAX finds, to rehearse without a chip; its "ok"
stays false, and it exits 0 only when every phase passed.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
N_MODELS, DURATION_S, SLO_S = 8, 3.0, 0.100
OPEN = ["--workload", "open", "--rate", "10"]
# The scheduler fills device 0 up to its 5 ms horizon before it gives work
# to device 1, so open traffic at 10 r/s per copy never reaches devices
# 2-3. Closed-loop traffic keeps 16 requests per copy outstanding, more
# than one device's horizon holds.
CLOSED = ["--workload", "closed", "--concurrency", "16"]
CHILD_TIMEOUT_S = 600


def child(args) -> int:
    """Phase A (or, with --child device, only the device's description);
    prints one JSON line for the parent."""
    import jax
    from repro.serving.engine import (PUBLISHED, REDUCED, check_logits,
                                      make_resnet_model, use_compile_cache)
    # JAX records a compile event for every program it builds, a read from
    # the persistent cache included, and a hit event for each such read
    compiles, hits = [], []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiles.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    jax.monitoring.register_event_listener(
        lambda name, **_: hits.append(name)
        if name == "/jax/compilation_cache/cache_hits" else None)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "bytes_limit": (dev.memory_stats() or {}).get("bytes_limit")}
    print(f"[device] {device}", flush=True)
    out = {"device": device}
    if args.child == "device" or (dev.platform != "tpu"
                                  and not args.cpu_rehearsal):
        print(json.dumps(out))
        return 0
    use_compile_cache()
    model = make_resnet_model("m0", seed=0, **(REDUCED if args.cpu_rehearsal
                                               else PUBLISHED))
    n_compiles, n_hits = len(compiles), len(hits)
    model.compile([dev])
    out["compile_s"] = {b: model.programs.compile_s[(dev, b)]
                        for b in model.batches}
    out["cache_hits"] = len(hits) - n_hits
    out["compilations"] = len(compiles) - n_compiles - out["cache_hits"]
    print(f"[phase A] compile seconds per bucket {out['compile_s']}: "
          f"{out['compilations']} compilations, {out['cache_hits']} "
          f"persistent-cache hits", flush=True)
    out["logits"] = check_logits(model, dev)
    print(f"[phase A] logits vs float32 CPU reference {out['logits']}",
          flush=True)
    print(json.dumps(out))
    return 0


def run_child(kind: str, args) -> dict:
    cmd = [sys.executable, str(REPO / "chip_smoke.py"), "--child", kind]
    if args.cpu_rehearsal:
        cmd.append("--cpu-rehearsal")
    proc = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                          timeout=CHILD_TIMEOUT_S)
    sys.stdout.write(proc.stdout)
    if proc.returncode != 0:
        raise RuntimeError(f"{kind} process exited {proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def serve(gpus: int, traffic: list, args, failures: list) -> dict:
    """Phase B on `gpus` devices; appends what went wrong to `failures`."""
    import serve_distributed
    argv = ["--backend", "jax", "--workers", "1", "--gpus", str(gpus),
            "--n-models", str(N_MODELS), "--duration", str(DURATION_S),
            "--slo", str(SLO_S), "--loadgen", "--loadgen-processes", "1",
            *traffic]
    if args.cpu_rehearsal:
        argv.append("--reduced")
    out = serve_distributed.serve(serve_distributed.parse_args(argv))
    c = out["client"]
    tag = f"[phase B, {gpus} device(s)]"
    print(f"{tag} sent {c['sent']} ok {c['goodput']} late {c['timeout']} "
          f"rejected {c['rejected']} lost {c['lost']}; LOADs {out['loads']} "
          f"EXECs {out['execs']}; client p50 {c['p50'] * 1e3:.2f} ms "
          f"p99 {c['p99'] * 1e3:.2f} ms", flush=True)
    net = c["report"]["net_overhead"]
    print(f"{tag} client max {c['report']['client_total']['max'] * 1e3:.2f}"
          f" ms; client-controller overhead p99 {net['p99'] * 1e3:.2f} ms "
          f"max {net['max'] * 1e3:.2f} ms", flush=True)
    print(f"{tag} learned INFER profile (s) per bucket "
          f"{out['infer_profile_s']}", flush=True)
    print(f"{tag} busy seconds per lane {out['busy_s']}", flush=True)
    print(f"{tag} daemon devices {out['devices']}", flush=True)
    platforms = {d["platform"] for ds in out["devices"].values() for d in ds}
    checks = {
        f"daemon served on {sorted(platforms)}":
            platforms != {"tpu"} and not args.cpu_rehearsal,
        "goodput is 0": c["goodput"] == 0 or out["goodput"] == 0,
        "a response was late": c["timeout"] > 0 or out["timeout"] > 0,
        "no LOAD ran": out["loads"] == 0,
        "no EXEC ran": out["execs"] == 0,
        f"daemon exit codes {out['worker_returncodes']}":
            out["worker_returncodes"] != [0],
        f"loadgen exit code {c['returncode']}": c["returncode"] != 0,
    }
    failures += [f"{tag} {what}" for what, bad in checks.items() if bad]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run every phase at 1/16 width without a TPU; "
                         "never reports ok")
    ap.add_argument("--child", choices=("phase-a", "device"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not (REPO / "src" / "repro").is_dir() \
            or not (REPO / "examples" / "serve_distributed.py").is_file():
        print("chip_smoke.py must run inside the repository checkout "
              "(src/repro and examples/ beside it)", file=sys.stderr)
        return 2
    sys.path[:0] = [str(REPO / "src"), str(REPO / "examples")]
    os.environ["PYTHONPATH"] = os.pathsep.join(
        filter(None, [str(REPO / "src"), os.environ.get("PYTHONPATH")]))
    # Every child names its platforms, so a TPU that fails to start raises
    # instead of leaving JAX on the CPU. Phase A's float32 reference and
    # the daemon's weight generation run on the host CPU beside the chip.
    platforms = os.environ.get("JAX_PLATFORMS") or (
        "" if args.cpu_rehearsal else "tpu")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    if args.child:
        return child(args)

    failures: list = []
    device = None
    try:
        phase = "phase A" if args.chips == 1 else "device"
        a = run_child("phase-a" if args.chips == 1 else "device", args)
        device = a["device"]
        if device["platform"] != "tpu" and not args.cpu_rehearsal:
            failures.append(f"no TPU: JAX found {device['platform']}")
        else:
            if args.chips == 1:
                if not a["logits"]["ok"]:
                    failures.append(f"logits check failed: {a['logits']}")
                phase = "phase B"
                serve(1, OPEN, args, failures)
            else:
                phase = "phase B on 4 devices"
                four = serve(4, CLOSED, args, failures)
                phase = "phase B on 1 device"
                serve(1, CLOSED, args, failures)
                idle = [f"gpu{g}/{lane}" for g in range(4)
                        for lane in ("LOAD", "EXEC")
                        if not four["busy_s"].get(f"w0/gpu{g}/{lane}")]
                if idle:
                    failures.append(f"devices never busy: {idle}")
    except Exception as e:  # every failure ends in "ok": false, exit 1
        traceback.print_exc()
        failures.append(f"{phase}: {e!r}")
    if args.cpu_rehearsal:
        print(json.dumps({"ok": False, "rehearsal": True, "device": device,
                          "failures": failures}))
        return 1 if failures else 0
    if failures:
        print(json.dumps({"ok": False, "failures": failures}))
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
