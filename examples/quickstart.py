"""Quickstart: serve real models through the Clockwork controller on CPU.

Starts an in-process cluster (controller + one worker with a JAX backend),
registers two models (a reduced ResNet-50 — the paper's eval model — and an
LM decode engine), submits batched requests, and prints latency/goodput.

Profiles persist across runs: the first run measures (or you pre-measure
with `python -m repro.telemetry.profiler`) and writes
experiments/profiles.json; repeat runs seed from it and skip warmup.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

import jax

from repro.core.actions import Request
from repro.core.clock import EventLoop, RealClock, RealtimePump
from repro.core.controller import Controller
from repro.core.scheduler import ClockworkScheduler
from repro.core.worker import Worker
from repro.serving.engine import (JaxBackend, make_lm_decode_model,
                                  make_resnet_model, seed_engines,
                                  update_store)
from repro.telemetry import ProfileStore
from repro.utils import welford_summary

STORE_PATH = "experiments/profiles.json"


def main():
    loop = EventLoop(RealClock())
    pump = RealtimePump(loop)
    print("[quickstart] compiling model batch buckets (AOT, like the "
          "paper's per-batch-size TVM kernels)...")
    engines = {
        "resnet50_mini": make_resnet_model("resnet50_mini", scale=16,
                                           batches=(1, 2, 4)),
        "qwen2_decode": make_lm_decode_model("qwen2_decode", "qwen2-0.5b",
                                             batches=(1, 2, 4), ctx=128),
    }
    store = ProfileStore.load_if_exists(STORE_PATH)
    if store is not None:
        print(f"[quickstart] seeding profiles from {STORE_PATH} "
              "(skipping warmup re-measurement)")
    dev = jax.devices()[0]
    profiles = seed_engines(engines, dev, store)
    for e in engines.values():
        if e.warmup_count == 0:   # store-seeded: warmup didn't compile it
            e.compile([dev])   # AOT, untimed — keeps compiles off hot path
    models = {k: v.modeldef() for k, v in engines.items()}
    backend = JaxBackend(engines, [dev])
    worker = Worker("w0", loop, backend, models, n_gpus=1, post=pump.post)
    controller = Controller(loop, models, ClockworkScheduler(),
                            action_delay=1e-4)
    controller.add_worker(worker, profiles)

    done = []
    controller.on_response = done.append

    slo = 2.0  # generous on a shared CPU; the controller still *schedules*
    print("[quickstart] submitting 30 requests across 2 models...")
    for i in range(30):
        controller.on_request(Request(model_id=list(models)[i % 2],
                                      arrival=loop.now(), slo=slo))
        pump.run(timeout=0.01)
    pump.run(timeout=5.0)

    ok = [r for r in done if r.status == "ok"]
    lat = [r.completion - r.arrival for r in ok]
    print(f"[quickstart] {len(ok)}/{len(done)} within SLO; latency stats "
          f"(s): {welford_summary(lat)}")
    for mid in models:
        est = controller.profiler.estimate("INFER", mid, 1)
        print(f"[quickstart] learned INFER profile {mid} b1: "
              f"{est * 1e3:.2f} ms")

    rep = controller.telemetry_report()
    bd = rep["breakdown"]
    print(f"[quickstart] latency breakdown (median s): "
          f"queue={bd['queue']['median']:.4f} "
          f"exec={bd['exec']['median']:.4f} "
          f"total={bd['total']['median']:.4f}; "
          f"cold_starts={bd['cold_starts']}")
    update_store(engines, store or ProfileStore(), controller) \
        .save(STORE_PATH)
    print(f"[quickstart] profiles persisted -> {STORE_PATH}")


if __name__ == "__main__":
    main()
