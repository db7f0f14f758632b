"""End-to-end behaviour tests for the whole system: a real (non-simulated)
serving round-trip on CPU through the Clockwork controller with a JAX
backend, plus dry-run machinery checks on a small forced-device mesh."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.clock import EventLoop, RealClock, RealtimePump
from repro.core.controller import Controller
from repro.core.scheduler import ClockworkScheduler
from repro.core.actions import Request
from repro.serving.engine import JaxModel, JaxBackend, make_resnet_model
from repro.core.worker import Worker


def test_real_jax_serving_roundtrip():
    """Controller + worker + actual jit'd ResNet execution on CPU: requests
    go in, on-time responses come out, measured latencies feed the profiler.
    """
    loop = EventLoop(RealClock())
    pump = RealtimePump(loop)
    dev = jax.devices()[0]
    jm = make_resnet_model("resnet_tiny", scale=16, batches=(1, 2, 4))
    profiles = jm.seed_profiles(dev)
    models = {"resnet_tiny": jm.modeldef()}
    backend = JaxBackend({"resnet_tiny": jm}, [dev])
    w = Worker("w0", loop, backend, models, n_gpus=1, post=pump.post)
    controller = Controller(loop, models, ClockworkScheduler(),
                            action_delay=1e-4)
    controller.add_worker(w, profiles=profiles)
    done = []
    controller.on_response = done.append
    for i in range(12):
        controller.on_request(Request(model_id="resnet_tiny",
                                      arrival=loop.now(), slo=5.0))
        pump.run(timeout=0.02)
    pump.run(timeout=3.0)
    ok = [r for r in done if r.status == "ok"]
    assert len(ok) >= 10, [r.status for r in done]
    # profiler learned real executions
    est = controller.profiler.estimate("INFER", "resnet_tiny", 1)
    assert est is not None and est > 0


def test_dryrun_cell_machinery_small_mesh():
    """Run the dry-run driver end-to-end in a subprocess with 8 forced host
    devices and a (2,4) mesh — validates the lowering/analysis pipeline
    without the cost of the 512-device production mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, json
        from repro.configs import get_smoke_config
        from repro.configs.base import ShapeSpec
        from repro.distributed.steps import build_sharded_step
        from repro.launch.mesh import make_mesh
        from repro.launch.dryrun import parse_collectives
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_smoke_config("gemma2-27b")
        shape = ShapeSpec("t", "train", 64, 8)
        step = build_sharded_step(cfg, mesh, shape, chunk=32)
        compiled = step.jitted.lower(*step.abstract).compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        colls = parse_collectives(compiled.as_text())
        print(json.dumps({
            "flops": cost.get("flops", 0.0),
            "temp": mem.temp_size_in_bytes,
            "n_collectives": len(colls),
            "kinds": sorted({c["kind"] for c in colls}),
        }))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd=__import__("os").path.join(
                             __import__("os").path.dirname(__file__), ".."))
    assert out.returncode == 0, out.stderr[-2000:]
    import json
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["flops"] > 0
    assert res["n_collectives"] > 0          # sharded training communicates
    assert "all-reduce" in res["kinds"]


def test_collective_parser():
    from repro.launch.dryrun import parse_collectives
    hlo = """
      %all-reduce.1 = f32[16,1024]{1,0} all-reduce(%dot), replica_groups=[16,16]<=[256], to_apply=%add
      %ag = bf16[8,512]{1,0} all-gather(%x), replica_groups=[32,8]<=[256], dimensions={1}
      %rs = (f32[4,4]{1,0}) reduce-scatter(%y), replica_groups=[1,4]<=[4]
    """
    ops = parse_collectives(hlo)
    kinds = {o["kind"] for o in ops}
    assert kinds == {"all-reduce", "all-gather", "reduce-scatter"}
    ar = next(o for o in ops if o["kind"] == "all-reduce")
    assert ar["result_bytes"] == 16 * 1024 * 4
    assert ar["group"] == 16
    ag = next(o for o in ops if o["kind"] == "all-gather")
    assert ag["operand_bytes"] == 8 * 512 * 2 // 8
