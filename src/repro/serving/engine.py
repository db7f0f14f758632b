"""Real JAX execution backend for the Clockwork worker.

Mirrors the paper's model runtime (§5.1): each model is AOT-compiled per
batch-size bucket (default 1,2,4,8,16 like Clockwork's TVM kernels), weights
live in host memory and LOAD places them on one device, EXEC runs exactly
one XLA program at a time on the device that holds the weights, UNLOAD
frees them there. Copies of one architecture share their compiled
programs. Execution times are measured and fed back to the controller's
profiler.

Profiles are persistent: `seed_from_store` / `seed_engines` load a
ProfileStore written by the offline profiler CLI
(`python -m repro.telemetry.profiler`), so repeat runs perform zero
warmup re-measurements (`warmup_count` stays 0).
"""
from __future__ import annotations

import functools
import math
import os
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import SingleDeviceSharding

from repro.core.worker import ModelDef, NotLoadedError
from repro.models import params as pspec
from repro.models.resnet import resnet50_forward, resnet50_spec
from repro.telemetry.profile_store import ProfileStore

BUCKETS = (1, 2, 4, 8, 16)
# ResNet-50 as the paper serves it (ImageNet: 1000 classes, 224x224 input)
# and the 1/16-width, 64-pixel cut that CPU tests run
PUBLISHED = {"scale": 1, "img": 224}
REDUCED = {"scale": 16, "img": 64}
# bf16 serving logits against a float32 forward of the same weights, by
# relative L2 error (check_logits). ~50 layers each round to bf16 (unit
# roundoff 2^-9); the XLA CPU backend lands at 0.3-0.7% for seeds 0-2 at
# reduced widths. 2% leaves room for another backend's rounding while an
# 8-bit float (roundoff 2^-4) would miss it.
LOGITS_RTOL = 0.02
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
# The host phases of one EXEC, in order (JaxModel.run): `input` copies the
# host input to the device, as a lane-dense slab where the input allows
# (`slab_shape`), `dispatch` calls the compiled program, `wait` blocks until
# its output is ready. Each is a profiler span "exec/<phase>"; the input's
# also names its `layout`, "slab" or "as-is".
EXEC_PHASES = ("input", "dispatch", "wait")
# The minor dimension of a TPU memory tile (8 x 128 for 32-bit elements)
LANES = 128


def use_compile_cache() -> None:
    """Persist compiled programs across processes. JAX reads
    JAX_COMPILATION_CACHE_DIR itself when it is set; otherwise the cache
    lives at one fixed path in the checkout, so later runs find it."""
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def device_memory_bytes(device) -> int:
    """Memory a worker's page cache may divide up on `device`: the
    allocator's limit where the backend reports one. A CPU device's memory
    is the host's."""
    stats = device.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if device.platform == "cpu":
        return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    raise RuntimeError(f"{device} reports no memory limit")


def slab_shape(x) -> Optional[Tuple[int, ...]]:
    """The lane-dense shape (batch, rows, LANES) in which the host input `x`
    (an array or a ShapeDtypeStruct) crosses to the device, or None where it
    crosses as it is. A single floating-point array whose per-example
    element count is a multiple of LANES crosses as a slab: the same C-order
    bytes, which for float32 with rows a multiple of 8 are already the TPU's
    tiled layout. An image batch with 3 channels minor is not, and the
    runtime would rearrange it on the host before its copy could start.
    The batch stays a dimension of its own: folded into the rows, it made
    the v5e compiler take minutes over full-width ResNet-50's bucket 8."""
    shape = getattr(x, "shape", None)
    if (shape is None or len(shape) < 2
            or not jnp.issubdtype(x.dtype, jnp.floating)
            or math.prod(shape[1:]) % LANES):
        return None
    return shape[0], math.prod(shape[1:]) // LANES, LANES


def to_wire(x):
    """The input `x` as it crosses to the device: a reshape of it into its
    slab (a view, for an array), or `x` itself where it crosses as it is or
    is a slab already (`slab_shape`)."""
    shape = slab_shape(x)
    if shape is None or shape == tuple(x.shape):
        return x
    if isinstance(x, jax.ShapeDtypeStruct):
        return jax.ShapeDtypeStruct(shape, x.dtype)
    return x.reshape(shape)


def _from_slab(forward: Callable, shape: tuple) -> Callable:
    """`forward` taking its input as the slab of an array of `shape`, which
    it reshapes back on the device. It keeps `forward`'s name, and so the
    compiled program keeps its module name (jit_<name>)."""
    @functools.wraps(forward)
    def on_slab(params, slab):
        return forward(params, slab.reshape(shape))
    return on_slab


class Executables:
    """One compiled executable per (device, batch bucket) of a forward
    function. Copies of a model differ only in their weights, so they share
    one of these instead of compiling once each."""

    def __init__(self, forward: Callable):
        self.forward = forward
        self._exe: Dict[tuple, object] = {}
        self.compile_s: Dict[tuple, float] = {}   # (device, bucket) -> s

    def get(self, device, b: int, params, x):
        """The executable for bucket `b` on `device`, compiled on first use
        for arguments shaped like `params` and the host input `x` (arrays or
        ShapeDtypeStructs). It takes the input as `to_wire(x)`."""
        exe = self._exe.get((device, b))
        if exe is None:
            on_device = SingleDeviceSharding(device)

            def spec(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=on_device)

            forward = self.forward if slab_shape(x) is None \
                else _from_slab(self.forward, tuple(x.shape))
            t0 = time.perf_counter()
            exe = jax.jit(forward).lower(
                jax.tree.map(spec, params),
                jax.tree.map(spec, to_wire(x))).compile()
            self.compile_s[(device, b)] = time.perf_counter() - t0
            self._exe[(device, b)] = exe
        return exe


class JaxModel:
    """One served model: host weights, their copies on devices, and the
    (shared) per-bucket executables. Every method that places or runs
    something names its device."""

    def __init__(self, model_id: str, forward: Callable, params,
                 make_input: Callable[[int], object], weights_bytes: int,
                 batches: Tuple[int, ...] = BUCKETS,
                 programs: Optional[Executables] = None):
        self.model_id = model_id
        self.host_params = jax.tree.map(np.asarray, params)
        self.device_params: Dict[object, object] = {}   # device -> weights
        self.make_input = make_input     # bucket -> host (numpy) input
        self._inputs: Dict[int, object] = {}
        self.weights_bytes = weights_bytes
        self.batches = tuple(sorted(batches))
        self.programs = programs if programs is not None \
            else Executables(forward)
        self._measured: Dict[Tuple[str, int], float] = {}
        self._load_s: Optional[float] = None
        self._fresh: set = set()     # keys measured in-process (not echoes)
        self.warmup_count = 0        # timed profiling measurements performed

    def load(self, device) -> float:
        t0 = time.perf_counter()
        params = jax.device_put(self.host_params, device)
        jax.block_until_ready(params)
        self.device_params[device] = params
        return time.perf_counter() - t0

    def unload(self, device):
        """Free this copy's buffers on `device` now, not whenever the
        garbage collector runs, so device memory matches the page cache."""
        for a in jax.tree.leaves(self.device_params.pop(device, None)):
            a.delete()

    def bucket(self, batch: int) -> int:
        for b in self.batches:
            if b >= batch:
                return b
        return self.batches[-1]

    def execute(self, b: int, x, device):
        """Run bucket `b` on input `x`, shaped like the bucket's input
        (`_input`) or already as it crosses (`to_wire`), on the host or
        already on `device`, with the weights held on `device`; returns the
        (not yet awaited) output. Never loads weights."""
        params = self.device_params.get(device)
        if params is None:
            raise NotLoadedError(
                f"{self.model_id} has no weights on {device}")
        exe = self.programs.get(device, b, self.host_params, self._input(b))
        x = to_wire(x)
        if not isinstance(x, jax.Array):
            x = jax.device_put(x, device)
        return exe(params, x)

    def run(self, batch: int, device, phases: Optional[dict] = None,
            gpu: Optional[int] = None) -> float:
        """One EXEC: copy a `batch`-bucket input to `device` and run the
        program there. Returns its wall time, which is all the work the
        action does, so the profile it feeds covers the whole action.
        Each phase (EXEC_PHASES) is a profiler span that names the lane's
        `gpu` (default: the device's id), the copy and the bucket; its
        seconds go into `phases` where one is given, and `slab_n` there is
        1 where the input crossed as a slab."""
        b = self.bucket(batch)
        x = self._input(b)
        wire = to_wire(x)
        layout = "as-is" if wire is x else "slab"
        args = {"gpu": device.id if gpu is None else gpu,
                "copy": self.model_id, "bucket": b}
        t0 = time.perf_counter()
        with TraceAnnotation("exec/input", layout=layout, **args):
            x = jax.device_put(wire, device)
        t1 = time.perf_counter()
        with TraceAnnotation("exec/dispatch", **args):
            out = self.execute(b, x, device)
        t2 = time.perf_counter()
        with TraceAnnotation("exec/wait", **args):
            jax.block_until_ready(out)
        t3 = time.perf_counter()
        if phases is not None:
            phases["input"] = t1 - t0
            phases["dispatch"] = t2 - t1
            phases["wait"] = t3 - t2
            phases["slab_n"] = int(layout == "slab")
        return t3 - t0

    def _input(self, b: int):
        """The host input of bucket `b`, as the model takes it: one seeded
        payload per bucket stands in for the requests' own."""
        x = self._inputs.get(b)
        if x is None:
            x = self._inputs[b] = self.make_input(b)
        return x

    def compile(self, devices):
        """AOT-compile every batch bucket for each device and make the
        bucket's input, untimed and without loading weights — compilation
        is not warmup re-measurement (paper §5.1: kernels are compiled
        ahead of time; profiles come from the ProfileStore)."""
        for d in devices:
            for b in self.batches:
                self.programs.get(d, b, self.host_params, self._input(b))

    def warm(self, devices) -> None:
        """Run every bucket once on each device, so that no first-run cost
        (loading the program onto the device, growing its allocator) lands
        in a served EXEC. Leaves no weights behind."""
        for d in devices:
            self.load(d)
            try:
                for b in self.batches:
                    self.run(b, d)
            finally:
                self.unload(d)

    # ------------------------------------------------------ profiling
    def measure(self, device, reps: int = 3
                ) -> Dict[Tuple[str, int], list]:
        """Timed sweep over batch buckets on `device`; returns raw
        durations per ("INFER", batch). The first rep per bucket (compile)
        is dropped. Weights this loads for the sweep are unloaded after
        it."""
        borrowed = device not in self.device_params
        if borrowed:
            self.load(device)
        try:
            out = {}
            for b in self.batches:
                durs = [self.run(b, device) for _ in range(reps + 1)][1:]
                self.warmup_count += reps + 1
                out[("INFER", b)] = durs
        finally:
            if borrowed:
                self.unload(device)
        return out

    def measure_load(self, device, reps: int = 2) -> List[float]:
        """Timed host->device weight transfers (the LOAD profile); leaves
        no weights on `device`."""
        durs = []
        for _ in range(max(1, reps)):
            self.unload(device)
            durs.append(max(self.load(device), 1e-5))
            self.warmup_count += 1
        self.unload(device)
        self._load_s = float(np.median(durs))
        self._fresh.add(("LOAD", 1))
        return durs

    def warmup(self, device, reps: int = 3):
        for (t, b), durs in self.measure(device, reps=reps).items():
            self._measured[(t, b)] = float(np.median(durs))
            self._fresh.add((t, b))

    def apply_profile(self, entries: Dict[Tuple[str, int], float]):
        """Seed measurements from persisted profiles — {("INFER", batch)
        or ("LOAD", 1): seconds} — so no warmup re-measurement happens."""
        for (t, b), d in entries.items():
            if t == "LOAD":
                self._load_s = float(d)
            else:
                self._measured[(t, b)] = float(d)
            self._fresh.discard((t, b))

    def seed_from_store(self, store: ProfileStore) -> bool:
        """Seed from a ProfileStore; returns False (and seeds nothing) if
        any of this model's batch buckets is missing from the store."""
        entries = {}
        for b in self.batches:
            p = store.get("INFER", self.model_id, b)
            if p is None:
                return False
            entries[("INFER", b)] = p.estimate
        lp = store.get("LOAD", self.model_id, 1)
        if lp is not None:
            entries[("LOAD", 1)] = lp.estimate
        self.apply_profile(entries)
        return True

    def seed_profiles(self, device) -> dict:
        """The INFER and LOAD profiles, measuring on `device` whatever no
        store seeded."""
        if not self._measured:
            self.warmup(device)
        if self._load_s is None:
            self.measure_load(device, reps=1)
        return self._profiles()

    def _profiles(self) -> dict:
        out = {("INFER", self.model_id, b): d
               for (_, b), d in self._measured.items()}
        if self._load_s is not None:
            out[("LOAD", self.model_id, 1)] = self._load_s
        return out

    def fresh_profiles(self) -> dict:
        """The profiles measured in this process — store-seeded echoes are
        excluded, so folding these back into a ProfileStore can never
        recycle its own estimates."""
        return {(t, mid, b): d for (t, mid, b), d in self._profiles().items()
                if (t, b) in self._fresh}

    def modeldef(self) -> ModelDef:
        """The worker's ground truth: weight bytes and the INFER profile
        as far as it is known."""
        return ModelDef(model_id=self.model_id,
                        weights_bytes=self.weights_bytes,
                        exec_latency={("INFER", b): d for (_, b), d
                                      in self._measured.items()})


class JaxBackend:
    """Worker backend that actually executes (RealClock mode): `gpu_id` g
    is `devices[g]`."""

    realtime = True
    exec_phases = EXEC_PHASES

    def __init__(self, models: Dict[str, JaxModel], devices):
        self.models = models
        self.devices = list(devices)

    def describe(self, gpu_id: int) -> dict:
        d = self.devices[gpu_id]
        return {"platform": d.platform, "kind": d.device_kind}

    def load_duration(self, model: ModelDef, gpu_id: int) -> float:
        return self.models[model.model_id].load(self.devices[gpu_id])

    def unload(self, model: ModelDef, gpu_id: int) -> None:
        self.models[model.model_id].unload(self.devices[gpu_id])

    def exec_duration(self, model: ModelDef, action,
                      phases: Optional[dict] = None) -> float:
        return self.models[model.model_id].run(
            action.batch_size, self.devices[action.gpu_id], phases,
            gpu=action.gpu_id)


def seed_engines(engines: Dict[str, JaxModel], device,
                 store: Optional[ProfileStore] = None) -> dict:
    """Seed every engine's profiles — from `store` when it covers the
    engine's buckets (zero warmup re-measurement), measuring on `device`
    otherwise — and return the combined (type, model, batch) -> secs dict
    that `Controller.add_worker(profiles=...)` takes."""
    profiles = {}
    for e in engines.values():
        if store is not None:
            e.seed_from_store(store)
        profiles.update(e.seed_profiles(device))
    return profiles


def update_store(engines: Dict[str, JaxModel], store: ProfileStore,
                 controller=None) -> ProfileStore:
    """Shutdown path: fold measured engine profiles and (optionally) the
    controller's live telemetry back into the persistent store.

    Only values actually measured this run (fresh_profiles) are folded —
    a store-seeded engine's seed_profiles() merely echoes the store's own
    estimates, and folding those back would let stale values masquerade
    as fresh samples. Live telemetry is folded from the Recorder only:
    the ActionProfiler's windows hold the same durations and would
    double-count them.
    """
    for e in engines.values():
        for (t, mid, b), d in e.fresh_profiles().items():
            store.update(t, mid, b, [d])
    if controller is not None:
        store.update_from_recorder(controller.recorder)
    return store


def make_resnet_model(model_id: str, scale: int = REDUCED["scale"],
                      img: int = REDUCED["img"], batches=BUCKETS,
                      seed: int = 0,
                      programs: Optional[Executables] = None) -> JaxModel:
    """ResNet-50 (the paper's evaluation model) with seeded weights:
    published widths at `scale=1` on 224-pixel inputs, narrower for CPU
    runs. Weights are made on the host CPU, where they live between
    LOADs."""
    spec = resnet50_spec(num_classes=1000, scale=scale)
    with jax.default_device(jax.devices("cpu")[0]):
        params = pspec.materialize(spec, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)

    def make_input(b):
        return rng.standard_normal((b, img, img, 3), dtype=np.float32)

    return JaxModel(model_id, resnet50_forward, params, make_input,
                    weights_bytes=pspec.param_bytes(spec), batches=batches,
                    programs=programs)


def resnet_fleet(n_models: int, scale: int, img: int,
                 seed: int = 0) -> Dict[str, JaxModel]:
    """`n_models` ResNet-50 copies m0..m{n-1}, each with its own seeded
    weights, sharing one set of compiled programs."""
    programs = Executables(resnet50_forward)
    return {f"m{i}": make_resnet_model(f"m{i}", scale, img, seed=seed + i,
                                       programs=programs)
            for i in range(n_models)}


def resnet_fleet_defs(n_models: int, scale: int) -> Dict[str, ModelDef]:
    """The controller's view of `resnet_fleet`: the same ids and weight
    bytes, computed from ParamSpec shapes without a JAX backend."""
    nbytes = pspec.param_bytes(resnet50_spec(num_classes=1000, scale=scale))
    return {f"m{i}": ModelDef(f"m{i}", weights_bytes=nbytes, exec_latency={})
            for i in range(n_models)}


def check_logits(model: JaxModel, device) -> dict:
    """Bucket-1 logits of `model` served on `device` for its seeded input,
    against a float32 forward of the same weights on the host CPU, by
    relative L2 error. A bf16 result that agrees to LOGITS_RTOL passes;
    computing below bf16 (or a wrong program) does not."""
    x = model.make_input(1)
    model.load(device)
    try:
        out = np.asarray(model.execute(1, x, device), np.float32)
    finally:
        model.unload(device)
    ref_params = jax.tree.map(lambda a: np.asarray(a, np.float32),
                              model.host_params)
    with jax.default_device(jax.devices("cpu")[0]), \
            jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(model.programs.forward)(ref_params, x))
    rel = float(np.linalg.norm(out - ref) / np.linalg.norm(ref))
    return {"rel_l2": rel, "rtol": LOGITS_RTOL, "shape": list(out.shape),
            "finite": bool(np.isfinite(out).all()),
            "ok": bool(np.isfinite(out).all() and rel <= LOGITS_RTOL)}


def make_lm_decode_model(model_id: str, arch: str = "qwen2-0.5b",
                         batches=(1, 2, 4, 8), ctx: int = 128,
                         seed: int = 0) -> JaxModel:
    """Reduced LM whose INFER action is one DECODE step (continuous-batching
    unit) — the Clockwork-for-LLMs adaptation (DESIGN.md §2)."""
    from repro.configs import get_smoke_config
    from repro.models.registry import get_bundle
    cfg = get_smoke_config(arch)
    bundle = get_bundle(cfg)
    params = bundle.init(jax.random.PRNGKey(seed))

    def forward(p, x):
        # one decode step against a ctx-sized cache (latency-equivalent to
        # steady-state decode; cache contents don't affect the compute cost)
        tokens, cur = x
        cache = bundle.init_cache(tokens.shape[0], ctx)
        logits, _ = bundle.decode(p, cache, tokens, cur)
        return logits

    def make_input(b):
        return (np.zeros((b, 1), np.int32), np.asarray(ctx // 2, np.int32))

    return JaxModel(model_id, forward, params, make_input,
                    weights_bytes=pspec.param_bytes(bundle.spec()),
                    batches=batches)
