"""Predictable worker (§4.4, §5.2).

One executor per (GPU/chip-slice, resource class): EXEC runs one inference at
a time (on TPU this is native — an XLA program owns the chip); LOAD owns the
host->HBM DMA path. Executors dequeue chronologically by `earliest`, wait
until `earliest`, and reject actions whose `latest` has passed — workers never
queue best-effort work, which is what stops stragglers from cascading.

Backends supply durations:
  * SimBackend — profile tables + configurable noise/spikes (C3), virtual time
  * JaxBackend (serving/engine.py) — actually executes JAX programs on the
    device `gpu_id` names and returns measured wall time (RealClock)

Both answer `load_duration(model, gpu_id)`, `unload(model, gpu_id)`,
`exec_duration(model, action)` and `describe(gpu_id)` (the device's
platform and kind, sent in HELLO). A realtime backend that times the
phases of its EXEC names them in `exec_phases`; its `exec_duration` then
takes a third argument, a dict it puts each phase's seconds in, and each
EXEC executor sums them beside its busy time (`Executor.phase_s`). The
dict's `slab_n`, where the backend sets it, is 1 for an EXEC whose input
crossed to the device as a lane-dense slab; the executor counts those
(`Executor.slab_n`).

A realtime backend's actions take wall time, so a Worker runs each of its
executors on a thread of its own and hands every end back through `post`,
a thread-safe way onto its loop's thread (`RealtimePump.post`). A
worker's lanes then overlap as the controller plans them: LOAD beside
EXEC, and each device beside the others.
"""
from __future__ import annotations

import dataclasses
import heapq
import itertools
import queue
import random
import threading
from typing import Callable, Dict, Optional, Tuple

from repro.core.actions import (EXEC_TYPES, Action, ActionType, Result,
                                ResultStatus)
from repro.core.clock import EventLoop
from repro.core.pagecache import PAGE_BYTES, PageCache


@dataclasses.dataclass
class ModelDef:
    """Ground-truth model properties (the controller sees only telemetry)."""
    model_id: str
    weights_bytes: int
    exec_latency: Dict[Tuple[str, int], float]   # (action_type, batch) -> s
    input_bytes: int = 602_112                   # paper Table 1 default
    output_bytes: int = 4_096

    def pages(self, page_bytes: int = PAGE_BYTES) -> int:
        return PageCache.pages_for(self.weights_bytes, page_bytes)


class NotLoadedError(RuntimeError):
    """A backend was asked to EXEC a model its device holds no weights of
    (for example while the model's LOAD is still running)."""


def _instant() -> float:
    return 1e-5


class SimBackend:
    """Deterministic-latency execution with controllable jitter.

    noise: multiplicative gaussian sigma (DNN inference ~0.03% in the paper);
    spike_prob/spike_scale: rare external-factor delays (C3).
    """

    realtime = False

    def __init__(self, host_to_dev_bw: float = 25e9, load_fixed: float = 1e-3,
                 noise: float = 0.0003, spike_prob: float = 0.0,
                 spike_scale: float = 5.0, seed: int = 0):
        self.host_to_dev_bw = host_to_dev_bw
        self.load_fixed = load_fixed
        self.noise = noise
        self.spike_prob = spike_prob
        self.spike_scale = spike_scale
        self.rng = random.Random(seed)

    def _jitter(self, d: float) -> float:
        if self.noise:
            d *= max(0.0, self.rng.gauss(1.0, self.noise))
        if self.spike_prob and self.rng.random() < self.spike_prob:
            d *= self.spike_scale
        return d

    def load_duration(self, model: ModelDef, gpu_id: int) -> float:
        return self._jitter(self.load_fixed
                            + model.weights_bytes / self.host_to_dev_bw)

    def describe(self, gpu_id: int) -> dict:
        return {"platform": "sim", "kind": "simulated"}

    def unload(self, model: ModelDef, gpu_id: int) -> None:
        pass  # simulated weights occupy nothing but their pages

    def exec_duration(self, model: ModelDef, action: Action) -> float:
        key = (action.type.value, action.batch_size)
        if key not in model.exec_latency:
            # interpolate: nearest known batch scaled linearly
            known = sorted(b for (t, b) in model.exec_latency
                           if t == action.type.value)
            if not known:
                raise KeyError(key)
            b0 = min(known, key=lambda b: abs(b - action.batch_size))
            base = model.exec_latency[(action.type.value, b0)]
            d = base * action.batch_size / b0
        else:
            d = model.exec_latency[key]
        return self._jitter(d)


class Executor:
    """Serial action executor with [earliest, latest] window enforcement."""

    def __init__(self, worker: "Worker", gpu_id: int, name: str):
        self.worker = worker
        self.gpu_id = gpu_id
        self.name = name
        self.q = []                      # heap: (earliest, seq, action)
        self._seq = itertools.count()
        self.busy = False
        self.total_busy = 0.0            # utilization telemetry
        self.lane = _Lane(self) if worker.backend.realtime else None
        # a realtime EXEC lane's seconds of total_busy in each phase the
        # backend times, and the rest of its busy time under "other": they
        # add up to total_busy. None where the backend times no phases.
        phases = getattr(worker.backend, "exec_phases", ())
        self.phase_s = dict.fromkeys(phases + ("other",), 0.0) \
            if phases and name == "EXEC" and self.lane else None
        # and its count of EXECs whose input crossed as a slab
        self.slab_n = None if self.phase_s is None else 0

    def submit(self, action: Action):
        heapq.heappush(self.q, (action.earliest, next(self._seq), action))
        self._poll()

    def _poll(self):
        loop = self.worker.loop
        if self.busy or not self.worker.alive:
            return
        while self.q:
            earliest, _, action = self.q[0]
            now = loop.now()
            if now < earliest - 1e-9:
                wake = earliest
                heapq.heappop(self.q)
                heapq.heappush(self.q, (earliest, next(self._seq), action))
                loop.schedule(wake, self._poll)
                return
            heapq.heappop(self.q)
            if now > action.latest + 1e-9:
                self.worker.emit_result(action, ResultStatus.REJECTED_LATE,
                                        now, now, 0.0)
                continue
            phases = None if self.phase_s is None else {}
            status, work = self.worker.perform(action, phases)
            if status is not ResultStatus.SUCCESS:
                self.worker.emit_result(action, status, now, now, 0.0)
                continue
            self.busy = True
            if self.lane is not None:   # ends in _end
                self.lane.jobs.put((action, now, work, phases))
                return
            duration = work()
            end = now + duration
            self.total_busy += duration

            def _done(a=action, t0=now, t1=end, d=duration):
                self.busy = False
                self.worker.finish(a)
                self.worker.emit_result(a, ResultStatus.SUCCESS, t0, t1, d)
                self._poll()

            loop.schedule(end, _done)
            return

    def _end(self, action: Action, t0: float, t1: float,
             err: Optional[ResultStatus], phases: Optional[dict] = None):
        """A realtime action ran from t0 to t1 (called on the loop's
        thread); its duration is all the time it held this executor, of
        which `phases` holds the parts the backend timed."""
        self.busy = False
        if err is None:
            self.total_busy += t1 - t0
            if phases is not None:
                self.slab_n += phases.pop("slab_n", 0)
                ps = self.phase_s
                for k, v in phases.items():
                    ps[k] += v
                ps["other"] += (t1 - t0) - sum(phases.values())
            self.worker.finish(action)
            self.worker.emit_result(action, ResultStatus.SUCCESS, t0, t1,
                                    t1 - t0)
        else:
            self.worker.emit_result(action, err, t0, t1, 0.0)
        self._poll()


class _Lane(threading.Thread):
    """One executor's thread: runs its realtime actions in order and hands
    each end back to the loop's thread through the worker's `post`. Any
    failure but NotLoadedError is raised again on the loop's thread, so no
    action fails quietly."""

    def __init__(self, ex: Executor):
        super().__init__(name=f"{ex.worker.worker_id}/gpu{ex.gpu_id}/"
                              f"{ex.name}", daemon=True)
        self.ex = ex
        self.jobs: "queue.SimpleQueue" = queue.SimpleQueue()
        self.start()

    def run(self):
        clock, post = self.ex.worker.loop.clock, self.ex.worker.post
        while True:
            job = self.jobs.get()
            if job is None:
                return
            action, t0, work, phases = job
            err = None
            try:
                work()
            except NotLoadedError:
                err = ResultStatus.ERROR_NOT_LOADED
            except BaseException as e:
                post(lambda e=e: _raise(e))
                return
            t1 = clock.now()    # RealClock reads time.monotonic: any thread
            post(lambda a=action, t0=t0, t1=t1, e=err, p=phases:
                 self.ex._end(a, t0, t1, e, p))


def _raise(e: BaseException):
    raise e


class Worker:
    """One worker process managing `n_gpus` accelerator slices."""

    def __init__(self, worker_id: str, loop: EventLoop,
                 backend: SimBackend, models: Dict[str, ModelDef],
                 n_gpus: int = 1, device_memory_bytes: float = 32e9,
                 reserved_bytes: float = 1e9,
                 result_delay: float = 0.0005,
                 post: Optional[Callable[[Callable[[], None]], None]] = None):
        self.worker_id = worker_id
        self.loop = loop
        self.backend = backend
        self.models = models
        if backend.realtime and post is None:
            raise ValueError("a realtime backend needs `post` onto the "
                             "loop's thread (RealtimePump.post)")
        self.post = post        # thread-safe: run a callable on loop's thread
        self.alive = True
        self.result_delay = result_delay
        self.on_result: Optional[Callable[[Result], None]] = None
        self.pagecaches = [PageCache(int(device_memory_bytes
                                         - reserved_bytes))
                           for _ in range(n_gpus)]
        self.execs: Dict[Tuple[int, str], Executor] = {}
        for g in range(n_gpus):
            self.execs[(g, "EXEC")] = Executor(self, g, "EXEC")
            self.execs[(g, "LOAD")] = Executor(self, g, "LOAD")
        self.n_gpus = n_gpus

    # -------------------------------------------------- controller-facing
    def receive(self, action: Action):
        if not self.alive:
            return
        action.received_at = self.loop.now()
        lane = "LOAD" if action.type in (ActionType.LOAD,
                                         ActionType.UNLOAD) else "EXEC"
        self.execs[(action.gpu_id, lane)].submit(action)

    def ping(self, reply: Callable[[], None]):
        if self.alive:
            self.loop.schedule_in(self.result_delay, reply)

    def fail(self):
        """Crash: drop all queued work, stop emitting results."""
        self.alive = False

    def close(self):
        """Stop the lane threads once their running actions end, so no
        device work is in flight when the process tears its backend
        down."""
        lanes = [ex.lane for ex in self.execs.values() if ex.lane]
        for lane in lanes:
            lane.jobs.put(None)
        for lane in lanes:
            lane.join()

    # -------------------------------------------------- execution
    def perform(self, action: Action, phases: Optional[dict] = None):
        """Books `action` in the page cache when it starts and returns
        (status, work): `work()` does it on the backend and returns its
        duration, and is None unless the status is SUCCESS. An EXEC's
        timed phases go to `phases` where one is given."""
        pc = self.pagecaches[action.gpu_id]
        model = self.models.get(action.model_id)
        if model is None:
            return ResultStatus.ERROR_NOT_LOADED, None
        g = action.gpu_id
        if action.type == ActionType.LOAD:
            if pc.contains(action.model_id):
                return ResultStatus.SUCCESS, _instant
            if not pc.alloc(action.model_id, model.pages(pc.page_bytes)):
                return ResultStatus.ERROR_NO_PAGES, None
            return ResultStatus.SUCCESS, \
                lambda: self.backend.load_duration(model, g)
        if action.type == ActionType.UNLOAD:
            if not pc.free(action.model_id):
                return ResultStatus.SUCCESS, _instant

            def unload():
                self.backend.unload(model, g)
                return _instant()
            return ResultStatus.SUCCESS, unload
        # EXEC family
        if not pc.contains(action.model_id):
            return ResultStatus.ERROR_NOT_LOADED, None
        pc.touch(action.model_id)
        args = (model, action) if phases is None else (model, action, phases)
        return ResultStatus.SUCCESS, \
            lambda: self.backend.exec_duration(*args)

    def finish(self, action: Action):
        pass  # hook (real backends release IO buffers here)

    def emit_result(self, action: Action, status: ResultStatus,
                    t_start: float, t_end: float, duration: float):
        if not self.alive or self.on_result is None:
            return
        r = Result(action_id=action.id, action_type=action.type,
                   model_id=action.model_id, worker_id=self.worker_id,
                   gpu_id=action.gpu_id, status=status, t_start=t_start,
                   t_end=t_end, duration=duration,
                   batch_size=action.batch_size,
                   request_ids=action.request_ids,
                   t_received=action.received_at)
        self.loop.schedule_in(self.result_delay, lambda: self.on_result(r))

    # -------------------------------------------------- runtime descriptor
    def spec(self) -> dict:
        """Wire-serializable descriptor of this worker (memory geometry and
        what each device is) — the payload a WorkerDaemon sends in its
        HELLO so the controller can build an exact PageCache mirror without
        sharing the process."""
        return {"worker_id": self.worker_id,
                "gpus": [{"total_pages": pc.total_pages,
                          "page_bytes": pc.page_bytes,
                          **self.backend.describe(g)}
                         for g, pc in enumerate(self.pagecaches)]}

    # -------------------------------------------------- telemetry
    def utilization(self, horizon: float) -> Dict[str, float]:
        out = {}
        for (g, name), ex in self.execs.items():
            out[f"gpu{g}/{name}"] = ex.total_busy / max(horizon, 1e-9)
        return out
