"""One open-loop generator process of a benchmark run.

    python bench/loadgen.py --controller HOST:PORT --index K --count G \
        --seed S --rate R --n-models M --slo-s 0.1 --warm-s W --window-s D \
        --start T --out FILE

Draws the run's whole schedule from the seed (arrivals.py), keeps every
G-th request from the K-th, and sends each through the program's
`RemoteClient` when it falls due. `--start` is the due time of the
schedule's first instant on `time.monotonic()`, which every process of a
run shares. Each request's latency is its response's receipt minus its
due time, so a generator that falls behind counts its own lateness; the
lateness itself (send minus due) is recorded too.

It writes one JSON object to `--out` with, for the window's requests:
counts by outcome, the latencies of the ok responses, every send's
lateness, the requests that missed their SLO per 100 ms of due time,
each ok request's `net_overhead` (client total minus the controller's
admission-to-completion), and the protocol faults it saw: responses
never received, duplicated, or naming another model.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import sys
import time

from repro.core.actions import Request
from repro.core.clock import EventLoop, RealClock, RealtimePump
from repro.runtime.client import RemoteClient
from repro.runtime.transport import tcp_connect
from repro.telemetry.recorder import Recorder

import arrivals

CONNECT_TIMEOUT_S = 30.0
# a window request with no answer this long after the window closed is
# counted as never answered
ANSWER_WAIT_S = 60.0
MISS_BIN_S = 0.1    # the window's misses are counted in bins of due time


class Generator:
    """Sends one share of the schedule and keeps what came back."""

    def __init__(self, loop: EventLoop, client: RemoteClient, mine: list,
                 base: float, slo_s: float):
        self.loop = loop
        self.client = client
        self.mine = mine            # [(due offset, model index, window)]
        self.base = base            # loop time of offset 0
        self.slo_s = slo_s
        self.sent: dict = {}        # request id -> [due, send, model, window]
        self.got: dict = {}         # request id -> (receipt, status, model)
        self.frames = collections.Counter()   # RESPONSE frames per id
        self.window_open = 0        # window requests not yet answered
        self.done_sending = not mine
        if mine:
            loop.schedule(base + mine[0][0], lambda: self._send(0))

    def _send(self, j: int) -> None:
        due_off, m, window = self.mine[j]
        now = self.loop.now()
        req = Request(model_id=f"m{m}", arrival=now, slo=self.slo_s)
        self.sent[req.id] = (self.base + due_off, now, req.model_id, window)
        self.window_open += window
        self.client.submit(req)
        if j + 1 < len(self.mine):
            self.loop.schedule(self.base + self.mine[j + 1][0],
                               lambda: self._send(j + 1))
        else:
            self.done_sending = True

    def count_frame(self, msg: dict) -> None:
        if msg.get("kind") == "response":
            self.frames[msg["request"]["id"]] += 1

    def on_response(self, resp: Request) -> None:
        rec = self.sent.get(resp.id)
        if rec is None or resp.id in self.got:
            return
        self.got[resp.id] = (self.loop.now(), resp.status, resp.model_id)
        self.window_open -= rec[3]

    def finished(self) -> bool:
        return self.done_sending and self.window_open == 0

    def report(self, window_start: float) -> dict:
        """The window's requests; `window_start` is the window's first
        due time on this process's loop clock."""
        spans = {s.request_id: s for s in self.client.recorder.iter_spans()}
        status = collections.Counter()
        misses = collections.Counter()
        lat, lag, net = [], [], []
        ok_in_slo = unanswered = wrong_model = 0
        for rid, (due, send, model, window) in self.sent.items():
            if not window:
                continue
            lag.append(send - due)
            got = self.got.get(rid)
            if got is None:
                unanswered += 1
                misses[int((due - window_start) / MISS_BIN_S)] += 1
                continue
            t_recv, st, resp_model = got
            status[st or "none"] += 1
            wrong_model += resp_model != model
            met = st == "ok" and t_recv - due <= self.slo_s
            ok_in_slo += met
            if not met:
                misses[int((due - window_start) / MISS_BIN_S)] += 1
            if st == "ok":
                lat.append(t_recv - due)
                span = spans.get(rid)
                if span is not None:
                    net.append(span.net_overhead)
        window_ids = [rid for rid, rec in self.sent.items() if rec[3]]
        return {"attempted": len(window_ids), "ok_in_slo": ok_in_slo,
                "status": dict(status), "unanswered": unanswered,
                "duplicates": sum(max(0, self.frames[rid] - 1)
                                  for rid in window_ids),
                "wrong_model": wrong_model, "latency_ok_s": lat,
                "lag_s": lag, "net_overhead_s": net,
                "misses": sorted(misses.items())}


def connect(host: str, port: int, post):
    t0 = time.monotonic()
    while True:
        try:
            return tcp_connect(host, port, post)
        except OSError:
            if time.monotonic() - t0 >= CONNECT_TIMEOUT_S:
                raise
            time.sleep(0.1)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--controller", required=True)
    p.add_argument("--index", type=int, required=True)
    p.add_argument("--count", type=int, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rate", type=float, required=True)
    p.add_argument("--n-models", type=int, required=True)
    p.add_argument("--popularity", default="uniform")
    p.add_argument("--slo-s", type=float, required=True)
    p.add_argument("--warm-s", type=float, required=True)
    p.add_argument("--window-s", type=float, required=True)
    p.add_argument("--start", type=float, required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    # A full collection over this process's requests and spans stalls the
    # sends for tens of milliseconds, and the stall would count against
    # the system. Nothing here makes cycles; the process lives a minute.
    gc.disable()

    sched = arrivals.schedule(args.seed, args.rate, args.warm_s,
                              args.window_s, args.n_models, args.popularity)
    mine = sched[args.index::args.count]
    host, _, port = args.controller.rpartition(":")
    loop = EventLoop(RealClock())
    pump = RealtimePump(loop, max_poll=0.005)
    channel = connect(host, int(port), pump.post)
    # one span per request of warm-up and window, with room to spare
    client = RemoteClient(loop, channel,
                          recorder=Recorder(capacity=2 * len(mine) + 1024))
    base = args.start - (time.monotonic() - loop.now())
    gen = Generator(loop, client, mine, base, args.slo_s)
    client.attach([gen])
    on_message = channel.on_message

    def counted(msg):
        gen.count_frame(msg)
        on_message(msg)

    channel.on_message = counted
    close_by = base + args.warm_s + args.window_s + ANSWER_WAIT_S
    pump.run(until=gen.finished, timeout=max(0.0, close_by - loop.now()))
    client.close()
    with open(args.out, "w") as f:
        json.dump(gen.report(base + args.warm_s), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
