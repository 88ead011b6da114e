"""Closed-loop rounds over HTTP (traffic ``"driver": "http_closed"``).

Each round, every tenant's round is submitted to the edge server, then
all of its ``clients_per_round`` uploads start at once, one keep-alive
connection each, in the order the seed draws; the next round starts
once every fused vector of the round is on the host. The update vectors
are made once in set-up; sample counts and order are drawn per round
(``schedule.round_uploads``). Rounds run back to back from the window's
start, and none starts after its end; the last one runs to its end.
"""
from __future__ import annotations

import itertools
import queue
import sys
import threading
import time
from pathlib import Path

# each round's uploads are recorded with it, so it can be checked as it ends
CHECKS_EACH_ROUND = True

ROOT = Path(__file__).resolve().parents[2]


def run(ctx):
    from bench import harness, sut
    from bench.child import Child

    tr = ctx.cell.traffic
    wait_s = tr["client_timeout_s"] + 60
    s = sut.Session(ctx)
    gen = None
    rounds = itertools.count()
    try:
        gen = Child(Path(__file__), {
            "port": s.system.port, "tokens": s.system.tokens,
            "seed": ctx.seed, "config": ctx.cell.config, "traffic": tr})
        gen.recv(timeout=600)   # the update vectors are made

        def one_round() -> None:
            pending = [s.system.submit(t) for t in s.system.tenants]
            gen.send({"round": next(rounds)})
            recs = [s.system.finish(p) for p in pending]
            ups = gen.recv(timeout=wait_s)["uploads"]
            s.record(recs, [harness.UploadRec(due=None, **u) for u in ups])

        def drive(w1: float) -> None:
            while time.monotonic() < w1:
                one_round()
                s.traced.stop_if_due()

        for _ in range(tr["warmup_rounds"]):
            one_round()
        s.measure(time.monotonic(), drive)
        s.read_state()
    finally:
        if gen is not None:
            gen.close()
        s.close()
    return s.run()


class _Slot:
    """One client connection, and the thread that uploads on it."""

    def __init__(self, args: dict, updates: list):
        from repro.serving import HttpStoreClient

        self.client = HttpStoreClient(
            "127.0.0.1", args["port"], tokens=args["tokens"],
            timeout=args["traffic"]["client_timeout_s"])
        self.updates = updates
        self.todo: "queue.Queue" = queue.Queue()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self) -> None:
        from bench.child import upload

        try:
            while True:
                item = self.todo.get()
                if item is None:
                    return
                up, done = item
                done.put(upload(self.client, up, self.updates[up.key]))
        finally:
            self.client.close()


def child() -> None:
    from bench import payloads, schedule, sut
    from bench.child import Parent

    io = Parent()
    args = io.args
    cfg, tr, seed = args["config"], args["traffic"], args["seed"]
    n = cfg["clients_per_round"]
    updates = [sut.update_of(cfg, payloads.make(seed, cfg, k))
               for k in range(tr.get("payload_pool", n))]
    tenants = range(cfg["tenants"])
    slots = [_Slot(args, updates) for _ in range(cfg["tenants"] * n)]
    io.send({"ready": True})
    for cmd in io.commands():
        r = cmd["round"]
        per_tenant = [schedule.round_uploads(seed, cfg, tr, t, r)
                      for t in tenants]
        done: "queue.Queue" = queue.Queue()
        for i in range(n):          # the drawn order, tenants interleaved
            for t in tenants:
                slots[t * n + i].todo.put((per_tenant[t][i], done))
        io.send({"uploads": [done.get() for _ in range(len(slots))]})
    for slot in slots:
        slot.todo.put(None)
    for slot in slots:
        slot.thread.join()


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    child()
