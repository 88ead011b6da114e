"""Open-loop uploads over HTTP (traffic ``"driver": "http_open"``).

The load generator sends each upload when the seed's Poisson schedule
says it is due (``schedule.open_loop_epoch``), whether or not earlier
uploads were acknowledged: a dispatcher hands due uploads to a pool of
``senders`` threads with one keep-alive connection each, so a slow
server makes uploads wait and never slows the schedule. An upload's
latency runs from when it was due.

Each tenant's rounds run back to back, each gated at
``clients_per_round`` arrivals. The window starts at the first epoch
boundary after every tenant has finished ``warmup_rounds`` rounds. At
its end no new round is submitted; traffic goes on until each tenant's
open round has closed, then stops.
"""
from __future__ import annotations

import math
import queue
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def run(ctx):
    from bench import harness, sut
    from bench.child import Child

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    s = sut.Session(ctx)
    gen = None
    loops = [threading.Thread(target=s.tenant_rounds, args=(t,), daemon=True)
             for t in s.system.tenants]
    try:
        gen = Child(Path(__file__), {
            "port": s.system.port, "tokens": s.system.tokens,
            "seed": ctx.seed, "config": cfg, "traffic": tr})
        gen.recv(timeout=600)   # the payload pool is made
        for t in loops:
            t.start()
        t0 = time.monotonic() + 0.2
        gen.send({"start": t0})
        s.wait_warm(tr["warmup_rounds"])
        epoch = tr["epoch_s"]
        s.measure(t0 + epoch * math.ceil((time.monotonic() - t0) / epoch))
        for t in loops:
            t.join(timeout=cfg["service"]["monitor_timeout"] + 60)
        gen.send({"stop": True})
        uploads = gen.recv(timeout=tr["client_timeout_s"] + 60)["uploads"]
        s.uploads.extend(harness.UploadRec(**u) for u in uploads)
        s.read_state()
    finally:
        if gen is not None:
            gen.close()
        s.close()
    return s.run()


def child() -> None:
    from bench import payloads, schedule, sut
    from bench.child import Parent, upload
    from repro.serving import HttpStoreClient

    io = Parent()
    args = io.args
    cfg, tr, seed = args["config"], args["traffic"], args["seed"]
    updates = [sut.update_of(cfg, payloads.make(seed, cfg, k))
               for k in range(tr["payload_pool"])]
    due: "queue.Queue" = queue.Queue()
    records = []
    records_lock = threading.Lock()

    def sender() -> None:
        client = HttpStoreClient("127.0.0.1", args["port"],
                                 tokens=args["tokens"],
                                 timeout=tr["client_timeout_s"])
        try:
            while True:
                item = due.get()
                if item is None:
                    return
                up, at = item
                rec = upload(client, up, updates[up.key])
                rec["due"] = at
                with records_lock:
                    records.append(rec)
        finally:
            client.close()

    senders = [threading.Thread(target=sender, daemon=True)
               for _ in range(tr["senders"])]
    for s in senders:
        s.start()
    io.send({"ready": True})
    commands = io.commands()
    t0 = next(commands)["start"]
    stopped = threading.Event()

    def listen() -> None:
        for cmd in commands:
            if cmd.get("stop"):
                break
        stopped.set()

    listener = threading.Thread(target=listen, daemon=True)
    listener.start()
    epoch = 0
    while not stopped.is_set():
        for up in schedule.open_loop_epoch(seed, cfg, tr, epoch):
            at = t0 + up.due
            while not stopped.is_set():
                left = at - time.monotonic()
                if left <= 0:
                    break
                stopped.wait(min(left, 0.05))
            if stopped.is_set():
                break
            due.put((up, at))
        epoch += 1
    for _ in senders:
        due.put(None)
    for s in senders:
        s.join()
    io.send({"uploads": records})
    listener.join(timeout=5.0)


if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    child()
