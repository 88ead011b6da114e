"""Rounds written in-process, without HTTP (traffic ``"driver":
"store_writer"``).

One writer thread per tenant: it submits a round through the edge
server's scheduler, lands the round's ``clients_per_round`` updates with
``UpdateStore.write`` as fast as it can (``schedule.round_uploads``, the
payloads made once in set-up), waits for the fused vector, and goes on
to its next round. All tenants run at once under the scheduler's
``max_running``. The window starts once every tenant has finished
``warmup_rounds`` rounds; at its end each writer finishes its round and
stops.
"""
from __future__ import annotations

import threading
import time

# each round's writes are recorded with it, so it can be checked as it ends
CHECKS_EACH_ROUND = True


def run(ctx):
    from bench import harness, payloads, schedule, sut, tracing

    cfg, tr = ctx.cell.config, ctx.cell.traffic
    s = sut.Session(ctx)
    updates = [sut.update_of(cfg, payloads.make(ctx.seed, cfg, k))
               for k in range(tr["payload_pool"])]

    def lander(index: int, tenant: str):
        def land(r: int):
            recs = []
            with tracing.span("write_round"):
                for up in schedule.round_uploads(ctx.seed, cfg, tr, index, r):
                    sent = time.monotonic()
                    s.system.store.write(up.cid, updates[up.key],
                                         weight=float(up.weight),
                                         tenant=tenant)
                    recs.append(harness.UploadRec(
                        cid=up.cid, tenant=tenant, key=up.key,
                        weight=up.weight, due=None, sent=sent,
                        acked=time.monotonic()))
            return recs
        return land

    writers = [threading.Thread(target=s.tenant_rounds,
                                args=(t, lander(i, t)), daemon=True)
               for i, t in enumerate(s.system.tenants)]
    try:
        for t in writers:
            t.start()
        s.wait_warm(tr["warmup_rounds"])
        s.measure(time.monotonic())
        for t in writers:
            t.join(timeout=cfg["service"]["monitor_timeout"] + 60)
        s.read_state()
    finally:
        s.close()
    return s.run()
