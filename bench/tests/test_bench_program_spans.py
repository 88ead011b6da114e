"""The program's ``repro.*`` spans: what a round and an upload emit under
a profiler session on the CPU, and what the readers of ``bench/layers.py``
make of them, on real traces and on hand-made spans."""
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, layers, tracing
from bench.harness import RoundRec

ROUND_SPANS = ["repro.monitor.wait", "repro.store.load",
               "repro.engine.stage", "repro.engine.device_wait",
               "repro.engine.step", "repro.engine.copyout",
               "repro.engine.finalize", "repro.store.consume"]


def _traced(tmp_path, work):
    """Run ``work()`` under a profiler session; its result and the
    program spans it emitted."""
    prof = tracing.Profile(str(tmp_path))
    prof.start()
    try:
        out = work()
    finally:
        prof.stop()
    return out, layers.read_spans(prof.path(), prof.marks[tracing.START])


def test_async_store_round_spans_nest_with_their_ids(tmp_path):
    from repro.core import AggregationService, UpdateStore

    store = UpdateStore()
    svc = AggregationService(
        fusion="fedavg", local_strategy="jnp", store=store,
        threshold_frac=1.0, monitor_timeout=10.0,
        stream_chunk_bytes=2 * 4000)        # two rows a block
    rng = np.random.default_rng(0)
    for i in range(6):
        store.write(f"c{i}", rng.normal(size=1000).astype(np.float32),
                    tenant="ta")
    t0 = time.monotonic()
    (fused, rep), found = _traced(tmp_path, lambda: svc.aggregate(
        from_store=True, async_round=True, expected_clients=6,
        tenant="ta"))
    t1 = time.monotonic()
    assert rep.n_clients == 6 and rep.round_id >= 1
    (rnd,) = [s for s in found if s.name == "repro.round"]
    assert rnd.stats == {"tenant": "ta", "round": rep.round_id}
    assert t0 <= rnd.start < rnd.end <= t1
    names = [s.name for s in found]
    for name in ROUND_SPANS + ["repro.engine.compile"]:
        assert name in names, name
    assert names.count("repro.store.load") == 3
    assert names.count("repro.engine.step") == 3
    assert names.count("repro.engine.device_wait") == 4   # 3 steps, finalize
    for s in found:
        # one thread, inside the round, with the round's ids
        assert s.thread == rnd.thread, s.name
        assert rnd.start <= s.start <= s.end <= rnd.end, s.name
        assert s.stats["tenant"] == "ta" and \
            s.stats["round"] == rep.round_id, s.name
    (compile_,) = [s for s in found if s.name == "repro.engine.compile"]
    assert isinstance(compile_.stats["key"], str) and compile_.stats["key"]
    # the compile ran inside a block's staging, not beside it
    assert any(s.start <= compile_.start and compile_.end <= s.end
               for s in found if s.name == "repro.engine.stage")
    # each step waited for the device before it ran
    steps = sorted(s.start for s in found if s.name == "repro.engine.step")
    waits = sorted(s.end for s in found
                   if s.name == "repro.engine.device_wait")
    assert all(w <= st for w, st in zip(waits, steps))
    # self time: the round less its direct children, which the other
    # spans tile without overlap
    direct = [s for s in found if s is not rnd and not any(
        p is not rnd and p is not s and p.start <= s.start
        and s.end <= p.end for p in found)]
    own = sum(b - a for a, b in rnd.own)
    assert own == pytest.approx(
        rnd.seconds - sum(s.seconds for s in direct), abs=1e-9)
    assert 0 < own < rnd.seconds


def test_round_ids_count_up_per_service():
    from repro.core import AggregationService

    svc = AggregationService(fusion="fedavg", local_strategy="jnp")
    ups = [np.ones(8, np.float32), np.zeros(8, np.float32)]
    ids = [svc.aggregate(updates=ups)[1].round_id for _ in range(3)]
    assert ids == [ids[0], ids[0] + 1, ids[0] + 2]


def test_http_upload_spans_carry_tenant_and_client(tmp_path):
    from repro.core import UpdateStore
    from repro.serving import HttpStoreClient, IngestServer

    store = UpdateStore()
    with IngestServer(store, {"tok-a": "appa"}) as srv:
        cli = HttpStoreClient("127.0.0.1", srv.port, token="tok-a")
        _, found = _traced(tmp_path, lambda: cli.write(
            "c7", np.arange(3000, dtype=np.float32), tenant="appa"))
    by = {s.name: s for s in found}
    read, parse, ack = (by["repro.frontend.read"],
                        by["repro.frontend.parse"],
                        by["repro.frontend.ack_wait"])
    commit = by["repro.ingest.commit"]
    assert read.stats == {"tenant": "appa"}
    assert parse.stats == ack.stats == {"tenant": "appa", "client": "c7"}
    assert commit.stats["n"] == 1 and commit.stats["queue_wait_s"] >= 0
    # three spans in order on the handler's thread; the commit on the
    # committer's, inside the handler's wait for it
    assert read.thread == parse.thread == ack.thread != commit.thread
    assert read.end <= parse.start and parse.end <= ack.start
    assert ack.start <= commit.start and commit.end <= ack.end


def test_benchmark_reduction_leaves_program_spans_out(tmp_path):
    """The program's spans change nothing the existing reduction (and so
    no existing metric) reads: no op, busy interval or benchmark span."""
    from repro.utils import spans

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    prof = tracing.Profile(str(tmp_path))
    prof.start()
    t0 = time.monotonic()
    with spans.span("engine.step", tenant="t"):
        f(x).block_until_ready()
    t1 = time.monotonic()
    with tracing.span("fetch"):
        f(x).block_until_ready()
    prof.stop()
    red = tracing.reduce(prof.path(), prof.marks)
    assert not any(name.startswith("repro") for name in red.ops)
    assert [s[0] for s in red.spans] == ["fetch"]
    found = layers.read_spans(prof.path(), prof.marks[tracing.START])
    (step,) = found
    assert step.name == "repro.engine.step" and step.stats == {"tenant": "t"}
    assert t0 - 1e-3 <= step.start < step.end <= t1 + 1e-3


# -- the readers, by hand -------------------------------------------------------


def _span(name, a, b, thread, **stats):
    return layers.Span(name, a, b, ("/host:CPU", thread), stats)


def _hand_run(monkeypatch, found, rounds, window=(0.0, 10.0)):
    layers._own(found)
    monkeypatch.setattr(layers, "spans", lambda run: found)
    return types.SimpleNamespace(trace=types.SimpleNamespace(window=window),
                                 rounds=rounds)


def _read(name, run):
    return harness.load_module(
        harness.BENCH / "metrics" / f"{name}.py").read(run)


def test_close_readers_give_exact_self_times(monkeypatch):
    a, b = dict(tenant="a", round=2), dict(tenant="b", round=1)
    found = [
        # tenant a: its round opens 1 s into its close [2, 6]
        _span("repro.round", 3.0, 5.5, 1, **a),
        _span("repro.store.load", 3.2, 3.6, 1, **a),
        _span("repro.engine.stage", 3.6, 4.0, 1, **a),
        _span("repro.engine.compile", 3.7, 3.9, 1, **a),
        _span("repro.engine.device_wait", 4.0, 4.5, 1, **a),
        _span("repro.engine.step", 4.5, 5.0, 1, **a),
        _span("repro.engine.copyout", 5.0, 5.1, 1, **a),
        _span("repro.engine.finalize", 5.1, 5.3, 1, **a),
        _span("repro.store.consume", 5.3, 5.4, 1, **a),
        # tenant b: open before its close [1, 4], a load straddling its
        # start and a step straddling its end
        _span("repro.round", 0.0, 4.5, 2, **b),
        _span("repro.store.load", 0.5, 1.5, 2, **b),
        _span("repro.engine.step", 3.5, 4.5, 2, **b),
    ]
    rounds = [RoundRec(tenant="a", started=0.0, closed=2.0, on_host=6.0),
              RoundRec(tenant="b", started=0.0, closed=1.0, on_host=4.0),
              # a close the traced sub-window cuts: left out
              RoundRec(tenant="a", started=9.0, closed=9.5, on_host=11.0)]
    run = _hand_run(monkeypatch, found, rounds)
    want = {
        "close_slot_wait_ms": (1.0 + 0.0) / 2,
        "close_store_ms": ((0.4 + 0.1) + 0.5) / 2,
        "close_device_wait_ms": (0.5 + 0.0) / 2,
        # the stage less the compile inside it, and the copy-out
        "close_engine_host_ms": ((0.4 - 0.2) + 0.1 + 0.0) / 2,
        "close_device_call_ms": ((0.5 + 0.2) + 0.5) / 2,
    }
    for name, secs in want.items():
        assert _read(name, run) == pytest.approx(1e3 * secs), name


def test_upload_readers_by_hand(monkeypatch):
    found = [
        _span("repro.frontend.read", 1.0, 1.1, 1, tenant="t"),
        _span("repro.frontend.read", 2.0, 2.3, 2, tenant="t"),
        _span("repro.frontend.read", 9.9, 10.5, 3, tenant="t"),  # cut off
        _span("repro.frontend.parse", 1.1, 1.15, 1, tenant="t", client="x"),
        _span("repro.ingest.commit", 3.0, 3.1, 9, n=2, queue_wait_s=0.3),
        _span("repro.ingest.commit", 4.0, 4.4, 9, n=1, queue_wait_s=0.0),
    ]
    run = _hand_run(monkeypatch, found, [])
    for suffix in ("", ".silo"):
        assert _read("upload_read_ms" + suffix, run) == pytest.approx(200)
        assert _read("upload_parse_ms" + suffix, run) == pytest.approx(50)
        assert _read("store_commit_ms" + suffix, run) == \
            pytest.approx(1e3 * (2 * 0.1 + 0.4) / 3)
        assert _read("ingest_queue_wait_ms" + suffix, run) == \
            pytest.approx(1e3 * 0.3 / 3)


NEW = ["close_slot_wait_ms", "close_store_ms", "close_device_wait_ms",
       "close_engine_host_ms", "close_device_call_ms", "upload_read_ms",
       "upload_parse_ms", "ingest_queue_wait_ms", "store_commit_ms",
       "upload_read_ms.silo", "upload_parse_ms.silo",
       "ingest_queue_wait_ms.silo", "store_commit_ms.silo"]


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_without_program_spans(monkeypatch, name):
    """A program that emits no spans reads None, untraced or traced."""
    rounds = [RoundRec(tenant="a", started=0.0, closed=2.0, on_host=6.0)]
    untraced = types.SimpleNamespace(
        trace=None, rounds=rounds,
        ctx=types.SimpleNamespace(trace_dir=None))
    assert _read(name, untraced) is None
    assert _read(name, _hand_run(monkeypatch, [], rounds)) is None
