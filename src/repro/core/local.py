"""Single-node aggregation engines (paper §III-D1).

``jnp`` strategy  — the faithful baseline: plain dense ops on one device,
                    the analogue of the frameworks' single-threaded NumPy.
``pallas`` strategy — the TPU analogue of the paper's Numba path: the
                    streaming fused kernel (one HBM pass, VMEM tiling).

Both support *chunked streaming* for reducible fusions so a memory-capped
node can still aggregate more clients than fit at once (the knob used by
the Fig. 1/2 memory-wall benchmarks).

Compiled paths persist across rounds (the tentpole):

  * dense reducible rounds bucket the client count to the next power of
    two (zero-weight padded rows) and reuse ONE AOT-compiled executable
    per (fusion, bucket, P, dtype) — elastic rounds stop re-tracing;
  * the memory-capped path is a single ``lax.scan`` over fixed-size
    client chunks (ONE executable) instead of the seed's Python loop of
    per-chunk jit dispatches;
  * ``fuse_stream`` consumes blocks straight off an
    ``UpdateStore.iter_chunks`` iterator — the dense (n, P) matrix never
    exists on the host, nor (for rows of ``_PLACE_MIN_ROW_BYTES`` and
    more) does a (chunk, P) block: the store's rows cross to the device
    as they are and are stacked there — accumulating with one cached
    step executable.

``combine`` always runs OUTSIDE the compiled artifacts because FedAvgM /
FedAdam carry python-side server state that must advance every round.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compress import BLOCK, CompressedBlock, RowBlock
from repro.core.fusion.base import FusionAlgorithm
from repro.kernels.fused_fusion.kernel import (
    weighted_sum_dequant_pallas,
    weighted_sum_pallas,
)
from repro.kernels.robust_fusion.kernel import topk_carve_pallas
from repro.utils import spans
from repro.utils.jitcache import (
    CompiledCache, bucket_rows, fusion_cache_key, note_trace,
)

# fusions whose weighted-sum partial routes through the fused Pallas kernel
_PALLAS_WSUM = ("fedavg", "gradavg", "iteravg", "fedavgm", "fedadam")

# A row's own device_put costs a fixed time; stacking a block on the host
# costs time per byte. On a v5e host a device_put costs 0.174 ms a row,
# and stacking a 64 MiB block then moving it in one transfer runs at
# 0.77 GB/s (PERF.md, section 6): rows from 0.174 ms x 0.77 GB/s, about
# 133 kB, reach the device sooner one by one.
_PLACE_MIN_ROW_BYTES = 133_000


def _check_scale(scale) -> np.ndarray:
    """A block's optional third element must be a NUMERIC per-row scale —
    catch the easy mistake of feeding ``UpdateStore.iter_arrivals``
    (whose third element is the client-id list) to an engine directly."""
    arr = np.asarray(scale)
    if arr.dtype.kind not in "fiu":
        raise TypeError(
            "fuse_stream: blocks must be (updates, weights[, scale]) with "
            f"a numeric per-row scale, got dtype {arr.dtype}; note "
            "UpdateStore.iter_arrivals yields (block, weights, client_ids)"
            " — adapt it (as AggregationService's async round does) before"
            " streaming into an engine"
        )
    return arr


def _block_meta(block) -> Tuple[int, int, int, bool]:
    """(rows, width, logical dim, compressed) of a stream block: a
    :class:`RowBlock`, a :class:`CompressedBlock` or a dense (c, P)
    array."""
    if isinstance(block, RowBlock):
        return block.rows, block.width, block.dim, block.compressed
    if isinstance(block, CompressedBlock):
        return block.rows, int(block.codes.shape[1]), block.dim, True
    rows, width = block.shape
    return int(rows), int(width), int(width), False


def _as_rows(block) -> RowBlock:
    """A stacked block's rows as host views (a device array is copied
    to the host first)."""
    if isinstance(block, CompressedBlock):
        return RowBlock(arrays=tuple(np.asarray(block.codes)),
                        scales=np.asarray(block.scales), dim=block.dim)
    arr = np.asarray(block)
    return RowBlock(arrays=tuple(arr), scales=None, dim=int(arr.shape[1]))


@jax.jit
def _assemble_rows(*rows):
    """``chunk`` (width,) device rows -> one (chunk, width) operand. jit
    keeps one executable per (chunk, width, dtype); it is cold only
    where the stream step of that chunk, width and dtype is cold too, so
    ``CompiledCache.misses`` still flags any round that compiles."""
    note_trace()
    return jnp.stack(rows)


class _ZeroRows:
    """One device-resident zero row per (width, dtype): the pad of every
    ragged block, so no ragged size compiles or copies anything."""

    def __init__(self):
        self._lock = threading.Lock()
        self._rows: dict = {}  # guarded-by: _lock

    def get(self, width: int, dtype: np.dtype):
        with self._lock:
            row = self._rows.get((width, dtype.str))
            if row is None:
                row = jax.device_put(np.zeros((width,), dtype))
                self._rows[(width, dtype.str)] = row
        return row


@dataclasses.dataclass
class StreamReport:
    """Phase accounting for one streamed aggregation."""

    ingest_seconds: float = 0.0    # stalls waiting on store blocks
    compile_seconds: float = 0.0   # executable build (0.0 on warm rounds)
    # the step calls and finalize, each with its wait for the device
    # semaphore, plus the state copy-out: not device time alone
    compute_seconds: float = 0.0
    n_rows: int = 0
    n_blocks: int = 0
    # rows that crossed to the device as the store's rows, with no host
    # stack (the operand assembled on the device)
    rows_placed: int = 0
    chunk_rows: int = 0
    # actual payload bytes ingested (pre-padding; codes + scales for
    # compressed blocks) — what RoundReport.bytes_ingested reports
    ingest_bytes: int = 0
    # pre-finalize carry state (flat tuple of np arrays, the fusion's
    # reducer-state pytree) so async rounds can carry it forward
    acc_state: Optional[tuple] = None
    # the sum-family view of acc_state, kept populated for reducible
    # fusions (back-compat with callers that carry (wsum, tot) directly)
    acc_wsum: Optional[np.ndarray] = None
    acc_tot: float = 0.0


@dataclasses.dataclass
class LocalEngine:
    """Fuses on the local device."""

    strategy: str = "jnp"        # "jnp" | "pallas"
    memory_cap_bytes: Optional[int] = None  # simulate a memory-limited node
    # derived, not set: the Pallas kernels are written for the TPU, and
    # on any other backend they run in the Pallas interpreter
    interpret: bool = dataclasses.field(init=False)

    name: str = "local"

    def __post_init__(self):
        self.interpret = jax.default_backend() != "tpu"
        self.cache = CompiledCache(name=f"local:{self.strategy}")
        # per-THREAD compile accounting: concurrent tenants' rounds share
        # this engine, and one round's warm fold must not read another
        # round's cold compile time (or vice versa)
        self._tls = threading.local()
        self._zero_rows = _ZeroRows()

    @property
    def last_compile_seconds(self) -> float:
        """Compile seconds paid by the CURRENT thread's last fuse call
        (0.0 on warm rounds). Thread-local, so concurrent rounds on a
        shared engine each see their own compile phase."""
        return getattr(self._tls, "compile_seconds", 0.0)

    @last_compile_seconds.setter
    def last_compile_seconds(self, value: float) -> None:
        self._tls.compile_seconds = value

    # -- public --------------------------------------------------------------
    def fuse(
        self, fusion: FusionAlgorithm, updates, weights, device_sem=None,
    ) -> jnp.ndarray:
        """Dense fuse. ``device_sem`` (optional semaphore) bounds
        concurrent device execution like ``fuse_stream``'s. On the
        REDUCIBLE paths (cached executables) it is held only around
        executable invocation — a cold compile builds outside it, so
        one tenant's first-bucket compile never stalls other tenants'
        folds. The non-reducible paths compile lazily inside their
        first call, so a cold round there holds the semaphore through
        its compile (they have no AOT cache to warm separately)."""
        updates = jnp.asarray(updates)
        if weights is None:
            weights = jnp.ones((updates.shape[0],), jnp.float32)
        weights = fusion.effective_weights(jnp.asarray(weights, jnp.float32))
        n, P = updates.shape
        batch_bytes = updates.dtype.itemsize * P
        self.last_compile_seconds = 0.0
        sem = spans.DeviceSlot(device_sem)

        if self.memory_cap_bytes is not None:
            max_rows = max(int(self.memory_cap_bytes // max(batch_bytes, 1)), 1)
            if max_rows < n:
                if not fusion.streamable:
                    raise MemoryError(
                        f"{fusion.name}: {n} updates x {batch_bytes} B exceed "
                        f"the {self.memory_cap_bytes} B cap and the fusion "
                        "is not streamable — classify as DISTRIBUTED"
                    )
                if not fusion.reducible:
                    # order-statistic reducer: chunk the dense input
                    # through the streamed carve fold (bounded carry)
                    def chunks():
                        for i in range(0, n, max_rows):
                            yield updates[i: i + max_rows], \
                                weights[i: i + max_rows]

                    fused, _ = self.fuse_stream(
                        fusion, chunks(), chunk_rows=max_rows,
                        device_sem=device_sem, n_hint=n,
                    )
                    return fused
                return self._streamed(fusion, updates, weights, max_rows,
                                      device_sem)

        if fusion.reducible:
            return self._fuse_reducible_dense(fusion, updates, weights,
                                              device_sem)
        # dense order statistics run the fusion's own (XLA) sort under
        # both strategies: the carve kernel inserts rows in O(n * K) per
        # coordinate, and at the dense median K = (n-1)//2 the sort's
        # O(n log n) wins — the kernel serves streamed rounds, whose K
        # the service's robust_state_budget bounds
        with sem:
            return self._bounded(fusion.fuse(updates, weights), device_sem)

    @staticmethod
    def _bounded(out, device_sem):
        """Wait for ``out`` while a device semaphore is installed —
        async dispatch would otherwise escape the execution bound."""
        if device_sem is not None:
            jax.block_until_ready(out)
        return out

    def fuse_stream(
        self,
        fusion: FusionAlgorithm,
        blocks: Iterable[Tuple[np.ndarray, ...]],
        init: Optional[tuple] = None,
        chunk_rows: Optional[int] = None,
        device_sem=None,
        n_hint: Optional[int] = None,
    ) -> Tuple[jnp.ndarray, StreamReport]:
        """Fuse a streamable fusion from an iterator of (chunk, P) blocks
        (e.g. ``UpdateStore.iter_chunks``; ``iter_arrivals`` yields client
        ids as its third element, so adapt it — the AggregationService
        async round does — rather than feeding it here directly) without
        ever holding the dense matrix: one cached step executable folds
        each block into the fusion's reducer carry state (the reducer
        protocol in ``fusion/base.py``) — a (P,) fp32 weighted-sum pair
        for the reducible family, the O(K*P) top-k carve state for
        order-statistic fusions. ``n_hint`` (the expected client count)
        sizes order-statistic carve buffers; reducible fusions ignore it.
        Order-statistic (``fusion.weighted == False``) streams ignore
        client weights — the engine passes a 0/1 validity row — and
        reject per-row staleness scales with a ValueError.

        Blocks are ``(updates, weights)`` or ``(updates, weights, scale)``
        — the optional NUMERIC (c,) ``scale`` multiplies the EFFECTIVE
        weights, so staleness discounting bites even for fusions (IterAvg)
        that remap client weights. ``updates`` is a
        :class:`repro.core.compress.RowBlock` (the store's rows, what
        ``iter_chunks`` yields), a dense (c, P) array, OR a
        :class:`repro.core.compress.CompressedBlock` (int8 codes + fp32
        per-block scales). A RowBlock's rows of ``_PLACE_MIN_ROW_BYTES``
        and more are never stacked on the host: they cross to the device
        in one batched ``device_put`` and one cached executable per
        (chunk, width, dtype) assembles the (chunk, width) operand there
        (``StreamReport.rows_placed`` counts them); smaller rows, for
        which a transfer each costs more than a host copy, are stacked
        on the host in one copy. Compressed blocks fold WITHOUT host
        dequantization — the pallas strategy folds the scales into the
        weighted-sum kernel, the jnp strategy into the einsum — and a
        single round may freely mix dense and compressed blocks
        (stragglers may be uncompressed): each payload kind gets its own
        cached step executable (the compile cache is keyed by payload
        dtype/shape), all folding into ONE shared (P,) fp32 accumulator.
        ``chunk_rows`` pins the step
        executable's row count (undersized blocks are zero-weight padded:
        placed rows on the device, from one cached device zero row per
        (width, dtype), so no ragged size compiles anything; host-stacked
        rows, the weights and the small scale rows on the host):
        pass the configured chunk so elastic/async rounds whose LAST block
        varies still hit one cached executable — the key
        ``is_warm_stream`` probes. Unset, the first block's size is used.
        ``init`` seeds the carry state with a previous round's
        ``acc_state`` — the async carry-over; for reducible fusions this
        is the historical (wsum, tot) tuple. The final pre-finalize state
        is returned on the report (``acc_state``, plus
        ``acc_wsum``/``acc_tot`` for reducible fusions).
        ``device_sem`` (optional semaphore / context manager) bounds
        concurrent DEVICE execution when several rounds stream through
        one engine at once: each block's step and the final combine
        acquire it, while ingest stalls (the straggler wait) stay
        outside — so concurrent tenants overlap their waits but the
        hardware only runs the configured number of folds at a time.
        Returns (fused, StreamReport).

        All carry state (``state``/``step``) is per-call local:
        concurrent ``fuse_stream`` calls on one shared engine never
        cross their folds (only the compile cache is shared, and it is
        single-flight per key)."""
        if not fusion.streamable:
            raise ValueError(
                f"{fusion.name} is not streamable — streamed aggregation "
                "needs a reducer decomposition (weighted sum or "
                "order-statistic carve)"
            )
        weighted = fusion.weighted
        rep = StreamReport()
        sem = spans.DeviceSlot(device_sem)
        it = iter(blocks)
        steps: dict = {}   # payload kind -> cached step executable
        state = sig = None  # flat tuple of jnp leaves + its cache sig
        chunk = dim = None
        compile_total = 0.0
        self.last_compile_seconds = 0.0
        while True:
            t0 = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                break
            rep.ingest_seconds += time.perf_counter() - t0
            block, w = item[0], item[1]
            rows, width, bdim, compressed = _block_meta(block)
            if chunk is None:
                chunk = int(chunk_rows) if chunk_rows else rows
            if rows < chunk and not isinstance(block, RowBlock):
                # a ragged stacked block is padded the one way a
                # RowBlock is (see _place)
                block = _as_rows(block)
            with spans.span("engine.stage") as stage:
                scale = _check_scale(item[2]) if len(item) > 2 else None
                if scale is not None and not weighted:
                    raise ValueError(
                        f"{fusion.name}: per-row staleness scales are "
                        "unsupported — order statistics cannot discount rows"
                    )
                if state is None:
                    dim = bdim
                    rep.chunk_rows = chunk
                    state = self._stream_state(fusion, dim, n_hint, init)
                    sig = fusion.state_signature(dim, n_hint)
                elif bdim != dim:
                    raise ValueError(
                        f"fuse_stream: block dim {bdim} != stream dim {dim}"
                    )
                if rows > chunk:
                    raise ValueError(
                        f"fuse_stream: block of {rows} rows exceeds "
                        f"chunk_rows={chunk}"
                    )
                rep.ingest_bytes += int(block.nbytes)   # pre-padding payload
                kind = ("q", width, block.block) if compressed \
                    else ("d", np.dtype(block.dtype).str)
                step = steps.get(kind)
                if step is None:
                    avals = tuple(
                        jax.ShapeDtypeStruct(np.shape(leaf),
                                             np.asarray(leaf).dtype)
                        for leaf in state
                    )
                    if compressed:
                        step, compile_s = self._stream_step_q(
                            fusion, chunk, dim, width, block.block, sig,
                            avals,
                        )
                    else:
                        step, compile_s = self._stream_step(
                            fusion, chunk, dim, block.dtype, sig, avals,
                        )
                    steps[kind] = step
                    # mixed rounds accumulate one compile per payload kind
                    compile_total += compile_s
                    rep.compile_seconds = compile_total
                    self.last_compile_seconds = compile_total
                scales = block.scales if compressed else None
                placed = 0
                if isinstance(block, RowBlock):
                    payload, placed = self._place(block.arrays, chunk)
                    rep.rows_placed += placed
                else:
                    payload = block.codes if compressed else block
                stage.set_metadata(rows_placed=placed)
                if rows < chunk:           # ragged final block: zero-weight pad
                    wpad = np.zeros((chunk,), np.float32)
                    wpad[:rows] = w
                    w = wpad
                    if compressed:         # scale rows are padded on the host
                        spad = np.zeros((chunk, scales.shape[1]), np.float32)
                        spad[:rows] = scales
                        scales = spad
                if weighted:
                    w = np.array(
                        fusion.effective_weights(jnp.asarray(w, jnp.float32))
                    )
                    if scale is not None:
                        w[:rows] *= np.asarray(scale, np.float32)[:rows]
                    if rows < chunk:
                        w[rows:] = 0.0     # effective_weights may remap pads
                else:
                    # order-statistic fold: weights carry only row VALIDITY
                    w = np.zeros((chunk,), np.float32)
                    w[:rows] = 1.0
            t0 = time.perf_counter()
            with sem, spans.span("engine.step"):
                if compressed:
                    state = step(payload, scales, w, *state)
                else:
                    state = step(payload, w, *state)
                if device_sem is not None:
                    # dispatch is async: holding the semaphore only
                    # bounds execution if we wait for it (single-tenant
                    # rounds skip the sync and keep the pipeline deep)
                    jax.block_until_ready(state)  # lint: disable=sync-under-sem -- deliberate: the permit must cover device EXECUTION, not just dispatch (PR 5's device_concurrency contract)
            rep.compute_seconds += time.perf_counter() - t0
            rep.n_rows += rows
            rep.n_blocks += 1
        if rep.n_blocks == 0:
            if init is None:
                raise ValueError("fuse_stream: empty block iterator")
            # carry-only round: nothing arrived, finalize the carried state
            state = tuple(jnp.asarray(x, jnp.float32) for x in init)
        t0 = time.perf_counter()
        with spans.span("engine.copyout"):
            rep.acc_state = tuple(np.asarray(leaf) for leaf in state)
        if fusion.reducible:
            rep.acc_wsum = rep.acc_state[0]
            rep.acc_tot = float(rep.acc_state[1])
        with sem, spans.span("engine.finalize"):
            fused = jax.block_until_ready(fusion.finalize(state))  # lint: disable=sync-under-sem -- deliberate: the permit must cover device EXECUTION, not just dispatch (PR 5's device_concurrency contract)
        rep.compute_seconds += time.perf_counter() - t0
        return fused, rep

    def _place(self, arrays, chunk: int):
        """The (chunk, width) step operand from host rows, and how many
        rows reached the device with no host stack. Rows of
        ``_PLACE_MIN_ROW_BYTES`` and more cross in one batched
        ``device_put`` and one cached executable per (chunk, width,
        dtype) stacks them on the device, filling a ragged block's
        missing rows with the cached device zero row, so every ragged
        size reuses it; a one-row chunk crosses as a (1, width) view
        with no assembly. Smaller rows are stacked (and padded) on the
        host in one copy and cross with the step call."""
        if chunk == 1:
            return jax.device_put(arrays[0][None]), 1
        width, dtype = int(arrays[0].shape[0]), np.dtype(arrays[0].dtype)
        if arrays[0].nbytes < _PLACE_MIN_ROW_BYTES:
            out = np.zeros((chunk, width), dtype)
            np.stack(arrays, out=out[:len(arrays)])
            return out, 0
        placed = jax.device_put(list(arrays))
        placed += [self._zero_rows.get(width, dtype)] * (chunk - len(placed))
        return _assemble_rows(*placed), len(arrays)

    @staticmethod
    def _stream_state(fusion, dim, n_hint, init):
        """Fresh (or carried) reducer state as a flat tuple of jnp
        leaves. Carried leaves must match the fresh state's shapes."""
        proto = tuple(fusion.init_state(dim, n_hint))
        if init is None:
            return proto
        if len(init) != len(proto):
            raise ValueError(
                f"fuse_stream: carried state has {len(init)} leaves, "
                f"{fusion.name} expects {len(proto)}"
            )
        state = tuple(
            jnp.asarray(x, np.asarray(p).dtype) for x, p in zip(init, proto)
        )
        for got, want in zip(state, proto):
            if got.shape != want.shape:
                raise ValueError(
                    f"fuse_stream: carried accumulator has dim "
                    f"{got.shape}, stream blocks have dim {dim}"
                )
        return state

    # -- cache introspection (planner reuse term) -----------------------------
    def is_warm(self, fusion, n: int, P: int, dtype) -> bool:
        if not fusion.reducible:
            return False
        row_bytes = np.dtype(dtype).itemsize * P
        if self.memory_cap_bytes is not None:
            max_rows = max(int(self.memory_cap_bytes // max(row_bytes, 1)), 1)
            if max_rows < n:
                return self._scan_key(fusion, n, max_rows, P, dtype) \
                    in self.cache
        return self._dense_key(fusion, n, P, dtype) in self.cache

    def is_warm_stream(self, fusion, chunk: int, P: int, dtype,
                       block: Optional[int] = None,
                       n_hint: Optional[int] = None) -> bool:
        """Warm-path probe for the streamed step executable. ``dtype``
        int8 probes the COMPRESSED step (int8 codes + fp32 scales at
        quantization block ``block``, default ``compress.BLOCK``) —
        the key a compressed round's first fold would build. ``n_hint``
        matters for order-statistic fusions, whose carve-state capacity
        (and hence executable) is sized from it."""
        if not fusion.streamable:
            return False
        try:
            sig = fusion.state_signature(P, n_hint)
        except ValueError:   # carve fusion with no n_hint: can't stream
            return False
        if np.dtype(dtype) == np.int8:
            blk = int(block) if block else BLOCK
            Pq = -(-P // blk) * blk
            return self._step_key_q(fusion, chunk, P, Pq, blk, sig) \
                in self.cache
        return self._step_key(fusion, chunk, P, dtype, sig) in self.cache

    # -- internals ------------------------------------------------------------
    def _dense_key(self, fusion, n, P, dtype):
        return ("dense", fusion_cache_key(fusion), self.strategy,
                bucket_rows(n), P, np.dtype(dtype).str)

    def _step_key(self, fusion, chunk, P, dtype, sig):
        return ("stream", fusion_cache_key(fusion), self.strategy,
                chunk, P, np.dtype(dtype).str, sig)

    def _step_key_q(self, fusion, chunk, P, Pq, blk, sig):
        return ("streamq", fusion_cache_key(fusion), self.strategy,
                chunk, P, Pq, blk, sig)

    def _scan_key(self, fusion, n, max_rows, P, dtype):
        # keyed by chunk COUNT, not n: rounds sharing ceil(n/chunk) reuse
        # the executable. (No pow2 bucketing here — padding the dense
        # input up to a bucket would double peak memory on exactly the
        # memory-capped path; at most chunk-1 zero rows are acceptable.)
        k = -(-n // max_rows)
        return ("streamscan", fusion_cache_key(fusion), self.strategy,
                k, max_rows, P, np.dtype(dtype).str)

    def _partial_fn(self, fusion):
        """The stateless 'map' stage — closed over fusion hyperparameters,
        never over server state."""
        use_pallas = self.strategy == "pallas" and fusion.name in _PALLAS_WSUM
        interpret = self.interpret

        def partial(u, w):
            if use_pallas:
                return weighted_sum_pallas(u, w, interpret=interpret), \
                    jnp.sum(w)
            return fusion.partial(u, w)

        return partial

    def _fuse_reducible_dense(self, fusion, updates, weights,
                              device_sem=None):
        n, P = updates.shape
        B = bucket_rows(n)
        key = self._dense_key(fusion, n, P, updates.dtype)
        partial = self._partial_fn(fusion)
        # compile OUTSIDE the device semaphore (single-flight per key)
        fn, compile_s = self.cache.get(
            key, lambda: partial,
            jax.ShapeDtypeStruct((B, P), updates.dtype),
            jax.ShapeDtypeStruct((B,), jnp.float32),
        )
        self.last_compile_seconds = compile_s
        if B != n:   # zero-weight rows: no contribution to any reducible op
            updates = jnp.pad(updates, ((0, B - n), (0, 0)))
            weights = jnp.pad(weights, (0, B - n))
        sem = spans.DeviceSlot(device_sem)
        with sem:
            wsum, tot = fn(updates, weights)
            return self._bounded(fusion.combine(wsum, tot), device_sem)

    def _carve_fn(self, fusion):
        """Strategy-specific carve kernel injected into the fusion's
        fold (None = the fusion's jnp reference merge)."""
        del fusion
        if self.strategy != "pallas":
            return None
        interpret = self.interpret

        def carve(u, valid, ssum, topk, botk):
            return topk_carve_pallas(u, valid, ssum, topk, botk,
                                     interpret=interpret)

        return carve

    def _fold_fn(self, fusion):
        """The per-block fold: fusion-owned semantics with this engine's
        strategy-specific kernels injected."""
        if fusion.reducible:
            partial = self._partial_fn(fusion)
            return lambda st, u, w: tuple(
                fusion.fold_block(st, u, w, partial=partial))
        carve = self._carve_fn(fusion)
        return lambda st, u, w: tuple(
            fusion.fold_block(st, u, w, carve=carve))

    def _stream_step(self, fusion, chunk, P, dtype, sig, state_avals):
        """One compiled fold step: (block, w, *state) -> updated state
        tuple (reducible: (wsum, tot); carve: (sum, count, topk, botk))."""
        key = self._step_key(fusion, chunk, P, dtype, sig)
        fold = self._fold_fn(fusion)

        def build():
            def step(u, w, *state):
                return fold(tuple(state), u, w)

            return step

        return self.cache.get(
            key, build,
            jax.ShapeDtypeStruct((chunk, P), np.dtype(dtype)),
            jax.ShapeDtypeStruct((chunk,), jnp.float32),
            *state_avals,
        )

    def _partial_q_fn(self, fusion, dim, blk):
        """The 'map' stage for COMPRESSED blocks: (codes (c, Pq) int8,
        scales (c, Pq//blk) fp32, w (c,)) -> (partial wsum (dim,), tot).
        The fp32 update matrix never exists on the host; on device it
        either never materializes at all (pallas: scales fold into the
        weighted-sum kernel tile by tile; jnp weighted-sum fusions: the
        per-row weight and per-block scale fold into one einsum with the
        same MAC count as the dense path) or exists only as a transient
        inside the compiled step (general reducible fusions that need
        real update values, e.g. clipping norms)."""
        use_pallas = self.strategy == "pallas" and fusion.name in _PALLAS_WSUM
        # _PALLAS_WSUM fusions' partial IS the plain weighted sum + sum(w),
        # which is what justifies the scale-folding shortcut for exactly
        # this set under the jnp strategy too
        plain_wsum = fusion.name in _PALLAS_WSUM
        interpret = self.interpret

        def partial_q(q, s, w):
            if use_pallas:
                ws = weighted_sum_dequant_pallas(
                    q, s, w, block=blk, interpret=interpret
                )
                return ws[:dim], jnp.sum(w)
            c, Pq = q.shape
            B = Pq // blk
            if plain_wsum:
                # block-batched contraction over clients: out[b] =
                # (w * s[:, b]) @ codes[:, b] — XLA lowers it to B small
                # matvecs, ~4x faster here than the flat (c, B, blk)
                # einsum because the transposed int8 operand is
                # convert-and-contracted per block
                ws = jnp.einsum(
                    "bn,bnk->bk",
                    (w[:, None] * s).T,
                    q.reshape(c, B, blk).transpose(1, 0, 2)
                     .astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                ).reshape(-1)[:dim]
                return ws, jnp.sum(w)
            u = (q.astype(jnp.float32).reshape(c, B, blk)
                 * s[:, :, None]).reshape(c, Pq)[:, :dim]
            return fusion.partial(u, w)

        return partial_q

    def _stream_step_q(self, fusion, chunk, P, Pq, blk, sig, state_avals):
        """The compressed twin of ``_stream_step``: (codes, scales, w,
        *state) -> updated state, the same carry as the dense step —
        which is what lets mixed dense/compressed rounds share one
        accumulator. For carve fusions the (codes, scales) payload is
        dequantized in-trace inside the fold (bit-identical to the host
        dequant, so the order statistics match the dense path)."""
        key = self._step_key_q(fusion, chunk, P, Pq, blk, sig)
        if fusion.reducible:
            partial_q = self._partial_q_fn(fusion, P, blk)

            def fold(state, q, s, w):
                partial = lambda payload, wv: partial_q(
                    payload[0], payload[1], wv)
                return tuple(fusion.fold_block(state, (q, s), w,
                                               partial=partial))
        else:
            carve = self._carve_fn(fusion)

            def fold(state, q, s, w):
                return tuple(fusion.fold_block(state, (q, s), w,
                                               carve=carve))

        def build():
            def step(q, s, w, *state):
                return fold(tuple(state), q, s, w)

            return step

        return self.cache.get(
            key, build,
            jax.ShapeDtypeStruct((chunk, Pq), np.int8),
            jax.ShapeDtypeStruct((chunk, Pq // blk), jnp.float32),
            jax.ShapeDtypeStruct((chunk,), jnp.float32),
            *state_avals,
        )

    def _streamed(self, fusion, updates, weights, max_rows,
                  device_sem=None) -> jnp.ndarray:
        """Memory-capped dense input: ONE scanned executable over fixed
        (max_rows, P) client chunks — bounded resident set, no Python loop
        of per-chunk jit dispatches (the seed behavior)."""
        n, P = updates.shape
        k = -(-n // max_rows)
        padded_n = k * max_rows
        key = self._scan_key(fusion, n, max_rows, P, updates.dtype)
        partial = self._partial_fn(fusion)

        def build():
            def scanned(u3, w2):
                def body(carry, xs):
                    u, w = xs
                    ws, t = partial(u, w)
                    return (carry[0] + ws, carry[1] + t), None

                init = (jnp.zeros((P,), jnp.float32),
                        jnp.zeros((), jnp.float32))
                (wsum, tot), _ = jax.lax.scan(body, init, (u3, w2))
                return wsum, tot

            return scanned

        fn, compile_s = self.cache.get(
            key, build,
            jax.ShapeDtypeStruct((k, max_rows, P), updates.dtype),
            jax.ShapeDtypeStruct((k, max_rows), jnp.float32),
        )
        self.last_compile_seconds = compile_s
        if padded_n != n:
            updates = jnp.pad(updates, ((0, padded_n - n), (0, 0)))
            weights = jnp.pad(weights, (0, padded_n - n))
        sem = spans.DeviceSlot(device_sem)
        with sem:
            wsum, tot = fn(
                updates.reshape(k, max_rows, P),
                weights.reshape(k, max_rows),
            )
            return self._bounded(fusion.combine(wsum, tot), device_sem)
