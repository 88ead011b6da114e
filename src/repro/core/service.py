"""AggregationService — the paper's top-level contribution (Algorithm 1 +
§III-D): an adaptive, elastic aggregation facade that routes every round's
workload to the best engine and transitions seamlessly between them.

Round flow (mirrors Algorithm 1):
  1. S = w_s * n  -> classify + plan (planner.py's roofline cost model,
     plus a reuse term: engines holding a compiled executable for this
     round's shape bucket are costed below cold ones).
  2. small  -> single-chip engine (jnp baseline or fused Pallas path),
     updates land in memory exactly as IBMFL receives them over gRPC.
  3. large  -> clients were already redirected to the UpdateStore (the
     seamless-transition hook, §III-D3); monitor(T_h, timeout) gates the
     round; STREAMABLE fusions then STREAM (chunk, P) blocks off the
     store through one cached step executable — on the single-chip
     engine or per-shard over the mesh — so the dense (n, P) matrix
     never materializes on the host. Streamable = the reducible sum
     family (O(P) carry) plus the order-statistic reducers
     (TrimmedMean / CoordMedian) via the O(K*P) top-k carve, gated by
     ``robust_state_budget``; over-budget carve rounds and
     non-streamable fusions (Krum) fall back to the dense read /
     distributed engine with a ``RoundReport.notes`` entry.
  4. The fused flat vector is unflattened back into the model pytree.

ASYNC ROUNDS (``aggregate(from_store=True, async_round=True)``): instead
of idling in ``Monitor.wait()`` and only then ingesting, the round feeds
``UpdateStore.iter_arrivals`` into the engine's ``fuse_stream`` — partial
sums fold WHILE stragglers are still writing, and the monitor's
threshold/timeout gate decides when the in-flight stream closes. Folded
updates are consumed from the store (queue semantics); stragglers that
miss the close land in the next round. With ``staleness_discount=γ`` the
accumulator carries over between rounds (continuous / multi-tenant
aggregation): round r starts from γ × round r−1's partial sums and a
straggler that is a rounds late folds at weight γ^a. With the discount
disabled (None, the default) each async round is independent and — on a
fixed client set — bit-for-bit the same reduction as the synchronous
streamed path (tests/test_equivalence.py). ``async_round="auto"`` lets
the planner's overlap model choose (async wins once the expected monitor
wait dominates the close-drain residue).

ADAPTIVE ROUNDS (``AggregationService(adaptive=True, cost_bias=b)``):
the static threshold/timeout gate is replaced per round by the
``repro.core.adaptive`` controller's learned policy — an
exponentially-weighted empirical arrival curve per ``tenant`` (fed by
the store's write timestamps) is minimized against the planner's
cost-vs-staleness objective, so the gate closes exactly when the
marginal straggler stops being worth the wait. ``cost_bias`` is the
paper's user knob: 0 optimizes round wall-clock, 1 optimizes update
inclusion. A tenant without arrival history borrows the controller's
cross-tenant PRIOR curve (cold-start transfer), and a tenant whose
arrival behavior is drifting faster than the EW window gets a widened
deadline backstop. ``save_controller`` / ``load_controller`` persist
the learned state into ``repro/checkpoint`` alongside model state.

MULTI-TENANT ROUNDS: both the service-side cross-round state — carry
accumulator, straggler ages, learned curves — AND the UpdateStore
itself are keyed by ``tenant``: every write lands in one tenant's
store partition, and a round gates on, folds, and consumes ONLY its
own tenant's partition. Concurrent tenants interleave open rounds on
one shared store (and share the engines' warm compile caches) without
stealing each other's updates — see docs/MULTITENANCY.md.

CONCURRENT ROUND EXECUTION: ``aggregate`` is thread-safe — rounds for
DIFFERENT tenants run genuinely concurrently on one service (the
``RoundScheduler`` below owns one worker thread per tenant), while two
rounds for the SAME tenant serialize on a per-tenant lock (carry
accumulators, straggler ages, and the store's queue semantics assume
one open round per tenant). What concurrent rounds share is safe by
construction: the engines' compile caches are single-flight per shape
bucket (two tenants racing the same bucket compile once and share the
executable), engine accumulator state is per-call, compile-phase
accounting is per-thread, the adaptive controller serializes
internally, and DEVICE execution is bounded by the service's
``device_concurrency`` semaphore — concurrent tenants overlap their
monitor waits and host staging, while the hardware only runs the
configured number of folds at a time. One caveat: stateful fusions
(FedAvgM / FedAdam carry server-side velocity) share that state across
every tenant on the service — use a stateless fusion (fedavg family)
or one service per tenant when concurrent tenants train distinct
models.

Convergence guarantee (paper §IV-C): every engine computes the *same*
fusion formula — tests/test_equivalence.py asserts allclose across
engines, which is the system's core invariant.
"""
from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adaptive import AdaptiveController, ClosePolicy
from repro.core.compress import (
    BLOCK,
    CompressedUpdate,
    ErrorFeedbackCompressor,
    compressed_bytes,
)
from repro.core.distributed import DistributedEngine
from repro.core.fusion import FusionAlgorithm, get_fusion
from repro.core.local import LocalEngine
from repro.core.monitor import Monitor, MonitorResult
from repro.core.planner import Plan, Planner
from repro.core.store import DEFAULT_TENANT, StoreStats, UpdateStore
from repro.core.workload import Workload, WorkloadClass, classify
from repro.utils import spans
from repro.utils.mem import HardwareSpec, detect_hardware
from repro.utils.pytree import flat_vector_to_tree, tree_to_flat_vector

PyTree = Any

# Monitor threshold sentinel: no client count can close the gate — the
# round is gated by the timeout alone (async rounds with no expected
# client count).
_TIMEOUT_GATED = 1 << 62


@dataclasses.dataclass
class RoundReport:
    plan: Plan
    n_clients: int
    update_bytes: int
    # wall time of the fusion computation; on async rounds this spans the
    # whole overlapped window (fusing AND waiting ran concurrently), so
    # compare phase_seconds across round modes, not fuse_seconds
    fuse_seconds: float
    monitor: Optional[MonitorResult] = None
    route_next_to_store: bool = False
    streamed: bool = False       # True: chunked store pipeline (no dense n,P)
    # ingest (store -> host blocks) / compile (executable build; 0.0 on
    # warm rounds) / compute (the engine's fold: per-block host staging,
    # the wait for ``device_sem`` behind other rounds' folds, transfer,
    # fold steps, state copy-out and finalize — not device time alone)
    # — the paper's Fig. 12 phases; ``FairRoundScheduler`` adds queue
    # (submit to admission: the wait for a running slot)
    phase_seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    # seconds of the monitor window during which fusion work proceeded
    # CONCURRENTLY with the straggler wait (0.0 on serialized rounds)
    overlap_seconds: float = 0.0
    async_round: bool = False    # arrival-driven overlapped round
    empty: bool = False          # monitor timed out with nothing to fuse
    tenant: str = DEFAULT_TENANT  # store partition / continuity key
    # the gate that closed this round — source == "learned" once the
    # adaptive controller has enough arrival history for the tenant
    close_policy: Optional[ClosePolicy] = None
    # snapshot of the TENANT's store accounting at round close (writes /
    # bytes / reads / evictions — per-partition, not spool-global)
    store_stats: Optional[StoreStats] = None
    # actual payload bytes the fusion ingested (pre-padding): int8 codes
    # + fp32 scales on compressed rounds, the dense matrix bytes
    # otherwise — the paper's transport-cost metric
    bytes_ingested: int = 0
    # operator-facing routing notes, e.g. why a robust round fell back
    # from the streamed carve to the dense path (state budget exceeded)
    notes: Tuple[str, ...] = ()
    # the service's round sequence number: the ``round`` stat of the
    # round's ``repro.*`` spans
    round_id: int = 0


class AggregationService:
    """Adaptive aggregation service over a (possibly trivial) mesh."""

    def __init__(
        self,
        fusion: FusionAlgorithm | str = "fedavg",
        mesh=None,
        hw: Optional[HardwareSpec] = None,
        local_strategy: str = "pallas",
        store: Optional[UpdateStore] = None,
        threshold_frac: float = 0.8,
        monitor_timeout: float = 30.0,
        memory_cap_bytes: Optional[int] = None,
        stream_chunk_bytes: int = 64 << 20,
        staleness_discount: Optional[float] = None,
        adaptive: bool = False,
        cost_bias: float = 0.5,
        compress: bool | int = False,
        device_concurrency: int = 1,
        secure=None,
        robust_state_budget: int = 64 << 20,
        clock=time.monotonic,
        sleep=time.sleep,
        poll_interval: float = 0.01,
    ):
        """Configure the adaptive aggregation facade.

        Args:
          fusion: fusion algorithm name (``repro.core.fusion.REGISTRY``)
            or instance; reducible ones (fedavg family) unlock streaming
            and async rounds.
          mesh: optional device mesh — enables the distributed (and,
            with a ``pod`` axis, hierarchical) engines.
          hw: hardware spec for the planner's roofline cost model;
            by default the TPU this process runs on, looked up by
            ``device_kind`` (``repro.utils.mem.detect_hardware``).
          local_strategy: ``"jnp"`` (baseline) or ``"pallas"`` (fused
            kernel) for the single-chip engine.
          store: the UpdateStore clients write to (``from_store``
            rounds); a private memory-backed store by default.
          threshold_frac: the STATIC gate — close once this fraction of
            ``expected_clients`` has landed. The adaptive controller
            re-derives it per round when ``adaptive=True``.
          monitor_timeout: static gate deadline in seconds; also the cap
            no learned deadline may exceed.
          memory_cap_bytes: simulate a memory-limited aggregator node
            (forces chunked streaming below the cap).
          stream_chunk_bytes: target bytes per streamed (chunk, P) block
            when no memory cap is set.
          staleness_discount: γ in (0, 1] enables continuous rounds —
            the accumulator carries over between async rounds scaled by
            γ (per tenant), and a straggler folding ``a`` rounds late is
            discounted to γ^a of its weight. None (default): every
            round is independent and bit-equivalent to the synchronous
            streamed path.
          adaptive: learn per-tenant arrival curves and replace the
            static gate with the controller's learned threshold/deadline
            (``repro.core.adaptive``); state is inspectable at
            ``self.controller``.
          cost_bias: the paper's user knob in [0, 1] — 0 optimizes
            round wall-clock (cost), 1 optimizes update inclusion
            (efficiency); only meaningful with ``adaptive=True``.
          compress: quantized transport. ``True`` (block size
            ``repro.core.compress.BLOCK``) or an explicit block size
            enables ``compress_update`` — clients spool int8 codes +
            fp32 per-block scales (~4x fewer bytes) with per-tenant
            error feedback, and store rounds stream them through the
            engines' dequant-folding step without ever materializing
            the fp32 matrix. Mixed rounds are fine: a straggler that
            writes uncompressed fp32 folds into the same accumulator.
          device_concurrency: how many concurrent rounds may EXECUTE on
            the device at once (a bounded semaphore the engines acquire
            per fold step). Default 1 — on a small edge host the
            hardware serializes folds anyway, so concurrent tenants
            overlap only their monitor waits and host staging; raise it
            when the backend genuinely runs kernels in parallel.
          secure: an optional ``repro.core.secure.SecureMasking``
            instance declaring that clients write pairwise-masked
            updates. Mask cancellation needs the plain SUM over the
            close set, so this requires a sum-reducible fusion —
            rejected at construction otherwise. (Composing secure
            masking with ASYNC close sets is the ROADMAP follow-on:
            the mask basis must be renegotiated per inclusion
            decision.)
          robust_state_budget: byte cap on an order-statistic fusion's
            streamed carry state (the O(K*P) top-k carve buffers).
            Rounds whose projected state exceeds it route to the dense
            / distributed path with a ``RoundReport.notes`` entry
            instead of streaming.
          clock / sleep / poll_interval: time sources for the monitor
            and arrival streams, injectable for deterministic tests.
        """
        self.fusion = (
            get_fusion(fusion) if isinstance(fusion, str) else fusion
        )
        self.mesh = mesh
        if hw is None:
            hw = detect_hardware()
        self.hw = hw
        self.store = store or UpdateStore()
        self.threshold_frac = threshold_frac
        self.monitor_timeout = monitor_timeout
        self.stream_chunk_bytes = stream_chunk_bytes
        self.memory_cap_bytes = memory_cap_bytes
        # async-round continuity: None -> every async round is independent
        # (sync-equivalent); γ in (0, 1] -> the accumulator carries over
        # between rounds scaled by γ, and a straggler folding a rounds
        # late is discounted to γ^a of its weight (continuous aggregation)
        if staleness_discount is not None and not 0 < staleness_discount <= 1:
            raise ValueError("staleness_discount must be in (0, 1] or None")
        self.staleness_discount = staleness_discount
        self.clock = clock               # injectable for deterministic tests
        self.sleep = sleep
        self.poll_interval = poll_interval
        # per-TENANT round continuity (multi-tenant rounds interleave
        # through one service without cross-talk): tenant -> (wsum, tot)
        # pre-combine carry, and tenant -> {straggler id -> rounds late}
        self._carry: Dict[str, tuple] = {}  # guarded-by: _state_lock
        self._stale_ages: Dict[str, Dict[str, int]] = {}  # guarded-by: _state_lock
        # tenant -> last observed monitor wait (async_round="auto"'s
        # projection input; O(1) instead of scanning history per round)
        self._last_wait: Dict[str, float] = {}  # guarded-by: _state_lock
        # concurrency: rounds for the SAME tenant serialize on a
        # per-tenant lock (carry / ages / queue semantics assume one
        # open round per tenant); _state_lock guards the shared maps
        # and history; the device semaphore bounds concurrent device
        # execution across all tenants' folds
        if device_concurrency < 1:
            raise ValueError("device_concurrency must be >= 1")
        self.device_concurrency = device_concurrency
        self.device_sem = threading.BoundedSemaphore(device_concurrency)
        self._state_lock = threading.Lock()
        self._tenant_locks: Dict[str, threading.Lock] = {}  # guarded-by: _state_lock
        self.local = LocalEngine(
            strategy=local_strategy, memory_cap_bytes=memory_cap_bytes
        )
        self.distributed = (
            DistributedEngine(mesh=mesh) if mesh is not None else None
        )
        self.hierarchical = (
            DistributedEngine(mesh=mesh, hierarchical=True)
            if mesh is not None and "pod" in mesh.axis_names else None
        )
        n_dev = mesh.devices.size if mesh is not None else 1
        n_pods = mesh.shape.get("pod", 1) if mesh is not None else 1
        self.planner = Planner(hw=hw, n_devices=n_dev, n_pods=n_pods)
        if not 0 <= cost_bias <= 1:
            raise ValueError("cost_bias must be in [0, 1]")
        self.cost_bias = cost_bias
        # quantized transport: normalize compress to an Optional block
        # size; per-tenant EF compressors are created lazily (client
        # residuals must not leak across tenants)
        if compress is True:
            self.compress_block: Optional[int] = BLOCK
        elif compress:
            if int(compress) < 1:
                raise ValueError("compress block size must be >= 1")
            self.compress_block = int(compress)
        else:
            self.compress_block = None
        self._compressors: Dict[str, ErrorFeedbackCompressor] = {}  # guarded-by: _state_lock
        # unsupported-combo fail-fasts: a clear ValueError here beats an
        # opaque one deep in the round path
        if self.compress_block is not None and not self.fusion.streamable:
            raise ValueError(
                "compress=True requires a streamable fusion (the dequant "
                f"fold runs inside the streamed step); {self.fusion.name} "
                "is not streamable"
            )
        if secure is not None and not self.fusion.reducible:
            raise ValueError(
                "SecureMasking requires a sum-reducible fusion — pairwise "
                "masks only cancel under summation — and "
                f"{self.fusion.name} is not reducible"
            )
        self.secure = secure
        if staleness_discount is not None and not self.fusion.weighted:
            raise ValueError(
                "staleness_discount requires a weighted fusion; "
                f"{self.fusion.name} folds order statistics that cannot "
                "be discounted"
            )
        if int(robust_state_budget) < 1:
            raise ValueError("robust_state_budget must be >= 1 byte")
        self.robust_state_budget = int(robust_state_budget)
        # the adaptive layer: learns per-tenant arrival curves off the
        # store's timestamps and re-derives the gate every round
        self.controller: Optional[AdaptiveController] = (
            AdaptiveController(
                cost_bias=cost_bias,
                threshold_frac=threshold_frac,
                timeout=monitor_timeout,
                planner=self.planner,
            ) if adaptive else None
        )
        self.history: List[RoundReport] = []  # guarded-by: _state_lock
        self._round_seq = itertools.count(1)   # RoundReport.round_id

    # -- quantized transport --------------------------------------------------
    def compress_update(
        self, client_id: str, update, tenant: str = DEFAULT_TENANT,
    ) -> CompressedUpdate:
        """Quantize one client update for spooling: int8 codes + fp32
        per-block scales, with per-tenant ERROR FEEDBACK — the client's
        quantization residual is carried into its next round's update,
        so the multi-round fused mean converges to the uncompressed
        one. Pass the result straight to ``store.write``; requires
        ``AggregationService(compress=...)``."""
        if self.compress_block is None:
            raise ValueError(
                "compress_update needs a compressing service "
                "(AggregationService(compress=True) or =block_size)"
            )
        if getattr(update, "ndim", None) != 1:
            update = tree_to_flat_vector(update)
        with self._state_lock:
            comp = self._compressors.get(tenant)
            if comp is None:
                comp = self._compressors[tenant] = ErrorFeedbackCompressor(
                    block=self.compress_block
                )
        return comp.compress_update(client_id, update)

    # -- streaming knobs ------------------------------------------------------
    def _row_bytes(self, p: int, dtype) -> int:
        """Per-client payload bytes in the store: real compressed size
        (padded codes + fp32 scales) when the partition holds int8
        quantized updates, dense bytes otherwise."""
        if np.dtype(dtype) == np.int8:
            return compressed_bytes(p, self.compress_block or BLOCK)
        return p * np.dtype(dtype).itemsize

    def _chunk_rows(self, n: int, row_bytes: int) -> int:
        """Rows per streamed block: half the memory cap (two blocks are
        resident under double buffering), else the chunk-size default."""
        budget = (
            self.memory_cap_bytes // 2
            if self.memory_cap_bytes is not None
            else self.stream_chunk_bytes
        )
        return max(1, min(n, int(budget // max(row_bytes, 1))))

    def _stream_mode(
        self, fusion: FusionAlgorithm, p: int, n_hint: int,
    ) -> Tuple[bool, Optional[str]]:
        """THE stream-eligibility predicate (one place, not three):
        can this round stream, and if not, why not (operator note).

        Reducible fusions always stream (O(P) sum carry). Order-statistic
        fusions stream through the top-k carve iff their projected carry
        state — O(K*P) bytes, K from ``n_hint`` — fits the service's
        ``robust_state_budget``; over-budget rounds route dense with a
        ``RoundReport.notes`` entry rather than raising."""
        if not fusion.streamable:
            return False, None
        if fusion.reducible:
            return True, None
        need = fusion.state_nbytes(p, max(int(n_hint), 1))
        if need > self.robust_state_budget:
            return False, (
                f"robust stream fallback: {fusion.name} carve state needs "
                f"{need / (1 << 20):.1f} MiB for n={int(n_hint)}, P={p} "
                f"(budget {self.robust_state_budget / (1 << 20):.1f} MiB) "
                "— routed to the dense path"
            )
        return True, None

    def _warm_engines(self, n: int, p: int, dtype, chunk_rows=None,
                      fusion: Optional[FusionAlgorithm] = None,
                      n_hint: Optional[int] = None):
        """Engines holding a compiled executable for this round's shape —
        dense keys, or (with ``chunk_rows``) the streamed step keys."""
        fusion = fusion if fusion is not None else self.fusion
        warm = set()
        if chunk_rows is not None:
            blk = self.compress_block or BLOCK
            if self.local.is_warm_stream(
                    fusion, chunk_rows, p, dtype, block=blk,
                    n_hint=n_hint):
                warm.add("local")
            if self.distributed is not None and self.distributed \
                    .is_warm_stream(fusion, chunk_rows, p, dtype,
                                    block=blk, n_hint=n_hint):
                warm.add("distributed")
            if self.hierarchical is not None and self.hierarchical \
                    .is_warm_stream(fusion, chunk_rows, p, dtype,
                                    block=blk, n_hint=n_hint):
                warm.add("hierarchical")
            return warm
        if self.local.is_warm(fusion, n, p, dtype):
            warm.add("local")
        if self.distributed is not None and \
                self.distributed.is_warm(fusion, n, p, dtype):
            warm.add("distributed")
        if self.hierarchical is not None and \
                self.hierarchical.is_warm(fusion, n, p, dtype):
            warm.add("hierarchical")
        return warm

    def _stream_engine(self, name: str):
        if name == "hierarchical" and self.hierarchical is not None:
            return self.hierarchical
        if name == "distributed" and self.distributed is not None:
            return self.distributed
        return self.local

    def _round_lock(self, tenant: str) -> threading.Lock:
        """The tenant's round-serialization lock (created on first use)."""
        with self._state_lock:
            lock = self._tenant_locks.get(tenant)
            if lock is None:
                lock = self._tenant_locks[tenant] = threading.Lock()
            return lock

    # -- Algorithm 1 ----------------------------------------------------------
    def aggregate(
        self,
        updates: Optional[Sequence[PyTree]] = None,
        weights: Optional[Sequence[float]] = None,
        template: Optional[PyTree] = None,
        expected_clients: Optional[int] = None,
        from_store: bool = False,
        async_round: bool | str = False,
        tenant: str = DEFAULT_TENANT,
        val_grad=None,
    ) -> Tuple[PyTree, RoundReport]:
        """One aggregation round. Returns ``(fused, RoundReport)``.

        Thread-safe: rounds for different tenants run concurrently
        (see ``RoundScheduler``); two calls for the SAME tenant
        serialize on the tenant's round lock.

        Input modes:
          * ``updates`` (+ optional ``weights``) — in-memory, the small
            path's arrival mode (updates arrived over RPC, IBMFL-style).
          * ``from_store=True`` — clients wrote to the UpdateStore; the
            monitor gates the round on ``expected_clients`` (falling
            back to the current store count).

        ``async_round`` (store rounds, streamable fusions only) overlaps
        fusion with the straggler wait via arrival-driven streaming:
        ``True`` forces it, ``"auto"`` defers to the planner's overlap
        cost model (async wins once the expected monitor wait dominates
        the close-drain residue), ``False`` serializes (wait, then
        ingest). With ``staleness_discount=γ`` configured, async rounds
        carry the accumulator across rounds per ``tenant`` and discount
        a straggler that is ``a`` rounds late to ``γ^a`` of its weight.

        ``tenant`` keys the round end-to-end: the store partition the
        round gates on, folds, and consumes (writes tagged for other
        tenants are invisible to it), plus all service-side cross-round
        state — carry accumulator, straggler ages, and the adaptive
        controller's learned arrival curve. Concurrent tenants can
        interleave open rounds on ONE shared store without stealing
        each other's updates, while sharing the engines' warm compile
        caches (docs/MULTITENANCY.md). With ``adaptive=True`` on the
        service, the round's close gate is the controller's learned
        threshold/deadline for this tenant — borrowed from the
        cross-tenant prior while the tenant is cold (see
        ``report.close_policy``).

        ``val_grad`` threads a per-round validation gradient to fusions
        that score against one (Zeno): the round runs on a per-call
        CLONE (``fusion.with_val_grad``), so two concurrent tenants
        passing different validation gradients never race one shared
        fusion's state.

        An empty round (timeout, nothing landed) returns
        ``(None, report)`` with ``report.empty`` set instead of
        raising. ``template`` (a model pytree) unflattens the fused
        vector back into model structure."""
        rid = next(self._round_seq)
        with spans.scope(tenant=tenant, round=rid), spans.span("round"):
            with self._round_lock(tenant):
                fused, report = self._aggregate_impl(
                    updates, weights, template, expected_clients,
                    from_store, async_round, tenant, val_grad,
                )
        report.round_id = rid
        return fused, report

    def _aggregate_impl(
        self,
        updates: Optional[Sequence[PyTree]],
        weights: Optional[Sequence[float]],
        template: Optional[PyTree],
        expected_clients: Optional[int],
        from_store: bool,
        async_round: bool | str,
        tenant: str,
        val_grad=None,
    ) -> Tuple[PyTree, RoundReport]:
        """``aggregate`` body; caller holds the tenant's round lock."""
        fusion = self.fusion
        if val_grad is not None:
            if not hasattr(fusion, "with_val_grad"):
                raise ValueError(
                    f"{fusion.name} does not score against a validation "
                    "gradient — val_grad only applies to Zeno-style "
                    "fusions"
                )
            fusion = fusion.with_val_grad(val_grad)
        monitor_result = None
        phase: Dict[str, float] = {}
        streamed = False
        policy = arrivals = t_round = t_round_store = None
        expected = expected_clients
        notes: Tuple[str, ...] = ()

        if from_store:
            expected = expected_clients or self.store.count(tenant)
            use_async = self._resolve_async(
                async_round, expected, tenant, fusion=fusion,
            )
            threshold = max(int(expected * self.threshold_frac), 1)
            timeout = self.monitor_timeout
            if self.controller is not None and expected > 0:
                # the adaptive gate: learned threshold/deadline for this
                # tenant (static until the arrival curve has history)
                policy = self.controller.policy(tenant, expected)
                threshold, timeout = policy.threshold, policy.deadline
            if use_async and expected == 0:
                # async rounds legitimately start BEFORE any arrival; with
                # no expected count, a threshold of 1 would close the gate
                # on the first client that lands — gate on the timeout
                # alone instead (such rounds report monitor.ready=False)
                threshold = _TIMEOUT_GATED
                policy = None
            monitor = Monitor(
                self.store,
                threshold=threshold,
                timeout=timeout,
                poll_interval=self.poll_interval,
                clock=self.clock, sleep=self.sleep,
                policy=policy,
                tenant=tenant,
            )
            t_round = self.clock()
            # arrival offsets are computed on the STORE's clock (the
            # timestamps' timebase), which may differ from the service
            # clock under injected test clocks
            t_round_store = self.store.clock()
            if use_async:
                return self._aggregate_async(
                    monitor, expected, template, tenant, t_round, policy,
                    t_round_store, fusion=fusion,
                )
            monitor_result = monitor.wait()
            # arrival snapshot AT CLOSE — the controller's training
            # signal; later stragglers belong to the next round's curve
            arrivals = self.store.arrival_times(tenant)
            if self.store.count(tenant) == 0:
                # timed-out round on an empty partition: structured empty
                # report, not a LookupError out of store.meta()
                return self._empty_round(
                    monitor_result, template, tenant=tenant,
                    t_round=t_round, expected=expected,
                )
            n, p, dtype = self.store.meta(tenant)
            row_bytes = self._row_bytes(p, dtype)
            chunk_rows = self._chunk_rows(n, row_bytes)
            load = Workload(
                update_bytes=row_bytes, n_clients=n,
                dtype_bytes=dtype.itemsize, params=p,
            )
            n_hint = max(n, expected or 0, 1)
            can_stream, stream_note = self._stream_mode(fusion, p, n_hint)
            notes = (stream_note,) if stream_note else ()
            plan = self.planner.plan(
                load, fusion,
                warm_engines=self._warm_engines(
                    n, p, dtype,
                    chunk_rows=chunk_rows if can_stream else None,
                    fusion=fusion,
                    n_hint=n_hint if can_stream else None,
                ),
            )
            if can_stream:
                # zero-materialization pipeline: (chunk, P) blocks flow
                # from the store through one cached step executable —
                # single-chip, or per-shard over the mesh (the dense
                # (n, P) matrix never stages on the host either way)
                engine = self._stream_engine(plan.engine)
                t0 = time.perf_counter()
                fused, srep = engine.fuse_stream(
                    fusion,
                    self.store.iter_chunks(chunk_rows, tenant=tenant),
                    chunk_rows=chunk_rows,
                    device_sem=self.device_sem,
                    n_hint=n_hint,
                )
                dt = time.perf_counter() - t0
                streamed = True
                phase = {
                    "ingest": srep.ingest_seconds,
                    "compile": srep.compile_seconds,
                    "compute": srep.compute_seconds,
                }
                return self._finish(
                    fused, template, plan, n, load, dt, monitor_result,
                    expected_clients, streamed, phase,
                    tenant=tenant, policy=policy, t_round=t_round_store,
                    expected=expected, arrivals=arrivals,
                    ingest_bytes=srep.ingest_bytes, fusion=fusion,
                    notes=notes,
                )
            t0 = time.perf_counter()
            stacked, w = self.store.read_stacked(tenant)
            phase["ingest"] = time.perf_counter() - t0
        else:
            assert updates is not None and len(updates) > 0
            t0 = time.perf_counter()
            flat = [
                np.asarray(
                    u if getattr(u, "ndim", None) == 1
                    else tree_to_flat_vector(u)
                )
                for u in updates
            ]
            stacked = np.stack(flat)
            phase["ingest"] = time.perf_counter() - t0
            w = (
                np.asarray(weights, np.float32)
                if weights is not None
                else np.ones((len(flat),), np.float32)
            )

        # dense path (in-memory round, or store round that can't stream):
        # one plan against the materialized matrix
        n, p = stacked.shape
        load = Workload(
            update_bytes=p * stacked.dtype.itemsize, n_clients=n,
            dtype_bytes=stacked.dtype.itemsize,
        )
        plan = self.planner.plan(
            load, fusion,
            warm_engines=self._warm_engines(
                n, p, stacked.dtype, fusion=fusion,
            ),
        )

        t0 = time.perf_counter()
        if plan.engine == "local":
            # the local engine scopes the semaphore itself: held around
            # executable invocation only, so a cold compile (outside it,
            # single-flight) never stalls other tenants' folds
            fused = self.local.fuse(
                fusion, stacked, w, device_sem=self.device_sem,
            )
            phase["compile"] = self.local.last_compile_seconds
            fused = jax.block_until_ready(fused)
        else:
            # mesh engines compile inside their fuse paths, so a cold
            # dense mesh round holds the semaphore through its compile
            # (known caveat — the mesh engines have no separate warm
            # step; the whole dispatch counts against the budget)
            with self.device_sem:
                if plan.engine == "hierarchical" \
                        and self.hierarchical is not None:
                    fused = self.hierarchical.fuse(fusion, stacked, w)
                    phase["compile"] = \
                        self.hierarchical.last_compile_seconds
                else:
                    assert self.distributed is not None, (
                        "planner chose the distributed engine but no "
                        "mesh was given"
                    )
                    fused = self.distributed.fuse(fusion, stacked, w)
                    phase["compile"] = \
                        self.distributed.last_compile_seconds
                fused = jax.block_until_ready(fused)  # lint: disable=sync-under-sem -- deliberate: the permit must cover device EXECUTION, not just dispatch, or device_concurrency would not bound real device work (PR 5)
        dt = time.perf_counter() - t0
        phase["compute"] = dt - phase.get("compile", 0.0)
        return self._finish(
            fused, template, plan, n, load, dt, monitor_result,
            expected_clients, streamed, phase,
            tenant=tenant, policy=policy, t_round=t_round_store,
            expected=expected, arrivals=arrivals,
            ingest_bytes=int(stacked.nbytes), fusion=fusion,
            notes=notes,
        )

    # -- async (monitor-overlapped) rounds ------------------------------------
    def _resolve_async(
        self, async_round: bool | str, expected: int,
        tenant: str = DEFAULT_TENANT,
        fusion: Optional[FusionAlgorithm] = None,
    ) -> bool:
        """Decide whether this store round overlaps fusion with the wait.
        Only streamable fusions can fold arrivals incrementally; "auto"
        asks the planner whether the expected monitor wait (the TENANT's
        last observed wait, else the timeout) dominates the drain
        residue. Projections are sized off ``tenant``'s store
        partition."""
        fusion = fusion if fusion is not None else self.fusion
        if not async_round or not fusion.streamable:
            return False
        if not fusion.reducible:
            # order-statistic streams must size + budget the carve state
            # up front: no known P yet, or over the state budget -> the
            # round runs synchronously (dense fallback with a note)
            try:
                _n_now, p, _dtype = self.store.meta(tenant)
            except LookupError:
                return False
            ok, _note = self._stream_mode(fusion, p, max(expected, 1))
            if not ok:
                return False
        if async_round != "auto":
            return True
        # the tenant's own history only: another tenant's wait says
        # nothing about this fleet's stragglers
        with self._state_lock:
            last_wait = self._last_wait.get(tenant)
        expected_wait = (
            last_wait if last_wait is not None else self.monitor_timeout
        )
        try:
            n, p, dtype = self.store.meta(tenant)
        except LookupError:
            # nothing has arrived yet — the wait is all there is, so
            # overlapping it is free
            return True
        n_proj = max(expected, n, 1)
        row_bytes = self._row_bytes(p, dtype)
        load = Workload(
            update_bytes=row_bytes, n_clients=n_proj,
            dtype_bytes=dtype.itemsize, params=p,
        )
        # cost against the same warmth the round itself will plan with —
        # a cached stream step must not be billed the cold compile term
        warm = self._warm_engines(
            n_proj, p, dtype,
            chunk_rows=self._chunk_rows(n_proj, row_bytes),
            fusion=fusion, n_hint=n_proj,
        )
        return self.planner.prefer_async(
            load, fusion, expected_wait, warm_engines=warm,
        )

    def _aggregate_async(
        self, monitor: Monitor, expected: int, template,
        tenant: str = DEFAULT_TENANT, t_round: Optional[float] = None,
        policy: Optional[ClosePolicy] = None,
        t_round_store: Optional[float] = None,
        fusion: Optional[FusionAlgorithm] = None,
    ) -> Tuple[PyTree, RoundReport]:
        """Arrival-driven round: fuse while stragglers write (Algorithm 1
        with the monitor folded INTO the ingest stream). The gate —
        static threshold/timeout or the controller's learned policy —
        closes the stream; folded updates are consumed from the
        tenant's store partition (other tenants' concurrent arrivals
        are invisible); stragglers missing the close age into the next
        round (per tenant)."""
        fusion = fusion if fusion is not None else self.fusion
        if t_round is None:
            t_round = monitor.clock()
        if t_round_store is None:
            t_round_store = self.store.clock()
        # learn (P, dtype) from the first arrival — or time out empty
        with spans.span("monitor.wait"):
            while True:
                count = self.store.count(tenant)
                waited = monitor.clock() - t_round
                if count > 0 or monitor.should_close(count, waited):
                    break
                self.store.wait_for_arrival(monitor.poll_interval,
                                            monitor.sleep)
        if self.store.count(tenant) == 0:
            mr = monitor.result(0, monitor.clock() - t_round)
            return self._empty_round(
                mr, template, async_round=True, tenant=tenant,
                t_round=t_round, expected=expected,
            )
        n_now, p, dtype = self.store.meta(tenant)
        row_bytes = self._row_bytes(p, dtype)
        n_proj = max(expected, n_now, 1)
        chunk_rows = self._chunk_rows(n_proj, row_bytes)
        load = Workload(
            update_bytes=row_bytes, n_clients=n_proj,
            dtype_bytes=dtype.itemsize, params=p,
        )
        plan = self.planner.plan(
            load, fusion,
            warm_engines=self._warm_engines(
                n_proj, p, dtype, chunk_rows=chunk_rows,
                fusion=fusion, n_hint=n_proj,
            ),
        )
        engine = self._stream_engine(plan.engine)

        closed_at: Dict[str, float] = {}

        def should_close(count: int, _stream_waited: float) -> bool:
            # waited is measured from ROUND start: the pre-first-arrival
            # poll above is part of the same monitor window
            waited = monitor.clock() - t_round
            done = monitor.should_close(count, waited)
            if done and "waited" not in closed_at:
                closed_at["count"] = count
                closed_at["waited"] = waited
            return done

        gamma = self.staleness_discount
        # carry/ages are per-tenant entries, but the MAPS are shared
        # across tenant round threads — reads take the state lock (the
        # tenant round lock serializes same-tenant rounds, so the
        # snapshot stays valid for the whole round)
        with self._state_lock:
            ages = self._stale_ages.get(tenant, {})
            carry = self._carry.get(tenant)
        folded: List[str] = []
        folded_versions: Dict[str, int] = {}
        io_stats: Dict[str, float] = {}

        def blocks():
            for block, w, ids in self.store.iter_arrivals(
                chunk_rows, should_close,
                poll_interval=monitor.poll_interval,
                clock=monitor.clock, sleep=monitor.sleep,
                versions_out=folded_versions, stats_out=io_stats,
                tenant=tenant,
            ):
                folded.extend(ids)
                if gamma is not None and ages:
                    scale = np.asarray(
                        [gamma ** ages.get(cid, 0) for cid in ids],
                        np.float32,
                    )
                    yield block, w, scale
                else:
                    yield block, w

        init = None
        if gamma is not None and carry is not None:
            init = fusion.discount_state(carry, gamma)
        t0 = time.perf_counter()
        fused, srep = engine.fuse_stream(
            fusion, blocks(), init=init, chunk_rows=chunk_rows,
            device_sem=self.device_sem, n_hint=n_proj,
        )
        dt = time.perf_counter() - t0

        # arrival snapshot BEFORE the consume drops timestamps — the
        # adaptive controller's training signal for this tenant's curve
        arrivals = self.store.arrival_times(tenant)
        # queue semantics: what we folded is consumed from the tenant's
        # partition (version-checked — an update re-written mid-round
        # survives for the next round); what raced past the close stays,
        # one round staler
        with spans.span("store.consume", n=len(folded)):
            self.store.remove(folded, versions=folded_versions,
                              tenant=tenant)
        # compute the next-age map BEFORE taking the state lock:
        # client_ids() takes the STORE lock, and the declared order
        # (state inner-most) forbids acquiring it under _state_lock
        next_ages = {
            cid: ages.get(cid, 0) + 1
            for cid in self.store.client_ids(tenant)
        }
        with self._state_lock:
            if gamma is not None:
                self._carry[tenant] = srep.acc_state
            self._stale_ages[tenant] = next_ages

        overlap = closed_at.get("waited", 0.0)
        mr = monitor.result(
            int(closed_at.get("count", len(folded))), overlap,
        )
        # the engine's ingest clock times next(it), which for the arrival
        # stream is dominated by the IDLE poll wait; report actual block
        # staging I/O instead so phases stay comparable across round modes
        # (the wait itself is the `overlap` phase / overlap_seconds)
        phase = {
            "ingest": io_stats.get("load_seconds", 0.0),
            "compile": srep.compile_seconds,
            "compute": srep.compute_seconds,
            "overlap": overlap,
        }
        return self._finish(
            fused, template, plan, srep.n_rows, load, dt, mr,
            expected, True, phase,
            overlap_seconds=overlap, async_round=True,
            tenant=tenant, policy=policy, t_round=t_round_store,
            expected=expected, arrivals=arrivals,
            ingest_bytes=srep.ingest_bytes, fusion=fusion,
        )

    def _empty_round(
        self, monitor_result: MonitorResult, template, async_round=False,
        tenant: str = DEFAULT_TENANT, t_round: Optional[float] = None,
        expected: Optional[int] = None,
    ) -> Tuple[None, RoundReport]:
        """Timed-out round with nothing to fuse: a structured report (the
        caller keeps the previous model) instead of a LookupError."""
        if self.controller is not None and expected:
            # an empty window is evidence too: the tenant's attainable
            # fraction decays toward zero
            self.controller.observe_round(tenant, [], expected)
        plan = Plan(
            engine="local", workload_class=WorkloadClass.VMEM_RESIDENT,
            est_seconds=0.0, breakdown={}, n_devices=1, feasible=True,
            reason="empty round: monitor timed out with no arrivals",
        )
        report = RoundReport(
            plan=plan, n_clients=0, update_bytes=0, fuse_seconds=0.0,
            monitor=monitor_result, route_next_to_store=True,
            streamed=False, phase_seconds={}, async_round=async_round,
            empty=True, tenant=tenant,
            store_stats=self.store.stats_for(tenant),
        )
        with self._state_lock:
            self.history.append(report)
            if monitor_result is not None:
                self._last_wait[tenant] = monitor_result.waited
        return None, report

    # -- round epilogue -------------------------------------------------------
    def _finish(
        self, fused, template, plan, n, load, dt, monitor_result,
        expected_clients, streamed, phase,
        overlap_seconds: float = 0.0, async_round: bool = False,
        tenant: str = DEFAULT_TENANT, policy: Optional[ClosePolicy] = None,
        t_round: Optional[float] = None, expected: Optional[int] = None,
        arrivals: Optional[Dict[str, float]] = None,
        ingest_bytes: int = 0,
        fusion: Optional[FusionAlgorithm] = None,
        notes: Tuple[str, ...] = (),
    ):
        fusion = fusion if fusion is not None else self.fusion
        # §III-D3 seamless transition: if next round's projected load would
        # overflow a single chip (even the streamed local path then needs
        # the store as its backing set), tell clients to write to the store.
        # replace(), not a fresh Workload: the projected load must keep
        # the round's REAL payload dtype/size — rebuilding with the
        # default dtype_bytes=4 made int8 rounds project 4x the params
        # they actually carry
        next_load = dataclasses.replace(
            load, n_clients=max(n, expected_clients or n),
        )
        route_next = (
            classify(next_load, self.hw) is WorkloadClass.DISTRIBUTED
            or self.planner.plan(next_load, fusion).engine != "local"
        )

        # feed the round's observed arrival offsets back into the
        # tenant's learned curve (store-gated rounds only)
        if self.controller is not None and arrivals is not None \
                and t_round is not None:
            offsets = [max(t - t_round, 0.0) for t in arrivals.values()]
            self.controller.observe_round(
                tenant, offsets, expected or n, est_seconds=dt,
            )

        report = RoundReport(
            plan=plan,
            n_clients=n,
            update_bytes=load.update_bytes,
            fuse_seconds=dt,
            monitor=monitor_result,
            route_next_to_store=route_next,
            streamed=streamed,
            phase_seconds=phase,
            overlap_seconds=overlap_seconds,
            async_round=async_round,
            tenant=tenant,
            close_policy=policy,
            store_stats=self.store.stats_for(tenant),
            bytes_ingested=ingest_bytes,
            notes=notes,
        )
        with self._state_lock:
            self.history.append(report)
            if monitor_result is not None:
                self._last_wait[tenant] = monitor_result.waited

        if template is not None:
            return flat_vector_to_tree(jnp.asarray(fused), template), report
        return fused, report

    # -- controller persistence (restart continuity) --------------------------
    def save_controller(self, path: str) -> str:
        """Persist the adaptive controller's learned state (per-tenant
        arrival curves + cross-tenant prior) as JSON at
        ``<path>.controller.json`` — pass the same ``path`` as the
        model checkpoint (``repro.checkpoint.save_pytree``) so the
        learned gates travel with the model. Returns the written path.
        Raises ``ValueError`` on a non-adaptive service."""
        from repro.checkpoint import save_controller_state

        if self.controller is None:
            raise ValueError(
                "save_controller needs an adaptive service "
                "(AggregationService(adaptive=True))"
            )
        return save_controller_state(path, self.controller)

    def load_controller(self, path: str) -> None:
        """Restore controller state saved by ``save_controller`` — a
        restarted service resumes with its learned curves instead of
        re-learning from static-timeout rounds. Raises ``ValueError``
        on a non-adaptive service."""
        from repro.checkpoint import load_controller_state

        if self.controller is None:
            raise ValueError(
                "load_controller needs an adaptive service "
                "(AggregationService(adaptive=True))"
            )
        load_controller_state(path, self.controller)


class RoundScheduler:
    """Concurrent round execution for N tenants on ONE service — the
    paper's multi-application edge aggregator without the one-service-
    per-tenant workaround.

    The scheduler owns one daemon WORKER THREAD per tenant (created on
    first ``submit``; same-tenant rounds queue FIFO behind it, so the
    service's per-tenant round lock never blocks a worker — ordering is
    by construction). Rounds for different tenants genuinely overlap:
    each worker's monitor wait, host staging, and controller access run
    concurrently, while device execution is bounded by the service's
    ``device_concurrency`` semaphore (default 1 — on a small edge host
    the only thing worth overlapping is the waiting, which is exactly
    what the paper's concurrency claim needs).

    Starvation control is the UpdateStore's per-tenant quota
    (``store.set_quota(tenant, max_updates=..., max_bytes=...,
    policy="reject"|"evict")``): a noisy tenant saturates its own
    budget and its own worker, never another tenant's monitor or
    partition. Scheduling itself is fair in the trivial sense — every
    tenant has its own worker, so there is no shared run queue to
    starve; the shared resources (device semaphore, compile cache) are
    FIFO under lock contention.

    Use as a context manager::

        with RoundScheduler(service) as sched:
            futs = [sched.submit(t, from_store=True, async_round=True,
                                 expected_clients=48)
                    for t in ("appA", "appB", "appC")]
            results = [f.result() for f in futs]   # (fused, report)

    or one fan-out-and-wait cycle with ``run_round([...])``. Futures
    carry an ``aggregate`` failure as their exception; a scheduler
    shutdown drains queued work before the workers exit."""

    def __init__(self, service: AggregationService):
        self.service = service
        self._queues: Dict[str, "queue.Queue"] = {}
        self._threads: Dict[str, threading.Thread] = {}
        self._lock = threading.Lock()
        self._closed = False

    def submit(
        self, tenant: str = DEFAULT_TENANT, **aggregate_kwargs
    ) -> "Future":
        """Enqueue one ``service.aggregate(tenant=..., **kwargs)`` round
        on the tenant's worker; returns a ``concurrent.futures.Future``
        resolving to ``(fused, RoundReport)``."""
        fut: Future = Future()
        with self._lock:
            if self._closed:
                raise RuntimeError("RoundScheduler is shut down")
            q = self._queues.get(tenant)
            if q is None:
                q = self._queues[tenant] = queue.Queue()
                t = threading.Thread(
                    target=self._worker, args=(q,),
                    name=f"round-scheduler:{tenant}", daemon=True,
                )
                self._threads[tenant] = t
                t.start()
            # enqueue under the lock: a put after shutdown()'s None
            # sentinel would land on a queue no worker reads and the
            # future would never resolve
            q.put((fut, tenant, aggregate_kwargs))
        return fut

    def run_round(
        self, tenants: Sequence[str], **aggregate_kwargs
    ) -> Dict[str, Tuple[PyTree, RoundReport]]:
        """One concurrent fan-out: submit a round for every tenant, wait
        for all, return ``{tenant: (fused, report)}``."""
        futs = {t: self.submit(t, **aggregate_kwargs) for t in tenants}
        return {t: f.result() for t, f in futs.items()}

    def _worker(self, q: "queue.Queue") -> None:
        while True:
            item = q.get()
            if item is None:
                return
            fut, tenant, kwargs = item
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(
                    self.service.aggregate(tenant=tenant, **kwargs)
                )
            except BaseException as exc:
                fut.set_exception(exc)

    def tenants(self) -> List[str]:
        """Tenants with a live worker."""
        with self._lock:
            return sorted(self._threads)

    def shutdown(self, wait: bool = True) -> None:
        """Stop accepting rounds; each worker drains its queue and
        exits. With ``wait`` (default) blocks until they have."""
        with self._lock:
            if self._closed:
                threads = list(self._threads.values())
            else:
                self._closed = True
                for q in self._queues.values():
                    q.put(None)
                threads = list(self._threads.values())
        if wait:
            for t in threads:
                t.join()

    def __enter__(self) -> "RoundScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


class FairRoundScheduler:
    """Waiting/running round admission for N tenants on ONE service —
    the sarathi-serve shape: submitted rounds join a per-tenant WAITING
    queue, a single admission loop moves them to RUNNING under a
    concurrency cap, picking the next tenant by weighted-fair virtual
    time with a capacity gate.

    Versus :class:`RoundScheduler` (one always-on worker per tenant,
    all submitted rounds run at once), this scheduler makes admission a
    DECISION:

      * ``max_running`` bounds rounds in flight — on an edge host the
        real bound is host staging memory and device time, not thread
        count;
      * tenant selection is weighted fair queuing: each tenant carries
        a virtual time advanced by ``1 / weight`` per admitted round,
        and the admission loop picks the eligible tenant with the
        smallest vtime (ties by name) — a tenant with weight 2 gets
        twice the round admissions of a weight-1 tenant under
        contention, and an idle tenant's first round is never starved
        behind a busy tenant's backlog (its vtime is clamped forward to
        the current minimum on arrival, the classic WFQ no-credit
        rule);
      * capacity awareness: a round whose projected host-staging
        footprint (2x streamed chunk, from the store partition's live
        ``meta`` — double-buffered blocks) does not fit
        ``capacity_bytes`` alongside the running rounds' footprints
        waits, EXCEPT when nothing is running (a too-big round must
        run alone rather than deadlock);
      * one round per tenant in flight: same-tenant submissions queue
        FIFO (the service's per-tenant round lock would serialize them
        anyway — keeping them waiting keeps their slot available for
        OTHER tenants: no head-of-line blocking).

    Use exactly like ``RoundScheduler``::

        with FairRoundScheduler(svc, max_running=2,
                                weights={"appA": 2.0}) as sched:
            futs = [sched.submit(t, from_store=True,
                                 expected_clients=48)
                    for t in tenants]
            results = [f.result() for f in futs]
    """

    def __init__(
        self,
        service: AggregationService,
        max_running: int = 2,
        weights: Optional[Dict[str, float]] = None,
        capacity_bytes: Optional[int] = None,
    ):
        if max_running < 1:
            raise ValueError("max_running must be >= 1")
        self.service = service
        self.max_running = int(max_running)
        self.capacity_bytes = capacity_bytes
        self._weights = dict(weights or {})
        self._lock = threading.Lock()
        self._wake = threading.Condition(self._lock)
        self._waiting: Dict[str, "queue.SimpleQueue"] = {}
        self._waiting_count: Dict[str, int] = {}
        self._running: Dict[str, int] = {}      # tenant -> footprint
        self._vtime: Dict[str, float] = {}
        self._closed = False
        self._drained = False
        self._admitted = 0  # guarded-by: _lock
        self._slot_wait_s = 0.0  # guarded-by: _lock
        self._admission_order: List[str] = []
        self._workers: List[threading.Thread] = []
        self._loop = threading.Thread(
            target=self._admission_loop, name="fair-scheduler",
            daemon=True,
        )
        self._loop.start()

    # -- submission ----------------------------------------------------------
    def submit(
        self, tenant: str = DEFAULT_TENANT, **aggregate_kwargs
    ) -> "Future":
        """Queue one round; returns a Future resolving to
        ``(fused, RoundReport)`` once the round is admitted AND run."""
        fut: Future = Future()
        with self._wake:
            if self._closed:
                raise RuntimeError("FairRoundScheduler is shut down")
            q = self._waiting.get(tenant)
            if q is None:
                q = self._waiting[tenant] = queue.SimpleQueue()
            q.put((fut, aggregate_kwargs, time.monotonic()))
            self._waiting_count[tenant] = (
                self._waiting_count.get(tenant, 0) + 1
            )
            self._wake.notify_all()
        return fut

    def run_round(
        self, tenants: Sequence[str], **aggregate_kwargs
    ) -> Dict[str, Tuple[PyTree, RoundReport]]:
        """One fair fan-out: submit a round per tenant, wait for all."""
        futs = {t: self.submit(t, **aggregate_kwargs) for t in tenants}
        return {t: f.result() for t, f in futs.items()}

    # -- admission -----------------------------------------------------------
    def _footprint(self, tenant: str) -> int:
        """Projected host-staging bytes for the tenant's next round:
        two streamed chunks (double buffering), sized from the LIVE
        store partition. An empty partition projects 0 — the round
        will gate on its monitor, not on staging memory."""
        store = getattr(self.service, "store", None)
        if store is None:
            return 0
        try:
            n, p, dtype = store.meta(tenant)
        except LookupError:
            return 0
        row = self.service._row_bytes(p, dtype)
        rows = self.service._chunk_rows(n, row)
        return 2 * rows * row

    def _eligible_locked(self) -> Optional[str]:
        """The weighted-fair pick among tenants with waiting rounds,
        honoring the running cap, one-in-flight-per-tenant, and the
        capacity gate. Caller holds ``self._lock``."""
        if len(self._running) >= self.max_running:
            return None
        used = sum(self._running.values())
        best: Optional[Tuple[float, str]] = None
        for tenant, count in self._waiting_count.items():
            if count <= 0 or tenant in self._running:
                continue
            vt = self._vtime.get(tenant, 0.0)
            if best is None or (vt, tenant) < best:
                # capacity gate: the footprint probe touches the store
                # index (cheap), so only probe the current best
                fp = self._footprint(tenant)
                if self.capacity_bytes is not None and self._running \
                        and used + fp > self.capacity_bytes:
                    continue
                best = (vt, tenant)
        return best[1] if best else None

    def _admission_loop(self) -> None:
        while True:
            with self._wake:
                tenant = self._eligible_locked()
                while tenant is None:
                    if self._closed and not any(
                        c > 0 for c in self._waiting_count.values()
                    ) and not self._running:
                        self._drained = True
                        self._wake.notify_all()
                        return
                    self._wake.wait(timeout=0.5)
                    tenant = self._eligible_locked()
                fut, kwargs, submitted = self._waiting[tenant].get_nowait()
                slot_wait = time.monotonic() - submitted
                self._slot_wait_s += slot_wait
                self._waiting_count[tenant] -= 1
                fp = self._footprint(tenant)
                self._running[tenant] = fp
                # WFQ no-credit rule: an idle tenant resumes at the
                # current virtual time, not at zero — it gets its fair
                # share from NOW, not a starvation-inducing backlog of
                # credit
                floor = min(
                    (self._vtime[t] for t in self._running
                     if t in self._vtime), default=0.0,
                )
                vt = max(self._vtime.get(tenant, 0.0), floor)
                weight = max(self._weights.get(tenant, 1.0), 1e-9)
                self._vtime[tenant] = vt + 1.0 / weight
                self._admitted += 1
                self._admission_order.append(tenant)
            worker = threading.Thread(
                target=self._run_one, args=(tenant, fut, kwargs, slot_wait),
                name=f"fair-round:{tenant}", daemon=True,
            )
            # track round workers so shutdown() can join them — a
            # drained queue only means each worker popped its tenant
            # from _running, not that the thread has exited
            with self._wake:
                self._workers = [
                    w for w in self._workers if w.is_alive()
                ]
                self._workers.append(worker)
            worker.start()

    def _run_one(self, tenant: str, fut: "Future", kwargs: dict,
                 slot_wait: float) -> None:
        if not fut.set_running_or_notify_cancel():
            with self._wake:
                self._running.pop(tenant, None)
                self._wake.notify_all()
            return
        try:
            fused, report = self.service.aggregate(tenant=tenant, **kwargs)
            report.phase_seconds["queue"] = slot_wait
            fut.set_result((fused, report))
        except BaseException as exc:
            fut.set_exception(exc)
        finally:
            with self._wake:
                self._running.pop(tenant, None)
                self._wake.notify_all()

    # -- introspection / shutdown --------------------------------------------
    def running(self) -> List[str]:
        """Tenants with an admitted round in flight."""
        with self._lock:
            return sorted(self._running)

    def waiting(self) -> Dict[str, int]:
        """Waiting round count per tenant."""
        with self._lock:
            return {t: c for t, c in self._waiting_count.items() if c}

    def admission_order(self) -> List[str]:
        """Tenants in admission order (the fairness audit trail)."""
        with self._lock:
            return list(self._admission_order)

    def stats(self) -> dict:
        """Rounds admitted and running, and ``slot_wait_s``: the summed
        wait of the admitted rounds from submit to admission."""
        with self._lock:
            return {"admitted": self._admitted,
                    "running": len(self._running),
                    "slot_wait_s": self._slot_wait_s}

    def shutdown(self, wait: bool = True) -> None:
        """Stop accepting submissions; drain waiting rounds, then stop
        the admission loop. ``wait`` blocks until drained."""
        with self._wake:
            self._closed = True
            self._wake.notify_all()
        if wait:
            with self._wake:
                while not self._drained:
                    self._wake.wait(timeout=0.5)
            self._loop.join(timeout=10.0)
            with self._wake:
                workers = list(self._workers)
                self._workers = []
            for worker in workers:
                worker.join(timeout=10.0)

    def __enter__(self) -> "FairRoundScheduler":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
