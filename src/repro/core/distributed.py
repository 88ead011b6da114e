"""Distributed aggregation engine — the paper's Spark-MapReduce path,
re-thought as ``shard_map`` over the TPU mesh (§III-D2, DESIGN.md §2).

Layouts (mesh axes: optional "pod", "data", "model"):
  * reducible fusions:     updates (n, P) sharded P(client_axes, "model").
        map    = local partial weighted-sum over the client shard,
        reduce = psum over the client axes (paper's MapReduce reduce).
        Result: (P,) sharded over "model".
  * coordinate-wise:       all_to_all re-shards clients -> coordinates, so
        each device holds ALL n client values for a slice of coordinates
        (what Spark's shuffle does before a per-key reduce), then applies
        the op locally. Result sharded over ("model", client_axes).
  * Krum / Zeno / GeoMedian: updates sharded P(None, all axes) — full
        client rows never materialize on one device; pairwise Gram blocks
        / score terms are computed per coordinate shard and psum'd.

Compiled paths are PERSISTENT across rounds: the ``shard_map`` closures
(which the seed rebuilt and re-``jax.jit``'d on every ``fuse()`` call)
live in a per-engine CompiledCache keyed by (fusion, padded shape, dtype,
path), AOT-compiled against concrete sharded example inputs so compile
time is measured per key — cold vs warm rounds are distinguishable via
``last_compile_seconds`` exactly like the local engine. Reducible rounds
additionally bucket the client count to the next power of two
(zero-weight padded rows), so elastic rounds with varying ``n`` reuse ONE
executable instead of re-tracing.

Reducible rounds can also STREAM: ``fuse_stream`` folds (chunk, P)
blocks (off ``UpdateStore.iter_chunks``, or the service-adapted arrival
stream) through one cached shard_map step executable whose (P,)-sharded
accumulator lives on the mesh — host staging is O(chunk * P) per block,
never the dense (n, P) matrix.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.compress import BLOCK, CompressedBlock, stack_block
from repro.core.fusion.base import FusionAlgorithm
from repro.core.fusion.robust import GeometricMedian, Krum, Zeno
from repro.core.local import StreamReport, _check_scale
from repro.utils import spans
from repro.utils.jitcache import CompiledCache, bucket_rows, fusion_cache_key


def _device_put(mesh: Mesh, x, spec: P):
    """Place ``x`` on the mesh. A host array goes straight to its shards
    (staging it whole on the default device first would put every
    (n, P) block on chip 0 before the reshard); a device array is
    resharded where it lives."""
    if not isinstance(x, jax.Array):
        x = np.asarray(x)
    return jax.device_put(x, NamedSharding(mesh, spec))


@dataclasses.dataclass
class DistributedEngine:
    """Map-reduce fusion over a device mesh."""

    mesh: Mesh
    client_axes: Tuple[str, ...] = ("data",)
    param_axis: str = "model"
    hierarchical: bool = False   # reduce within pod first, then across pods

    name: str = "distributed"

    def __post_init__(self):
        names = self.mesh.axis_names
        self.client_axes = tuple(a for a in self.client_axes if a in names)
        if "pod" in names and "pod" not in self.client_axes:
            # pods shard clients too (each pod's edge aggregates its region)
            self.client_axes = ("pod",) + self.client_axes
        self._n_client_shards = int(
            np.prod([self.mesh.shape[a] for a in self.client_axes])
        )
        self._n_param_shards = self.mesh.shape.get(self.param_axis, 1)
        self.cache = CompiledCache(name=f"distributed:{id(self.mesh)}")
        # per-THREAD compile accounting — concurrent rounds sharing this
        # engine each see their own fuse call's compile phase
        self._tls = threading.local()

    @property
    def last_compile_seconds(self) -> float:
        """Compile seconds paid by the CURRENT thread's last fuse call
        (0.0 on warm rounds); thread-local under concurrent rounds."""
        return getattr(self._tls, "compile_seconds", 0.0)

    @last_compile_seconds.setter
    def last_compile_seconds(self, value: float) -> None:
        self._tls.compile_seconds = value

    # -- shape bucketing -----------------------------------------------------
    def _padded_rows(self, n: int, reducible: bool) -> int:
        """Reducible rounds bucket n to a power of two (executable reuse);
        order-statistic paths pad only to the shard multiple — they slice
        padding by the REAL n inside the kernel, so their executables are
        n-specific anyway."""
        if reducible:
            b = bucket_rows(n)
            return b + ((-b) % self._n_client_shards)
        return n + ((-n) % self._n_client_shards)

    def is_warm(self, fusion, n: int, P_: int, dtype) -> bool:
        """Would this round hit an already-compiled executable?"""
        key = self._fuse_key(fusion, n, P_, dtype)
        return key in self.cache

    def _fuse_key(self, fusion, n: int, P_: int, dtype):
        pn = self._padded_rows(n, fusion.reducible)
        pad_p = (-P_) % (self._n_param_shards * self._n_client_shards)
        n_real = None if fusion.reducible else n
        return (
            fusion_cache_key(fusion), pn, P_ + pad_p, np.dtype(dtype).str,
            n_real, self.hierarchical,
        )

    # -- public -------------------------------------------------------------
    def fuse(self, fusion: FusionAlgorithm, updates, weights) -> jax.Array:
        """updates (n, P), weights (n,). Returns fused (P,) (sharded)."""
        self.last_compile_seconds = 0.0
        n, P_ = np.shape(updates)
        if weights is None:
            weights = jnp.ones((n,), jnp.float32)
        weights = fusion.effective_weights(jnp.asarray(weights, jnp.float32))
        pad_n = self._padded_rows(n, fusion.reducible) - n
        pad_p = (-P_) % (self._n_param_shards * self._n_client_shards)
        if pad_n or pad_p:
            # pad where the matrix lives: host blocks stay on the host
            # until _device_put shards them
            xp = jnp if isinstance(updates, jax.Array) else np
            updates = xp.pad(updates, ((0, pad_n), (0, pad_p)))
            # zero weight => padded rows contribute nothing to reducible
            # fusions; robust paths mask them explicitly
            weights = jnp.pad(weights, (0, pad_n))
        out = self._dispatch(fusion, updates, weights, n)
        return out[:P_]

    # -- dispatch -----------------------------------------------------------
    def _dispatch(self, fusion, updates, weights, n_real: int):
        if fusion.reducible:
            return self._fuse_reducible(fusion, updates, weights, n_real)
        if fusion.coordinatewise:
            return self._fuse_coordinatewise(fusion, updates, weights, n_real)
        if isinstance(fusion, Krum):
            return self._fuse_krum(fusion, updates, weights, n_real)
        if isinstance(fusion, Zeno):
            return self._fuse_zeno(fusion, updates, weights, n_real)
        if isinstance(fusion, GeometricMedian):
            return self._fuse_geomedian(fusion, updates, weights, n_real)
        raise NotImplementedError(
            f"no distributed strategy for fusion {fusion.name!r}"
        )

    def _cspec(self):
        return tuple(self.client_axes) if len(self.client_axes) > 1 else (
            self.client_axes[0] if self.client_axes else None
        )

    # -- reducible: map-reduce ------------------------------------------------
    def _partials(self, fusion, u, w):
        """The local 'map' stage over one client/param shard (full-row
        norms are psum'd over param shards first when the fusion needs
        them), followed by the client-axis reduce."""
        if fusion.needs_row_norms:
            sq = jnp.sum(u.astype(jnp.float32) ** 2, axis=1)
            if self._n_param_shards > 1:
                sq = jax.lax.psum(sq, self.param_axis)
            wsum, tot = fusion.partial_with_norms(u, w, jnp.sqrt(sq))
        else:
            wsum, tot = fusion.partial(u, w)
        if self.hierarchical:
            # edge stage: reduce within the pod's client shards first,
            # then the (smaller) cross-pod reduce — the paper's
            # client-edge-cloud hierarchy on the pod axis.
            for ax in reversed(self.client_axes):
                wsum = jax.lax.psum(wsum, ax)
                tot = jax.lax.psum(tot, ax)
        else:
            wsum = jax.lax.psum(wsum, self.client_axes)
            tot = jax.lax.psum(tot, self.client_axes)
        return wsum, tot

    def _fuse_reducible(self, fusion, updates, weights, n_real):
        mesh = self.mesh
        in_u = P(self._cspec(), self.param_axis)
        in_w = P(self._cspec())
        out = P(self.param_axis)

        def build():
            def mapper(u, w):
                return self._partials(fusion, u, w)

            return jax.shard_map(
                mapper, mesh=mesh, in_specs=(in_u, in_w),
                out_specs=(out, P()), check_vma=False,
            )

        u = _device_put(mesh, updates, in_u)
        w = _device_put(mesh, weights, in_w)
        fn = self._key_get(fusion, updates, None, build, u, w)
        wsum, tot = fn(u, w)
        # combine stays OUTSIDE the compiled closure: FedAvgM/FedAdam keep
        # python-side server state that must update every round, not once
        # at trace time.
        return fusion.combine(wsum, tot)

    # -- coordinate-wise: shuffle (all_to_all) then local --------------------
    def _fuse_coordinatewise(self, fusion, updates, weights, n_real):
        mesh = self.mesh
        in_u = P(self._cspec(), self.param_axis)
        out = P((self.param_axis,) + tuple(self.client_axes))

        def build():
            def mapper(u):
                for ax in self.client_axes:
                    u = jax.lax.all_to_all(
                        u, ax, split_axis=1, concat_axis=0, tiled=True
                    )
                # u now holds ALL padded client rows for a coordinate
                # slice; drop padding rows so order statistics are exact.
                u = u[:n_real]
                return fusion.fuse(u, None)

            return jax.shard_map(
                mapper, mesh=mesh, in_specs=(in_u,), out_specs=out,
                check_vma=False,
            )

        u = _device_put(mesh, updates, in_u)
        fn = self._key_get(fusion, updates, n_real, build, u)
        return fn(u)

    # -- Krum: psum'd Gram matrix --------------------------------------------
    def _fuse_krum(self, fusion: Krum, updates, weights, n_real):
        mesh = self.mesh
        all_axes = tuple(self.client_axes) + (self.param_axis,)
        in_u = P(None, all_axes)
        out = P(all_axes)

        def build():
            def mapper(u):
                uf = u.astype(jnp.float32)
                gram = jax.lax.psum(uf @ uf.T, all_axes)
                gram = gram[:n_real, :n_real]
                idx = fusion.select_from_gram(gram)
                return jnp.mean(uf[:n_real][idx], axis=0)

            return jax.shard_map(
                mapper, mesh=mesh, in_specs=(in_u,), out_specs=out,
                check_vma=False,
            )

        u = _device_put(mesh, updates, in_u)
        fn = self._key_get(fusion, updates, n_real, build, u)
        return fn(u)

    # -- Zeno: psum'd scores ---------------------------------------------------
    def _fuse_zeno(self, fusion: Zeno, updates, weights, n_real):
        mesh = self.mesh
        all_axes = tuple(self.client_axes) + (self.param_axis,)
        in_u = P(None, all_axes)
        out = P(all_axes)
        g_val = fusion._g_val

        def build():
            def mapper(u, g):
                uf = u.astype(jnp.float32)
                inner = jax.lax.psum(uf @ g, all_axes)[:n_real]
                sq = jax.lax.psum(jnp.sum(uf * uf, axis=1), all_axes)[:n_real]
                s = fusion.scores(inner, sq)
                keep = max(n_real - fusion.n_suspect, 1)
                _, idx = jax.lax.top_k(s, keep)
                return jnp.mean(uf[:n_real][idx], axis=0)

            return jax.shard_map(
                mapper, mesh=mesh, in_specs=(in_u, P(all_axes)),
                out_specs=out, check_vma=False,
            )

        u = _device_put(mesh, updates, in_u)
        if g_val is None:
            g_val = jnp.mean(jnp.asarray(updates, jnp.float32), axis=0)
        g = _device_put(mesh, jnp.asarray(g_val, jnp.float32), P(all_axes))
        fn = self._key_get(fusion, updates, n_real, build, u, g)
        return fn(u, g)

    # -- Geometric median: distributed Weiszfeld -------------------------------
    def _fuse_geomedian(self, fusion: GeometricMedian, updates, weights,
                        n_real):
        mesh = self.mesh
        all_axes = tuple(self.client_axes) + (self.param_axis,)
        in_u = P(None, all_axes)
        out = P(all_axes)

        def build():
            def mapper(u, w):
                uf = u.astype(jnp.float32)[:n_real]
                wf = w.astype(jnp.float32)[:n_real]
                wf = wf / jnp.sum(wf)
                z = jnp.einsum("np,n->p", uf, wf)

                def step(z, _):
                    d2 = jax.lax.psum(
                        jnp.sum((uf - z[None, :]) ** 2, axis=1), all_axes
                    )
                    d = jnp.sqrt(d2)
                    beta = wf / jnp.maximum(d, fusion.smooth)
                    beta = beta / jnp.sum(beta)
                    return jnp.einsum("np,n->p", uf, beta), None

                z, _ = jax.lax.scan(step, z, None, length=fusion.iters)
                return z

            return jax.shard_map(
                mapper, mesh=mesh, in_specs=(in_u, P(None)), out_specs=out,
                check_vma=False,
            )

        u = _device_put(mesh, updates, in_u)
        w = _device_put(mesh, weights, P(None))
        fn = self._key_get(fusion, updates, n_real, build, u, w)
        return fn(u, w)

    # -- streaming: per-shard chunked ingest ----------------------------------
    def _stream_key(self, fusion, chunk: int, P_: int, dtype, sig):
        pc = chunk + (-chunk) % self._n_client_shards
        pad_p = (-P_) % (self._n_param_shards * self._n_client_shards)
        return ("stream", fusion_cache_key(fusion), pc, P_ + pad_p,
                np.dtype(dtype).str, self.hierarchical, sig)

    def _dequant_key(self, chunk: int, P_: int, blk: int, weighted: bool):
        pc = chunk + (-chunk) % self._n_client_shards
        Pq = -(-P_ // blk) * blk
        pad_p = (-P_) % (self._n_param_shards * self._n_client_shards)
        return ("dequant", pc, Pq, blk, P_, P_ + pad_p, weighted)

    def is_warm_stream(self, fusion, chunk: int, P_: int, dtype,
                       block: Optional[int] = None,
                       n_hint: Optional[int] = None) -> bool:
        """Warm-path probe. ``dtype`` int8 probes the COMPRESSED route:
        the on-device dequant executable (at quantization block
        ``block``, default ``compress.BLOCK``) AND the fp32 fold step it
        feeds — a compressed round is only warm with both. ``n_hint``
        sizes order-statistic carve state (its executables are keyed by
        the carve capacity)."""
        if not fusion.streamable:
            return False
        try:
            sig = fusion.state_signature(P_, n_hint)
        except ValueError:   # carve fusion with no n_hint: can't stream
            return False
        if np.dtype(dtype) == np.int8:
            blk = int(block) if block else BLOCK
            return (
                self._dequant_key(chunk, P_, blk, fusion.weighted)
                in self.cache
                and self._stream_key(fusion, chunk, P_, np.float32, sig)
                in self.cache
            )
        return self._stream_key(fusion, chunk, P_, dtype, sig) in self.cache

    def _dequant_fn(self, pc, Pq, blk, dim, pdim, u_spec, weighted,
                    q_ex, s_ex):
        """Cached on-device dequant executable for streamed compressed
        blocks: (codes (pc, Pq) int8, scales (pc, Pq//blk) fp32) ->
        (pc, pdim) fp32, output sharding-constrained to the step
        executable's update layout (``u_spec`` — client-sharded for the
        sum path, client-replicated for the carve path) — so the fp32
        block exists only as a device-side transient between two
        compiled artifacts, never on the host, and mixed fp32/int8
        rounds share ONE fold step and ONE on-mesh accumulator."""
        mesh = self.mesh
        key = ("dequant", pc, Pq, blk, dim, pdim, weighted)

        def build():
            def deq(q, s):
                u = (
                    q.astype(jnp.float32).reshape(pc, Pq // blk, blk)
                    * s[:, :, None]
                ).reshape(pc, Pq)[:, :dim]
                if pdim != dim:
                    u = jnp.pad(u, ((0, 0), (0, pdim - dim)))
                return jax.lax.with_sharding_constraint(
                    u, NamedSharding(mesh, u_spec)
                )

            return deq

        return self.cache.get(key, build, q_ex, s_ex)

    def _leaf_spec(self, shape, pdim) -> P:
        """Mesh placement for one reducer-state leaf by shape rule:
        trailing param axis sharded over ``param_axis`` ((pdim,) and
        (K, pdim) leaves), scalars replicated."""
        if len(shape) == 0 or shape[-1] != pdim:
            return P()
        if len(shape) == 1:
            return P(self.param_axis)
        return P(*([None] * (len(shape) - 1) + [self.param_axis]))

    def fuse_stream(
        self,
        fusion: FusionAlgorithm,
        blocks: Iterable[Tuple[np.ndarray, ...]],
        init: Optional[tuple] = None,
        chunk_rows: Optional[int] = None,
        device_sem=None,
        n_hint: Optional[int] = None,
    ) -> Tuple[jax.Array, StreamReport]:
        """Per-shard streaming ingest: fold (chunk, P) blocks (e.g. from
        ``UpdateStore.iter_chunks``) through ONE cached shard_map step
        executable. Each block is staged host-side at O(chunk * P) (a
        store :class:`repro.core.compress.RowBlock` is stacked by
        ``stack_block``),
        device_put sharded over (client_axes, param_axis), and psum'd
        into a (P,)-sharded on-mesh accumulator — the dense (n, P)
        matrix never exists on the host. A block may be a
        :class:`repro.core.compress.CompressedBlock` (int8 codes + fp32
        per-block scales): it stages host-side at its COMPRESSED size,
        dequantizes on-device through a cached executable, and feeds
        the same fp32 fold step dense fp32 blocks use — mixed
        dense/compressed rounds (stragglers may be uncompressed) share
        one step and one on-mesh accumulator, and the fp32 matrix never
        exists on the host. Block / ``init`` / ``chunk_rows`` /
        ``n_hint`` semantics match ``LocalEngine.fuse_stream`` (numeric
        per-block staleness scale; carried reducer state in/out via the
        StreamReport; pass the configured ``chunk_rows`` so variable
        final blocks reuse one executable — ``iter_arrivals`` yields
        client ids, adapt it before streaming here; ``device_sem``
        bounds concurrent device execution across rounds sharing this
        engine, and all carry state is per-call local so concurrent
        folds never cross).

        Layouts per reducer family: the SUM path shards blocks
        P(client_axes, param_axis) and psums partials (the historical
        map-reduce); the order-statistic CARVE path shards blocks
        P(None, param_axis) — every device along the client axes holds
        all chunk rows for its coordinate slice and carves them locally,
        no collective needed — with the (K, P) extreme buffers sharded
        over the param axis, so per-device carry stays O(K * P/shards)."""
        if not fusion.streamable:
            raise ValueError(
                f"{fusion.name} is not streamable — streamed aggregation "
                "needs a reducer decomposition (weighted sum or "
                "order-statistic carve)"
            )
        weighted = fusion.weighted
        mesh = self.mesh
        self.last_compile_seconds = 0.0
        if weighted:
            in_u = P(self._cspec(), self.param_axis)
            in_w = P(self._cspec())
        else:
            # carve path: replicate rows across client axes, shard coords
            in_u = P(None, self.param_axis)
            in_w = P(None)
        rep = StreamReport()
        sem = spans.DeviceSlot(device_sem)
        it = iter(blocks)
        steps: dict = {}   # payload dtype -> cached fold step
        deqs: dict = {}    # (Pq, blk) -> cached dequant executable
        state = sig = None
        leaf_specs = None
        chunk = dim = None
        pc = pdim = 0
        compile_total = 0.0
        while True:
            t0 = time.perf_counter()
            try:
                item = next(it)
            except StopIteration:
                break
            rep.ingest_seconds += time.perf_counter() - t0
            with spans.span("engine.stage"):
                # the store's row blocks are stacked on the host here: the
                # sharded device_put takes one host array per block
                block, w = stack_block(item[0]), item[1]
                scale = _check_scale(item[2]) if len(item) > 2 else None
                if scale is not None and not weighted:
                    raise ValueError(
                        f"{fusion.name}: per-row staleness scales are "
                        "unsupported — order statistics cannot discount rows"
                    )
                compressed = isinstance(block, CompressedBlock)
                rows = block.rows if compressed else block.shape[0]
                bdim = block.dim if compressed else block.shape[1]
                if chunk is None:
                    dim = bdim
                    chunk = int(chunk_rows) if chunk_rows else rows
                    rep.chunk_rows = chunk
                    pc = chunk + (-chunk) % self._n_client_shards
                    pdim = dim + (
                        (-dim) % (self._n_param_shards * self._n_client_shards)
                    )
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
                if weighted:
                    wpad = np.zeros((pc,), np.float32)
                    wpad[:rows] = w
                    w_eff = np.array(
                        fusion.effective_weights(jnp.asarray(wpad, jnp.float32))
                    )
                    if scale is not None:
                        w_eff[:rows] *= np.asarray(scale, np.float32)[:rows]
                    w_eff[rows:] = 0.0         # effective_weights may remap pads
                else:
                    # order-statistic fold: weights carry only row VALIDITY
                    w_eff = np.zeros((pc,), np.float32)
                    w_eff[:rows] = 1.0
                t0 = time.perf_counter()
                if compressed:
                    # host staging at the COMPRESSED size; the fp32 block
                    # exists only on device, between the dequant executable
                    # and the fold step
                    Pq, blk = block.codes.shape[1], block.block
                    if rows < pc:
                        qpad = np.zeros((pc, Pq), np.int8)
                        qpad[:rows] = block.codes
                        spad = np.zeros((pc, Pq // blk), np.float32)
                        spad[:rows] = block.scales
                    else:
                        qpad, spad = block.codes, block.scales
                    cspec2 = P(self._cspec(), None) if weighted else P(None, None)
                    q_dev = _device_put(mesh, qpad, cspec2)
                    s_dev = _device_put(mesh, spad, cspec2)
                    deq = deqs.get((Pq, blk))
                    if deq is None:
                        deq, c_s = self._dequant_fn(
                            pc, Pq, blk, dim, pdim, in_u, weighted, q_dev,
                            s_dev,
                        )
                        deqs[(Pq, blk)] = deq
                        compile_total += c_s
                    u_dev = deq(q_dev, s_dev)
                    dtype = np.dtype(np.float32)
                else:
                    if rows < pc or pdim != dim:  # shard-multiple/ragged pad
                        padded = np.zeros((pc, pdim), block.dtype)
                        padded[:rows, :dim] = block
                        block = padded
                    u_dev = _device_put(mesh, block, in_u)
                    dtype = np.dtype(block.dtype)
                w_dev = _device_put(mesh, w_eff, in_w)
                rep.ingest_seconds += time.perf_counter() - t0
                if state is None:
                    host_state = self._stream_state_host(fusion, dim, pdim,
                                                         n_hint, init)
                    leaf_specs = tuple(
                        self._leaf_spec(np.shape(x), pdim) for x in host_state
                    )
                    state = tuple(
                        _device_put(mesh, x, s)
                        for x, s in zip(host_state, leaf_specs)
                    )
                step = steps.get(dtype.str)
                if step is None:
                    def build():
                        def step_fn(u, wv, *leaves):
                            st = tuple(leaves)
                            if fusion.reducible:
                                partial = lambda uu, ww: self._partials(
                                    fusion, uu, ww)
                                new = fusion.fold_block(st, u, wv,
                                                        partial=partial)
                            else:
                                # local carve per coordinate shard — rows are
                                # replicated across client axes, no collective
                                new = fusion.fold_block(st, u, wv)
                            return tuple(new)

                        return jax.shard_map(
                            step_fn, mesh=mesh,
                            in_specs=(in_u, in_w) + leaf_specs,
                            out_specs=leaf_specs, check_vma=False,
                        )

                    step, compile_s = self.cache.get(
                        self._stream_key(fusion, chunk, dim, dtype, sig),
                        build, u_dev, w_dev, *state,
                    )
                    steps[dtype.str] = step
                    # mixed rounds accumulate one compile per payload kind
                    compile_total += compile_s
            rep.compile_seconds = compile_total
            self.last_compile_seconds = compile_total
            t0 = time.perf_counter()
            with sem, spans.span("engine.step"):
                state = step(u_dev, w_dev, *state)
                if device_sem is not None:
                    # async dispatch must not escape the execution bound
                    jax.block_until_ready(state)  # lint: disable=sync-under-sem -- deliberate: the permit must cover device EXECUTION, not just dispatch (PR 5's device_concurrency contract)
            rep.compute_seconds += time.perf_counter() - t0
            rep.n_rows += rows
            rep.n_blocks += 1
        if rep.n_blocks == 0:
            if init is None:
                raise ValueError("fuse_stream: empty block iterator")
            # carry-only round: nothing arrived, finalize the carried state
            dim = int(np.shape(init[0])[-1])
            state = tuple(jnp.asarray(x, jnp.float32) for x in init)
            pdim = dim
        t0 = time.perf_counter()
        # slice param-padded leaves back to the real dim BEFORE finalize:
        # padded coordinates carry garbage (inf sentinels on the carve
        # path) that must never reach the finalize arithmetic
        with spans.span("engine.copyout"):
            host_leaves = tuple(np.asarray(x) for x in state)
        sliced = tuple(
            x[..., :dim] if x.ndim and x.shape[-1] == pdim else x
            for x in host_leaves
        )
        rep.acc_state = sliced
        if fusion.reducible:
            rep.acc_wsum = sliced[0]
            rep.acc_tot = float(sliced[1])
        with sem, spans.span("engine.finalize"):
            fused = jax.block_until_ready(fusion.finalize(sliced))  # lint: disable=sync-under-sem -- deliberate: the permit must cover device EXECUTION, not just dispatch (PR 5's device_concurrency contract)
        rep.compute_seconds += time.perf_counter() - t0
        return fused, rep

    def _stream_state_host(self, fusion, dim, pdim, n_hint, init):
        """Initial reducer state as host arrays, zero-padded on the
        param axis to the shard multiple so carried state re-shards
        cleanly (padded coords are sliced off before finalize)."""
        proto = tuple(fusion.init_state(dim, n_hint))
        if init is not None:
            if len(init) != len(proto):
                raise ValueError(
                    f"fuse_stream: carried state has {len(init)} leaves, "
                    f"{fusion.name} expects {len(proto)}"
                )
            for x, p in zip(init, proto):
                if np.shape(x) != np.shape(p):
                    raise ValueError(
                        f"fuse_stream: carried accumulator has shape "
                        f"{np.shape(x)}, stream blocks have dim {dim}"
                    )
            proto = tuple(np.asarray(x, np.float32) for x in init)
        out = []
        for leaf in proto:
            leaf = np.asarray(leaf, np.float32)
            if leaf.ndim and leaf.shape[-1] == dim and pdim != dim:
                pad = [(0, 0)] * (leaf.ndim - 1) + [(0, pdim - dim)]
                leaf = np.pad(leaf, pad)
            out.append(leaf)
        return tuple(out)

    # -- cache plumbing -------------------------------------------------------
    def _key_get(self, fusion, padded_updates, n_real, build, *concrete):
        """Fetch (or AOT-compile against the concrete sharded example
        inputs) the executable for this round's padded shape, accumulating
        measured compile seconds into ``last_compile_seconds``."""
        pn, pp = np.shape(padded_updates)
        key = (
            fusion_cache_key(fusion), pn, pp,
            np.dtype(padded_updates.dtype).str, n_real, self.hierarchical,
        )
        fn, compile_s = self.cache.get(key, build, *concrete)
        self.last_compile_seconds += compile_s
        return fn
