"""HBM-resident vector store.

The reference keeps vectors in a RAM cache (vector/cache/sharded_lock_cache.go)
plus an lsmkv bucket on disk (vector/flat/index.go:164-175). On TPU the
authoritative hot copy lives in HBM as capacity-padded JAX arrays:

- ``vectors``  [C, d]  storage dtype f32 (exact) or bf16 (2x capacity)
- ``valid``    [C]     live-slot mask (False = unfilled or tombstoned)
- ``sq_norms`` [C]     cached squared row norms (corpus term of the l2 expansion)

Mutability under XLA's immutable-buffer model (SURVEY §7 hard part #1):
writes are scatter updates inside a jitted function whose buffers are
*donated*, so XLA updates HBM in place — no copy, no realloc per insert.
Deletes flip ``valid`` bits (tombstones, reference: hnsw/index.go:115); the
mask is applied inside the top-k scan so dead slots never win. Capacity
grows by power-of-two re-allocation (one recompile per capacity level).

When a mesh is provided, all three arrays are row-sharded over the ``shard``
axis and every update/search runs SPMD; slot→device placement is implicit
(slot // rows_per_device), the TPU analog of the reference's murmur3
shard ring (usecases/sharding/state.go:167-176).
"""

from __future__ import annotations

import functools
import os
import threading
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu.ops.candidates import shared_candidates_topk
from weaviate_tpu.ops.distances import normalize
from weaviate_tpu.ops.topk import chunked_topk_distances
from weaviate_tpu.runtime import hbm_ledger, kernelscope, placement, tracing
from weaviate_tpu.runtime import transfer
from weaviate_tpu.runtime.transfer import DeviceResultHandle
from weaviate_tpu.parallel.mesh import n_row_shards, shardable_capacity
from weaviate_tpu.parallel.sharded_search import (
    replicate_array,
    shard_array,
    sharded_topk,
)

_DEFAULT_CHUNK = 8192

# The per-chunk selector of every store's scan (ops/topk.py
# ``chunked_topk_distances``): approx_max_k candidates, 4x oversampled,
# with exact carry merges. Non-TPU backends lower it to the exact top_k.
SCAN_SELECTION = "approx"


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


class AllowBits(NamedTuple):
    """Per-query filters as the scan takes them, already on the device:
    ``bits`` [B, capacity_pad / 32] uint32 in ``pack_allow_bitmask``'s
    layout. What an index that keeps its filters' operands
    (engine/filter_operands.py) hands ``search_async`` in place of a
    [B, capacity] bool block."""
    bits: jax.Array


class AllowSlots(NamedTuple):
    """ONE filter shared by the batch as the gathered cutover takes it
    (``DeviceVectorStore.gathered_slots``): ``slots`` [bucket] int32 on
    the device, the ``count`` allowed slots in ascending order, then -1;
    None where the filter is too broad for the cutover."""
    slots: jax.Array | None
    count: int


def normalize_allow_mask(allow_mask, n_queries: int):
    """Shared allow-mask intake for the plain and quantized stores:
    [1, C] broadcasts to the shared [C] form (keeping the gathered
    low-selectivity cutover); a [B, C] mask must match the query count.
    Operands an index prepared (``AllowBits``, ``AllowSlots``) pass."""
    if allow_mask is None or isinstance(allow_mask, (AllowBits, AllowSlots)):
        return allow_mask
    allow_mask = np.asarray(allow_mask)
    if allow_mask.ndim == 2 and allow_mask.shape[0] == 1:
        allow_mask = allow_mask[0]
    elif allow_mask.ndim == 2 and allow_mask.shape[0] != n_queries:
        raise ValueError(
            f"allow_mask rows {allow_mask.shape[0]} != "
            f"queries {n_queries}")
    return allow_mask


@jax.jit
def apply_allow_mask(valid, allow):
    """A shared allow list folded into the live-row mask on the device.
    One op in one program, as the eager ``logical_and`` it replaces was:
    jitted only so that a profile names the program by its role
    (``jit_apply_allow_mask``) instead of by its op."""
    return jnp.logical_and(valid, allow)


@jax.jit
def stack_allow_rows(*rows):
    """A dispatch's ``allow_bits`` [B, W] from B packed rows [W] that are
    already on the device, a row a query in order; a mask that several
    queries carry is passed as often (the same buffer). One program a
    padded batch size, the scan's own variants and no more."""
    return jnp.stack(rows)


def batched_mask_operands(allow_mask, n_queries: int, capacity: int, mesh,
                          owner: dict | None = None, device=None):
    """[B, capacity] per-query mask -> scan-kernel operands, under a
    ``store.mask_pack`` span: single-device packs the bitmask on the host
    (32x smaller transfer); a mesh ships the bool mask column-sharded so
    each device packs its own row-aligned slice on device. Returns
    (allow_bits, allow_rows_dev) — exactly one is non-None. ``owner``
    labels the transient device buffer in the HBM ledger (weakref-
    tracked: the entry lives exactly as long as the buffer). ``AllowBits``
    (single device only) were packed by the index that prepared them."""
    if isinstance(allow_mask, AllowBits):
        return allow_mask.bits, None
    owner = owner or {}
    with tracing.span("store.mask_pack", stage="mask_pack",
                      queries=n_queries):
        if mesh is None:
            from weaviate_tpu.ops.pallas_kernels import (mask_pad_cols,
                                                         pack_allow_bitmask)

            bits = placement.put(pack_allow_bitmask(
                allow_mask, mask_pad_cols(capacity)), device)
            hbm_ledger.ledger.track("allow_bitmask", bits, **owner)
            return bits, None
        if (allow_mask.shape == (n_queries, capacity)
                and allow_mask.dtype == np.bool_):
            full = allow_mask  # already the exact shape — no copy
        else:
            full = np.zeros((n_queries, capacity), dtype=bool)
            w = min(allow_mask.shape[1], capacity)
            full[:, :w] = allow_mask[:, :w]
        from weaviate_tpu.parallel.sharded_search import tracked_shard_array

        return None, tracked_shard_array(
            jnp.asarray(full), mesh, dim=1, component="allow_mask",
            owner=owner)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2), static_argnames=("normalize_rows",))
def _scatter_rows(vectors, valid, sq_norms, slots, new_vecs, write_mask,
                  normalize_rows: bool = False):
    """Write ``new_vecs`` [m,d] into rows ``slots`` [m]; rows with
    write_mask=False are redirected to a scratch row (capacity-1 duplicate
    writes are benign because mode='drop' handles OOB)."""
    new_vecs = new_vecs.astype(jnp.float32)
    if normalize_rows:
        new_vecs = normalize(new_vecs)
    new_vecs = new_vecs.astype(vectors.dtype)
    norms = jnp.sum(new_vecs.astype(jnp.float32) ** 2, axis=-1)
    # redirect masked (padding) rows out of range; 'drop' makes them no-ops
    tgt = jnp.where(write_mask, slots, vectors.shape[0])
    vectors = vectors.at[tgt].set(new_vecs, mode="drop")
    valid = valid.at[tgt].set(True, mode="drop")
    sq_norms = sq_norms.at[tgt].set(norms, mode="drop")
    return vectors, valid, sq_norms


@functools.partial(jax.jit, donate_argnums=(0,))
def _clear_slots(valid, slots):
    return valid.at[slots].set(False, mode="drop")


def _probe_scatter(valid, slot: int) -> None:
    """Force one element of a freshly-scattered valid mask to the host.

    jax dispatch is async: ``_scatter_rows`` returning only means the work
    was ENQUEUED. A tiny data-dependent fetch is a completion probe that
    cannot return early (engine/hnsw_build.py:_t) — it surfaces an async
    runtime failure (device OOM, preemption, poisoned buffer) as an
    exception at the flush site, while the staged rows are still held and
    re-flushable, instead of silently dropping rows whose add() already
    returned success. Module-level so tests can inject async failures."""
    bool(np.asarray(valid[slot]))


class DeviceVectorStore:
    """Mutable (host-managed, device-resident) vector store.

    Thread-safe for interleaved add/delete/search (a single host lock guards
    buffer swaps; reads take a snapshot reference — the analog of the
    reference's sharded RW locks in vector/common/sharded_locks.go).
    """

    # ``search_async`` takes filters an index has already put on the
    # device (``AllowBits``, ``AllowSlots``) where ``mesh`` is None
    takes_allow_operands = True

    def __init__(
        self,
        dim: int,
        metric: str = "l2-squared",
        capacity: int = _DEFAULT_CHUNK,
        dtype=jnp.float32,
        mesh=None,
        chunk_size: int = _DEFAULT_CHUNK,
        normalize_on_add: bool | None = None,
        component: str = "corpus",
    ):
        self.dim = dim
        # HBM-ledger component label: the epoch store passes a per-epoch
        # label ("corpus@e3") so /v1/debug/memory and the hbm_bytes gauge
        # attribute device bytes to individual epochs — and releasing an
        # epoch visibly drops exactly its own series
        self.hbm_component = component
        self.metric = metric
        self.dtype = dtype
        self.mesh = mesh
        self.chunk_size = chunk_size
        self.n_shards = n_row_shards(mesh)
        # cosine provider normalizes at insert (reference stores normalized
        # vectors and uses the dot kernel: cosine_dist.go "cosine-dot")
        self.normalize_on_add = (
            metric in ("cosine", "cosine-dot")
            if normalize_on_add is None
            else normalize_on_add
        )
        self._lock = threading.RLock()
        # Compiled Pallas distance kernels on TPU; XLA path elsewhere
        # (interpret-mode Pallas is test-only — far too slow to serve from).
        from weaviate_tpu.ops.pallas_kernels import PALLAS_METRICS, recommended

        self.use_pallas = recommended() and metric in PALLAS_METRICS
        self._count = 0  # high-water mark of allocated slots
        # Host-side append staging: each small add() batch lands in a numpy
        # buffer (microseconds) and rows reach HBM in large amortized
        # scatters — a per-batch device dispatch costs a fixed round trip
        # that dominates the import path. Every read path flushes first, so visibility is
        # unchanged; slot assignment stays eager so callers' id<->slot
        # bookkeeping is identical.
        self._staged_slots: list[np.ndarray] = []
        self._staged_vecs: list[np.ndarray] = []
        self._staged_rows = 0
        self._stage_limit = max(4096, (32 << 20) // (dim * 4))
        # HBM ledger wiring: the (collection, shard, tenant) labels are
        # captured ONCE from the ambient owner scope the shard layer sets
        # around index construction; grows/compacts update the same
        # entries, and a finalizer releases them when the store is
        # dropped (e.g. compress() swapping in a quantized store).
        self._hbm_owner = hbm_ledger.current_owner()
        # the chip the owning shard was placed on (runtime/placement.py):
        # every array this store makes, now or when it grows, is
        # committed there and its programs follow; None on a mesh and
        # outside any shard (the default device, as before)
        self.device = None if mesh is not None \
            else self._hbm_owner.get("device")
        self._hbm_keys: dict[str, int] = {}
        weakref.finalize(self, hbm_ledger.ledger.release_many,
                         self._hbm_keys.values())
        capacity = self._align(capacity)
        self.capacity = capacity
        # host mirror of the live-slot mask + O(1) live counter, both
        # maintained under ``_lock`` by add/set_at/delete/compact — the
        # serving path never syncs on a device sum for a count (the
        # retired G1 ``live_count`` baseline entry; the device mask
        # stays the authority for scans, and WEAVIATE_TPU_DEBUG_COUNTS=1
        # cross-checks the two)
        self._valid_np = np.zeros(capacity, dtype=bool)
        self._live_count = 0
        self._alloc(capacity)

    # -- capacity management -------------------------------------------------

    def _align(self, capacity: int) -> int:
        capacity = max(capacity, 2 * self.n_shards)
        capacity = _next_pow2(capacity)
        cs = min(self.chunk_size, capacity // self.n_shards)
        return shardable_capacity(capacity, self.n_shards, cs)

    def _placed(self, arr, dim=0):
        if self.mesh is None:
            return placement.put(arr, self.device)
        return shard_array(jnp.asarray(arr), self.mesh, dim=dim)

    def _operand(self, arr):
        """A dispatch's host operand (the query block, a slot list)
        where the program will run: straight to the store's device."""
        if self.mesh is None:
            return placement.put(arr, self.device)
        return jnp.asarray(arr)

    def _zeros(self, shape, dtype):
        if self.mesh is None:
            return placement.zeros(shape, dtype, self.device)
        return shard_array(jnp.zeros(shape, dtype), self.mesh)

    def _alloc(self, capacity: int):
        self.vectors = self._zeros((capacity, self.dim), self.dtype)
        self.valid = self._zeros((capacity,), jnp.bool_)
        self.sq_norms = self._zeros((capacity,), jnp.float32)
        self._hbm_sync()

    def _hbm_sync(self):
        """(Re-)publish this store's device footprint into the ledger —
        called after every (re)allocation so totals track capacity, not
        just construction."""
        nbytes = sum(int(a.nbytes)
                     for a in (self.vectors, self.valid, self.sq_norms))
        hbm_ledger.ledger.set_keyed(
            self._hbm_keys, self.hbm_component, nbytes,
            owner=self._hbm_owner,
            dtype=jnp.dtype(self.dtype).name,
            sharding="sharded" if self.mesh is not None else "single")

    def _grow(self, min_capacity: int):
        """Capacity-double the device arrays + host valid mirror.
        Caller holds ``_lock``."""
        from weaviate_tpu.parallel.sharded_search import grow_rows

        new_cap = self._align(_next_pow2(min_capacity))
        pad = new_cap - self.capacity
        self.capacity = new_cap
        grown = np.zeros(new_cap, dtype=bool)
        grown[: len(self._valid_np)] = self._valid_np
        self._valid_np = grown
        # Donated, shard-local zero-pad (no full-array round trip through
        # one device, no transient 2x copy).
        self.vectors = grow_rows(self.vectors, pad, self.mesh)
        self.valid = grow_rows(self.valid, pad, self.mesh)
        self.sq_norms = grow_rows(self.sq_norms, pad, self.mesh)
        self._hbm_sync()

    # -- mutation ------------------------------------------------------------

    def add(self, vectors: np.ndarray) -> np.ndarray:
        """Append a batch [m,d]; returns assigned slot ids [m] (int64).

        Slots are assigned sequentially from the high-water mark. Padding to
        power-of-two batch buckets bounds the number of compiled variants.
        """
        vectors = np.asarray(vectors, dtype=np.float32)
        if vectors.ndim == 1:
            vectors = vectors[None, :]
        m, d = vectors.shape
        if d != self.dim:
            raise ValueError(f"vector dim {d} != store dim {self.dim}")
        with self._lock:
            slots = np.arange(self._count, self._count + m, dtype=np.int64)
            if self._count + m > self.capacity:
                self._grow(self._count + m)
            self._count += m
            # fresh slots from the high-water mark: all newly live
            # (staged rows count — every read path flushes first, so
            # their visibility matches the device mask's)
            self._valid_np[slots] = True
            self._live_count += m
            # copy: the caller may reuse/mutate its buffer before flush
            self._staged_slots.append(slots.astype(np.int32))
            self._staged_vecs.append(vectors.copy())
            self._staged_rows += m
            if self._staged_rows >= self._stage_limit:
                self._flush_staged_locked()
            return slots

    def flush_staged(self) -> None:
        """Push any host-staged rows to device HBM (one padded scatter)."""
        with self._lock:
            self._flush_staged_locked()

    def _flush_staged_locked(self) -> None:
        """Scatter the staged rows to HBM. Caller holds ``_lock`` (the
        _locked suffix is the contract; this lint-checks it too)."""
        m = self._staged_rows
        if m == 0:
            return
        vectors = (self._staged_vecs[0] if len(self._staged_vecs) == 1
                   else np.concatenate(self._staged_vecs))
        slots = (self._staged_slots[0] if len(self._staged_slots) == 1
                 else np.concatenate(self._staged_slots))
        bucket = _next_pow2(max(m, 8))
        # sub-f32 storage (bf16) transfers in the STORAGE dtype — half the
        # host->device bytes; the scan reads bf16 rows either way, and the
        # in-kernel norms then derive from exactly the rows being scanned.
        # cosine keeps f32 staging: rows normalize in-kernel pre-cast.
        stage_dt = (jnp.dtype(self.dtype)
                    if (not self.normalize_on_add
                        and jnp.dtype(self.dtype).itemsize < 4)
                    else np.dtype(np.float32))
        padded = np.zeros((bucket, self.dim), dtype=stage_dt)
        padded[:m] = vectors.astype(stage_dt)
        slot_buf = np.zeros(bucket, dtype=np.int32)
        slot_buf[:m] = slots
        mask = np.zeros(bucket, dtype=bool)
        mask[:m] = True
        # the transfer buffers for the scatter are a real (transient)
        # device allocation — ledger-tracked for the duration of the
        # flush so peak watermarks see import bursts
        stage_key = hbm_ledger.ledger.register(
            "staging", padded.nbytes + slot_buf.nbytes + mask.nbytes,
            dtype=str(stage_dt),
            sharding="replicated" if self.mesh is not None else "single",
            **self._hbm_owner)
        try:
            self.vectors, self.valid, self.sq_norms = _scatter_rows(
                self.vectors,
                self.valid,
                self.sq_norms,
                self._placed_replicated(slot_buf),
                self._placed_replicated(padded),
                self._placed_replicated(mask),
                normalize_rows=self.normalize_on_add,
            )
            # drop the staging buffers only after the scatter MATERIALIZED
            # — dispatch is async, so an exception can surface here
            # (transfer OOM, compile failure at a new bucket) or later on
            # the device (runtime failure on the enqueued scatter). The
            # probe forces the result before the rows stop being
            # re-flushable; one host RTT per flush, amortized over
            # >= _stage_limit staged rows.
            _probe_scatter(self.valid, int(slots[m - 1]))
        finally:
            hbm_ledger.ledger.release(stage_key)
        self._staged_vecs.clear()
        self._staged_slots.clear()
        self._staged_rows = 0

    def set_at(self, slots: np.ndarray, vectors: np.ndarray):
        """Overwrite specific slots (update path)."""
        vectors = np.asarray(vectors, dtype=np.float32)
        slots = np.asarray(slots, dtype=np.int32)
        m = len(slots)
        with self._lock:
            self._flush_staged_locked()
            if m and int(slots.max()) >= self.capacity:
                self._grow(int(slots.max()) + 1)
            self._count = max(self._count, int(slots.max()) + 1 if m else 0)
            if m:
                u = np.unique(slots)
                self._live_count += int(np.count_nonzero(
                    ~self._valid_np[u]))
                self._valid_np[u] = True
            bucket = _next_pow2(max(m, 8))
            padded = np.zeros((bucket, self.dim), dtype=np.float32)
            padded[:m] = vectors
            slot_buf = np.zeros(bucket, dtype=np.int32)
            slot_buf[:m] = slots
            mask = np.zeros(bucket, dtype=bool)
            mask[:m] = True
            self.vectors, self.valid, self.sq_norms = _scatter_rows(
                self.vectors, self.valid, self.sq_norms,
                self._placed_replicated(slot_buf),
                self._placed_replicated(padded),
                self._placed_replicated(mask),
                normalize_rows=self.normalize_on_add,
            )

    def delete(self, slots) -> None:
        """Tombstone slots (reference: delete = tombstone + later cleanup,
        hnsw/delete.go). Slots stay allocated until compaction.

        Rows still HOST-STAGED (added but not yet flushed) are
        tombstoned in the staging buffer itself — scrubbed so they never
        reach HBM — instead of paying a full device flush just to clear
        a mask bit the scatter was about to set. The device-side clear
        still runs for every requested slot (clearing a never-set slot
        is a no-op), so interleaved add/delete/flush sequences agree
        with the host mirror no matter which side of the flush the
        delete lands on."""
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int32))
        m = len(slots)
        if m == 0:
            return
        with self._lock:
            in_range = np.unique(slots[(slots >= 0)
                                       & (slots < self.capacity)])
            self._live_count -= int(np.count_nonzero(
                self._valid_np[in_range]))
            self._valid_np[in_range] = False
            if self._staged_rows:
                self._scrub_staged_locked(in_range)
            bucket = _next_pow2(max(m, 8))
            buf = np.full(bucket, self.capacity + 1, dtype=np.int32)  # OOB no-op
            buf[:m] = slots
            self.valid = _clear_slots(self.valid, self._placed_replicated(buf))

    def _scrub_staged_locked(self, dead: np.ndarray) -> None:
        """Drop staged rows whose slots are in ``dead`` so a deleted-
        before-flush row never lands on device at all. Caller holds
        ``_lock``."""
        kept_slots: list[np.ndarray] = []
        kept_vecs: list[np.ndarray] = []
        rows = 0
        for sl, vec in zip(self._staged_slots, self._staged_vecs):
            keep = ~np.isin(sl, dead)
            if keep.all():
                kept_slots.append(sl)
                kept_vecs.append(vec)
                rows += len(sl)
            elif keep.any():
                kept_slots.append(sl[keep])
                kept_vecs.append(vec[keep])
                rows += int(keep.sum())
        self._staged_slots = kept_slots
        self._staged_vecs = kept_vecs
        self._staged_rows = rows

    def _placed_replicated(self, arr):
        if self.mesh is None:
            return placement.put(arr, self.device)
        return replicate_array(jnp.asarray(arr), self.mesh)

    # -- queries -------------------------------------------------------------

    @property
    def count(self) -> int:
        """Allocated slots (including tombstones)."""
        return self._count

    def live_count(self) -> int:
        """Live (non-tombstoned) slots — an O(1) host counter maintained
        under ``_lock`` by add/set_at/delete/compact. The device
        ``sum(valid)`` round-trip this used to pay (the second graftlint
        G1 baseline entry) is retired from the serving path; set
        ``WEAVIATE_TPU_DEBUG_COUNTS=1`` to cross-check the counter
        against the device mask on every call."""
        with self._lock:
            if os.environ.get("WEAVIATE_TPU_DEBUG_COUNTS", "").lower() \
                    in ("1", "true", "on"):
                self._flush_staged_locked()
                dev = int(jnp.sum(self.valid))  # graftlint: disable=G1 — debug-only cross-check, env-gated off the serving path
                assert dev == self._live_count, (
                    f"live-count drift: device says {dev}, host counter "
                    f"says {self._live_count}")
            return self._live_count

    def get(self, slots) -> np.ndarray:
        """Fetch vectors by slot (host copy) — object-resolution path."""
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int32))
        with self._lock:
            self._flush_staged_locked()
            rows = self.vectors[self._operand(slots)]
        return np.asarray(rows, dtype=np.float32)

    def search(self, queries: np.ndarray, k: int, allow_mask: np.ndarray | None = None):
        """Brute-force top-k. queries [B,d] (or [d]); returns (dists [B,k],
        slots [B,k]) as numpy, ascending by distance; dead slots never appear.

        ``allow_mask`` is the device-side AllowList (reference:
        helpers/allow_list.go consumed at hnsw/search.go /
        flat/index.go:319) in one of two forms:

        - [capacity] (or [count]) bool — ONE filter shared by the whole
          batch; highly selective masks cut over to the gathered path.
        - [B, capacity] bool — PER-QUERY filters. Rows pack into a
          bitmask (uint32 [B, capacity/32], pallas_kernels.
          pack_allow_bitmask) that the scan kernels unpack tile-locally,
          so B differently-filtered requests still run as one device
          program. A [1, capacity] mask broadcasts to the shared form.

        The D2H transfer happens inside the returned handle's
        ``result()`` (tracing.d2h — the sanctioned boundary), not here:
        this method is ``search_async(...).result()``.
        """
        return self.search_async(queries, k, allow_mask).result()

    def search_async(self, queries: np.ndarray, k: int,
                     allow_mask: np.ndarray | None = None
                     ) -> DeviceResultHandle:
        """Dispatch-only twin of ``search`` (ISSUE 7 tentpole): the scan
        launches under ``_lock`` and the results STAY DEVICE-RESIDENT in
        the returned ``DeviceResultHandle``. ``.result()`` performs the
        one sanctioned device->host transfer (``transfer.d2h`` span) and
        runs the gathered-path host remapping; the serving pipeline
        instead drains the handle on a dedicated transfer thread while
        the next batch dispatches (runtime/query_batcher.py), so the
        device never idles on a host sync. ``queries``: numpy, or a
        float32 block that already lies on this store's device (a
        collection's drain uploads one a chip)."""
        if not isinstance(queries, jax.Array):
            queries = np.asarray(queries, dtype=np.float32)
        squeeze = queries.ndim == 1
        if squeeze:
            queries = queries[None, :]
        allow_mask = normalize_allow_mask(allow_mask, len(queries))
        with tracing.span("store.scan", rows=self.capacity,
                          queries=len(queries), k=k,
                          sharded=self.mesh is not None,
                          filtered=allow_mask is not None) as sp:
            # Dispatch happens under the lock: writers *donate* the store
            # buffers, which invalidates any handle a concurrent reader
            # grabbed but hasn't dispatched against yet. Execution is
            # async, so the lock only covers the (cheap) dispatch —
            # materialization waits outside.
            with self._lock:
                self._flush_staged_locked()
                vectors, valid, norms = (self.vectors, self.valid,
                                         self.sq_norms)
                capacity = self.capacity
                allow_bits = allow_rows_dev = None
                gathered = False
                if isinstance(allow_mask, AllowBits) or (
                        isinstance(allow_mask, np.ndarray)
                        and allow_mask.ndim == 2):
                    sp.set(path="bitmask_batched")
                    # EXPLAIN notes are host ints only (no device reads
                    # — graftlint G1/G5 pin it) and a one-contextvar-
                    # read no-op when nobody asked
                    kernelscope.explain_note(
                        "store", path="bitmask_batched", rows=capacity,
                        queries=len(queries), k=k)
                    allow_bits, allow_rows_dev = batched_mask_operands(
                        allow_mask, len(queries), capacity, self.mesh,
                        owner=self._hbm_owner, device=self.device)
                elif allow_mask is not None:
                    # ONE filter for the batch. An index that keeps its
                    # filters' operands hands the slot list over as it
                    # lies on the device (it asked ``gathered_slots``
                    # when it built the list); anything else is listed
                    # here, a request at a time
                    slots, m_allowed = (
                        allow_mask if isinstance(allow_mask, AllowSlots)
                        else self.gathered_slots(allow_mask))
                    gathered = slots is not None
                    kernelscope.explain_note(
                        "store",
                        path="gathered" if gathered else "shared_mask",
                        rows=capacity, m_allowed=m_allowed,
                        queries=len(queries), k=k,
                        selectivity=round(m_allowed / capacity, 6)
                        if capacity else 0.0)
                    if gathered:
                        sp.set(path="gathered", allowed=m_allowed)
                        d, i = self._dispatch_gathered(queries, k, slots)
                    else:
                        full = np.zeros(capacity, dtype=bool)
                        full[: len(allow_mask)] = allow_mask
                        valid = apply_allow_mask(valid, self._placed(full))
                else:
                    kernelscope.explain_note(
                        "store", path="full_scan", rows=capacity,
                        queries=len(queries), k=k)
                if not gathered:
                    k_eff = min(k, capacity)
                    # cosine runs as "cosine" against rows normalized at
                    # insert (the query side is normalized inside the
                    # kernel)
                    metric = ("cosine" if self.metric in ("cosine",
                                                          "cosine-dot")
                              else self.metric)
                    cs = min(self.chunk_size, capacity // self.n_shards)
                    if self.mesh is None:
                        d, i = chunked_topk_distances(
                            self._operand(queries), vectors, k=k_eff,
                            chunk_size=cs, metric=metric, valid=valid,
                            x_sq_norms=norms, use_pallas=self.use_pallas,
                            selection=SCAN_SELECTION,
                            allow_bits=allow_bits,
                        )
                    else:
                        d, i = sharded_topk(
                            self._operand(queries), vectors, valid, norms,
                            k=k_eff, chunk_size=cs, metric=metric,
                            mesh=self.mesh, use_pallas=self.use_pallas,
                            selection=SCAN_SELECTION,
                            allow_rows=allow_rows_dev,
                        )
        # materialization (and its device-time attribution) lives in the
        # handle: a sync here would serialize concurrent readers behind
        # this dispatch AND idle the device between batches

        def _finish(d_np, i_np, _gathered=gathered, _k=k,
                    _squeeze=squeeze):
            if _gathered:
                d_np, i_np = DeviceVectorStore._finish_gathered(
                    d_np, i_np, _k)
            if _squeeze:
                return d_np[0], i_np[0]
            return d_np, i_np

        return DeviceResultHandle(
            (d, i), finish=_finish,
            attrs={"rows": capacity, "queries": len(queries), "k": k,
                   # which dispatch shape ran: the hybridplane composes
                   # on the device arrays and must refuse the gathered
                   # path (its finish step pads to k on the HOST)
                   "path": "gathered" if gathered else "device"})

    def epoch_scan(self, queries: np.ndarray, k: int,
                   allow_mask: np.ndarray | None = None):
        """Dispatch-only scan for the epoch store (engine/epochs.py):
        top-k of THIS store alone, ids STORE-LOCAL, results left
        device-resident for the cross-epoch merge. ``allow_mask``
        carries this epoch's column slice of the global filter ([cap]
        shared or [B, cap] per-query). The gathered low-selectivity
        cutover is deliberately not taken here: its bucket-local remap
        is a host finish step, and the epoch merge needs raw device
        candidates (single-epoch stores keep the cutover through the
        ``search`` passthrough)."""
        queries = np.asarray(queries, dtype=np.float32)
        allow_mask = normalize_allow_mask(allow_mask, len(queries))
        with self._lock:
            self._flush_staged_locked()
            vectors, valid, norms = self.vectors, self.valid, self.sq_norms
            capacity = self.capacity
            allow_bits = allow_rows_dev = None
            if allow_mask is not None and allow_mask.ndim == 2:
                allow_bits, allow_rows_dev = batched_mask_operands(
                    allow_mask, len(queries), capacity, self.mesh,
                    owner=self._hbm_owner, device=self.device)
            elif allow_mask is not None:
                full = np.zeros(capacity, dtype=bool)
                w = min(len(allow_mask), capacity)
                full[:w] = allow_mask[:w]
                valid = apply_allow_mask(valid, self._placed(full))
            k_eff = min(k, capacity)
            metric = ("cosine" if self.metric in ("cosine", "cosine-dot")
                      else self.metric)
            cs = min(self.chunk_size, capacity // self.n_shards)
            if self.mesh is None:
                return chunked_topk_distances(
                    self._operand(queries), vectors, k=k_eff, chunk_size=cs,
                    metric=metric, valid=valid, x_sq_norms=norms,
                    use_pallas=self.use_pallas, selection=SCAN_SELECTION,
                    allow_bits=allow_bits)
            return sharded_topk(
                self._operand(queries), vectors, valid, norms, k=k_eff,
                chunk_size=cs, metric=metric, mesh=self.mesh,
                use_pallas=self.use_pallas, selection=SCAN_SELECTION,
                allow_rows=allow_rows_dev)

    def gathered_slots(self, slot_mask: np.ndarray) -> AllowSlots:
        """The store's cutover for ONE filter shared by a batch: where
        the mask is selective enough, its allowed slots in a dense pow2
        bucket on the device, to be gathered and scanned alone; else
        ``slots`` None and the masked full scan serves it. Numbers from a
        pre-chip rig, not measured on the v5e (ROADMAP S13): the masked
        full scan is selectivity-independent (~11.1 ms at 1M x 128,
        B = 256); the gather is ~1.4 ms + linear (5.2 ms at 10 %, 23 ms
        at 50 %): crossover ~22 %, policy cut at capacity / 8 with a
        1-GB budget for the transient gather, counted on the PADDED
        bucket at the storage dtype. Called with ``_lock`` held, or by
        an index that holds its own over every write."""
        m_allowed = int(np.count_nonzero(slot_mask))
        bucket = 1 << max(7, (m_allowed - 1).bit_length())
        row_bytes = self.dim * jnp.dtype(self.vectors.dtype).itemsize
        if (self.mesh is not None
                or not 0 < m_allowed <= self.capacity // 8
                or bucket * row_bytes > (1 << 30)):
            return AllowSlots(None, m_allowed)
        slot_buf = np.full(bucket, -1, dtype=np.int32)
        slot_buf[:m_allowed] = np.flatnonzero(slot_mask)
        return AllowSlots(placement.put(slot_buf, self.device), m_allowed)

    def _dispatch_gathered(self, queries: np.ndarray, k: int, slots):
        """Filtered search at low selectivity: gather the allowed rows
        into a dense pow2-padded buffer on device and scan THAT
        (reference analog: flatSearchCutoff routes small filters to
        brute force over the allow list, hnsw/index.go:95). ``slots``
        [bucket] int32 on the device: the allowed slots, then -1. Called
        under ``_lock`` by ``search_async``; dispatch only, ONE program
        — results materialize outside the lock. Buckets bound compiled
        variants. Returns (d_dev, i_dev)."""
        metric = ("cosine" if self.metric in ("cosine", "cosine-dot")
                  else self.metric)
        return shared_candidates_topk(
            self._operand(queries), slots, self.vectors,
            min(k, slots.shape[0]), metric, row_norms=self.sq_norms,
            valid=self.valid, use_pallas=self.use_pallas,
            selection=SCAN_SELECTION,
        )

    @staticmethod
    def _finish_gathered(d_np: np.ndarray, i_np: np.ndarray, k: int):
        """Host half of the gathered path. The candidate plane remaps
        bucket-local winners to global slots ON DEVICE (row_ids), so
        this is pad-only up to search()'s [B, k] contract."""
        if i_np.shape[1] < k:
            pad = k - i_np.shape[1]
            i_np = np.pad(i_np, ((0, 0), (0, pad)), constant_values=-1)
            d_np = np.pad(d_np, ((0, 0), (0, pad)),
                          constant_values=np.float32(np.inf))
        return d_np, i_np

    def search_by_distance(self, query: np.ndarray, max_distance: float,
                           allow_mask: np.ndarray | None = None,
                           batch: int = 4096):
        """All slots within ``max_distance`` (reference:
        SearchByVectorDistance, vector_index.go:31). Iteratively widens k
        until the worst returned hit exceeds the threshold."""
        k = min(64, self.capacity)
        while True:
            d, i = self.search(query, k, allow_mask)
            within = d <= max_distance
            # done if some slot beyond threshold surfaced or we've pulled everything
            if (~within).any() or k >= self.capacity or within.sum() >= self.live_count():
                return d[within], i[within]
            k = min(k * 4, self.capacity)

    # -- maintenance ---------------------------------------------------------

    def compact(self) -> np.ndarray:
        """Defragment: drop tombstoned rows, repack live rows contiguously.
        Returns old_slot -> new_slot mapping (-1 for dropped). The HBM analog
        of the reference's tombstone-cleanup cycle (hnsw tombstone cleanup /
        lsmkv compaction)."""
        with tracing.span("store.compact", rows=self.capacity) as sp, \
                self._lock:
            self._flush_staged_locked()
            valid_np = self._valid_np  # host mirror — no device sync
            live = np.nonzero(valid_np)[0]
            mapping = np.full(self.capacity, -1, dtype=np.int64)
            mapping[live] = np.arange(len(live))
            sp.set(live=len(live))
            # the rebuild's one D2H rides the sanctioned boundary
            # (transfer.d2h span, device_ms split from memcpy on sampled
            # traces) instead of a bare np.asarray sync in engine/
            (vec_host,) = transfer.d2h(self.vectors)
            vec_np = vec_host[live]
            self._count = len(live)
            new_cap = self._align(max(len(live), 2))
            self.capacity = new_cap
            self._valid_np = np.zeros(new_cap, dtype=bool)
            self._live_count = 0  # set_at below re-marks the live rows
            self._alloc(new_cap)
            if len(live):
                self.set_at(np.arange(len(live)), vec_np)
            return mapping

    # -- persistence hooks ---------------------------------------------------

    def snapshot(self) -> dict:
        """Host-side snapshot for checkpointing (driver: storage layer WAL +
        snapshot gives restart durability, reference hnsw/startup.go:57)."""
        with self._lock:
            self._flush_staged_locked()
            return {
                "vectors": np.asarray(self.vectors, dtype=np.float32),
                "valid": np.asarray(self.valid),
                "count": self._count,
                "dim": self.dim,
                "metric": self.metric,
                "dtype": jnp.dtype(self.dtype).name,
                "chunk_size": self.chunk_size,
            }

    def twin_shapes(self):
        """What decides this store's scan program besides the batch and
        k, for runtime/placement.py ``Twins``: stores that read equal
        here on different chips run the same programs, each its own
        copy. None on a mesh."""
        if self.mesh is not None:
            return None
        return ("flat", self.capacity, self.dim,
                jnp.dtype(self.dtype).name, self.metric,
                self.chunk_size, self.use_pallas)

    @classmethod
    def restore(cls, snap: dict, **kwargs) -> "DeviceVectorStore":
        # storage config survives the checkpoint round-trip unless the
        # caller explicitly overrides it
        kwargs.setdefault("dtype", jnp.dtype(snap.get("dtype", "float32")))
        kwargs.setdefault("chunk_size", snap.get("chunk_size", _DEFAULT_CHUNK))
        store = cls(dim=snap["dim"], metric=snap["metric"],
                    capacity=max(len(snap["valid"]), 2), **kwargs)
        live = np.nonzero(snap["valid"])[0]
        store._count = snap["count"]
        if len(live):
            # vectors were already normalized at original insert; don't re-normalize
            orig = store.normalize_on_add
            store.normalize_on_add = False
            store.set_at(live, snap["vectors"][live])
            store.normalize_on_add = orig
        store._count = snap["count"]
        return store
