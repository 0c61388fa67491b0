"""Flat (brute-force) vector index — the TPU-native first-class citizen.

Reference: adapters/repos/db/vector/flat/index.go (lsmkv cursor full scan,
index.go:319). Here the full scan is the MXU's favourite workload: one
batched distance matmul over the HBM-resident corpus per chunk, fused with
a running top-k. On a v5e-8 row-sharded mesh the same call runs SPMD with an
ICI all_gather merge.

Doc-id mapping: callers address vectors by external int64 doc ids (the shard
layer maps UUIDs → doc ids, as the reference does in adapters/repos/db/docid).
Internally ids map to store slots; tombstoned slots are reclaimed by
``compact()``.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time

import jax
import numpy as np

from weaviate_tpu import native
from weaviate_tpu.engine.filter_operands import (FilterOperandCache,
                                                 stable_mask)
from weaviate_tpu.engine.store import (AllowBits, AllowSlots,
                                       DeviceVectorStore, stack_allow_rows)
from weaviate_tpu.runtime import hbm_ledger, kernelscope, placement, tracing
from weaviate_tpu.runtime.metrics import (allow_translate_total,
                                          filter_operand_total)
from weaviate_tpu.runtime.transfer import DeviceResultHandle


def _per_query_allow(allow_list) -> bool:
    """True when ``allow_list`` is a sequence of PER-QUERY allow lists
    (entries None or array-like) rather than one shared filter. A plain
    Python list of scalar doc ids — including the empty list (a filter
    matching nothing) — keeps its historical shared-filter meaning."""
    if not isinstance(allow_list, (list, tuple)) or len(allow_list) == 0:
        return False
    return any(a is None or np.ndim(a) > 0 for a in allow_list)


def _allow_form(allow_list: np.ndarray) -> str:
    """The form ``FlatIndex._allow_mask`` translates ``allow_list`` in:
    the ``form`` of ``weaviate_tpu_allow_translate_total`` and of the
    ``flat.search_batch`` span."""
    return "mask" if allow_list.dtype == np.bool_ else "ids"


@contextlib.contextmanager
def _compress_stage(quantization: str, stage: str):
    """One stage of ``FlatIndex.compress``: a child span of
    ``index.compress`` where the import is traced, and one observation
    of ``weaviate_tpu_index_compress_seconds`` either way (none for a
    stage that raised)."""
    from weaviate_tpu.runtime.metrics import index_compress_seconds

    t0 = time.perf_counter()
    with tracing.span(stage) as sp:
        yield sp
    index_compress_seconds.labels(quantization, stage).observe(
        time.perf_counter() - t0)


class FlatIndex:
    """Implements the reference ``VectorIndex`` contract
    (adapters/repos/db/vector_index.go:24-45) for brute-force search."""

    index_type = "flat"
    # the batched entry point accepts PER-QUERY allow lists (a sequence in
    # the allow_list slot) and runs them as one bitmask-batched device
    # program — the QueryBatcher keys on this to coalesce filtered
    # requests instead of dispatching them solo
    supports_batched_filters = True
    # device scans compile one executable per (B, k) shape — the batcher
    # pads drains to pow2 buckets to bound the variant count
    compiled_batch_shapes = True
    # compact() renumbers slots and counts here; compress() looks, to see
    # whether the rows it encoded outside the lock still lie where they did
    _compactions = 0
    # runtime/memwatch.py MemoryMonitor (None: a store makes its own):
    # handed to the quantized store this index builds, here or in
    # compress(), which asks it where its rescore rows may live
    memwatch = None
    # the slot table's generation: moves under ``_lock`` wherever
    # ``_slot_to_id`` or the store (its capacity, its identity) changes,
    # and with it goes every filter operand the index kept on the device
    # (``_operands``: engine/filter_operands.py, made at its first use)
    _slot_gen = 0
    _operands = None

    def __init__(self, dim: int, metric: str = "l2-squared", mesh=None,
                 dtype=None, capacity: int = 8192, chunk_size: int = 8192,
                 quantization: str | None = None, store=None,
                 epoch_rows: int = 0, memwatch=None, **quant_kwargs):
        import jax.numpy as jnp

        self.dim = dim
        self.metric = metric
        self.memwatch = memwatch
        if store is not None:
            # injected store (IVFIndex subclass passes an IVFStore; the
            # id<->slot bookkeeping below is store-agnostic)
            self.store = store
        elif epoch_rows:
            # epoch-stacked device corpus (engine/epochs.py): writes land
            # in a small active epoch, sealed epochs are immutable,
            # tombstone-heavy ones compact in the background and the
            # coldest can migrate to a sibling shard under HBM pressure
            from weaviate_tpu.engine.epochs import EpochStore

            self.store = EpochStore(
                dim=dim, metric=metric, epoch_rows=epoch_rows,
                capacity=capacity, dtype=dtype, mesh=mesh,
                chunk_size=chunk_size, quantization=quantization,
                quant_kwargs=quant_kwargs or None)
        elif quantization:
            from weaviate_tpu.engine.quantized import QuantizedVectorStore

            self.store = QuantizedVectorStore(
                dim=dim, metric=metric, quantization=quantization,
                capacity=capacity, chunk_size=chunk_size, mesh=mesh,
                memwatch=memwatch, **quant_kwargs,
            )
        else:
            if quant_kwargs:
                raise TypeError(
                    f"unexpected kwargs without quantization: {sorted(quant_kwargs)}"
                )
            self.store = DeviceVectorStore(
                dim=dim,
                metric=metric,
                capacity=capacity,
                dtype=dtype or jnp.float32,
                mesh=mesh,
                chunk_size=chunk_size,
            )
        self._lock = threading.RLock()
        self._id_to_slot: dict[int, int] = {}
        self._slot_to_id: np.ndarray = np.full(self.store.capacity, -1, dtype=np.int64)
        if self.device is not None:
            placement.twins.register(self)

    # -- placement (runtime/placement.py) --------------------------------------

    @property
    def device(self):
        """The chip this index's store keeps its arrays on: the owning
        shard's, None on a mesh or outside any shard. Read from the store
        of the moment, so it outlives ``compress``'s swap."""
        return getattr(self.store, "device", None)

    def twin_shapes(self):
        """The store's shapes as ``placement.Twins`` compares them, None
        for a store that does not say (a mesh, epochs, an injected
        one)."""
        fn = getattr(self.store, "twin_shapes", None)
        return None if fn is None else fn()

    def warm_twin(self, size) -> None:
        """Run, and so build or load on this index's chip, the program an
        unfiltered dispatch of ``size`` = (queries, k) takes: an index of
        the same shapes on another chip has just met it."""
        b, k = size
        handle = self.search_by_vector_batch_async(
            np.zeros((b, self.dim), dtype=np.float32), k)
        if handle is not None:
            handle.result()

    # -- VectorIndex contract -------------------------------------------------

    def add(self, doc_id: int, vector: np.ndarray) -> None:
        self.add_batch([doc_id], np.asarray(vector)[None, :])

    def add_batch(self, doc_ids, vectors: np.ndarray) -> None:
        """Insert or update a batch (reference AddBatch, vector_index.go:26).

        Re-adding an existing id overwrites its vector in place."""
        doc_ids = np.asarray(doc_ids, dtype=np.int64)
        vectors = np.asarray(vectors, dtype=np.float32)
        if len(doc_ids) != len(vectors):
            raise ValueError(f"{len(doc_ids)} ids != {len(vectors)} vectors")
        # dedupe within the batch, last occurrence wins — otherwise one id
        # would allocate two live slots and corrupt the id<->slot mapping
        if len(doc_ids) != len(set(doc_ids.tolist())):
            last = {int(i): idx for idx, i in enumerate(doc_ids.tolist())}
            keep = sorted(last.values())
            doc_ids, vectors = doc_ids[keep], vectors[keep]
        with self._lock:
            existing = np.array([i in self._id_to_slot for i in doc_ids.tolist()])
            if existing.any():
                upd_slots = np.array(
                    [self._id_to_slot[int(i)] for i in doc_ids[existing]],
                    dtype=np.int64,
                )
                self.store.set_at(upd_slots, vectors[existing])
            fresh = ~existing
            if fresh.any():
                slots = self.store.add(vectors[fresh])
                self._ensure_slot_map()
                for i, s in zip(doc_ids[fresh].tolist(), slots.tolist()):
                    self._id_to_slot[int(i)] = int(s)
                    self._slot_to_id[int(s)] = int(i)
                self._slots_moved()

    def _ensure_slot_map(self):
        """Grow the slot->id reverse map with store capacity. Caller
        holds ``_lock``."""
        if len(self._slot_to_id) < self.store.capacity:
            grown = np.full(self.store.capacity, -1, dtype=np.int64)
            grown[: len(self._slot_to_id)] = self._slot_to_id
            self._slot_to_id = grown
            self._slots_moved()

    def _slots_moved(self) -> None:
        """Caller holds ``_lock`` and has changed ``_slot_to_id`` or the
        store: no filter operand built before holds any longer."""
        self._slot_gen += 1
        if self._operands is not None:
            self._operands.clear()

    def delete(self, *doc_ids) -> None:
        """Tombstone docs (reference Delete, vector_index.go:28)."""
        with self._lock:
            slots = [self._id_to_slot.pop(int(i)) for i in doc_ids
                     if int(i) in self._id_to_slot]
            if slots:
                self._slot_to_id[slots] = -1
                self.store.delete(np.asarray(slots))
                self._slots_moved()

    def contains(self, doc_id: int) -> bool:
        return int(doc_id) in self._id_to_slot

    def __len__(self) -> int:
        return len(self._id_to_slot)

    def search_by_vector(self, query: np.ndarray, k: int,
                         allow_list: np.ndarray | None = None):
        """Top-k by vector (reference SearchByVector, vector_index.go:29).

        ``allow_list``: bool mask over doc-id space or array of allowed doc
        ids (the reference's roaring-bitmap AllowList). Returns
        (doc_ids [<=k] int64, dists [<=k] f32), ascending.
        """
        # The index lock spans search + id resolution so a concurrent
        # compact() can't remap slots between the scan and _resolve.
        with tracing.span("flat.search", k=k,
                          filtered=allow_list is not None,
                          device=placement.label(self.device)):
            with self._lock:
                allow_mask = self._shared_operand(
                    None if allow_list is None else np.asarray(allow_list))
                d, slots = self.store.search(np.asarray(query), k,
                                             allow_mask)
                return self._resolve(d, slots, k)

    def search_by_vector_batch(self, queries: np.ndarray, k: int,
                               allow_list=None):
        """Batched query path — amortizes one matmul across B queries.

        ``allow_list`` is either ONE allow list shared by the whole batch
        (bool mask over doc-id space or array of allowed doc ids — a
        plain list of scalar ids still means this), or a list/tuple of B
        per-query allow lists (entries None or array-like; None =
        unfiltered). Per-query lists translate to slot masks and run as a
        single bitmask-batched device program (engine/store.py). Returns
        (doc_ids [B,k] int64 with -1 padding, dists [B,k])."""
        queries = np.atleast_2d(np.asarray(queries))
        per_query = _per_query_allow(allow_list)
        with tracing.span("flat.search_batch", k=k, queries=len(queries),
                          filtered=allow_list is not None,
                          per_query_filters=per_query,
                          device=placement.label(self.device)) as sp:
            with self._lock:
                kind, allow_mask = self._translate_batch_allow(
                    queries, allow_list, per_query, sp)
                kernelscope.explain_note(
                    "index", kind=str(self.index_type),
                    per_query_filters=bool(per_query),
                    filtered=allow_list is not None,
                    queries=len(queries), k=k)
                if kind == "rowwise":
                    # a store with supports_batched_filters=False takes
                    # shared 1-D masks only — serve per-query filters
                    # row by row rather than crashing on a 2-D mask
                    # (IVF now takes the batched bitmask path above)
                    d = np.full((len(queries), k), np.float32(np.inf),
                                dtype=np.float32)
                    slots = np.full((len(queries), k), -1,
                                    dtype=np.int64)
                    for r, m in enumerate(allow_mask):
                        dr, sr = self.store.search(
                            queries[r:r + 1], k, m)
                        kk = min(k, dr.shape[1])
                        d[r, :kk] = dr[0, :kk]
                        slots[r, :kk] = sr[0, :kk]
                    ids = np.where(slots >= 0,
                                   self._slot_to_id_safe(slots), -1)
                    return ids, d
                d, slots = self.store.search(queries, k, allow_mask)
                ids = np.where(slots >= 0, self._slot_to_id_safe(slots),
                               -1)
                return ids, d

    def _translate_batch_allow(self, queries, allow_list, per_query: bool,
                               sp):
        """Allow-list intake shared by the sync and async batch paths.
        Caller holds ``_lock`` and passes its span, which takes the
        ``form`` the lists were translated in ("mask", "ids", or
        "ids+mask" for a batch that held both). Returns ("mask", what
        the store's ``search_async`` takes: None, a slot mask, or an
        operand that already lies on the device) for the
        single-dispatch forms, or ("rowwise", per-row masks) when the
        store cannot take a 2-D mask."""
        if per_query and len(allow_list) != len(queries):
            raise ValueError(
                f"{len(allow_list)} allow lists != "
                f"{len(queries)} queries")
        lists = [None if a is None else np.asarray(a)
                 for a in (allow_list if per_query else [allow_list])]
        forms = sorted({_allow_form(a) for a in lists if a is not None})
        if forms:
            sp.set(form="+".join(forms))
        if not per_query:
            return "mask", self._shared_operand(lists[0])
        if all(a is None for a in lists):
            return "mask", None
        if not self.supports_batched_filters:
            return "rowwise", [self._allow_mask(a) for a in lists]
        if self._keeps_operands():
            return "mask", self._bitmask_operand(lists)
        # a mesh ships bool rows column-sharded, an epoch store slices
        # them by epoch, an injected store takes what it took: the block.
        # Unfiltered rows get an all-ones mask (the store still ANDs
        # with its live-slot validity)
        filter_operand_total.labels("bitmask", "uncached").inc(
            sum(a is not None for a in lists))
        allow_mask = np.ones((len(lists), self.store.capacity),
                             dtype=bool)
        for r, a in enumerate(lists):
            if a is not None:
                m = self._allow_mask(a)
                allow_mask[r, :] = False
                allow_mask[r, :len(m)] = m
        return "mask", allow_mask

    # -- a filter's device operands (engine/filter_operands.py) ---------------

    def _keeps_operands(self) -> bool:
        """Whether this index's store takes filters that already lie on
        the device: one device, and a store that says so."""
        store = self.store
        return (getattr(store, "takes_allow_operands", False)
                and store.mesh is None)

    def _operand_cache(self) -> FilterOperandCache:
        """Caller holds ``_lock``."""
        if self._operands is None:
            self._operands = FilterOperandCache(
                getattr(self.store, "_hbm_owner", None))
        return self._operands

    def allowed_count(self, allow) -> int:
        """Doc ids an allow list lets through (a bool mask over doc ids,
        or an array of them): what the batcher's solo cut goes by. A
        mask this index keeps operands of has its count there."""
        allow = np.asarray(allow)
        if allow.dtype != np.bool_:
            return allow.size
        cache = self._operands
        if cache is not None and stable_mask(allow):
            return cache.doc_count(allow)
        return int(np.count_nonzero(allow))

    def _bitmask_operand(self, lists) -> AllowBits:
        """Per-query allow lists (None = unfiltered) -> the dispatch's
        packed ``allow_bits`` on the device, bit-equal to
        ``pack_allow_bitmask`` of the translated [B, capacity] block.
        Caller holds ``_lock``.

        A row refers to its mask's packed row on the device
        (``_packed_rows``); the rows are stacked by one program on the
        device, so a dispatch whose masks are all known translates,
        packs and uploads nothing. The ``store.mask_pack`` span (the
        dispatch's ``mask_pack`` stage) is this whole step."""
        with tracing.span("store.mask_pack", stage="mask_pack",
                          queries=len(lists)) as sp:
            rows, hits, misses, distinct = self._packed_rows(lists)
            bits = stack_allow_rows(*rows)
            hbm_ledger.ledger.track("allow_bitmask", bits,
                                    **self._operand_cache().owner)
            sp.set(hits=hits, misses=misses, distinct=distinct)
        return AllowBits(bits)

    def _packed_rows(self, lists, translated: dict | None = None):
        """Each entry of ``lists`` as its mask's packed bitmap row ON
        THE DEVICE -> (rows, hits, misses, distinct masks). Caller holds
        ``_lock``.

        A row is kept from an earlier dispatch (``hit``), or built now,
        once a mask OBJECT however many rows carry it (``shared``) and
        kept where the mask cannot change (``miss``; else ``uncached``);
        unfiltered and padded rows share one all-ones row.
        ``translated``: slot masks the caller has made of some of the
        lists already, by ``id`` of the list."""
        from weaviate_tpu.ops.pallas_kernels import (mask_pad_cols,
                                                     pack_allow_bitmask)

        store = self.store
        capacity = store.capacity
        n_cols = mask_pad_cols(capacity)
        stamp = (self._slot_gen, capacity)
        cache = self._operand_cache()
        rows: list = [None] * len(lists)
        first: dict[int, int] = {}  # id(mask) -> the first row with it
        build = []                  # (row, mask, keep) to translate
        hits = shared = 0
        ones = None                 # unfiltered and padded rows' one
        for r, a in enumerate(lists):
            if a is None:
                if ones is None:
                    ones = cache.ones(stamp, lambda: placement.put(
                        pack_allow_bitmask(
                            np.ones(capacity, dtype=bool), n_cols)[0],
                        store.device))
                rows[r] = ones
            elif first.setdefault(id(a), r) != r:
                shared += 1
            else:
                keep = stable_mask(a)
                e = cache.get(a, stamp) if keep else None
                if e is not None and e.bits is not None:
                    rows[r] = e.bits
                    hits += 1
                else:
                    build.append((r, a, keep))
        if build:
            block = np.zeros((len(build), capacity), dtype=bool)
            for j, (_r, a, _keep) in enumerate(build):
                m = (translated or {}).get(id(a))
                if m is None:
                    m = self._allow_mask(a)
                block[j, :len(m)] = m
            packed = pack_allow_bitmask(block, n_cols)
            for j, (r, a, keep) in enumerate(build):
                rows[r] = placement.put(packed[j], store.device)
                if keep:
                    cache.attach(a, stamp, bits=rows[r])
        for r, a in enumerate(lists):
            if rows[r] is None:
                rows[r] = rows[first[id(a)]]
        misses = sum(keep for _r, _a, keep in build)
        for result, n in (("hit", hits), ("miss", misses),
                          ("shared", shared),
                          ("uncached", len(build) - misses)):
            if n:
                filter_operand_total.labels("bitmask", result).inc(n)
        return rows, hits, misses, len(first)

    def _shared_operand(self, allow):
        """ONE allow list for the whole batch (None = unfiltered) ->
        what the store takes. A mask that cannot change and is selective
        enough for the store's gathered cutover becomes its slot list ON
        THE DEVICE, built once and kept (``AllowSlots``: a solo dispatch
        then uploads its query row and nothing else); anything else is
        translated to a slot mask as it always was, and the store lists
        it. Caller holds ``_lock``."""
        if allow is None:
            return None
        store = self.store
        keep = (self._keeps_operands() and hasattr(store, "gathered_slots")
                and stable_mask(allow))
        if keep:
            stamp = (self._slot_gen, store.capacity)
            cache = self._operand_cache()
            e = cache.get(allow, stamp)
            if e is not None and e.slots is not None:
                filter_operand_total.labels("gathered", "hit").inc()
                return AllowSlots(e.slots, e.slot_count)
        slot_mask = self._allow_mask(allow)
        if keep:
            op = store.gathered_slots(slot_mask)
            if op.slots is not None:
                cache.attach(allow, stamp, slots=op.slots,
                             slot_count=op.count)
                filter_operand_total.labels("gathered", "miss").inc()
                return op
        filter_operand_total.labels("gathered", "uncached").inc()
        return slot_mask

    def search_by_vector_batch_async(self, queries: np.ndarray, k: int,
                                     allow_list=None):
        """Async twin of ``search_by_vector_batch`` (ISSUE 7): dispatch
        under the index lock, results device-resident in the returned
        ``DeviceResultHandle`` (resolving to the same (doc_ids [B,k],
        dists [B,k]) contract). Returns ``None`` when this index cannot
        serve the request async — injected stores without
        ``search_async``, or per-query filters on stores without
        batched-filter support (the IVF store now provides both) — and
        the caller falls back to the sync path.

        The slot -> doc-id resolution in the finish step runs against
        the ``_slot_to_id`` table captured AT DISPATCH: ``compact()``
        replaces the array wholesale, so an in-flight handle keeps the
        mapping its scan was dispatched against; a concurrent
        ``delete()`` writes -1 in place, which drops the row at the
        shard layer exactly like the sync path's post-search delete
        race."""
        if not hasattr(self.store, "search_async"):
            return None
        if not isinstance(queries, jax.Array):
            # (a block that already lies on this index's device comes
            # from a collection's drain: ``takes_device_queries``)
            queries = np.atleast_2d(np.asarray(queries))
        per_query = _per_query_allow(allow_list)
        with tracing.span("flat.search_batch", k=k, queries=len(queries),
                          filtered=allow_list is not None,
                          per_query_filters=per_query,
                          dispatch="async",
                          device=placement.label(self.device)) as sp:
            with self._lock:
                kind, allow_mask = self._translate_batch_allow(
                    queries, allow_list, per_query, sp)
                if kind == "rowwise":
                    return None
                # EXPLAIN: index-level plan facts (host ints only; the
                # store layer notes the cutover it actually takes)
                kernelscope.explain_note(
                    "index", kind=str(self.index_type),
                    per_query_filters=bool(per_query),
                    filtered=allow_mask is not None,
                    queries=len(queries), k=k)
                handle = self.store.search_async(queries, k, allow_mask)
                table = self._slot_to_id  # replaced (not resized) by compact
            if allow_list is None and self.device is not None:
                # one set look-up where the size is known on this chip
                placement.twins.dispatched(self, (len(queries), k))

        def _resolve(res, _table=table):
            d, slots = res
            clipped = np.clip(slots, 0, len(_table) - 1)
            ids = np.where(slots >= 0, _table[clipped], -1)
            return ids, d

        return handle.map(_resolve)

    @property
    def takes_device_queries(self) -> bool:
        """True where ``search_by_vector_batch_async`` takes an
        UNFILTERED [B, d] float32 block that already lies on this
        index's device (``placement.put(block, self.device)``) as it
        takes a numpy one: a collection's drain uploads its block once
        a chip and hands it to every member shard there
        (db/drain.py). The plain single-device store alone; any other
        would fetch the block back to encode or split it."""
        return type(self.store) is DeviceVectorStore \
            and self.store.mesh is None

    # -- hybrid dataplane (ISSUE 18) ------------------------------------------

    @property
    def supports_device_hybrid(self) -> bool:
        """True when this index can run the fused sparse+dense hybrid
        program: the plain device store only — quantized/epoch/injected
        stores keep the host hybrid path (their async handles don't
        expose raw (dist, slot) arrays in store-slot space)."""
        return type(self.store) is DeviceVectorStore

    def slots_for_doc_ids(self, doc_ids) -> np.ndarray:
        """Store slots for external doc ids (-1 = not in this index) —
        the shard layer translates BM25 candidates with this before
        packing sparse operands."""
        with self._lock:
            return np.asarray(
                [self._id_to_slot.get(int(d), -1) for d in doc_ids],
                dtype=np.int32)

    def hybrid_batch_async(self, queries: np.ndarray, k: int,
                           allow_list=None, sparse_ops=None):
        """One fused device program for a mixed hybrid + pure-vector
        drain: the dense scan dispatches async, its DEVICE-RESIDENT
        (dist, slot) arrays feed straight into the BM25 scoring + fusion
        program (``ops/bm25.py::hybrid_topk``) — one dispatch chain, one
        D2H through the returned handle. ``sparse_ops`` is a per-row
        list of ``SparseOperand`` (None = pure-vector row riding the
        same batch). Returns None when the device hybrid path can't take
        the request (unsupported store, rowwise filters, or a dispatch
        shape whose finish step remaps on the host) — callers fall back
        to the host hybrid path."""
        from weaviate_tpu.ops.bm25 import hybrid_topk, stack_sparse_operands

        if not self.supports_device_hybrid:
            return None
        queries = np.atleast_2d(np.asarray(queries))
        sparse_ops = list(sparse_ops or [None] * len(queries))
        live_ops = [op for op in sparse_ops if op is not None]
        per_query = _per_query_allow(allow_list)
        if allow_list is not None and not per_query:
            # ONE list for the batch rides as a row a query (packed
            # once: the rows share the object): the gathered path's
            # finish step pads on the HOST, which would break the
            # on-device fusion composition
            allow_list, per_query = [allow_list] * len(queries), True
        # dense leg depth: every row's over-fetch must fit so fusion
        # ranks match the host reference; pow2 so the scan compiles per
        # bucket, not per drain
        fetch = max([k] + [int(op.fetch) for op in live_ops])
        f_depth = 1 << max(0, fetch - 1).bit_length()
        with tracing.span("flat.hybrid_batch", k=k, queries=len(queries),
                          hybrid=len(live_ops), dispatch="async") as sp:
            with self._lock:
                kind, allow_mask = self._translate_batch_allow(
                    queries, allow_list, per_query, sp)
                if kind == "rowwise":
                    return None
                kernelscope.explain_note(
                    "hybrid", queries=len(queries),
                    hybrid_rows=len(live_ops), k=k, fetch=fetch,
                    terms=int(sum(op.stats.get("terms", 0)
                                  for op in live_ops)),
                    candidates=int(sum(op.stats.get("candidates", 0)
                                       for op in live_ops)),
                    pruned_frac=round(float(np.mean(
                        [op.stats.get("pruned_frac", 0.0)
                         for op in live_ops])), 6) if live_ops else 0.0,
                    fusion_ranked=int(sum(1 for op in live_ops
                                          if op.fusion == 0)),
                    fusion_relative=int(sum(1 for op in live_ops
                                            if op.fusion == 1)))
                handle = self.store.search_async(queries, f_depth,
                                                 allow_mask)
                if (handle.attrs.get("path") != "device"
                        or len(handle.arrays) != 2):
                    return None
                dn_d, dn_i = handle.arrays
                pack = stack_sparse_operands(sparse_ops, len(queries))
                use_pallas = bool(getattr(self.store, "use_pallas",
                                          False))
                d, i = hybrid_topk(dn_d, dn_i, pack, k,
                                   use_pallas=use_pallas)
                table = self._slot_to_id  # replaced wholesale by compact

        def _resolve(d_np, i_np, _table=table):
            clipped = np.clip(i_np, 0, len(_table) - 1)
            ids = np.where(i_np >= 0, _table[clipped], -1)
            return ids, d_np

        return DeviceResultHandle(
            (d, i), finish=_resolve,
            attrs=dict(handle.attrs, hybrid=len(live_ops), k=k))

    def hybrid_batch(self, queries: np.ndarray, k: int, allow_list=None,
                     sparse_ops=None):
        """Sync twin of ``hybrid_batch_async`` (same fused program, the
        D2H just happens inline). Returns None on the same conditions."""
        h = self.hybrid_batch_async(queries, k, allow_list, sparse_ops)
        return None if h is None else h.result()

    def search_by_vector_distance(self, query: np.ndarray, max_distance: float,
                                  allow_list: np.ndarray | None = None):
        """Range search (reference SearchByVectorDistance,
        vector_index.go:31)."""
        with self._lock:
            allow_mask = self._allow_mask(allow_list)
            d, slots = self.store.search_by_distance(np.asarray(query), max_distance,
                                                     allow_mask)
            return self._resolve(d, slots, len(slots))

    # -- helpers --------------------------------------------------------------

    def _allow_mask(self, allow_list):
        """Allow list over doc ids -> bool mask over the store's slots
        (None = unfiltered), ``len(_slot_to_id[:capacity])`` long. A
        dead slot reads False, and so does a slot whose doc id the list
        does not cover. The input's dtype picks the form, and both give
        the same mask bit for bit:

        - a BOOL MASK over the doc-id space (what ``Shard.allow_mask``
          hands every served request) is looked up, one gather through
          the slot table: 0.32 ms at 262,144 slots whatever the
          selectivity, where listing its ids and searching them took
          2.4 to 21 ms a request on the batcher's worker (PERF.md, PR
          31: on the chip's host);
        - an ARRAY OF DOC IDS (REST paths, tests) is sorted and
          binary-searched per slot in the native library
          (csrc/weaviate_native.cpp): a list of a few ids is not worth a
          mask over the whole id space.
        """
        if allow_list is None:
            return None
        allow_list = np.asarray(allow_list)
        form = _allow_form(allow_list)
        allow_translate_total.labels(form).inc()
        with self._lock:
            table = self._slot_to_id[: self.store.capacity]
            if form == "ids":
                return native.membership(table, np.unique(allow_list))
            if not len(allow_list):
                return np.zeros(len(table), dtype=bool)
            # read as uint64 a dead slot's -1 is the largest id there
            # is, so one compare rejects it together with the ids of rows
            # added after the mask was built
            covered = table.view(np.uint64) < np.uint64(len(allow_list))
            return allow_list.take(table, mode="clip") & covered

    def _slot_to_id_safe(self, slots):
        clipped = np.clip(slots, 0, len(self._slot_to_id) - 1)
        return self._slot_to_id[clipped]

    def _resolve(self, d, slots, k):
        live = slots >= 0
        ids = self._slot_to_id_safe(slots)[live]
        return ids[:k], d[live][:k]

    # -- compression ----------------------------------------------------------

    def compress(self, quantization: str = "pq",
                 training_limit: int | None = None, **quant_kwargs) -> None:
        """Runtime compression: train a quantizer on current contents and swap
        the store (reference: hnsw/compress.go:38, enabled via a config
        update once enough data exists). Slot layout is preserved, so the
        id<->slot mapping carries over untouched. ``training_limit``: a
        PQ codebook, or an SQ range, is fitted on the first that many
        live rows in slot order (upstream's pq.trainingLimit and
        sq.trainingLimit), so one import gives one quantizer whatever
        the batch that crossed the limit held.

        Searches go on, exactly, from the old store while the codebook
        is fitted and the rows are encoded: ``_lock`` is held for the
        snapshot, and again for the catch-up (what was written or
        deleted meanwhile) and the swap. On the v5e the fit and the
        encoding of 100,000 x 96 take 4-16 s (PERF.md, PR 28)."""
        from weaviate_tpu.engine.epochs import EpochStore
        from weaviate_tpu.engine.quantized import QuantizedVectorStore
        from weaviate_tpu.runtime.metrics import index_compress_total

        staged = functools.partial(_compress_stage, quantization)

        def twin(old, snap):
            """-> a trained quantized store that holds ``snap``'s rows."""
            with staged("train") as sp:
                new = self._quantized_twin(old, quantization, quant_kwargs)
                live = np.nonzero(snap["valid"])[0]
                live_vecs = snap["vectors"][live]
                if not new.trained:
                    if len(live) < new.min_training_rows:
                        raise RuntimeError(
                            f"need >= {new.min_training_rows} live vectors "
                            f"to train {quantization.upper()}, "
                            f"have {len(live)}"
                        )
                    train_vecs = live_vecs[:training_limit]
                    new.train(train_vecs)
                    sp.set(rows_trained=len(train_vecs))
            with staged("encode") as sp:
                sp.set(rows_encoded=len(live))
                if len(live):
                    # vectors were already normalized at original insert
                    new.set_at_prenormalized(live, live_vecs)
            new._count = snap["count"]
            return new

        with tracing.span("index.compress", quantization=quantization,
                          rows=len(self)):
            with self._lock:
                old = self.store
                if isinstance(old, EpochStore):
                    if old.quantization:
                        raise RuntimeError("index is already compressed")
                    with staged("train"):
                        self._compress_epochs(old, quantization,
                                              training_limit=training_limit,
                                              **quant_kwargs)
                    index_compress_total.labels(quantization, "ok").inc()
                    return
                if isinstance(old, QuantizedVectorStore):
                    raise RuntimeError("index is already compressed")
                snap = old.snapshot()
                compactions = self._compactions
            new = twin(old, snap)
            with staged("swap") as sp, self._lock:
                if self.store is not old:
                    raise RuntimeError("index is already compressed")
                if self._compactions != compactions:
                    # slots were renumbered under the fit: once more, on
                    # the rows as they lie now, with the lock held
                    new = twin(old, old.snapshot())
                else:
                    sp.set(rows_caught_up=self._catch_up(
                        new, snap, old.snapshot()))
                self.store = new
                self._slots_moved()
            index_compress_total.labels(quantization, "ok").inc()

    def _quantized_twin(self, old, quantization: str, quant_kwargs: dict):
        """An empty quantized store of ``old``'s geometry. It inherits
        the old store's HBM-ledger owner labels (compress runs outside
        the shard's owner scope); the old store's entries release via
        its finalizer once the swap drops the last reference."""
        from weaviate_tpu.engine.quantized import QuantizedVectorStore
        from weaviate_tpu.runtime import hbm_ledger

        own = getattr(old, "_hbm_owner", None) or hbm_ledger.current_owner()
        with hbm_ledger.owner(**own):
            return QuantizedVectorStore(
                dim=self.dim, metric=self.metric, quantization=quantization,
                capacity=old.capacity, chunk_size=old.chunk_size,
                mesh=old.mesh, memwatch=self.memwatch,
                **quant_kwargs)

    @staticmethod
    def _catch_up(new, then: dict, now: dict) -> int:
        """Bring ``new``, which holds snapshot ``then`` of the old store,
        to snapshot ``now`` of it: rows added or overwritten since are
        encoded, rows deleted since are dropped. -> rows touched."""
        n = len(now["valid"])
        was_valid = np.zeros(n, dtype=bool)
        was_valid[:len(then["valid"])] = then["valid"]
        differs = np.ones(n, dtype=bool)
        differs[:len(then["vectors"])] = (
            now["vectors"][:len(then["vectors"])] != then["vectors"]
        ).any(axis=1)
        rewrite = np.nonzero(now["valid"] & (differs | ~was_valid))[0]
        gone = np.nonzero(was_valid & ~now["valid"])[0]
        if len(rewrite):
            new.set_at_prenormalized(rewrite, now["vectors"][rewrite])
        if len(gone):
            new.delete(gone)
        new._count = now["count"]
        return len(rewrite) + len(gone)

    def _compress_epochs(self, old, quantization: str,
                         training_limit: int | None = None,
                         **quant_kwargs) -> None:
        """Epoch-preserving compression: the quantized twin keeps the
        SAME global slot layout (epochs re-split by epoch_rows), so the
        id<->slot tables carry over untouched. Caller holds ``_lock``."""
        from weaviate_tpu.engine.epochs import EpochStore
        from weaviate_tpu.runtime import hbm_ledger

        snap = old.snapshot()
        own = getattr(old, "_owner", None) or hbm_ledger.current_owner()
        with hbm_ledger.owner(**own):
            new = EpochStore(
                dim=self.dim, metric=self.metric,
                epoch_rows=old.epoch_rows, chunk_size=old.chunk_size,
                mesh=old.mesh, quantization=quantization,
                quant_kwargs=quant_kwargs)
        live = np.nonzero(snap["valid"])[0]
        live_vecs = snap["vectors"][live]
        if quantization == "pq":
            centroids = new._quant_kwargs.get("pq_centroids", 16)
            if len(live) < centroids:
                raise RuntimeError(
                    f"need >= {centroids} live vectors to train PQ, "
                    f"have {len(live)}")
        new._restore_rows(live, snap["vectors"], int(snap["count"]))
        if quantization == "pq":
            new.train(live_vecs[:training_limit])
        self.store = new
        self._slots_moved()

    @property
    def compressed(self) -> bool:
        """Reference Compressed() (vector_index.go:37)."""
        from weaviate_tpu.engine.epochs import EpochStore
        from weaviate_tpu.engine.quantized import QuantizedVectorStore

        if isinstance(self.store, EpochStore):
            return bool(self.store.quantization)
        return isinstance(self.store, QuantizedVectorStore)

    # -- epoch hooks (engine/epochs.py; db/collection.py migration) -----------

    @property
    def epoch_store(self):
        """The backing ``EpochStore`` when this index is epoch-backed,
        else None (the maintenance policy keys on this)."""
        from weaviate_tpu.engine.epochs import EpochStore

        return self.store if isinstance(self.store, EpochStore) else None

    def epoch_doc_ids(self, eid: int) -> np.ndarray:
        """Doc ids of one epoch's live rows — the unit the migration
        policy serializes to a sibling shard."""
        es = self.epoch_store
        if es is None:
            return np.empty(0, np.int64)
        with self._lock:
            gslots = es.live_globals_of(eid)
            gslots = gslots[gslots < len(self._slot_to_id)]
            ids = self._slot_to_id[gslots]
            return ids[ids >= 0]

    # -- maintenance / persistence -------------------------------------------

    def compact(self):
        """Reclaim tombstoned rows; remaps id→slot tables."""
        with self._lock:
            mapping = self.store.compact()
            self._compactions += 1
            new_slot_to_id = np.full(self.store.capacity, -1, dtype=np.int64)
            for doc_id, slot in list(self._id_to_slot.items()):
                ns = int(mapping[slot])
                self._id_to_slot[doc_id] = ns
                new_slot_to_id[ns] = doc_id
            self._slot_to_id = new_slot_to_id
            self._slots_moved()

    def snapshot(self) -> dict:
        with self._lock:
            snap = self.store.snapshot()
            snap["slot_to_id"] = self._slot_to_id.copy()
            snap["index_type"] = self.index_type
            return snap

    @classmethod
    def restore(cls, snap: dict, mesh=None, **kwargs) -> "FlatIndex":
        idx = cls.__new__(cls)
        idx.dim = snap["dim"]
        idx.metric = snap["metric"]
        idx.memwatch = kwargs.pop("memwatch", None)
        if snap.get("epoch_rows"):
            from weaviate_tpu.engine.epochs import EpochStore

            idx.store = EpochStore.restore(snap, mesh=mesh, **kwargs)
        elif snap.get("quantization"):
            from weaviate_tpu.engine.quantized import QuantizedVectorStore

            idx.store = QuantizedVectorStore.restore(
                snap, mesh=mesh, memwatch=idx.memwatch, **kwargs)
        else:
            idx.store = DeviceVectorStore.restore(snap, mesh=mesh, **kwargs)
        idx._lock = threading.RLock()
        slot_to_id = snap["slot_to_id"]
        # the snapshot's table can be WIDER than the restored store's
        # capacity (an epoch store sealed early keeps an active epoch's
        # unused range; restore re-splits by epoch_rows) — size to the
        # max so no entry is dropped; slots past the restored count are
        # -1 (nothing live ever pointed there)
        size = max(idx.store.capacity, len(slot_to_id))
        idx._slot_to_id = np.full(size, -1, dtype=np.int64)
        idx._slot_to_id[: len(slot_to_id)] = slot_to_id
        idx._id_to_slot = {
            int(doc): int(slot)
            for slot, doc in enumerate(slot_to_id)
            if doc >= 0 and snap["valid"][slot]
        }
        if idx.device is not None:
            placement.twins.register(idx)
        return idx
