"""Shard: the unit of storage + indexing.

Reference: adapters/repos/db/shard.go (ShardLike :77, struct :185) — owns an
lsmkv Store (objects bucket + docid mappings), one vector index per named
vector, and the inverted index. Write path parity: shard_write_put.go
(putObjectLSM -> updateInvertedIndexLSM -> VectorIndex.Add); read path:
shard_read.go (ObjectVectorSearch / ObjectSearch).
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import weakref

import numpy as np

from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.runtime import placement, tracing
from weaviate_tpu.runtime.metrics import filter_leaf_total
from weaviate_tpu.schema.config import CollectionConfig, VectorConfig
from weaviate_tpu.storage.kv import KVStore
from weaviate_tpu.storage.objects import StorageObject
from weaviate_tpu.text.inverted import InvertedIndex, LeafStats

logger = logging.getLogger(__name__)


class ShardReadOnlyError(RuntimeError):
    """Write refused: shard status is READONLY
    (PUT /v1/schema/{class}/shards/{shard})."""


class StagedExpiredError(RuntimeError):
    """2PC commit refused: the staged entry outlived the staged-entry
    TTL (WEAVIATE_TPU_STAGED_TTL_S). The coordinator treats this like
    any other per-replica commit failure — abort + anti-entropy."""

# bucket names (reference: helpers/helpers.go:22-25)
BUCKET_OBJECTS = "objects"
BUCKET_DOCID = "docid"  # uuid -> doc_id  (adapters/repos/db/docid)
BUCKET_META = "meta"  # counters, checkpoints (indexcounter/)


def _make_vector_index(vc: VectorConfig, dim: int, mesh=None, memwatch=None):
    cfg = vc.index
    if cfg.index_type == "noop":
        return None
    import jax.numpy as jnp

    common = dict(
        dim=dim,
        metric=cfg.metric,
        capacity=8192,
        chunk_size=8192,
    )
    # the watchdog goes down to whatever store a flat index builds, now
    # or when it compresses: a compressed store asks it whether its
    # float32 rescore rows may live on the device (engine/quantized.py)
    flat = dict(common, memwatch=memwatch)
    # a pq or sq class starts on full rows and compresses once, at its
    # training limit (Shard._maybe_compress); bq needs no training and is
    # compressed from its first row
    if cfg.index_type == "flat" and cfg.quantization == "bq":
        return FlatIndex(
            quantization="bq",
            rescore_limit=cfg.rescore_limit,
            prefix_bits=cfg.prefix_bits,
            mesh=mesh,
            epoch_rows=cfg.epoch_rows,
            **flat,
        )
    if cfg.index_type == "flat":
        return FlatIndex(
            mesh=mesh,
            dtype=jnp.bfloat16 if cfg.storage_dtype == "bfloat16" else jnp.float32,
            epoch_rows=cfg.epoch_rows,
            **flat,
        )
    if cfg.index_type == "ivf":
        from weaviate_tpu.engine.ivf import IVFIndex

        if cfg.quantization == "bq":
            # no bq form for IVF lists — honor the compression request on
            # the flat scan (documented fallback, not a silent drop)
            return FlatIndex(quantization="bq", mesh=mesh,
                             rescore_limit=cfg.rescore_limit,
                             prefix_bits=cfg.prefix_bits, **flat)
        # mesh forwarded so the single-replica guard fires loudly instead of
        # silently landing a sharded corpus on one device
        return IVFIndex(nlist=cfg.ivf_nlist, nprobe=cfg.ivf_nprobe,
                        mesh=mesh,
                        flat_search_cutoff=cfg.flat_search_cutoff,
                        quantization=cfg.quantization,
                        pq_segments=cfg.pq_segments,
                        pq_centroids=cfg.pq_centroids,
                        dtype=jnp.bfloat16 if cfg.storage_dtype == "bfloat16"
                        else jnp.float32,
                        **common)
    if cfg.index_type == "hnsw":
        # reference-parity graph index (engine/hnsw.py). A pq-quantized
        # hnsw keeps its GRAPH (runtime ADC compression is applied once
        # enough data exists — compress.go:38); bq has no ADC form for
        # graph hops, so bq configs run the quantized flat scan instead.
        if cfg.quantization == "bq":
            return FlatIndex(quantization="bq", mesh=mesh,
                             rescore_limit=cfg.rescore_limit,
                             prefix_bits=cfg.prefix_bits, **flat)
        from weaviate_tpu.engine.hnsw import HNSWIndex

        return HNSWIndex(
            dim=dim, metric=cfg.metric,
            max_connections=cfg.max_connections,
            ef_construction=cfg.ef_construction, ef=cfg.ef,
            flat_cutoff=cfg.flat_search_cutoff,
        )
    if cfg.index_type == "dynamic":
        # the ANN regime on TPU is IVF (SURVEY §7 step 5), entered via the
        # dynamic flat→ANN upgrade so small corpora stay exact
        from weaviate_tpu.engine.dynamic import DynamicIndex

        if cfg.quantization == "bq":
            # quantized flat scan is already the fast path; stays flat
            # (DynamicIndex refuses to upgrade a quantized impl)
            return DynamicIndex(
                threshold=cfg.flat_to_ann_threshold,
                quantization="bq",
                rescore_limit=cfg.rescore_limit,
                prefix_bits=cfg.prefix_bits,
                mesh=mesh,
                **flat,
            )
        return DynamicIndex(
            threshold=cfg.flat_to_ann_threshold, mesh=mesh,
            flat_search_cutoff=cfg.flat_search_cutoff,
            nlist=cfg.ivf_nlist, nprobe=cfg.ivf_nprobe,
            dtype=jnp.bfloat16 if cfg.storage_dtype == "bfloat16" else jnp.float32,
            # an sq class stays flat, as a bq one does: exact until
            # sq.trainingLimit, then the compressed scan (the IVF index
            # it would upgrade into has no sq form)
            upgradable=cfg.quantization != "sq",
            **flat,
        )
    raise ValueError(f"unknown index type {cfg.index_type}")


class _Search:
    """One shard search between its two halves
    (``Shard.vector_search_begin`` / ``vector_search_end``)."""

    __slots__ = ("k", "queued", "batcher", "item", "found", "span")

    def __init__(self, k: int, queued, found=None):
        self.k = k
        self.queued = queued    # (ids, dists) of not-yet-indexed vectors
        self.batcher = None     # the QueryBatcher that holds ``item``
        self.item = None
        self.found = found      # (ids, dists) where nothing was enqueued
        self.span = None

    def wait(self) -> None:
        """Block until the shard's batcher has delivered, under the
        request's deadline."""
        if self.item is not None:
            self.batcher.wait(self.item)

    def discard(self) -> None:
        """The request is over: an item still queued leaves its queue,
        and a span that ``vector_search_end`` did not reach is closed
        (the shard stays in the trace of a request that failed)."""
        if self.item is not None:
            self.batcher.discard(self.item)
        span, self.span = self.span, None
        tracing.close_span(span)

    def feed(self, ids, dists) -> None:
        """The index's answer, where the collection's drain searched
        for this shard (``vector_search_begin(..., enqueue=False)``):
        one member's ``[k]`` row of the drain's delivery, -1 where the
        scan found nothing or the block was padded to another member's
        width."""
        live = ids >= 0
        self.found = (ids[live].astype(np.int64, copy=False),
                      dists[live].astype(np.float32, copy=False))

    @property
    def t_deliver(self) -> float:
        """When the answer was there (0.0: at once, nothing enqueued)."""
        return (self.item.t_deliver or 0.0) if self.item is not None \
            else 0.0

    def phases(self) -> tuple[float, float, float]:
        return self.batcher.phases(self.item) if self.item is not None \
            else (0.0, 0.0, 0.0)


class Shard:
    def __init__(self, data_dir: str, collection: CollectionConfig, name: str,
                 mesh=None, memwatch=None, async_indexing: bool | None = None,
                 sync_wal: bool | None = None):
        self.name = name
        self.memwatch = memwatch
        # PERSISTENCE_WAL_SYNC (reference: commit logger fsync
        # discipline): fsync every acked write's WAL frame. Parsed by
        # config._flag itself so the two can never disagree.
        if sync_wal is None:
            from weaviate_tpu.config import _flag

            sync_wal = _flag(os.environ, "PERSISTENCE_WAL_SYNC")
        self.sync_wal = sync_wal
        # ASYNC_INDEXING (reference env gate, repo.go/index_queue.go):
        # imports enqueue vectors; a background worker drains into the
        # vector index. Off by default — searches stay read-your-writes.
        # Same accepted values as config._flag so the two never disagree.
        if async_indexing is None:
            async_indexing = os.environ.get(
                "ASYNC_INDEXING", "").lower() in ("true", "1", "on",
                                                  "enabled")
        self.async_indexing = async_indexing
        self._index_queues: dict[str, "IndexQueue"] = {}
        # one compression at a time a shard: the queue's worker and a
        # config update may both find the gate open
        self._compress_lock = threading.Lock()
        # server-side dynamic batching: concurrent single-query searches
        # coalesce into one device dispatch (continuous batching — see
        # runtime/query_batcher.py). QUERY_DYNAMIC_BATCHING=false opts out.
        self.dynamic_batching = os.environ.get(
            "QUERY_DYNAMIC_BATCHING", "true").lower() in (
                "true", "1", "on", "enabled")
        # zero-sync serving pipeline (ISSUE 7): batched dispatches return
        # device-resident handles and drain D2H on a transfer thread
        # while the next batch dispatches. QUERY_ASYNC_PIPELINE=false
        # opts back into worker-synchronous fetches.
        self.async_pipeline = os.environ.get(
            "QUERY_ASYNC_PIPELINE", "true").lower() in (
                "true", "1", "on", "enabled")
        self._query_batchers: dict[str, "QueryBatcher"] = {}
        # hybridplane (ISSUE 18): device-resident BM25 + fusion rides the
        # dense dispatch when the index supports it. Kill switch keeps
        # hybrid on the host reference path; the candidate budget bounds
        # the packed sparse operand (over-budget queries fall back).
        self.device_hybrid = os.environ.get(
            "WEAVIATE_TPU_DEVICE_HYBRID", "true").lower() in (
                "true", "1", "on", "enabled")
        try:
            self.hybrid_max_candidates = int(os.environ.get(
                "WEAVIATE_TPU_HYBRID_MAX_CANDIDATES", "4096"))
        except ValueError:
            self.hybrid_max_candidates = 4096
        # READONLY shard status (reference: PUT /v1/schema/{c}/shards/{s}
        # — schema_shards handlers flip writes off per shard); persisted
        # below once the meta bucket is open so restarts keep the freeze
        self.read_only = False
        self.collection_name = collection.name
        self.config = collection
        # exact-case directory: two collections differing only in case are
        # distinct and must not share (or cross-delete) storage
        self.dir = os.path.join(data_dir, collection.name, name)
        os.makedirs(self.dir, exist_ok=True)
        self._lock = threading.RLock()
        # write generation (a sequence lock): odd while a section that
        # mutates the inverted index or the doc-id space runs
        # (``_writing``), bumped under ``_lock`` and read without it
        # by ``allow_mask``, which builds a filter's mask outside the lock
        # and keeps it only if no such section ran meanwhile
        self._write_gen = 0
        self._write_depth = 0
        self.store = KVStore(self.dir, sync_wal=self.sync_wal)
        self.objects = self.store.bucket(BUCKET_OBJECTS, "replace")
        self.docid = self.store.bucket(BUCKET_DOCID, "replace")
        self.meta = self.store.bucket(BUCKET_META, "replace")
        # deletion tombstones (uuid -> mtime ms) so anti-entropy can tell
        # "deleted here" from "never seen" and not resurrect deletes
        self.tombstones = self.store.bucket("tombstones", "replace")
        # staged 2PC batches: request id -> ("put", [objs]) | ("delete", uuid).
        # In-memory ON PURPOSE — that is what makes the abort-unreachable
        # path crash-safe: a replica that dies between prepare and
        # commit restarts with nothing staged (an implicit abort), and
        # the write converges through anti-entropy if it committed
        # elsewhere. Live orphans (coordinator died / stayed partitioned)
        # expire after ``staged_ttl_s``: gc drops them, and commit_staged
        # REFUSES them even before gc ran, so a straggler commit racing a
        # partition heal can never land a stale write late.
        self._staged: dict[str, tuple] = {}
        self.staged_ttl_s = float(os.environ.get(
            "WEAVIATE_TPU_STAGED_TTL_S", str(self.STAGED_TTL_S)))
        self._staged_expired = 0
        # epoch-migration routing overrides (uuid -> destination shard),
        # durable in the meta bucket; the in-memory count makes the
        # common case (no migrations) a zero-cost check on reads/puts
        self._migrated_count = sum(
            1 for k in self.meta.keys() if k.startswith(b"migrated:"))
        # memory-pressure rescue hook (db/collection.py wires this to
        # epoch compaction + migration): called once when admission
        # would 507, then admission re-checks before actually rejecting
        self.memory_rescue = None
        # optional per-shard HBM quota (WEAVIATE_TPU_SHARD_HBM_LIMIT_
        # BYTES): the placement-level watermark epoch migration exists
        # for — moving the coldest sealed epoch to a sibling genuinely
        # relieves THIS shard's ledger footprint, where the device-
        # global budget only compaction can relieve locally
        try:
            self.shard_hbm_limit = int(os.environ.get(
                "WEAVIATE_TPU_SHARD_HBM_LIMIT_BYTES", "0") or 0)
        except ValueError:
            self.shard_hbm_limit = 0
        self._counter = self.meta.get(b"doc_counter") or 0
        self.read_only = bool(self.meta.get(b"read_only") or False)
        self.mesh = mesh
        # the chip of this host the shard's vector indexes live on, from
        # now until ``close`` (runtime/placement.py: the least-held local
        # device, so a collection's shards spread evenly and a host of
        # one chip gives every shard that chip); a mesh places its own
        self.device = None if mesh is not None else placement.acquire()
        self._release_device = weakref.finalize(
            self, placement.release, self.device)
        # named vector indexes, built lazily at first insert (dim inference)
        self.vector_indexes: dict[str, FlatIndex] = {}
        # persistent inverted index: postings/filterables write through the
        # shard's own LSM store and are read on demand — NOT rebuilt from
        # objects at open (reference: inverted/ lsmkv buckets)
        self._inverted = InvertedIndex(collection, store=self.store)
        # doc_id -> uuid, rebuilt at startup; the object-resolution hot path
        # after a vector search (reference: docid bucket, adapters/repos/db/docid)
        self._doc_to_uuid: dict[int, str] = {}
        self._restore_vector_indexes()

    # -- startup -------------------------------------------------------------

    def _restore_vector_indexes(self):
        """Rebuild HBM state from the durable object store (reference:
        hnsw/startup.go:57 replays the commit log; we replay the objects
        bucket — the vectors ARE the log)."""
        batch: dict[str, tuple[list[int], list[np.ndarray]]] = {}
        # one-time migration: a shard written before the inverted index was
        # persistent has objects but empty inv_* buckets — rebuild postings
        # from objects once so pre-upgrade data stays searchable
        migrate_inverted = self._inverted.doc_count == 0
        migrated = 0
        migrate_chunk: list[StorageObject] = []
        for key, raw in self.objects.iter_items():
            obj = StorageObject.from_bytes(raw)
            self._doc_to_uuid[obj.doc_id] = obj.uuid
            if migrate_inverted:
                migrate_chunk.append(obj)
                if len(migrate_chunk) >= 2000:  # batched WAL frames
                    self._inverted.index_objects(migrate_chunk)
                    migrated += len(migrate_chunk)
                    migrate_chunk = []
            for vec_name, vec in obj.vectors.items():
                ids, vecs = batch.setdefault(vec_name, ([], []))
                ids.append(obj.doc_id)
                vecs.append(vec)
        if migrate_chunk:
            self._inverted.index_objects(migrate_chunk)
            migrated += len(migrate_chunk)
        self._inverted.reconcile_doc_count(len(self._doc_to_uuid))
        if migrated:
            import logging

            logging.getLogger(__name__).info(
                "shard %s: migrated %d objects into the persistent "
                "inverted index", self.name, migrated)
        for vec_name, (ids, vecs) in batch.items():
            # tolerate poisoned rows (dim drift from old bugs/corruption)
            # instead of refusing to start — reference analog:
            # hnsw/corrupt_commit_logs_fixer.go skips bad log entries
            dim = len(vecs[0])
            keep = [j for j, v in enumerate(vecs) if len(v) == dim]
            if len(keep) != len(vecs):
                import logging

                logging.getLogger(__name__).warning(
                    "shard %s: skipping %d vectors with mismatched dims for %r",
                    self.name, len(vecs) - len(keep), vec_name,
                )
            idx = self._ensure_vector_index(vec_name, dim)
            if idx is not None and keep:
                # the bucket walks in uuid order; doc ids were handed out
                # in import order, and slots in doc-id order are what a
                # pq codebook's "first pq_training_limit rows" means
                keep.sort(key=ids.__getitem__)
                idx.add_batch(
                    np.asarray([ids[j] for j in keep]),
                    np.stack([vecs[j] for j in keep]),
                )
                # runtime compression (compress.go:38) is re-applied from
                # the same gate, so a restart neither loses it nor trains
                # on other rows than the import did
                self._maybe_compress(vec_name, idx)

    def _ensure_vector_index(self, vec_name: str, dim: int):
        if vec_name in self.vector_indexes:
            return self.vector_indexes[vec_name]
        vc = self.config.vector_config(vec_name)
        if vc is None:
            vc = VectorConfig(name=vec_name)
        # HBM-ledger owner scope: every device array the index (and its
        # stores) allocates — now or on a later grow — is attributed to
        # this (collection, shard, tenant)
        from weaviate_tpu.runtime import hbm_ledger

        with hbm_ledger.owner(self.collection_name, self.name,
                              tenant=self._tenant_label(),
                              device=self.device):
            idx = _make_vector_index(vc, dim, mesh=self.mesh,
                                     memwatch=self.memwatch)
        self.vector_indexes[vec_name] = idx
        self._register_drift_canary(vec_name)
        return idx

    def _register_drift_canary(self, vec_name: str) -> None:
        """Hand this vector space to driftwatch as a canary target. The
        callbacks resolve ``self.vector_indexes[vec_name]`` per call so
        they survive compress()/DynamicIndex upgrades swapping stores
        under the same key, and the probe search routes through
        ``_query_batcher`` — the REAL serving dispatch (coalescing,
        faultline point, kernelscope attribution), not a side channel."""
        from weaviate_tpu.runtime import driftwatch

        def _idx():
            return self.vector_indexes.get(vec_name)

        def corpus_fn():
            idx = _idx()
            id_map = getattr(idx, "_id_to_slot", None)
            if not id_map:
                return None
            # one ordered walk of the objects bucket, each object's one
            # vector copied straight into its row: no StorageObject is
            # built and no point look-up made. Which docs count is the
            # index's id map; the rows are the durable store's own.
            cap = len(id_map)
            ids = np.empty(cap, dtype=np.int64)
            vecs = np.empty((cap, idx.dim), dtype=np.float32)
            read = StorageObject.read_vector_into
            n = 0
            for _key, raw in self.objects.iter_items():
                if n == cap:  # the corpus grew under the walk
                    break
                d = read(raw, vec_name, vecs[n])
                if d is not None and d in id_map:
                    ids[n] = d
                    n += 1
            if not n:
                return None
            order = np.argsort(ids[:n], kind="stable")
            return ids[order], vecs[order]

        def rows_fn():
            idx = _idx()
            return 0 if idx is None else len(idx)

        def epoch_token_fn():
            idx = _idx()
            if idx is None:
                return None
            es = getattr(idx, "epoch_store", None)
            if es is not None:
                return (tuple((e["epoch"], e["rows"], e["live"])
                              for e in es.epoch_stats()), len(idx))
            return (len(idx),)

        def pairwise_fn(qs, vecs):
            idx = _idx()
            metric = getattr(idx, "metric", "l2-squared")
            return Shard._host_pairwise(qs, vecs, metric)

        def search_fn(queries, k):
            idx = _idx()
            if idx is None or getattr(idx, "search_by_vector_batch",
                                      None) is None:
                return None
            b = self._query_batcher(vec_name, idx)
            out = []
            for q in np.asarray(queries, dtype=np.float32):
                ids, _ = b.search(q, k, None)
                ids = np.asarray(ids)
                out.append(ids[ids >= 0].astype(np.int64))
            return out

        driftwatch.register_canary(
            f"{self.collection_name}/{self.name}/{vec_name or '-'}",
            collection=self.collection_name, shard=self.name,
            search_fn=search_fn, corpus_fn=corpus_fn,
            epoch_token_fn=epoch_token_fn, pairwise_fn=pairwise_fn,
            rows_fn=rows_fn)

    def _tenant_label(self) -> str:
        """Tenants ARE shards in this layout (reference: partitioned
        shards keyed by tenant name) — the ledger's tenant label is the
        shard name iff multi-tenancy is on."""
        return self.name if self.config.multi_tenancy.enabled else ""

    def _maybe_compress(self, vec_name: str, idx) -> None:
        """Compress ``idx`` if its config asks for it and its gate
        (``VectorIndexConfig.compress_due``) is open: the one place
        runtime compression starts from, for an import, the async
        queue's drain, a restart and a config update alike."""
        vc = self.config.vector_config(vec_name)
        if (vc is None or getattr(idx, "compressed", True)
                or not hasattr(idx, "compress")
                or not vc.index.compress_due(len(idx))):
            return
        cfg = vc.index
        with self._compress_lock:
            if idx.compressed:  # another thread's drain got here first
                return
            try:
                idx.compress(quantization=cfg.quantization,
                             pq_segments=cfg.pq_segments,
                             pq_centroids=cfg.pq_centroids,
                             rescore_limit=cfg.rescore_limit,
                             prefix_bits=cfg.prefix_bits,
                             training_limit=cfg.training_limit)
            except Exception:  # noqa: BLE001 — the write itself stands
                # past the gate nothing is left to wait for: a class
                # that cannot compress is a fault. It keeps answering
                # exactly from full rows, and the next batch tries again.
                from weaviate_tpu.runtime.metrics import index_compress_total

                index_compress_total.labels(cfg.quantization,
                                            "failed").inc()
                logger.exception(
                    "shard %s/%s: %s compression failed at %d rows",
                    self.name, vec_name, cfg.quantization, len(idx))

    # -- write path ----------------------------------------------------------

    @contextlib.contextmanager
    def _writing(self):
        """Caller holds ``_lock``. Wraps a section that mutates the
        inverted index or the doc-id space: ``_write_gen`` is odd from its
        first statement to its last (the outermost section's, where they
        nest), so a reader outside the lock can tell that its evaluation
        overlapped none (``allow_mask``)."""
        self._write_depth += 1
        if self._write_depth == 1:
            self._write_gen += 1
        try:
            yield
        finally:
            self._write_depth -= 1
            if self._write_depth == 0:
                self._write_gen += 1

    def _next_doc_id(self) -> int:
        with self._lock:
            doc_id = self._counter
            self._counter += 1
            self.meta.put(b"doc_counter", self._counter)
            return doc_id

    def put_object(self, obj: StorageObject) -> int:
        """Insert or update (reference: shard_write_put.go:218 putObjectLSM).

        Updates keep the uuid but get a fresh doc id, tombstoning the old
        one in the vector indexes (reference does the same doc-id bump)."""
        return self.put_object_batch([obj])[0]

    def _expected_dim(self, vec_name: str) -> int | None:
        idx = self.vector_indexes.get(vec_name)
        if idx is not None:
            return idx.dim
        vc = self.config.vector_config(vec_name)
        if vc is not None and vc.dim:
            return vc.dim
        return None

    def _validate_vectors(self, objs: list[StorageObject]) -> None:
        """Reject dim mismatches BEFORE any mutation — a failed index add
        after the object landed in the store would poison restart replay."""
        first_dims: dict[str, int] = {}
        for obj in objs:
            for vec_name, vec in obj.vectors.items():
                dim = self._expected_dim(vec_name) or first_dims.get(vec_name)
                if dim is None:
                    first_dims[vec_name] = len(vec)
                elif len(vec) != dim:
                    raise ValueError(
                        f"vector dim {len(vec)} != expected dim {dim} "
                        f"for vector {vec_name!r} (object {obj.uuid})"
                    )

    def put_object_batch(self, objs: list[StorageObject]) -> list[int]:
        """Reference: shard_write_batch_objects.go:33."""
        # dedupe by uuid (last wins): a duplicate in one batch would queue
        # the first occurrence's vector for an already-deleted doc id,
        # leaving a ghost row in the index
        if len({o.uuid for o in objs}) != len(objs):
            last = {o.uuid: i for i, o in enumerate(objs)}
            objs = [objs[i] for i in sorted(last.values())]
        doc_ids: list[int] = []
        gate = self.memwatch is not None or self.shard_hbm_limit
        if gate:
            # optimistic rescue pass, OUTSIDE the shard lock so the
            # hook (epoch compaction, then migrating the coldest sealed
            # epoch to a sibling — db/collection.py) can touch sibling
            # shards without a lock cycle. The AUTHORITATIVE admission
            # check re-runs under the lock below, serialized with the
            # adds, so N concurrent importers can't all pass against
            # the same stale usage. Read-only shards skip the rescue —
            # they refuse with ShardReadOnlyError, not 507.
            nbytes = sum(int(np.asarray(v).nbytes)
                         for o in objs for v in o.vectors.values())
            if not self.read_only:
                try:
                    self._admit_device_bytes(nbytes)
                except MemoryError:
                    if self.memory_rescue is None:
                        raise
                    try:
                        self.memory_rescue()
                    except Exception:  # noqa: BLE001 — best-effort; the
                        logger.exception(  # typed 507 below is the answer
                            "shard %s/%s: memory-pressure rescue failed",
                            self.collection_name, self.name)
        with self._lock, self._writing():
            if self.read_only:
                raise ShardReadOnlyError(
                    f"shard {self.name!r} is read-only (status READONLY)")
            self._validate_vectors(objs)
            if gate:
                # refuse BEFORE mutating anything (reference memwatch
                # CheckAlloc semantics): vectors land in device HBM
                self._admit_device_bytes(nbytes)
            vec_batches: dict[str, tuple[list[int], list[np.ndarray]]] = {}
            # doc ids for the whole batch come from one counter bump (one
            # meta write instead of len(objs))
            first_id = self._counter
            self._counter += len(objs)
            self.meta.put(b"doc_counter", self._counter)
            docid_puts: list[tuple[bytes, object]] = []
            object_puts: list[tuple[bytes, object]] = []
            uuid_keys = [o.uuid.encode() for o in objs]
            old_raws = self.docid.get_many(uuid_keys)
            # flagship import shape (exactly one unnamed vector per
            # object): all storobj value frames come out of ONE native
            # call; props are msgpacked here so the bytes match the
            # Python encoder exactly. Any other shape — or a uuid the
            # fast parser rejects — keeps the per-object Python codec.
            frames = None
            from weaviate_tpu import native

            single_vec = (objs and native.available() and all(
                len(o.vectors) == 1 and "" in o.vectors for o in objs))
            if single_vec:
                import msgpack

                vec_block = np.stack([
                    np.asarray(o.vectors[""], dtype=np.float32)
                    for o in objs])
                n_objs = len(objs)
                frames = native.storobj_encode_batch(
                    uuid_keys,
                    [msgpack.packb(o.properties, use_bin_type=True)
                     for o in objs],
                    vec_block,
                    np.arange(first_id, first_id + n_objs, dtype=np.int64),
                    np.fromiter((o.creation_time_ms for o in objs),
                                np.int64, n_objs),
                    np.fromiter((o.last_update_time_ms for o in objs),
                                np.int64, n_objs))
            # update path: every replaced doc's teardown runs BATCHED —
            # the per-object form paid one device dispatch per tombstone
            # (flat.delete -> store.delete) and one inverted pass each,
            # which made re-imports ~5x slower than fresh inserts
            updates = [(int(old_raw), obj.uuid)
                       for obj, old_raw in zip(objs, old_raws)
                       if old_raw is not None]
            if updates:
                self._delete_docs_batch(updates)
            for i, obj in enumerate(objs):
                obj.doc_id = first_id + i
                docid_puts.append((uuid_keys[i], obj.doc_id))
                self._doc_to_uuid[obj.doc_id] = obj.uuid
                object_puts.append((
                    uuid_keys[i],
                    frames[i] if frames is not None else obj.to_bytes()))
                if frames is None:
                    for vec_name, vec in obj.vectors.items():
                        ids, vecs = vec_batches.setdefault(
                            vec_name, ([], []))
                        ids.append(obj.doc_id)
                        vecs.append(np.asarray(vec, dtype=np.float32))
                doc_ids.append(obj.doc_id)
            if frames is not None:
                vec_batches[""] = (doc_ids, vec_block)
            # ordering invariant: inverted postings land BEFORE the objects
            # bucket. A crash in between leaves ghost postings (doc ids the
            # object replay never resurrects — filters mask them out and
            # result resolution drops them), never missing postings for a
            # visible object. The objects-bucket WAL is the commit point.
            self._inverted.index_objects(objs)
            # clear any prior delete markers in one frame
            self.tombstones.delete_many(k for k, _ in docid_puts)
            self.docid.put_many(docid_puts)
            self.objects.put_many(object_puts)
            for vec_name, (ids, vecs) in vec_batches.items():
                idx = self._ensure_vector_index(vec_name, len(vecs[0]))
                if idx is None:
                    continue
                # fast path hands a prebuilt [n, d] block; list -> stack
                block = vecs if isinstance(vecs, np.ndarray) \
                    else np.stack(vecs)
                if self.async_indexing:
                    self._index_queue(vec_name, idx).push(
                        np.asarray(ids), block)
                else:
                    idx.add_batch(np.asarray(ids), block)
                    self._maybe_compress(vec_name, idx)
        return doc_ids

    def _query_batcher(self, vec_name: str, idx):
        """The shard's per-vector-space QueryBatcher, built lazily (shared
        by the dense path and the hybridplane's fused dispatch)."""
        batch_fn = idx.search_by_vector_batch
        b = self._query_batchers.get(vec_name)
        if b is None:
            from weaviate_tpu.runtime.query_batcher import QueryBatcher

            # filtered requests coalesce (bitmask-batched) when the index
            # supports per-query allow lists; the capacity hook powers
            # the batcher's selectivity cutover and reports 0 (= never
            # solo) unless the CURRENT store has a solo gathered path
            # (single-device DeviceVectorStore) — elsewhere a solo
            # dispatch is a full masked scan, strictly worse than riding
            # the batch. Resolved per call: compress()/upgrade() swap
            # idx.store after the batcher exists.
            def _gathered_capacity(i=idx) -> int:
                s = getattr(i, "store", None)
                es = getattr(i, "epoch_store", None)
                if es is not None:
                    # single-epoch passthrough keeps the solo gathered
                    # cutover (the epoch IS a DeviceVectorStore); a
                    # multi-epoch stack has no host-remap solo path, so
                    # selective filters ride the batched bitmask there
                    if (es.mesh is None and not es.quantization
                            and es.epoch_count == 1):
                        return es.capacity
                    return 0
                if (s is None or getattr(s, "mesh", None) is not None
                        or not hasattr(s, "_dispatch_gathered")):
                    return 0
                return s.capacity

            # a mask the index keeps device operands of has its count
            # there (engine/filter_operands.py); resolved per call like
            # the rest, None where the index of the moment has no such
            # method (the batcher then counts)
            def _allowed_count(allow, i=idx):
                fn = getattr(i, "allowed_count", None)
                return None if fn is None else fn(allow)

            # zero-sync pipeline: resolved through getattr PER CALL so a
            # compress()/DynamicIndex.upgrade() swapping the impl under
            # the cached batcher degrades to the sync path (None) instead
            # of pinning a stale bound method
            def _async_batch(queries, k2, allow=None, i=idx):
                fn = getattr(i, "search_by_vector_batch_async", None)
                return None if fn is None else fn(queries, k2, allow)

            # fused sparse+dense drain (ISSUE 18): hybrid rows ride the
            # same coalescing window as plain vector queries; resolved
            # per call for the same impl-swap reason as _async_batch
            def _hybrid_batch(queries, k2, allows=None, sparses=None,
                              i=idx):
                fn = getattr(i, "hybrid_batch_async", None)
                return None if fn is None else fn(queries, k2, allows,
                                                  sparses)

            b = self._query_batchers.setdefault(
                vec_name,
                QueryBatcher(
                    batch_fn,
                    # callable: DynamicIndex upgrades / compress() can
                    # change the capability under the cached batcher
                    supports_filter_batching=lambda i=idx: bool(
                        getattr(i, "supports_batched_filters", False)),
                    capacity_fn=_gathered_capacity,
                    count_fn=_allowed_count,
                    pad_pow2=bool(getattr(idx, "compiled_batch_shapes",
                                          True)),
                    async_batch_fn=(_async_batch if self.async_pipeline
                                    else None),
                    hybrid_batch_fn=_hybrid_batch,
                    owner={"collection": self.collection_name,
                           "shard": self.name,
                           "tenant": self._tenant_label(),
                           "device": self.device},
                    # kernelscope variant label: residency EWMAs key on
                    # (index kind, b bucket, k bucket) compiled variants
                    kind=str(getattr(idx, "index_type", "index")),
                ))
        return b

    def _index_queue(self, vec_name: str, idx):
        q = self._index_queues.get(vec_name)
        if q is None:
            from weaviate_tpu.runtime.index_queue import IndexQueue

            q = IndexQueue(
                idx, after_add=lambda: self._maybe_compress(vec_name, idx))
            self._index_queues[vec_name] = q
        return q

    def _delete_doc(self, doc_id: int, uuid: str, old=None):
        for q in self._index_queues.values():
            q.delete(doc_id)  # drop any queued insert for this doc
        for idx in self.vector_indexes.values():
            if idx is not None:
                idx.delete(doc_id)
        if old is None:
            old = self.get_object(uuid)
        if old is not None:
            self._inverted.unindex_object(old)
        self._doc_to_uuid.pop(doc_id, None)

    def _delete_docs_batch(self, pairs: list[tuple[int, str]]) -> None:
        """Batched twin of ``_delete_doc`` for the update path: one
        vector-index delete (one device tombstone scatter), one batched
        object fetch, one inverted unindex pass."""
        doc_ids = [d for d, _u in pairs]
        for q in self._index_queues.values():
            for d in doc_ids:
                q.delete(d)
        for idx in self.vector_indexes.values():
            if idx is not None:
                idx.delete(*doc_ids)
        raws = self.objects.get_many([u.encode() for _d, u in pairs])
        olds = [StorageObject.from_bytes(r) for r in raws if r is not None]
        if olds:
            self._inverted.unindex_objects(olds)
        for d in doc_ids:
            self._doc_to_uuid.pop(d, None)

    def delete_object(self, uuid: str, tombstone_ms: int | None = None) -> bool:
        import time as _time

        with self._lock, self._writing():
            if self.read_only:
                raise ShardReadOnlyError(
                    f"shard {self.name!r} is read-only (status READONLY)")
            raw = self.docid.get(uuid.encode())
            if raw is None:
                return False
            # same ordering invariant as the put path: the object/docid
            # deletes commit FIRST, the inverted unindex follows — a crash
            # in between leaves benign ghost postings, never a visible
            # object invisible to filters/BM25
            old = self.get_object(uuid)
            self.docid.delete(uuid.encode())
            self.objects.delete(uuid.encode())
            self.tombstones.put(uuid.encode(),
                                tombstone_ms or int(_time.time() * 1000))
            self._delete_doc(int(raw), uuid, old=old)
            return True

    # -- read path -----------------------------------------------------------

    def get_object(self, uuid: str) -> StorageObject | None:
        raw = self.objects.get(uuid.encode())
        if raw is None:
            return None
        return StorageObject.from_bytes(raw)

    def get_frames(self, uuids: list[str],
                   routes: dict | None = None) -> list[bytes | None]:
        """The stored frame (``StorageObject.to_bytes``) of every uuid,
        None for one that is gone, in ONE ``kv.get_many``: a Search's
        reply reads its objects here, one lock acquisition and one
        search a segment a request, not a result, and decodes none
        (``routes``: ``Bucket.get_many``'s tally)."""
        return self.objects.get_many([u.encode() for u in uuids], routes)

    def get_objects(self, uuids: list[str],
                    routes: dict | None = None) -> list[StorageObject | None]:
        """``[get_object(u) for u in uuids]`` over one :meth:`get_frames`."""
        return [None if raw is None else StorageObject.from_bytes(raw)
                for raw in self.get_frames(uuids, routes)]

    def exists(self, uuid: str) -> bool:
        return self.docid.get(uuid.encode()) is not None

    def object_count(self) -> int:
        # exact and O(1): maintained by put/delete/restore (len(self.docid)
        # would re-scan every segment per key)
        return len(self._doc_to_uuid)

    def object_by_doc_id(self, doc_id: int) -> StorageObject | None:
        uuid = self._doc_to_uuid.get(int(doc_id))
        return None if uuid is None else self.get_object(uuid)

    def objects_by_doc_ids(self, doc_ids) -> list[StorageObject | None]:
        """Batched doc-id -> object resolution: ONE ``kv.get_many``
        layer snapshot for the whole id list instead of a point lookup
        (lock + sealed-list copy) per doc — the native data plane's
        reply-building feed (warm pass + cache-miss fill) reads through
        here, so property fetch on the hot path is one LSM batch per
        reply batch."""
        uuids = [self._doc_to_uuid.get(int(d)) for d in doc_ids]
        known = [u for u in uuids if u is not None]
        objs = iter(self.get_objects(known) if known else ())
        return [None if u is None else next(objs) for u in uuids]

    def vector_search(self, query: np.ndarray, k: int, vec_name: str = "",
                      allow_list: np.ndarray | None = None):
        """(doc_ids, dists) for the shard-local search (reference:
        shard_read.go ObjectVectorSearch). With async indexing on, queued
        (not-yet-indexed) vectors are brute-forced and merged so the path
        stays read-your-writes (reference: index queue search over the
        unindexed tail)."""
        idx = self.vector_indexes.get(vec_name)
        if idx is None:
            return np.empty(0, np.int64), np.empty(0, np.float32)
        with tracing.span("shard.vector_search", shard=self.name, k=k,
                          filtered=allow_list is not None):
            return self._search_end(
                self._search_begin(idx, query, k, vec_name, allow_list))

    def drain_member(self, vec_name: str = ""):
        """The index a collection's drain launches for this shard
        (db/drain.py), or None where this shard's searches ride no
        batcher: no such vector space, ``QUERY_DYNAMIC_BATCHING`` off,
        an index without a batched entry point."""
        idx = self.vector_indexes.get(vec_name)
        if idx is None or not self.dynamic_batching or getattr(
                idx, "search_by_vector_batch", None) is None:
            return None
        return idx

    def vector_search_begin(self, query: np.ndarray, k: int,
                            vec_name: str = "",
                            allow_list: np.ndarray | None = None,
                            enqueue: bool = True) -> "_Search":
        """First half of ``vector_search``, for a request that searches
        several shards (``Collection.near_vector``): the snapshot of the
        queued vectors and the enqueue on this shard's batcher. Returns
        at once where the index has a batched entry point; the caller's
        thread is held by no shard. ``vector_search_end`` gives what
        ``vector_search`` gives. ``enqueue=False``: the collection's
        drain searches this shard's index (``drain_member``) with its
        other members'; the snapshot and the span are taken here all the
        same, and the caller ``feed``s the search its member's answer
        before ``vector_search_end``."""
        idx = self.vector_indexes.get(vec_name)
        if idx is None:
            return _Search(k, None, found=(np.empty(0, np.int64),
                                           np.empty(0, np.float32)))
        span = tracing.open_span("shard.vector_search", shard=self.name,
                                 k=k, filtered=allow_list is not None)
        search = tracing.run_in(span, self._search_begin, idx, query, k,
                                vec_name, allow_list, enqueue)
        search.span = span
        return search

    def vector_search_end(self, search: "_Search", charge: bool = True):
        """Second half: wait for the answer under the request's deadline
        and merge the queued vectors in. ``charge``: whether this
        search's queue_wait, device and transfer are the request's (a
        fan-out charges the one on its critical path)."""
        span, search.span = search.span, None
        try:
            return tracing.run_in(span, self._search_end, search, charge)
        finally:
            tracing.close_span(span)

    def _search_begin(self, idx, query, k, vec_name, allow_list,
                      enqueue: bool = True) -> "_Search":
        # snapshot BEFORE the index search: every queued vector is either
        # in the snapshot or already drained into the index by the time
        # the index search runs — the union misses nothing (the reverse
        # order races a drain finishing between the two reads)
        search = _Search(k, self._queued_candidates(vec_name, query,
                                                    allow_list))
        if not enqueue:
            return search
        if self.dynamic_batching and query.ndim == 1 and getattr(
                idx, "search_by_vector_batch", None) is not None:
            # dynamic-batched single-query search: concurrent callers
            # share one device dispatch (VERDICT r1 item 6)
            search.batcher = self._query_batcher(vec_name, idx)
            search.item = search.batcher.enqueue(query, k, allow_list)
        else:
            # index types without a batch entry point: the direct path
            search.found = idx.search_by_vector(query, k,
                                                allow_list=allow_list)
        return search

    def _search_end(self, search: "_Search", charge: bool = True):
        if search.item is not None:
            ids, dists = search.batcher.finish(
                search.batcher.wait(search.item), charge)
            live = ids >= 0
            ids, dists = (np.asarray(ids)[live].astype(np.int64),
                          np.asarray(dists)[live].astype(np.float32))
        else:
            ids, dists = search.found
        if search.queued is None:
            return ids, dists
        q_ids, q_dists = search.queued
        cat_ids = np.concatenate([np.asarray(ids, np.int64), q_ids])
        cat_d = np.concatenate([np.asarray(dists, np.float32), q_dists])
        order = np.argsort(cat_d, kind="stable")
        # dedup (a drain may have landed an in-flight vector in the index
        # between the index search and the snapshot), best distance first
        seen: set = set()
        out_ids, out_d = [], []
        for j in order:
            did = int(cat_ids[j])
            if did in seen:
                continue
            seen.add(did)
            out_ids.append(did)
            out_d.append(float(cat_d[j]))
            if len(out_ids) == search.k:
                break
        return (np.asarray(out_ids, np.int64),
                np.asarray(out_d, np.float32))

    def vector_search_batch(self, queries: np.ndarray, k: int,
                            vec_name: str = ""):
        """Batched twin of vector_search for the native data plane's
        coalesced dispatch (csrc/dataplane.cpp): one index batch search,
        queued (not-yet-indexed) vectors brute-forced against the whole
        query block and merged per row. No filters — filtered queries
        take the fallback path. Returns (ids [B, k], dists [B, k],
        counts [B]); dead rows are -1-padded."""
        idx = self.vector_indexes.get(vec_name)
        b = len(queries)
        if idx is None:
            return (np.full((b, k), -1, np.int64),
                    np.full((b, k), np.inf, np.float32),
                    np.zeros(b, np.int64))
        queue = self._index_queues.get(vec_name)
        pending = queue.snapshot() if queue is not None else []
        ids, dists = idx.search_by_vector_batch(queries, k)
        return self._finish_batch_results(ids, dists, pending, queries,
                                          idx.metric, k)

    def vector_search_batch_async(self, queries: np.ndarray, k: int,
                                  vec_name: str = ""):
        """Dispatch-only twin of ``vector_search_batch`` for the native
        data plane's pipelined loop (ISSUE 7): returns a
        ``DeviceResultHandle`` resolving to the same (ids, dists,
        counts), or ``None`` when the index has no async path — the
        plane then falls back to the synchronous call. The queued-tail
        snapshot is taken BEFORE the index dispatch (same ordering
        invariant as ``_search_begin``) and merged in the
        handle's host finish step."""
        idx = self.vector_indexes.get(vec_name)
        if idx is None:
            return None
        fn = getattr(idx, "search_by_vector_batch_async", None)
        if fn is None:
            return None
        queue = self._index_queues.get(vec_name)
        pending = queue.snapshot() if queue is not None else []
        handle = fn(queries, k)
        if handle is None:
            return None
        queries = np.asarray(queries, np.float32)

        def _finish(res, _pending=pending, _queries=queries, _k=k,
                    _metric=idx.metric):
            ids, dists = res
            return self._finish_batch_results(ids, dists, _pending,
                                              _queries, _metric, _k)

        return handle.map(_finish)

    def _finish_batch_results(self, ids, dists, pending, queries,
                              metric: str, k: int):
        """Host half shared by the sync and pipelined batch paths:
        merge the queued (not-yet-indexed) tail, count live rows."""
        b = len(queries)
        ids = np.asarray(ids, np.int64)
        dists = np.asarray(dists, np.float32)
        if pending:
            q_ids = np.asarray([d for d, _ in pending], np.int64)
            q_vecs = np.stack([v for _, v in pending]).astype(np.float32)
            qd = self._host_pairwise(np.asarray(queries, np.float32),
                                     q_vecs, metric)  # [B, nq]
            cat_ids = np.concatenate(
                [ids, np.broadcast_to(q_ids, (b, len(q_ids)))], axis=1)
            cat_d = np.concatenate([dists, qd.astype(np.float32)], axis=1)
            order = np.argsort(cat_d, axis=1, kind="stable")
            out_i = np.full((b, k), -1, np.int64)
            out_d = np.full((b, k), np.inf, np.float32)
            for r in range(b):
                seen: set = set()
                n = 0
                for j in order[r]:
                    did = int(cat_ids[r, j])
                    if did < 0 or did in seen:
                        continue
                    seen.add(did)
                    out_i[r, n] = did
                    out_d[r, n] = cat_d[r, j]
                    n += 1
                    if n == k:
                        break
            ids, dists = out_i, out_d
        counts = (ids >= 0).sum(axis=1).astype(np.int64)
        return ids, dists, counts

    @staticmethod
    def _host_pairwise(qs: np.ndarray, vecs: np.ndarray,
                       metric: str) -> np.ndarray:
        """[B, n] host-BLAS distances (queued-tail scoring; see the
        numpy-not-jit note in _queued_candidates)."""
        if metric in ("cosine", "cosine-dot"):
            def unit(a):
                n = np.linalg.norm(a, axis=-1, keepdims=True)
                return a / np.where(n > 1e-30, n, 1.0)

            return 1.0 - unit(qs) @ unit(vecs).T
        if metric == "dot":
            return -(qs @ vecs.T)
        if metric == "hamming":
            return (qs[:, None, :] != vecs[None, :, :]).sum(-1).astype(
                np.float32)
        if metric == "manhattan":
            return np.abs(qs[:, None, :] - vecs[None, :, :]).sum(-1)
        sq = (qs ** 2).sum(-1)[:, None] + (vecs ** 2).sum(-1)[None, :]
        return sq - 2.0 * (qs @ vecs.T)

    def _queued_candidates(self, vec_name: str, query: np.ndarray,
                           allow_list: np.ndarray | None):
        queue = self._index_queues.get(vec_name)
        if queue is None:
            return None
        pending = queue.snapshot()
        if not pending:
            return None
        ids = np.asarray([d for d, _ in pending], dtype=np.int64)
        vecs = np.stack([v for _, v in pending]).astype(np.float32)
        if allow_list is not None:
            allow = np.asarray(allow_list)
            if allow.dtype == np.bool_:
                keep = (ids < len(allow)) & allow[
                    np.clip(ids, 0, len(allow) - 1)]
            else:
                keep = np.isin(ids, allow.astype(np.int64))
            ids, vecs = ids[keep], vecs[keep]
            if not len(ids):
                return None
        metric = getattr(self.vector_indexes.get(vec_name), "metric",
                         "l2-squared")
        # plain numpy: the pending set's length changes every drain tick,
        # and a jitted path would recompile per distinct length (the
        # device store pads to buckets for exactly this reason) — the
        # queue is small, host BLAS is plenty
        q = np.asarray(query, np.float32)
        d = self._host_pairwise(q[None, :], vecs, metric)[0]
        return ids, d.astype(np.float32)

    def bm25_search(self, query: str, k: int = 10,
                    properties: list[str] | None = None,
                    allow_mask: np.ndarray | None = None):
        """(doc_ids, scores) keyword search (reference: shard ObjectSearch →
        inverted.BM25Searcher). ``allow_mask`` accepts either form the
        vector path does: bool mask or doc-id array."""
        with tracing.span("shard.bm25_search", shard=self.name, k=k,
                          filtered=allow_mask is not None):
            return self._inverted.bm25_search(query, k, properties,
                                              self._norm_allow(allow_mask))

    def _norm_allow(self, allow_mask):
        """Allow-list normalization shared by the keyword and hybrid
        paths: bool mask passes through, doc-id arrays densify over this
        shard's doc-id space."""
        if allow_mask is None:
            return None
        allow_mask = np.asarray(allow_mask)
        if allow_mask.dtype != np.bool_:
            ids = allow_mask.astype(np.int64)
            allow_mask = np.zeros(self.doc_id_space, dtype=bool)
            allow_mask[ids[ids < len(allow_mask)]] = True
        return allow_mask

    # -- hybrid dataplane (ISSUE 18) ------------------------------------------

    def _hybrid_index(self, vec_name: str):
        """The vector index for ``vec_name`` iff it can run the fused
        device hybrid program (and the kill switch is off)."""
        if not self.device_hybrid:
            return None
        idx = self.vector_indexes.get(vec_name)
        if idx is None or not getattr(idx, "supports_device_hybrid",
                                      False):
            return None
        return idx

    def _hybrid_operand(self, idx, query: str, k: int, alpha: float,
                        fusion: str, properties, allow_mask):
        """Plan one hybrid query's sparse leg for device scoring:
        ``bm25_pack`` picks the candidate universe + per-segment
        operands, doc ids translate to store slots. None = this query
        can't ride the device path (no candidates, budget blown, or a
        candidate isn't resident in the vector index)."""
        from weaviate_tpu.ops.bm25 import SparseOperand, fusion_kind

        pack = self._inverted.bm25_pack(
            query, properties, allow_mask,
            max_candidates=self.hybrid_max_candidates)
        if pack is None:
            return None
        slots = idx.slots_for_doc_ids(pack["doc_ids"])
        if len(slots) == 0 or (slots < 0).any():
            # a candidate missing from the vector index would silently
            # vanish from the sparse leg — host fallback keeps recall
            return None
        return SparseOperand(
            pack["doc_ids"], slots, pack["seg_tf"], pack["seg_len"],
            pack["seg_term"], pack["seg_boost"], pack["seg_avg"],
            pack["idf"], pack["k1"], pack["b"], pack["one_minus_b"],
            float(alpha), fusion_kind(fusion),
            max(k * 10, 100),  # host reference over-fetch (collection.py)
            pack["stats"])

    def hybrid_search(self, query: str, vector, k: int = 10,
                      alpha: float = 0.75, fusion: str = "rankedFusion",
                      properties: list[str] | None = None,
                      vec_name: str = "",
                      allow_mask: np.ndarray | None = None):
        """Fused device hybrid (ISSUE 18): ONE batched device program
        runs the dense scan, BM25F-scores the packed sparse candidates,
        and merges the legs (RRF / relative-score) — no host scoring, no
        second dispatch. Single queries coalesce with concurrent vector
        and hybrid traffic through the shard's QueryBatcher. Returns
        (doc_ids, fused_scores) or None when the device path can't serve
        this query — callers then run the host reference path
        (text/hybrid.py)."""
        idx = self._hybrid_index(vec_name)
        if idx is None or vector is None:
            return None
        queue = self._index_queues.get(vec_name)
        if queue is not None and queue.snapshot():
            # queued (not-yet-indexed) vectors are invisible to the
            # device dense leg; the host path brute-forces that tail
            return None
        allow_mask = self._norm_allow(allow_mask)
        with tracing.span("shard.hybrid_search", shard=self.name, k=k,
                          filtered=allow_mask is not None):
            op = self._hybrid_operand(idx, query, k, alpha, fusion,
                                      properties, allow_mask)
            if op is None:
                return None
            from weaviate_tpu.runtime.query_batcher import \
                DeviceHybridUnavailable

            q = np.asarray(vector, np.float32)
            try:
                if self.dynamic_batching and q.ndim == 1:
                    b = self._query_batcher(vec_name, idx)
                    ids, dists = b.search(q, k, allow_mask, sparse=op)
                else:
                    h = idx.hybrid_batch_async(
                        np.atleast_2d(q), k,
                        [allow_mask] if allow_mask is not None else None,
                        [op])
                    if h is None:
                        return None
                    ids, dists = h.result()
                    ids, dists = ids[0], dists[0]
            except DeviceHybridUnavailable:
                return None
            ids = np.asarray(ids)[:k]
            dists = np.asarray(dists)[:k]
            live = ids >= 0
            # hybrid rows carry NEGATED fused scores on the distance
            # plane; flip back for the caller
            return (ids[live].astype(np.int64),
                    (-dists[live]).astype(np.float32))

    def hybrid_search_async(self, query: str, vector, k: int = 10,
                            alpha: float = 0.75,
                            fusion: str = "rankedFusion",
                            properties: list[str] | None = None,
                            vec_name: str = "",
                            allow_mask: np.ndarray | None = None):
        """Dispatch-only twin of ``hybrid_search``: returns a
        ``DeviceResultHandle`` resolving to the same (doc_ids,
        fused_scores), with the D2H draining on the TransferPipeline
        while the caller dispatches more work. None = host fallback
        (same conditions as the sync path)."""
        idx = self._hybrid_index(vec_name)
        if idx is None or vector is None:
            return None
        queue = self._index_queues.get(vec_name)
        if queue is not None and queue.snapshot():
            return None
        allow_mask = self._norm_allow(allow_mask)
        op = self._hybrid_operand(idx, query, k, alpha, fusion,
                                  properties, allow_mask)
        if op is None:
            return None
        q = np.atleast_2d(np.asarray(vector, np.float32))
        h = idx.hybrid_batch_async(
            q, k, [allow_mask] if allow_mask is not None else None, [op])
        if h is None:
            return None

        def _finish(res, _k=k):
            ids, dists = res
            ids = np.asarray(ids)[0][:_k]
            dists = np.asarray(dists)[0][:_k]
            live = ids >= 0
            return (ids[live].astype(np.int64),
                    (-dists[live]).astype(np.float32))

        return h.map(_finish)

    @property
    def doc_id_space(self) -> int:
        """Upper bound (exclusive) on doc ids ever assigned — the size of
        AllowList masks."""
        return self._counter

    def allow_mask(self, where) -> np.ndarray | None:
        """Filter tree → bool mask over this shard's doc-id space
        (reference: inverted.Searcher → helpers.AllowList). The one entry
        point of every filtered read, and the owner of its invariants:

        - **I1, isolation:** the mask equals what an evaluation under
          ``Shard._lock`` would return at an instant, between the call and
          its return, at which no write to the shard was in progress. The
          mask is BUILT outside the lock (leaf clauses from the inverted
          index's memo, ``InvertedIndex.leaf_mask``) and kept only if the
          write generation was even when the build began and has not
          moved when it ends: every section that mutates the inverted
          index or the doc-id space runs inside ``_writing``, so no
          such section overlapped the build. Otherwise (a write was in
          progress, or began meanwhile) the filter is evaluated again
          under the lock, as it always was (``result="locked"``). The
          inverted index's ``_version`` could not carry this alone: it
          moves once, at a mutation's END, and an update is an unindex and
          an index inside one section.
        - **I2, read your writes:** a write acknowledged before the call
          ended its section, dropping the memo, before the generation was
          read here: the mask holds it; a delete likewise.
        - **I3:** a memoised mask is shared and read-only
          (``flags.writeable`` False). No consumer writes to the mask it
          is handed; one that tried would raise.

        The span is the request's ``filter`` stage; ``leaf_hits`` /
        ``leaf_misses`` / ``locked`` on it are this call's leaf look-ups
        (``weaviate_tpu_filter_leaf_total{result}`` sums them)."""
        if where is None:
            return None
        from weaviate_tpu.filters import compute_allow_mask

        with tracing.span("shard.allow_mask", stage="filter",
                          shard=self.name) as sp:
            stats = LeafStats()
            gen = self._write_gen
            mask = None if gen & 1 else compute_allow_mask(
                where, self._inverted, self.doc_id_space, stats)
            locked = mask is None or self._write_gen != gen
            if locked:
                with self._lock:
                    mask = compute_allow_mask(where, self._inverted,
                                              self.doc_id_space, stats)
                filter_leaf_total.labels("locked").inc()
            if stats.hits:
                filter_leaf_total.labels("hit").inc(stats.hits)
            if stats.misses:
                filter_leaf_total.labels("miss").inc(stats.misses)
            sp.set(leaf_hits=stats.hits, leaf_misses=stats.misses,
                   locked=locked)
            return mask

    def set_read_only(self, value: bool) -> None:
        """Persisted so a restart keeps the freeze (reference persists
        shard status)."""
        with self._lock:
            self.read_only = bool(value)
            self.meta.put(b"read_only", bool(value))

    # -- epoch migration (db/collection.py orchestrates; see
    #    ARCHITECTURE.md "Epoch store") ---------------------------------------

    def _admit_device_bytes(self, nbytes: int) -> None:
        """Both admission gates, typed 507 on either: the device-global
        watermark (memwatch; compaction relieves it) and the per-shard
        quota (ledger bytes vs ``shard_hbm_limit``; epoch MIGRATION
        relieves it — the bytes move to a sibling's ledger scope)."""
        what = f"import {self.collection_name}/{self.name}"
        if self.memwatch is not None:
            self.memwatch.check_device_alloc(nbytes, what=what,
                                             device=self.device)
        if self.shard_hbm_limit and self.over_shard_limit(nbytes):
            from weaviate_tpu.runtime.hbm_ledger import ledger
            from weaviate_tpu.runtime.memwatch import \
                InsufficientMemoryError

            used = ledger.shard_bytes(self.collection_name, self.name)
            high = (self.memwatch.high_watermark
                    if self.memwatch is not None else 0.9)
            raise InsufficientMemoryError(
                f"device allocation of {nbytes} bytes ({what}) would "
                f"exceed {high:.0%} of shard HBM quota "
                f"{self.shard_hbm_limit} (ledger usage {used})",
                projected=used + int(nbytes),
                budget=self.shard_hbm_limit, source="ledger")

    def over_shard_limit(self, extra: int = 0) -> bool:
        """Is this shard's ledger footprint (+``extra``) past its quota
        watermark? The epoch policy migrates when this trips."""
        if not self.shard_hbm_limit:
            return False
        from weaviate_tpu.runtime.hbm_ledger import ledger

        high = (self.memwatch.high_watermark
                if self.memwatch is not None else 0.9)
        used = ledger.shard_bytes(self.collection_name, self.name)
        return used + int(extra) > self.shard_hbm_limit * high

    def migrated_to(self, uuid: str) -> str | None:
        """Destination shard of a migrated object, or None. The durable
        marker keeps uuid ring routing correct after an epoch moved its
        objects to a sibling; the in-memory count keeps this a no-op
        when no migration ever happened."""
        if self._migrated_count <= 0:
            return None
        v = self.meta.get(b"migrated:" + uuid.encode())
        if v is None:
            return None
        return v.decode() if isinstance(v, (bytes, bytearray)) else str(v)

    def clear_migrated(self, uuid: str) -> None:
        """Drop a routing override (the object was re-put or deleted at
        its ring home)."""
        with self._lock:
            if self.meta.get(b"migrated:" + uuid.encode()) is not None:
                self.meta.delete(b"migrated:" + uuid.encode())
                self._migrated_count = max(0, self._migrated_count - 1)

    def mark_migrating(self, uuids: list[str], dst_name: str) -> None:
        """Durably record the routing markers (one WAL frame) BEFORE
        the destination ingest: a kill anywhere after this point leaves
        every copy findable — GETs prefer the ring copy and follow the
        marker only on a miss, deletes/re-puts clean BOTH sides through
        the marker, search dedups by uuid. A marker pointing at a copy
        that never landed (kill before ingest) is harmless for the same
        reasons."""
        with self._lock:
            keys = [b"migrated:" + u.encode() for u in uuids]
            fresh = sum(1 for k in keys if self.meta.get(k) is None)
            self.meta.put_many([(k, dst_name) for k in keys])
            self._migrated_count += fresh  # re-marking an interrupted
            # move must not inflate the fast-path counter

    def migrate_out(self, uuids: list[str], dst_name: str) -> int:
        """Source-side cutover AFTER the destination acked the ingest
        (markers were written by ``mark_migrating`` before it): remove
        the objects — batched index tombstones, inverted unindex,
        docid/objects deletes. Crash ordering: a kill before this point
        leaves a double-present object (never a lost one, and the
        pre-ingest markers mean deletes reach both copies); after it,
        reads route through the markers to the destination."""
        with self._lock, self._writing():
            keys = [u.encode() for u in uuids]
            pairs = []
            for u, k in zip(uuids, keys):
                raw = self.docid.get(k)
                if raw is not None:
                    pairs.append((int(raw), u))
            if pairs:
                self._delete_docs_batch(pairs)
            self.docid.delete_many(keys)
            self.objects.delete_many(keys)
            return len(pairs)

    def epoch_maintenance(self, tick: bool = False) -> bool:
        """Run the epoch policy for every epoch-backed index on this
        shard: seal overfull actives, drop empty sealed epochs, fold
        tombstone-heavy ones (reclaims HBM through the ledger
        finalizers). Indexes exposing their own ``maintain`` hook (IVF
        delta fold / drift retrain, dynamic's deferred upgrade) get the
        same tick; ``tick`` says the call is the cyclemanager's (an IVF
        index then leaves a part-filled delta alone while writes keep
        arriving, engine/ivf.py). Returns True when work was done or is
        left for the next tick (cyclemanager backoff signal)."""
        did = False
        for idx in self.vector_indexes.values():
            es = getattr(idx, "epoch_store", None)
            if es is not None:
                did = es.maintain() or did
            idx_maintain = getattr(idx, "maintain", None)
            if idx_maintain is not None:
                did = bool(idx_maintain(tick=tick)) or did
        return did

    # -- replication support -------------------------------------------------

    STAGED_TTL_S = 120.0

    def stage(self, request_id: str, task: tuple) -> None:
        """2PC prepare: hold a write until commit/abort
        (reference: replica store staging before commit). A READONLY
        shard votes NO here — failing at prepare keeps all replicas
        consistent instead of silently diverging at commit."""
        import time as _time

        with self._lock:
            if self.read_only:
                raise ShardReadOnlyError(
                    f"shard {self.name!r} is read-only (status READONLY)")
            self._staged[request_id] = (_time.monotonic(), task)

    def gc_staged(self) -> int:
        """Drop staged batches whose coordinator never came back (crash
        between prepare and commit/abort) — anti-entropy re-delivers the
        write if it committed elsewhere. Every expiry is counted
        (``weaviate_tpu_replication_staged_expired_total``): an orphaned
        prepare must neither leak nor commit, and the counter is how a
        chaos run proves the TTL path actually fired."""
        import time as _time

        cutoff = _time.monotonic() - self.staged_ttl_s
        with self._lock:
            stale = [rid for rid, (t, _task) in self._staged.items()
                     if t < cutoff]
            for rid in stale:
                del self._staged[rid]
            self._staged_expired += len(stale)
        if stale:
            self._count_staged_expired(len(stale))
        return len(stale)

    def _count_staged_expired(self, n: int) -> None:
        try:
            from weaviate_tpu.runtime.metrics import (
                replication_staged_expired)

            replication_staged_expired.labels(
                self.collection_name, self.name).inc(n)
        except Exception:  # pragma: no cover — registry unavailable
            pass

    def commit_staged(self, request_id: str):
        """2PC commit. An entry past its TTL is REFUSED, not applied:
        without this, a commit that sat in flight across a partition
        (or a coordinator straggler thread racing the heal) could land
        a stale write long after the rest of the replica set aborted —
        the expiry has to be deterministic at the commit boundary, not
        dependent on whether the gc cycle happened to run first."""
        import time as _time

        with self._lock:
            entry = self._staged.pop(request_id, None)
            if entry is not None \
                    and _time.monotonic() - entry[0] > self.staged_ttl_s:
                self._staged_expired += 1
                self._count_staged_expired(1)
                raise StagedExpiredError(
                    f"replication request {request_id!r} staged "
                    f"{_time.monotonic() - entry[0]:.1f}s ago, past the "
                    f"{self.staged_ttl_s:.0f}s TTL — refused (late "
                    "commit after partition heal)")
        if entry is None:
            raise KeyError(f"unknown replication request {request_id!r}")
        _t, task = entry
        kind = task[0]
        if kind == "put":
            return self.put_object_batch(task[1])
        if kind == "delete":
            return self.delete_object(task[1], tombstone_ms=task[2])
        raise ValueError(f"unknown staged task kind {kind!r}")

    def staged_status(self) -> dict:
        """Introspection for the chaos checker's leak invariant: live
        staged entries (gc'd first so the answer is TTL-deterministic)
        and the total this shard ever expired."""
        self.gc_staged()
        with self._lock:
            return {"staged": len(self._staged),
                    "expired_total": self._staged_expired}

    def abort_staged(self, request_id: str) -> None:
        with self._lock:
            self._staged.pop(request_id, None)

    def object_digest(self, uuid: str) -> dict | None:
        """Replica-comparable digest (reference: Finder digest reads,
        repairer.go). None = never seen here."""
        raw = self.objects.get(uuid.encode())
        if raw is not None:
            obj = StorageObject.from_bytes(raw)
            return {"uuid": uuid, "mtime": obj.last_update_time_ms,
                    "deleted": False, "hash": obj.content_hash()}
        ts = self.tombstones.get(uuid.encode())
        if ts is not None:
            return {"uuid": uuid, "mtime": int(ts), "deleted": True,
                    "hash": b""}
        return None

    def iter_digests(self):
        with self._lock:
            uuids = list(self._doc_to_uuid.values())
            tombs = [(k.decode(), int(v)) for k, v in
                     ((k, self.tombstones.get(k)) for k in
                      self.tombstones.keys()) if v is not None]
        for uuid in uuids:
            d = self.object_digest(uuid)
            if d is not None and not d["deleted"]:
                yield d
        for uuid, ts in tombs:
            yield {"uuid": uuid, "mtime": ts, "deleted": True, "hash": b""}

    def build_hashtree(self, depth: int = 8):
        """Merkle tree over all digests (reference: shard hashtree kept
        by the hashbeater; we rebuild per beat — object counts per shard
        make this cheap relative to the network round-trips saved)."""
        from weaviate_tpu.replication.hashtree import MerkleTree

        tree = MerkleTree(depth)
        for d in self.iter_digests():
            tree.insert(d["uuid"], d["mtime"], d["deleted"], d["hash"])
        return tree

    def bucket_digests(self, depth: int, buckets: list[int]) -> list[dict]:
        """Digest entries falling into the given hashtree leaf buckets."""
        from weaviate_tpu.replication.hashtree import MerkleTree

        want = set(buckets)
        return [d for d in self.iter_digests()
                if MerkleTree.bucket_of(d["uuid"], depth) in want]

    def apply_sync(self, raw_objects: list[bytes],
                   deletes: list[dict]) -> int:
        """Apply newer peer state (anti-entropy propagation). Winner per
        uuid decided by digest_rank (mtime, tombstone-beats-object,
        content-hash tie-break)."""
        from weaviate_tpu.replication.hashtree import digest_rank

        applied = 0
        with self._lock:
            for raw in raw_objects:
                obj = StorageObject.from_bytes(raw)
                if self.migrated_to(obj.uuid):
                    # the durable cutover moved this uuid to its marker
                    # destination: re-applying a peer's (stale) copy here
                    # would resurrect the moved-away object at its old
                    # ring home — double-present to search, and the next
                    # hashbeat would propagate the zombie back out.
                    # Anti-entropy must respect the marker like reads do.
                    logger.debug("apply_sync: skipping %s — migrated to "
                                 "%s", obj.uuid, self.migrated_to(obj.uuid))
                    continue
                mine = self.object_digest(obj.uuid)
                incoming = {"mtime": obj.last_update_time_ms,
                            "deleted": False, "hash": obj.content_hash()}
                if mine is not None and digest_rank(mine) >= digest_rank(incoming):
                    continue
                obj.doc_id = 0  # re-assigned locally
                self.put_object_batch([obj])
                applied += 1
            for d in deletes:
                mine = self.object_digest(d["uuid"])
                incoming = {"mtime": d["mtime"], "deleted": True, "hash": b""}
                if mine is None:
                    # never saw it: record the tombstone so our tree converges
                    self.tombstones.put(d["uuid"].encode(), d["mtime"])
                    applied += 1
                    continue
                if digest_rank(mine) >= digest_rank(incoming):
                    continue
                if mine["deleted"]:
                    self.tombstones.put(d["uuid"].encode(), d["mtime"])
                else:
                    self.delete_object(d["uuid"], tombstone_ms=d["mtime"])
                applied += 1
        return applied

    # -- maintenance ---------------------------------------------------------

    def flush(self):
        for name, q in self._index_queues.items():
            if not q.wait_idle(timeout=30.0):
                logger.warning(
                    "shard %s/%s: index queue %r still has %d queued "
                    "vectors after 30s — flush() returns with the vector "
                    "index lagging the object store",
                    self.collection_name, self.name, name, q.size())
        for b in (self.objects, self.docid, self.meta):
            b.flush()

    def maintenance(self, compact_above: int = 4) -> bool:
        """One background cycle: flush dirty memtables, compact segment
        stacks past the threshold (reference: store_cyclecallbacks.go).
        Returns True when work was done (cyclemanager backoff signal)."""
        from weaviate_tpu.runtime.metrics import (
            lsm_segment_count, vector_index_compressed,
            vector_index_hbm_bytes, vector_index_tombstones)

        did = False
        if self.gc_staged():
            did = True
        for b in self.store.buckets():
            # sealed-memtable flush + threshold compaction, all off the
            # write path (reference: store_cyclecallbacks.go)
            if b.maintain(compact_above=compact_above):
                did = True
            lsm_segment_count.labels(f"{self.collection_name}/{self.name}/{b.name}"
                                     ).set(b.segment_count)
        for vec_name, idx in self.vector_indexes.items():
            if idx is None:
                continue
            labels = (self.collection_name, self.name, vec_name or "default")
            store = getattr(idx, "store", None)
            device = placement.label(getattr(idx, "device", None))
            live = len(idx)
            total = getattr(store, "count", live) if store is not None                 else getattr(idx, "_count", live)
            vector_index_tombstones.labels(*labels).set(max(total - live, 0))
            vector_index_compressed.labels(*labels).set(
                1 if getattr(idx, "compressed", False) else 0)
            hbm = 0
            stores = ([ep.store for ep in store.epochs]
                      if getattr(idx, "epoch_store", None) is not None
                      else [store])
            for st in stores:
                for arr_name in ("vectors", "valid", "sq_norms", "codes",
                                 "rescore_rows", "list_vecs", "list_codes",
                                 "list_valid", "list_slots", "list_norms"):
                    arr = getattr(st, arr_name, None)
                    if arr is not None and hasattr(arr, "nbytes"):
                        hbm += int(arr.nbytes)
            vector_index_hbm_bytes.labels(*labels, device).set(hbm)
        return did

    def close(self):
        from weaviate_tpu.runtime import driftwatch

        driftwatch.unregister_canaries(
            f"{self.collection_name}/{self.name}/")
        for q in self._index_queues.values():
            q.stop()
        for b in self._query_batchers.values():
            b.stop()
        self.store.close()
        self._release_device()
