"""Collection: shard routing + scatter-gather queries.

Reference: adapters/repos/db/index.go (Index struct :156) — putObject routes
by sharding state (:637), objectVectorSearch scatter-gathers across shards
and merges by distance (:1541-1663). Multi-tenant collections address one
shard per tenant.
"""

from __future__ import annotations

import functools
import heapq
import logging
import os
import threading
import time
import uuid as uuid_mod
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from weaviate_tpu.db.drain import CollectionDrain
from weaviate_tpu.db.shard import Shard
from weaviate_tpu.db.sharding import ShardingState
from weaviate_tpu.runtime import degrade
from weaviate_tpu.runtime import metrics as monitoring
from weaviate_tpu.runtime import tailboard, tracing
from weaviate_tpu.runtime.query_batcher import BatcherStopped
from weaviate_tpu.schema.config import CollectionConfig
from weaviate_tpu.storage.objects import StorageObject

logger = logging.getLogger(__name__)


class SearchResult:
    """One hit. ``frame`` is its object as stored (``StorageObject.
    to_bytes``), read AFTER the search; ``object`` decodes it at the
    first read by whoever reads one (rerank, group-by, REST / GraphQL,
    the gRPC reply's Python path), once. A plain gRPC Search's reply is
    encoded from the frames and decodes none (api/grpc/server.py)."""

    __slots__ = ("uuid", "distance", "score", "frame", "_object", "shard",
                 "rerank_score")

    def __init__(self, uuid, distance=None, score=None, object=None,
                 shard=None, frame=None):
        self.uuid = uuid
        self.distance = distance
        self.score = score
        self._object = object
        self.frame = frame
        self.shard = shard
        self.rerank_score = None  # set by the reranker module path

    @property
    def object(self) -> StorageObject | None:
        obj = self._object
        if obj is None and self.frame is not None:
            # two threads may both decode: the objects are equal, one stays
            obj = self._object = StorageObject.from_bytes(self.frame)
        return obj

    @object.setter
    def object(self, obj: StorageObject | None) -> None:
        self._object, self.frame = obj, None

    @property
    def attached(self) -> bool:
        """Whether the object was read already, decoded or not."""
        return self.frame is not None or self._object is not None

    def __repr__(self):
        return f"SearchResult({self.uuid}, dist={self.distance}, score={self.score})"


def _remote_result(item: dict, shard_name: str) -> "SearchResult":
    return SearchResult(
        uuid=item["uuid"], distance=item.get("distance"),
        score=item.get("score"), shard=shard_name,
        frame=item.get("object") or None)


def _timed(query_type: str):
    """Record query latency per collection (reference: monitoring
    query-duration metric vecs, usecases/monitoring/prometheus.go) and
    log queries slower than the configured threshold (parsed once in
    runtime/tracing.py — one source for QUERY_SLOW_LOG_*)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, **kwargs):
            t0 = time.perf_counter()
            with monitoring.query_duration.labels(self.config.name,
                                                  query_type).time(), \
                    tracing.span(f"query.{query_type}",
                                 collection=self.config.name):
                out = fn(self, *args, **kwargs)
            threshold = tracing.get_slow_threshold()
            # inside a trace the ROOT logs slow queries with the full
            # span breakdown — logging here too would double-report
            if threshold > 0 and not tracing.is_active():
                took = time.perf_counter() - t0
                if took >= threshold:
                    import logging

                    logging.getLogger("weaviate_tpu.slow_query").warning(
                        "slow %s query on %s: %.3fs (threshold %.3fs)",
                        query_type, self.config.name, took, threshold)
            return out

        return wrapper

    return deco


class _ShardHits:
    """A local shard's answer to one fanned-out search, as arrays:
    ``[pos]`` builds the result of one position (None where the object
    has been deleted since), so that a merge pays for its winners."""

    __slots__ = ("name", "shard", "ids", "dists")

    def __init__(self, name: str, shard, ids, dists):
        self.name, self.shard, self.ids, self.dists = name, shard, ids, dists

    def __len__(self) -> int:
        return len(self.ids)

    def __getitem__(self, pos: int):
        uuid = self.shard._doc_to_uuid.get(int(self.ids[pos]))
        return None if uuid is None else SearchResult(
            uuid=uuid, distance=float(self.dists[pos]), shard=self.name)


class Collection:
    def __init__(self, data_dir: str, config: CollectionConfig,
                 sharding_state: ShardingState | None = None, mesh=None,
                 local_node: str = "node-0", on_sharding_change=None,
                 memwatch=None, remote=None, nodes_provider=None,
                 async_indexing: bool | None = None,
                 sync_wal: bool | None = None,
                 node_hbm_provider=None):
        config.validate()
        if mesh is not None and any(v.index.quantization == "sq"
                                    for v in config.vectors):
            # refused at creation, not at the swap: the sq store has no
            # mesh-sharded scan (engine/quantized.py)
            raise ValueError("sq is not supported on a mesh-sharded "
                             "database")
        self.config = config
        self.data_dir = data_dir
        self.mesh = mesh
        self.local_node = local_node
        self.memwatch = memwatch
        self.async_indexing = async_indexing  # None = shard reads the env
        self.sync_wal = sync_wal  # None = shard reads PERSISTENCE_WAL_SYNC
        # cross-node data plane (reference: Index holds a
        # sharding.RemoteIndexClient for non-local shards, index.go:1607)
        self.remote = remote
        self._nodes_provider = nodes_provider or (lambda: [local_node])
        # node -> HBM ledger bytes (gossiped meta in a cluster); feeds
        # ledger-driven placement + the cross-node epoch migration
        # target choice. None = only the local ledger is known.
        self._node_hbm_provider = node_hbm_provider
        # cluster hook fn(collection_name, [tenant]) routing auto tenant
        # creation through Raft; None = apply locally (single node)
        self._auto_tenant_hook = None
        # FROZEN-tier offload target (a backup backend); set by Database
        self.offload_backend = None
        self._lock = threading.RLock()
        # reentrancy guard for the epoch memory-pressure rescue (a
        # migration's target-side ingest runs admission too)
        self._rescue_tls = threading.local()
        # at most ONE epoch migration in flight per collection: the
        # mover holds the SOURCE shard's lock across ingest + cutover
        # (so concurrent writes to the moving uuids can't be lost), and
        # serializing migrations means only one thread ever nests two
        # shard locks — no ABBA ordering can arise. RLock: a rescue
        # fired from a migration's own target-side admission re-enters.
        self._migrate_lock = threading.RLock()
        # Sharded per-uuid write locks for read-modify-write flows
        # (reference appends, PATCH) — the RMW must be atomic per object but
        # must not hold the collection-wide lock across a replicated put,
        # where one slow replica's 2PC RPC would block every unrelated
        # request (reference analog: vector/common/sharded_locks.go).
        self._uuid_locks = [threading.RLock() for _ in range(64)]
        if sharding_state is None:
            if config.multi_tenancy.enabled:
                sharding_state = ShardingState.create_partitioned()
            else:
                # ledger-driven placement (ROADMAP item 2): round-robin
                # starts at the node with the most HBM headroom, so a
                # new collection's shards land on light nodes first
                sharding_state = ShardingState.create(
                    config.sharding.desired_count,
                    nodes=self._placement_nodes(),
                    replication_factor=config.replication.factor,
                )
        self.sharding = sharding_state
        # persistence hook: auto-created tenants must reach the schema store
        # or they vanish from sharding state on restart
        self._on_sharding_change = on_sharding_change or (lambda col: None)
        self.shards: dict[str, Shard] = {}
        # vector space -> the ONE batcher over this collection's local
        # shards (db/drain.py), built by the first request that takes it
        self._drains: dict[str, CollectionDrain] = {}
        for name in self.sharding.shard_names:
            if self.local_node in self.sharding.nodes_for(name) and \
                    self.sharding.status_of(name) not in ("COLD", "FROZEN"):
                self._load_shard(name)  # COLD/FROZEN tenants stay unloaded
        self._pool = ThreadPoolExecutor(max_workers=8,
                                        thread_name_prefix=f"{config.name}-search")
        # hot/cold tenant tracking (reference: entities/tenantactivity +
        # rest/tenantactivity/handler.go): tenant -> last access stamps
        self.tenant_activity: dict[str, dict] = {}

    def uuid_lock(self, uuid: str) -> threading.RLock:
        """Lock guarding read-modify-write of one object (sharded by uuid
        hash; collisions just serialize two unrelated RMWs, never deadlock
        since callers take at most one)."""
        return self._uuid_locks[hash(uuid) % len(self._uuid_locks)]

    def _record_tenant(self, tenant: str | None, kind: str) -> None:
        if not tenant or not self.config.multi_tenancy.enabled:
            return
        now = time.time()
        with self._lock:  # REST reads snapshot under the same lock
            entry = self.tenant_activity.setdefault(
                tenant, {"reads": 0, "writes": 0, "lastRead": None,
                         "lastWrite": None})
            if kind == "read":
                entry["reads"] += 1
                entry["lastRead"] = now
            else:
                entry["writes"] += 1
                entry["lastWrite"] = now

    def tenant_activity_snapshot(self) -> dict:
        with self._lock:
            return {t: dict(v) for t, v in self.tenant_activity.items()}

    def apply_runtime_config(self) -> None:
        """Propagate runtime-mutable config (reference: UpdateUserConfig →
        hnsw/config_update.go) into LIVE shard objects, which copied
        config values at construction: BM25 k1/b and per-index search
        knobs (ef / nprobe / rescore / upgrade threshold)."""
        with self._lock:
            shards = list(self.shards.values())
        for shard in shards:
            inv = shard._inverted
            inv.k1 = self.config.inverted.bm25_k1
            inv.b = self.config.inverted.bm25_b
            for vec_name, idx in shard.vector_indexes.items():
                vc = self.config.vector_config(vec_name)
                if idx is None or vc is None:
                    continue
                for attr, value in (
                    ("ef", vc.index.ef),
                    ("rescore_limit", vc.index.rescore_limit),
                    ("nprobe", vc.index.ivf_nprobe),
                    ("threshold", vc.index.flat_to_ann_threshold),
                    # upstream's flatSearchCutoff under its two names
                    # here: the IVF / dynamic index's and the graph's
                    ("flat_search_cutoff", vc.index.flat_search_cutoff),
                    ("flat_cutoff", vc.index.flat_search_cutoff),
                ):
                    # 0 is meaningful (= auto); only skip absent values
                    if hasattr(idx, attr) and value is not None:
                        setattr(idx, attr, value)
                # runtime compression enable (compress.go:38): the
                # config sticks, and the shard's gate decides when. A pq
                # class below pq.trainingLimit defers; the import that
                # crosses the limit, or a restart above it, compresses.
                shard._maybe_compress(vec_name, idx)

    # -- shard management ----------------------------------------------------

    def _load_shard(self, name: str) -> Shard:
        # check-then-insert under the lock: two concurrent writers must not
        # construct two Shard objects (two WALs, two doc counters) for the
        # same on-disk shard
        with self._lock:
            if name not in self.shards:
                shard = Shard(
                    self.data_dir, self.config, name, mesh=self.mesh,
                    memwatch=self.memwatch,
                    async_indexing=self.async_indexing,
                    sync_wal=self.sync_wal)
                # admission rescue: compact tombstone-heavy epochs,
                # then migrate the coldest sealed epoch to a sibling
                # with headroom, BEFORE a 507 latches (epoch policy)
                shard.memory_rescue = (
                    lambda s=shard: self._rescue_shard(s))
                self.shards[name] = shard
            return self.shards[name]

    def _require_active(self, tenant: str) -> None:
        """COLD/FROZEN tenants reject access unless auto-activation is on
        (reference: tenant activityStatus + autoTenantActivation)."""
        status = self.sharding.status_of(tenant)
        if status in ("COLD", "FROZEN"):
            if self.config.multi_tenancy.auto_tenant_activation:
                self.set_tenant_status(tenant, "HOT")
            else:
                raise ValueError(
                    f"tenant {tenant!r} is not active (activityStatus "
                    f"{status}); activate it or enable "
                    "autoTenantActivation")

    def _check_tenant(self, tenant: str | None, kind: str = "read") -> None:
        if self.config.multi_tenancy.enabled:
            if not tenant:
                raise ValueError("multi-tenant collection requires a tenant")
            if tenant not in self.sharding.shard_names:
                raise KeyError(f"tenant {tenant!r} does not exist")
            self._require_active(tenant)
            self._record_tenant(tenant, kind)

    def _ensure_tenant_shard(self, tenant: str | None) -> None:
        if not self.config.multi_tenancy.enabled:
            return
        with self._lock:
            if tenant in self.sharding.shard_names:
                self._require_active(tenant)
                self._record_tenant(tenant, "write")
                return
            if not self.config.multi_tenancy.auto_tenant_creation:
                raise KeyError(f"tenant {tenant!r} does not exist")
            hook = self._auto_tenant_hook
            if hook is None:
                self.sharding.add_tenant(
                    tenant, nodes=self._nodes_provider(),
                    replication_factor=self.config.replication.factor)
                self._on_sharding_change(self)
                self._record_tenant(tenant, "write")
                return
        # cluster mode: tenant creation must go through Raft so every node
        # applies the same placement — a local-only mutation would diverge
        # from the replica that has to accept the write. Called OUTSIDE the
        # collection lock: the FSM apply (another thread on followers)
        # needs that lock to install the tenant.
        hook(self.config.name, [tenant])
        if tenant not in self.sharding.shard_names:
            raise RuntimeError(f"auto tenant creation for {tenant!r} did "
                               "not converge")
        self._record_tenant(tenant, "write")

    def _reported_hbm(self) -> dict:
        """The hbm provider's reading (gossiped ``hbmBytes`` meta in a
        cluster), {} when no provider is wired or it fails — stale
        gossip must never fail collection creation or migration."""
        if self._node_hbm_provider is None:
            return {}
        try:
            return {str(k): int(v) for k, v in
                    dict(self._node_hbm_provider()).items()}
        except Exception:  # noqa: BLE001
            return {}

    def _node_hbm_bytes(self, reported: dict | None = None) -> dict:
        """node -> known HBM ledger bytes. The local node always reads
        its own ledger (authoritative); other nodes come from the
        provider reading (pass ``reported`` to reuse one already
        fetched), defaulting to 0 — an unknown node is assumed empty,
        which keeps single-node behavior identical to the
        pre-placement code."""
        from weaviate_tpu.runtime.hbm_ledger import ledger

        out = dict(reported) if reported is not None \
            else self._reported_hbm()
        out[self.local_node] = ledger.total_bytes()
        return out

    def _placement_nodes(self) -> list[str]:
        """Candidate nodes ordered by HBM headroom (lightest ledger
        first; sort is stable so equally-loaded nodes keep the
        provider's order). ShardingState.create round-robins shards
        from index 0, so the lightest node receives the first shard(s)
        of every new collection.

        Ranking engages only when at least one PEER (a node other than
        this one) has actually reported through the hbm provider
        (gossip in a cluster): with no peer information, the provider's
        order stands — the gossip view always contains this node's own
        reading, and comparing the local live ledger against
        unreported-as-zero peers would spuriously demote the local node
        on every non-empty process."""
        nodes = list(self._nodes_provider())
        reported = self._reported_hbm()
        if not any(n != self.local_node for n in reported):
            return nodes
        hbm = self._node_hbm_bytes(reported)
        return sorted(nodes, key=lambda n: hbm.get(n, 0))

    def _require_remote(self, shard_name: str):
        if self.remote is None:
            raise RuntimeError(
                f"shard {shard_name!r} is placed on "
                f"{self.sharding.nodes_for(shard_name)} but node "
                f"{self.local_node!r} has no remote client configured")
        return self.remote

    def _is_local(self, shard_name: str) -> bool:
        return self.local_node in self.sharding.nodes_for(shard_name)

    def _read_node(self, shard_name: str) -> str:
        """Preferred replica for a read: local if we own it, else the
        first placed node (reference: Finder picks the local/first
        replica for direct reads)."""
        if self._is_local(shard_name):
            return self.local_node
        return self.sharding.nodes_for(shard_name)[0]

    def _remote_search_degraded(self, shard_name: str, **kwargs):
        """Remote-shard scatter leg with replica failover and graceful
        degradation: try each placed replica in read-preference order
        (the per-peer circuit breaker makes a known-dead node cost ~0
        deadline budget); when every replica is unreachable, return
        ``None`` — the shard contributes NOTHING, the query still
        answers, and an explicit ``missing_shard`` marker rides the
        response (surfaced by the REST edge + the degraded counter)
        instead of the whole-query failure a single dead replica used
        to cause."""
        from weaviate_tpu.cluster.transport import RpcError

        remote = self._require_remote(shard_name)
        nodes = [n for n in self.sharding.nodes_for(shard_name)
                 if n != self.local_node]
        last: Exception | None = None
        for i, node in enumerate(nodes):
            try:
                items = remote.search_shard(node, self.config.name,
                                            shard_name, **kwargs)
            except RpcError as e:
                last = e
                # NOT a degraded marker: if a later replica serves, the
                # answer is complete — failover is an implementation
                # detail, and marking it partial would make clients
                # distrust full results
                import logging

                logging.getLogger(__name__).warning(
                    "replica %s failed for %s/%s, failing over: %s",
                    node, self.config.name, shard_name, e)
                continue
            return items
        degrade.report("missing_shard", collection=self.config.name,
                       shard=shard_name,
                       detail=str(last) if last is not None
                       else "no reachable replica")
        return None

    def _target_shard_names(self, tenant: str | None,
                            kind: str = "read") -> list[str]:
        if self.config.multi_tenancy.enabled:
            if not tenant:
                raise ValueError("multi-tenant collection requires a tenant")
            if tenant not in self.sharding.shard_names:
                raise KeyError(f"tenant {tenant!r} does not exist")
            self._require_active(tenant)
            self._record_tenant(tenant, kind)
            return [tenant]
        return list(self.sharding.shard_names)

    def _target_shards(self, tenant: str | None) -> list[Shard]:
        """LOCAL shards addressed by a query (all shards on a single
        node; the locally-placed subset in a cluster)."""
        return [self._load_shard(n) for n in self._target_shard_names(tenant)
                if self._is_local(n)]

    # -- tenants -------------------------------------------------------------

    def add_tenant(self, tenant: str, nodes: list[str] | None = None):
        with self._lock:
            self.sharding.add_tenant(
                tenant, nodes=nodes or self._nodes_provider(),
                replication_factor=self.config.replication.factor)
            if self._is_local(tenant):
                self._load_shard(tenant)
            self._on_sharding_change(self)

    def remove_tenant(self, tenant: str):
        with self._lock:
            shard = self.shards.pop(tenant, None)
            if shard is not None:
                shard.close()
            self.sharding.remove_tenant(tenant)

    def tenants(self) -> list[str]:
        return list(self.sharding.shard_names) if self.config.multi_tenancy.enabled else []

    def set_tenant_status(self, tenant: str, status: str) -> None:
        """HOT/COLD/FROZEN tenant offload (reference: PUT tenants with
        activityStatus; COLD unloads the shard from memory/HBM, files
        stay on disk; FROZEN ships the files to the offload backend and
        removes them locally — entities/tenantactivity + offload
        modules; HOT loads it back)."""
        status = status.upper()
        if status not in ("HOT", "COLD", "FROZEN"):
            raise ValueError(
                "tenant activityStatus must be HOT, COLD or FROZEN")
        if tenant not in self.sharding.shard_names:
            raise KeyError(f"tenant {tenant!r} does not exist")
        with self._lock:
            prev = self.sharding.status_of(tenant)
            if status == prev:
                return
            # the side effect runs BEFORE the status commits: a failed
            # freeze/thaw (no offload backend, backend error) leaves the
            # tenant in its previous, working state instead of wedged
            if prev == "FROZEN" and status in ("HOT", "COLD"):
                self._unfreeze_tenant(tenant)
            if status == "FROZEN":
                self._freeze_tenant(tenant)
            elif status == "COLD":
                shard = self.shards.pop(tenant, None)
                if shard is not None:
                    shard.close()
            elif self._is_local(tenant):
                self._load_shard(tenant)
            self.sharding.tenant_status[tenant] = status
            self._on_sharding_change(self)

    def _offload_backend(self):
        backend = self.offload_backend
        if backend is None:
            raise RuntimeError(
                "FROZEN tenants need an offload backend: configure a "
                "backup module and OFFLOAD_BACKEND (reference: offload-s3 "
                "module + tenant activityStatus FROZEN)")
        return backend

    def _offload_id(self, tenant: str) -> str:
        return f"tenant-offload--{self.config.name}--{tenant}"

    def _freeze_tenant(self, tenant: str) -> None:
        """Stream the tenant's shard files to the offload backend, then
        delete them locally (reference: FROZEN tier — local resources are
        released entirely; files live in cloud storage)."""
        import json as _json
        import shutil as _shutil

        from weaviate_tpu.backup.cluster import put_file_compressed
        from weaviate_tpu.modules.backup_backends import walk_files

        backend = self._offload_backend()
        shard = self.shards.pop(tenant, None)
        if shard is not None:
            shard.flush()
            shard.close()
        sh_dir = os.path.join(self.data_dir, self.config.name, tenant)
        oid = self._offload_id(tenant)
        backend.initialize(oid)
        # an empty tenant still gets a manifest — thawing must always find
        # one (a manifest-less freeze would wedge the tenant FROZEN)
        stored = [put_file_compressed(backend, oid, rel,
                                      os.path.join(sh_dir, rel))
                  for rel in (walk_files(sh_dir)
                              if os.path.isdir(sh_dir) else [])]
        backend.put(oid, "manifest.json",
                    _json.dumps({"files": stored}).encode())
        _shutil.rmtree(sh_dir, ignore_errors=True)

    def _unfreeze_tenant(self, tenant: str) -> None:
        import json as _json

        from weaviate_tpu.backup.cluster import (get_file_decompressed,
                                                 logical_name)

        backend = self._offload_backend()
        oid = self._offload_id(tenant)
        try:
            manifest = _json.loads(backend.get(oid, "manifest.json"))
        except KeyError:
            # tenant frozen by a pre-manifest version or never offloaded
            # data — nothing to pull back
            manifest = {"files": []}
        sh_dir = os.path.abspath(
            os.path.join(self.data_dir, self.config.name, tenant))
        for stored in manifest.get("files", []):
            dst = os.path.abspath(
                os.path.join(sh_dir, logical_name(stored)))
            if not dst.startswith(sh_dir + os.sep):
                raise ValueError(
                    f"offload manifest path {stored!r} escapes the shard")
            get_file_decompressed(backend, oid, stored, dst)

    # -- object CRUD ---------------------------------------------------------

    def _write_to_shard(self, shard_name: str, objs: list[StorageObject],
                        consistency: str = "QUORUM") -> None:
        """Write a batch to the shard's replicas. Replicated shards take
        the 2PC coordinator (reference: replica.Replicator, replicator.go:57);
        single-replica shards write directly (index.go:922)."""
        nodes = self.sharding.nodes_for(shard_name)
        if len(nodes) > 1:
            from weaviate_tpu.replication import Replicator

            Replicator(self).put_objects(shard_name, objs, consistency)
            return
        node = nodes[0]
        if node == self.local_node:
            shard = self._load_shard(shard_name)
            shard.put_object_batch(objs)
            # clean any migrated sibling copy AFTER the fresh write
            # landed: a 507/crash before the write must leave the old
            # copy intact (double-present is deduped; lost is lost)
            self._unmigrate(shard, objs)
        else:
            self._require_remote(shard_name).put_objects(
                node, self.config.name, shard_name,
                [o.to_bytes() for o in objs])

    def put_object(self, properties: dict, vector=None, vectors: dict | None = None,
                   uuid: str | None = None, tenant: str | None = None,
                   consistency: str = "QUORUM", creation_time_ms: int = 0) -> str:
        """``creation_time_ms``: carried through on updates so a re-put keeps
        the original creation stamp (reference merge semantics)."""
        uuid = uuid or str(uuid_mod.uuid4())
        obj = StorageObject(uuid=uuid, properties=properties,
                            creation_time_ms=creation_time_ms)
        if creation_time_ms:
            # an update keeps its creation stamp but is "touched" now
            obj.last_update_time_ms = int(time.time() * 1000)
        if vector is not None:
            obj.vector = np.asarray(vector, dtype=np.float32)
        for name, vec in (vectors or {}).items():
            obj.vectors[name] = np.asarray(vec, dtype=np.float32)
        if self.config.multi_tenancy.enabled:
            self._ensure_tenant_shard(tenant)
        shard_name = self.sharding.shard_for(uuid, tenant)
        self._write_to_shard(shard_name, [obj], consistency)
        monitoring.objects_total.labels(self.config.name, "put").inc()
        return uuid

    def batch_put(self, objects: list[dict], tenant: str | None = None,
                  consistency: str = "QUORUM") -> list[dict]:
        """Batch import; per-object error reporting, not transactional
        (reference: usecases/objects/batch_add.go)."""
        results = []
        by_shard: dict[str, list[StorageObject]] = {}
        metas: dict[str, list[int]] = {}
        for i, spec in enumerate(objects):
            try:
                uid = spec.get("uuid") or str(uuid_mod.uuid4())
                obj = StorageObject(uuid=uid,
                                    properties=spec.get("properties", {}))
                if spec.get("vector") is not None:
                    obj.vector = np.asarray(spec["vector"], dtype=np.float32)
                for name, vec in (spec.get("vectors") or {}).items():
                    obj.vectors[name] = np.asarray(vec, dtype=np.float32)
                shard_name = self.sharding.shard_for(uid, tenant)
                by_shard.setdefault(shard_name, []).append(obj)
                metas.setdefault(shard_name, []).append(i)
                results.append({"uuid": uid, "status": "SUCCESS"})
            except Exception as e:  # per-object failure, keep going
                results.append({"uuid": spec.get("uuid"), "status": "FAILED",
                                "error": str(e)})
        for shard_name, objs in by_shard.items():
            try:
                if self.config.multi_tenancy.enabled:
                    self._ensure_tenant_shard(shard_name)
                self._write_to_shard(shard_name, objs, consistency)
                monitoring.objects_total.labels(self.config.name, "put"
                                                ).inc(len(objs))
            except MemoryError:
                # admission rejection (memwatch watermark) must surface
                # as the typed 507 at the API layer, not dissolve into
                # per-object FAILED entries under an HTTP 200 — bulk
                # import is the path capacity gating exists for
                raise
            except Exception as e:
                for i in metas[shard_name]:
                    results[i] = {"uuid": results[i]["uuid"], "status": "FAILED",
                                  "error": str(e)}
        return results

    def get_object(self, uuid: str, tenant: str | None = None,
                   consistency: str | None = None) -> StorageObject | None:
        """``consistency``: None = direct read from the preferred replica;
        a level (ONE/QUORUM/ALL) = digest-compared read with read repair
        (reference: Finder.Pull, coordinator.go:178)."""
        self._check_tenant(tenant)
        name = self.sharding.shard_for(uuid, tenant)
        if consistency is not None and len(self.sharding.nodes_for(name)) > 1:
            from weaviate_tpu.replication import Finder

            return Finder(self).get_object(uuid, name, consistency)
        if self._is_local(name):
            shard = self._load_shard(name)
            obj = shard.get_object(uuid)
            if obj is None:
                # epoch migration moved this object to a sibling: the
                # durable marker keeps ring routing correct (the
                # sibling may live on another NODE after a cross-node
                # epoch move)
                dst = shard.migrated_to(uuid)
                if dst and dst != name:
                    if self._is_local(dst):
                        return self._load_shard(dst).get_object(uuid)
                    if self.remote is not None:
                        raw = self._require_remote(dst).get_object(
                            self._read_node(dst), self.config.name,
                            dst, uuid)
                        if raw is not None:
                            return StorageObject.from_bytes(raw)
            return obj
        raw = self._require_remote(name).get_object(
            self._read_node(name), self.config.name, name, uuid)
        return None if raw is None else StorageObject.from_bytes(raw)

    def delete_object(self, uuid: str, tenant: str | None = None,
                      consistency: str = "QUORUM") -> bool:
        self._check_tenant(tenant, kind="write")  # deletes are writes
        name = self.sharding.shard_for(uuid, tenant)
        nodes = self.sharding.nodes_for(name)
        if len(nodes) > 1:
            from weaviate_tpu.replication import Replicator

            ok = Replicator(self).delete(name, uuid, consistency)
        elif nodes[0] == self.local_node:
            shard = self._load_shard(name)
            ok = shard.delete_object(uuid)
            # a migrated copy (or the transient double-present crash
            # window) lives at the marker's destination — delete it too
            # so exactly zero copies remain, and drop the marker
            # (cross-node moves route the delete over the shard RPC)
            dst = shard.migrated_to(uuid)
            if dst and dst != name:
                if self._is_local(dst):
                    ok = self._load_shard(dst).delete_object(uuid) or ok
                elif self.remote is not None:
                    ok = self._require_remote(dst).delete_object(
                        self._read_node(dst), self.config.name, dst,
                        uuid) or ok
            if dst:
                shard.clear_migrated(uuid)
        else:
            ok = self._require_remote(name).delete_object(
                nodes[0], self.config.name, name, uuid)
        if ok:
            monitoring.objects_total.labels(self.config.name, "delete").inc()
        return ok

    def batch_delete(self, where, tenant: str | None = None,
                     dry_run: bool = False, verbose: bool = False,
                     consistency: str = "QUORUM",
                     max_matches: int = 10_000) -> dict:
        """Delete all objects matching a filter (reference: batch_delete —
        REST DELETE /v1/batch/objects and gRPC BatchDelete; match set capped
        at QUERY_MAXIMUM_RESULTS like the reference's dryRun/match cap).
        Returns {"matches", "successful", "failed", "objects": [...]}, where
        ``objects`` is populated per-uuid only when ``verbose``."""
        names = self._target_shard_names(tenant, kind="write")
        where_dict = where.to_dict() if where is not None else None
        uuids: list[str] = []
        for name in names:
            if len(uuids) >= max_matches:
                break
            if self._is_local(name):
                shard = self._load_shard(name)
                mask = shard.allow_mask(where) if where is not None else None
                with shard._lock:
                    items = list(shard._doc_to_uuid.items())
                for doc_id, uid in items:
                    if mask is not None and (doc_id >= len(mask)
                                             or not mask[doc_id]):
                        continue
                    uuids.append(uid)
                    if len(uuids) >= max_matches:
                        break
            else:
                raws = self._require_remote(name).list_objects(
                    self._read_node(name), self.config.name, name,
                    limit=max_matches - len(uuids), where=where_dict)
                uuids.extend(StorageObject.from_bytes(r).uuid for r in raws)
        result = {"matches": len(uuids), "successful": 0, "failed": 0,
                  "objects": []}
        for uid in uuids:
            if dry_run:
                ok, err = True, None
            else:
                try:
                    ok = self.delete_object(uid, tenant, consistency)
                    err = None if ok else "not found"
                except Exception as e:  # per-object errors, not transactional
                    ok, err = False, str(e)
            result["successful" if ok else "failed"] += 1
            if verbose:
                entry = {"id": uid, "successful": ok}
                if err:
                    entry["error"] = err
                result["objects"].append(entry)
        return result

    def object_count(self, tenant: str | None = None) -> int:
        """One replica per shard counts (replicas would double-count)."""
        if self.config.multi_tenancy.enabled and not tenant:
            return 0
        total = 0
        for name in self._target_shard_names(tenant):
            if self._is_local(name):
                total += self._load_shard(name).object_count()
            elif self.remote is not None:
                total += self.remote.overview(self._read_node(name),
                                              self.config.name,
                                              name)["object_count"]
        return total

    def iter_objects(self, tenant: str | None = None):
        for shard in self._target_shards(tenant):
            for key, raw in shard.objects.iter_items():
                yield StorageObject.from_bytes(raw)

    def fetch_objects(self, limit: int = 25, offset: int = 0,
                      sort: list[dict] | None = None, where=None,
                      tenant: str | None = None,
                      after: str | None = None) -> list[StorageObject]:
        """List objects with optional filter/sort/cursor (reference:
        /v1/objects listing; sorter/objects_sorter.go; cursor via ?after=
        which requires uuid order — sort and after are mutually exclusive,
        as in the reference API)."""
        from weaviate_tpu.query.sorter import sort_objects

        if after is not None and sort:
            raise ValueError("'after' cursor cannot be combined with sort")
        names = self._target_shard_names(tenant)
        where_dict = where.to_dict() if where is not None else None
        if sort:
            # property sort needs the values: materialize candidates
            objs: list[StorageObject] = []
            for name in names:
                if self._is_local(name):
                    shard = self._load_shard(name)
                    mask = shard.allow_mask(where) if where is not None else None
                    for _key, raw in shard.objects.iter_items():
                        obj = StorageObject.from_bytes(raw)
                        if mask is not None and (obj.doc_id >= len(mask)
                                                 or not mask[obj.doc_id]):
                            continue
                        objs.append(obj)
                else:
                    raws = self._require_remote(name).list_objects(
                        self._read_node(name), self.config.name, name,
                        where=where_dict)
                    objs.extend(StorageObject.from_bytes(r) for r in raws)
            return sort_objects(objs, sort)[offset: offset + limit]
        # uuid-ordered page: select uuids from the in-RAM docid map (or a
        # remote page), only deserialize what is actually returned
        candidates: list[tuple[str, object]] = []  # (uuid, shard name | obj)
        for name in names:
            if self._is_local(name):
                shard = self._load_shard(name)
                mask = shard.allow_mask(where) if where is not None else None
                with shard._lock:  # snapshot: writers mutate _doc_to_uuid
                    items = list(shard._doc_to_uuid.items())
                for doc_id, uid in items:
                    if mask is not None and (doc_id >= len(mask)
                                             or not mask[doc_id]):
                        continue
                    if after is not None and uid <= after:
                        continue
                    candidates.append((uid, name))
            else:
                # each remote shard over-fetches its own first offset+limit
                # matching objects; the merge below trims to the page
                raws = self._require_remote(name).list_objects(
                    self._read_node(name), self.config.name, name,
                    limit=offset + limit, after=after, where=where_dict)
                for raw in raws:
                    obj = StorageObject.from_bytes(raw)
                    candidates.append((obj.uuid, obj))
        candidates.sort(key=lambda t: t[0])
        page = candidates[offset: offset + limit]
        out = []
        for uid, src in page:
            obj = src if isinstance(src, StorageObject) else \
                self._load_shard(src).get_object(uid)
            if obj is not None:
                out.append(obj)
        return out

    # -- aggregation ---------------------------------------------------------

    @_timed("aggregate")
    def aggregate(self, properties: list[str] | None = None,
                  group_by: str | None = None, where=None,
                  tenant: str | None = None,
                  requested: dict[str, list[str]] | None = None,
                  near_vector=None, near_vec_name: str = "",
                  near_max_distance: float | None = None,
                  object_limit: int | None = None,
                  top_occurrences_limit: int = 5) -> dict:
        """Scatter-gather aggregation (reference: aggregator/aggregator.go →
        per-shard fold, shard_combiner.go merge). With ``near_vector`` +
        ``object_limit``, aggregates over the top-k of a vector search
        instead of the whole (filtered) corpus (aggregator/hybrid.go)."""
        from weaviate_tpu.query.aggregator import (
            aggregate_objects,
            combine_partials,
            finalize_aggregation,
        )

        if near_vector is not None:
            k = object_limit or 100
            hits = self.near_vector(near_vector, k=k, tenant=tenant,
                                    vec_name=near_vec_name,
                                    include_objects=True, where=where,
                                    max_distance=near_max_distance)
            partials = [aggregate_objects((r.object for r in hits if r.object),
                                          properties, group_by)]
        else:
            def one(name: str):
                if not self._is_local(name):
                    return self._require_remote(name).aggregate(
                        self._read_node(name), self.config.name, name,
                        properties, group_by,
                        where.to_dict() if where is not None else None)
                shard = self._load_shard(name)
                mask = shard.allow_mask(where) if where is not None else None

                def objs():
                    for _key, raw in shard.objects.iter_items():
                        obj = StorageObject.from_bytes(raw)
                        if mask is not None and (obj.doc_id >= len(mask)
                                                 or not mask[obj.doc_id]):
                            continue
                        yield obj

                return aggregate_objects(objs(), properties, group_by)

            names = self._target_shard_names(tenant)
            partials = [one(names[0])] if len(names) == 1 else \
                list(self._pool.map(tracing.propagate(one), names))
        return finalize_aggregation(combine_partials(partials), requested,
                                    top_occurrences_limit)

    # -- search --------------------------------------------------------------

    def _attach_objects(self, results: list[SearchResult]) -> None:
        """Fill in .object for results that don't carry one yet — ONE
        batched read per local shard (``Shard.get_objects``: one lock
        acquisition and one search a segment, not a result), ONE batched
        remote get per non-local shard (not one RPC per result). A result
        whose object has gone since the search keeps ``object = None``."""
        missing: dict[str, list[SearchResult]] = {}
        for r in results:
            if not r.attached:
                missing.setdefault(r.shard, []).append(r)
        if not missing:
            return
        with tracing.span("objects.fetch", stage="fetch",
                          n=sum(len(rs) for rs in missing.values()),
                          shards=len(missing)) as sp:
            reads, routes = 0, {}
            for name, rs in missing.items():
                if self._is_local(name):
                    reads += 1
                    raws = self._load_shard(name).get_frames(
                        [r.uuid for r in rs], routes)
                    for r, raw in zip(rs, raws):
                        r.frame = raw
                else:
                    from weaviate_tpu.cluster.transport import RpcError

                    try:
                        raws = self._require_remote(name).get_objects(
                            self._read_node(name), self.config.name, name,
                            [r.uuid for r in rs])
                    except RpcError as e:
                        # the replica died between search and property
                        # fetch: serve the ids/distances we have with a
                        # degraded marker rather than failing the query
                        degrade.report("objects_unavailable",
                                       collection=self.config.name,
                                       shard=name, detail=str(e))
                        continue
                    for r, raw in zip(rs, raws):
                        r.frame = raw or None
            sp.set(reads=reads, array_keys=routes.get("array", 0),
                   scalar_keys=routes.get("scalar", 0))

    # -- epoch migration (ROADMAP item 3: ledger-driven epoch placement) ------

    def _unmigrate(self, shard, objs) -> None:
        """A re-put at an object's ring home supersedes its migrated
        copy: delete the sibling's copy and drop the routing marker —
        called AFTER the fresh write landed (a failed or crashed re-put
        must never have destroyed the only copy first; the transient
        double-present window is deduped by uuid in the merge, and GETs
        prefer the ring copy). Zero-cost when the shard never migrated
        anything."""
        if shard._migrated_count <= 0:
            return
        for obj in objs:
            dst = shard.migrated_to(obj.uuid)
            if dst and dst != shard.name:
                if self._is_local(dst):
                    self._load_shard(dst).delete_object(obj.uuid)
                elif self.remote is not None:
                    self._require_remote(dst).delete_object(
                        self._read_node(dst), self.config.name, dst,
                        obj.uuid)
            if dst:
                shard.clear_migrated(obj.uuid)

    def _sibling_with_headroom(self, src_name: str) -> str | None:
        """The local sibling shard with the most HBM headroom (smallest
        ledger footprint) — the migration target. None when this
        collection has no other local shard."""
        from weaviate_tpu.runtime.hbm_ledger import ledger

        def over_quota(name: str) -> bool:
            # quota check from ALREADY-LOADED shards only: a cold shard
            # holds no device arrays (its ledger bytes are ~0), and
            # constructing N-1 Shard objects mid-rescue — fresh device
            # stores, bucket opens — is exactly wrong under pressure
            sh = self.shards.get(name)
            return sh is not None and sh.over_shard_limit()

        best, best_bytes = None, None
        for name in self.sharding.shard_names:
            if name == src_name or not self._is_local(name):
                continue
            if over_quota(name):
                continue  # no headroom there either
            b = ledger.shard_bytes(self.config.name, name)
            if best_bytes is None or b < best_bytes:
                best, best_bytes = name, b
        if best is None:
            return None
        if over_quota(src_name):
            # quota pressure: any under-quota sibling IS headroom
            return best
        src_bytes = ledger.shard_bytes(self.config.name, src_name)
        # "headroom exists" = the sibling is meaningfully lighter than
        # the source; migrating between two equally-full shards would
        # just bounce the epoch back on the next cycle
        return best if best_bytes < src_bytes else None

    def _remote_sibling_with_headroom(self, src_name: str) -> str | None:
        """The cross-NODE half of epoch migration (ROADMAP item 3's
        leftover, riding item 2's placement machinery): a sibling shard
        placed on another node, chosen by that node's gossiped HBM
        ledger bytes. Only nodes whose reported footprint is BELOW this
        node's qualify — a local move cannot relieve device-global
        pressure (two shards of one process share the chips), but
        shipping the epoch to a genuinely lighter node does. Nodes with
        no gossiped ledger reading are skipped: never ship an epoch
        blind."""
        if self.remote is None:
            return None
        hbm = self._node_hbm_bytes()
        local_bytes = hbm.get(self.local_node, 0)
        best, best_bytes = None, None
        for name in self.sharding.shard_names:
            if name == src_name or self._is_local(name):
                continue
            node = self.sharding.nodes_for(name)[0]
            b = hbm.get(node)
            if b is None or b >= local_bytes:
                continue
            if best_bytes is None or b < best_bytes:
                best, best_bytes = name, b
        return best

    def migrate_epoch(self, src_name: str, vec_name: str = "",
                      dst_name: str | None = None) -> int:
        """Migrate the coldest sealed epoch of ``src_name``'s
        epoch-backed index to a sibling shard with headroom: serialize
        the epoch's objects from the source LSM, durable ingest on the
        target (``Shard.put_object_batch`` — vectors land in the
        target's device epochs), then the atomic source-side cutover
        (``Shard.migrate_out``: durable routing markers + slot→doc-id
        table rows dropped under the index lock) and the epoch's HBM
        released (``drop_epoch``). Crash ordering keeps every object
        served EXACTLY once: before the cutover markers the ring copy
        answers; after them the marker routes reads to the target; the
        transient double-present window is deduped by uuid in the
        scatter-gather merge. Returns objects moved (0 = nothing to
        do). Single-replica, non-tenant collections only — a replicated
        shard's epochs rebalance through the replication story, not
        this local move."""
        if (self.config.replication.factor > 1
                or self.config.multi_tenancy.enabled
                or not self._is_local(src_name)):
            return 0
        src = self._load_shard(src_name)
        moved_total = 0
        with self._migrate_lock:
            for name, idx in list(src.vector_indexes.items()):
                if vec_name and name != vec_name:
                    continue
                es = getattr(idx, "epoch_store", None)
                if es is None:
                    continue
                eid = es.coldest_sealed()
                if eid is None:
                    continue
                dst = dst_name or self._sibling_with_headroom(src_name)
                if dst is None and dst_name is None:
                    # no LOCAL headroom: the cross-node half — ship the
                    # epoch to a sibling shard on a lighter node,
                    # behind the same durable-marker cutover
                    dst = self._remote_sibling_with_headroom(src_name)
                if dst is None or dst == src_name:
                    return moved_total
                if self._is_local(dst):
                    moved_total += self._migrate_one(src, idx, es, eid,
                                                     dst)
                else:
                    moved_total += self._migrate_one_remote(
                        src, idx, es, eid, dst)
        return moved_total

    def _migrate_one(self, src, idx, es, eid: int, dst: str) -> int:
        """Move one epoch. Caller holds ``_migrate_lock``. The SOURCE
        shard's lock is held across serialize -> target ingest ->
        cutover so a concurrent put/delete of a moving uuid cannot land
        in the un-synchronized window (it would be erased by the
        cutover, or resurrected from the target's pre-write copy);
        writers to the source simply queue behind the move, bounded by
        one epoch's ingest."""
        from weaviate_tpu.runtime import faultline

        src_name = src.name
        with src._lock:
            doc_ids = idx.epoch_doc_ids(eid)
            if not len(doc_ids):
                es.drop_epoch(eid)
                return 0
            objs = [o for o in src.objects_by_doc_ids(doc_ids)
                    if o is not None]
            if not objs:
                return 0
            # 1) durable routing markers FIRST: from here on, deletes
            #    and re-puts of a moving uuid reach BOTH sides no
            #    matter where a kill lands (a marker to a copy that
            #    never ingests is harmless — GETs prefer the ring copy)
            src.mark_migrating([o.uuid for o in objs], dst)
            faultline.fire("epoch.migrate.pre_ingest", shard=src_name,
                           epoch=eid, docs=len(doc_ids))
            try:
                # 2) durable ingest at the target (fresh doc ids there;
                #    vectors land in the target's own device epochs)
                self._load_shard(dst).put_object_batch(objs)
            except MemoryError:
                # the sibling hit ITS watermark mid-ingest: nothing was
                # cut over, the source still serves — clean the markers
                # back off (nothing landed at dst) and report no move
                for o in objs:
                    src.clear_migrated(o.uuid)
                logger.warning(
                    "epoch migration %s/%s e%d -> %s aborted: target "
                    "at watermark", self.config.name, src_name, eid, dst)
                return 0
            faultline.fire("epoch.migrate.post_ingest", shard=src_name,
                           epoch=eid)
            # 3) source cutover: the batched removal from LSM +
            #    slot→doc-id tables (markers already durable)
            src.migrate_out([o.uuid for o in objs], dst)
            faultline.fire("epoch.migrate.post_cutover", shard=src_name,
                           epoch=eid)
            # 4) the (now all-tombstone) epoch's HBM releases through
            #    the ledger finalizers at cutover
            es.drop_epoch(eid)
            es.migrations_total += 1
        monitoring.epoch_migrations.labels(self.config.name,
                                           src_name).inc()
        logger.info(
            "epoch migration: %s/%s e%d -> %s (%d objects)",
            self.config.name, src_name, eid, dst, len(objs))
        return len(objs)

    def _migrate_one_remote(self, src, idx, es, eid: int,
                            dst: str) -> int:
        """Cross-node twin of ``_migrate_one``: same durable-marker
        cutover ordering, with the target-side durable ingest riding
        the remote shard client (``put_objects`` → the destination
        node's ``Shard.put_object_batch``, so vectors land in ITS
        device epochs under ITS admission control). Markers go first (a
        marker to a copy that never ingests is harmless — GETs prefer
        the ring copy); an ingest RPC failure aborts with NOTHING cut
        over and the markers LEFT IN PLACE: a timeout or lost reply is
        ambiguous — the put may have landed durably on the target — and
        dropping the markers would orphan that copy as an undeletable
        zombie (searches would keep surfacing it after the ring copy is
        deleted). Kept markers keep every copy reachable: deletes and
        re-puts clean BOTH sides through them, search dedups by uuid,
        and a later retry simply re-marks and re-ingests (idempotent by
        uuid). The source shard lock is held across the RPC — the same
        writes-queue-behind-the-move contract as the local twin;
        exposure is bounded by the remote client's per-attempt deadline
        (REMOTE_RPC_TIMEOUT_S) + the per-peer circuit breaker failing
        known-dead nodes fast, and migrations are serialized per
        collection. The same ``epoch.migrate.*`` fault points fire, so
        the crashtest harness covers this path too."""
        from weaviate_tpu.cluster.transport import RpcError
        from weaviate_tpu.runtime import faultline

        src_name = src.name
        dst_node = self.sharding.nodes_for(dst)[0]
        with src._lock:
            doc_ids = idx.epoch_doc_ids(eid)
            if not len(doc_ids):
                es.drop_epoch(eid)
                return 0
            objs = [o for o in src.objects_by_doc_ids(doc_ids)
                    if o is not None]
            if not objs:
                return 0
            src.mark_migrating([o.uuid for o in objs], dst)
            faultline.fire("epoch.migrate.pre_ingest", shard=src_name,
                           epoch=eid, docs=len(doc_ids))
            try:
                self._require_remote(dst).put_objects(
                    dst_node, self.config.name, dst,
                    [o.to_bytes() for o in objs])
            except RpcError as e:
                # ambiguous outcome (the put may have landed before a
                # timeout/lost reply): keep the markers so a possibly-
                # present target copy stays reachable for deletes and
                # dedup — clearing them here would orphan it
                logger.warning(
                    "cross-node epoch migration %s/%s e%d -> %s@%s "
                    "aborted (markers kept, nothing cut over): %s",
                    self.config.name, src_name, eid, dst, dst_node, e)
                return 0
            faultline.fire("epoch.migrate.post_ingest", shard=src_name,
                           epoch=eid)
            src.migrate_out([o.uuid for o in objs], dst)
            faultline.fire("epoch.migrate.post_cutover", shard=src_name,
                           epoch=eid)
            es.drop_epoch(eid)
            es.migrations_total += 1
        monitoring.epoch_migrations.labels(self.config.name,
                                           src_name).inc()
        logger.info(
            "cross-node epoch migration: %s/%s e%d -> %s@%s "
            "(%d objects)", self.config.name, src_name, eid, dst,
            dst_node, len(objs))
        return len(objs)

    def epoch_maintenance(self, tick: bool = False) -> bool:
        """One background policy cycle (registered with the database's
        cyclemanager as ``epoch-maintenance`` — the ONLY driver of epoch
        upkeep, so the work runs once per interval): per-shard seal /
        drop / compact — deletes RECLAIM HBM here, which is what
        relieves the device-GLOBAL admission watermark — then migrate
        the coldest sealed epoch off any shard over its per-shard quota
        watermark to a sibling with headroom instead of letting the
        quota 507 writes. (A local move cannot reduce device-global
        usage — two shards of one process share the chips — so only
        quota pressure, the budget migration genuinely relieves,
        triggers it.)"""
        did = False
        with self._lock:
            shards = list(self.shards.values())
        for shard in shards:
            did = shard.epoch_maintenance(tick=tick) or did
        for shard in shards:
            if shard.over_shard_limit():
                did = self.migrate_epoch(shard.name) > 0 or did
        return did

    def _rescue_shard(self, shard) -> bool:
        """Synchronous memory-pressure rescue (wired as
        ``shard.memory_rescue``): compact first — tombstone-heavy
        epochs give bytes back without moving anything — then migrate
        the coldest sealed epoch to a sibling with headroom. Runs on
        the importing thread, once, before admission re-checks. The
        thread-local reentrancy guard stops a migration's own
        target-side ingest (which runs admission too) from cascading
        rescues across the ring."""
        if getattr(self._rescue_tls, "active", False):
            return False
        self._rescue_tls.active = True
        try:
            did = shard.epoch_maintenance()
            if shard.over_shard_limit():
                did = self.migrate_epoch(shard.name) > 0 or did
            return did
        finally:
            self._rescue_tls.active = False

    @staticmethod
    def _and_masks(a, b) -> np.ndarray:
        """Intersect two allow lists (bool mask or doc-id array forms)."""
        a, b = np.asarray(a), np.asarray(b)
        if a.dtype != np.bool_ and b.dtype != np.bool_:
            # both doc-id arrays: native sorted-set intersect (the roaring
            # AND of the reference, csrc/weaviate_native.cpp)
            from weaviate_tpu import native

            return native.intersect_sorted(
                np.unique(a), np.unique(b)).astype(np.int64)

        def to_mask(x, size):
            if x.dtype == np.bool_:
                m = np.zeros(size, dtype=bool)
                m[: len(x)] = x
                return m
            m = np.zeros(size, dtype=bool)
            m[x[x < size]] = True
            return m

        size = max(len(a) if a.dtype == np.bool_ else (int(a.max()) + 1 if len(a) else 0),
                   len(b) if b.dtype == np.bool_ else (int(b.max()) + 1 if len(b) else 0))
        return to_mask(a, size) & to_mask(b, size)

    @staticmethod
    def _merge_by_distance(gathered: list, k: int) -> list:
        """Cross-shard reduce: each shard's entry is already ascending, so
        a k-way heap merge walks them in order and stops at k (reference:
        index.go:1644-1648 sort+truncate). An entry is a list of results
        (a remote shard's, or one local shard's) or a ``_ShardHits``: a
        local shard's doc ids and distances, whose uuids are looked up
        and whose results are built HERE, for the winners alone. Plain
        Python on purpose: with 32 request threads on one interpreter a
        call that gives the lock up (ctypes, as the native merge this
        replaced; numpy over more than a few hundred elements) waits its
        turn to take it back, 20 ms a merge on the chip's host (PERF.md
        section 6, PR 34), where the whole walk is some tens of heap
        steps."""
        sources = [g for g in gathered if len(g)]
        if not sources:
            return []
        if len(sources) == 1 and isinstance(sources[0], list):
            return sources[0][:k]

        def ascending(li, g):
            dists = g.dists.tolist() if isinstance(g, _ShardHits) else \
                [r.distance for r in g]
            for pos, d in enumerate(dists):
                yield d, li, pos

        # dedup by uuid, best (first, ascending) distance wins: an
        # epoch-migration crash window can briefly leave an object
        # present on two shards — it must never be served twice, and the
        # walk goes on past a duplicate so that it never eats into the k
        # contract. Results without a uuid (score-only merges) always
        # pass.
        out, seen = [], set()
        for _d, li, pos in heapq.merge(*(ascending(li, g)
                                         for li, g in enumerate(sources))):
            r = sources[li][pos]
            if r is None:   # deleted since the shard answered
                continue
            u = getattr(r, "uuid", None)
            if u is not None:
                if u in seen:
                    continue
                seen.add(u)
            out.append(r)
            if len(out) == k:
                break
        return out

    @_timed("vector")
    def near_vector(self, query, k: int = 10, vec_name: str = "",
                    tenant: str | None = None, include_objects: bool = True,
                    allow_list_by_shard: dict | None = None,
                    max_distance: float | None = None,
                    where=None, autocut: int = 0) -> list[SearchResult]:
        """Scatter-gather nearVector (reference: index.go:1541
        objectVectorSearch -> per-shard parallel search -> merge+truncate).
        ``where``: optional Filter tree, evaluated per shard to an AllowList
        mask applied inside the device scan.

        More than one shard (``_fan_out``): the request's own thread
        searches the LOCAL shards under the request's one deadline and
        merges once; no thread is held a local shard. A plain request
        over several of them is ONE item on the collection's drain
        (db/drain.py: one batcher a collection, every member shard's
        scan launched over one query block) and is charged that drain's
        ``queue_wait``, ``device`` and ``transfer``. One that carries a
        filter or an allow list builds each shard's allow mask, enqueues
        on every local shard's own batcher, waits for all of them, and
        is charged those of the shard whose answer arrived last (its
        critical path). Remote shards keep the pool: a blocking HTTP
        call is what a pool is for. On both routes a request over
        several LOCAL shards carries two request stages more
        (runtime/tailboard.py): ``fanout_wait``, first enqueue to (last)
        delivery less those three, and ``merge``."""
        query = np.asarray(query, dtype=np.float32)
        names = self._target_shard_names(tenant)
        if len(names) == 1:
            merged = self._merge_by_distance(
                [self._near_vector_shard(names[0], query, k, vec_name,
                                         allow_list_by_shard, where,
                                         include_objects)], k)
        else:
            merged = self._fan_out(names, query, k, vec_name,
                                   allow_list_by_shard, where,
                                   include_objects)
        if max_distance is not None:
            merged = [r for r in merged if r.distance <= max_distance]
        if autocut > 0 and merged:
            from weaviate_tpu.query.autocut import autocut as _autocut

            merged = merged[: _autocut([r.distance for r in merged], autocut)]
        if include_objects:
            self._attach_objects(merged)
        return merged

    def _shard_allow(self, name: str, shard, allow_list_by_shard, where):
        """A local shard's allow mask: the caller's list for it, ANDed
        with the filter evaluated on that shard."""
        allow = None if allow_list_by_shard is None else \
            allow_list_by_shard.get(name)
        if where is not None:
            fmask = shard.allow_mask(where)
            allow = fmask if allow is None else \
                self._and_masks(allow, fmask)
        return allow

    def _near_vector_shard(self, name: str, query, k: int, vec_name: str,
                           allow_list_by_shard, where,
                           include_objects: bool) -> list[SearchResult]:
        """One shard's nearVector in one blocking call: the whole of a
        one-shard request, and a remote shard's part of a fan-out."""
        if self._is_local(name):
            shard = self._load_shard(name)
            ids, dists = shard.vector_search(
                query, k, vec_name,
                self._shard_allow(name, shard, allow_list_by_shard, where))
            out = []
            for doc_id, dist in zip(ids.tolist(), dists.tolist()):
                uuid = shard._doc_to_uuid.get(doc_id)
                if uuid is not None:
                    out.append(SearchResult(uuid=uuid, distance=dist,
                                            shard=name))
            return out
        # remote shard: the owning node evaluates filters and resolves
        # objects (reference: remote.SearchShard, index.go:1607);
        # replica failover + degraded (partial) results on total loss
        items = self._remote_search_degraded(
            name, vector=query, k=k, vec_name=vec_name,
            where=where.to_dict() if where is not None else None,
            include_objects=include_objects)
        if items is None:
            return []
        return [_remote_result(i, name) for i in items]

    def _fan_out(self, names, query, k: int, vec_name: str,
                 allow_list_by_shard, where,
                 include_objects: bool) -> list[SearchResult]:
        """``near_vector`` over several shards: remote shards go to the
        pool as futures, the local shards are searched from the
        request's own thread under the one deadline, and everything is
        merged once. Several LOCAL shards take one of two routes, by
        what the request carries (``weaviate_tpu_fanout_route_total``):
        ``drain``, a plain request: ONE item on the collection's drain
        (db/drain.py), one wait, one finish, every shard's ``[k]`` from
        that one delivery; ``shards``, a request with a filter or an
        allow list (and a drain retired under the request): one item a
        shard on the shards' own batchers, as every fan-out was until
        ISSUE 42. A shard does the same for itself on both: its
        snapshot of queued vectors before the enqueue and their union
        after, the ``ids >= 0`` filter, its span."""
        local = [n for n in names if self._is_local(n)]
        remote = {n: self._pool.submit(
            tracing.propagate(self._near_vector_shard), n, query, k,
            vec_name, allow_list_by_shard, where, include_objects)
            for n in names if n not in local}
        shards = {n: self._load_shard(n) for n in local}
        hits = None
        if len(local) > 1 and allow_list_by_shard is None and where is None:
            drain = self._drain_for(vec_name, shards)
            if drain is not None:
                try:
                    hits, t_first, t_last, waits = self._search_drain(
                        drain, shards, query, k, vec_name)
                except BatcherStopped:
                    pass    # retired under this request: the shards answer
        route = "drain" if hits is not None else "shards"
        if hits is None:
            hits, t_first, t_last, waits = self._search_shards(
                shards, query, k, vec_name, allow_list_by_shard, where)
        gathered = [hits[n] if n in hits else remote[n].result()
                    for n in names]
        t_merge = time.perf_counter()
        merged = self._merge_by_distance(gathered, k)
        if len(local) > 1:
            # observed for a request that fanned out over local shards,
            # and for no other (runtime/tailboard.py, point 5)
            monitoring.fanout_shards_total.labels(self.config.name).inc(
                len(local))
            monitoring.fanout_route_total.labels(self.config.name,
                                                 route).inc()
            monitoring.fanout_width.observe(len(local))
            tailboard.fanout((t_last - t_first) - waits,
                             time.perf_counter() - t_merge)
        return merged

    def _search_shards(self, shards: dict, query, k: int, vec_name: str,
                       allow_list_by_shard, where):
        """Route ``shards``: every local shard is enqueued on its own
        batcher and all are waited for. -> (hits a shard, first
        enqueue, last delivery, the charged shard's queue_wait + device
        + transfer)."""
        allows = {n: self._shard_allow(n, shard, allow_list_by_shard, where)
                  for n, shard in shards.items()}
        t_first = time.perf_counter()
        searches: dict = {}
        hits: dict = {}
        try:
            for n, shard in shards.items():
                searches[n] = shard.vector_search_begin(query, k, vec_name,
                                                        allows[n])
            for search in searches.values():
                search.wait()
            # the critical path: the shard whose answer arrived last is
            # the one this request is charged for, and is finished first
            # (its ``wake`` runs from that delivery to now)
            t_waited = time.perf_counter()
            last = max(shards, key=lambda n: searches[n].t_deliver,
                       default=None)
            for n in sorted(shards, key=lambda n: n != last):
                hits[n] = _ShardHits(n, shards[n], *shards[n]
                                     .vector_search_end(searches[n],
                                                        charge=n == last))
        except BaseException:
            # a shard that refused or raised, or the budget spent (the
            # typed DeadlineExceeded, once): what is still queued
            # elsewhere for this request leaves its queue, and every
            # shard not yet finished closes its span
            for search in searches.values():
                search.discard()
            raise
        if last is None:
            return hits, t_first, t_waited, 0.0
        return (hits, t_first, searches[last].t_deliver or t_waited,
                sum(searches[last].phases()))

    def _drain_for(self, vec_name: str, shards: dict):
        """The collection's drain over ``shards`` (name -> local shard,
        in the request's order), built or rebuilt where the set it was
        built over is no longer this one; None where a shard's searches
        ride no batcher (``Shard.drain_member``)."""
        members = []
        for name, shard in shards.items():
            idx = shard.drain_member(vec_name)
            if idx is None:
                return None
            members.append((name, shard, idx))
        drain = self._drains.get(vec_name)
        if drain is not None and drain.serves(members):
            return drain
        with self._lock:
            old = self._drains.get(vec_name)
            if old is not None and old.serves(members):
                return old
            drain = self._drains[vec_name] = CollectionDrain(
                self.config.name, members)
        if old is not None:
            # what it has launched is delivered; what is still queued is
            # refused typed and answered by the shards (``_fan_out``)
            old.stop()
        return drain

    def _search_drain(self, drain, shards: dict, query, k: int,
                      vec_name: str):
        """Route ``drain``: ONE item, one wait under the request's
        deadline, one finish (the request is charged the drain's
        queue_wait, device, transfer and wake); every shard keeps its
        span and its read-your-writes union. -> as ``_search_shards``."""
        t_first = time.perf_counter()
        searches: dict = {}
        item = None
        try:
            for n, shard in shards.items():
                searches[n] = shard.vector_search_begin(
                    query, k, vec_name, enqueue=False)
            item = drain.batcher.enqueue(query, k)
            ids, dists = drain.batcher.finish(drain.batcher.wait(item))
            hits = {}
            for s, n in enumerate(drain.names):
                searches[n].feed(ids[s], dists[s])
                hits[n] = _ShardHits(n, shards[n], *shards[n]
                                     .vector_search_end(searches[n],
                                                        charge=False))
        except BaseException:
            # a member that raised, the drain stopped, or the budget
            # spent (typed once, by ``wait``): the item leaves the queue
            # if it is still there, and every shard not yet finished
            # closes its span
            if item is not None:
                drain.batcher.discard(item)
            for search in searches.values():
                search.discard()
            raise
        return (hits, t_first, item.t_deliver or time.perf_counter(),
                sum(drain.batcher.phases(item)))

    @_timed("bm25")
    def bm25(self, query: str, k: int = 10, properties: list[str] | None = None,
             tenant: str | None = None, include_objects: bool = True,
             allow_list_by_shard: dict | None = None,
             where=None, autocut: int = 0) -> list[SearchResult]:
        """Scatter-gather keyword search; merge by score descending
        (reference: Index.objectSearch → per-shard BM25 → merge)."""
        names = self._target_shard_names(tenant)

        def one(name: str) -> list[SearchResult]:
            if self._is_local(name):
                shard = self._load_shard(name)
                ids, scores = shard.bm25_search(
                    query, k, properties,
                    self._shard_allow(name, shard, allow_list_by_shard,
                                      where))
                out = []
                for doc_id, score in zip(ids.tolist(), scores.tolist()):
                    uuid = shard._doc_to_uuid.get(doc_id)
                    if uuid is not None:
                        out.append(SearchResult(uuid=uuid, score=score,
                                                shard=name))
                return out
            items = self._remote_search_degraded(
                name, query=query, k=k, properties=properties,
                where=where.to_dict() if where is not None else None,
                include_objects=include_objects)
            if items is None:
                return []
            return [_remote_result(i, name) for i in items]

        gathered = [one(names[0])] if len(names) == 1 else \
            list(self._pool.map(tracing.propagate(one), names))

        merged = [r for results in gathered for r in results]
        merged.sort(key=lambda r: -r.score)
        merged = merged[:k]
        if autocut > 0 and merged:
            from weaviate_tpu.query.autocut import autocut as _autocut

            merged = merged[: _autocut([-r.score for r in merged], autocut)]
        if include_objects:
            self._attach_objects(merged)
        return merged

    @_timed("hybrid")
    def hybrid(self, query: str, vector=None, alpha: float = 0.75, k: int = 10,
               properties: list[str] | None = None, vec_name: str = "",
               tenant: str | None = None, fusion: str = "relativeScore",
               where=None, include_objects: bool = True,
               autocut: int = 0) -> list[SearchResult]:
        """Hybrid sparse+dense search (reference: hybrid/searcher.go:74 runs
        both legs in parallel, then fuses). ``alpha`` weighs the dense leg
        (0 = pure BM25, 1 = pure vector). ``vector=None`` degrades to
        sparse-only, as the reference does without a vectorizer.

        Single-local-shard queries with a query vector take the fused
        DEVICE path first (ISSUE 18): one batched device program runs the
        dense scan, scores the packed BM25 candidates, and fuses — the
        host two-thread reference below stays the fallback (and the
        parity oracle) for everything the device path declines."""
        from weaviate_tpu.text.hybrid import fusion_ranked, fusion_relative_score

        # over-fetch each leg so fusion has overlap to work with; legs run on
        # ephemeral threads, NOT self._pool — a leg parked in a pool worker
        # while its inner scatter-gather waits for that same pool can deadlock
        import threading as _threading

        if vector is None:
            alpha = 0.0  # degrade to sparse-only (reference does the same
            # when no vectorizer can produce a query vector)
        # evaluate the filter once per shard and let both legs reuse the
        # masks — only possible when every target shard is local; with
        # remote shards the filter tree travels down instead
        names = self._target_shard_names(tenant)
        allow_by_shard = None
        where_down = where
        if where is not None:
            if all(self._is_local(n) for n in names):
                allow_by_shard = {n: self._load_shard(n).allow_mask(where)
                                  for n in names}
                where_down = None

        if (vector is not None and len(names) == 1
                and self._is_local(names[0]) and where_down is None):
            dev = self._hybrid_device(
                names[0], query, vector, alpha, k, properties, vec_name,
                fusion, None if allow_by_shard is None
                else allow_by_shard.get(names[0]))
            if dev is not None:
                if autocut > 0 and dev:
                    from weaviate_tpu.query.autocut import autocut_results

                    dev = autocut_results(dev, autocut, by="score")
                if include_objects:
                    self._attach_objects(dev)
                return dev

        fetch = max(k * 10, 100)
        legs, weights = [], []
        results: dict[str, list] = {}
        errors: dict[str, BaseException] = {}

        def run(name, fn, *a):
            try:
                results[name] = fn(*a)
            except BaseException as e:  # re-raised on the caller thread
                errors[name] = e

        # legs skip object fetch; only the fused top-k pays for it below
        # (tracing.propagate: Thread targets don't inherit contextvars)
        threads = []
        if alpha < 1.0:
            threads.append(_threading.Thread(
                target=tracing.propagate(run),
                args=("sparse", self.bm25, query, fetch,
                      properties, tenant, False, allow_by_shard,
                      where_down)))
        if vector is not None and alpha > 0.0:
            threads.append(_threading.Thread(
                target=tracing.propagate(run),
                args=("dense", self.near_vector, vector, fetch,
                      vec_name, tenant, False, allow_by_shard,
                      None, where_down)))
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise next(iter(errors.values()))
        if "sparse" in results:
            legs.append(results["sparse"])
            weights.append(1.0 - alpha)
        if "dense" in results:
            dense = results["dense"]
            # similarity score for fusion: any monotone-decreasing map of
            # distance works (min-max normalization is affine-invariant)
            for r in dense:
                r.score = -r.distance
            legs.append(dense)
            weights.append(alpha)
        if not legs:
            return []
        fuse = fusion_relative_score if fusion == "relativeScore" else fusion_ranked
        # fusion returns (fused_score, result) pairs WITHOUT mutating the
        # leg results (see text/hybrid.py); materialize fresh results so
        # concurrent queries sharing leg objects never race on .score
        fused = [SearchResult(uuid=r.uuid, distance=r.distance, score=s,
                              object=r._object, shard=r.shard, frame=r.frame)
                 for s, r in fuse(legs, weights, k)]
        if autocut > 0 and fused:
            from weaviate_tpu.query.autocut import autocut_results

            fused = autocut_results(fused, autocut, by="score")
        if include_objects:
            self._attach_objects(fused)
        return fused

    def _hybrid_device(self, name: str, query: str, vector, alpha: float,
                       k: int, properties, vec_name: str, fusion: str,
                       allow_mask) -> list[SearchResult] | None:
        """Fused device hybrid for one local shard (ISSUE 18). None =
        the shard declined (unsupported index, candidate budget, kill
        switch) and the caller runs the host reference path."""
        shard = self._load_shard(name)
        res = shard.hybrid_search(
            query, np.asarray(vector, np.float32), k, alpha=alpha,
            fusion=fusion, properties=properties, vec_name=vec_name,
            allow_mask=allow_mask)
        if res is None:
            return None
        ids, scores = res
        out = []
        for doc_id, score in zip(ids.tolist(), scores.tolist()):
            uuid = shard._doc_to_uuid.get(doc_id)
            if uuid is not None:
                out.append(SearchResult(uuid=uuid, score=score,
                                        shard=name))
        return out

    def hybrid_async(self, query: str, vector=None, alpha: float = 0.75,
                     k: int = 10, properties: list[str] | None = None,
                     vec_name: str = "", tenant: str | None = None,
                     fusion: str = "relativeScore", where=None,
                     include_objects: bool = True, autocut: int = 0):
        """Dispatch-only twin of ``hybrid``: returns a
        ``DeviceResultHandle`` resolving to the same ``list[SearchResult]``.
        On the device path the handle's D2H drains through the
        TransferPipeline while the caller dispatches more work; when the
        device path declines, the host reference runs inline and the
        handle is pre-resolved (``DeviceResultHandle.ready``)."""
        from weaviate_tpu.runtime.transfer import DeviceResultHandle

        names = self._target_shard_names(tenant)
        if (vector is not None and len(names) == 1
                and self._is_local(names[0]) and where is None):
            shard = self._load_shard(names[0])
            h = shard.hybrid_search_async(
                query, np.asarray(vector, np.float32), k, alpha=alpha,
                fusion=fusion, properties=properties, vec_name=vec_name)
            if h is not None:
                name = names[0]

                def _finish(res, _shard=shard, _name=name):
                    ids, scores = res
                    out = []
                    for doc_id, score in zip(ids.tolist(),
                                             scores.tolist()):
                        uuid = _shard._doc_to_uuid.get(doc_id)
                        if uuid is not None:
                            out.append(SearchResult(uuid=uuid,
                                                    score=score,
                                                    shard=_name))
                    if autocut > 0 and out:
                        from weaviate_tpu.query.autocut import \
                            autocut_results

                        out = autocut_results(out, autocut, by="score")
                    if include_objects:
                        self._attach_objects(out)
                    return out

                return h.map(_finish)
        return DeviceResultHandle.ready(self.hybrid(
            query, vector, alpha, k, properties, vec_name, tenant,
            fusion, where, include_objects, autocut))

    # -- maintenance ---------------------------------------------------------

    def flush(self):
        for s in self.shards.values():
            s.flush()

    def close(self):
        self._pool.shutdown(wait=False)
        with self._lock:
            drains, self._drains = list(self._drains.values()), {}
        for drain in drains:
            drain.stop()
        for s in self.shards.values():
            s.close()
