"""Dynamic index: exact flat scan below a threshold, IVF ANN above.

Reference: adapters/repos/db/vector/dynamic/index.go — starts flat and
upgrades to HNSW once the object count crosses a threshold
(ShouldUpgrade :348, Upgrade :370; requires ASYNC_INDEXING). Here the
upgrade target is the TPU-native IVF index, and the swap happens inline at
the insert that crosses the threshold (our "async queue" is the IVF delta
buffer itself, which absorbs the migrated rows batched).

Brute force on TPU is fast enough that the default threshold can sit far
above the reference's — exact search IS the preferred regime until the
corpus is large enough that probing beats one more matmul.
"""

from __future__ import annotations

import threading

import numpy as np

from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.engine.ivf import (DEFAULT_FLAT_SEARCH_CUTOFF, IVFIndex,
                                     maintain_stage)


class DynamicIndex:
    """VectorIndex-contract wrapper delegating to flat, then IVF."""

    index_type = "dynamic"

    def __init__(self, dim: int, metric: str = "l2-squared",
                 threshold: int = 100_000, mesh=None, capacity: int = 8192,
                 chunk_size: int = 8192, nlist: int = 0, nprobe: int = 0,
                 upgrade_quantization: str | None = None,
                 upgradable: bool = True,
                 flat_search_cutoff: int = DEFAULT_FLAT_SEARCH_CUTOFF,
                 **flat_kwargs):
        self.dim = dim
        self.metric = metric
        self.threshold = threshold
        self.mesh = mesh
        self._nlist = nlist
        self._nprobe = nprobe
        self._chunk_size = chunk_size
        # residency for the upgrade TARGET: the flat regime stays full
        # precision (exact scan is the point), but the IVF index it
        # migrates into can start life residual-quantized
        self._upgrade_quantization = upgrade_quantization
        # False: the flat regime is final (an sq class, whose compressed
        # scan the IVF index has no form of: it stays flat at any size,
        # as a bq class does once its store is quantized)
        self._upgradable = upgradable
        # upstream's hnsw.flatSearchCutoff, for the ANN index this class
        # upgrades into (the flat regime is exact whatever the filter)
        self._flat_search_cutoff = int(flat_search_cutoff)
        self._lock = threading.RLock()
        # captured so the runtime flat->IVF upgrade (which runs on an
        # insert thread, outside any shard owner scope) keeps the new
        # index's HBM-ledger attribution
        from weaviate_tpu.runtime import hbm_ledger

        self._hbm_owner = hbm_ledger.current_owner()
        self._impl = FlatIndex(dim=dim, metric=metric, mesh=mesh,
                               capacity=capacity, chunk_size=chunk_size,
                               **flat_kwargs)

    # -- upgrade lifecycle ----------------------------------------------------

    @property
    def upgraded(self) -> bool:
        return isinstance(self._impl, IVFIndex)

    @property
    def flat_search_cutoff(self) -> int:
        return self._flat_search_cutoff

    @flat_search_cutoff.setter
    def flat_search_cutoff(self, cutoff: int) -> None:
        with self._lock:
            self._flat_search_cutoff = int(cutoff)
            if self.upgraded:
                self._impl.flat_search_cutoff = int(cutoff)

    def should_upgrade(self) -> bool:
        """Reference ShouldUpgrade (dynamic/index.go:348). Mesh-sharded and
        quantized flat stay flat: the SPMD exact scan already scales across
        devices, and the PQ/BQ-compressed scan is already the fast path."""
        return (self._upgradable and not self.upgraded
                and self.mesh is None
                and not self._impl.compressed
                and len(self._impl) >= self.threshold)

    def upgrade(self) -> None:
        """Migrate flat contents into a fresh IVF index (reference Upgrade,
        dynamic/index.go:370)."""
        with self._lock:
            if self.upgraded:
                return
            with maintain_stage("upgrade", "dynamic.upgrade",
                                rows=len(self._impl)) as (_, less):
                self._upgrade_locked(less)

    def _upgrade_locked(self, less: list) -> None:
        """The migration itself (caller holds ``_lock``). The seconds of
        the training inside it go to ``less[0]``: they are observed as
        the ``train`` stage."""
        flat = self._impl
        snap = flat.snapshot()
        slot_to_id = snap["slot_to_id"]
        valid = snap["valid"]
        live = [s for s in range(min(len(slot_to_id), len(valid)))
                if valid[s] and slot_to_id[s] >= 0]
        from weaviate_tpu.runtime import hbm_ledger

        with hbm_ledger.owner(**self._hbm_owner):
            ivf = IVFIndex(dim=self.dim, metric=self.metric,
                           chunk_size=self._chunk_size,
                           nlist=self._nlist, nprobe=self._nprobe,
                           train_threshold=max(self.threshold, 256),
                           dtype=getattr(flat.store, "dtype", None),
                           flat_search_cutoff=self._flat_search_cutoff,
                           quantization=self._upgrade_quantization)
        if live:
            ids = slot_to_id[live]
            vecs = snap["vectors"][live]
            ivf.add_batch(ids, vecs)
            if not ivf.trained:
                ivf.train()
            less[0] += ivf.store.train_seconds
        self._impl = ivf

    # -- VectorIndex contract (delegated) ------------------------------------

    def add(self, doc_id: int, vector) -> None:
        self.add_batch([doc_id], np.asarray(vector)[None, :])

    def add_batch(self, doc_ids, vectors) -> None:
        with self._lock:
            self._impl.add_batch(doc_ids, vectors)
            if self.should_upgrade():
                self.upgrade()

    def maintain(self, tick: bool = False) -> bool:
        """Maintenance tick (db/shard.py epoch_maintenance): catch a
        deferred upgrade (e.g. after a restore that landed above the
        threshold without an insert) and forward the tick to the live
        impl — the IVF regime folds its delta / retrains here. -> the
        impl's answer: work done, or left for the next tick."""
        with self._lock:
            did = False
            if self.should_upgrade():
                self.upgrade()
                did = True
            impl_maintain = getattr(self._impl, "maintain", None)
            if impl_maintain is not None:
                did = bool(impl_maintain(tick=tick)) or did
            return did

    def __getattr__(self, name):
        # everything else (search/delete/len/compact/...) hits the live impl
        return getattr(self._impl, name)

    def __len__(self) -> int:
        return len(self._impl)

    def snapshot(self) -> dict:
        snap = self._impl.snapshot()
        snap["index_type"] = "dynamic"
        snap["dynamic_threshold"] = self.threshold
        snap["dynamic_upgraded"] = self.upgraded
        snap["dynamic_upgrade_quantization"] = self._upgrade_quantization
        snap["dynamic_upgradable"] = self._upgradable
        snap["flat_search_cutoff"] = self._flat_search_cutoff
        return snap

    @classmethod
    def restore(cls, snap: dict, mesh=None, **kwargs) -> "DynamicIndex":
        idx = cls.__new__(cls)
        idx.threshold = snap.get("dynamic_threshold", 100_000)
        idx.mesh = mesh
        idx.dim = snap["dim"]
        idx.metric = snap["metric"]
        idx._nlist = snap.get("nlist", 0)
        idx._nprobe = snap.get("nprobe", 0)
        idx._chunk_size = snap.get("chunk_size", 8192)
        idx._upgrade_quantization = snap.get("dynamic_upgrade_quantization")
        idx._upgradable = snap.get("dynamic_upgradable", True)
        idx._flat_search_cutoff = snap.get("flat_search_cutoff",
                                           DEFAULT_FLAT_SEARCH_CUTOFF)
        idx._lock = threading.RLock()
        from weaviate_tpu.runtime import hbm_ledger

        idx._hbm_owner = hbm_ledger.current_owner()
        if snap.get("dynamic_upgraded"):
            idx._impl = IVFIndex.restore(snap, **kwargs)
        else:
            idx._impl = FlatIndex.restore(snap, mesh=mesh, **kwargs)
        return idx
