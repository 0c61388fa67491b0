"""Epochstore: immutable device epochs with on-device compaction.

The reference's LSM engine (lsmkv ``replace``: immutable segments + one
active memtable + background compaction) applied to HBM (ROADMAP item 3):
instead of one donated buffer that only ever grows, the corpus becomes a
stack of IMMUTABLE device epochs plus one small ACTIVE epoch.

- Writes land in the active epoch through the existing staged-scatter
  fast path; when it reaches ``epoch_rows`` it is SEALED — a frozen
  array whose vectors the serving lock never has to guard again — and a
  fresh active epoch opens.
- Reads fuse across the stack: every epoch runs the SAME scan programs a
  single-buffer store does (the chunked scan / bq / pq4 scan-reduce), and
  the per-epoch survivor sets merge ON DEVICE with ``ops.topk.
  merge_epoch_topk`` — the ICI-merge pattern from
  ``parallel/sharded_search.py`` turned inward, so no new Pallas kernels
  exist and multi-epoch results are bit-identical to a single-buffer
  scan (the merge is exact; per-epoch selection error never compounds).
- Deletes stay tombstone masks, but now they RECLAIM HBM: a background
  policy (``maintain()``, registered with ``runtime/cyclemanager.py`` by
  the database) folds tombstone-heavy sealed epochs — gather live rows
  into a fresh store, release the old one through the HBM ledger's
  weakref finalizers — and drops empty epochs outright.
- Global slot ids are STABLE across compaction: each epoch carries a
  local->global ``slot_map`` the merge gathers through, so the
  ``FlatIndex`` id<->slot tables never need remapping when an epoch
  repacks, and a sealed epoch can migrate to a sibling shard wholesale
  (``extract_epoch``/``drop_epoch`` — db/collection.py orchestrates the
  durable move).

Each epoch's device arrays register in the HBM ledger under a per-epoch
component label (``corpus@e3``, ``codes@e3``): /v1/debug/memory and the
``hbm_bytes`` gauge show exactly which epoch owns which bytes, and
dropping an epoch visibly releases exactly its series.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from weaviate_tpu.engine.quantized import QuantizedVectorStore
from weaviate_tpu.engine.store import DeviceVectorStore, normalize_allow_mask
from weaviate_tpu.ops.topk import merge_epoch_topk
from weaviate_tpu.runtime import hbm_ledger, kernelscope, tracing, transfer
from weaviate_tpu.runtime.transfer import DeviceResultHandle

#: default seal threshold (rows) when epochs are enabled without an
#: explicit size; 0 disables epoching entirely (legacy single buffer)
DEFAULT_EPOCH_ROWS = int(os.environ.get("WEAVIATE_TPU_EPOCH_ROWS", "0") or 0)

#: tombstone fraction past which maintain() folds a sealed epoch
COMPACT_TOMBSTONE_FRAC = 0.25


class _Epoch:
    """One epoch: a backing store + its slice of the global slot space.

    ``base`` is the first global slot; ``span`` the number of global
    slots this epoch ever covered (fixed at seal). ``map_np`` is the
    local->global table (``None`` = identity ``base + local``, the
    pre-compaction layout); ``local_of`` its inverse over ``[0, span)``
    (-1 = dropped). Compaction repacks local rows but keeps the global
    ids — only these maps change.
    """

    __slots__ = ("eid", "base", "span", "store", "sealed", "map_np",
                 "local_of", "_dev_map", "_dev_map_cap", "last_query_t",
                 "created_t")

    def __init__(self, eid: int, base: int, store):
        self.eid = eid
        self.base = base
        self.span = 0
        self.store = store
        self.sealed = False
        self.map_np: np.ndarray | None = None  # None = identity
        self.local_of: np.ndarray | None = None
        self._dev_map = None
        self._dev_map_cap = -1
        self.last_query_t = time.monotonic()
        self.created_t = time.monotonic()

    def slot_map_device(self):
        """Device int32 local->global table for the merge gather,
        rebuilt lazily when the store grows or compacts. On a mesh the
        table is REPLICATED like the candidate sets it gathers for —
        the merge then stays one SPMD program with no implicit
        re-placement (the same alignment contract the column-sharded
        allow masks keep in parallel/sharded_search.py)."""
        import jax.numpy as jnp

        cap = self.store.capacity
        if self._dev_map is None or self._dev_map_cap != cap:
            if self.map_np is None:
                host = self.base + np.arange(cap, dtype=np.int32)
            else:
                host = np.full(cap, -1, dtype=np.int32)
                w = min(len(self.map_np), cap)
                host[:w] = self.map_np[:w]
            mesh = getattr(self.store, "mesh", None)
            if mesh is not None:
                from weaviate_tpu.parallel.sharded_search import (
                    replicate_array)

                self._dev_map = replicate_array(jnp.asarray(host), mesh)
            else:
                from weaviate_tpu.runtime import placement

                self._dev_map = placement.put(
                    host, getattr(self.store, "device", None))
            self._dev_map_cap = cap
        return self._dev_map

    def locals_for(self, gslots: np.ndarray) -> np.ndarray:
        """Global slots (already in this epoch's range) -> local rows
        (-1 = dropped by compaction)."""
        off = gslots - self.base
        if self.local_of is None:
            return off
        out = np.full(len(off), -1, dtype=np.int64)
        ok = (off >= 0) & (off < len(self.local_of))
        out[ok] = self.local_of[off[ok]]
        return out

    def live_globals(self) -> np.ndarray:
        """Global slot ids of this epoch's live rows."""
        valid = self.store._valid_np
        locs = np.nonzero(valid[: self.store.capacity])[0]
        if self.map_np is None:
            return self.base + locs.astype(np.int64)
        return self.map_np[locs]

    def live_count(self) -> int:
        return int(self.store.live_count())

    def stats(self) -> dict:
        live = self.live_count()
        return {
            "epoch": self.eid,
            "base": self.base,
            "span": self.span if self.sealed else self.store.count,
            "rows": int(self.store.count),
            "live": live,
            "tombstones": max(int(self.store.count) - live, 0),
            "sealed": self.sealed,
            "capacity": int(self.store.capacity),
            "lastQueryAgeS": round(time.monotonic() - self.last_query_t, 3),
        }


class EpochStore:
    """Epoch-stacked device store with the ``DeviceVectorStore`` method
    surface (and its quantized twin's, when ``quantization`` is set).

    Thread-safe: ``_lock`` guards the epoch list and slot-space
    bookkeeping; each backing store keeps its own lock for buffer swaps
    (always acquired AFTER this one — consistent order, no ABBA).
    """

    def __init__(self, dim: int, *, metric: str = "l2-squared",
                 epoch_rows: int = 0, capacity: int = 8192,
                 dtype=None, mesh=None, chunk_size: int = 8192,
                 normalize_on_add: bool | None = None,
                 quantization: str | None = None,
                 quant_kwargs: dict | None = None):
        import jax.numpy as jnp

        if quantization == "sq":
            # one range would have to be shared by every epoch as the PQ
            # codebook is (train below): not carried through yet
            raise ValueError("quantization='sq' has no epoch-stacked form "
                             "yet: epoch_rows must be 0")
        self.dim = dim
        self.metric = metric
        self.epoch_rows = int(epoch_rows) or DEFAULT_EPOCH_ROWS or (1 << 20)
        self.dtype = dtype or jnp.float32
        self.mesh = mesh
        self.chunk_size = chunk_size
        self.quantization = quantization
        self._quant_kwargs = dict(quant_kwargs or {})
        self.normalize_on_add = (
            metric in ("cosine", "cosine-dot")
            if normalize_on_add is None else normalize_on_add)
        self._initial_capacity = min(capacity, self.epoch_rows)
        self._lock = threading.RLock()
        self._owner = hbm_ledger.current_owner()
        # the owning shard's chip: every epoch's store is built under
        # ``_owner``'s scope and commits its arrays there
        # (runtime/placement.py); None on a mesh
        self.device = None if mesh is not None else self._owner.get("device")
        self._codebook = self._quant_kwargs.pop("codebook", None)
        if quantization:
            # an epoch's store keeps its rescore rows on the host unless
            # told otherwise: the one rescore of a dispatch is the finish
            # step's, over every epoch's tier (_dispatch_quantized_locked)
            self._quant_kwargs.setdefault("rescore", "host")
        self._next_slot = 0
        self._next_eid = 0
        self.compactions_total = 0
        self.migrations_total = 0
        self._published_eids: set[str] = set()
        self.epochs: list[_Epoch] = []
        with self._lock:
            self._open_epoch_locked()

    # -- epoch lifecycle ------------------------------------------------------

    def _new_store(self, capacity: int, eid: int):
        """Backing store for one epoch, ledger-labeled per epoch and
        constructed under this store's captured owner scope (sealing
        happens on the write path, which may run outside the shard's
        construction-time scope)."""
        with hbm_ledger.owner(**self._owner):
            if self.quantization:
                return QuantizedVectorStore(
                    dim=self.dim, metric=self.metric,
                    quantization=self.quantization, capacity=capacity,
                    chunk_size=self.chunk_size, mesh=self.mesh,
                    normalize_on_add=self.normalize_on_add,
                    codebook=self._codebook,
                    component_suffix=f"@e{eid}",
                    **self._quant_kwargs)
            return DeviceVectorStore(
                dim=self.dim, metric=self.metric, capacity=capacity,
                dtype=self.dtype, mesh=self.mesh,
                chunk_size=self.chunk_size,
                normalize_on_add=self.normalize_on_add,
                component=f"corpus@e{eid}")

    def _open_epoch_locked(self) -> _Epoch:
        """Open a fresh active epoch at the current slot high-water.
        Caller holds ``_lock``."""
        eid = self._next_eid
        self._next_eid += 1
        ep = _Epoch(eid, self._next_slot,
                    self._new_store(self._initial_capacity, eid))
        self.epochs.append(ep)
        return ep

    def _seal_active_locked(self) -> None:
        """Freeze the active epoch (flush its staged rows so the sealed
        arrays are complete) and open a new one. Caller holds
        ``_lock``."""
        act = self.epochs[-1]
        if hasattr(act.store, "flush_staged"):
            act.store.flush_staged()
        act.span = int(act.store.count)
        act.sealed = True
        self._next_slot = act.base + act.span
        self._open_epoch_locked()

    def seal_active(self) -> None:
        """Public seal hook (tests, pre-migration)."""
        with self._lock:
            if self.epochs[-1].store.count > 0:
                self._seal_active_locked()

    # -- slot-space mapping ---------------------------------------------------

    def _group_by_epoch(self, gslots: np.ndarray):
        """Map global slots to (epoch, local rows) groups. Caller holds
        ``_lock``. Slots in dropped/migrated ranges are silently skipped
        (their rows are gone — the same contract as deleting an already
        tombstoned slot)."""
        if len(self.epochs) == 1 and self.epochs[0].base == 0:
            yield self.epochs[0], gslots.astype(np.int64)
            return
        bases = np.array([e.base for e in self.epochs], dtype=np.int64)
        spans = np.array(
            [e.span if e.sealed else e.store.count for e in self.epochs],
            dtype=np.int64)
        gslots = np.asarray(gslots, dtype=np.int64)
        idx = np.searchsorted(bases, gslots, side="right") - 1
        ok = idx >= 0
        ok[ok] &= gslots[ok] - bases[idx[ok]] < np.maximum(
            spans[idx[ok]], 1)
        for ei in np.unique(idx[ok]):
            sel = ok & (idx == ei)
            ep = self.epochs[int(ei)]
            loc = ep.locals_for(gslots[sel])
            loc = loc[loc >= 0]
            if len(loc):
                yield ep, loc

    # -- DeviceVectorStore surface: mutation ----------------------------------

    def add(self, vectors: np.ndarray) -> np.ndarray:
        """Append a batch; returns GLOBAL slot ids. Batches larger than
        the active epoch's remaining room split across a seal boundary —
        slot ids stay contiguous because the new epoch opens exactly at
        the high-water mark."""
        vectors = np.asarray(vectors, dtype=np.float32)
        if vectors.ndim == 1:
            vectors = vectors[None, :]
        m = len(vectors)
        out = np.empty(m, dtype=np.int64)
        with self._lock:
            pos = 0
            while pos < m:
                act = self.epochs[-1]
                room = self.epoch_rows - int(act.store.count)
                if room <= 0:
                    self._seal_active_locked()
                    continue
                take = min(room, m - pos)
                locs = act.store.add(vectors[pos:pos + take])
                out[pos:pos + take] = act.base + np.asarray(locs,
                                                            dtype=np.int64)
                pos += take
                self._next_slot = max(self._next_slot,
                                      act.base + int(act.store.count))
        return out

    def set_at(self, slots, vectors: np.ndarray) -> None:
        """Overwrite existing global slots in their owning epochs (the
        update path keeps slot ids; sealed vectors are frozen for scans
        but the donated scatter update is the same LSM exception the
        reference makes for in-place doc-id reuse)."""
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        vectors = np.atleast_2d(np.asarray(vectors, dtype=np.float32))
        with self._lock:
            if len(slots) and int(slots.max()) >= self._addressable():
                raise ValueError(
                    f"set_at slot {int(slots.max())} beyond epoch-store "
                    f"high-water {self._addressable()} — epoch stores "
                    "assign slots at add()")
            order = {int(s): i for i, s in enumerate(slots)}
            for ep, loc in self._group_by_epoch(slots):
                gl = (ep.base + loc if ep.map_np is None
                      else ep.map_np[loc])
                rows = vectors[[order[int(g)] for g in gl]]
                ep.store.set_at(loc, rows)

    def set_at_prenormalized(self, slots, vectors: np.ndarray) -> None:
        """set_at for rows normalized at their original insert
        (restore/compress paths)."""
        with self._lock:
            flips = []
            for ep in self.epochs:
                flips.append((ep.store, ep.store.normalize_on_add))
                ep.store.normalize_on_add = False
            try:
                self.set_at(slots, vectors)
            finally:
                for st, orig in flips:
                    st.normalize_on_add = orig

    def delete(self, slots) -> None:
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        if len(slots) == 0:
            return
        with self._lock:
            for ep, loc in self._group_by_epoch(slots):
                ep.store.delete(loc)

    def flush_staged(self) -> None:
        with self._lock:
            act = self.epochs[-1]
            if hasattr(act.store, "flush_staged"):
                act.store.flush_staged()

    # -- DeviceVectorStore surface: queries -----------------------------------

    def _addressable(self) -> int:
        """Exclusive upper bound on assigned global slots. Caller holds
        ``_lock``."""
        act = self.epochs[-1]
        return max(self._next_slot, act.base + int(act.store.count))

    @property
    def count(self) -> int:
        """Global slot high-water (including tombstones and migrated
        ranges) — the size filters/doc tables key against."""
        with self._lock:
            return self._addressable()

    @property
    def capacity(self) -> int:
        """Addressable global slot space (last epoch's range end) — the
        width of shared allow masks and slot->id tables."""
        with self._lock:
            act = self.epochs[-1]
            return act.base + int(act.store.capacity)

    def live_count(self) -> int:
        with self._lock:
            return sum(ep.live_count() for ep in self.epochs)

    @property
    def epoch_count(self) -> int:
        with self._lock:
            return len(self.epochs)

    @property
    def trained(self) -> bool:
        if not self.quantization:
            return True
        with self._lock:
            return self.epochs[-1].store.trained

    def train(self, vectors: np.ndarray | None = None, iters: int = 8,
              seed: int = 0) -> None:
        """Fit the (shared) PQ codebook and re-encode every epoch — one
        codebook across the stack, so candidates merge in one code
        space."""
        if self.quantization != "pq":
            return
        with self._lock:
            if vectors is None:
                parts = []
                for ep in self.epochs:
                    lg = ep.live_globals()
                    if len(lg):
                        loc = ep.locals_for(lg)
                        parts.append(ep.store._vectors_for(loc))
                vectors = (np.concatenate(parts) if parts
                           else np.zeros((0, self.dim), np.float32))
            act = self.epochs[-1]
            act.store.train(vectors, iters=iters, seed=seed)
            self._codebook = act.store.codebook
            for ep in self.epochs[:-1]:
                ep.store.codebook = self._codebook
                ep.store._reencode_all()
                ep.store._hbm_sync()

    def get(self, slots) -> np.ndarray:
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        out = np.zeros((len(slots), self.dim), dtype=np.float32)
        order = {}
        with self._lock:
            for i, s in enumerate(slots):
                order.setdefault(int(s), []).append(i)
            for ep, loc in self._group_by_epoch(slots):
                gl = (ep.base + loc if ep.map_np is None
                      else ep.map_np[loc])
                rows = ep.store.get(loc)
                for g, row in zip(gl, rows):
                    for i in order.get(int(g), ()):
                        out[i] = row
        return out

    def _slice_allow(self, allow_mask, ep: _Epoch):
        """Column-slice a global allow mask to one epoch's LOCAL row
        space (compaction-aware through ``local_of``). Caller holds
        ``_lock``."""
        if allow_mask is None:
            return None
        base, cap = ep.base, int(ep.store.capacity)
        span = ep.span if ep.sealed else int(ep.store.count)
        if allow_mask.ndim == 1:
            seg = np.zeros(cap, dtype=bool)
            w = max(min(len(allow_mask) - base, span), 0)
            if w > 0:
                g_allowed = allow_mask[base:base + w]
                if ep.local_of is None:
                    seg[:w] = g_allowed
                else:
                    loc = ep.local_of[:w][g_allowed[: len(ep.local_of)]]
                    loc = loc[(loc >= 0) & (loc < cap)]
                    seg[loc] = True
            return seg
        b = allow_mask.shape[0]
        seg = np.zeros((b, cap), dtype=bool)
        w = max(min(allow_mask.shape[1] - base, span), 0)
        if w > 0:
            g_allowed = allow_mask[:, base:base + w]
            if ep.local_of is None:
                seg[:, :w] = g_allowed
            else:
                lo = ep.local_of[:w]
                ok = lo >= 0
                seg[:, lo[ok]] = g_allowed[:, ok]
        return seg

    def search(self, queries: np.ndarray, k: int,
               allow_mask: np.ndarray | None = None):
        return self.search_async(queries, k, allow_mask).result()

    def search_async(self, queries: np.ndarray, k: int,
                     allow_mask: np.ndarray | None = None
                     ) -> DeviceResultHandle:
        """Dispatch-only epoch-fused search: every epoch's scan kernel
        dispatches under ``_lock``, survivor sets merge ON DEVICE
        (``merge_epoch_topk``), and the returned handle's finish step
        runs the one global host rescore (quantized) — so the zero-sync
        serving pipeline drains exactly one D2H per batch no matter how
        many epochs exist."""
        queries = np.asarray(queries, dtype=np.float32)
        squeeze = queries.ndim == 1
        if squeeze:
            queries = queries[None, :]
        now = time.monotonic()
        with self._lock:
            eps = list(self.epochs)
            for ep in eps:
                ep.last_query_t = now
            if len(eps) == 1 and eps[0].base == 0 and eps[0].map_np is None:
                # single-epoch passthrough: the epoch IS the store —
                # full engine behavior including the gathered cutover
                return eps[0].store.search_async(
                    queries[0] if squeeze else queries, k, allow_mask)
        allow_mask = normalize_allow_mask(allow_mask, len(queries))
        with tracing.span("store.epoch_scan", epochs=len(eps),
                          queries=len(queries), k=k,
                          quantized=bool(self.quantization),
                          filtered=allow_mask is not None):
            with self._lock:
                eps = [e for e in self.epochs if int(e.store.count) > 0]
                if not eps:
                    b = len(queries)
                    d0 = np.full((b, k), np.float32(np.inf), np.float32)
                    i0 = np.full((b, k), -1, np.int64)
                    return DeviceResultHandle.ready(
                        (d0[0], i0[0]) if squeeze else (d0, i0))
                if self.quantization:
                    return self._dispatch_quantized_locked(
                        eps, queries, k, allow_mask, squeeze)
                return self._dispatch_flat_locked(
                    eps, queries, k, allow_mask, squeeze)

    def _dispatch_flat_locked(self, eps, queries, k, allow_mask, squeeze):
        """Per-epoch flat scans + device merge. Caller holds ``_lock``."""
        parts, maps = [], []
        for ep in eps:
            d, i = ep.store.epoch_scan(
                queries, k, self._slice_allow(allow_mask, ep))
            parts.append((d, i))
            maps.append(ep.slot_map_device())
        # EXPLAIN (host ints, no-op without a sink): epoch fanout and
        # the on-device merge shape of this dispatch
        kernelscope.explain_note(
            "epochs", epochs=len(parts), merge_fanin=len(parts),
            k_merge=k, rescore_mode="none", queries=len(queries), k=k)
        md, mi = merge_epoch_topk(tuple(parts), tuple(maps), k=k)

        def _finish(d_np, i_np, _squeeze=squeeze):
            i_np = i_np.astype(np.int64, copy=False)
            if _squeeze:
                return d_np[0], i_np[0]
            return d_np, i_np

        return DeviceResultHandle(
            (md, mi), finish=_finish,
            attrs={"rows": self.capacity, "queries": len(queries),
                   "k": k, "epochs": len(parts)})

    def _dispatch_quantized_locked(self, eps, queries, k, allow_mask,
                                   squeeze):
        """Per-epoch compressed scans + device merge + ONE global host
        rescore in the finish step. Caller holds ``_lock``."""
        template = eps[-1].store
        qn = template._maybe_norm(queries)
        mode = template.rescore_mode()
        rl = template.rescore_limit
        snaps = []  # (base, span, local_of, tiers, count) at dispatch
        parts, maps = [], []
        # "fused" (single-device rows resident on the device) degrades to
        # "post" here: the merged candidates span per-epoch tier
        # SNAPSHOTS, so the exact pass must route through the epoch-aware
        # _vectors_for gather — a scan's own tail has no cross-snapshot
        # view
        if mode == "fused":
            mode = "post"
        # both rescore modes need the oversampled candidate set — the
        # inline (in-SPMD) rescore sees k_cand code-distance candidates
        # per epoch exactly like the single-buffer path; only
        # rescore-less stores scan at k
        k_cand = max(k * rl, k) if mode in ("post", "inline") else k
        for ep in eps:
            cap = int(ep.store.capacity)
            kc = min(k_cand, cap)
            d, i, tiers = ep.store.epoch_scan(
                qn, kc, kc if mode == "post" else min(k, cap),
                self._slice_allow(allow_mask, ep), pre_normalized=True)
            parts.append((d, i))
            maps.append(ep.slot_map_device())
            snaps.append((ep.base, ep.span or int(ep.store.count),
                          None if ep.local_of is None
                          else ep.local_of.copy(), tiers,
                          int(ep.store.count)))
        k_merge = k_cand if mode == "post" else k
        # EXPLAIN: epoch fanout, merge shape and the (possibly fused->
        # post degraded) rescore mode of this dispatch — host ints only
        kernelscope.explain_note(
            "epochs", epochs=len(parts), merge_fanin=len(parts),
            k_merge=k_merge, k_cand=k_cand, rescore_mode=mode,
            queries=len(queries), k=k)
        md, mi = merge_epoch_topk(tuple(parts), tuple(maps), k=k_merge)
        cap_total = self.capacity
        dim = self.dim

        def _vectors_for(slots, _snaps=snaps, _dim=dim):
            """Global-slot -> full-precision rows across the dispatch-
            time epoch tier snapshots (the finish step's rescore feed)."""
            slots = np.asarray(slots, dtype=np.int64)
            out = np.zeros((len(slots), _dim), dtype=np.float32)
            for base, span, local_of, tiers, cnt in _snaps:
                sel = (slots >= base) & (slots < base + max(span, 1))
                if not sel.any():
                    continue
                loc = slots[sel] - base
                if local_of is not None:
                    lo = np.full(len(loc), 0, dtype=np.int64)
                    ok = loc < len(local_of)
                    lo[ok] = np.where(local_of[loc[ok]] >= 0,
                                      local_of[loc[ok]], 0)
                    loc = lo
                loc = np.clip(loc, 0, max(cnt - 1, 0))
                out[sel] = QuantizedVectorStore._tier_vectors(
                    *tiers, loc)
            return out

        def _finish(d_np, i_np, _queries=qn, _k=k, _squeeze=squeeze,
                    _mode=mode, _cap=cap_total):
            i_np = i_np.astype(np.int64, copy=False)
            if _mode == "post":
                with tracing.span("store.host_rescore",
                                  candidates=int(i_np.shape[1])):
                    d_np, i_np = template._host_rescore(
                        _queries, i_np, _k, capacity=_cap,
                        vectors_for=_vectors_for)
            out_d = d_np[:, :_k].astype(np.float32)
            out_i = i_np[:, :_k]
            if _squeeze:
                return out_d[0], out_i[0]
            return out_d, out_i

        return DeviceResultHandle(
            (md, mi), finish=_finish,
            attrs={"rows": cap_total, "queries": len(queries), "k": k,
                   "epochs": len(parts),
                   "quantization": self.quantization})

    def search_by_distance(self, query: np.ndarray, max_distance: float,
                           allow_mask: np.ndarray | None = None):
        k = min(64, max(self.capacity, 1))
        while True:
            d, i = self.search(query, k, allow_mask)
            within = d <= max_distance
            if ((~within).any() or k >= self.capacity
                    or within.sum() >= self.live_count()):
                return d[within], i[within]
            k = min(k * 4, self.capacity)

    # -- maintenance: compaction / migration ----------------------------------

    def compact(self) -> np.ndarray:
        """Full-store compaction with STABLE global slots: every sealed
        epoch folds its tombstones in place (live global ids unchanged);
        returns the old->new mapping the FlatIndex contract expects —
        identity for live slots, -1 for dead ones."""
        with self._lock:
            cap = self.capacity
            for ep in list(self.epochs):
                if ep.sealed:
                    if ep.live_count() == 0:
                        self.drop_epoch(ep.eid)
                    elif int(ep.store.count) > ep.live_count():
                        self.compact_epoch(ep.eid)
            mapping = np.full(cap, -1, dtype=np.int64)
            for ep in self.epochs:
                lg = ep.live_globals()
                lg = lg[lg < cap]
                mapping[lg] = lg
            return mapping

    def compact_epoch(self, eid: int) -> bool:
        """Fold one sealed epoch's tombstones on device: the backing
        store repacks live rows into a right-sized fresh allocation
        (its ``compact()`` routes the one D2H through ``transfer.d2h``),
        the old arrays release through the ledger's weakref finalizers,
        and this epoch's local->global maps re-point — global slot ids
        do not change, so no index table anywhere needs remapping."""
        with self._lock:
            ep = self._epoch_by_id(eid)
            if ep is None or not ep.sealed:
                return False
            old_cap = int(ep.store.capacity)
            old_map = (ep.base + np.arange(old_cap, dtype=np.int64)
                       if ep.map_np is None else ep.map_np)
            with tracing.span("store.compact_epoch", epoch=ep.eid,
                              rows=old_cap):
                mapping = ep.store.compact()
            new_cap = int(ep.store.capacity)
            new_map = np.full(new_cap, -1, dtype=np.int64)
            moved = mapping >= 0
            src = np.nonzero(moved)[0]
            new_map[mapping[src]] = old_map[src]
            ep.map_np = new_map
            local_of = np.full(ep.span, -1, dtype=np.int64)
            filled = new_map >= 0
            off = new_map[filled] - ep.base
            ok = (off >= 0) & (off < ep.span)
            local_of[off[ok]] = np.nonzero(filled)[0][ok]
            ep.local_of = local_of
            ep._dev_map = None
            self.compactions_total += 1
            try:
                from weaviate_tpu.runtime.metrics import epoch_compactions

                epoch_compactions.labels(
                    self._owner.get("collection", "_unowned"),
                    self._owner.get("shard", "-")).inc()
            except Exception:  # noqa: BLE001 — observability must not gate
                pass
            self._publish_metrics_locked()
            return True

    def drop_epoch(self, eid: int) -> bool:
        """Remove an epoch from the stack (post-migration cutover, or an
        all-tombstone epoch). Its device arrays release through the
        stores' ledger finalizers as soon as the last in-flight handle
        drops its reference."""
        with self._lock:
            ep = self._epoch_by_id(eid)
            if ep is None:
                return False
            if ep is self.epochs[-1] and not ep.sealed:
                return False  # never drop the live write target
            self.epochs.remove(ep)
            if not self.epochs:
                self._open_epoch_locked()
            self._publish_metrics_locked()
            return True

    def extract_epoch(self, eid: int):
        """Serialize one epoch for migration: returns ``(global_slots
        [n], vectors [n, d] f32)`` of its live rows (one ``transfer.d2h``
        for the flat tier; the quantized form reads its full-precision
        tier). The epoch itself is untouched — the caller cuts over
        (``drop_epoch``) only after the target shard acked the ingest."""
        with self._lock:
            ep = self._epoch_by_id(eid)
            if ep is None:
                return np.empty(0, np.int64), np.zeros((0, self.dim),
                                                       np.float32)
            if hasattr(ep.store, "flush_staged"):
                ep.store.flush_staged()
            lg = ep.live_globals()
            loc = ep.locals_for(lg)
            if isinstance(ep.store, QuantizedVectorStore):
                rows = ep.store._vectors_for(loc)
            else:
                (vec_host,) = transfer.d2h(ep.store.vectors)
                rows = vec_host[loc].astype(np.float32)
            return lg, rows

    def live_globals_of(self, eid: int) -> np.ndarray:
        """Global slot ids of one epoch's live rows (the migration
        planner maps these through the index's slot->doc table)."""
        with self._lock:
            ep = self._epoch_by_id(eid)
            return (np.empty(0, np.int64) if ep is None
                    else ep.live_globals())

    def coldest_sealed(self) -> int | None:
        """The sealed epoch least recently touched by a query (the
        migration victim when the ledger crosses watermark)."""
        with self._lock:
            cands = [e for e in self.epochs if e.sealed
                     and e.live_count() > 0]
            if not cands:
                return None
            return min(cands, key=lambda e: e.last_query_t).eid

    def maintain(self, tombstone_frac: float = COMPACT_TOMBSTONE_FRAC
                 ) -> bool:
        """One background cycle (cyclemanager callback body): seal an
        overfull active epoch, drop empty sealed epochs, fold
        tombstone-heavy ones. Returns True when work was done."""
        did = False
        with self._lock:
            if int(self.epochs[-1].store.count) >= self.epoch_rows:
                self._seal_active_locked()
                did = True
            for ep in list(self.epochs):
                if not ep.sealed:
                    continue
                total = int(ep.store.count)
                live = ep.live_count()
                if total and live == 0:
                    did = self.drop_epoch(ep.eid) or did
                elif total and (total - live) / total >= tombstone_frac:
                    did = self.compact_epoch(ep.eid) or did
            self._publish_metrics_locked()
        return did

    def _epoch_by_id(self, eid: int) -> _Epoch | None:
        """Caller holds ``_lock``."""
        for ep in self.epochs:
            if ep.eid == eid:
                return ep
        return None

    def epoch_stats(self) -> list[dict]:
        with self._lock:
            return [ep.stats() for ep in self.epochs]

    # -- observability --------------------------------------------------------

    def _publish_metrics_locked(self) -> None:
        """Refresh the ``weaviate_tpu_epoch_*`` gauges; stale per-epoch
        series are removed when their epoch compacts away or migrates.
        Caller holds ``_lock``; gauges have their own locks and never
        call back in."""
        try:
            from weaviate_tpu.runtime.metrics import (epoch_count,
                                                      epoch_live_rows,
                                                      epoch_tombstone_rows)

            col = self._owner.get("collection", "_unowned")
            shard = self._owner.get("shard", "-")
            epoch_count.labels(col, shard).set(float(len(self.epochs)))
            seen = set()
            for ep in self.epochs:
                label = f"e{ep.eid}"
                seen.add(label)
                st = ep.stats()
                epoch_live_rows.labels(col, shard, label).set(
                    float(st["live"]))
                epoch_tombstone_rows.labels(col, shard, label).set(
                    float(st["tombstones"]))
            for stale in self._published_eids - seen:
                epoch_live_rows.remove(col, shard, stale)
                epoch_tombstone_rows.remove(col, shard, stale)
            self._published_eids = seen
        except Exception:  # noqa: BLE001 — observability must not gate
            pass

    # -- persistence ----------------------------------------------------------

    def snapshot(self) -> dict:
        """Flattened host snapshot over the global slot space (epoch
        boundaries are an HBM layout detail — restore re-splits by
        ``epoch_rows``). Compatible with the ``DeviceVectorStore``
        snapshot schema plus the epoch config."""
        with self._lock:
            self.flush_staged()
            import jax.numpy as jnp

            cap = self._addressable()
            vecs = np.zeros((cap, self.dim), dtype=np.float32)
            valid = np.zeros(max(cap, 1), dtype=bool)
            for ep in self.epochs:
                lg = ep.live_globals()
                lg = lg[lg < cap]
                if not len(lg):
                    continue
                loc = ep.locals_for(lg)
                if isinstance(ep.store, QuantizedVectorStore):
                    rows = ep.store._vectors_for(loc)
                else:
                    (vec_host,) = transfer.d2h(ep.store.vectors)
                    rows = vec_host[loc]
                vecs[lg] = rows
                valid[lg] = True
            snap = {
                "vectors": vecs,
                "valid": valid[:max(cap, 1)],
                "count": cap,
                "dim": self.dim,
                "metric": self.metric,
                "dtype": jnp.dtype(self.dtype).name,
                "chunk_size": self.chunk_size,
                "epoch_rows": self.epoch_rows,
                "quantization": self.quantization,
            }
            if self.quantization:
                snap["quant_kwargs"] = dict(self._quant_kwargs)
                snap["codebook"] = (
                    None if self._codebook is None
                    else np.asarray(self._codebook.centroids))
            return snap

    @classmethod
    def restore(cls, snap: dict, mesh=None, **kwargs) -> "EpochStore":
        import jax.numpy as jnp

        store = cls(
            dim=snap["dim"], metric=snap["metric"],
            epoch_rows=snap.get("epoch_rows", 0),
            dtype=jnp.dtype(snap.get("dtype", "float32")),
            mesh=mesh, chunk_size=snap.get("chunk_size", 8192),
            quantization=snap.get("quantization"),
            quant_kwargs=snap.get("quant_kwargs"), **kwargs)
        if snap.get("codebook") is not None:
            from weaviate_tpu.ops import pq as pq_ops

            store._codebook = pq_ops.PQCodebook(
                jnp.asarray(snap["codebook"]))
            store.epochs[-1].store.codebook = store._codebook
        live = np.nonzero(snap["valid"])[0]
        store._restore_rows(live, snap["vectors"], int(snap["count"]))
        return store

    def _restore_rows(self, live: np.ndarray, vectors: np.ndarray,
                      count: int) -> None:
        """Rebuild the epoch stack over ``[0, count)`` global slots from
        flattened rows (restore / compress): epochs re-split every
        ``epoch_rows`` slots, identity maps, all but the last sealed."""
        with self._lock:
            assert self._next_slot == 0 and len(self.epochs) == 1, \
                "_restore_rows only populates a fresh store"
            for base in range(0, max(count, 1), self.epoch_rows):
                act = self.epochs[-1]
                act.base = base
                hi = min(base + self.epoch_rows, count)
                sel = live[(live >= base) & (live < hi)]
                if len(sel):
                    # pre-size the store so local slots exist, then
                    # overwrite the live ones (already normalized rows)
                    act.store.set_at(
                        np.array([hi - base - 1]),
                        np.zeros((1, self.dim), np.float32))
                    flips = act.store.normalize_on_add
                    act.store.normalize_on_add = False
                    try:
                        act.store.set_at(sel - base, vectors[sel])
                    finally:
                        act.store.normalize_on_add = flips
                    # the pre-size scratch row is dead unless slot hi-1
                    # is genuinely live
                    if (hi - 1) not in sel:
                        act.store.delete(np.array([hi - base - 1]))
                elif hi > base:
                    act.store.set_at(
                        np.array([hi - base - 1]),
                        np.zeros((1, self.dim), np.float32))
                    act.store.delete(np.array([hi - base - 1]))
                if hi < count:
                    self._seal_active_locked()
            self._next_slot = count
            self._publish_metrics_locked()
