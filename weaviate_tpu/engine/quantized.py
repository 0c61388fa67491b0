"""Quantized (PQ/BQ/SQ) vector store: compressed codes in HBM, exact rescore.

Reference parity:
- flat BQ path with rescore: vector/flat/index.go:347 (searchByVectorBQ)
- HNSW runtime compression hook: vector/hnsw/compress.go:38 (train on
  current contents, swap cache for a compressed one)
- compressor plumbing: compressionhelpers/compression.go:37
- scalar quantization: upstream compressionhelpers/scalar_quantization.go
  (v1.26+; ops/sq.py)
- compression composes with sharding because quantizer state is per-shard
  (compress.go:38 inside usecases/sharding/state.go:28) — here the same
  composition is one SPMD program over a device mesh
  (parallel/sharded_search.py:sharded_quantized_topk).

Memory layout: HBM holds the codes ([C, m] uint8 for PQ — 16-64x
smaller than f32; [C, w] uint32 sign-bits for BQ — 32x smaller; [C, d]
int8 for SQ, one byte a dimension, 4x smaller, with ``row_terms`` [C]
int32 beside them: what each row adds to the integer score of any scan,
written once with its code) plus the valid mask; on a mesh PQ and BQ are
row-sharded over the ``shard`` axis (SQ has no SPMD form yet and refuses
a mesh).

A store keeps ONE full-precision tier, the rows the exact rescore reads,
and where it lives is decided by residency, not by a name:

- **float32 rows in HBM beside the codes** (``rescore_rows`` [C, d
  rounded up to whole 128-lane tiles: ``_row_lanes``]), wherever the memory watchdog grants them (``runtime/memwatch.py
  device_fits``: the device budget and high watermark every import
  passes): what a single-device store gets unless it is told otherwise,
  so what every compressed class of a ``Server`` gets. The rescore is
  then the LAST step of the scan's own program (``jit_bq_topk``,
  ``jit_pq_topk``, ``jit_sq_topk``; ops/candidates.py ``rescore_tail``):
  the candidates' rows are gathered, scored in float32 arithmetic
  (``Precision.HIGHEST``) and cut to the request's k on the device, one
  program a dispatch, [B, k] back to the host. No host copy is kept:
  ``get`` and ``snapshot`` read the device rows.
- **float32 rows in host RAM** (``_host_vectors``), where the watchdog
  does not grant them, or from the grow that would pass its watermark on
  (one D2H, once, logged; never promoted back): the scan returns its
  oversampled candidates and the handle's finish step gathers and scores
  them in numpy. Also what ``rescore="host"`` pins (the epoch store's
  per-epoch stores, whose merged candidates span tier snapshots) and
  what a mesh-sharded store defaults to.
- ``rescore="device"`` on a MESH: bf16 rows row-sharded next to the
  codes; each device rescores ITS OWN candidates inside the same SPMD
  program before the ICI merge (owning-device rescore — vectors never
  cross the interconnect). No cell and no ``Server`` reaches it yet
  (``Server`` passes ``mesh=None``).
- ``rescore="none"``: codes only — the capacity regime (e.g. 100M x 768
  BQ = 9.6 GB across a mesh). Results are code-distance ordered unless
  ``fetch_fn`` (ids -> f32 rows, e.g. backed by the shard's LSM objects
  bucket) is given, which re-enables exact rescore from durable storage.
"""

from __future__ import annotations

import functools
import logging
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu.ops import bq as bq_ops
from weaviate_tpu.ops import pq as pq_ops
from weaviate_tpu.ops import sq as sq_ops
from weaviate_tpu.ops.distances import normalize_np
from weaviate_tpu.parallel.mesh import n_row_shards, shardable_capacity
from weaviate_tpu.runtime import hbm_ledger, kernelscope, placement, tracing
from weaviate_tpu.runtime.memwatch import MemoryMonitor
from weaviate_tpu.runtime.metrics import rescore_dispatch_total
from weaviate_tpu.runtime.transfer import DeviceResultHandle

logger = logging.getLogger(__name__)

_DEFAULT_CHUNK = 8192


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_codes(codes, valid, slots, new_codes, write_mask):
    """Donated in-place scatter of code rows (mode='drop' makes redirected
    padding rows no-ops) — same mutability model as store._scatter_rows."""
    tgt = jnp.where(write_mask, slots, codes.shape[0])
    codes = codes.at[tgt].set(new_codes, mode="drop")
    valid = valid.at[tgt].set(True, mode="drop")
    return codes, valid


@functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                   static_argnames=("metric",))
def _scatter_sq_rows(codes, terms, valid, slots, rows, write_mask, params,
                     metric: str):
    """SQ's write: float32 rows are encoded where they land (codes and
    the per-row int32 terms scattered in the same program), so nothing
    comes back to the host and the writer waits for no device result."""
    new_codes, new_terms = sq_ops.sq_encode(rows, params, metric)
    tgt = jnp.where(write_mask, slots, codes.shape[0])
    return (codes.at[tgt].set(new_codes, mode="drop"),
            terms.at[tgt].set(new_terms, mode="drop"),
            valid.at[tgt].set(True, mode="drop"))


# rows a piece of a write that carries float32 rows to the device (SQ's
# encode, the resident rescore rows): bounds the block that goes up
_WRITE_ROWS = 65536


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_prefix(prefix_t, slots, new_cols, write_mask):
    """Donated column scatter into the transposed prefix array [Wp, C]."""
    tgt = jnp.where(write_mask, slots, prefix_t.shape[1])
    return prefix_t.at[:, tgt].set(new_cols, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rescore(rows, slots, new_rows, write_mask):
    """``new_rows`` [m, dim] into the resident ``rows`` [C, >= dim] (the
    float32 tier is as wide as whole lanes: ``_row_lanes``)."""
    tgt = jnp.where(write_mask, slots, rows.shape[0])
    new_rows = jnp.pad(new_rows.astype(rows.dtype),
                       ((0, 0), (0, rows.shape[1] - new_rows.shape[1])))
    return rows.at[tgt].set(new_rows, mode="drop")


def _row_lanes(dim: int) -> int:
    """Width of the resident float32 rescore rows: ``dim`` rounded up to
    whole 128-lane tiles, the rest zeros (they add nothing to a dot
    product or a squared difference). The chip lays a float32 [rows,
    960] or [rows, 96] out with the ROWS on the lanes, and a program
    that gathers rows from it first copies ALL of it, row-major, every
    dispatch (1 GB at 262,144 x 960: PERF.md, PR 32 and PR 38); a width
    of whole tiles is kept row-major, and a row is one contiguous
    read."""
    return -(-dim // 128) * 128


@functools.partial(jax.jit, donate_argnums=(0,))
def _clear_valid(valid, slots):
    return valid.at[slots].set(False, mode="drop")


@functools.partial(jax.jit, donate_argnums=(1,))
def _set_valid(codes, valid, slots, write_mask):
    tgt = jnp.where(write_mask, slots, codes.shape[0])
    return valid.at[tgt].set(True, mode="drop")


class QuantizedVectorStore:
    """PQ-, BQ- or SQ-compressed store with the DeviceVectorStore method
    surface.

    On a mesh, codes (and bf16 rescore rows in ``rescore="device"`` mode)
    are row-sharded over the ``shard`` axis and every search runs SPMD.

    ``rescore=None`` (what every caller but a test or the epoch store
    leaves it at) is the residency rule of the module docstring: a
    single-device store keeps float32 rows on the device where
    ``memwatch`` (the process's ``MemoryMonitor``, handed down by the
    shard; None: one that reads the backend's own limit) grants them
    and on the host where not; a mesh-sharded one keeps them on the
    host.
    """

    # ``search_async`` takes per-query filters an index has already
    # packed and put on the device (``AllowBits``) where ``mesh`` is None
    takes_allow_operands = True

    def __init__(
        self,
        dim: int,
        metric: str = "l2-squared",
        quantization: str = "pq",
        capacity: int = _DEFAULT_CHUNK,
        chunk_size: int = _DEFAULT_CHUNK,
        pq_segments: int | None = None,
        pq_centroids: int = 16,
        # oversampling multiplier: the compressed scan returns
        # rescore_limit*k candidates for exact rescore (reference keeps an
        # absolute rescoreLimit, flat/index.go:301; 16x measures ~0.99
        # candidate-recall@10 on clustered 96-dim data)
        rescore_limit: int = 16,
        normalize_on_add: bool | None = None,
        codebook: pq_ops.PQCodebook | None = None,
        mesh=None,
        rescore: str | None = None,
        fetch_fn=None,
        # BQ capacity regime: width (in bits, multiple of 128) of a
        # separately-stored transposed sign-bit prefix. Searches then run
        # two-stage (prefix scan -> gathered full-width refine ->
        # rescore), reading ~prefix_bits/dim of the code bytes in stage 1
        # (ops/bq.py bq_topk_twostage). Single-device stores only — the
        # mesh path scans full codes per shard.
        prefix_bits: int | None = None,
        # HBM-ledger component suffix ("@e3" for epoch stores): codes/
        # prefix/rescore_rows register as "codes@e3" etc. so per-epoch
        # device bytes are individually visible and individually released
        component_suffix: str = "",
        # the gate device allocations pass (runtime/memwatch.py): decides
        # whether the float32 rescore rows may live in HBM
        memwatch: MemoryMonitor | None = None,
    ):
        if quantization not in ("pq", "bq", "sq"):
            raise ValueError(f"unknown quantization {quantization!r}")
        if quantization == "sq":
            # refused, never dropped: each of these would serve another
            # store than the one asked for
            if mesh is not None:
                raise ValueError(
                    "quantization='sq' has no mesh-sharded scan yet")
            if prefix_bits:
                raise ValueError("prefix_bits requires quantization pq or bq")
            if metric not in sq_ops.SQ_METRICS:
                raise ValueError(
                    f"no scalar-quantized scan for metric {metric!r}")
            if dim > sq_ops.SQ_MAX_DIM:
                raise ValueError(
                    f"quantization='sq' sums in int32: dim {dim} is over "
                    f"{sq_ops.SQ_MAX_DIM}")
        if rescore is None:
            rescore = "device" if mesh is None else "host"
        if rescore not in ("host", "device", "none"):
            raise ValueError(f"unknown rescore mode {rescore!r}")
        self.dim = dim
        self.metric = metric
        self.quantization = quantization
        self.chunk_size = chunk_size
        # HBM ledger wiring — same pattern as DeviceVectorStore: labels
        # captured from the ambient owner scope, entries updated across
        # grows, finalizer-released when the store is dropped; with them
        # the owning shard's chip, where every array this store makes is
        # committed (runtime/placement.py; None on a mesh)
        self._hbm_owner = hbm_ledger.current_owner()
        self.device = None if mesh is not None \
            else self._hbm_owner.get("device")
        self.rescore_limit = rescore_limit
        self.rescore = rescore
        self._memwatch = memwatch or MemoryMonitor()
        self.fetch_fn = fetch_fn
        if pq_segments:
            self.pq_segments = pq_segments
        else:
            self.pq_segments = pq_ops.default_pq_segments(dim, pq_centroids)
        self.pq_centroids = pq_centroids
        self.codebook = codebook
        # SQ's twin of the codebook: the fitted range (train, restore)
        self.sq_quantizer = None
        self.normalize_on_add = (
            metric in ("cosine", "cosine-dot")
            if normalize_on_add is None
            else normalize_on_add
        )
        self.mesh = mesh
        self.n_shards = n_row_shards(mesh)
        self.hbm_component_suffix = component_suffix
        self.prefix_words = 0
        if prefix_bits and mesh is None:
            wp = max(4, prefix_bits // 32 // 4 * 4)
            if quantization == "bq":
                # a prefix at least as wide as the code itself saves
                # nothing (and would crash the column scatter for
                # dim <= 128)
                if wp < bq_ops.bq_words(dim):
                    self.prefix_words = wp
            else:
                # PQ two-stage: the prefix is a BQ SIGN slice of the raw
                # vectors (ops/pq.pq_topk_twostage) — it needs that many
                # leading dims to exist
                if wp * 32 <= dim:
                    self.prefix_words = wp
        from weaviate_tpu.ops.pallas_kernels import recommended

        self.use_pallas = recommended()
        self._lock = threading.RLock()
        self._count = 0
        self._hbm_keys: dict[str, int] = {}
        weakref.finalize(self, hbm_ledger.ledger.release_many,
                         self._hbm_keys.values())
        self.capacity = self._align(capacity)
        self._valid_np = np.zeros(self.capacity, dtype=bool)
        self._alloc_codes()

    # -- internals -----------------------------------------------------------

    def _align(self, capacity: int) -> int:
        capacity = max(capacity, 2 * self.n_shards)
        capacity = _next_pow2(capacity)
        cs = max(1, min(self.chunk_size, capacity // self.n_shards))
        return shardable_capacity(capacity, self.n_shards, cs)

    def _placed(self, arr, dim=0):
        if self.mesh is None:
            return placement.put(arr, self.device)
        from weaviate_tpu.parallel.sharded_search import shard_array

        return shard_array(jnp.asarray(arr), self.mesh, dim=dim)

    def _placed_replicated(self, arr):
        if self.mesh is None:
            return placement.put(arr, self.device)
        from weaviate_tpu.parallel.sharded_search import replicate_array

        return replicate_array(jnp.asarray(arr), self.mesh)

    def _operand(self, arr):
        """A host operand of one program (a query block, rows to encode)
        where the program will run: straight to the store's device."""
        if self.mesh is None:
            return placement.put(arr, self.device)
        return jnp.asarray(arr)

    @property
    def codebook(self):
        return self._codebook

    @codebook.setter
    def codebook(self, codebook):
        """Wherever it was fitted or read from, a codebook this store
        scans with lies on the store's device. Caller holds ``_lock``
        (``train``) or has the only reference to the store (``__init__``,
        ``restore``, an epoch store handing its codebook down)."""
        if codebook is not None and self.device is not None:
            codebook = pq_ops.PQCodebook(
                placement.put(codebook.centroids, self.device))
        self._codebook = codebook

    @property
    def sq_quantizer(self):
        return self._sq_quantizer

    @sq_quantizer.setter
    def sq_quantizer(self, quantizer):
        """Caller holds ``_lock`` or has the only reference, as for
        ``codebook``."""
        if quantizer is not None and self.device is not None:
            quantizer = quantizer._replace(
                params=placement.put(quantizer.params, self.device))
        self._sq_quantizer = quantizer

    def _code_width(self) -> int:
        if self.quantization == "pq":
            return self.pq_segments
        if self.quantization == "sq":
            return self.dim
        return bq_ops.bq_words(self.dim)

    def _code_dtype(self):
        return {"pq": jnp.uint8, "sq": jnp.int8}.get(self.quantization,
                                                     jnp.uint32)

    def _scan_metric(self) -> str:
        return ("cosine" if self.metric in ("cosine", "cosine-dot")
                else self.metric)

    def _zeros(self, shape, dtype):
        if self.mesh is None:
            return placement.zeros(shape, dtype, self.device)
        from weaviate_tpu.parallel.sharded_search import sharded_zeros

        return sharded_zeros(shape, dtype, self.mesh)

    def _alloc_codes(self):
        w = self._code_width()
        self.codes = self._zeros((self.capacity, w), self._code_dtype())
        self.row_terms = (
            self._zeros((self.capacity,), jnp.int32)
            if self.quantization == "sq" else None
        )
        self.prefix_t = (
            placement.zeros((self.prefix_words, self.capacity), jnp.uint32,
                            self.device)
            if self.prefix_words else None
        )
        if self._valid_np.any():
            self.valid = self._placed(self._valid_np)
        else:
            self.valid = self._zeros((self.capacity,), jnp.bool_)
        self._alloc_rows()
        self._hbm_sync()

    def _rows_fit(self, capacity: int) -> bool:
        """May float32 rescore rows of ``capacity`` slots be allocated on
        the device? The watchdog's answer, on top of what is there now
        (a grow holds the old rows until the new ones are written)."""
        return self._memwatch.device_fits(
            capacity * _row_lanes(self.dim) * 4, device=self.device)

    def _alloc_rows(self):
        """The full-precision tier, empty, at this capacity: on the
        device where it is wanted and granted, else on the host. Caller
        holds ``_lock`` (or is ``__init__``)."""
        self.rescore_rows = self._host_vectors = None
        if self.rescore == "device" and self.mesh is not None:
            self.rescore_rows = self._zeros((self.capacity, self.dim),
                                            jnp.bfloat16)
        elif self.rescore == "device" and self._rows_fit(self.capacity):
            self.rescore_rows = placement.zeros(
                (self.capacity, _row_lanes(self.dim)), jnp.float32,
                self.device)
        elif self.rescore != "none":
            if self.rescore == "device":
                logger.warning(
                    "%s store of %d x %d: no room on the device for its "
                    "float32 rescore rows (%d bytes); they stay on the "
                    "host", self.quantization, self.capacity, self.dim,
                    self.capacity * _row_lanes(self.dim) * 4)
            self._host_vectors = np.zeros((self.capacity, self.dim),
                                          dtype=np.float32)

    def _rows_to_host(self):
        """The device tier becomes the host tier: one D2H, once (a grow
        would pass the watchdog's watermark). Caller holds ``_lock``."""
        logger.warning(
            "%s store of %d x %d: growing its float32 rescore rows would "
            "pass the device's high watermark; they move to the host",
            self.quantization, self.capacity, self.dim)
        self._host_vectors = self._device_rows_np()
        self.rescore_rows = None

    def _device_rows_np(self) -> np.ndarray:
        """All of the device tier as float32 [capacity, dim] on the host."""
        return np.array(
            np.asarray(self.rescore_rows)[:, :self.dim], dtype=np.float32)

    def _hbm_sync(self):
        """Publish the device footprint per component: codes (+valid),
        SQ's per-row terms, the transposed prefix, the rescore rows, and
        the PQ codebook."""
        sharding = "sharded" if self.mesh is not None else "single"

        def _set(component, nbytes, dtype=None):
            hbm_ledger.ledger.set_keyed(
                self._hbm_keys, component + self.hbm_component_suffix,
                nbytes, owner=self._hbm_owner,
                dtype=dtype, sharding=sharding)

        _set("codes", int(self.codes.nbytes) + int(self.valid.nbytes),
             dtype=jnp.dtype(self._code_dtype()).name)
        _set("row_terms",
             0 if self.row_terms is None else int(self.row_terms.nbytes),
             dtype="int32")
        _set("prefix",
             0 if self.prefix_t is None else int(self.prefix_t.nbytes),
             dtype="uint32")
        _set("rescore_rows",
             0 if self.rescore_rows is None
             else int(self.rescore_rows.nbytes),
             dtype="float32" if self.mesh is None else "bfloat16")
        _set("codebook",
             0 if self.codebook is None
             else int(np.asarray(self.codebook.centroids).nbytes),
             dtype="float32")

    def _encode(self, vectors: np.ndarray) -> np.ndarray:
        if self.quantization == "pq":
            if self.codebook is None:
                raise RuntimeError("PQ store not trained; call train() first")
            with tracing.span("store.pq_encode", rows=len(vectors)):
                return pq_ops.pq_encode(self.codebook, vectors)
        if self.quantization == "sq":
            # encoded on the device by the write itself (_write_codes)
            return None
        (codes,) = tracing.d2h(bq_ops.bq_encode(self._operand(vectors)))
        return codes

    def _maybe_norm(self, vectors: np.ndarray) -> np.ndarray:
        if self.normalize_on_add:
            return normalize_np(vectors)
        return vectors

    # -- training ------------------------------------------------------------

    @property
    def trained(self) -> bool:
        if self.quantization == "sq":
            return self.sq_quantizer is not None
        return self.quantization == "bq" or self.codebook is not None

    @property
    def min_training_rows(self) -> int:
        """Fewest rows ``train`` can fit this store's quantizer on."""
        return self.pq_centroids if self.quantization == "pq" else 1

    def train(self, vectors: np.ndarray | None = None, iters: int = 8, seed: int = 0):
        """Fit the quantizer (PQ: the codebook; SQ: the range) on given
        vectors or current live contents, and (re-)encode everything
        stored so far."""
        if self.quantization == "bq":
            return
        with self._lock:
            if vectors is None:
                live = np.nonzero(self._valid_np)[0]
                vectors = self._vectors_for(live)
            vectors = self._maybe_norm(np.asarray(vectors, dtype=np.float32))
            if self.quantization == "sq":
                self.sq_quantizer = sq_ops.sq_fit(vectors)
            else:
                self.codebook = pq_ops.pq_fit(
                    vectors, m=self.pq_segments, k=self.pq_centroids,
                    iters=iters, seed=seed,
                )
            self._reencode_all()
            self._hbm_sync()

    def _vectors_for(self, slots: np.ndarray) -> np.ndarray:
        """Full-precision rows for given slots from whichever tier has
        them (under ``_lock``: a write donates the device rows)."""
        with self._lock:
            return self._tier_vectors(*self._tiers(), slots)

    def _tiers(self) -> tuple:
        """The full-precision tiers as they stand (the finish step of a
        dispatch keeps this snapshot). Caller holds ``_lock``."""
        return (self._host_vectors, self.rescore_rows, self.fetch_fn,
                self.dim)

    @staticmethod
    def _tier_vectors(host_vectors, rescore_rows, fetch_fn, dim: int,
                      slots: np.ndarray) -> np.ndarray:
        """Tier pick shared by the live path (``_vectors_for``) and the
        async finish step's dispatch-time snapshot (``_tiers``)."""
        if host_vectors is not None:
            return host_vectors[slots]
        if rescore_rows is not None:
            # one gather program a pow2 bucket of slots, not one a length
            m = len(slots)
            buf = np.zeros(_next_pow2(max(m, 8)), dtype=np.int32)
            buf[:m] = slots
            return np.asarray(rescore_rows[jnp.asarray(buf)],
                              dtype=np.float32)[:m, :dim]
        if fetch_fn is not None:
            return np.asarray(fetch_fn(slots), dtype=np.float32)
        raise RuntimeError(
            "no full-precision tier (rescore='none', no fetch_fn) — "
            "train() needs explicit vectors")

    def _reencode_all(self, batch: int = 262144):
        live = np.nonzero(self._valid_np)[0]
        for s in range(0, len(live), batch):
            sl = live[s:s + batch]
            rows = self._vectors_for(sl)
            # rows ride along so _write_codes can (re-)derive the PQ sign
            # prefix — a train() AFTER add() must not leave prefix_t zeroed
            self._write_codes(sl, self._encode(rows), rows=rows)

    # -- mutation ------------------------------------------------------------

    def add(self, vectors: np.ndarray) -> np.ndarray:
        vectors = np.asarray(vectors, dtype=np.float32)
        if vectors.ndim == 1:
            vectors = vectors[None, :]
        m = len(vectors)
        with self._lock:
            slots = np.arange(self._count, self._count + m, dtype=np.int64)
            self._count += m
            if self._count > self.capacity:
                self._grow(self._count)
            self._write(slots, vectors)
            return slots

    def set_at(self, slots, vectors: np.ndarray):
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        vectors = np.asarray(vectors, dtype=np.float32)
        with self._lock:
            if len(slots) and int(slots.max()) >= self.capacity:
                self._grow(int(slots.max()) + 1)
            self._count = max(self._count, int(slots.max()) + 1 if len(slots) else 0)
            self._write(slots, vectors)

    def _write(self, slots: np.ndarray, vectors: np.ndarray):
        vectors = self._maybe_norm(vectors)
        if self._host_vectors is not None:
            self._host_vectors[slots] = vectors
        self._valid_np[slots] = True
        codes = self._encode(vectors) if self.trained else None
        self._write_codes(slots, codes, rows=vectors)

    def _write_codes(self, slots: np.ndarray, codes: np.ndarray | None,
                     rows: np.ndarray | None, pref: np.ndarray | None = None):
        """Scatter codes (and the resident rescore rows) into the device
        arrays, donated in place; padding to pow2 buckets bounds compiled
        variants."""
        if (pref is None and rows is not None and self.prefix_words
                and self.quantization == "pq" and codes is not None):
            # PQ prefix comes from the raw vectors' sign bits, not the
            # codes (the BQ store slices its own codes instead); derived
            # here so every write path — add, re-encode after train,
            # restore-from-vectors — carries it
            (pref,) = tracing.d2h(bq_ops.bq_encode(self._operand(
                np.asarray(rows)[:, :self.prefix_words * 32])))
        m = len(slots)
        if m == 0:
            return
        # SQ encodes on the device, inside the write: rows in, no codes
        sq_rows = (self.quantization == "sq" and codes is None
                   and rows is not None and self.trained)
        rows_up = rows is not None and (sq_rows
                                        or self.rescore_rows is not None)
        if rows_up and m > _WRITE_ROWS:
            for s in range(0, m, _WRITE_ROWS):
                e = s + _WRITE_ROWS
                self._write_codes(
                    slots[s:e], None if codes is None else codes[s:e],
                    rows[s:e], None if pref is None else pref[s:e])
            return
        bucket = _next_pow2(max(m, 8))
        slot_buf = np.zeros(bucket, dtype=np.int32)
        slot_buf[:m] = slots
        mask = np.zeros(bucket, dtype=bool)
        mask[:m] = True
        slot_dev = self._placed_replicated(slot_buf)
        mask_dev = self._placed_replicated(mask)
        rows_dev = None
        if rows_up:
            # the float32 block goes up ONCE, for SQ's encode and the
            # resident rescore rows alike
            rbuf = rows            # an import batch fills its bucket
            if m < bucket:
                rbuf = np.zeros((bucket, self.dim), dtype=np.float32)
                rbuf[:m] = rows
            rows_dev = self._placed_replicated(rbuf)
        if sq_rows:
            with tracing.span("store.sq_encode", rows=m):
                self.codes, self.row_terms, self.valid = _scatter_sq_rows(
                    self.codes, self.row_terms, self.valid, slot_dev,
                    rows_dev, mask_dev, self.sq_quantizer.params,
                    metric=self._scan_metric())
        elif codes is not None:
            w = self._code_width()
            cbuf = np.zeros((bucket, w), dtype=np.asarray(codes).dtype)
            cbuf[:m] = codes
            self.codes, self.valid = _scatter_codes(
                self.codes, self.valid, slot_dev,
                self._placed_replicated(cbuf), mask_dev)
            if self.prefix_t is not None:
                if self.quantization == "bq":
                    pcols = cbuf[:, :self.prefix_words].T.copy()
                else:
                    pbuf = np.zeros((bucket, self.prefix_words),
                                    dtype=np.uint32)
                    if pref is not None:
                        pbuf[:m] = pref[:, :self.prefix_words]
                    pcols = pbuf.T.copy()
                self.prefix_t = _scatter_prefix(
                    self.prefix_t, slot_dev, self._placed_replicated(pcols),
                    mask_dev)
        else:
            # mask-redirect padding entries like _scatter_codes does —
            # a bare scatter of the zero-padded slot buffer would mark
            # slot 0 valid on every write
            self.valid = _set_valid(self.codes, self.valid, slot_dev,
                                    mask_dev)
        if self.rescore_rows is not None and rows is not None:
            self.rescore_rows = _scatter_rescore(
                self.rescore_rows, slot_dev, rows_dev, mask_dev)

    def _grow(self, min_capacity: int):
        """Capacity-double codes/valid/mirrors. Caller holds ``_lock``."""
        new_cap = self._align(_next_pow2(min_capacity))
        if new_cap <= self.capacity:
            return
        old_cap = self.capacity
        pad = new_cap - old_cap
        if (self.rescore_rows is not None and self.mesh is None
                and not self._rows_fit(new_cap)):
            self._rows_to_host()
        grown_m = np.zeros(new_cap, dtype=bool)
        grown_m[:old_cap] = self._valid_np
        self._valid_np = grown_m
        if self._host_vectors is not None:
            grown_v = np.zeros((new_cap, self.dim), dtype=np.float32)
            grown_v[:old_cap] = self._host_vectors
            self._host_vectors = grown_v
        from weaviate_tpu.parallel.sharded_search import grow_rows

        self.capacity = new_cap
        self.codes = grow_rows(self.codes, pad, self.mesh)
        self.valid = grow_rows(self.valid, pad, self.mesh)
        if self.rescore_rows is not None:
            self.rescore_rows = grow_rows(self.rescore_rows, pad, self.mesh)
        if self.row_terms is not None:
            self.row_terms = jnp.pad(self.row_terms, (0, pad))
        if self.prefix_t is not None:
            self.prefix_t = jnp.pad(self.prefix_t, ((0, 0), (0, pad)))
        self._hbm_sync()

    def set_at_prenormalized(self, slots, vectors: np.ndarray):
        """set_at for vectors already normalized at their original insert
        (restore/compact/compress paths) — skips re-normalization."""
        orig = self.normalize_on_add
        self.normalize_on_add = False
        try:
            self.set_at(slots, vectors)
        finally:
            self.normalize_on_add = orig

    def delete(self, slots) -> None:
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        if len(slots) == 0:
            return
        with self._lock:
            self._valid_np[slots] = False
            m = len(slots)
            bucket = _next_pow2(max(m, 8))
            buf = np.full(bucket, self.capacity + 1, dtype=np.int32)  # OOB no-op
            buf[:m] = slots
            self.valid = _clear_valid(self.valid, self._placed_replicated(buf))

    # -- queries -------------------------------------------------------------

    @property
    def count(self) -> int:
        return self._count

    def live_count(self) -> int:
        return int(self._valid_np.sum())

    def get(self, slots) -> np.ndarray:
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        return self._vectors_for(slots).copy()

    def _scan(self, queries_dev, k_cand: int, valid, k_out: int,
              allow_bits=None, allow_rows=None, rescore_rows=None):
        """Dispatch the compressed scan (single-device or SPMD).

        ``allow_bits`` ([B, C/32] uint32 packed per-query masks) feeds the
        single-device kernels; ``allow_rows`` ([B, C] bool, column-sharded)
        feeds the SPMD path, which packs each shard's slice on device.
        ``rescore_rows`` (single-device: the resident float32 tier) makes
        the scan's program end with the exact rescore, cut to ``k_out``."""
        capacity = self.capacity
        cs = min(self.chunk_size, capacity // self.n_shards)
        metric = self._scan_metric()
        tail = {} if rescore_rows is None else dict(
            rescore_rows=rescore_rows, rescore_k=k_out)
        if self.quantization == "sq":
            return sq_ops.sq_topk(
                queries_dev, self.codes, self.row_terms,
                self.sq_quantizer.params, k=k_cand, chunk_size=cs,
                metric=metric, valid=valid, allow_bits=allow_bits, **tail,
            )
        if self.quantization == "pq":
            quant_key = "pq4" if self.pq_centroids <= 16 else "pq"
            cent = self.codebook.centroids
            qw = None
        else:
            quant_key = "bq"
            cent = None
            qw = bq_ops.bq_encode(queries_dev)
        if self.mesh is not None:
            from weaviate_tpu.parallel.sharded_search import (
                sharded_quantized_topk,
            )

            per_dev_k = min(k_cand, capacity // self.n_shards)
            return sharded_quantized_topk(
                queries_dev, qw, self.codes, valid, self.rescore_rows, cent,
                k=per_dev_k, k_out=k_out, chunk_size=cs,
                quantization=quant_key, metric=metric, mesh=self.mesh,
                use_pallas=self.use_pallas, allow_rows=allow_rows,
            )
        if quant_key in ("pq4", "pq"):
            if self.prefix_t is not None:
                qp = bq_ops.bq_encode(
                    queries_dev[:, :self.prefix_words * 32])
                return pq_ops.pq_topk_twostage(
                    queries_dev, qp, self.codes, cent, self.prefix_t,
                    k=k_cand, refine=max(2, self.rescore_limit // 2),
                    metric=metric, valid=valid, m=self.pq_segments,
                    use_pallas=self.use_pallas, allow_bits=allow_bits, **tail,
                )
            if quant_key == "pq4":
                return pq_ops.pq4_topk(
                    queries_dev, self.codes, cent, k=k_cand, chunk_size=cs,
                    metric=metric, valid=valid, allow_bits=allow_bits, **tail,
                )
            return pq_ops.pq_topk(
                queries_dev, self.codes, cent, k=k_cand, chunk_size=cs,
                metric=metric, valid=valid, allow_bits=allow_bits, **tail,
            )
        if tail:    # hamming scans see code words: the queries ride along
            tail.update(rescore_q=queries_dev, rescore_metric=metric)
        if self.prefix_t is not None:
            return bq_ops.bq_topk_twostage(
                qw, self.codes, self.prefix_t, k=k_cand,
                refine=max(2, self.rescore_limit // 2), valid=valid,
                use_pallas=self.use_pallas, allow_bits=allow_bits, **tail,
            )
        return bq_ops.bq_topk(
            qw, self.codes, k=k_cand, chunk_size=cs, valid=valid,
            use_pallas=self.use_pallas, allow_bits=allow_bits, **tail,
        )

    def rescore_mode(self) -> str:
        """Where the exact rescore happens for this store as it stands:
        ``"fused"`` (single-device float32 rows resident: the LAST step
        of the scan's own program, distances already exact — the epoch
        store treats this like ``"post"`` because its candidates span
        per-epoch tier snapshots), ``"inline"`` (inside the SPMD program
        of a mesh, against each device's own bf16 rows), ``"post"``
        (oversampled candidates come back for a host rescore: host
        rows, or ``fetch_fn``), or ``"none"`` (code-distance order is
        the contract)."""
        if self.rescore_rows is not None:
            return "fused" if self.mesh is None else "inline"
        if (self._host_vectors is not None
                or (self.rescore == "none" and self.fetch_fn is not None)):
            return "post"
        return "none"

    def epoch_scan(self, queries: np.ndarray, k_cand: int, k_out: int,
                   allow_mask: np.ndarray | None = None,
                   pre_normalized: bool = False):
        """Dispatch-only compressed scan for the epoch store: candidates
        stay device-resident with STORE-LOCAL ids for the cross-epoch
        merge; the (single, global) host rescore runs in the epoch
        store's finish step against the returned dispatch-time tier
        snapshot. ``pre_normalized`` skips query normalization when the
        epoch store already normalized once for every epoch (normalizing
        per epoch would not be bit-identical to the single-store path).
        Returns ``(d_dev, i_dev, tiers)``."""
        from weaviate_tpu.engine.store import (apply_allow_mask,
                                               batched_mask_operands,
                                               normalize_allow_mask)

        queries = np.asarray(queries, dtype=np.float32)
        if not pre_normalized:
            queries = self._maybe_norm(queries)
        allow_mask = normalize_allow_mask(allow_mask, len(queries))
        with self._lock:
            if not self.trained:
                raise RuntimeError(
                    f"{self.quantization.upper()} store not trained; "
                    f"call train() first")
            capacity = self.capacity
            valid = self.valid
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
            d, i = self._scan(self._operand(queries), min(k_cand, capacity),
                              valid, min(k_out, capacity),
                              allow_bits=allow_bits,
                              allow_rows=allow_rows_dev)
            tiers = self._tiers()
        return d, i, tiers

    def search(self, queries: np.ndarray, k: int, allow_mask: np.ndarray | None = None):
        """Two-stage: compressed scan (oversampled) -> exact rescore.

        Reference BQ rescore: flat/index.go:347; oversampling factor =
        ``rescore_limit`` (*k candidates pulled from the compressed scan).
        Where the full-precision rows are resident on the device the
        rescore is the last step of the scan's program (on a mesh: inside
        the SPMD program, on the owning device); where they are on the
        host (or behind ``fetch_fn``) the oversampled candidates come back
        for a vectorized exact rescore; plain ``"none"`` returns
        code-distance order directly (``rescore_mode``).

        ``allow_mask`` accepts the same two forms as
        ``DeviceVectorStore.search``: a shared [capacity] bool mask, or
        per-query [B, capacity] masks packed into a bitmask consumed
        inside the compressed scan kernels (disallowed rows never even
        become rescore candidates).

        Like the plain store, this is ``search_async(...).result()`` —
        the D2H transfer (and the host rescore, where the rows are on
        the host) rides the handle's finish step.
        """
        return self.search_async(queries, k, allow_mask).result()

    def search_async(self, queries: np.ndarray, k: int,
                     allow_mask: np.ndarray | None = None
                     ) -> DeviceResultHandle:
        """Dispatch-only twin of ``search``: the compressed scan
        launches under ``_lock``; its result ([B, k] exact answers
        where the rescore ran in the program, else the oversampled
        candidates) stays device-resident in the returned handle, whose
        finish step runs the exact host rescore (when this store's
        rescore mode needs one) after the boundary transfer."""
        from weaviate_tpu.engine.store import (AllowBits, apply_allow_mask,
                                               batched_mask_operands,
                                               normalize_allow_mask)

        queries = np.asarray(queries, dtype=np.float32)
        squeeze = queries.ndim == 1
        if squeeze:
            queries = queries[None, :]
        queries = self._maybe_norm(queries)
        allow_mask = normalize_allow_mask(allow_mask, len(queries))
        with tracing.span("store.quantized_scan", rows=self.capacity,
                          queries=len(queries), k=k,
                          quantization=self.quantization,
                          sharded=self.mesh is not None) as sp:
            with self._lock:
                if not self.trained:
                    raise RuntimeError(
                        f"{self.quantization.upper()} store not trained; "
                        f"call train() first")
                # fused / inline = the exact rescore happens inside the
                # scan's program; post = oversampled candidates come back
                # for a host-side exact pass (host rows or fetch_fn). ONE
                # classifier (rescore_mode), read under the lock (a grow
                # can move the rows to the host), serves this and the
                # epoch-store dispatch so the two paths can never drift.
                mode = self.rescore_mode()
                post_rescore = mode == "post"
                capacity = self.capacity
                valid = self.valid
                allow_bits = allow_rows_dev = None
                if isinstance(allow_mask, AllowBits) or (
                        allow_mask is not None and allow_mask.ndim == 2):
                    sp.set(path="bitmask_batched")
                    allow_bits, allow_rows_dev = batched_mask_operands(
                        allow_mask, len(queries), capacity, self.mesh,
                        owner=self._hbm_owner, device=self.device)
                elif allow_mask is not None:
                    full = np.zeros(capacity, dtype=bool)
                    full[: len(allow_mask)] = allow_mask[:capacity]
                    valid = apply_allow_mask(valid, self._placed(full))
                if mode == "none":
                    k_cand = k_out = min(k, capacity)
                else:
                    # the scan oversamples; only a host rescore needs
                    # the candidates themselves back
                    k_cand = min(max(k * self.rescore_limit, k), capacity)
                    k_out = k_cand if post_rescore else min(k, capacity)
                    rescore_dispatch_total.labels(
                        "host" if post_rescore else "device").inc()
                    if not post_rescore:
                        sp.set(path="device_rescore")
                # EXPLAIN: host ints only (no device reads), a no-op
                # when nobody asked — the rescore plan of this dispatch
                kernelscope.explain_note(
                    "quantized", quantization=str(self.quantization),
                    rescore_mode=mode, k_cand=k_cand, rows=capacity,
                    queries=len(queries), k=k,
                    path=("bitmask_batched" if allow_bits is not None
                          else "shared_mask" if allow_mask is not None
                          else "full_scan"))
                d, i = self._scan(
                    self._operand(queries), k_cand, valid, k_out,
                    allow_bits=allow_bits, allow_rows=allow_rows_dev,
                    rescore_rows=(self.rescore_rows if mode == "fused"
                                  else None))
                # dispatch-time snapshot for the finish step's rescore:
                # the scan's candidate slot-ids are only meaningful
                # against THIS capacity/row layout — compact()/_grow()
                # replace the full-precision tiers wholesale, and with
                # the pipelined drain the dispatch->finish window is a
                # whole overlapped batch, not microseconds
                rescore_tiers = self._tiers() if post_rescore else None
        # materialization + host rescore live in the handle's finish
        # step: the candidates cross D2H at the API boundary (or on the
        # serving pipeline's transfer thread), never under the lock

        def _finish(d_np, i_np, _queries=queries, _k=k, _squeeze=squeeze,
                    _post=post_rescore, _cap=capacity,
                    _tiers=rescore_tiers):
            i_np = i_np.astype(np.int64, copy=False)
            if _post:
                with tracing.span("store.host_rescore", stage="rescore",
                                  candidates=int(i_np.shape[1])):
                    d_np, i_np = self._host_rescore(
                        _queries, i_np, _k, capacity=_cap,
                        vectors_for=lambda s: self._tier_vectors(
                            *_tiers, s))
            out_d = d_np[:, :_k].astype(np.float32)
            out_i = i_np[:, :_k]
            if _squeeze:
                return out_d[0], out_i[0]
            return out_d, out_i

        return DeviceResultHandle(
            (d, i), finish=_finish,
            attrs={"rows": capacity, "queries": len(queries), "k": k,
                   "quantization": self.quantization})

    def _host_rescore(self, queries: np.ndarray, cand_ids: np.ndarray,
                      k: int, capacity: int | None = None,
                      vectors_for=None):
        """Vectorized exact rescore: one gather + one batched distance over
        [B, k_cand, d] (no per-query Python loop). ``capacity`` /
        ``vectors_for`` pin the row layout the candidate ids were scanned
        against (the async finish step passes its dispatch-time
        snapshot); defaults read the live store."""
        b, kc = cand_ids.shape
        cap = self.capacity if capacity is None else capacity
        safe = np.clip(cand_ids, 0, cap - 1)
        # the tier pick (host rows -> device rows -> fetch_fn)
        cand = ((vectors_for or self._vectors_for)(
            safe.reshape(-1))).reshape(b, kc, self.dim)
        metric = self._scan_metric()
        if metric == "dot":
            dd = -np.einsum("bd,bkd->bk", queries, cand)
        elif metric == "cosine":
            dd = 1.0 - np.einsum("bd,bkd->bk", queries, cand)
        else:
            diff = queries[:, None, :] - cand
            dd = np.einsum("bkd,bkd->bk", diff, diff)
        dd = np.where(cand_ids >= 0, dd, np.float32(3.0e38))
        k_eff = min(k, kc)
        part = np.argpartition(dd, k_eff - 1, axis=1)[:, :k_eff]
        pd = np.take_along_axis(dd, part, axis=1)
        order = np.argsort(pd, axis=1, kind="stable")
        sel = np.take_along_axis(part, order, axis=1)
        out_d = np.take_along_axis(dd, sel, axis=1).astype(np.float32)
        out_i = np.take_along_axis(cand_ids, sel, axis=1)
        out_i = np.where(out_d >= np.float32(3.0e38), -1, out_i)
        return out_d, out_i

    def search_by_distance(self, query: np.ndarray, max_distance: float,
                           allow_mask: np.ndarray | None = None):
        k = min(64, self.capacity)
        while True:
            d, i = self.search(query, k, allow_mask)
            within = d <= max_distance
            if (~within).any() or k >= self.capacity or within.sum() >= self.live_count():
                return d[within], i[within]
            k = min(k * 4, self.capacity)

    # -- maintenance / persistence -------------------------------------------

    def compact(self) -> np.ndarray:
        with tracing.span("store.compact", rows=self.capacity,
                          quantization=self.quantization), self._lock:
            live = np.nonzero(self._valid_np)[0]
            mapping = np.full(self.capacity, -1, dtype=np.int64)
            mapping[live] = np.arange(len(live))
            vecs = self._vectors_for(live) if len(live) else np.zeros(
                (0, self.dim), np.float32)
            self._count = 0
            self.capacity = self._align(max(len(live), 1))
            self._valid_np = np.zeros(self.capacity, dtype=bool)
            self._alloc_codes()
            if len(live):
                self.set_at_prenormalized(np.arange(len(live)), vecs)
            return mapping

    def twin_shapes(self):
        """What decides this store's scan program besides the batch and
        k (runtime/placement.py ``Twins``); None on a mesh."""
        if self.mesh is not None:
            return None
        return (self.quantization, self.capacity, self.dim, self.metric,
                self.chunk_size, self.rescore_limit,
                self.pq_segments, self.pq_centroids, self.prefix_words,
                self.rescore_mode(), self.use_pallas)

    def snapshot(self) -> dict:
        with self._lock:
            snap = {
                "valid": self._valid_np.copy(),
                "count": self._count,
                "dim": self.dim,
                "metric": self.metric,
                "quantization": self.quantization,
                "pq_segments": self.pq_segments,
                "pq_centroids": self.pq_centroids,
                "rescore_limit": self.rescore_limit,
                "rescore": self.rescore,
                "prefix_bits": self.prefix_words * 32,
                "chunk_size": self.chunk_size,
                "codebook": (
                    None if self.codebook is None
                    else np.asarray(self.codebook.centroids)
                ),
                "sq_quantizer": (
                    None if self.sq_quantizer is None
                    else np.asarray(self.sq_quantizer[:2], np.float32)
                ),
            }
            if self._host_vectors is not None:
                snap["vectors"] = self._host_vectors.copy()
            elif self.rescore_rows is not None:
                snap["vectors"] = self._device_rows_np()
            else:
                snap["codes"] = np.asarray(self.codes)
                if self.prefix_t is not None and self.quantization == "pq":
                    # PQ prefixes derive from the raw vectors — a
                    # codes-only snapshot must carry them explicitly
                    snap["prefix_t"] = np.asarray(self.prefix_t)
            return snap

    @classmethod
    def restore(cls, snap: dict, mesh=None, **kwargs) -> "QuantizedVectorStore":
        kwargs.setdefault("rescore", snap.get("rescore", "host"))
        if snap.get("prefix_bits"):
            kwargs.setdefault("prefix_bits", snap["prefix_bits"])
        store = cls(
            dim=snap["dim"],
            metric=snap["metric"],
            quantization=snap["quantization"],
            capacity=max(len(snap["valid"]), 2),
            chunk_size=snap["chunk_size"],
            pq_segments=snap["pq_segments"],
            pq_centroids=snap["pq_centroids"],
            rescore_limit=snap["rescore_limit"],
            mesh=mesh,
            **kwargs,
        )
        if snap.get("codebook") is not None:
            store.codebook = pq_ops.PQCodebook(jnp.asarray(snap["codebook"]))
        if snap.get("sq_quantizer") is not None:
            store.sq_quantizer = sq_ops.sq_quantizer(*snap["sq_quantizer"])
        live = np.nonzero(snap["valid"])[0]
        if len(live):
            if "vectors" in snap:
                store.set_at_prenormalized(live, snap["vectors"][live])
            else:
                # codes-only snapshot: restore codes directly
                store._valid_np[live] = True
                store._write_codes(live, snap["codes"][live], rows=None)
                if store.row_terms is not None:
                    # SQ's terms follow from the codes: summed again
                    store.row_terms = sq_ops.sq_row_terms(
                        store.codes, store._scan_metric())
                if snap.get("prefix_t") is not None \
                        and store.prefix_t is not None:
                    pt = snap["prefix_t"]
                    store.prefix_t = store._placed_replicated(np.pad(
                        pt, ((0, 0),
                             (0, store.capacity - pt.shape[1]))))
        store._count = snap["count"]
        store._hbm_sync()  # codebook/prefix set after __init__'s sync
        return store
