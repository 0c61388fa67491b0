"""IVF (inverted-file) ANN index — the TPU-native ANN.

The reference's ANN is HNSW (vector/hnsw/index.go): a pointer-chasing graph
whose hot loop (search.go:173-341) is one-vector-at-a-time — the worst
possible shape for a systolic array. The TPU-idiomatic replacement
(SURVEY §7 step 5) is IVF/ScaNN-style partitioning:

- **train**: coarse k-means over the corpus (ops/kmeans.py, MXU Lloyd's)
- **layout**: posting lists as ONE dense padded tensor ``[nlist, cap, d]``
  in HBM (+ valid mask, slot ids, cached norms) — uniform shapes so the
  probe gather is a static-shape `take`, not ragged pointer chasing
- **search**: query→centroid matmul → top-nprobe lists → each probed
  list gathered as the ``[cap, d]`` slab it is and scored in the same
  program (``_ivf_probe_topk``), per-query ``allow_bits`` folded per
  candidate, exact top-k. Dispatch only — ``search_async`` returns a
  DeviceResultHandle and ``search`` is its ``.result()``, so sync and
  async are bit-exact by construction.
- **residual PQ** (quantization="pq"): posting lists hold uint8 codes of
  the RESIDUAL ``r = x - centroid[assign]`` (IVF-ADC; the residual has
  ~nlist× less variance than the raw vector, so the same code budget
  buys a tighter quantizer). The probe scores candidates by ADC —
  ``||q-c-r̂||² = ||q-c||² - 2·q·r̂ + t_row`` with
  ``t_row = 2·c·r̂ + ||r̂||²`` precomputed per row at encode — then
  oversampled candidates rescore EXACTLY on device against a full-rows
  tier (gather-matmul via the plane). The f32 host mirror survives only
  for retrain/rebuild/persistence and is ledger-accounted as a host-tier
  component, like HNSW's host graph.
- **delta buffer**: recent inserts land in a small brute-force scanned
  DeviceVectorStore (exact), merged into lists when it fills (the LSM
  memtable idea applied to HBM; mirrors how the reference's async index
  queue batches graph inserts, index_queue.go:42).

Maintenance is incremental: deletes tombstone rows AND record the hole
(list, pos); later scatters refill holes before extending the tail, and
a row that finds its home list full spills to the next-nearest centroid
with room. ``compact()`` therefore just folds the delta in — no full
rebuild (``rebuild_count`` stays flat across compactions) — and
``maintain()`` retrains only past a centroid-drift proxy (live count
grew ``retrain_factor``× since training).

Updates re-route the slot through the delta buffer. Global slot ids are
stable across flushes, so the FlatIndex id<->slot bookkeeping works
unchanged — IVFIndex subclasses FlatIndex and swaps the store.
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
import time
import weakref
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu.engine.filter_operands import stable_mask
from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.engine.store import (AllowSlots, DeviceVectorStore,
                                       _next_pow2, normalize_allow_mask,
                                       stack_allow_rows)
from weaviate_tpu.ops.candidates import (gather_rescore_topk,
                                         masked_candidate_topk)
from weaviate_tpu.ops.distances import (MASKED_DISTANCE, normalize,
                                        normalize_np, pairwise_distance)
from weaviate_tpu.ops.kmeans import kmeans_assign, kmeans_fit
from weaviate_tpu.ops.pallas_kernels import _MASK_WORDS, allow_bits_for_ids
from weaviate_tpu.ops.topk import topk_smallest
from weaviate_tpu.runtime import hbm_ledger, kernelscope, placement, tracing
from weaviate_tpu.runtime.metrics import (filter_operand_total,
                                          ivf_candidate_rows_total,
                                          ivf_cutoff_programs_total,
                                          ivf_cutoff_rows_total,
                                          ivf_delta_rows,
                                          ivf_filtered_requests_total,
                                          ivf_list_capacity, ivf_lists,
                                          ivf_live_rows,
                                          ivf_maintain_seconds,
                                          ivf_probe_dispatches_total,
                                          ivf_probe_programs_total,
                                          ivf_probed_lists_total,
                                          ivf_queries_total)
from weaviate_tpu.runtime.transfer import DeviceResultHandle

_SUPPORTED_METRICS = ("l2-squared", "dot", "cosine", "cosine-dot")

#: upstream's ``flatSearchCutoff`` at its default: a filter that allows
#: fewer live rows than this is answered by an exact scan over them, never
#: by the probe (0 turns the rule off)
DEFAULT_FLAT_SEARCH_CUTOFF = 40_000

#: a maintenance tick folds a part-filled delta once no write has come
#: for this long (the scheduler's base tick). By the clock and not from
#: one tick to the next: after an import the scheduler's one thread is
#: held for half a minute by the LSM's flushes and by driftwatch's seal,
#: and the fold then fell into the first seconds a settled server served
#: (12 of 15 windows of ``cohere-dynamic-cosine.filtered-c32``, PERF.md)
TAIL_FOLD_PAUSE_S = 5.0


class IVFAllow(NamedTuple):
    """A dispatch's per-query filters as ``IVFIndex`` routed them
    (``IVFIndex._bitmask_operand``), every operand already on the device.

    ``exact``: one ``(slots, count, rows)`` a DISTINCT mask under the
    cutoff: its slot list (``AllowSlots``' form: ascending, then -1, a
    pow2 bucket), the live rows it allows, and the block's rows that
    carry it. ``order``: the block's other rows, the probe's, in the
    order the probe takes them (filtered rows first, then unfiltered
    and padded ones), followed by the exact route's (they only fill the
    last chunk); ``n_probe`` of them want the probe's answer and
    ``n_filtered`` of those carry a filter. ``bits``: the probe rows'
    packed masks in ``order``, one ``[chunk, W]`` block a chunk of the
    probe (a raw mask the store packed itself: one ``[1, W]`` or ``[B,
    W]`` array; None: none of them is filtered). ``delta_allow``: the block's masks over the DELTA
    buffer's slots, bool ``[B, delta capacity]`` in the block's own row
    order, None where the delta holds no row (the window's state) or no
    probe row is filtered: the one operand still made on the host a
    dispatch, a few thousand bits a row."""
    exact: tuple
    order: np.ndarray
    n_probe: int
    n_filtered: int
    bits: tuple | jax.Array | None
    delta_allow: np.ndarray | None


@contextlib.contextmanager
def maintain_stage(stage: str, span: str, **attrs):
    """One step that builds or keeps the index, off the request path: a
    span (under a sampled import's trace, or a root of its own) and one
    observation of ``weaviate_tpu_ivf_maintain_seconds{stage}``, none
    for a step that raised. Yields ``(span, less)``: seconds added to
    ``less[0]`` are taken off the observation (the training inside an
    upgrade is observed as ``train``)."""
    less = [0.0]
    t0 = time.perf_counter()
    with tracing.span(span, **attrs) as sp:
        yield sp, less
    ivf_maintain_seconds.labels(stage).observe(
        max(0.0, time.perf_counter() - t0 - less[0]))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_lists(list_vecs, list_valid, list_slots, list_norms,
                   flat_idx, vecs, slots, write_mask):
    """Scatter rows into the flattened [nlist*cap] list tensor."""
    nlist, cap, dim = list_vecs.shape
    fv = list_vecs.reshape(nlist * cap, dim)
    fva = list_valid.reshape(nlist * cap)
    fs = list_slots.reshape(nlist * cap)
    fn = list_norms.reshape(nlist * cap)
    tgt = jnp.where(write_mask, flat_idx, nlist * cap)  # OOB rows drop
    vecs = vecs.astype(fv.dtype)
    norms = jnp.sum(vecs.astype(jnp.float32) ** 2, axis=-1)
    fv = fv.at[tgt].set(vecs, mode="drop")
    fva = fva.at[tgt].set(True, mode="drop")
    fs = fs.at[tgt].set(slots, mode="drop")
    fn = fn.at[tgt].set(norms, mode="drop")
    return (fv.reshape(nlist, cap, dim), fva.reshape(nlist, cap),
            fs.reshape(nlist, cap), fn.reshape(nlist, cap))


@functools.partial(jax.jit, donate_argnums=(0,))
def _clear_list_rows(list_valid, flat_idx):
    nlist, cap = list_valid.shape
    flat = list_valid.reshape(nlist * cap)
    return flat.at[flat_idx].set(False, mode="drop").reshape(nlist, cap)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_code_lists(list_codes, list_valid, list_slots, list_tvals,
                        flat_idx, codes, tvals, slots, write_mask):
    """PQ-mode scatter: residual codes [m] uint8 + per-row ADC constant
    ``t_row`` into the [nlist, cap, …] list tensors."""
    nlist, cap, m = list_codes.shape
    fc = list_codes.reshape(nlist * cap, m)
    fva = list_valid.reshape(nlist * cap)
    fs = list_slots.reshape(nlist * cap)
    ft = list_tvals.reshape(nlist * cap)
    tgt = jnp.where(write_mask, flat_idx, nlist * cap)
    fc = fc.at[tgt].set(codes, mode="drop")
    fva = fva.at[tgt].set(True, mode="drop")
    fs = fs.at[tgt].set(slots, mode="drop")
    ft = ft.at[tgt].set(tvals, mode="drop")
    return (fc.reshape(nlist, cap, m), fva.reshape(nlist, cap),
            fs.reshape(nlist, cap), ft.reshape(nlist, cap))


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows_at(rows, idx, vecs, write_mask):
    """Scatter f32 rows into the device rescore tier (PQ mode)."""
    tgt = jnp.where(write_mask, idx, rows.shape[0])  # OOB rows drop
    return rows.at[tgt].set(vecs.astype(rows.dtype), mode="drop")


@functools.partial(jax.jit,
                   static_argnames=("k", "nprobe", "metric", "use_allow"))
def _ivf_probe_topk_pq(q, centroids, c_norms, list_codes, list_valid,
                       list_slots, list_tvals, pq_centroids, allow_bits,
                       k: int, nprobe: int, metric: str, use_allow: bool):
    """Residual-PQ probe: gather CODES from the probed lists and score by
    residual ADC. Codes encode ``r = x - centroid[assign]``, so the
    distance decomposes into a per-(query, probe) base term the coarse
    matmul already produced, a per-row constant ``t_row`` cached at
    encode, and the only data-dependent part — ``q·r̂`` — which the
    one-hot int8 LUT matmul computes on the MXU:

        l2:     ||q-c-r̂||² = ||q-c||²  - 2·q·r̂ + (2·c·r̂ + ||r̂||²)
        dot:    -q·x̂       = -q·c      -   q·r̂
        cosine: 1 - q·x̂    = 1 + (-q·c -   q·r̂)

    ADC order is approximate (rank-only): callers exact-rescore the
    oversampled survivors via the candidate plane. HBM reads per probed
    row are m+4 bytes instead of 4d — the capacity regime IVF-PQ exists
    for (reference: PQ inside each shard's HNSW,
    compressionhelpers/product_quantization.go:372). The one-hot int8
    matmul ADC (chunked over probed rows, bounded [B, Pc, kc*m]
    transients) replaced a per-segment take_along_axis formulation that
    issued B*P*m VPU random gathers and OOM'd beyond nprobe=8.
    Per-query allow bitmasks fold per candidate (allow_bits_for_ids) —
    never a dense [B, capacity] unpack."""
    from weaviate_tpu.ops.pq import quantize_lut_int8

    nlist, cap, m = list_codes.shape
    b = q.shape[0]
    q32 = q.astype(jnp.float32)
    if metric in ("cosine", "cosine-dot"):
        q32 = normalize(q32)
    cd = pairwise_distance(q32, centroids, metric="l2-squared",
                           x_sq_norms=c_norms)
    _, probes = jax.lax.top_k(-cd, nprobe)          # [B, nprobe]
    cd_p = jnp.take_along_axis(cd, probes, axis=1)  # ||q-c||² per probe

    codes = list_codes[probes].reshape(b, nprobe * cap, m)
    vld = list_valid[probes].reshape(b, nprobe * cap)
    slots = list_slots[probes].reshape(b, nprobe * cap)
    tval = list_tvals[probes].reshape(b, nprobe * cap)
    p = codes.shape[1]
    # residual LUT: factor * q_seg · codeword (factor −2 for l2, −1 for
    # the dot family) — no qn/cn terms, those live in base/t_row
    ds = pq_centroids.shape[2]
    kc = pq_centroids.shape[1]
    qs = q32.reshape(b, m, ds)
    rdots = jnp.einsum("bms,mks->bmk", qs, pq_centroids,
                       preferred_element_type=jnp.float32)
    lut = (-2.0 if metric == "l2-squared" else -1.0) * rdots
    lut8, scale = quantize_lut_int8(lut)
    # ~128 MB one-hot transient per scan step ACROSS the query batch
    # (b * pc * kc * m int8)
    pc = max(256, min(p, (1 << 27) // (kc * m * max(b, 1))))
    n_chunks = -(-p // pc)
    pad_p = n_chunks * pc - p
    codes_p = jnp.pad(codes, ((0, 0), (0, pad_p), (0, 0)))
    codes_c = codes_p.reshape(b, n_chunks, pc, m).transpose(1, 0, 2, 3)

    def one_chunk(carry, codes_blk):
        # copy-major tile (lane c*m + s) matching the code-major LUT flatten
        rep = jnp.tile(codes_blk.astype(jnp.int32), (1, 1, kc))
        lane = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 2) // m
        oh = (rep == lane).astype(jnp.int8)          # [B, Pc, kc*m]
        dots = jax.lax.dot_general(
            lut8, oh,
            dimension_numbers=(((1,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.int32)         # [B, Pc]
        return carry, dots

    _, d8 = jax.lax.scan(one_chunk, None, codes_c)
    adc = (jnp.transpose(d8, (1, 0, 2)).reshape(b, n_chunks * pc)[:, :p]
           .astype(jnp.float32) / scale[:, None])     # ≈ factor · q·r̂
    if metric == "l2-squared":
        d = jnp.maximum(jnp.repeat(cd_p, cap, axis=1) + adc + tval, 0.0)
    else:
        qn = jnp.sum(q32 * q32, axis=-1, keepdims=True)
        base = -0.5 * (qn + c_norms[probes] - cd_p)   # = -q·c per probe
        d = jnp.repeat(base, cap, axis=1) + adc
        if metric != "dot":
            d = 1.0 + d
    if use_allow:
        vld = vld & allow_bits_for_ids(allow_bits, slots)
    d = jnp.where(vld, d, MASKED_DISTANCE)
    td, ts = topk_smallest(d, slots, min(k, p))
    # masked rows keep their slot through top_k — drop them HERE or the
    # exact rescore downstream would resurrect them with real distances
    return td, jnp.where(td >= MASKED_DISTANCE, -1, ts)


@functools.partial(jax.jit,
                   static_argnames=("k", "nprobe", "metric", "use_allow"))
def _ivf_probe_topk(q, centroids, c_norms, list_vecs, list_valid, list_slots,
                    list_norms, allow_bits, k: int, nprobe: int,
                    metric: str, use_allow: bool):
    """Full-rows probe, ONE program a chunk: q [B,d] → centroid
    distances [B,nlist] (MXU matmul) → top-nprobe → each probed posting
    list read as the contiguous ``[cap, d]`` slab it is
    (``list_vecs[probes]``: ``nprobe`` blocks a query, not ``nprobe *
    cap`` rows: the candidate plane's per-position gather, right for
    the scattered candidates of its other callers, costs nine tenths
    of a probe's time here, PERF.md section 6, PRs 43 and 47).
    The arithmetic is ``gather_rescore_topk``'s: float32 rows at
    HIGHEST, cosine 1 - dot on unit rows, l2 by the cached norms,
    ``allow_bits`` folded a candidate by its slot, dead, empty and
    disallowed positions at ``MASKED_DISTANCE``, exact top-k with ties
    to the lower position. Returns (dists [B,k'], slots [B,k'] int32)
    ascending; dead/filtered rows never surface. Memory is
    O(B * nprobe * cap * d): callers chunk B."""
    nlist, cap, dim = list_vecs.shape
    b = q.shape[0]
    q32 = q.astype(jnp.float32)
    if metric in ("cosine", "cosine-dot"):
        q32 = normalize(q32)
    cd = pairwise_distance(q32, centroids, metric="l2-squared",
                           x_sq_norms=c_norms)
    _, probes = jax.lax.top_k(-cd, nprobe)  # [B, nprobe]
    g = list_vecs[probes].astype(jnp.float32)  # [B, nprobe, cap, d]
    # float32 rows state float32 arithmetic (ops/candidates.py: at
    # DEFAULT the chip makes one bf16 pass over float32 operands)
    dots = jnp.einsum(
        "bd,bpcd->bpc", q32, g, preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if list_vecs.dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))
    if metric == "l2-squared":
        q_norms = jnp.sum(q32 * q32, axis=-1)[:, None, None]
        d = jnp.maximum(
            q_norms - 2.0 * dots + list_norms[probes].astype(jnp.float32),
            0.0)
    elif metric == "dot":
        d = -dots
    else:  # cosine family: rows and q unit-norm -> distance 1 - cos
        d = 1.0 - dots
    # the valid flags folded into the slots over the whole [nlist, cap]
    # (2 MB in the served cell) so that ONE small slab gather carries both
    ids = jnp.where(list_valid, list_slots, -1)[probes]  # [B, nprobe, cap]
    d = d.reshape(b, nprobe * cap)
    ids = ids.reshape(b, nprobe * cap).astype(jnp.int32)
    ok = ids >= 0
    if use_allow:
        ok = ok & allow_bits_for_ids(allow_bits, ids)
    d = jnp.where(ok, d, MASKED_DISTANCE)
    return masked_candidate_topk(d, ids, min(k, nprobe * cap))


def _exact_over_rows(q, rows, live, slots, k: int, metric: str):
    """The exact route's arithmetic over the gathered rows ``[C, d]``
    (the probe's: float32 rows at HIGHEST, cosine 1 - dot on unit rows,
    l2 from the rows' own norms), dead and padded positions at
    ``MASKED_DISTANCE``, exact top-k with ties to the lower position: the
    slot list ascends, so to the lower slot."""
    q32 = q.astype(jnp.float32)
    if metric in ("cosine", "cosine-dot"):
        q32 = normalize(q32)
    dots = jnp.einsum(
        "bd,cd->bc", q32, rows.astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if rows.dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))
    if metric == "l2-squared":
        r32 = rows.astype(jnp.float32)
        d = jnp.maximum(
            jnp.sum(q32 * q32, axis=-1)[:, None] - 2.0 * dots
            + jnp.sum(r32 * r32, axis=-1)[None, :], 0.0)
    elif metric == "dot":
        d = -dots
    else:
        d = 1.0 - dots
    d = jnp.where(live[None, :], d, MASKED_DISTANCE)
    ids = jnp.broadcast_to(jnp.where(live, slots, -1)[None, :], d.shape)
    return masked_candidate_topk(d, ids, min(k, slots.shape[0]))


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _ivf_flat_cutoff_topk(q, slots, slot_map, list_vecs, delta_vecs,
                          k: int, metric: str):
    """The exact route under ``flatSearchCutoff``, ONE program a distinct
    mask: ``slots`` [C] int32 (the mask's allowed live slots ascending,
    then -1; C a power of two) are looked up in ``slot_map`` [capacity]
    (a slot's position: ``list * cap + pos`` in the posting lists,
    ``-2 - delta slot`` in the delta buffer, -1 dead), their rows
    gathered from where they lie and scored against the whole block
    ``q`` [B, d]; rows of the block that carry another mask are dropped
    on the host. O(C * d) bytes whatever the lists hold. ONE variant
    whether the delta holds rows or not (a second gather that finds
    nothing when it is empty): a variant a state would be loaded in
    whatever window first meets the state a fold leaves (my chip runs,
    PR 51: 24-30 programs loaded in the first two seconds of a window
    whose warm-up had ended before the delta's tail was folded)."""
    nlist, cap, _ = list_vecs.shape
    n = slot_map.shape[0]
    loc = jnp.where((slots >= 0) & (slots < n),
                    slot_map[jnp.clip(slots, 0, n - 1)], -1)
    in_list = loc >= 0
    lp = jnp.clip(loc, 0, nlist * cap - 1)
    dp = jnp.clip(-2 - loc, 0, delta_vecs.shape[0] - 1)
    rows = jnp.where(in_list[:, None], list_vecs[lp // cap, lp % cap],
                     delta_vecs[dp].astype(list_vecs.dtype))
    return _exact_over_rows(q, rows, in_list | (loc <= -2), slots, k,
                            metric)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _ivf_flat_cutoff_topk_rows(q, slots, rows_by_slot, k: int, metric: str):
    """The exact route where the lists hold codes (residual PQ): the
    float32 rows lie by SLOT in the rescore tier, so no position is
    looked up; ``slots`` and the answer as ``_ivf_flat_cutoff_topk``."""
    n = rows_by_slot.shape[0]
    live = (slots >= 0) & (slots < n)
    return _exact_over_rows(q, rows_by_slot[jnp.clip(slots, 0, n - 1)],
                            live, slots, k, metric)


class IVFStore:
    """DeviceVectorStore-compatible store backed by IVF posting lists plus a
    brute-force delta buffer. Slot ids are append-order and stable."""

    mesh = None  # single-replica; collection-level sharding distributes IVF
    # ``search_async`` takes filters an index prepared on the device: a
    # shared slot list under the cutoff (``AllowSlots``, what
    # ``gathered_slots`` builds) and a routed dispatch (``IVFAllow``)
    takes_allow_operands = True

    def __init__(self, dim: int, metric: str = "l2-squared",
                 capacity: int = 8192, chunk_size: int = 8192,
                 nlist: int = 0, nprobe: int = 0,
                 train_threshold: int = 16_384,
                 delta_threshold: int = 8192,
                 query_chunk: int = 16,
                 dtype=None,
                 quantization: str | None = None,
                 pq_segments: int | None = None,
                 pq_centroids: int = 16,
                 rescore_limit: int = 16,
                 retrain_factor: float = 4.0,
                 flat_search_cutoff: int = DEFAULT_FLAT_SEARCH_CUTOFF):
        if metric not in _SUPPORTED_METRICS:
            raise ValueError(
                f"ivf supports {_SUPPORTED_METRICS}, not {metric!r}")
        if quantization not in (None, "pq"):
            raise ValueError(f"ivf quantization must be None or 'pq', "
                             f"not {quantization!r}")
        self.dim = dim
        self.metric = metric
        self.chunk_size = chunk_size
        self.dtype = dtype or jnp.float32
        # the number of lists the user asked for (0 = automatic: about
        # 2 sqrt(rows), so every retrain re-sizes the partition to the
        # corpus it finds) and, apart from it, the number the lists were
        # last trained with
        self._user_nlist = nlist
        self.nlist = nlist
        self.nprobe = nprobe  # 0 = auto (nlist/8, min 8)
        self.train_threshold = train_threshold
        self.delta_threshold = delta_threshold
        self.query_chunk = query_chunk
        # upstream's flatSearchCutoff: a filter that allows fewer live
        # rows is answered exactly over them (``gathered_slots``); 0 = off
        self.flat_search_cutoff = int(flat_search_cutoff)
        # Residual IVF-PQ residency: posting lists hold uint8 codes of
        # x - centroid[assign]; oversampled candidates rescore EXACTLY on
        # device against the _rescore_rows tier. The host f32 mirror
        # survives for retrain/rebuild/persistence only (ledger: a
        # "host_mirror" host-tier component). The delta buffer stays
        # exact either way.
        self.quantization = quantization
        self.pq_centroids = pq_centroids
        if quantization and not pq_segments:
            from weaviate_tpu.ops.pq import default_pq_segments

            pq_segments = default_pq_segments(dim, pq_centroids)
        self.pq_segments = pq_segments
        self.rescore_limit = rescore_limit
        self.retrain_factor = retrain_factor
        self.codebook = None
        self.list_codes = None
        self.list_tvals = None  # [nlist, cap] f32 per-row ADC constant
        self._host_rows = (
            np.zeros((max(capacity, 1024), dim), dtype=np.float32)
            if quantization else None)
        self._rescore_rows = None  # device [pow2, d] exact-rescore tier
        self.normalize_on_add = metric in ("cosine", "cosine-dot")
        self._lock = threading.RLock()
        self._count = 0  # global slot high-water mark
        # maintenance counters (asserted by tests: compaction must not
        # full-rebuild, retrain only fires past the drift proxy)
        self.rebuild_count = 0
        self.retrain_count = 0
        self.train_seconds = 0.0  # wall seconds inside train(), summed
        self._live_at_train = 0
        # HBM ledger: centroid + posting-list tensors publish under the
        # owner labels captured here; the delta store self-accounts (it
        # is a DeviceVectorStore constructed in this same owner scope)
        # and is built on the same chip (runtime/placement.py): the
        # owning shard's device travels in the owner scope, and every
        # list tensor, the centroids and a dispatch's operands go
        # through the one ``put`` under it
        self._hbm_owner = hbm_ledger.current_owner()
        self.device = self._hbm_owner.get("device")
        self._labels = (str(self._hbm_owner.get("collection") or "-"),
                        str(self._hbm_owner.get("shard") or "-"))
        self._hbm_keys: dict[str, int] = {}
        weakref.finalize(self, hbm_ledger.ledger.release_many,
                         self._hbm_keys.values())
        # delta buffer (exact scan); delta slot -> global slot
        with hbm_ledger.owner(**self._hbm_owner):
            self.delta = DeviceVectorStore(
                dim, metric, capacity=min(capacity, delta_threshold * 2),
                chunk_size=chunk_size, dtype=self.dtype)
        self._delta_slots: dict[int, int] = {}  # delta slot -> global
        # the same map as an array over the delta's slots (-1 = free),
        # kept with the dict so that a search builds nothing a slot
        self._delta_gmap = np.full(self.delta.capacity, -1, np.int32)
        # the unfiltered probe's ``allow_bits`` operand (64 bytes of
        # zeros, never read), uploaded at the first such search and kept:
        # made a dispatch it was an eager one-op program beside the probe
        self._no_bits = None
        # when the last write came (``time.monotonic``): a tick folds a
        # part-filled delta only once the writes have paused
        self._last_write_t = 0.0
        # slot -> ("delta", dslot) | ("list", flat_idx)
        self._slot_loc: dict[int, tuple] = {}
        # the same map as an array over the slots, kept with the dict:
        # ``list * cap + pos`` in the lists, ``-2 - dslot`` in the delta,
        # -1 dead. The exact route under the cutoff reads it ON THE
        # DEVICE (``slot_map``): uploaded again when ``_layout_gen`` has
        # moved, which every write, fold, retrain and growth of ``cap``
        # does (slots are stable across a fold, positions are not)
        self._loc_np = np.full(max(capacity, 1024), -1, np.int32)
        self._layout_gen = 0
        self._slot_map = None
        self._slot_map_gen = -1
        # list tensors (allocated at train time)
        self.centroids = None  # jnp [nlist, d]
        self._centroids_np = None  # host twin (assign/residuals/spill)
        self._c_norms = None
        self.list_vecs = None  # [nlist, cap, d]
        self.list_valid = None
        self.list_slots = None
        self.list_norms = None
        self.list_cap = 0
        self._fill: np.ndarray | None = None  # host per-list fill count
        # freed (list, pos) positions, refilled LIFO before the tail
        # grows — positions survive cap growth, flat indices would not
        self._holes: dict[int, list[int]] = {}

    def _hbm_sync(self):
        """Publish centroid + posting-list + rescore-tier device bytes
        and the host mirror (host tier) to the ledger (the delta
        DeviceVectorStore accounts for itself)."""
        cent = 0 if self.centroids is None else (
            int(self.centroids.nbytes) + int(self._c_norms.nbytes))
        hbm_ledger.ledger.set_keyed(
            self._hbm_keys, "centroids", cent, owner=self._hbm_owner,
            dtype="float32")
        # the list tensors one component each: /v1/debug/memory lists
        # them by name, as it lists a compressed store's codes
        for name in ("list_vecs", "list_codes", "list_norms", "list_tvals",
                     "list_valid", "list_slots"):
            arr = getattr(self, name)
            hbm_ledger.ledger.set_keyed(
                self._hbm_keys, name, 0 if arr is None else int(arr.nbytes),
                owner=self._hbm_owner,
                dtype="" if arr is None else jnp.dtype(arr.dtype).name)
        hbm_ledger.ledger.set_keyed(
            self._hbm_keys, "rescore_rows",
            0 if self._rescore_rows is None
            else int(self._rescore_rows.nbytes),
            owner=self._hbm_owner, dtype=jnp.dtype(self.dtype).name)
        hbm_ledger.ledger.set_keyed(
            self._hbm_keys, "slot_map",
            0 if self._slot_map is None else int(self._slot_map.nbytes),
            owner=self._hbm_owner, dtype="int32")
        hbm_ledger.ledger.set_keyed(
            self._hbm_keys, "host_mirror",
            0 if self._host_rows is None else int(self._host_rows.nbytes),
            owner=self._hbm_owner, dtype="float32", placement="host")

    # -- properties mirrored from DeviceVectorStore ---------------------------

    @property
    def count(self) -> int:
        return self._count

    @property
    def capacity(self) -> int:
        """Global slot-space bound (exclusive upper bound on slot ids)."""
        return max(_next_pow2(max(self._count, 1)), 8)

    @property
    def trained(self) -> bool:
        return self.centroids is not None

    def live_count(self) -> int:
        with self._lock:
            return len(self._slot_loc)

    # -- mutation -------------------------------------------------------------

    def add(self, vectors: np.ndarray) -> np.ndarray:
        vectors = np.asarray(vectors, dtype=np.float32)
        if vectors.ndim == 1:
            vectors = vectors[None, :]
        with self._lock:
            slots = np.arange(self._count, self._count + len(vectors),
                              dtype=np.int64)
            self._count += len(vectors)
            self._last_write_t = time.monotonic()
            self._remember_rows(slots, vectors)
            self._add_to_delta(slots, vectors)
            self._maybe_reorganize()
            return slots

    def _remember_rows(self, slots: np.ndarray, vectors: np.ndarray):
        """PQ mode keeps the originals twice: an f32 host mirror (codes
        are lossy — retrain/rebuild/persistence read from here) and the
        device ``_rescore_rows`` tier the exact candidate rescore gathers
        from. Caller holds ``_lock``."""
        if self._host_rows is None or len(slots) == 0:
            return
        if self.normalize_on_add:
            vectors = normalize_np(vectors)
        mx = int(np.max(slots))
        if mx >= len(self._host_rows):
            grown = np.zeros((_next_pow2(mx + 1), self.dim), np.float32)
            grown[: len(self._host_rows)] = self._host_rows
            self._host_rows = grown
        self._host_rows[slots] = vectors
        need = _next_pow2(max(mx + 1, 1024))
        if self._rescore_rows is None:
            self._rescore_rows = self._zeros((need, self.dim), self.dtype)
        elif mx >= self._rescore_rows.shape[0]:
            old = self._rescore_rows
            self._rescore_rows = (self._zeros((need, self.dim), self.dtype)
                                  .at[: old.shape[0]].set(old))
        bucket = _next_pow2(max(len(slots), 8))
        i_buf = np.zeros(bucket, np.int32)
        i_buf[: len(slots)] = slots
        v_buf = np.zeros((bucket, self.dim), np.float32)
        v_buf[: len(slots)] = vectors
        m_buf = np.zeros(bucket, bool)
        m_buf[: len(slots)] = True
        self._rescore_rows = _scatter_rows_at(
            self._rescore_rows, self._put(i_buf), self._put(v_buf),
            self._put(m_buf))
        self._hbm_sync()

    def _add_to_delta(self, slots: np.ndarray, vectors: np.ndarray):
        """Rows into the delta buffer, and both maps of its slots.
        Caller holds ``_lock``."""
        dslots = self.delta.add(vectors)
        if self.delta.capacity > len(self._delta_gmap):  # the delta grew
            grown = np.full(self.delta.capacity, -1, np.int32)
            grown[:len(self._delta_gmap)] = self._delta_gmap
            self._delta_gmap = grown
        self._delta_gmap[dslots] = slots
        for g, d in zip(slots.tolist(), dslots.tolist()):
            self._delta_slots[int(d)] = int(g)
            self._slot_loc[int(g)] = ("delta", int(d))
        self._set_loc(slots, -2 - np.asarray(dslots, dtype=np.int64))

    def _set_loc(self, slots, loc) -> None:
        """Positions of ``slots`` in the array twin of ``_slot_loc``
        (``loc``: an array, or one value for all). Caller holds
        ``_lock``."""
        slots = np.asarray(slots, dtype=np.int64)
        if not len(slots):
            return
        top = int(slots.max())
        if top >= len(self._loc_np):
            grown = np.full(_next_pow2(top + 1), -1, np.int32)
            grown[:len(self._loc_np)] = self._loc_np
            self._loc_np = grown
        self._loc_np[slots] = loc
        self._layout_gen += 1

    def _punch_hole(self, flat_idx: int):
        """Record a freed list position for hole-first refill. Caller
        holds ``_lock``; positions (not flat indices) survive cap growth."""
        l, p = divmod(int(flat_idx), self.list_cap)
        self._holes.setdefault(l, []).append(p)

    def set_at(self, slots: np.ndarray, vectors: np.ndarray):
        """Overwrite slots in place. List-resident slots are tombstoned there
        and re-routed through the delta buffer (their assignment may change)."""
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        vectors = np.asarray(vectors, dtype=np.float32)
        if vectors.ndim == 1:
            vectors = vectors[None, :]
        with self._lock:
            self._count = max(self._count, int(slots.max()) + 1 if len(slots) else 0)
            self._last_write_t = time.monotonic()
            self._remember_rows(slots, vectors)
            delta_upd_d, delta_upd_v = [], []
            fresh_s, fresh_v = [], []
            clear_flat = []
            for s, v in zip(slots.tolist(), vectors):
                loc = self._slot_loc.get(int(s))
                if loc is not None and loc[0] == "delta":
                    delta_upd_d.append(loc[1])
                    delta_upd_v.append(v)
                else:
                    if loc is not None:  # list-resident: tombstone there
                        clear_flat.append(loc[1])
                        self._punch_hole(loc[1])
                    fresh_s.append(int(s))
                    fresh_v.append(v)
            if clear_flat:
                self.list_valid = _clear_list_rows(
                    self.list_valid, self._put(np.asarray(clear_flat, dtype=np.int32)))
            if delta_upd_d:
                self.delta.set_at(np.asarray(delta_upd_d),
                                  np.stack(delta_upd_v))
            if fresh_s:
                self._add_to_delta(np.asarray(fresh_s), np.stack(fresh_v))
            self._maybe_reorganize()

    def delete(self, slots) -> None:
        slots = np.atleast_1d(np.asarray(slots, dtype=np.int64))
        with self._lock:
            self._last_write_t = time.monotonic()
            clear_flat, delta_del = [], []
            self._set_loc(slots[(slots >= 0) & (slots < len(self._loc_np))],
                          -1)
            for s in slots.tolist():
                loc = self._slot_loc.pop(int(s), None)
                if loc is None:
                    continue
                if loc[0] == "delta":
                    delta_del.append(loc[1])
                    self._delta_slots.pop(loc[1], None)
                    self._delta_gmap[loc[1]] = -1
                else:
                    clear_flat.append(loc[1])
                    self._punch_hole(loc[1])
            if delta_del:
                self.delta.delete(np.asarray(delta_del))
            if clear_flat:
                self.list_valid = _clear_list_rows(
                    self.list_valid, self._put(np.asarray(clear_flat, dtype=np.int32)))

    # -- training / reorganization -------------------------------------------

    def _maybe_reorganize(self):
        if not self.trained:
            if len(self._slot_loc) >= self.train_threshold:
                self.train()
        elif len(self._delta_slots) >= self.delta_threshold:
            # a full delta is folded on the write path, and where the
            # corpus has outgrown its partition the fold is the retrain:
            # both fall at row counts the writes alone decide, never at
            # a maintenance tick's
            if self._retrain_due():
                self.train()
            else:
                self.flush_delta()

    def _retrain_due(self) -> bool:
        """The centroid-drift proxy: the live count grew
        ``retrain_factor`` x since the lists were trained."""
        return (len(self._slot_loc)
                >= self.retrain_factor * max(self._live_at_train, 1))

    def _auto_nlist(self, n: int) -> int:
        # ~2*sqrt(N) lists, pow2-rounded, clamped: large enough to prune,
        # small enough that centroids fit one matmul
        return int(min(8192, max(16, _next_pow2(int(2 * math.sqrt(n))))))

    def train(self, force_nlist: int | None = None):
        """Learn the coarse partition from current contents and move
        everything into posting lists (reference analog: hnsw compress.go:38
        trains PQ once enough data exists — same lifecycle hook). On an
        already-trained store this is the RETRAIN path (``maintain``'s
        drift gate lands here); routine delta absorption goes through
        ``flush_delta`` without touching the centroids. The number of
        lists is the user's where one was given and else follows the
        corpus (``_auto_nlist`` of the live rows), at every retrain."""
        with self._lock, maintain_stage(
                "train", "ivf.train", retrain=self.trained) as (sp, _):
            t0 = time.perf_counter()
            vecs, slots = self._all_live_host()
            n = len(vecs)
            if n == 0:
                raise RuntimeError("cannot train IVF on an empty store")
            was_trained = self.trained
            nlist = force_nlist or self._user_nlist or self._auto_nlist(n)
            nlist = min(nlist, n)
            self.nlist = nlist
            sp.set(rows=n, nlist=nlist)
            cents = kmeans_fit(vecs, nlist, iters=10)
            if self.normalize_on_add:
                # keep centroids on the sphere so probe distances stay comparable
                cents = normalize_np(cents)
            self._centroids_np = np.asarray(cents, dtype=np.float32)
            self.centroids = self._put(self._centroids_np)
            self._c_norms = jnp.sum(self.centroids * self.centroids, axis=1)
            assign = kmeans_assign(vecs, self._centroids_np)
            if self.quantization:
                from weaviate_tpu.ops.pq import pq_fit

                # the codebook quantizes RESIDUALS, not raw vectors — the
                # coarse assignment has already absorbed most of the
                # variance, so the same m×kc budget codes a much tighter
                # distribution (classic IVFADC)
                res = vecs - self._centroids_np[assign]
                self.codebook = pq_fit(res, m=self.pq_segments,
                                       k=self.pq_centroids, iters=8)
            self._rebuild_lists(vecs, slots, assign=assign)
            # delta fully absorbed
            self._reset_delta()
            self._live_at_train = len(self._slot_loc)
            if was_trained:
                self.retrain_count += 1
            self._hbm_sync()
            self._publish_gauges()
            self.train_seconds += time.perf_counter() - t0

    def maintain(self, tick: bool = False, now: float | None = None) -> bool:
        """Incremental maintenance hook (db/shard.py epoch maintenance):
        fold the delta into lists; RETRAIN only when the corpus outgrew
        its partition (live count >= retrain_factor x live-at-train — the
        centroid-drift proxy). Compaction never lands here, so steady
        tombstone churn costs hole-refills, not full rebuilds.

        ``tick``: the call is the cyclemanager's, not a caller's who
        wants the fold now. A tick leaves a part-filled delta alone
        while writes keep arriving (its rows are searched exactly where
        they are, a full delta is folded on the write path, and a fold
        at an arbitrary moment of an import would make the lists depend
        on when a tick fell) and folds it at the first tick that finds
        the writes paused: none for ``TAIL_FOLD_PAUSE_S`` by the clock
        (``now``: ``time.monotonic()``), however long the scheduler's
        one thread was kept from ticking meanwhile. -> whether work was
        done or is left for the next tick (the cyclemanager's backoff
        signal)."""
        with self._lock:
            now = time.monotonic() if now is None else now
            moved = now - self._last_write_t < TAIL_FOLD_PAUSE_S
            if not self.trained:
                if len(self._slot_loc) >= self.train_threshold:
                    self.train()
                    return True
                return False
            if self._retrain_due():
                self.train()
                return True
            if not self._delta_slots:
                return False
            if not (tick and moved):
                self.flush_delta()
            return True

    def _publish_gauges(self) -> None:
        """The index's shape as of this train or flush (caller holds
        ``_lock``)."""
        ivf_lists.labels(*self._labels).set(self.nlist)
        ivf_list_capacity.labels(*self._labels).set(self.list_cap)
        ivf_delta_rows.labels(*self._labels).set(len(self._delta_slots))
        ivf_live_rows.labels(*self._labels).set(len(self._slot_loc))

    def _put(self, arr):
        """``arr`` on this store's device (runtime/placement.py)."""
        return placement.put(arr, self.device)

    def _zeros(self, shape, dtype):
        return placement.zeros(shape, dtype, self.device)

    def _all_live_host(self):
        """(vectors [L,d] f32, slots [L] int64) for every live slot."""
        out_v, out_s = [], []
        if self.trained and (self.list_vecs is not None
                             or self.list_codes is not None):
            lval = np.asarray(self.list_valid).reshape(-1)
            lslot = np.asarray(self.list_slots).reshape(-1)
            live = np.nonzero(lval)[0]
            slots_live = lslot[live].astype(np.int64)
            if self.quantization:
                # codes are lossy — originals live in the host mirror
                out_v.append(self._host_rows[slots_live])
            else:
                lv = np.asarray(self.list_vecs,
                                dtype=np.float32).reshape(-1, self.dim)
                out_v.append(lv[live])
            out_s.append(slots_live)
        dsnap = self.delta.snapshot()
        dlive = np.nonzero(dsnap["valid"])[0]
        if len(dlive):
            out_v.append(dsnap["vectors"][dlive])
            out_s.append(np.asarray(
                [self._delta_slots[int(d)] for d in dlive], dtype=np.int64))
        if not out_v:
            return (np.empty((0, self.dim), np.float32),
                    np.empty(0, np.int64))
        return np.concatenate(out_v), np.concatenate(out_s)

    def _rebuild_lists(self, vecs: np.ndarray, slots: np.ndarray,
                       assign: np.ndarray | None = None):
        """Assign + scatter everything into fresh list tensors.
        Caller holds ``_lock`` (train/retrain/compress section)."""
        if assign is None:
            assign = (kmeans_assign(vecs, self._centroids_np)
                      if len(vecs) else np.empty(0, np.int64))
        assign = np.asarray(assign, dtype=np.int64)
        n = len(vecs)
        counts = (np.bincount(assign, minlength=self.nlist) if n
                  else np.zeros(self.nlist, dtype=np.int64))
        # cap targets ~2x the perfectly-even fill (pow2) instead of the
        # fullest list: one hot cluster no longer pads EVERY list to its
        # size — overfull lists spill their farthest members to the
        # next-nearest centroid with room (imbalance-aware nprobe)
        cap = max(8, _next_pow2(-(-2 * n // max(self.nlist, 1))) if n else 8)
        while self.nlist * cap < n:
            cap *= 2
        if n:
            cap = min(cap, max(8, _next_pow2(int(counts.max()))))
        while True:
            spilled = self._spill_overfull(vecs, assign, cap)
            if spilled is not None:
                assign = spilled
                break
            cap *= 2  # unplaceable at this cap — relax and retry
        self.list_cap = cap
        if self.quantization:
            self.list_codes = self._zeros(
                (self.nlist, cap, self.pq_segments), jnp.uint8)
            self.list_tvals = self._zeros((self.nlist, cap), jnp.float32)
            self.list_vecs = None
            self.list_norms = None
        else:
            self.list_vecs = self._zeros((self.nlist, cap, self.dim),
                                         self.dtype)
            self.list_norms = self._zeros((self.nlist, cap), jnp.float32)
            self.list_codes = None
            self.list_tvals = None
        self.list_valid = self._zeros((self.nlist, cap), jnp.bool_)
        self.list_slots = self._put(
            np.full((self.nlist, cap), -1, dtype=np.int32))
        self._fill = np.zeros(self.nlist, dtype=np.int64)
        self._holes = {}
        self.rebuild_count += 1
        self._hbm_sync()
        self._scatter_assigned(vecs, slots, assign)

    def _spill_overfull(self, vecs: np.ndarray, assign: np.ndarray,
                        cap: int) -> np.ndarray | None:
        """Rebalance at train time: each overfull list keeps its ``cap``
        CLOSEST members (ties break toward the lower row index —
        deterministic) and spills the rest to the nearest centroid with
        room. Returns the adjusted assignment, or None when some row
        cannot be placed anywhere at this cap (caller doubles cap).
        Keeps cap-padding honest: without it one hot cluster sets cap
        for every list and the probe gathers mostly dead padding."""
        counts = np.bincount(assign, minlength=self.nlist)
        over = np.flatnonzero(counts > cap)
        if len(over) == 0:
            return assign
        cents = self._centroids_np
        assign = assign.copy()
        room = np.clip(cap - counts, 0, None)
        for l in over.tolist():
            members = np.flatnonzero(assign == l)
            d_own = np.sum((vecs[members] - cents[l]) ** 2, axis=1)
            # lexsort's LAST key is primary: distance asc, index tiebreak
            order = members[np.lexsort((members, d_own))]
            for r in order[cap:].tolist():
                d_all = np.sum((cents - vecs[r]) ** 2, axis=1)
                d_all[l] = np.inf
                for t in np.argsort(d_all, kind="stable").tolist():
                    if room[t] > 0:
                        assign[r] = t
                        room[t] -= 1
                        break
                else:
                    return None
        return assign

    def _take_position(self, l: int) -> int:
        """Next free position in list ``l``: holes first (LIFO), then the
        tail. -1 when the list is full. Caller holds ``_lock``."""
        hs = self._holes.get(l)
        if hs:
            return hs.pop()
        if self._fill[l] < self.list_cap:
            p = int(self._fill[l])
            self._fill[l] += 1
            return p
        return -1

    def _find_room(self, vec: np.ndarray, exclude: int) -> int:
        """Nearest centroid (excluding ``exclude``) whose list has a hole
        or tail room — the runtime spill target. -1 if every list is full."""
        d = np.sum((self._centroids_np - vec) ** 2, axis=1)
        d[exclude] = np.inf
        for t in np.argsort(d, kind="stable").tolist():
            if self._holes.get(t) or self._fill[t] < self.list_cap:
                return int(t)
        return -1

    def _scatter_assigned(self, vecs, slots, assign):
        """Place (vec, slot) pairs: holes first, then the list tail, then
        spill to the next-nearest centroid with room; only when EVERY
        list is full does capacity grow. Residual-PQ encodes against the
        FINAL assignment (spill included), so codes always quantize the
        residual of the centroid actually probed."""
        if len(vecs) == 0:
            return 0
        assign = np.asarray(assign, dtype=np.int64).copy()
        pos = np.empty(len(assign), dtype=np.int64)
        spilled = 0
        for i, l in enumerate(assign.tolist()):
            p = self._take_position(int(l))
            if p >= 0:
                pos[i] = p
                continue
            t = self._find_room(vecs[i], exclude=int(l))
            if t >= 0:
                assign[i] = t
                pos[i] = self._take_position(t)
                spilled += 1
            else:
                self._grow_cap()
                pos[i] = self._take_position(int(l))
        # positions stay valid across _grow_cap (p < old_cap < new_cap);
        # flat indices are computed once, against the FINAL cap
        flat_idx = assign * self.list_cap + pos
        bucket = _next_pow2(max(len(vecs), 8))
        i_buf = np.zeros(bucket, np.int32)
        i_buf[:len(vecs)] = flat_idx
        s_buf = np.zeros(bucket, np.int32)
        s_buf[:len(vecs)] = slots
        m_buf = np.zeros(bucket, bool)
        m_buf[:len(vecs)] = True
        if self.quantization:
            from weaviate_tpu.ops.pq import pq_encode, pq_reconstruct

            cents = self._centroids_np[assign]
            res = vecs - cents
            codes = pq_encode(self.codebook, res)
            rhat = np.asarray(pq_reconstruct(  # graftlint: disable=G1 — maintenance-time boundary (encode, not serving)
                self._put(codes), self.codebook.centroids,
                self.codebook.m))
            tvals = (2.0 * np.sum(cents * rhat, axis=1)
                     + np.sum(rhat * rhat, axis=1)).astype(np.float32)
            c_buf = np.zeros((bucket, self.pq_segments), np.uint8)
            c_buf[:len(vecs)] = codes
            t_buf = np.zeros(bucket, np.float32)
            t_buf[:len(vecs)] = tvals
            (self.list_codes, self.list_valid, self.list_slots,
             self.list_tvals) = _scatter_code_lists(
                self.list_codes, self.list_valid, self.list_slots,
                self.list_tvals,
                self._put(i_buf), self._put(c_buf), self._put(t_buf),
                self._put(s_buf), self._put(m_buf))
        else:
            v_buf = np.zeros((bucket, self.dim), np.float32)
            v_buf[:len(vecs)] = vecs
            (self.list_vecs, self.list_valid, self.list_slots,
             self.list_norms) = _scatter_lists(
                self.list_vecs, self.list_valid, self.list_slots,
                self.list_norms,
                self._put(i_buf), self._put(v_buf), self._put(s_buf),
                self._put(m_buf))
        for s, fi in zip(slots.tolist(), flat_idx.tolist()):
            self._slot_loc[int(s)] = ("list", int(fi))
        self._set_loc(slots, flat_idx)
        return spilled

    def _grow_cap(self):
        """Double per-list capacity (repack on host — rare, amortized).
        Caller holds ``_lock``."""
        old_cap = self.list_cap
        new_cap = old_cap * 2
        pad = new_cap - old_cap
        if self.quantization:
            self.list_codes = jnp.concatenate(
                [self.list_codes,
                 self._zeros((self.nlist, pad, self.pq_segments),
                             jnp.uint8)], axis=1)
            self.list_tvals = jnp.concatenate(
                [self.list_tvals,
                 self._zeros((self.nlist, pad), jnp.float32)], axis=1)
        else:
            self.list_vecs = jnp.concatenate(
                [self.list_vecs,
                 self._zeros((self.nlist, pad, self.dim), self.dtype)],
                axis=1)
            self.list_norms = jnp.concatenate(
                [self.list_norms,
                 self._zeros((self.nlist, pad), jnp.float32)], axis=1)
        self.list_valid = jnp.concatenate(
            [self.list_valid, self._zeros((self.nlist, pad), jnp.bool_)],
            axis=1)
        self.list_slots = jnp.concatenate(
            [self.list_slots,
             self._put(np.full((self.nlist, pad), -1, dtype=np.int32))],
            axis=1)
        self.list_cap = new_cap
        self._hbm_sync()
        # flat indices shift: old flat l*old_cap+p -> l*new_cap+p
        # (hole POSITIONS are cap-invariant and carry over untouched)
        for s, loc in self._slot_loc.items():
            if loc[0] == "list":
                l, p = divmod(loc[1], old_cap)
                self._slot_loc[s] = ("list", l * new_cap + p)
        listed = self._loc_np >= 0
        self._loc_np[listed] += (self._loc_np[listed] // old_cap) * pad
        self._layout_gen += 1

    def flush_delta(self):
        """Merge the delta buffer into posting lists (memtable flush) —
        an INCREMENTAL scatter into holes/tails, never a rebuild."""
        with self._lock:
            if not self.trained:
                return
            if not self._delta_slots and self.delta.count == 0:
                return
            with maintain_stage("flush", "ivf.flush_delta") as (sp, _):
                self._flush_delta_locked(sp)
            self._publish_gauges()

    def _flush_delta_locked(self, sp) -> None:
        """The fold itself (caller holds ``_lock``)."""
        dsnap = self.delta.snapshot()
        live = np.nonzero(dsnap["valid"])[0]
        if len(live) == 0:
            self._reset_delta()
            return
        vecs = dsnap["vectors"][live]
        slots = np.asarray([self._delta_slots[int(d)] for d in live],
                           dtype=np.int64)
        if self.quantization and self.codebook is None:
            # compression was enabled while the store was empty —
            # the codebook trains on the first flush with enough data
            # (until then rows stay in the exact delta)
            if len(vecs) < self.pq_centroids:
                return
            from weaviate_tpu.ops.pq import pq_fit

            a0 = kmeans_assign(vecs, self._centroids_np)
            self.codebook = pq_fit(vecs - self._centroids_np[a0],
                                   m=self.pq_segments,
                                   k=self.pq_centroids, iters=8)
        assign = kmeans_assign(vecs, self._centroids_np)
        spilled = self._scatter_assigned(vecs, slots, assign)
        sp.set(rows=len(vecs), spilled=spilled)
        self._reset_delta()

    def _reset_delta(self):
        """Swap in a fresh delta store. Caller holds ``_lock``."""
        # rebuilt outside the shard's construction scope — re-enter the
        # captured owner labels so the fresh delta store stays attributed
        with hbm_ledger.owner(**self._hbm_owner):
            self.delta = DeviceVectorStore(
                self.dim, self.metric,
                capacity=min(self.capacity, self.delta_threshold * 2),
                chunk_size=self.chunk_size, dtype=self.dtype)
        self._delta_slots = {}
        self._delta_gmap = np.full(self.delta.capacity, -1, np.int32)

    # -- queries -------------------------------------------------------------

    def _effective_nprobe(self) -> int:
        if self.nprobe:
            return min(self.nprobe, self.nlist)
        return min(self.nlist, max(8, self.nlist // 8))

    def _delta_allow(self, allow_mask, b: int):
        """Project the GLOBAL allow mask ([cap] shared or [B, cap]
        per-query) onto delta-local slots. Caller holds ``_lock``."""
        if allow_mask is None:
            return None
        cap_d = self.delta.capacity
        gmap = self._delta_gmap[:cap_d]
        ds = np.flatnonzero((gmap >= 0) & (gmap < allow_mask.shape[-1]))
        if allow_mask.ndim == 2:
            out = np.zeros((b, cap_d), dtype=bool)
            out[:, ds] = allow_mask[:, gmap[ds]]
            return out
        out = np.zeros(cap_d, dtype=bool)
        out[ds] = allow_mask[gmap[ds]]
        return out

    def search(self, queries: np.ndarray, k: int,
               allow_mask: np.ndarray | None = None,
               nprobe: int | None = None):
        """Merged top-k over delta (exact) + probed lists (ANN). This IS
        ``search_async(...).result()`` — sync and async agree bit-for-bit
        by construction; the D2H transfer rides the handle's sanctioned
        boundary (transfer.d2h span)."""
        return self.search_async(queries, k, allow_mask,
                                 nprobe=nprobe).result()

    def probe_chunk(self) -> int:
        """Query rows ONE probe program takes. 16 in both served cells;
        at 768-d a chunk's slabs are 3.2 GB, which the chip's program
        never holds whole (`memory_peak_bytes` 2.5 GB beside 1.6 GB of
        lists: PERF.md, PR 51), so no rule cuts it by bytes."""
        return max(1, self.query_chunk)

    def exact_route(self, m_allowed: int) -> bool:
        """The cutoff rule (upstream's ``flatSearchCutoff``): a filter
        that allows fewer than ``flat_search_cutoff`` live rows of a
        TRAINED store is answered exactly over those rows, never by the
        probe. An untrained store scans its delta buffer, which is exact
        already; a cutoff of 0 turns the rule off."""
        return (m_allowed < self.flat_search_cutoff and self.trained
                and (self.list_vecs is not None
                     or self._rescore_rows is not None))

    def gathered_slots(self, slot_mask: np.ndarray) -> AllowSlots:
        """ONE filter's operand by the cutoff rule, as the flat store's
        method of the same name gives its own cutover's: under the
        cutoff the allowed slots ascending in a pow2 bucket on the
        device (then -1), for the exact route; else ``slots`` None and
        the masked probe serves it. Called with ``_lock`` held, or by an
        index that holds its own over every write."""
        m_allowed = int(np.count_nonzero(slot_mask))
        if not self.exact_route(m_allowed):
            return AllowSlots(None, m_allowed)
        slot_buf = np.full(1 << max(7, (m_allowed - 1).bit_length()), -1,
                           dtype=np.int32)
        slot_buf[:m_allowed] = np.flatnonzero(slot_mask)
        return AllowSlots(self._put(slot_buf), m_allowed)

    def slot_map(self):
        """``_loc_np`` over the slot space on the device, uploaded again
        where a write, a fold, a retrain or a growth of ``cap`` has moved
        a row since (``_layout_gen``). Caller holds ``_lock``."""
        n = self.capacity
        if (self._slot_map is None or self._slot_map_gen != self._layout_gen
                or self._slot_map.shape[0] != n):
            buf = np.full(n, -1, np.int32)
            w = min(n, len(self._loc_np))
            buf[:w] = self._loc_np[:w]
            self._slot_map = self._put(buf)
            self._slot_map_gen = self._layout_gen
            hbm_ledger.ledger.set_keyed(
                self._hbm_keys, "slot_map", int(self._slot_map.nbytes),
                owner=self._hbm_owner, dtype="int32")
        return self._slot_map

    def delta_rows(self):
        """(delta slots, their global slots) of the rows the delta
        buffer holds. Caller holds ``_lock``."""
        gmap = self._delta_gmap[:self.delta.capacity]
        ds = np.flatnonzero(gmap >= 0)
        return ds, gmap[ds]

    def _routed(self, allow_mask, b: int) -> IVFAllow:
        """What ``search_async`` was handed, in ``IVFAllow``'s terms. A
        raw mask is routed here: ONE mask for the block by the cutoff
        rule (``gathered_slots``), a ``[B, capacity]`` block of them to
        the masked probe, packed on the host a dispatch as it always was
        (an index that prepared its operands hands an ``IVFAllow`` or an
        ``AllowSlots`` and nothing is packed). Caller holds ``_lock``."""
        rows = np.arange(b)
        if isinstance(allow_mask, IVFAllow):
            return allow_mask
        if allow_mask is None:
            return IVFAllow((), rows, b, 0, None, None)
        if not isinstance(allow_mask, (AllowSlots, np.ndarray)):
            raise TypeError(
                f"an IVF store takes a bool mask, AllowSlots or IVFAllow, "
                f"not {type(allow_mask).__name__}")
        if not isinstance(allow_mask, AllowSlots) and allow_mask.ndim == 1:
            op = self.gathered_slots(allow_mask)
            if op.slots is not None:
                allow_mask = op
        if isinstance(allow_mask, AllowSlots):
            return IVFAllow(((allow_mask.slots, allow_mask.count, rows),),
                            rows, 0, 0, None, None)
        from weaviate_tpu.ops.pallas_kernels import (mask_pad_cols,
                                                     pack_allow_bitmask)

        bits = self._put(pack_allow_bitmask(
            allow_mask, mask_pad_cols(self.capacity)))
        hbm_ledger.ledger.track("allow_bitmask", bits, **self._hbm_owner)
        return IVFAllow((), rows, b, b, bits,
                        self._delta_allow(allow_mask, b)
                        if self.delta.live_count() > 0 else None)

    def search_async(self, queries: np.ndarray, k: int,
                     allow_mask: np.ndarray | None = None,
                     nprobe: int | None = None) -> DeviceResultHandle:
        """Dispatch-only twin of ``search``: every leg launches under
        ``_lock`` and the results stay device-resident in the returned
        handle. ``allow_mask`` takes the DeviceVectorStore forms, [cap]
        bool shared or [B, cap] bool per-query, and what an index
        prepared on the device (``AllowSlots``, ``IVFAllow``).

        Two routes (``IVFAllow``): rows whose filter allows fewer live
        rows than ``flat_search_cutoff`` are answered EXACTLY, one
        program a distinct mask over the whole block
        (``_ivf_flat_cutoff_topk``: the allowed rows gathered by slot
        from the lists and the delta); the others by the delta buffer's
        exact scan (``epoch_scan``, ids remapped to global on the
        device) merged with the probe (+ residual-PQ exact rescore via
        the candidate plane), per-query ``allow_bits`` folded a
        candidate inside the probe, in chunks of ``probe_chunk`` rows
        with the rows that want the probe's answer FIRST, so a block of
        32 whose half took the exact route probes once, not twice. The
        legs' answers are joined a row on the host (``_finish``)."""
        queries = np.asarray(queries, dtype=np.float32)
        squeeze = queries.ndim == 1
        if squeeze:
            queries = queries[None, :]
        b = len(queries)
        if not isinstance(allow_mask, IVFAllow):
            allow_mask = normalize_allow_mask(allow_mask, b)
        np_probe, gather = 0, ""
        with tracing.span("ivf.search", queries=b, k=k,
                          filtered=allow_mask is not None) as sp, \
                self._lock:
            plan = self._routed(allow_mask, b)
            exact_out = []            # (dists, slots) a distinct mask
            delta_leg = None          # (dists, slots) where the delta holds rows
            outs_d, outs_i = [], []   # the probe's, a chunk of queries each
            delta_live = self.delta.live_count() > 0
            metric = self.metric
            if plan.exact:
                gathered = sum(count for _s, count, _r in plan.exact)
                with tracing.span("ivf.flat_cutoff", masks=len(plan.exact),
                                  rows=gathered, queries=b,
                                  with_delta=delta_live):
                    q_all = self._put(queries)
                    if delta_live:
                        self.delta.flush_staged()
                    for slots, _count, _rows in plan.exact:
                        if self.quantization:
                            exact_out.append(_ivf_flat_cutoff_topk_rows(
                                q_all, slots, self._rescore_rows, k,
                                metric))
                        else:
                            exact_out.append(_ivf_flat_cutoff_topk(
                                q_all, slots, self.slot_map(),
                                self.list_vecs, self.delta.vectors, k,
                                metric))
                ivf_cutoff_rows_total.inc(gathered)
                ivf_cutoff_programs_total.inc(len(plan.exact))
                ivf_filtered_requests_total.labels("flat_cutoff").inc(
                    sum(len(rows) for _s, _c, rows in plan.exact))
            if plan.n_filtered:
                ivf_filtered_requests_total.labels("probe").inc(
                    plan.n_filtered)
            probes = (self.trained and self._fill is not None
                      and int(self._fill.sum()) > 0)
            lists_probed = min((nprobe or self._effective_nprobe()),
                               self.nlist) if probes else 0
            # the rows the delta's scan and the probe take: those that
            # want their answer first, then whatever fills the last chunk
            chunk = self.probe_chunk() if probes else max(b, 1)
            m = min(b, -(-plan.n_probe // chunk) * chunk)
            np_probe = lists_probed if m else 0
            qp = queries[plan.order[:m]]
            if m and delta_live:
                d_allow = plan.delta_allow
                if d_allow is not None and d_allow.ndim == 2:
                    d_allow = d_allow[plan.order[:m]]
                dd, di = self.delta.epoch_scan(
                    qp, min(k, self.delta.capacity), d_allow)
                gd = self._put(self._delta_gmap)
                di = jnp.where(di >= 0,
                               gd[jnp.clip(di, 0, gd.shape[0] - 1)], -1)
                delta_leg = (jnp.where(di >= 0, dd, MASKED_DISTANCE),
                             di.astype(jnp.int32))
            if m and probes:
                use_allow = plan.bits is not None
                if not use_allow and self._no_bits is None:
                    self._no_bits = self._put(
                        np.zeros((1, _MASK_WORDS), np.uint32))
                k_cand = k * self.rescore_limit if self.quantization else k
                k_eff = min(k_cand, np_probe * self.list_cap)
                # how the probe reads its lists: whole ``[cap, d]`` slabs
                # of rows, or slabs of codes and then the survivors' rows
                gather = "codes" if self.quantization else "slab"
                for n, s in enumerate(range(0, m, chunk)):
                    q_dev = self._put(qp[s:s + chunk])
                    if not use_allow:
                        bch = self._no_bits
                    elif isinstance(plan.bits, tuple):
                        bch = plan.bits[n]    # stacked a chunk already
                    elif plan.bits.shape[0] == 1:
                        bch = plan.bits
                    else:
                        bch = plan.bits[s:s + chunk]
                    if self.quantization:
                        _, cand = _ivf_probe_topk_pq(
                            q_dev, self.centroids, self._c_norms,
                            self.list_codes, self.list_valid,
                            self.list_slots, self.list_tvals,
                            self.codebook.centroids, bch, k_eff,
                            np_probe, self.metric, use_allow)
                        # exact device rescore of the ADC oversample —
                        # masks already folded (dropped slots are -1)
                        qd, qs_ = gather_rescore_topk(
                            q_dev, cand, self._rescore_rows,
                            min(k, k_eff), self.metric)
                    else:
                        qd, qs_ = _ivf_probe_topk(
                            q_dev, self.centroids, self._c_norms,
                            self.list_vecs, self.list_valid,
                            self.list_slots, self.list_norms, bch, k_eff,
                            np_probe, self.metric, use_allow)
                    outs_d.append(qd)
                    outs_i.append(qs_)
                # EXPLAIN: the probe plan, host ints only (no device
                # reads — G1 stays empty); a no-op unless a sink is
                # installed for this dispatch
                kernelscope.explain_note(
                    "ivf", nprobe=np_probe, nlist=self.nlist,
                    lists_frac=(round(np_probe / self.nlist, 6)
                                if self.nlist else 0.0),
                    candidates=k_eff,
                    rescored=(k_eff if self.quantization else 0),
                    quantized=bool(self.quantization),
                    filtered=bool(use_allow), queries=m, k=k,
                    delta_leg=delta_leg is not None, gather=gather)
                ivf_queries_total.inc(m)
                ivf_probed_lists_total.inc(m * np_probe)
                ivf_candidate_rows_total.inc(m * np_probe * self.list_cap)
                ivf_probe_programs_total.inc(len(outs_d))
                ivf_probe_dispatches_total.inc()
            route = ("both" if plan.exact and plan.n_filtered
                     else "flat_cutoff" if plan.exact
                     else "probe" if plan.n_filtered else "")
            sp.set(nprobe=np_probe, nlist=self.nlist,
                   list_cap=self.list_cap,
                   delta_rows=len(self._delta_slots),
                   candidates=np_probe * self.list_cap, gather=gather)
            if route:
                sp.set(route=route, cutoff=self.flat_search_cutoff,
                       allowed=sum(c for _s, c, _r in plan.exact))
            kernelscope.explain_note(
                "ivf", merge_legs=(delta_leg is not None) + bool(outs_d),
                route=route or "unfiltered",
                cutoff=self.flat_search_cutoff,
                exact_masks=len(plan.exact))
            if delta_leg is None and not outs_d and not exact_out:
                d_e = np.full((b, k), MASKED_DISTANCE, np.float32)
                i_e = np.full((b, k), -1, np.int64)
                return DeviceResultHandle.ready(
                    (d_e[0], i_e[0]) if squeeze else (d_e, i_e))
            if delta_leg is None:
                # the probe alone (an empty delta): each chunk's pair
                # crosses to the host as its program left it and the
                # chunks are joined there: ONE Execute a chunk
                arrays = tuple(a for pair in zip(outs_d, outs_i)
                               for a in pair)
            elif not outs_d:
                arrays = delta_leg
            else:
                probe_leg = [outs[0] if len(outs) == 1
                             else jnp.concatenate(outs)
                             for outs in (outs_d, outs_i)]
                cat_d, cat_i = (jnp.concatenate(pair, axis=1)
                                for pair in zip(delta_leg, probe_leg))
                arrays = topk_smallest(cat_d, cat_i,
                                       min(k, cat_d.shape[1]))
            n_scan = len(arrays)      # the delta's and the probe's arrays
            arrays = tuple(arrays) + tuple(
                a for pair in exact_out for a in pair)

        # what the host's half needs of the plan: which rows each leg
        # answered (the operands themselves stay with the dispatch)
        probe_rows = plan.order[:plan.n_probe]
        exact_rows = [rows for _slots, _count, rows in plan.exact]

        def _finish(*host, _k=k, _squeeze=squeeze, _n_scan=n_scan):
            # a row each from the leg that answered it, padded to k like
            # the flat store's contract
            d_out = np.full((b, _k), MASKED_DISTANCE, np.float32)
            i_out = np.full((b, _k), -1, np.int64)

            def take(rows, d_np, i_np, at):
                kk = min(_k, d_np.shape[1])
                d_out[rows, :kk] = d_np[at, :kk]
                i_out[rows, :kk] = i_np[at, :kk]

            if _n_scan:
                scan = host[:_n_scan]
                d_np, i_np = (scan if _n_scan == 2 else
                              (np.concatenate(scan[0::2]),
                               np.concatenate(scan[1::2])))
                take(probe_rows, d_np, i_np, slice(len(probe_rows)))
            for g, rows in enumerate(exact_rows):
                take(rows, host[_n_scan + 2 * g], host[_n_scan + 2 * g + 1],
                     rows)
            i_out = np.where(d_out >= MASKED_DISTANCE, -1, i_out)
            if _squeeze:
                return d_out[0], i_out[0]
            return d_out, i_out

        lists_frac = (np_probe / self.nlist) if self.nlist else 0.0
        return DeviceResultHandle(
            arrays, finish=_finish,
            attrs={"queries": b, "k": k, "nprobe": np_probe,
                   "nlist": self.nlist, "lists_frac": lists_frac})

    def search_by_distance(self, query: np.ndarray, max_distance: float,
                           allow_mask: np.ndarray | None = None):
        k = 64
        while True:
            d, i = self.search(query, k, allow_mask)
            within = (d <= max_distance) & (i >= 0)
            if (~within).any() or k >= max(self._count, 1):
                return d[within], i[within]
            k = min(k * 4, max(self._count, 1))

    # -- maintenance ---------------------------------------------------------

    def compact(self) -> np.ndarray:
        """Epoch/tombstone compaction is INCREMENTAL now: deletes already
        punched reusable holes, so compaction just folds the delta into
        lists — no full rebuild (``rebuild_count`` stays flat; the
        epochstore's maintain() relies on this being cheap). Slot ids
        stay stable (identity mapping for live slots) — the IVF layout
        doesn't tie slots to physical rows the way the flat store does."""
        with self._lock:
            mapping = np.full(self.capacity, -1, dtype=np.int64)
            for s in self._slot_loc:
                mapping[s] = s
            if self.trained:
                self.flush_delta()
            return mapping

    # -- persistence ---------------------------------------------------------

    def snapshot(self) -> dict:
        with self._lock:
            vecs, slots = self._all_live_host()
            keep = np.asarray([s in self._slot_loc for s in slots.tolist()],
                              dtype=bool) if len(slots) else np.empty(0, bool)
            return {
                "kind": "ivf",
                "dim": self.dim,
                "metric": self.metric,
                "count": self._count,
                "nlist": self.nlist if self.trained else 0,
                "user_nlist": self._user_nlist,
                "nprobe": self.nprobe,
                "centroids": (np.asarray(self.centroids, np.float32)
                              if self.trained else None),
                "live_vectors": vecs[keep] if len(slots) else vecs,
                "live_slots": slots[keep] if len(slots) else slots,
                "chunk_size": self.chunk_size,
                "dtype": jnp.dtype(self.dtype).name,
                "train_threshold": self.train_threshold,
                "delta_threshold": self.delta_threshold,
                # FlatIndex.snapshot() compatibility ("quantization" keys
                # the FlatIndex restore dispatch; IVF-PQ state rides under
                # its own keys)
                "valid": self._valid_over_slots(),
                "quantization": None,
                "ivf_quantization": self.quantization,
                "pq_segments": self.pq_segments,
                "pq_centroids": self.pq_centroids,
                "rescore_limit": self.rescore_limit,
                "retrain_factor": self.retrain_factor,
                "flat_search_cutoff": self.flat_search_cutoff,
                "pq_codebook": (np.asarray(self.codebook.centroids)
                                if self.codebook is not None else None),
            }

    def _valid_over_slots(self) -> np.ndarray:
        v = np.zeros(self.capacity, dtype=bool)
        for s in self._slot_loc:
            v[s] = True
        return v

    @classmethod
    def restore(cls, snap: dict, **kwargs) -> "IVFStore":
        # storage dtype survives the round-trip unless explicitly overridden
        # (same contract as DeviceVectorStore.restore)
        dtype = kwargs.pop("dtype", None) or jnp.dtype(snap.get("dtype", "float32"))
        # (a snapshot from before the two were kept apart pins the
        # number it was trained with, as that tree did)
        store = cls(dim=snap["dim"], metric=snap["metric"],
                    nlist=snap.get("user_nlist", snap.get("nlist", 0)),
                    nprobe=snap.get("nprobe", 0),
                    chunk_size=snap.get("chunk_size", 8192),
                    train_threshold=snap.get("train_threshold", 16_384),
                    delta_threshold=snap.get("delta_threshold", 8192),
                    dtype=dtype,
                    quantization=snap.get("ivf_quantization"),
                    pq_segments=snap.get("pq_segments"),
                    pq_centroids=snap.get("pq_centroids", 16),
                    rescore_limit=snap.get("rescore_limit", 16),
                    retrain_factor=snap.get("retrain_factor", 4.0),
                    flat_search_cutoff=snap.get(
                        "flat_search_cutoff", DEFAULT_FLAT_SEARCH_CUTOFF))
        slots = np.asarray(snap["live_slots"], dtype=np.int64)
        vecs = np.asarray(snap["live_vectors"], dtype=np.float32)
        store._count = snap["count"]
        if snap.get("pq_codebook") is not None:
            from weaviate_tpu.ops.pq import PQCodebook

            store.codebook = PQCodebook(jnp.asarray(snap["pq_codebook"]))
        if store.quantization and len(slots):
            # mirror rows were normalized at original insert
            norm = store.normalize_on_add
            store.normalize_on_add = False
            store._remember_rows(slots, vecs)
            store.normalize_on_add = norm
        if snap.get("centroids") is not None:
            store.nlist = snap["nlist"]
            store._centroids_np = np.asarray(snap["centroids"], np.float32)
            store.centroids = store._put(store._centroids_np)
            store._c_norms = jnp.sum(store.centroids * store.centroids, axis=1)
            if store.quantization and store.codebook is None:
                # quantization enabled before any codebook could train
                # (empty compress + sub-threshold adds): rows go back to
                # the exact delta; empty code lists keep _fill truthful
                store._rebuild_lists(np.empty((0, store.dim), np.float32),
                                     np.empty(0, np.int64))
                if len(vecs):
                    store._add_to_delta(slots, vecs)
            else:
                # empty corpora still allocate list tensors so later
                # delta flushes have somewhere to scatter (a None _fill
                # would crash the first _maybe_reorganize)
                store._rebuild_lists(vecs, slots)
            store._live_at_train = len(store._slot_loc)
            store._hbm_sync()  # centroids set outside _rebuild_lists
        elif len(vecs):
            # untrained: everything back into the delta buffer
            store._add_to_delta(slots, vecs)
        return store


class IVFIndex(FlatIndex):
    """VectorIndex-contract ANN index: FlatIndex id<->slot bookkeeping over
    an IVFStore (the bookkeeping is store-agnostic). See FlatIndex for the
    contract docs (reference: vector_index.go:24-45)."""

    index_type = "ivf"
    # IVFStore folds [B, capacity] per-query masks into packed allow_bits
    # inside the probe — the QueryBatcher coalesces filtered IVF requests
    # into one device program instead of dispatching them solo
    supports_batched_filters = True

    def __init__(self, dim: int, metric: str = "l2-squared",
                 capacity: int = 8192, chunk_size: int = 8192,
                 nlist: int = 0, nprobe: int = 0,
                 train_threshold: int = 16_384, delta_threshold: int = 8192,
                 mesh=None, dtype=None, quantization: str | None = None,
                 **quant_kwargs):
        if mesh is not None:
            raise NotImplementedError(
                "ivf is single-replica; collection sharding distributes it")
        store = IVFStore(dim=dim, metric=metric, capacity=capacity,
                         chunk_size=chunk_size, nlist=nlist, nprobe=nprobe,
                         train_threshold=train_threshold,
                         delta_threshold=delta_threshold, dtype=dtype,
                         quantization=quantization, **quant_kwargs)
        super().__init__(dim=dim, metric=metric, capacity=capacity,
                         chunk_size=chunk_size, store=store)

    # -- upstream's flatSearchCutoff ------------------------------------------

    @property
    def flat_search_cutoff(self) -> int:
        """A filter that allows fewer live rows than this is answered by
        an exact scan over them, never by the probe (0: off). The rule is
        applied a REQUEST, from its own mask and the slot table alone
        (``IVFStore.gathered_slots``), so an answer does not depend on
        what the request was coalesced with."""
        return self.store.flat_search_cutoff

    @flat_search_cutoff.setter
    def flat_search_cutoff(self, cutoff: int) -> None:
        with self._lock:
            self.store.flat_search_cutoff = int(cutoff)
            self._slots_moved()   # the kept operands were routed by the old one

    def _exact_operand(self, allow, cache, stamp, translated: dict):
        """One DISTINCT allow list's route -> (``AllowSlots`` for the
        exact route or None for the probe, where the operand came from).
        A mask that cannot change keeps its route with its operand: a
        slot list under the cutoff (kept here), a packed row over it
        (kept by ``_packed_rows``, which finds the slot mask made here
        in ``translated``). Caller holds ``_lock``."""
        keep = stable_mask(allow)
        e = cache.get(allow, stamp) if keep else None
        if e is not None and e.slots is not None:
            return AllowSlots(e.slots, e.slot_count), "hit"
        if e is not None and e.bits is not None:
            return None, ""
        slot_mask = self._allow_mask(allow)
        op = self.store.gathered_slots(slot_mask)
        if op.slots is None:
            translated[id(allow)] = slot_mask
            return None, ""
        if keep:
            cache.attach(allow, stamp, slots=op.slots, slot_count=op.count)
        return op, "miss" if keep else "uncached"

    def _bitmask_operand(self, lists) -> IVFAllow:
        """Per-query allow lists (None = unfiltered) -> the dispatch as
        the store takes it, every operand on the device. Caller holds
        ``_lock``; the ``store.mask_pack`` span is this whole step.

        Each DISTINCT mask is routed once by the cutoff rule
        (``_exact_operand``): under it, its rows share ONE slot list and
        ONE exact program; the others are the probe's, their packed rows
        looked up or built as a flat index does (``_packed_rows``) and
        stacked a CHUNK of the probe, in the order the probe takes them.
        A dispatch whose masks are all known translates, packs and
        uploads nothing (but the delta buffer's few thousand bits a row
        while it holds rows: ``_delta_block``)."""
        store = self.store
        b = len(lists)
        stamp = (self._slot_gen, store.capacity)
        cache = self._operand_cache()
        with tracing.span("store.mask_pack", stage="mask_pack",
                          queries=b) as sp:
            routes: dict[int, tuple | None] = {}   # id(mask) -> exact entry
            translated: dict[int, np.ndarray] = {}
            filtered, plain = [], []               # the probe's rows
            counts = {"hit": 0, "miss": 0, "shared": 0, "uncached": 0}
            for r, a in enumerate(lists):
                if a is None:
                    plain.append(r)
                    continue
                if id(a) not in routes:
                    op, result = self._exact_operand(a, cache, stamp,
                                                     translated)
                    routes[id(a)] = None if op is None else (
                        op.slots, op.count, [r])
                    if op is not None:
                        counts[result] += 1
                    else:
                        filtered.append(r)
                elif routes[id(a)] is None:
                    filtered.append(r)
                else:
                    routes[id(a)][2].append(r)
                    counts["shared"] += 1
            for result, n in counts.items():
                if n:
                    filter_operand_total.labels("gathered", result).inc(n)
            exact = tuple((slots, count, np.asarray(rows))
                          for slots, count, rows in
                          (e for e in routes.values() if e is not None))
            probe = filtered + plain
            probing = np.zeros(b, dtype=bool)
            probing[probe] = True
            order = np.concatenate([np.asarray(probe, dtype=np.int64),
                                    np.flatnonzero(~probing)])
            bits = delta_allow = None
            hits = misses = 0
            if probe:
                # the probe of a filtered dispatch always takes bits, the
                # all-ones row for an unfiltered or padded row: ONE probe
                # variant a block size, also where every filtered row of
                # the drain went the exact route (met once in a few
                # hundred dispatches, so no warm-up would have loaded a
                # second one)
                chunk = store.probe_chunk()
                m = min(b, -(-len(probe) // chunk) * chunk)
                rows, hits, misses, _ = self._packed_rows(
                    [lists[r] if probing[r] else None for r in order[:m]],
                    translated)
                bits = tuple(stack_allow_rows(*rows[s:s + chunk])
                             for s in range(0, m, chunk))
                for block in bits:
                    hbm_ledger.ledger.track("allow_bitmask", block,
                                            **cache.owner)
                if store.delta.live_count() > 0:
                    delta_allow = self._delta_block(lists, filtered)
            sp.set(hits=hits + counts["hit"],
                   misses=misses + counts["miss"], distinct=len(routes),
                   exact_masks=len(exact))
        return IVFAllow(exact, order, len(probe), len(filtered), bits,
                        delta_allow)

    def _delta_block(self, lists, filtered) -> np.ndarray:
        """The probe rows' masks over the DELTA buffer's slots, bool
        ``[B, delta capacity]``: each distinct mask looked up at the doc
        ids of the rows the delta holds (a few thousand at most), all
        ones for the other rows (the store folds its live flags in).
        Caller holds ``_lock``."""
        store = self.store
        ds, gslots = store.delta_rows()
        docs = self._slot_to_id_safe(gslots)
        block = np.ones((len(lists), store.delta.capacity), dtype=bool)
        seen: dict[int, np.ndarray] = {}
        for r in filtered:
            a = lists[r]
            col = seen.get(id(a))
            if col is None:
                if a.dtype == np.bool_:
                    col = ((docs >= 0) & (docs < len(a))
                           & a[np.clip(docs, 0, max(len(a) - 1, 0))]
                           if len(a) else np.zeros(len(docs), dtype=bool))
                else:
                    col = np.isin(docs, a)
                seen[id(a)] = col
            block[r] = False
            block[r, ds] = col
        return block

    def train(self, nlist: int | None = None):
        """Force coarse training now (normally automatic at threshold)."""
        with self._lock:
            self.store.train(force_nlist=nlist)

    def maintain(self, tick: bool = False, now: float | None = None) -> bool:
        """Incremental maintenance (db/shard.py epoch_maintenance): the
        delta folded, a retrain only past the drift gate — never a
        compaction-triggered full rebuild. ``tick``, ``now`` and the
        result as ``IVFStore.maintain``."""
        with self._lock:
            return self.store.maintain(tick=tick, now=now)

    def compress(self, quantization: str = "pq", **quant_kwargs) -> None:
        """Runtime switch to residual-PQ residency: fit a codebook on the
        residuals of live contents and rebuild the posting lists as codes
        (reference lifecycle: hnsw/compress.go:38 via config update).
        Slot ids are stable, so the id<->slot maps carry over untouched.
        On an untrained store the codebook deferral stands (residuals
        need centroids): it trains alongside the coarse partition."""
        if quantization != "pq":
            raise ValueError("ivf supports quantization='pq'")
        from weaviate_tpu.ops.pq import default_pq_segments, pq_fit

        with self._lock:
            st = self.store
            if st.quantization:
                raise RuntimeError("index is already compressed")
            vecs, slots = st._all_live_host()
            # every fallible step runs BEFORE any store mutation, so a
            # rejected compress leaves the uncompressed index fully intact
            pq_centroids = quant_kwargs.get("pq_centroids") or st.pq_centroids
            pq_segments = (quant_kwargs.get("pq_segments")
                           or st.pq_segments
                           or default_pq_segments(st.dim, pq_centroids))
            if 0 < len(vecs) < pq_centroids:
                raise RuntimeError(
                    f"need >= {pq_centroids} live vectors to train PQ, "
                    f"have {len(vecs)}")
            codebook = None
            if len(vecs) and st.trained:
                assign = kmeans_assign(vecs, st._centroids_np)
                codebook = pq_fit(vecs - st._centroids_np[assign],
                                  m=pq_segments, k=pq_centroids, iters=8)
            st.quantization = "pq"
            st.pq_segments = pq_segments
            st.pq_centroids = pq_centroids
            if quant_kwargs.get("rescore_limit"):
                st.rescore_limit = quant_kwargs["rescore_limit"]
            st.codebook = codebook
            st._host_rows = np.zeros(
                (max(_next_pow2(max(st.capacity, 1)), 1024), st.dim),
                dtype=np.float32)
            if len(vecs):
                norm = st.normalize_on_add
                st.normalize_on_add = False  # rows already normalized
                st._remember_rows(slots, vecs)
                st.normalize_on_add = norm
            if st.trained:
                # rebuild absorbs delta-resident rows too — reset the
                # delta or its slots would be live in BOTH legs (duplicate
                # results now, double-scatter at the next flush). The
                # empty case still rebuilds so _fill reflects reality.
                st._rebuild_lists(vecs, slots)
                st._reset_delta()
            st._hbm_sync()

    @property
    def trained(self) -> bool:
        return self.store.trained

    @property
    def compressed(self) -> bool:
        return bool(self.store.quantization)

    @classmethod
    def restore(cls, snap: dict, **kwargs) -> "IVFIndex":
        idx = cls.__new__(cls)
        idx.dim = snap["dim"]
        idx.metric = snap["metric"]
        idx.store = IVFStore.restore(snap, **kwargs)
        idx._lock = threading.RLock()
        slot_to_id = snap["slot_to_id"]
        idx._slot_to_id = np.full(idx.store.capacity, -1, dtype=np.int64)
        idx._slot_to_id[: len(slot_to_id)] = slot_to_id
        idx._id_to_slot = {
            int(doc): int(slot)
            for slot, doc in enumerate(slot_to_id)
            if doc >= 0 and slot < len(snap["valid"]) and snap["valid"][slot]
        }
        return idx
