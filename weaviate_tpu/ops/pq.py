"""Product quantization (PQ) on TPU.

Reference: adapters/repos/db/vector/compressionhelpers/product_quantization.go
(ProductQuantizer: Fit :372, Encode :420, per-query DistanceLookUpTable
:33-151 with LUT ``Distance`` :440) trained by kmeans.go / tile_encoder.go.

TPU re-design: the reference's per-query lookup table + per-pair code gather
is a scalar-gather workload that would starve the MXU. Because PQ segments
are orthogonal, the asymmetric distance

    sum_m LUT[m, code[n, m]]     (reference product_quantization.go:440)

is *exactly* ``dist(q, x_hat_n)`` where ``x_hat_n`` is the vector
reconstructed from centroids. So compressed search becomes:

    per chunk: look codes up -> x_hat [chunk, d] -> one distance matmul

The look-up is per *chunk* (shared by the whole query batch) and exact: it
moves a centroid's float32 value, so the distance is the same float32 matmul
as the uncompressed path over 16-64x fewer HBM bytes (codes are m uint8s
instead of d floats). HOW the look-up is done decides everything: as an XLA
gather (``jnp.take`` a segment) the chip does one scalar load a value,
249 ms a dispatch at 262,144 x 96 codes; as a lane gather inside the vreg
(``pallas_kernels.pq8_lookup_block``: rows on lanes, a dimension's 256
levels in two vregs) it is 0.25 ms of a 2-6 ms dispatch (PERF.md, PR 29).
``pq_reconstruct`` takes the kernel on a TPU and ``jnp.take``, its twin and
its definition, elsewhere; every 8-bit geometry takes the same path.

k-means fit runs as batched Lloyd iterations over all segments at once
(einsum over [N, m, ds]), chunk-scanned so HBM never holds [N, m, k].
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class PQCodebook(NamedTuple):
    """centroids [m, k, ds] f32 — m segments, k centroids each, ds = d/m."""

    centroids: jnp.ndarray

    @property
    def m(self) -> int:
        return self.centroids.shape[0]

    @property
    def k(self) -> int:
        return self.centroids.shape[1]

    @property
    def ds(self) -> int:
        return self.centroids.shape[2]

    @property
    def dim(self) -> int:
        return self.m * self.ds


def default_pq_segments(dim: int, pq_centroids: int = 16) -> int:
    """Segment-count policy shared by every PQ surface: 4-bit codes target
    1 bit/dim (m = d/4), 8-bit codes 1 byte per 8 dims; m must divide d
    for the orthogonal-segment ADC."""
    target = max(1, dim // (4 if pq_centroids <= 16 else 8))
    while dim % target:
        target -= 1
    return target


def _seg_view(vectors: jnp.ndarray, m: int) -> jnp.ndarray:
    n, d = vectors.shape
    assert d % m == 0, f"dim {d} not divisible by {m} segments"
    return vectors.reshape(n, m, d // m)


@functools.partial(jax.jit, static_argnames=("m",))
def _assign(vectors, centroids, m: int):
    """Nearest centroid per segment: [N, m] int32."""
    vs = _seg_view(vectors.astype(jnp.float32), m)  # [N, m, ds]
    # ||v - c||^2 = ||v||^2 - 2 v.c + ||c||^2 ; argmin over k drops ||v||^2
    dots = jnp.einsum(
        "nms,mks->nmk", vs, centroids, preferred_element_type=jnp.float32
    )
    cn = jnp.sum(centroids * centroids, axis=-1)  # [m, k]
    return jnp.argmin(cn[None, :, :] - 2.0 * dots, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("m", "k"))
def _lloyd_step(vectors, centroids, m: int, k: int):
    """One Lloyd iteration over every segment at once."""
    vs = _seg_view(vectors.astype(jnp.float32), m)
    assign = _assign(vectors, centroids, m)  # [N, m]
    one_hot = jax.nn.one_hot(assign, k, dtype=jnp.float32)  # [N, m, k]
    sums = jnp.einsum(
        "nmk,nms->mks", one_hot, vs, preferred_element_type=jnp.float32
    )
    counts = jnp.sum(one_hot, axis=0)  # [m, k]
    fresh = sums / jnp.maximum(counts, 1.0)[:, :, None]
    # keep the old centroid for empty clusters
    return jnp.where((counts > 0)[:, :, None], fresh, centroids)


def pq_fit(
    vectors: np.ndarray,
    m: int,
    k: int = 256,
    iters: int = 8,
    sample: int = 65536,
    seed: int = 0,
) -> PQCodebook:
    """Train a PQ codebook (reference Fit, product_quantization.go:372).

    Trains on a random sample (the reference also caps its training set);
    all ``m`` segments train in parallel on device.
    """
    vectors = np.asarray(vectors, dtype=np.float32)
    n, d = vectors.shape
    if n < k:
        raise ValueError(f"need >= {k} vectors to train k={k} PQ, have {n}")
    rng = np.random.default_rng(seed)
    if n > sample:
        vectors = vectors[rng.choice(n, sample, replace=False)]
        n = sample
    # init: k distinct data points per segment
    init_idx = rng.choice(n, k, replace=False)
    centroids = jnp.asarray(
        _seg_view(jnp.asarray(vectors), m)[init_idx].transpose(1, 0, 2)
    )  # [m, k, ds]
    x = jnp.asarray(vectors)
    for _ in range(iters):
        centroids = _lloyd_step(x, centroids, m, k)
    # the codebook stays a device array (pq_encode reads it on device) —
    # blocking here only serialized training against the host for no
    # reader; any deferred device error surfaces at first encode
    return PQCodebook(centroids=centroids)


def pq_encode(codebook: PQCodebook, vectors: np.ndarray, batch: int = 65536) -> np.ndarray:
    """Encode vectors -> codes [N, m] uint8 (reference Encode :420)."""
    from weaviate_tpu.runtime import tracing  # lazy: ops must not pull runtime at import

    vectors = np.asarray(vectors, dtype=np.float32)
    out = np.empty((len(vectors), codebook.m), dtype=np.uint8)
    for s in range(0, len(vectors), batch):
        chunk = jnp.asarray(vectors[s : s + batch])
        (codes,) = tracing.d2h(_assign(chunk, codebook.centroids, codebook.m))
        out[s : s + batch] = codes.astype(np.uint8)
    return out


def _rows_from_codes(codes: jnp.ndarray, centroids: jnp.ndarray):
    """codes [N, m] uint8 -> x_hat [N, d] f32, x_hat[n, s*ds + j] =
    centroids[s, codes[n, s], j]. Traced inside the caller's program (no
    jit of its own), so the choice below is made once a program: on a TPU
    the lane-gather kernel, for every geometry; elsewhere the take it is
    held equal to (XLA:CPU gathers well, XLA:TPU one scalar at a time)."""
    from weaviate_tpu.ops import pallas_kernels as pk

    if pk.recommended():
        return pk.pq8_lookup_block(codes, centroids, interpret=False)
    idx = codes.astype(jnp.int32)  # [N, m]
    gathered = jax.vmap(
        lambda table, ix: jnp.take(table, ix, axis=0), in_axes=(0, 1), out_axes=1
    )(centroids, idx)  # [N, m, ds]
    return gathered.reshape(codes.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("m",))
def pq_reconstruct(codes: jnp.ndarray, centroids: jnp.ndarray, m: int):
    """codes [N, m] uint8 -> x_hat [N, d] f32: each segment's centroid,
    bit-equal to ``centroids[s, codes[:, s]]``.

    The decompression half of the scan (``pq_topk`` traces the same
    look-up into its own program) and IVF's residual encode.
    """
    assert codes.shape[1] == m == centroids.shape[0]
    return _rows_from_codes(codes, centroids)


@functools.partial(
    jax.jit, static_argnames=("k", "chunk_size", "metric", "m", "rescore_k")
)
def pq_topk(
    q: jnp.ndarray,
    codes: jnp.ndarray,
    centroids: jnp.ndarray,
    k: int,
    chunk_size: int,
    metric: str = "l2-squared",
    valid: jnp.ndarray | None = None,
    id_offset: jnp.ndarray | int = 0,
    m: int | None = None,
    allow_bits: jnp.ndarray | None = None,
    rescore_rows: jnp.ndarray | None = None,
    rescore_k: int = 0,
):
    """Compressed brute-force top-k: scan codes in chunks, look each chunk's
    codes up (``_rows_from_codes``: exact), score the rows.

    Matches LUT-ADC results exactly for l2-squared/dot/cosine (orthogonal
    segments). Returns (dists [B,k], ids [B,k]) like chunked_topk.
    ``allow_bits`` adds a per-query packed allow bitmask, unpacked once
    and folded per chunk like the shared ``valid``. ``rescore_rows``
    [N, >= d] float32 (a single-device store's resident full-precision
    tier; ``id_offset`` 0) makes the program END with the exact rescore
    of its k candidates against ``q``, cut to ``rescore_k``
    (ops/candidates.py ``rescore_tail``).
    """
    from weaviate_tpu.ops.candidates import rescore_tail
    from weaviate_tpu.ops.distances import MASKED_DISTANCE, pairwise_distance
    from weaviate_tpu.ops.topk import approx_topk_smallest, topk_smallest

    m = m or centroids.shape[0]
    n = codes.shape[0]
    assert n % chunk_size == 0, f"codes rows {n} not a multiple of {chunk_size}"
    num_chunks = n // chunk_size
    b = q.shape[0]

    code_chunks = codes.reshape(num_chunks, chunk_size, m)
    valid_chunks = None if valid is None else valid.reshape(num_chunks, chunk_size)
    allow_chunks = None
    if allow_bits is not None:
        from weaviate_tpu.ops.pallas_kernels import unpack_allow_bitmask

        allow_chunks = jnp.moveaxis(
            unpack_allow_bitmask(allow_bits, n).reshape(
                b, num_chunks, chunk_size), 1, 0)

    init_d = jnp.full((b, k), MASKED_DISTANCE, dtype=jnp.float32)
    init_i = jnp.full((b, k), -1, dtype=jnp.int32)

    def body(carry, inp):
        best_d, best_i = carry
        chunk_idx, cc, vc, ac = inp
        x_hat = _rows_from_codes(cc, centroids)
        d = pairwise_distance(q, x_hat, metric=metric)
        if vc is not None:
            d = jnp.where(vc[None, :], d, MASKED_DISTANCE)
        if ac is not None:
            d = jnp.where(ac, d, MASKED_DISTANCE)
        ids = (
            chunk_idx * chunk_size
            + id_offset
            + jax.lax.broadcasted_iota(jnp.int32, (1, chunk_size), 1)
        )
        ids = jnp.broadcast_to(ids, (b, chunk_size))
        # two-stage: approx-select within THIS chunk only (one 0.95-recall
        # invocation per candidate), then EXACT merge of the tiny carried
        # set — carried winners can never be dropped by the approx op
        ck_d, ck_i = approx_topk_smallest(d, ids, min(k, chunk_size))
        ck_d = ck_d.astype(jnp.float32)  # bf16 kernel output -> f32 merge
        new_d, new_i = topk_smallest(
            jnp.concatenate([best_d, ck_d], axis=1),
            jnp.concatenate([best_i, ck_i], axis=1),
            k,
        )
        return (new_d, new_i), None

    chunk_ids = jnp.arange(num_chunks, dtype=jnp.int32)
    if num_chunks == 1:
        (fd, fi), _ = body(
            (init_d, init_i),
            (chunk_ids[0], code_chunks[0],
             None if valid_chunks is None else valid_chunks[0],
             None if allow_chunks is None else allow_chunks[0]),
        )
    else:
        (fd, fi), _ = jax.lax.scan(
            body, (init_d, init_i),
            (chunk_ids, code_chunks, valid_chunks, allow_chunks)
        )
    return rescore_tail(fd, fi, q, rescore_rows, rescore_k, metric,
                        valid=valid, allow_bits=allow_bits)


# -- 4-bit PQ (k<=16): ADC as one MXU matmul per tile ------------------------
#
# The TPU-first operating point: 16 centroids let the per-query lookup
# table ride the MXU (ops/pallas_kernels.pq4_lut_block builds a one-hot in
# VMEM and contracts it against the LUT — mk = 4d FLOPs/row at m = d/4)
# while codes stay 8-32x smaller than bf16 rows in HBM. Exactly the
# reference's DistanceLookUpTable semantics (product_quantization.go:
# 33-151, Distance :440) with the scalar gather turned into a matmul.


def quantize_lut_int8(lut: jnp.ndarray):
    """Per-query int8 quantization of ADC tables, code-major flattened.

    lut [B, m, kc] f32 -> (lut8 [B, kc*m] int8 with lane order c*m + s —
    the order pltpu.repeat / jnp.tile copy-major one-hots produce —
    scale [B] f32). Rank-preserving within each query (one shared scale);
    inverse: adc = dots / scale. Shared by the pq4 scan kernel and the
    IVF probe so the clamp/flatten conventions cannot drift apart.
    """
    b, m, kc = lut.shape
    scale = 127.0 / jnp.maximum(
        jnp.max(jnp.abs(lut.reshape(b, -1)), axis=1), 1e-20)
    lut8 = jnp.clip(jnp.round(lut * scale[:, None, None]), -127, 127)
    lut8 = jnp.transpose(lut8, (0, 2, 1)).reshape(b, kc * m)
    return lut8.astype(jnp.int8), scale


@functools.partial(jax.jit, static_argnames=("metric", "m"))
def pq_lut(q: jnp.ndarray, centroids: jnp.ndarray, metric: str, m: int):
    """Per-query ADC lookup tables: [B, m, k] f32.

    l2-squared: LUT[b,s,c] = ||q_seg[b,s] - centroids[s,c]||^2  (exact ADC)
    dot:        LUT[b,s,c] = -q_seg . c
    cosine:     1 - q.x_hat with the +1 folded into segment 0 (constant
                shift per code value keeps the sum exact)
    """
    qs = _seg_view(q.astype(jnp.float32), m)  # [B, m, ds]
    dots = jnp.einsum("bms,mks->bmk", qs, centroids,
                      preferred_element_type=jnp.float32)
    if metric == "l2-squared":
        qn = jnp.sum(qs * qs, axis=-1)  # [B, m]
        cn = jnp.sum(centroids * centroids, axis=-1)  # [m, k]
        return qn[:, :, None] - 2.0 * dots + cn[None, :, :]
    if metric == "dot":
        return -dots
    # cosine / cosine-dot: operands normalized by the caller
    lut = -dots
    return lut.at[:, 0, :].add(1.0)


@functools.partial(jax.jit, static_argnames=("k", "refine", "metric", "m",
                                             "use_pallas",
                                             "chunk_budget_bytes",
                                             "rescore_k"))
def pq_topk_twostage(
    q: jnp.ndarray,
    q_prefix_words: jnp.ndarray,
    codes: jnp.ndarray,
    centroids: jnp.ndarray,
    prefix_t: jnp.ndarray,
    k: int,
    refine: int = 8,
    metric: str = "l2-squared",
    valid: jnp.ndarray | None = None,
    id_offset: jnp.ndarray | int = 0,
    m: int | None = None,
    use_pallas: bool = True,
    chunk_budget_bytes: int = 128 << 20,
    allow_bits: jnp.ndarray | None = None,
    rescore_rows: jnp.ndarray | None = None,
    rescore_k: int = 0,
):
    """Two-stage PQ scan: the BQ prefix idea extended to PQ.

    An exhaustive ADC scan pays 2*B*N*d MXU FLOPs no matter how small the
    codes: pruning is the only way under it.
    Stage 1 scans a 128/256-bit transposed BQ SIGN prefix (built from the
    raw vectors at insert, ops/bq semantics; int8-MXU hamming via
    bq_scan_reduce) and keeps refine*k candidates; stage 2 gathers those
    candidates' PQ codes, reconstructs them with a one-hot MXU matmul
    against the shared codebook (per-query LUT gathers and tiny-table
    takes are the measured TPU anti-patterns — 80x/7x slower), and
    scores the reconstructions directly. On TPU the codebook rides the
    matmul in bf16, so stage-2 distances carry ~2^-8 relative rounding —
    ordering noise absorbed by the oversampled candidate set and the
    caller's exact rescore (QuantizedVectorStore.search); the CPU path
    is f32. The full code array is only touched at R = refine*k rows
    per query.
    """
    from weaviate_tpu.ops import bq as bq_ops
    from weaviate_tpu.ops.candidates import rescore_tail
    from weaviate_tpu.ops.distances import MASKED_DISTANCE
    from weaviate_tpu.ops.topk import topk_smallest

    n = codes.shape[0]
    m = m or centroids.shape[0]

    if use_pallas:
        from weaviate_tpu.ops.pallas_kernels import bq_scan_reduce

        # per-query mask prunes in stage 1; stage 2 only sees allowed rows
        vals1, ids1 = bq_scan_reduce(
            q_prefix_words, prefix_t, valid=valid,
            reduce_l=bq_ops._auto_reduce_l(n), transposed=True,
            allow_bits=allow_bits)
        r = min(refine * k, vals1.shape[1])
        negd, pos = jax.lax.approx_max_k(-vals1, r, recall_target=0.95)
        cand_d1 = -negd
        cand = jnp.take_along_axis(ids1, pos, axis=1)  # [B, R] rows
    else:
        cand_d1, ids1 = bq_ops.bq_topk(
            q_prefix_words, prefix_t.T, k=min(refine * k, n), valid=valid,
            use_pallas=False, allow_bits=allow_bits)
        cand = jnp.where(ids1 < 0, 0, ids1)
        r = cand.shape[1]

    b = q.shape[0]
    cg = codes[jnp.clip(cand, 0, n - 1)]  # [B, R, m]
    kc = centroids.shape[1]
    # the CPU backend lacks the bf16 x bf16 -> f32 dot; TPU takes bf16
    dt = jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32
    cent_dt = centroids.astype(dt)
    qn = jnp.sum(q * q, -1)[:, None]

    def score_chunk(cg_c):  # [B, Rc, m] -> [B, Rc]
        rc_ = cg_c.shape[1]
        oh = jax.nn.one_hot(cg_c.reshape(b * rc_, m).astype(jnp.int32),
                            kc, dtype=dt)
        x_hat = jnp.einsum(
            "rmk,mks->rms", oh, cent_dt,
            preferred_element_type=jnp.float32).reshape(b, rc_, -1)
        if metric == "l2-squared":
            return (qn - 2.0 * jnp.einsum(
                "bd,brd->br", q, x_hat,
                preferred_element_type=jnp.float32)
                + jnp.sum(x_hat * x_hat, -1))
        if metric == "dot":
            return -jnp.einsum("bd,brd->br", q, x_hat,
                               preferred_element_type=jnp.float32)
        # cosine / cosine-dot: operands normalized by the caller
        return 1.0 - jnp.einsum("bd,brd->br", q, x_hat,
                                preferred_element_type=jnp.float32)

    # bound the one-hot transient ([B*Rc, m, kc]) — at 8-bit PQ (kc=256)
    # and large B the unchunked tensor reaches gigabytes
    rc = max(1, min(r, chunk_budget_bytes // max(1, b * m * kc * 2)))
    if rc >= r:
        d2 = score_chunk(cg)
    else:
        n_chunks = (r + rc - 1) // rc
        pad = n_chunks * rc - r
        cg_p = jnp.pad(cg, ((0, 0), (0, pad), (0, 0)))
        parts = jnp.transpose(
            cg_p.reshape(b, n_chunks, rc, m), (1, 0, 2, 3))
        d2 = jax.lax.map(score_chunk, parts)  # [n_chunks, B, rc]
        d2 = jnp.transpose(d2, (1, 0, 2)).reshape(b, -1)[:, :r]
    d2 = jnp.where(cand_d1 >= MASKED_DISTANCE * 0.5, MASKED_DISTANCE, d2)
    kk = min(k, r)
    fd, fi = topk_smallest(d2, cand, kk)
    if kk < k:
        fd = jnp.pad(fd, ((0, 0), (0, k - kk)),
                     constant_values=MASKED_DISTANCE)
        fi = jnp.pad(fi, ((0, 0), (0, k - kk)), constant_values=-1)
    fi = jnp.where(fd >= MASKED_DISTANCE * 0.5, -1, fi + id_offset)
    return rescore_tail(fd, fi, q, rescore_rows, rescore_k, metric,
                        valid=valid, allow_bits=allow_bits)


@functools.partial(jax.jit, static_argnames=("k", "chunk_size", "metric", "m",
                                             "reduce_l", "rescore_k"))
def pq4_topk(
    q: jnp.ndarray,
    codes: jnp.ndarray,
    centroids: jnp.ndarray,
    k: int,
    chunk_size: int = 0,
    metric: str = "l2-squared",
    valid: jnp.ndarray | None = None,
    id_offset: jnp.ndarray | int = 0,
    m: int | None = None,
    reduce_l: int | None = None,
    allow_bits: jnp.ndarray | None = None,
    rescore_rows: jnp.ndarray | None = None,
    rescore_k: int = 0,
):
    """Compressed brute-force top-k over 4-bit codes via the fused ADC scan
    kernel (pallas_kernels.pq4_scan_reduce: per-query int8 LUT, one-hot
    int8 matmul, in-kernel strided block-argmin), then one approx_max_k
    over the ~N/L survivors and an exact final top-k. Same
    contract as pq_topk (``rescore_rows`` / ``rescore_k`` too);
    ``chunk_size`` is accepted for API compatibility."""
    from weaviate_tpu.ops.bq import _auto_reduce_l
    from weaviate_tpu.ops.candidates import rescore_tail
    from weaviate_tpu.ops.pallas_kernels import pq4_scan_reduce

    m = m or centroids.shape[0]
    n = codes.shape[0]
    lut = pq_lut(q, centroids, metric, m)  # [B, m, k]
    rl = reduce_l if reduce_l is not None else _auto_reduce_l(n)
    vals, ids = pq4_scan_reduce(lut, codes, valid=valid, reduce_l=rl,
                                allow_bits=allow_bits)
    from weaviate_tpu.ops.topk import select_survivors

    return rescore_tail(
        *select_survivors(vals, ids, k, id_offset), q,
        rescore_rows, rescore_k, metric, valid=valid, allow_bits=allow_bits)
