"""Binary quantization (BQ) on TPU.

Reference: adapters/repos/db/vector/compressionhelpers/binary_quantization.go
(:22 — sign bit per dimension packed into uint64 words, hamming distance via
XOR + popcount, with full-precision rescore in the flat index,
vector/flat/index.go:347).

TPU re-design: bits pack into uint32 words (int64 lanes are wasteful on
TPU); hamming runs as `population_count(xor(q, x))` on the VPU over [N, w]
word arrays — one vectorized pass instead of per-pair scalar loops. 32x
HBM compression; candidates are rescored against full-precision vectors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32


def bq_words(dim: int) -> int:
    return -(-dim // WORD_BITS)


@jax.jit
def bq_encode(vectors: jnp.ndarray) -> jnp.ndarray:
    """Pack sign bits: [N, d] float -> [N, ceil(d/32)] uint32.

    Bit j of word w is set iff vectors[:, w*32+j] >= 0 (reference uses the
    sign bit the same way, binary_quantization.go:30).
    """
    n, d = vectors.shape
    w = bq_words(d)
    pad = w * WORD_BITS - d
    bits = (vectors >= 0).astype(jnp.uint32)
    if pad:
        bits = jnp.concatenate([bits, jnp.zeros((n, pad), dtype=jnp.uint32)], axis=1)
    bits = bits.reshape(n, w, WORD_BITS)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)[None, None, :]
    return jnp.sum(bits << shifts, axis=-1, dtype=jnp.uint32)


def _auto_reduce_l(n: int) -> int:
    """Strided-reduction factor: keep >= ~16k candidate slots so the
    birthday-bound top-k loss stays negligible, cap at the kernel's 64."""
    l = max(1, min(n // 16384, 64))
    return 1 << (l.bit_length() - 1)


@functools.partial(jax.jit, static_argnames=("k", "chunk_size", "use_pallas",
                                             "reduce_l", "rescore_k",
                                             "rescore_metric"))
def bq_topk(
    q_words: jnp.ndarray,
    x_words: jnp.ndarray,
    k: int,
    chunk_size: int = 0,
    valid: jnp.ndarray | None = None,
    id_offset: jnp.ndarray | int = 0,
    use_pallas: bool = False,
    reduce_l: int | None = None,
    allow_bits: jnp.ndarray | None = None,
    rescore_q: jnp.ndarray | None = None,
    rescore_rows: jnp.ndarray | None = None,
    rescore_k: int = 0,
    rescore_metric: str = "cosine",
):
    """Hamming top-k over packed words: q [B, w] uint32, x [N, w] uint32.

    ``use_pallas`` takes the fused scan kernel (pallas_kernels.
    bq_scan_reduce: ±64-int8 MXU matmul + in-kernel strided block-argmin,
    then one approx_max_k over the N/L survivors). The fallback is a plain
    XLA XOR+popcount pass (small corpora / CPU tests). ``chunk_size`` is
    accepted for API compatibility; the fused kernel supertiles
    internally.

    EXACTNESS: the two paths do NOT return identical result sets. The
    fallback (``use_pallas=False``) is fully exact. The pallas path is
    approximate twice over — the strided block-argmin keeps one winner
    per ``reduce_l`` rows (a true top-k member is dropped whenever two
    winners share a block; birthday-bound loss ~k^2/(2*N/reduce_l)) and
    the survivor selection uses ``approx_max_k`` (recall~0.95 per spec).
    ``reduce_l=1`` removes only the block-argmin loss: the survivor
    selection still runs approx_max_k, so the pallas path never matches
    the fallback bit-for-bit. Production callers oversample + rescore as
    QuantizedVectorStore does, which absorbs the loss (measured recall
    deltas in PARITY.md).

    ``allow_bits`` [B, ceil(N_512/32)] uint32 adds a per-query allow
    bitmask (pallas_kernels.pack_allow_bitmask layout): the pallas path
    unpacks it subtile-locally in VMEM, the XLA fallback unpacks once and
    folds a per-chunk where.

    ``rescore_rows`` [N, >= d] float32 (a single-device store's resident
    full-precision tier; ``id_offset`` 0) makes this program END with
    the exact rescore (ops/candidates.py ``rescore_tail``): the k
    candidates' rows are gathered, scored against the float32 queries
    ``rescore_q`` [B, d] under ``rescore_metric`` and cut to
    ``rescore_k``: ``(exact dists [B, rescore_k], ids)`` come back.
    """
    from weaviate_tpu.ops.candidates import rescore_tail
    from weaviate_tpu.ops.distances import MASKED_DISTANCE
    from weaviate_tpu.ops.topk import topk_smallest

    tail = functools.partial(
        rescore_tail, q=rescore_q, rows=rescore_rows, k=rescore_k,
        metric=rescore_metric,
        valid=valid, allow_bits=allow_bits)
    n, w = x_words.shape
    b = q_words.shape[0]

    if use_pallas:
        from weaviate_tpu.ops.pallas_kernels import bq_scan_reduce
        from weaviate_tpu.ops.topk import select_survivors

        rl = reduce_l if reduce_l is not None else _auto_reduce_l(n)
        vals, ids = bq_scan_reduce(q_words, x_words, valid=valid,
                                   reduce_l=rl, allow_bits=allow_bits)
        return tail(*select_survivors(vals, ids, k, id_offset))

    allow_rows = None
    if allow_bits is not None:
        from weaviate_tpu.ops.pallas_kernels import unpack_allow_bitmask

        allow_rows = unpack_allow_bitmask(allow_bits, n)

    # XLA fallback: chunked XOR+popcount pass; pad odd sizes with dead rows
    # so peak memory stays O(B * chunk)
    chunk_size = min(chunk_size or 8192, n)
    if n % chunk_size:
        pad = chunk_size - n % chunk_size
        x_words = jnp.pad(x_words, ((0, pad), (0, 0)))
        valid = ((jnp.arange(n + pad) < n) if valid is None
                 else jnp.pad(valid.astype(bool), (0, pad)))
        if allow_rows is not None:
            allow_rows = jnp.pad(allow_rows, ((0, 0), (0, pad)))
        n += pad
    num_chunks = n // chunk_size
    x_chunks = x_words.reshape(num_chunks, chunk_size, w)
    valid_chunks = None if valid is None else valid.reshape(num_chunks, chunk_size)
    allow_chunks = (
        None if allow_rows is None
        else jnp.moveaxis(
            allow_rows.reshape(b, num_chunks, chunk_size), 1, 0))

    init_d = jnp.full((b, k), MASKED_DISTANCE, dtype=jnp.float32)
    init_i = jnp.full((b, k), -1, dtype=jnp.int32)

    def body(carry, inp):
        best_d, best_i = carry
        chunk_idx, xc, vc, ac = inp
        x_or = jax.lax.bitwise_xor(q_words[:, None, :], xc[None, :, :])
        d = jnp.sum(
            jax.lax.population_count(x_or), axis=-1, dtype=jnp.int32
        ).astype(jnp.float32)
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
        new_d, new_i = topk_smallest(
            jnp.concatenate([best_d, d], axis=1),
            jnp.concatenate([best_i, ids], axis=1),
            k,
        )
        new_i = jnp.where(new_d >= MASKED_DISTANCE, -1, new_i)
        return (new_d, new_i), None

    chunk_ids = jnp.arange(num_chunks, dtype=jnp.int32)
    if num_chunks == 1:
        (fd, fi), _ = body(
            (init_d, init_i),
            (chunk_ids[0], x_chunks[0],
             None if valid_chunks is None else valid_chunks[0],
             None if allow_chunks is None else allow_chunks[0]),
        )
    else:
        (fd, fi), _ = jax.lax.scan(
            body, (init_d, init_i),
            (chunk_ids, x_chunks, valid_chunks, allow_chunks)
        )
    return tail(fd, fi)


@functools.partial(jax.jit, static_argnames=("k", "refine", "use_pallas",
                                             "rescore_k", "rescore_metric"))
def bq_topk_twostage(
    q_words: jnp.ndarray,
    x_words: jnp.ndarray,
    x_prefix_t: jnp.ndarray,
    k: int,
    refine: int = 8,
    valid: jnp.ndarray | None = None,
    id_offset: jnp.ndarray | int = 0,
    use_pallas: bool = True,
    allow_bits: jnp.ndarray | None = None,
    rescore_q: jnp.ndarray | None = None,
    rescore_rows: jnp.ndarray | None = None,
    rescore_k: int = 0,
    rescore_metric: str = "cosine",
):
    """Two-stage BQ scan for the capacity regime.

    Stage 1 scans a CONTIGUOUS transposed prefix array ``x_prefix_t``
    [Wp, N] (the first 32*Wp sign bits of every row, stored separately so
    the scan reads Wp/W of the bytes — column-slicing the full row-major
    code array would still fetch whole HBM lines) and keeps refine*k
    candidates per query. Stage 2 gathers the candidates' FULL rows from
    the row-major ``x_words`` [N, W] (contiguous row gathers) and scores
    exact hamming with one XOR+popcount over [B, R, W]. Exact top-k of
    stage 2 follows; the only approximation is stage-1 candidate recall
    (tunable via ``refine`` and the prefix width). ``rescore_*``: the
    exact rescore as the program's last step, as in ``bq_topk``.
    """
    from weaviate_tpu.ops.candidates import rescore_tail
    from weaviate_tpu.ops.distances import MASKED_DISTANCE
    from weaviate_tpu.ops.topk import topk_smallest

    n, w = x_words.shape
    wp = x_prefix_t.shape[0]
    b = q_words.shape[0]

    if use_pallas:
        from weaviate_tpu.ops.pallas_kernels import bq_scan_reduce

        # the per-query mask prunes in stage 1: disallowed rows never
        # become candidates, so stage 2 inherits the filter for free
        vals1, ids1 = bq_scan_reduce(
            q_words[:, :wp], x_prefix_t, valid=valid,
            reduce_l=_auto_reduce_l(n), transposed=True,
            allow_bits=allow_bits)
        r = min(refine * k, vals1.shape[1])
        negd, pos = jax.lax.approx_max_k(-vals1, r, recall_target=0.95)
        cand_d1 = -negd
        cand = jnp.take_along_axis(ids1, pos, axis=1)  # [B, R] rows
    else:
        # fallback top-k already returns the pruned candidate set, sorted
        cand_d1, ids1 = bq_topk(q_words[:, :wp], x_prefix_t.T,
                                k=min(refine * k, n), valid=valid,
                                use_pallas=False, allow_bits=allow_bits)
        cand = jnp.where(ids1 < 0, 0, ids1)
        r = cand.shape[1]
    # stage 2: full-width exact hamming on the gathered candidates
    xg = x_words[jnp.clip(cand, 0, n - 1)]         # [B, R, W]
    x_or = jax.lax.bitwise_xor(q_words[:, None, :], xg)
    ham = jnp.sum(jax.lax.population_count(x_or), axis=-1,
                  dtype=jnp.int32).astype(jnp.float32)
    ham = jnp.where(cand_d1 >= MASKED_DISTANCE * 0.5, MASKED_DISTANCE, ham)
    kk = min(k, r)
    fd, fi = topk_smallest(ham, cand, kk)
    if kk < k:
        fd = jnp.pad(fd, ((0, 0), (0, k - kk)),
                     constant_values=MASKED_DISTANCE)
        fi = jnp.pad(fi, ((0, 0), (0, k - kk)), constant_values=-1)
    fi = jnp.where(fd >= MASKED_DISTANCE * 0.5, -1, fi + id_offset)
    return rescore_tail(fd, fi, rescore_q, rescore_rows, rescore_k,
                        rescore_metric, valid=valid, allow_bits=allow_bits)


def bq_hamming_np(a_words: np.ndarray, b_words: np.ndarray) -> np.ndarray:
    """Host reference: hamming between packed rows [A, w] x [B, w] -> [A, B]."""
    x = np.bitwise_xor(a_words[:, None, :], b_words[None, :, :])
    return np.unpackbits(x.view(np.uint8), axis=-1).sum(-1)
