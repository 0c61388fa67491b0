"""Scalar quantization (SQ) on TPU: one byte a dimension, an int8 MXU scan.

Reference: upstream compressionhelpers/scalar_quantization.go (v1.26+,
``vectorIndexConfig.sq``), written from its documentation; the fork this
tree was modelled on predates it. Two float32 scalars describe the whole
space: ``a``, the least component of the training rows, and ``b``, the
greatest less ``a``. A component becomes the byte

    c = clip(floor((x - a) * (255 / b)), 0, 255)

(rows that arrive later and fall outside ``[a, a + b]`` clip), and reads
back as ``a + (b / 255) c``. The query is encoded the same way, so with
``s = b / 255`` every distance is an integer sum and two scalars:

    l2-squared  = s^2 * sum (cq - cx)^2
    dot         = D a^2 + a s (sum cq + sum cx) + s^2 * sum cq cx
    cosine      = 1 - dot, on rows normalised before they are encoded

TPU design. The v5e's MXU multiplies SIGNED bytes into int32 (393 TOP/s,
twice its bf16 rate, at half the bytes a row), so the program holds
``c - 128`` as int8 everywhere. A difference of two codes does not see
the shift; a product does, and is put right with the sums of both sides.
One chunk of codes is scored against all encoded queries by ONE
``dot_general`` with ``preferred_element_type=int32``; what a row adds on
its own (``sq_row_terms``: sum (c-128)^2 for l2, sum c for dot) is an int32
written once beside its code, 4 bytes a row, never recomputed by a scan.
The sums are exact: at 960 dimensions the largest is 62.4M, past what
float32 (2^24) or bfloat16 could accumulate without rounding, inside
int32 up to ``SQ_MAX_DIM``. Only the LAST step, integer to float32 times
the scalars, rounds, once, so that ``ops/topk.py``'s selection (floats)
can rank it and a store without a rescore tier returns real distances.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# every intermediate of _code_scores stays inside int32 up to here
# (81,664 * D < 2^31); no published embedding is a tenth as wide
SQ_MAX_DIM = 16_384
# the distances a scalar-quantized store can be asked for (cosine-dot is
# scanned as cosine, as every quantized store does)
SQ_METRICS = ("l2-squared", "dot", "cosine", "cosine-dot")


class SQQuantizer(NamedTuple):
    """The fitted range: ``a`` the least training component, ``b`` the
    greatest less ``a`` (float32 both, and all the state there is), and
    ``params``, the three float32 the jitted programs take, on the device:
    ``[a, 255 / b, b / 255]``."""

    a: np.float32
    b: np.float32
    params: jnp.ndarray


def sq_quantizer(a, b) -> SQQuantizer:
    """The quantizer of a known range (``sq_fit``; a snapshot). Codes a
    unit (x -> (x - a) * 255 / b) and units a code (c -> a + c * b / 255)
    are divided once, here, in float64: a multiply rounds the same on every
    backend, a divide inside the program need not."""
    a, b = np.float32(a), np.float32(b)
    return SQQuantizer(a, b, jnp.asarray(
        [a, np.float32(255.0 / float(b)), np.float32(float(b) / 255.0)],
        jnp.float32))


def sq_fit(vectors: np.ndarray) -> SQQuantizer:
    """The range of ``vectors`` [N, d]: two passes over host memory, no
    device work. A constant training set gets ``b`` = 1 so that nothing
    divides by zero (every code is then 0 or clipped)."""
    vectors = np.asarray(vectors, dtype=np.float32)
    if vectors.size == 0:
        raise ValueError("need >= 1 vector to fit a scalar quantizer")
    a = np.float32(vectors.min())
    b = np.float32(vectors.max()) - a
    return sq_quantizer(a, b if b > 0 else 1.0)


def _encode_rows(x: jnp.ndarray, params: jnp.ndarray) -> jnp.ndarray:
    """[N, d] f32 -> [N, d] int8, the code less 128. Traced into its
    caller; a subtract, a multiply, a floor and a clip, each exactly
    rounded, so numpy gives the same bytes (tests/sq_reference.py)."""
    c = jnp.clip(jnp.floor((x.astype(jnp.float32) - params[0]) * params[1]),
                 0.0, 255.0)
    return (c - 128.0).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("metric",))
def sq_row_terms(codes: jnp.ndarray, metric: str) -> jnp.ndarray:
    """[N, d] int8 -> [N] int32, what a row (or a query) adds to every
    score it takes part in: sum (c - 128)^2 for l2-squared (the shift
    cancels in a difference), sum c for dot and cosine."""
    s = codes.astype(jnp.int32)
    if metric == "l2-squared":
        return jnp.sum(s * s, axis=-1)
    return jnp.sum(s, axis=-1) + 128 * codes.shape[-1]


def _code_scores(qs, q_terms, cs, row_terms, metric: str) -> jnp.ndarray:
    """Encoded queries [B, d] int8 against encoded rows [N, d] int8 ->
    [B, N] int32, exact: sum (cq - cx)^2 for l2-squared, sum cq cx for
    dot and cosine, in the UNSHIFTED codes' terms whatever the MXU saw."""
    p = jax.lax.dot_general(qs, cs, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.int32)
    both = q_terms[:, None] + row_terms[None, :]
    if metric == "l2-squared":
        return both - 2 * p
    return p + 128 * both - 128 * 128 * qs.shape[-1]


def _distances(scores, q_terms, row_terms, params, metric: str, dim: int):
    """The one rounding: int32 scores -> float32 distances."""
    a, step = params[0], params[2]
    if metric == "l2-squared":
        return scores.astype(jnp.float32) * (step * step)
    sums = (q_terms[:, None] + row_terms[None, :]).astype(jnp.float32)
    dot = (dim * a * a + (a * step) * sums
           + (step * step) * scores.astype(jnp.float32))
    return 1.0 - dot if metric == "cosine" else -dot


@functools.partial(jax.jit, static_argnames=("metric",))
def sq_encode(x: jnp.ndarray, params: jnp.ndarray, metric: str = "l2-squared"):
    """Rows [N, d] f32 -> (codes [N, d] int8, the code less 128; row terms
    [N] int32), on the device and left there: the store traces this into
    the program that scatters both (``engine/quantized.py``), so a row
    goes up once as float32 and its code never comes down. ``params`` is
    ``SQQuantizer.params``."""
    codes = _encode_rows(x, params)
    return codes, sq_row_terms(codes, metric)


@functools.partial(jax.jit, static_argnames=("k", "chunk_size", "metric",
                                             "rescore_k"))
def sq_topk(
    q: jnp.ndarray,
    codes: jnp.ndarray,
    row_terms: jnp.ndarray,
    params: jnp.ndarray,
    k: int,
    chunk_size: int,
    metric: str = "l2-squared",
    valid: jnp.ndarray | None = None,
    id_offset: jnp.ndarray | int = 0,
    allow_bits: jnp.ndarray | None = None,
    rescore_rows: jnp.ndarray | None = None,
    rescore_k: int = 0,
):
    """Compressed brute-force top-k over one-byte codes: the float32
    queries [B, d] are encoded in the program, then the codes [N, d] int8
    are scanned in chunks, each an int8 x int8 -> int32 matmul plus the
    rows' resident terms. Returns (dists [B, k] f32, ids [B, k]) like
    ``pq_topk``; ``valid`` and ``allow_bits`` mask as they do there, and
    so do the selection a chunk, the exact merge, and the exact rescore
    that ends the program where ``rescore_rows`` are given."""
    from weaviate_tpu.ops.candidates import rescore_tail
    from weaviate_tpu.ops.distances import MASKED_DISTANCE
    from weaviate_tpu.ops.topk import approx_topk_smallest, topk_smallest

    n, dim = codes.shape
    assert n % chunk_size == 0, f"codes rows {n} not a multiple of {chunk_size}"
    num_chunks = n // chunk_size
    b = q.shape[0]
    qs = _encode_rows(q, params)
    q_terms = sq_row_terms(qs, metric)

    allow_rows = None
    if allow_bits is not None:
        from weaviate_tpu.ops.pallas_kernels import unpack_allow_bitmask

        allow_rows = unpack_allow_bitmask(allow_bits, n)

    init_d = jnp.full((b, k), MASKED_DISTANCE, dtype=jnp.float32)
    init_i = jnp.full((b, k), -1, dtype=jnp.int32)

    def body(carry, chunk_idx):
        # a chunk is CUT from the arrays as they lie (no [chunks, chunk,
        # d] view of the codes: the chip keeps an int8 [N, d] with N on
        # the lanes, and such a view costs a copy of all of it a dispatch)
        best_d, best_i = carry
        start = chunk_idx * chunk_size
        cut = functools.partial(jax.lax.dynamic_slice_in_dim,
                                start_index=start, slice_size=chunk_size)
        cc, tc = cut(codes, axis=0), cut(row_terms, axis=0)
        d = _distances(_code_scores(qs, q_terms, cc, tc, metric),
                       q_terms, tc, params, metric, dim)
        if valid is not None:
            d = jnp.where(cut(valid, axis=0)[None, :], d, MASKED_DISTANCE)
        if allow_rows is not None:
            d = jnp.where(cut(allow_rows, axis=1), d, MASKED_DISTANCE)
        ids = (
            start
            + id_offset
            + jax.lax.broadcasted_iota(jnp.int32, (1, chunk_size), 1)
        )
        ids = jnp.broadcast_to(ids, (b, chunk_size))
        # as pq_topk: approx-select within THIS chunk only, then an EXACT
        # merge of the small carried set
        ck_d, ck_i = approx_topk_smallest(d, ids, min(k, chunk_size))
        new_d, new_i = topk_smallest(
            jnp.concatenate([best_d, ck_d.astype(jnp.float32)], axis=1),
            jnp.concatenate([best_i, ck_i], axis=1),
            k,
        )
        new_i = jnp.where(new_d >= MASKED_DISTANCE, -1, new_i)
        return (new_d, new_i), None

    (fd, fi), _ = jax.lax.scan(body, (init_d, init_i),
                               jnp.arange(num_chunks, dtype=jnp.int32))
    return rescore_tail(fd, fi, q, rescore_rows, rescore_k, metric,
                        valid=valid, allow_bits=allow_bits)
