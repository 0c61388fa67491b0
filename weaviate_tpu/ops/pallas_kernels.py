"""Pallas TPU kernels for the distance hot path.

The reference's only native code is per-pair SIMD assembly for vector
distances (adapters/repos/db/vector/hnsw/distancer/asm/*.s — AVX2/AVX512/
NEON/SVE dot, l2, hamming; runtime dispatch in distancer/l2_amd64.go:19-25).
These kernels are the TPU equivalent, transposed to the hardware's shape:
instead of one query×one vector at a time, a whole query block is scored
against a corpus tile in one fused kernel so the FLOPs land on the 128x128
MXU and the mask/bias epilogue rides along in VMEM without an extra HBM
round-trip.

Kernels:

- ``distance_block``    fused [B,d]x[TILE,d] -> [B,TILE] distance + validity
                        mask epilogue (l2-squared / dot / cosine). One MXU
                        matmul per tile; the (1-valid)*MASKED epilogue fuses
                        into the same VMEM residency.
- ``bq_hamming_block``  packed binary-quantized hamming: uint32 XOR +
                        popcount + reduce (reference: BQ hamming over uint64
                        words, compressionhelpers/binary_quantization.go:22).
                        VPU-bound — kept for conformance; the fast path is:
- ``bq_mxu_block``      hamming VIA THE MXU: packed sign bits unpack to 0/1
                        planes in VMEM (shift+mask, zero extra HBM traffic)
                        and hamming(q,x) = |q| + |x| - 2*q.x becomes one
                        bf16 matmul. The MXU runs ~2 orders faster than the
                        VPU popcount loop, so "bit tricks" lose to matmuls
                        on TPU; HBM reads stay d/8 bytes per row (16x less
                        than bf16).
- ``pq4_lut_block``     4-bit-PQ ADC scan: per-query LUTs [B, k*m] hit the
                        codes through an in-VMEM one-hot (pltpu.repeat +
                        lane-iota compare) and ONE bf16 matmul — exact
                        LUT-ADC semantics (reference DistanceLookUpTable,
                        product_quantization.go:440) at mk=4d FLOPs/row with
                        m=d/4 codes reading 8-32x fewer HBM bytes per row.
- ``pq8_lookup_block``  8-bit-PQ dequantisation: codes -> reconstructed rows
                        through a lane gather inside the vreg (corpus rows
                        on lanes, a dimension's 256 levels in two vregs),
                        where XLA's gather is one scalar load a value.
                        Exact; what ``ops/pq.py::pq_topk`` scans with.

On CPU (tests, dev) the kernels run through the Pallas interpreter —
bit-identical semantics, no Mosaic compile. ``recommended()`` says whether
the compiled path is worth it on the current backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from weaviate_tpu.ops.distances import MASKED_DISTANCE

# Metrics with an MXU-shaped Pallas kernel. hamming-on-floats and manhattan
# stay on the XLA path (elementwise 3D intermediates — VPU-bound either way,
# nothing for a hand kernel to win).
PALLAS_METRICS = ("l2-squared", "dot", "cosine", "cosine-dot")

_LANE = 128  # TPU lane width: last dim of every tile.
_SUBLANE = 8  # f32 sublane count: second-to-last dim multiple.


def recommended() -> bool:
    """True when compiled Pallas kernels should be used (TPU backend).
    A backend that fails to initialise raises — it is an error, not a
    reason to serve from the interpreter."""
    return jax.default_backend() == "tpu"


def _pad_to(n: int, m: int) -> int:
    return (n + m - 1) // m * m


# -- per-query allow bitmasks -------------------------------------------------
#
# Filtered BATCHED search: each query row carries its own packed allow
# bitmask so B filtered requests share one device program (the reference
# consumes one AllowList per query inside the scan, helpers/allow_list.go).
# A [B, N] f32 mask would multiply the kernel's per-tile input traffic by
# B; packed words cost B*N/8 bytes total and unpack tile-locally in VMEM.
#
# Layout is BLOCK-STRIDED to match the kernels' in-VMEM unpack (the same
# pltpu.repeat + lane-iota-shift idiom the BQ kernels use for bit planes):
# within each MASK_BLOCK-column block, the block's W = MASK_BLOCK/32 words
# hold   bit j of word w  =  allow[block_base + j*W + w],
# so ``pltpu.repeat(words, 32, axis=1)`` (lane l -> word l % W) followed by
# a ``lane_iota // W`` shift lands allow[block_base + l] on lane l exactly
# — no in-kernel gather, no data permutation. Every masked kernel consumes
# whole MASK_BLOCK-column blocks (tiles/subtiles are forced 512-aligned
# when a mask is present), so one fixed layout serves them all.

MASK_BLOCK = 512
_MASK_WORDS = MASK_BLOCK // 32  # 16 words per block


def mask_pad_cols(n: int) -> int:
    """Packed-mask column count covering ``n`` corpus rows."""
    return _pad_to(max(n, 1), MASK_BLOCK)


def pack_allow_bitmask(allow, n_cols: int | None = None):
    """Host-side packer: allow [B, C] (or [C]) bool -> uint32
    [B, n_cols // 32] in block-strided order. Columns past C pack as 0
    (disallowed — they are dead padding either way)."""
    import numpy as np

    allow = np.asarray(allow, dtype=bool)
    if allow.ndim == 1:
        allow = allow[None, :]
    b, c = allow.shape
    if n_cols is None:
        n_cols = mask_pad_cols(c)
    buf = np.zeros((b, n_cols), dtype=bool)
    keep = min(c, n_cols)
    buf[:, :keep] = allow[:, :keep]
    a = buf.reshape(b, n_cols // MASK_BLOCK, 32, _MASK_WORDS)
    shifts = np.arange(32, dtype=np.uint32)[None, None, :, None]
    words = (a.astype(np.uint32) << shifts).sum(axis=2, dtype=np.uint32)
    return words.reshape(b, n_cols // 32)


def pack_allow_bitmask_jnp(allow: jnp.ndarray) -> jnp.ndarray:
    """Traceable twin of ``pack_allow_bitmask`` for on-device packing
    (the sharded path packs each shard's column slice locally)."""
    b, c = allow.shape
    n_cols = mask_pad_cols(c)
    allow = allow.astype(bool)
    if n_cols != c:
        allow = jnp.pad(allow, ((0, 0), (0, n_cols - c)))
    a = allow.reshape(b, n_cols // MASK_BLOCK, 32, _MASK_WORDS)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
    words = jnp.sum(a.astype(jnp.uint32) << shifts, axis=2)
    return words.astype(jnp.uint32).reshape(b, n_cols // 32)


def unpack_allow_bitmask(bits: jnp.ndarray, n_cols: int | None = None):
    """Inverse of the packer: [B, W] uint32 -> [B, n_cols] bool. Traceable
    (the XLA fallback scans unpack once and apply a plain where)."""
    b, w_total = bits.shape
    total = w_total * 32
    bits = jnp.asarray(bits)
    a = bits.reshape(b, total // MASK_BLOCK, 1, _MASK_WORDS)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :, None]
    cols = ((a >> shifts) & jnp.uint32(1)).reshape(b, total)
    out = cols.astype(bool)
    if n_cols is not None and n_cols != total:
        out = (out[:, :n_cols] if n_cols < total else
               jnp.pad(out, ((0, 0), (0, n_cols - total))))
    return out


def allow_bits_for_ids(bits: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Per-CANDIDATE allow lookup in the block-strided packed layout.

    ``bits`` [Ba, W] uint32 (``Ba == 1`` broadcasts over the batch),
    ``ids`` [B, C] int32 global column ids -> [B, C] bool. This is the
    candidate plane's fold (ops/candidates.py): instead of unpacking a
    dense [B, capacity] mask, each candidate gathers its ONE word —
    column c lives at word ``(c // MASK_BLOCK) * W_blk + (c % MASK_BLOCK)
    % W_blk``, bit ``(c % MASK_BLOCK) // W_blk`` (the packer's
    block-strided order above). Ids outside [0, 32·W) — including the -1
    empty-slot sentinel — read as disallowed, matching the packer's
    zeros-past-C convention.
    """
    b, c = ids.shape
    n_cols = bits.shape[1] * 32
    safe = jnp.clip(ids, 0, n_cols - 1)
    off = safe % MASK_BLOCK
    word = (safe // MASK_BLOCK) * _MASK_WORDS + (off % _MASK_WORDS)
    bit = (off // _MASK_WORDS).astype(jnp.uint32)
    wb = jnp.broadcast_to(jnp.asarray(bits, dtype=jnp.uint32),
                          (b, bits.shape[1]))
    w = jnp.take_along_axis(wb, word, axis=1)
    ok = ((w >> bit) & jnp.uint32(1)) != 0
    return ok & (ids >= 0) & (ids < n_cols)


def _fit_mask_words(allow_bits, b_pad: int, n_cols: int):
    """Pad/slice packed words [B, >= n_cols // 32] to whole blocks
    [b_pad, n_cols // MASK_BLOCK, 16] int32 (Mosaic wants signed lanes;
    bit extraction is sign-agnostic). Padding rows/columns are zeros =
    disallowed, matching the dead-row masking."""
    wn = n_cols // 32
    ab = jnp.asarray(allow_bits)
    if ab.shape[1] < wn:
        ab = jnp.pad(ab, ((0, 0), (0, wn - ab.shape[1])))
    elif ab.shape[1] > wn:
        ab = ab[:, :wn]
    if ab.shape[0] < b_pad:
        ab = jnp.pad(ab, ((0, b_pad - ab.shape[0]), (0, 0)))
    if ab.dtype == jnp.uint32:
        ab = jax.lax.bitcast_convert_type(ab, jnp.int32)
    return ab.astype(jnp.int32).reshape(
        b_pad, n_cols // MASK_BLOCK, _MASK_WORDS)


def _block_major_mask(allow_bits, b_pad: int, n_cols: int):
    """The scan kernels' mask operand: BLOCK-MAJOR [n_blocks, b_pad, 16].
    A grid step and the subtile loop then index whole packed blocks on
    the LEADING axis — the chip's compiler refuses both a 16-lane block
    of a wider array and a dynamic lane offset it cannot prove
    128-aligned, while the trailing (b_pad, 16) dims here are always the
    full array dims. One XLA transpose of B * N / 8 bytes per call."""
    return jnp.swapaxes(_fit_mask_words(allow_bits, b_pad, n_cols), 0, 1)


def _mask_block_spec(blocks: int, b: int):
    """BlockSpec of ``blocks`` consecutive packed blocks per grid step
    over a ``_block_major_mask`` operand."""
    return pl.BlockSpec((blocks, b, _MASK_WORDS), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)


def _mask_unpack_block(mw, interpret: bool):
    """One packed block's words [B, W] int32 -> [B, 32W] 0/1 int32 with
    lane l = allow[block_base + l] (see the layout note above)."""
    if interpret:
        rep = jnp.concatenate([mw] * 32, axis=1)
    else:
        rep = pltpu.repeat(mw, 32, axis=1)
    shift = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1) // mw.shape[1]
    return jax.lax.shift_right_logical(rep, shift) & 1


def _mask_unpack_blocks(mw, interpret: bool):
    """Unpack whole blocks [nb, B, W] int32 -> [B, nb * 32W] 0/1 int32:
    per-block repeat+shift, lane-concat across blocks."""
    parts = [_mask_unpack_block(mw[i], interpret)
             for i in range(mw.shape[0])]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _distance_kernel(metric: str):
    """Build the tile kernel body for one metric.

    refs: q [B,d] f32/bf16, x [TILE,d], valid [1,TILE] f32, xn [1,TILE] f32,
    out [B,TILE] f32. All VMEM-resident for the tile.
    """

    def kernel(q_ref, x_ref, valid_ref, xn_ref, out_ref):
        q = q_ref[:]
        x = x_ref[:]
        # One MXU contraction: [B,d] x [TILE,d]^T -> [B,TILE], f32 accumulate.
        # f32xf32 requests HIGHEST (multi-pass exact matmul) to match the XLA
        # path's recall-parity guarantee (distances._dot_matrix); bf16 storage
        # takes the single-pass MXU matmul.
        f32_exact = q.dtype == jnp.float32 and x.dtype == jnp.float32
        dots = jax.lax.dot_general(
            q,
            x,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST if f32_exact else jax.lax.Precision.DEFAULT,
        )
        if metric == "l2-squared":
            qn = jnp.sum(q.astype(jnp.float32) * q.astype(jnp.float32), axis=1, keepdims=True)
            d = jnp.maximum(qn - 2.0 * dots + xn_ref[:], 0.0)
        elif metric == "dot":
            d = -dots
        else:  # cosine / cosine-dot: operands pre-normalized by the wrapper
            d = 1.0 - dots
        # Masking epilogue fused into the same tile: dead slots can never win.
        out_ref[:] = d + (1.0 - valid_ref[:]) * MASKED_DISTANCE

    return kernel


@functools.partial(
    jax.jit, static_argnames=("metric", "tile_n", "interpret")
)
def _distance_tiled(q, x, valid_f, xn, metric, tile_n, interpret):
    b, d = q.shape
    n = x.shape[0]
    grid = (n // tile_n,)
    return pl.pallas_call(
        _distance_kernel(metric),
        grid=grid,
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n * d,
            bytes_accessed=q.size * q.dtype.itemsize + x.size * x.dtype.itemsize + b * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(q, x, valid_f, xn)


def distance_block(
    q: jnp.ndarray,
    x: jnp.ndarray,
    metric: str = "l2-squared",
    valid: jnp.ndarray | None = None,
    x_sq_norms: jnp.ndarray | None = None,
    tile_n: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused masked distances: q [B,d] vs x [N,d] -> [B,N] f32, lower=closer.

    Pads B to the f32 sublane multiple, d to the lane width, N to the tile —
    padded corpus rows are marked invalid so they surface as MASKED_DISTANCE.
    Zero-padding the feature axis is exact for dot/l2/cosine (zeros add
    nothing to the contraction).
    """
    if metric not in PALLAS_METRICS:
        raise ValueError(f"no pallas kernel for metric {metric!r}")
    if interpret is None:
        interpret = not recommended()

    b, d = q.shape
    n = x.shape[0]
    q = q.astype(jnp.float32) if q.dtype not in (jnp.float32, jnp.bfloat16) else q
    if metric in ("cosine", "cosine-dot"):
        from weaviate_tpu.ops.distances import normalize

        q = normalize(q.astype(jnp.float32))

    pb = _pad_to(max(b, 1), _SUBLANE)
    pd = _pad_to(max(d, 1), _LANE)
    tile_n = min(tile_n, _pad_to(max(n, 1), _LANE))
    pn = _pad_to(max(n, 1), tile_n)

    if (pb, pd) != (b, d):
        q = jnp.pad(q, ((0, pb - b), (0, pd - d)))
    if (pn, pd) != (n, d):
        x = jnp.pad(x, ((0, pn - n), (0, pd - d)))

    if valid is None:
        valid_f = (jnp.arange(pn) < n).astype(jnp.float32)
    else:
        valid_f = jnp.pad(valid.astype(jnp.float32), (0, pn - n))
    if x_sq_norms is None:
        x32 = x.astype(jnp.float32)
        xn = jnp.sum(x32 * x32, axis=1)
    else:
        xn = jnp.pad(x_sq_norms.astype(jnp.float32), (0, pn - n))

    out = _distance_tiled(
        q, x, valid_f[None, :], xn[None, :], metric, tile_n, interpret
    )
    return out[:b, :n]


def _bq_kernel(q_ref, x_ref, out_ref):
    """Packed-bits hamming tile: q [B,W] u32, x [TILE,W] u32 -> [B,TILE] f32."""
    q = q_ref[:]
    x = x_ref[:]
    xor = jnp.bitwise_xor(q[:, None, :], x[None, :, :])
    # Mosaic can't reduce unsigned ints — popcount fits in int32 regardless.
    pop = jax.lax.population_count(xor).astype(jnp.int32)
    out_ref[:] = jnp.sum(pop, axis=-1).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def _bq_tiled(q_bits, x_bits, tile_n, interpret):
    b, w = q_bits.shape
    n = x_bits.shape[0]
    return pl.pallas_call(
        _bq_kernel,
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((b, w), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, w), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        interpret=interpret,
    )(q_bits, x_bits)


def _bq_mxu_kernel(q_ref, x_ref, qpop_ref, xpop_ref, valid_ref, out_ref):
    """MXU hamming tile: q01 [B, 32W] bf16 (bit-plane order), x [TILE, W]
    int32 packed. Unpack x to 0/1 planes in VMEM, one matmul, fused
    hamming + mask epilogue."""
    x = x_ref[:]
    # bit-plane unpack: lane block j holds bit j of every word -> the
    # unpacked feature order is d' = j*W + w (queries pre-permuted to match)
    planes = [((x >> j) & 1) for j in range(32)]
    bits = jnp.concatenate(planes, axis=1).astype(jnp.bfloat16)  # [TILE, 32W]
    dots = jax.lax.dot_general(
        q_ref[:], bits,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, TILE]
    d = qpop_ref[:] + xpop_ref[:] - 2.0 * dots
    # candidates are exactly rescored downstream — bf16 output halves the
    # dominant HBM cost (the [B, chunk] distance intermediate)
    out_ref[:] = (d + (1.0 - valid_ref[:]) * MASKED_DISTANCE
                  ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("tile_n", "interpret"))
def _bq_mxu_tiled(q01, x_packed, qpop, xpop, valid_f, tile_n, interpret):
    b = q01.shape[0]
    n, w = x_packed.shape
    return pl.pallas_call(
        _bq_mxu_kernel,
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((b, 32 * w), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, w), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((b, 1), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.bfloat16),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n * 32 * w,
            bytes_accessed=q01.size * 2 + x_packed.size * 4 + b * n * 2,
            transcendentals=0,
        ),
        interpret=interpret,
    )(q01, x_packed, qpop, xpop, valid_f)


def bq_queries_to_planes(q_bits: jnp.ndarray, w: int) -> jnp.ndarray:
    """Unpack packed query words [B, W] uint32 -> bit-plane-ordered 0/1
    bf16 [B, 32W] matching ``_bq_mxu_kernel``'s in-VMEM unpack order
    (d' = j*W + w)."""
    planes = [((q_bits >> jnp.uint32(j)) & jnp.uint32(1)) for j in range(32)]
    return jnp.concatenate(planes, axis=1).astype(jnp.bfloat16)


def bq_mxu_block(
    q_bits: jnp.ndarray,
    x_bits: jnp.ndarray,
    x_pop: jnp.ndarray | None = None,
    valid: jnp.ndarray | None = None,
    tile_n: int = 512,
    interpret: bool | None = None,
    q_planes: jnp.ndarray | None = None,
    q_pop: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Hamming distances via the MXU: q_bits [B,W] uint32, x_bits [N,W]
    uint32 -> [B,N] f32 bit differences, invalid rows masked.

    The corpus stays packed in HBM (d/8 bytes per row); unpacking happens
    in VMEM inside the kernel. ``x_pop`` ([N] f32 popcounts) amortizes the
    |x| term — pass the store's cached copy when scanning repeatedly.
    ``q_planes``/``q_pop`` (from ``bq_queries_to_planes``, already padded
    to the sublane multiple) let a chunked scan hoist the loop-invariant
    query unpack out of the scan body.
    """
    if interpret is None:
        interpret = not recommended()
    b, w = q_bits.shape
    n = x_bits.shape[0]
    pb = _pad_to(max(b, 1), _SUBLANE)
    tile_n = min(tile_n, _pad_to(max(n, 1), _LANE))
    pn = _pad_to(max(n, 1), tile_n)
    if pb != b:
        q_bits = jnp.pad(q_bits, ((0, pb - b), (0, 0)))
    if pn != n:
        x_bits = jnp.pad(x_bits, ((0, pn - n), (0, 0)))
    if q_planes is None:
        q01 = bq_queries_to_planes(q_bits, w)
        qpop = jnp.sum(q01.astype(jnp.float32), axis=1, keepdims=True)
    else:
        q01, qpop = q_planes, q_pop
    if x_pop is None:
        xpop = jnp.sum(
            jax.lax.population_count(x_bits).astype(jnp.int32), axis=1
        ).astype(jnp.float32)
    else:
        xpop = jnp.pad(x_pop.astype(jnp.float32), (0, pn - n))
    # Mosaic has no uint32->bf16 cast; the kernel's bit planes convert
    # from int32 instead (bit extraction is sign-agnostic)
    if x_bits.dtype == jnp.uint32:
        x_bits = jax.lax.bitcast_convert_type(x_bits, jnp.int32)
    if valid is None:
        valid_f = (jnp.arange(pn) < n).astype(jnp.float32)
    else:
        valid_f = jnp.pad(valid.astype(jnp.float32), (0, pn - n))
    out = _bq_mxu_tiled(q01, x_bits, qpop, xpop[None, :], valid_f[None, :],
                        tile_n, interpret)
    return out[:b, :n]


def _pq4_kernel(lut_ref, c_ref, valid_ref, out_ref, *, k, m, interpret):
    """4-bit PQ ADC tile: lut [B, k*m] bf16 CODE-MAJOR (lane c*m+s holds
    LUT[s][c]), codes [TILE, m] uint8. pltpu.repeat tiles the code row k
    times (lane c*m+s = codes[s]), a lane-iota//m compare builds the
    one-hot, one bf16 matmul contracts against the LUT."""
    c = c_ref[:].astype(jnp.int32)  # [TILE, m]
    if interpret:  # tile-concat == pltpu.repeat semantics, interpreter-safe
        rep = jnp.concatenate([c] * k, axis=1)
    else:
        rep = pltpu.repeat(c, k, axis=1)  # [TILE, k*m]
    lane_code = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1) // m
    oh = (rep == lane_code).astype(jnp.bfloat16)
    d = jax.lax.dot_general(
        lut_ref[:], oh,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, TILE]
    out_ref[:] = (d + (1.0 - valid_ref[:]) * MASKED_DISTANCE
                  ).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("k", "m", "tile_n", "interpret"))
def _pq4_tiled(lut_cm, codes, valid_f, k, m, tile_n, interpret):
    b = lut_cm.shape[0]
    n = codes.shape[0]
    return pl.pallas_call(
        functools.partial(_pq4_kernel, k=k, m=m, interpret=interpret),
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((b, k * m), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, m), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.bfloat16),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n * k * m,
            bytes_accessed=lut_cm.size * 2 + codes.size + b * n * 2,
            transcendentals=0,
        ),
        interpret=interpret,
    )(lut_cm, codes, valid_f)


def pq4_lut_block(
    lut: jnp.ndarray,
    codes: jnp.ndarray,
    valid: jnp.ndarray | None = None,
    tile_n: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Exact ADC distances for 4-bit PQ codes (reference LUT ``Distance``,
    product_quantization.go:440 — same sum, computed as one MXU matmul).

    lut [B, m, k<=16] f32 (seg-major); codes [N, m] uint8 in [0, k).
    Returns [B, N] f32 = sum_s lut[b, s, codes[n, s]] with invalid rows
    masked.
    """
    if interpret is None:
        interpret = not recommended()
    b, m, k = lut.shape
    if k > 16:
        raise ValueError(f"pq4 kernel requires k <= 16 centroids, got {k}")
    k = 16  # pad the code axis so lane count is m*16 regardless
    n = codes.shape[0]
    pb = _pad_to(max(b, 1), _SUBLANE)
    tile_n = min(tile_n, _pad_to(max(n, 1), _LANE))
    pn = _pad_to(max(n, 1), tile_n)
    if pb != b:
        lut = jnp.pad(lut, ((0, pb - b), (0, 0), (0, 0)))
    if lut.shape[2] < k:
        lut = jnp.pad(lut, ((0, 0), (0, 0), (0, k - lut.shape[2])))
    if pn != n:
        codes = jnp.pad(codes, ((0, pn - n), (0, 0)))
    # CODE-MAJOR flatten: lane c*m + s  (pltpu.repeat produces this order)
    lut_cm = jnp.transpose(lut, (0, 2, 1)).reshape(pb, k * m)
    lut_cm = lut_cm.astype(jnp.bfloat16)
    if valid is None:
        valid_f = (jnp.arange(pn) < n).astype(jnp.float32)
    else:
        valid_f = jnp.pad(valid.astype(jnp.float32), (0, pn - n))
    out = _pq4_tiled(lut_cm, codes, valid_f[None, :], k, m, tile_n, interpret)
    return out[:b, :n]


def _pq4_recon_kernel(q_ref, cflat_ref, c_ref, valid_ref, out_ref,
                      *, k, m, metric, interpret):
    """4-bit PQ scan via RECONSTRUCT-matmul: one-hot [TILE, mk] @
    block-diagonal centroids [mk, d] rebuilds x_hat in VMEM, then the
    normal distance matmul scores it. Per-row FLOPs 2*mk*d + 2*d*B —
    beats the LUT formulation's 2*mk*B once B > mk*d/(mk-d) (~170 at
    d=128), so large serving batches take this path."""
    c = c_ref[:].astype(jnp.int32)  # [TILE, m]
    if interpret:
        rep = jnp.concatenate([c] * k, axis=1)
    else:
        rep = pltpu.repeat(c, k, axis=1)  # [TILE, k*m] code-major
    lane_code = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1) // m
    oh = (rep == lane_code).astype(jnp.bfloat16)
    x_hat = jax.lax.dot_general(
        oh, cflat_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [TILE, d]
    xn = jnp.sum(x_hat * x_hat, axis=1)  # [TILE] = ||x_hat||^2 (exact:
    # segments are disjoint columns, so the reconstruction is exact)
    dots = jax.lax.dot_general(
        q_ref[:], x_hat.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [B, TILE]
    if metric == "l2-squared":
        q = q_ref[:].astype(jnp.float32)
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        d_ = qn - 2.0 * dots + xn[None, :]
    elif metric == "dot":
        d_ = -dots
    else:  # cosine: stored side normalized upstream; ADC keeps ranking
        d_ = 1.0 - dots
    out_ref[:] = (d_ + (1.0 - valid_ref[:]) * MASKED_DISTANCE
                  ).astype(jnp.bfloat16)


@functools.partial(jax.jit,
                   static_argnames=("k", "m", "metric", "tile_n", "interpret"))
def _pq4_recon_tiled(q, cflat, codes, valid_f, k, m, metric, tile_n,
                     interpret):
    b = q.shape[0]
    n = codes.shape[0]
    d = cflat.shape[1]
    return pl.pallas_call(
        functools.partial(_pq4_recon_kernel, k=k, m=m, metric=metric,
                          interpret=interpret),
        grid=(n // tile_n,),
        in_specs=[
            pl.BlockSpec((b, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k * m, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_n, m), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile_n), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((b, tile_n), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.bfloat16),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * k * m * d + 2 * b * n * d,
            bytes_accessed=q.size * 2 + codes.size + b * n * 2,
            transcendentals=0,
        ),
        interpret=interpret,
    )(q, cflat, codes, valid_f)


def pq4_recon_block(
    q: jnp.ndarray,
    codes: jnp.ndarray,
    centroids: jnp.ndarray,
    metric: str = "l2-squared",
    valid: jnp.ndarray | None = None,
    tile_n: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """ADC distances for 4-bit PQ via in-VMEM reconstruction (same
    candidate semantics as pq4_lut_block; cheaper for large B).

    q [B, d] f32/bf16 (cosine: pre-normalized by caller), codes [N, m]
    uint8, centroids [m, k<=16, ds].
    """
    if interpret is None:
        interpret = not recommended()
    m, kk, ds = centroids.shape
    if kk > 16:
        raise ValueError(f"pq4 kernel requires k <= 16 centroids, got {kk}")
    k = 16
    b, d = q.shape
    n = codes.shape[0]
    pb = _pad_to(max(b, 1), _SUBLANE)
    tile_n = min(tile_n, _pad_to(max(n, 1), _LANE))
    pn = _pad_to(max(n, 1), tile_n)
    q = q.astype(jnp.bfloat16)
    if pb != b:
        q = jnp.pad(q, ((0, pb - b), (0, 0)))
    if pn != n:
        codes = jnp.pad(codes, ((0, pn - n), (0, 0)))
    cent = centroids.astype(jnp.float32)
    if kk < k:
        cent = jnp.pad(cent, ((0, 0), (0, k - kk), (0, 0)))
    # CODE-MAJOR block-diagonal flatten matching pltpu.repeat's one-hot
    # order: cflat[c*m + s, s*ds:(s+1)*ds] = cent[s, c]
    eye = jnp.eye(m, dtype=jnp.float32)
    cflat = jnp.einsum("st,skd->ktsd", eye, cent)  # [k, t, s, ds]
    cflat = cflat.reshape(k * m, m * ds).astype(jnp.bfloat16)
    if valid is None:
        valid_f = (jnp.arange(pn) < n).astype(jnp.float32)
    else:
        valid_f = jnp.pad(valid.astype(jnp.float32), (0, pn - n))
    out = _pq4_recon_tiled(q, cflat, codes, valid_f[None, :], k, m,
                           metric, tile_n, interpret)
    return out[:b, :n]


# -- 8-bit PQ: the code look-up as a lane gather ------------------------------
#
# ``centroids[s, codes[n, s]]`` is a look-up in a table of at most 256
# entries. As an XLA gather it is one scalar load a value, serialised:
# 786,432 of them for an 8,192-row chunk at 96 segments, 249 ms a dispatch
# of the served scan on the v5e (PERF.md, PR 28). With the corpus rows on
# LANES it is what a vreg does in one instruction: sublane row r of a tile
# holds dimension r of 128 corpus rows, row r of the table holds that
# dimension's levels, 128 to a vreg, and ``take_along_axis(axis=1)`` on a
# [rows, 128] tile is a gather inside each sublane row. 256 levels are two
# halves of 128 lanes and a select on the code's bit 7. A value is moved,
# never multiplied: the result is the centroid's own float32, bit for bit.


def _pq8_lookup_kernel(codes_ref, table_ref, out_ref):
    """codes [TD, TN] int32 (rows on lanes; a segment's code repeated on
    each of its ds sublane rows), table [TD, 128 or 256] f32 (row r = the
    levels of dimension r) -> out[r, n] = table[r, codes[r, n]]."""
    for j in range(codes_ref.shape[1] // _LANE):
        cols = slice(j * _LANE, (j + 1) * _LANE)
        code = codes_ref[:, cols]
        low = code & (_LANE - 1)
        val = jnp.take_along_axis(table_ref[:, :_LANE], low, axis=1)
        for h in range(1, table_ref.shape[1] // _LANE):
            upper = jnp.take_along_axis(
                table_ref[:, h * _LANE:(h + 1) * _LANE], low, axis=1)
            val = jnp.where((code >> 7) == h, upper, val)
        out_ref[:, cols] = val


@functools.partial(jax.jit, static_argnames=("tile_d", "tile_n", "interpret"))
def _pq8_lookup_tiled(codes_t, table, tile_d, tile_n, interpret):
    dp, pn = codes_t.shape
    width = table.shape[1]
    return pl.pallas_call(
        _pq8_lookup_kernel,
        grid=(dp // tile_d, pn // tile_n),
        in_specs=[
            pl.BlockSpec((tile_d, tile_n), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_d, width), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile_d, tile_n), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((dp, pn), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=codes_t.size * 8 + table.size * 4),
        interpret=interpret,
        name="pq8_lookup",
    )(codes_t, table)


def pq8_lookup_block(
    codes: jnp.ndarray,
    centroids: jnp.ndarray,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Dequantise 8-bit PQ codes: codes [N, m] uint8, centroids [m, k<=256,
    ds] f32 -> x_hat [N, m*ds] f32 with x_hat[n, s*ds + j] =
    centroids[s, codes[n, s], j], bit-equal to the table look-up.

    Any geometry: the tile is cut from the shapes (dimensions in blocks of
    at most 512 sublane rows, corpus rows in lanes of at most 1,024, 1 MiB
    a block), 17..128 centroids take one 128-lane half of the table and
    129..256 two. The transposes in and out are XLA's, which folds the
    one out into the distance matmul's dimension numbers.
    """
    if interpret is None:
        interpret = not recommended()
    m, k, ds = centroids.shape
    if k > 256:
        raise ValueError(f"pq8 look-up takes k <= 256 centroids, got {k}")
    n = codes.shape[0]
    d = m * ds
    d_blocks = -(-d // 512)
    tile_d = _pad_to(-(-d // d_blocks), _SUBLANE)
    dp = d_blocks * tile_d
    tile_n = 1024
    while tile_n > _LANE and (tile_d * tile_n * 4 > (1 << 20)
                              or tile_n >= 2 * _pad_to(max(n, 1), _LANE)):
        tile_n //= 2
    pn = _pad_to(max(n, 1), tile_n)
    # table[s*ds + j, c] = centroids[s, c, j]
    table = jnp.transpose(centroids.astype(jnp.float32), (0, 2, 1))
    table = jnp.pad(table.reshape(d, k),
                    ((0, dp - d), (0, _pad_to(k, _LANE) - k)))
    codes_t = jnp.repeat(codes.astype(jnp.int32), ds, axis=1).T  # [d, N]
    codes_t = jnp.pad(codes_t, ((0, dp - d), (0, pn - n)))
    out = _pq8_lookup_tiled(codes_t, table, tile_d, tile_n, interpret)
    return out[:d, :n].T


_SCAN_ID_BITS = 6  # slice-id field width: reduce_l <= 64 strided slices


def _bq_scan_kernel(qmat_ref, x_ref, bias_ref, *refs,
                    w, subtiles, sub_rows, out_w, row_major, masked,
                    interpret):
    """Fused BQ scan supertile: ±1-int8 matmul hamming + strided block-argmin.

    Round-4 redesign of the BQ hot path. The ideas versus ``_bq_mxu_kernel``:

    1. hamming(q, x) = popcount(q) + (1 - 2q) . x_bits — ONE int8 matmul
       with a ±1 query matrix gives (hamming - qpop) exactly (int32
       accumulate), no |x| popcount input, no bf16 rounding. int8 runs the
       MXU at 2x the bf16 rate (measured 178 vs 85 TOP/s on v5e).
    2. the in-VMEM unpack is pltpu.repeat + one lane-iota shift + mask
       (full-width VPU ops) instead of 32 narrow slice-concats.
    3. the kernel reduces each supertile to supertile/L candidates via a
       STRIDED block-argmin before anything leaves VMEM: the [B, N]
       distance matrix — whose HBM write+readback dominated the old kernel
       at large B — shrinks by L. One candidate per strided block loses
       ~k^2/(2 * N/L) of the top-k (birthday bound) — rescored downstream.
    4. value+id+validity are packed into ONE int32 and the merge costs
       TWO VPU passes per element (+bias, min): the query matrix is
       scaled to ±64 so the MXU emits dots PRE-SHIFTED by 6 bits, the
       driver-precomputed bias row carries the strided slice index in
       the low 6 bits plus a +(2d+2)<<6 offset on dead rows that pushes
       them past every legit value. The winning lane position is implicit
       in the output column, so 6 id bits (reduce_l <= 64) identify the
       row exactly. Requires 64*(3d+2) < 2^31, i.e. d <= 16M.

    qmat [B, 32w] int8 in {-64, +64} (bit-plane order d' = j*w + word),
    x_t [w, ST] int32 packed TRANSPOSED — words ride the sublane axis so
    the VMEM tile is lane-dense (a [ST, w] block with w << 128 wastes
    128/w of VMEM to T(8,128) lane padding — the round-4 OOM), bias
    [1, ST] int32. Emits packed int32 [B, ST/L]; driver unpacks
    vals = packed >> 6 (+qpop) and ids = (packed & 63)*out_w + column.

    With ``masked``, an extra [ST/512, B, 16] int32 ref carries per-query
    packed allow words (_block_major_mask); disallowed slots are forced
    to INT32_MAX before the strided min so they can never win.
    """
    if masked:
        am_ref, out_ref = refs
    else:
        (out_ref,) = refs
    qmat = qmat_ref[:]
    slices_per_sub = sub_rows // out_w
    # loop-invariant: plane index of each unpacked row/lane
    rep_axis = 1 if row_major else 0
    shape = (sub_rows, 32 * w) if row_major else (32 * w, sub_rows)
    shift = jax.lax.broadcasted_iota(jnp.int32, shape, rep_axis) // w

    def one_subtile(j, acc):
        if row_major:
            x = x_ref[pl.ds(j * sub_rows, sub_rows), :]  # [sub, w] int32
        else:
            x = x_ref[:, pl.ds(j * sub_rows, sub_rows)]  # [w, sub] int32
        if interpret:
            rep = jnp.concatenate([x] * 32, axis=rep_axis)
        else:
            rep = pltpu.repeat(x, 32, axis=rep_axis)  # 32w copy-major
        bits = (jax.lax.shift_right_logical(rep, shift) & 1).astype(jnp.int8)
        dots = jax.lax.dot_general(
            qmat, bits,
            dimension_numbers=(((1,), (1 if row_major else 0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [B, sub] = (hamming - qpop) << 6
        packed = dots + bias_ref[:, pl.ds(j * sub_rows, sub_rows)]
        if masked:
            nb = sub_rows // MASK_BLOCK
            bits = _mask_unpack_blocks(am_ref[pl.ds(j * nb, nb)], interpret)
            packed = jnp.where(bits > 0, packed,
                               jnp.iinfo(jnp.int32).max)
        for s in range(slices_per_sub):
            acc = jnp.minimum(acc, packed[:, s * out_w:(s + 1) * out_w])
        return acc

    init = jnp.full((qmat.shape[0], out_w), jnp.iinfo(jnp.int32).max,
                    jnp.int32)
    if subtiles == 1:
        acc = one_subtile(0, init)
    else:
        acc = jax.lax.fori_loop(0, subtiles, one_subtile, init)
    out_ref[:] = acc


@functools.partial(jax.jit, static_argnames=(
    "supertile", "sub_rows", "out_w", "row_major", "masked", "interpret"))
def _bq_scan_tiled(qmat, x_t, bias, am, supertile, sub_rows, out_w,
                   row_major, masked, interpret):
    b = qmat.shape[0]
    if row_major:
        n, w = x_t.shape
        x_spec = pl.BlockSpec((supertile, w), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)
    else:
        w, n = x_t.shape
        x_spec = pl.BlockSpec((w, supertile), lambda i: (0, i),
                              memory_space=pltpu.VMEM)
    subtiles = supertile // sub_rows
    reduce_l = supertile // out_w
    in_specs = [
        pl.BlockSpec((b, 32 * w), lambda i: (0, 0), memory_space=pltpu.VMEM),
        x_spec,
        pl.BlockSpec((1, supertile), lambda i: (0, i), memory_space=pltpu.VMEM),
    ]
    operands = (qmat, x_t, bias)
    if masked:
        in_specs.append(_mask_block_spec(supertile // MASK_BLOCK, b))
        operands = operands + (am,)
    return pl.pallas_call(
        functools.partial(_bq_scan_kernel, w=w, subtiles=subtiles,
                          sub_rows=sub_rows, out_w=out_w,
                          row_major=row_major, masked=masked,
                          interpret=interpret),
        grid=(n // supertile,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, out_w), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n // reduce_l), jnp.int32),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n * 32 * w,
            bytes_accessed=qmat.size + x_t.size * 4
            + b * (n // reduce_l) * 4 + (b * n // 8 if masked else 0),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)


def bq_queries_to_pm1(q_bits: jnp.ndarray, w: int,
                      scale: int = 1) -> jnp.ndarray:
    """Packed query words [B, W] uint32 -> ±scale int8 matrix [B, 32W] in
    the kernel's bit-plane order (lane j*W + word): +scale where the bit
    is 0, -scale where it is 1, so qmat . x_bits = scale * sum x_d
    (1 - 2 q_d). ``scale=64`` makes the MXU emit dots pre-shifted by the
    6-bit id field of ``_bq_scan_kernel``'s packed merge."""
    planes = [((q_bits >> jnp.uint32(j)) & jnp.uint32(1)) for j in range(32)]
    q01 = jnp.concatenate(planes, axis=1).astype(jnp.int8)
    return (scale - 2 * scale * q01).astype(jnp.int8)


def bq_scan_reduce(
    q_bits: jnp.ndarray,
    x_bits: jnp.ndarray,
    valid: jnp.ndarray | None = None,
    reduce_l: int = 128,
    interpret: bool | None = None,
    transposed: bool = False,
    sub_rows: int | None = None,
    allow_bits: jnp.ndarray | None = None,
):
    """Full-corpus BQ scan with in-kernel candidate reduction.

    q_bits [B, W] uint32, x_bits [N, W] uint32 — or [W, N] with
    ``transposed=True``, the layout the kernel wants (stores keep the
    code matrix transposed to skip the per-call transpose). W is padded
    to a multiple of 4 so the unpacked lane count is a 128-multiple;
    zero bits in the pad are harmless: their ±1 query weight multiplies
    a 0 bit.

    Returns (vals [B, ceil(N/st)*st/L] f32, ids [B, ...] int32) where vals
    are TRUE hamming distances (qpop added back; dead/padded slots surface
    as huge values) and ids are global row indices; strided blocks keep one
    candidate each (see _bq_scan_kernel). Feed to approx/exact top-k, then
    rescore.

    ``allow_bits`` [B, >=ceil(N_512/32)] uint32 adds a per-query allow
    bitmask (pack_allow_bitmask layout); disallowed rows never surface,
    and supertile/sub_rows are forced MASK_BLOCK-aligned so subtiles
    unpack whole packed blocks.
    """
    if interpret is None:
        interpret = not recommended()
    b, w = q_bits.shape
    d = 32 * w
    pw = _pad_to(max(w, 1), 4)
    pb = _pad_to(max(b, 1), _SUBLANE)
    # orientation: words-on-lanes ("row-major") blocks tile VMEM at
    # [sub, 128-padded] — dense enough at w >= 24 and what the capacity
    # store keeps for cheap stage-2 row gathers. Narrow codes (w < 24)
    # waste >= 5x VMEM to lane padding, so they scan TRANSPOSED [w, N]
    # (words on the sublane axis).
    row_major = w >= 24 if not transposed else False
    if transposed:
        x_t = x_bits
        n = x_t.shape[1]
    elif row_major:
        x_t = x_bits
        n = x_bits.shape[0]
    else:
        x_t = x_bits.T
        n = x_bits.shape[0]
    # subtile rows bound the in-kernel unpack intermediates ([32w, sub] int32
    # repeat + iota + int8 bits ~ 9*sub*32w bytes) and the [B, sub] dots tile
    if sub_rows is None:
        if row_major:
            sub_rows = 256
        else:
            sub_rows = 2048 if pw <= 8 else (1024 if pw <= 24 else 512)
        if pb > 512:
            sub_rows = min(sub_rows, 1024)
    # out width per supertile: one strided-min slot per reduce_l rows.
    # supertile = reduce_l * out_w; reduce_l caps at 64 (the packed id
    # field is 6 bits). Row-major supertiles cap at 8192 rows: the VMEM
    # block pads w up to 128 lanes.
    reduce_l = max(1, min(reduce_l, 64))
    reduce_l = 1 << (reduce_l.bit_length() - 1)  # floor pow2
    st_cap = 8192 if row_major else 16384
    out_w = min(max(128, st_cap // reduce_l), sub_rows)
    supertile = reduce_l * out_w
    sub_rows = min(sub_rows, supertile)
    if allow_bits is not None:
        # masked subtiles unpack whole 512-column packed blocks (all of
        # out_w/sub_rows/supertile are pow2, so alignment = scaling up)
        while supertile % MASK_BLOCK:
            out_w *= 2
            supertile = reduce_l * out_w
        sub_rows = min(max(sub_rows, out_w, MASK_BLOCK), supertile)
    pn = _pad_to(max(n, 1), supertile)
    if pw != w:
        q_bits = jnp.pad(q_bits, ((0, 0), (0, pw - w)))
        x_t = (jnp.pad(x_t, ((0, 0), (0, pw - w))) if row_major
               else jnp.pad(x_t, ((0, pw - w), (0, 0))))
    if pb != b:
        q_bits = jnp.pad(q_bits, ((0, pb - b), (0, 0)))
    if pn != n:
        x_t = (jnp.pad(x_t, ((0, pn - n), (0, 0))) if row_major
               else jnp.pad(x_t, ((0, 0), (0, pn - n))))
    # bias row: strided slice index (row // out_w within the supertile) in
    # the low 6 bits; dead rows get +(2d+2) on the value field, past any
    # legit (hamming - qpop) in [-d, d]
    pos = jnp.arange(pn, dtype=jnp.int32)
    slice_id = pos % supertile // out_w
    if valid is None:
        dead = pos >= n
    else:
        dead = jnp.logical_not(jnp.pad(valid.astype(bool), (0, pn - n),
                                       constant_values=False))
        dead = jnp.logical_or(dead, pos >= n)
    bias = slice_id + jnp.where(dead, (2 * d + 2) << _SCAN_ID_BITS, 0)
    qmat = bq_queries_to_pm1(q_bits, pw, scale=1 << _SCAN_ID_BITS)
    qpop = jnp.sum(
        jax.lax.population_count(
            jax.lax.bitcast_convert_type(q_bits, jnp.int32)
        ).astype(jnp.int32), axis=1).astype(jnp.float32)
    if x_t.dtype == jnp.uint32:
        x_t = jax.lax.bitcast_convert_type(x_t, jnp.int32)
    am = (None if allow_bits is None
          else _block_major_mask(allow_bits, pb, pn))
    packed = _bq_scan_tiled(qmat, x_t, bias[None, :], am, supertile,
                            sub_rows, out_w, row_major,
                            allow_bits is not None, interpret)
    vals = jax.lax.shift_right_arithmetic(packed, _SCAN_ID_BITS)
    slice_ids = jax.lax.bitwise_and(packed, (1 << _SCAN_ID_BITS) - 1)
    col = jnp.arange(pn // reduce_l, dtype=jnp.int32)
    ids = (slice_ids * out_w                 # winning strided slice
           + (col % out_w)[None, :]          # lane position (implicit)
           + (col // out_w * supertile)[None, :])  # supertile base
    vals = vals[:b].astype(jnp.float32) + qpop[:b, None]
    # dead rows came back at hamming + 2d+2 (> d, the max legit hamming);
    # push them to the sentinel so downstream merges never surface them.
    # This pass runs on the reduced [B, N/L] array — cheap.
    vals = jnp.where(vals > d, MASKED_DISTANCE, vals)
    return vals, ids[:b]


def _pq4_scan_kernel(lut_ref, c_ref, bias_ref, *refs,
                     m, subtiles, sub_rows, out_w, row_major, masked,
                     interpret):
    """Fused 4-bit-PQ ADC scan supertile (the PQ twin of _bq_scan_kernel).

    lut [B, 16m] int8 CODE-MAJOR per-query tables (quantized with a
    per-query scale by the driver), codes [ST, m] uint8 row-major or
    [m, ST] transposed, bias [1, ST] int32 carrying the strided slice id
    (low 6 bits) and a dead-row offset. One int8 matmul against the
    in-VMEM one-hot gives integer ADC sums; merge is shift + add + min.
    ``masked``: extra [ST/512, B, 16] int32 ref of per-query packed
    allow words, applied exactly like _bq_scan_kernel's.
    """
    if masked:
        am_ref, out_ref = refs
    else:
        (out_ref,) = refs
    lut = lut_ref[:]
    slices_per_sub = sub_rows // out_w
    rep_axis = 1 if row_major else 0
    shape = (sub_rows, 16 * m) if row_major else (16 * m, sub_rows)
    code_iota = jax.lax.broadcasted_iota(jnp.int32, shape, rep_axis) // m

    def one_subtile(j, acc):
        if row_major:
            c = c_ref[pl.ds(j * sub_rows, sub_rows), :].astype(jnp.int32)
        else:
            c = c_ref[:, pl.ds(j * sub_rows, sub_rows)].astype(jnp.int32)
        if interpret:
            rep = jnp.concatenate([c] * 16, axis=rep_axis)
        else:
            rep = pltpu.repeat(c, 16, axis=rep_axis)  # 16m copy-major
        oh = (rep == code_iota).astype(jnp.int8)
        dots = jax.lax.dot_general(
            lut, oh,
            dimension_numbers=(((1,), (1 if row_major else 0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # [B, sub] integer ADC sums
        packed = (jax.lax.shift_left(dots, _SCAN_ID_BITS)
                  + bias_ref[:, pl.ds(j * sub_rows, sub_rows)])
        if masked:
            nb = sub_rows // MASK_BLOCK
            bits = _mask_unpack_blocks(am_ref[pl.ds(j * nb, nb)], interpret)
            packed = jnp.where(bits > 0, packed,
                               jnp.iinfo(jnp.int32).max)
        for s in range(slices_per_sub):
            acc = jnp.minimum(acc, packed[:, s * out_w:(s + 1) * out_w])
        return acc

    init = jnp.full((lut.shape[0], out_w), jnp.iinfo(jnp.int32).max,
                    jnp.int32)
    if subtiles == 1:
        acc = one_subtile(0, init)
    else:
        acc = jax.lax.fori_loop(0, subtiles, one_subtile, init)
    out_ref[:] = acc


@functools.partial(jax.jit, static_argnames=(
    "supertile", "sub_rows", "out_w", "row_major", "masked", "interpret"))
def _pq4_scan_tiled(lut8, codes, bias, am, supertile, sub_rows, out_w,
                    row_major, masked, interpret):
    b = lut8.shape[0]
    if row_major:
        n, m = codes.shape
        c_spec = pl.BlockSpec((supertile, m), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)
    else:
        m, n = codes.shape
        c_spec = pl.BlockSpec((m, supertile), lambda i: (0, i),
                              memory_space=pltpu.VMEM)
    subtiles = supertile // sub_rows
    reduce_l = supertile // out_w
    in_specs = [
        pl.BlockSpec((b, 16 * m), lambda i: (0, 0),
                     memory_space=pltpu.VMEM),
        c_spec,
        pl.BlockSpec((1, supertile), lambda i: (0, i),
                     memory_space=pltpu.VMEM),
    ]
    operands = (lut8, codes, bias)
    if masked:
        in_specs.append(_mask_block_spec(supertile // MASK_BLOCK, b))
        operands = operands + (am,)
    return pl.pallas_call(
        functools.partial(_pq4_scan_kernel, m=m, subtiles=subtiles,
                          sub_rows=sub_rows, out_w=out_w,
                          row_major=row_major, masked=masked,
                          interpret=interpret),
        grid=(n // supertile,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, out_w), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, n // reduce_l), jnp.int32),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * n * 16 * m,
            bytes_accessed=lut8.size + codes.size
            + b * (n // reduce_l) * 4 + (b * n // 8 if masked else 0),
            transcendentals=0,
        ),
        interpret=interpret,
    )(*operands)


def pq4_scan_reduce(
    lut: jnp.ndarray,
    codes: jnp.ndarray,
    valid: jnp.ndarray | None = None,
    reduce_l: int = 64,
    interpret: bool | None = None,
    transposed: bool = False,
    sub_rows: int | None = None,
    allow_bits: jnp.ndarray | None = None,
):
    """Full-corpus 4-bit-PQ ADC scan with in-kernel candidate reduction.

    lut [B, m, k<=16] f32 per-query ADC tables (ops/pq.py pq_lut); codes
    [N, m] uint8 row-major (or [m, N] with ``transposed=True``). The LUT
    is quantized to int8 with one scale per QUERY (rank-preserving within
    a query; the ~0.4% distance quantization is far below the downstream
    exact-rescore tolerance), so the scan runs at the int8 MXU rate with
    the same packed (value|slice-id) strided-min merge as the BQ kernel.

    Returns (vals [B, ~N/L] f32 approximate ADC distances with dead rows
    at MASKED_DISTANCE, ids [B, ~N/L] int32 global rows). ``allow_bits``
    adds a per-query packed allow bitmask (same contract as
    ``bq_scan_reduce``).
    """
    if interpret is None:
        interpret = not recommended()
    b, m, kk = lut.shape
    if kk > 16:
        raise ValueError(f"pq4 kernel requires k <= 16 centroids, got {kk}")
    pm = _pad_to(max(m, 1), 8)
    pb = _pad_to(max(b, 1), _SUBLANE)
    row_major = (m >= 24) if not transposed else False
    if transposed:
        n = codes.shape[1]
    else:
        n = codes.shape[0]
        if not row_major:
            codes = codes.T
    if sub_rows is None:
        if row_major:
            sub_rows = 256
        else:
            sub_rows = 2048 if pm <= 8 else (1024 if pm <= 24 else 512)
        if pb > 512:
            sub_rows = min(sub_rows, 1024)
    reduce_l = max(1, min(reduce_l, 64))
    reduce_l = 1 << (reduce_l.bit_length() - 1)
    st_cap = 8192 if row_major else 16384
    out_w = min(max(128, st_cap // reduce_l), sub_rows)
    supertile = reduce_l * out_w
    sub_rows = min(sub_rows, supertile)
    if allow_bits is not None:
        while supertile % MASK_BLOCK:
            out_w *= 2
            supertile = reduce_l * out_w
        sub_rows = min(max(sub_rows, out_w, MASK_BLOCK), supertile)
    pn = _pad_to(max(n, 1), supertile)
    if pm != m:
        lut = jnp.pad(lut, ((0, 0), (0, pm - m), (0, 0)))
        codes = (jnp.pad(codes, ((0, 0), (0, pm - m))) if row_major
                 else jnp.pad(codes, ((0, pm - m), (0, 0))))
    if lut.shape[2] < 16:
        lut = jnp.pad(lut, ((0, 0), (0, 0), (0, 16 - lut.shape[2])))
    if pb != b:
        lut = jnp.pad(lut, ((0, pb - b), (0, 0), (0, 0)))
    if pn != n:
        codes = (jnp.pad(codes, ((0, pn - n), (0, 0))) if row_major
                 else jnp.pad(codes, ((0, 0), (0, pn - n))))
    # per-query int8 quantization, code-major (padded segments carry
    # zero entries) — shared helper keeps this and the IVF probe in sync
    from weaviate_tpu.ops.pq import quantize_lut_int8

    lut8, scale = quantize_lut_int8(lut)
    dead_off = 2 * 127 * pm + 2  # past any legit int8 ADC sum
    pos = jnp.arange(pn, dtype=jnp.int32)
    slice_id = pos % supertile // out_w
    if valid is None:
        dead = pos >= n
    else:
        dead = jnp.logical_not(jnp.pad(valid.astype(bool), (0, pn - n),
                                       constant_values=False))
        dead = jnp.logical_or(dead, pos >= n)
    bias = slice_id + jnp.where(dead, dead_off << _SCAN_ID_BITS, 0)
    am = (None if allow_bits is None
          else _block_major_mask(allow_bits, pb, pn))
    packed = _pq4_scan_tiled(lut8, codes, bias[None, :], am, supertile,
                             sub_rows, out_w, row_major,
                             allow_bits is not None, interpret)
    raw = jax.lax.shift_right_arithmetic(packed, _SCAN_ID_BITS)
    slice_ids = jax.lax.bitwise_and(packed, (1 << _SCAN_ID_BITS) - 1)
    col = jnp.arange(pn // reduce_l, dtype=jnp.int32)
    ids = (slice_ids * out_w + (col % out_w)[None, :]
           + (col // out_w * supertile)[None, :])
    vals = raw[:b].astype(jnp.float32) / scale[:b, None]
    vals = jnp.where(raw[:b] > 127 * pm, MASKED_DISTANCE, vals)
    return vals, ids[:b]


def bq_hamming_block(
    q_bits: jnp.ndarray,
    x_bits: jnp.ndarray,
    tile_n: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Hamming distance between packed sign-bit codes.

    q_bits [B,W] uint32, x_bits [N,W] uint32 -> [B,N] f32 bit differences
    (reference: binary_quantization.go:22 — XOR + popcount over uint64 words;
    we pack to uint32, the TPU-native integer width).
    """
    if interpret is None:
        interpret = not recommended()
    b, w = q_bits.shape
    n = x_bits.shape[0]
    pb = _pad_to(max(b, 1), _SUBLANE)
    pw = _pad_to(max(w, 1), _LANE)
    tile_n = min(tile_n, _pad_to(max(n, 1), _SUBLANE))
    pn = _pad_to(max(n, 1), tile_n)
    if (pb, pw) != (b, w):
        q_bits = jnp.pad(q_bits, ((0, pb - b), (0, pw - w)))
    if (pn, pw) != (n, w):
        x_bits = jnp.pad(x_bits, ((0, pn - n), (0, pw - w)))
    out = _bq_tiled(q_bits, x_bits, tile_n, interpret)
    return out[:b, :n]


# -- block-sparse BM25F over packed posting candidates (hybridplane) ----------
#
# The candidate axis is the hybridplane's "corpus": the host MaxScore
# planner bounds WHICH docs ship (ops/bm25.py packs them), this kernel
# scores them. Per grid step one query row's candidate tile sits in VMEM
# with its [S, tile] tf / prop-length planes; the per-segment scalars
# (term index, boost, avg-len) and per-term idf/k1/b ride in SMEM like
# the pallas guide's scalar discipline prescribes, and candidate
# liveness arrives as block-strided packed words (the PR 3 MASK_BLOCK
# layout) unpacked tile-locally — the same repeat + lane-iota-shift
# idiom every masked kernel here uses. The unrolled segment/term loops
# preserve the HOST scorer's f32 accumulation order exactly (segments in
# pack order per term, terms in ub order), so the top-k parity oracle
# holds bit-for-bit against text/inverted.py.


def _bm25_kernel(tf_ref, ln_ref, mw_ref, term_ref, boost_ref, avg_ref,
                 idf_ref, sc_ref, o_ref, *, interpret: bool):
    s = tf_ref.shape[1]        # static: block shapes carry S and T
    t = idf_ref.shape[2]
    tf = tf_ref[0]                                     # [S, tile]
    ln = ln_ref[0]
    k1 = sc_ref[0, 0, 0]
    bb = sc_ref[0, 0, 1]
    omb = sc_ref[0, 0, 2]
    contribs = []
    for si in range(s):
        norm = omb + (bb * ln[si:si + 1, :]) / avg_ref[0, 0, si]
        ctb = (boost_ref[0, 0, si] * tf[si:si + 1, :]) \
            / jnp.maximum(norm, jnp.float32(1e-9))
        # adding exact 0.0 for misses keeps f32 parity with the host's
        # skip-the-miss accumulation (and guards padded segments)
        contribs.append(jnp.where(tf[si:si + 1, :] > 0.0, ctb, 0.0))
    score = jnp.zeros_like(contribs[0])                # [1, tile]
    for ti in range(t):
        acc = jnp.zeros_like(score)
        for si in range(s):
            acc = acc + jnp.where(term_ref[0, 0, si] == ti,
                                  contribs[si], 0.0)
        score = score + (idf_ref[0, 0, ti] * acc) / (k1 + acc)
    ok = _mask_unpack_blocks(mw_ref[:], interpret)
    o_ref[0] = jnp.where(ok > 0, -score, MASKED_DISTANCE)


@functools.partial(
    jax.jit, static_argnames=("s", "t", "tile_c", "interpret"))
def _bm25_tiled(tf, ln, mw, term, boost, avg, idf, sc, s, t, tile_c,
                interpret):
    """Every per-row operand carries a unit middle axis ([B, 1, X]) so a
    one-row block's trailing dims are the full array dims — the TPU
    lowering refuses a (1, X) block of a [B, X] array. ``mw`` is
    [B * C/512, 1, 16]: row i's packed blocks, one after another."""
    b, _, c = tf.shape
    tiles = c // tile_c
    nb = tile_c // MASK_BLOCK

    def smem(width):
        return pl.BlockSpec((1, 1, width), lambda i, j: (i, 0, 0),
                            memory_space=pltpu.SMEM)

    out = pl.pallas_call(
        functools.partial(_bm25_kernel, interpret=interpret),
        grid=(b, tiles),
        in_specs=[
            pl.BlockSpec((1, s, tile_c), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s, tile_c), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((nb, 1, _MASK_WORDS),
                         lambda i, j: (i * tiles + j, 0, 0),
                         memory_space=pltpu.VMEM),
            smem(s), smem(s), smem(s), smem(t), smem(4),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_c), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, 1, c), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=b * c * (4 * s + t * (s + 3)),
            bytes_accessed=2 * tf.size * 4 + b * c * 4 + mw.size * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(tf, ln, mw, term[:, None, :], boost[:, None, :], avg[:, None, :],
      idf[:, None, :], sc[:, None, :])
    return out[:, 0, :]


def bm25_block(seg_tf, seg_len, seg_term, seg_boost, seg_avg, idf,
               k1, b, omb, cand_bits, tile_c: int = 512,
               interpret: bool | None = None):
    """NEGATED BM25F scores over packed candidates.

    ``seg_tf``/``seg_len`` [B, S, C] f32 per-(term, prop) planes over the
    candidate axis; ``seg_term`` [B, S] int32 / ``seg_boost``/``seg_avg``
    [B, S] f32 segment scalars; ``idf`` [B, T] f32; ``k1``/``b``/``omb``
    [B] f32 per-row BM25 params (``omb`` = host-rounded f32 ``1 - b``);
    ``cand_bits`` [B, C // 32] uint32 block-strided candidate liveness
    (``pack_allow_bitmask`` layout). C must be a MASK_BLOCK multiple and
    S/T at least 1 (ops/bm25.py's ``stack_sparse_operands`` guarantees
    both). Returns [B, C] f32: ``-score`` on live candidates,
    MASKED_DISTANCE elsewhere — ready for the candidate-plane top-k.
    """
    if interpret is None:
        interpret = not recommended()
    b_n, s, c = seg_tf.shape
    t = idf.shape[1]
    tile_c = min(tile_c, c)
    mw = _fit_mask_words(cand_bits, b_n, c).reshape(
        b_n * (c // MASK_BLOCK), 1, _MASK_WORDS)
    sc = jnp.stack([jnp.asarray(k1, jnp.float32),
                    jnp.asarray(b, jnp.float32),
                    jnp.asarray(omb, jnp.float32),
                    jnp.zeros_like(jnp.asarray(k1, jnp.float32))], axis=1)
    return _bm25_tiled(seg_tf.astype(jnp.float32),
                       seg_len.astype(jnp.float32), mw,
                       seg_term.astype(jnp.int32),
                       seg_boost.astype(jnp.float32),
                       seg_avg.astype(jnp.float32),
                       idf.astype(jnp.float32), sc, s, t, tile_c,
                       interpret)
