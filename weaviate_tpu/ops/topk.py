"""Top-k selection over large corpora.

The reference merges per-shard results with a host-side sort
(adapters/repos/db/index.go:1644-1648) and maintains per-query binary heaps
in the HNSW hot loop (priorityqueue/queue.go). On TPU, selection is done
with ``jax.lax.top_k`` over distance tiles, with two composition primitives:

- ``chunked_topk``: scan an [N] axis in fixed-size chunks, carrying a running
  top-k — bounds peak memory to O(B * chunk) instead of O(B * N) so a single
  query batch can scan an HBM-resident corpus of any size.
- ``merge_topk``: merge candidate sets (e.g. per-device partial top-k after an
  all_gather over ICI) into a final top-k.

All shapes static; distances follow the "lower = closer" convention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from weaviate_tpu.ops.distances import MASKED_DISTANCE, pairwise_distance


@functools.partial(jax.jit, static_argnames=("k",))
def topk_smallest(dists: jnp.ndarray, ids: jnp.ndarray, k: int):
    """Smallest-k along the last axis. dists [B,N] f32, ids [N] or [B,N] int32.

    Returns (top_dists [B,k], top_ids [B,k]) sorted ascending by distance.
    """
    neg_d, idx = jax.lax.top_k(-dists, k)
    if ids.ndim == 1:
        top_ids = ids[idx]
    else:
        top_ids = jnp.take_along_axis(ids, idx, axis=-1)
    return -neg_d, top_ids


@functools.partial(jax.jit, static_argnames=("k",))
def approx_topk_smallest(dists: jnp.ndarray, ids: jnp.ndarray, k: int):
    """Approximate smallest-k via the TPU PartialReduce op
    (jax.lax.approx_min_k — the TPU-KNN paper's bucketed-argmin
    instruction; recall_target 0.95 per invocation). The right primitive
    for CANDIDATE generation: exact f32 rescore follows, so a rare
    dropped candidate costs recall epsilon while the selection itself
    stays O(N) with a tiny constant — lax.top_k at k~100 costs ~sort."""
    neg_d, idx = jax.lax.approx_max_k(-dists, k, recall_target=0.95)
    if ids.ndim == 1:
        top_ids = ids[idx]
    else:
        top_ids = jnp.take_along_axis(ids, idx, axis=-1)
    return -neg_d, top_ids


def select_survivors(vals, ids, k: int, id_offset=0):
    """Final selection over a scan-reduce survivor array: vals [B, M] f32
    (dead entries at MASKED_DISTANCE), ids [B, M] i32 global rows.

    The shared tail of the bq/pq4 scan-reduce consumers: one
    ``approx_max_k`` oversample (4x k) + exact merge. Pads to [B, k] with
    (MASKED_DISTANCE, -1) and applies ``id_offset`` to live entries
    only."""
    ncand = vals.shape[1]
    kk = min(k, ncand)
    if ncand > 4 * kk:
        negd, pos = jax.lax.approx_max_k(-vals, min(4 * kk, ncand),
                                         recall_target=0.95)
        vals = -negd
        ids = jnp.take_along_axis(ids, pos, axis=1)
    fd, fi = topk_smallest(vals, ids, kk)
    if kk < k:
        fd = jnp.pad(fd, ((0, 0), (0, k - kk)),
                     constant_values=MASKED_DISTANCE)
        fi = jnp.pad(fi, ((0, 0), (0, k - kk)), constant_values=-1)
    fi = jnp.where(fd >= MASKED_DISTANCE * 0.5, -1, fi + id_offset)
    return fd, fi


@functools.partial(jax.jit, static_argnames=("k",))
def merge_epoch_topk(parts, slot_maps, k: int):
    """Cross-epoch candidate merge (engine/epochs.py): the single-device
    twin of the ICI merge — per-epoch survivor sets become one global
    top-k without the distances ever leaving HBM.

    ``parts`` is a tuple of per-epoch ``(d [B, k_e], i [B, k_e])`` pairs
    with EPOCH-LOCAL row ids (-1 dead); ``slot_maps`` a matching tuple of
    ``[cap_e] int32`` local->global slot tables (compaction repacks an
    epoch's rows but keeps global slots stable through its map). Each
    epoch's ids gather through its map, the candidate sets concatenate in
    epoch order (so distance ties resolve to the lower global slot, same
    as a single-buffer scan), and the merge itself is EXACT
    (``lax.top_k``): per-epoch selection error never compounds across
    epochs, mirroring the chunk-carry contract of
    ``chunked_topk_distances``. Returns ``(d [B, k],
    i [B, k])`` global ids, (MASKED_DISTANCE, -1) padded."""
    mapped_d, mapped_i = [], []
    for (d, i), smap in zip(parts, slot_maps):
        cap = smap.shape[0]
        g = smap[jnp.clip(i, 0, cap - 1)]
        mapped_d.append(d)
        mapped_i.append(jnp.where(i >= 0, g, -1))
    cat_d = jnp.concatenate(mapped_d, axis=1)
    cat_i = jnp.concatenate(mapped_i, axis=1)
    ncand = cat_d.shape[1]
    kk = min(k, ncand)
    fd, fi = topk_smallest(cat_d, cat_i, kk)
    if kk < k:
        fd = jnp.pad(fd, ((0, 0), (0, k - kk)),
                     constant_values=MASKED_DISTANCE)
        fi = jnp.pad(fi, ((0, 0), (0, k - kk)), constant_values=-1)
    fi = jnp.where(fd >= MASKED_DISTANCE * 0.5, -1, fi)
    return fd, fi


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(dists: jnp.ndarray, ids: jnp.ndarray, k: int):
    """Merge candidate sets: dists [B, M], ids [B, M] -> top-k of the union.

    Used for the cross-shard reduce: every device contributes its local top-k,
    the [n_shards*k] candidates are all-gathered over ICI, and this picks the
    global winners (replaces the reference's host-side merge+sort+truncate,
    index.go:1644-1648).
    """
    return topk_smallest(dists, ids, k)


@functools.partial(
    jax.jit,
    static_argnames=("k", "chunk_size", "metric", "use_pallas", "selection"),
)
def chunked_topk_distances(
    q: jnp.ndarray,
    x: jnp.ndarray,
    k: int,
    chunk_size: int,
    metric: str = "l2-squared",
    valid: jnp.ndarray | None = None,
    x_sq_norms: jnp.ndarray | None = None,
    id_offset: jnp.ndarray | int = 0,
    use_pallas: bool = False,
    selection: str = "exact",
    allow_bits: jnp.ndarray | None = None,
    allow_rows: jnp.ndarray | None = None,
    row_ids: jnp.ndarray | None = None,
):
    """Brute-force top-k of ``q`` [B,d] against ``x`` [N,d], scanning in chunks.

    ``valid`` is an optional [N] bool mask (live slots / filter AllowList —
    the device-side analog of the reference's roaring-bitmap allow list,
    helpers/allow_list.go:19); invalid slots get MASKED_DISTANCE so they never
    surface. ``id_offset`` shifts local row indices into global id space for
    sharded corpora. N must be a multiple of chunk_size (pad the store, not
    the query path). Returns (dists [B,k], ids [B,k]) ascending.

    ``allow_bits`` adds a PER-QUERY allow bitmask ([B, ceil(N_512/32)]
    uint32, ``pallas_kernels.pack_allow_bitmask`` layout) — the batched
    filtered-search dataplane: unpacked once and folded into each tile
    by a [B, chunk] where. ``allow_rows`` ([B, N] bool) is the unpacked
    equivalent for callers that already hold a sliced bool mask (the
    sharded local path); pass at most one of the two.

    ``row_ids`` ([N] int32) remaps scanned row POSITIONS to global ids on
    device before returning — the candidate plane's slot remap
    (ops/candidates.shared_candidates_topk scans a gathered bucket whose
    row r is global slot ``row_ids[r]``; -1 marks bucket padding). Use
    with ``id_offset=0``; winners carrying a -1 row id surface as -1.

    ``selection`` picks the per-chunk candidate selector:

    - ``"exact"``: ``lax.top_k`` over every [B, k+chunk] tile — bit-exact,
      but at k~10-100 a wide top_k costs ~a sort and dominates the scan.
      The reference the tests and ``classification/`` call.
    - ``"approx"``: ``lax.approx_max_k`` (the TPU PartialReduce bucketed
      argmin — Chern et al., the TPU-KNN paper) pulls an OVERSAMPLED
      candidate set (4x k) per chunk at O(chunk) with a tiny constant; the
      carried running set is then merged EXACTLY, so selection error never
      compounds across chunks. Distances themselves are exact either way —
      the only approximation is which candidates survive a chunk, and with
      4x oversampling measured recall@10 vs exact is ≥0.999. On non-TPU
      backends XLA lowers approx_max_k to an exact top_k, so CPU tests see
      bit-exact results. What every store passes (``engine/store.py``
      ``SCAN_SELECTION``).
    """
    n = x.shape[0]
    assert n % chunk_size == 0, f"corpus rows {n} not a multiple of chunk {chunk_size}"
    if selection not in ("exact", "approx"):
        raise ValueError(
            f"selection must be 'exact' or 'approx', got {selection!r}")
    num_chunks = n // chunk_size
    b = q.shape[0]

    if allow_rows is None and allow_bits is not None:
        # one elementwise unpack pass; the per-chunk fold below is then a
        # plain where like the shared-valid one
        from weaviate_tpu.ops.pallas_kernels import unpack_allow_bitmask

        allow_rows = unpack_allow_bitmask(allow_bits, n)
    if allow_rows is not None:
        allow_rows = allow_rows.astype(bool)
        if allow_rows.shape[1] < n:
            allow_rows = jnp.pad(
                allow_rows, ((0, 0), (0, n - allow_rows.shape[1])))
        allow_rows = allow_rows[:, :n]

    x_chunks = x.reshape(num_chunks, chunk_size, x.shape[1])
    valid_chunks = None if valid is None else valid.reshape(num_chunks, chunk_size)
    norm_chunks = (
        None if x_sq_norms is None else x_sq_norms.reshape(num_chunks, chunk_size)
    )
    allow_chunks = (
        None if allow_rows is None
        else jnp.moveaxis(
            allow_rows.reshape(b, num_chunks, chunk_size), 1, 0)
    )

    init_d = jnp.full((b, k), MASKED_DISTANCE, dtype=jnp.float32)
    init_i = jnp.full((b, k), -1, dtype=jnp.int32)

    def body(carry, inp):
        best_d, best_i = carry
        chunk_idx, xc, vc, nc, ac = inp
        if use_pallas:
            # Fused Pallas tile kernel: MXU matmul + mask epilogue in VMEM
            # (ops/pallas_kernels.py) — the TPU stand-in for the reference's
            # SIMD distance asm.
            from weaviate_tpu.ops.pallas_kernels import distance_block

            # interpret=None → compiled on TPU, interpreter elsewhere (tests)
            d = distance_block(
                q, xc, metric=metric, valid=vc, x_sq_norms=nc, interpret=None
            )
        else:
            d = pairwise_distance(q, xc, metric=metric, x_sq_norms=nc)
            if vc is not None:
                d = jnp.where(vc[None, :], d, MASKED_DISTANCE)
        if ac is not None:
            d = jnp.where(ac, d, MASKED_DISTANCE)
        local_ids = (
            chunk_idx * chunk_size
            + id_offset
            + jax.lax.broadcasted_iota(jnp.int32, (1, chunk_size), 1)
        )
        local_ids = jnp.broadcast_to(local_ids, (b, chunk_size))
        if selection == "approx" and chunk_size > 4 * k:
            k_sel = min(max(4 * k, 32), chunk_size)
            neg_c, pos = jax.lax.approx_max_k(-d, k_sel, recall_target=0.95)
            cand_d = -neg_c
            cand_i = jnp.take_along_axis(local_ids, pos, axis=1)
        else:
            cand_d, cand_i = d, local_ids
        cat_d = jnp.concatenate([best_d, cand_d], axis=1)
        cat_i = jnp.concatenate([best_i, cand_i], axis=1)
        new_d, new_i = topk_smallest(cat_d, cat_i, k)
        return (new_d, new_i), None

    chunk_ids = jnp.arange(num_chunks, dtype=jnp.int32)
    xs = (chunk_ids, x_chunks, valid_chunks, norm_chunks, allow_chunks)
    if num_chunks == 1:
        # Avoid scan overhead for small corpora.
        (final_d, final_i), _ = body(
            (init_d, init_i),
            (
                chunk_ids[0],
                x_chunks[0],
                None if valid_chunks is None else valid_chunks[0],
                None if norm_chunks is None else norm_chunks[0],
                None if allow_chunks is None else allow_chunks[0],
            ),
        )
    else:
        (final_d, final_i), _ = jax.lax.scan(body, (init_d, init_i), xs)
    if row_ids is not None:
        final_i = jnp.where(final_i < 0, final_i,
                            row_ids[jnp.clip(final_i, 0, n - 1)])
    return final_d, final_i


@functools.partial(
    jax.jit,
    static_argnames=("k", "chunk_size", "metric", "use_pallas", "selection"),
)
def gathered_topk_distances(q, x, k, chunk_size, metric="l2-squared",
                            valid=None, x_sq_norms=None, use_pallas=False,
                            selection="exact", row_ids=None):
    """``chunked_topk_distances`` under a module name of its own: the
    scan over a dense gather of the few rows a highly selective filter
    allows (ops/candidates.shared_candidates_topk: the store's gathered
    cutover, the batcher's solo path). The same ops in the same one
    program, told apart by ROLE in a profile
    (``jit_gathered_topk_distances`` on the device's module line) and in
    the compile cache; ``jit_chunked_topk_distances`` keeps naming the
    full scans. It takes the arguments the gathered path passes."""
    return chunked_topk_distances.__wrapped__(
        q, x, k=k, chunk_size=chunk_size, metric=metric, valid=valid,
        x_sq_norms=x_sq_norms, use_pallas=use_pallas, selection=selection,
        row_ids=row_ids)


def chunked_topk(q, x, k, chunk_size=8192, metric="l2-squared", valid=None,
                 x_sq_norms=None, id_offset=0, selection="exact",
                 allow_bits=None, allow_rows=None):
    """Non-jit convenience wrapper (jit happens inside).

    Unlike the raw kernel, this accepts any corpus size: when ``chunk_size``
    does not divide N the corpus is padded with dead (masked) rows up to the
    next multiple, preserving the O(B*chunk) memory bound. The store path
    keeps capacity chunk-aligned and never pays this copy.
    """
    n = x.shape[0]
    chunk_size = min(chunk_size, n) or 1
    rem = n % chunk_size
    if rem:
        pad = chunk_size - rem
        x = jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), dtype=x.dtype)])
        if valid is None:
            valid = jnp.arange(n + pad) < n
        else:
            valid = jnp.concatenate([valid, jnp.zeros(pad, dtype=valid.dtype)])
        if x_sq_norms is not None:
            x_sq_norms = jnp.concatenate(
                [x_sq_norms, jnp.zeros(pad, dtype=x_sq_norms.dtype)]
            )
    return chunked_topk_distances(
        q, x, k, chunk_size, metric, valid, x_sq_norms, id_offset,
        selection=selection, allow_bits=allow_bits, allow_rows=allow_rows,
    )
