"""TPU bulk construction for HNSW.

The reference builds its graph by incremental insert (hnsw/insert.go:226):
each vector runs an ef-search against the partial graph — inherently
sequential, pointer-chasing, one-vector-at-a-time. At 1M vectors that path
is hours even in Go; in Python it is days. The TPU-first redesign turns
construction into the workload the MXU is best at:

1. **kNN graph on device**: every node's ``knn_k`` nearest neighbors come
   from the batched chunked scan (ops/topk.py), not from graph walks. One
   pass per layer over that layer's members.
2. **Vectorized diversity heuristic**: the reference's
   selectNeighborsHeuristic (heuristic.go) runs per node over its
   candidates; here it runs BATCHED over thousands of nodes at once with a
   running dominated mask — same selected sets, numpy-wide.
3. **Symmetrize + prune**: reverse edges are added in one bincount pass and
   over-budget adjacency is re-pruned with the same batched heuristic
   (insert.go's connectNeighbor shrink path, applied in bulk).

The result populates the SAME HNSWIndex structures the incremental path
uses — search, deletes, later incremental inserts, persistence all work
unchanged. Graph quality matches incremental construction (links come from
exact kNN candidates, strictly better candidate sets than ef-search
approximations).
"""

from __future__ import annotations

import functools
import logging
import math

import numpy as np

logger = logging.getLogger(__name__)


def _batched_heuristic(cand_d: np.ndarray, pair: np.ndarray, budget: int,
                       valid: np.ndarray | None = None) -> np.ndarray:
    """Diversity-select ``budget`` neighbors per row.

    cand_d [B, C] distances owner->candidate; pair [B, C, C] candidate
    pairwise distances; valid [B, C] optional candidate mask. Returns
    [B, budget] indices into C (-1 padded). Matches
    HNSWIndex._select_heuristic semantics including nearest-first backfill
    of pruned candidates.
    """
    b, c = cand_d.shape
    d = cand_d.copy()
    if valid is not None:
        d[~valid] = np.inf
    order = np.argsort(d, axis=1, kind="stable")
    d_s = np.take_along_axis(d, order, axis=1)
    rows_ix = np.arange(b)[:, None, None]
    pair_s = pair[rows_ix, order[:, :, None], order[:, None, :]]

    dominated = np.zeros((b, c), dtype=bool)
    selected = np.zeros((b, c), dtype=bool)
    count = np.zeros(b, dtype=np.int64)
    rows = np.arange(b)
    for _step in range(min(budget, c)):
        avail = ~dominated & ~selected & np.isfinite(d_s)
        first = np.argmax(avail, axis=1)
        has = avail[rows, first] & (count < budget)
        r = rows[has]
        if len(r) == 0:
            break
        f = first[has]
        selected[r, f] = True
        count[has] += 1
        dominated[r] |= pair_s[r, :, f] <= d_s[r]
    # backfill pruned (dominated, unselected) nearest-first up to budget
    need = budget - count
    if np.any(need > 0):
        fillable = dominated & ~selected & np.isfinite(d_s)
        # rank fillable candidates by position (already distance-sorted)
        prio = np.where(fillable, np.arange(c)[None, :], c)
        fill_order = np.argsort(prio, axis=1, kind="stable")
        fill_rank = np.empty_like(fill_order)
        np.put_along_axis(fill_rank, fill_order,
                          np.arange(c)[None, :].repeat(b, 0), axis=1)
        take = fillable & (fill_rank < need[:, None])
        selected |= take
    # emit selected positions (sorted by distance), mapped back through
    # ``order`` to original candidate indices
    out = np.full((b, budget), -1, dtype=np.int64)
    sel_prio = np.where(selected, np.arange(c)[None, :], c)
    sel_sorted = np.argsort(sel_prio, axis=1, kind="stable")
    n_sel = selected.sum(axis=1)
    width = min(budget, c)
    picks = sel_sorted[:, :width]
    orig = np.take_along_axis(order, picks, axis=1)
    keep = np.arange(width)[None, :] < n_sel[:, None]
    out[:, :width] = np.where(keep, orig, -1)
    return out


def _pairwise_block(vecs: np.ndarray, metric: str) -> np.ndarray:
    """pair [B, C, C] distances between candidate rows [B, C, d].

    np.matmul (batched BLAS) — a 3-operand einsum here falls back to
    numpy's generic loop and is ~50x slower at [1024, 192, 192, 128]."""
    if metric in ("l2-squared", "dot", "cosine", "cosine-dot"):
        dots = np.matmul(vecs, vecs.transpose(0, 2, 1))
        if metric == "l2-squared":
            sq = np.einsum("bcd,bcd->bc", vecs, vecs)
            return sq[:, :, None] - 2.0 * dots + sq[:, None, :]
        if metric == "dot":
            return -dots
        return 1.0 - dots
    if metric == "manhattan":
        return np.abs(vecs[:, :, None, :] - vecs[:, None, :, :]).sum(-1)
    return (vecs[:, :, None, :] != vecs[:, None, :, :]).sum(-1).astype(
        np.float32)


def _owner_dists(owner: np.ndarray, cands: np.ndarray, metric: str):
    """[B, d] x [B, C, d] -> [B, C] distances."""
    if metric in ("l2-squared", "dot", "cosine", "cosine-dot"):
        dots = np.matmul(cands, owner[:, :, None])[:, :, 0]
        if metric == "l2-squared":
            o = np.einsum("bd,bd->b", owner, owner)
            c = np.einsum("bcd,bcd->bc", cands, cands)
            return o[:, None] - 2.0 * dots + c
        if metric == "dot":
            return -dots
        return 1.0 - dots
    if metric == "manhattan":
        return np.abs(cands - owner[:, None, :]).sum(-1)
    return (cands != owner[:, None, :]).sum(-1).astype(np.float32)


# host-BLAS knn ceiling: above this the device path wins. 8192 (not the
# r4 32768): at 1M rows layer 1 has ~31k members and the host O(M^2 d)
# scan there was ~40 s of the build on one core; with the persistent
# compile cache the device path's per-shape jit cost no longer recurs.
_HOST_KNN_MAX = 8192
# CPU-backend ceiling: the XLA chunked scan on CPU beats the naive
# single-threaded numpy O(n^2 d) pass once layers get big (threaded
# matmuls + fused running top-k with bounded [qb, chunk] transients), so
# only modest layers keep the zero-compile host BLAS path there.
_CPU_HOST_KNN_MAX = 65536
_SELECT_DISPATCH_ROWS = 65536  # owners per host-level device dispatch


def _device_backend() -> bool:
    """Device link pipeline pays off on a real accelerator; on CPU the
    gather-heavy selects lose to host BLAS."""
    try:
        import jax

        return jax.default_backend() == "tpu"
    except Exception:  # noqa: BLE001
        return False


def _device_select_dispatch(xd, cand, owner_start, budget, metric, qb=1024):
    """Diversity-select on DEVICE for one dispatch of owners.

    xd [n, d] layer vectors (device-resident), cand [S, C] candidate
    positions (-1 padded, device), owners are rows owner_start..+S of xd.
    Returns [S, budget] selected positions (-1 padded), device array.

    Same semantics as ``_batched_heuristic`` (dominated-mask loop +
    nearest-first backfill), but batched on the chip: the pairwise
    candidate matrices are MXU matmuls and the budget-step loop is a
    ``lax.fori_loop`` over [B, C] masks. Owners are processed in
    ``lax.map`` blocks inside ONE jit per dispatch — per-block host round
    trips would pay a dispatch + fetch each, and >200k-row gather-heavy single
    programs crash the TPU worker (hence dispatch-level slicing; the
    jitted program is module-level so every dispatch after the first
    reuses the same trace, with ``start`` as a traced argument).
    """
    import jax.numpy as jnp

    return _select_dispatch_jit(xd, cand, jnp.int32(owner_start), budget,
                                metric, qb)


def _lazy_select_jit():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("budget", "metric", "qb"))
    def run(xd_, cand_, start, budget, metric, qb):
        s_rows, c = cand_.shape
        blocks = s_rows // qb
        def one(args):
            blk_i, cand_blk = args
            # xd_ may arrive bf16 (the scan-precision copy): gathers move
            # half the HBM bytes and the pair matmuls run the MXU's
            # native input width; every contraction accumulates f32
            owners = jax.lax.dynamic_slice(
                xd_, (start + blk_i * qb, 0), (qb, xd_.shape[1]))
            valid = cand_blk >= 0
            safe = jnp.clip(cand_blk, 0, xd_.shape[0] - 1)
            cvecs = xd_[safe]                             # [B, C, d]
            dots = jnp.einsum("bcd,bed->bce", cvecs, cvecs,
                              preferred_element_type=jnp.float32)
            if metric == "l2-squared":
                sq = jnp.einsum("bcd,bcd->bc", cvecs, cvecs,
                                preferred_element_type=jnp.float32)
                pair = sq[:, :, None] - 2.0 * dots + sq[:, None, :]
                osq = jnp.einsum("bd,bd->b", owners, owners,
                                 preferred_element_type=jnp.float32)
                od = jnp.einsum("bcd,bd->bc", cvecs, owners,
                                preferred_element_type=jnp.float32)
                cand_d = osq[:, None] - 2.0 * od + sq
            elif metric == "dot":
                pair = -dots
                cand_d = -jnp.einsum("bcd,bd->bc", cvecs, owners,
                                     preferred_element_type=jnp.float32)
            else:  # cosine family: rows normalized upstream
                pair = 1.0 - dots
                cand_d = 1.0 - jnp.einsum(
                    "bcd,bd->bc", cvecs, owners,
                    preferred_element_type=jnp.float32)
            cand_d = jnp.where(valid, cand_d, jnp.inf)
            # sort candidates by owner distance (full-width top_k = sort)
            negd, order = jax.lax.top_k(-cand_d, c)
            d_s = -negd                                   # [B, C] ascending
            pair_s = jnp.take_along_axis(
                jnp.take_along_axis(pair, order[:, :, None], axis=1),
                order[:, None, :], axis=2)                # [B, C, C]
            iota_c = jax.lax.broadcasted_iota(jnp.int32, (qb, c), 1)

            def step(_i, st):
                dominated, selected, count = st
                avail = (~dominated) & (~selected) & jnp.isfinite(d_s)
                first = jnp.argmax(avail, axis=1)         # [B]
                has = jnp.take_along_axis(
                    avail, first[:, None], axis=1)[:, 0] & (count < budget)
                pick = (iota_c == first[:, None]) & has[:, None]
                selected = selected | pick
                count = count + has.astype(jnp.int32)
                pcol = jnp.take_along_axis(
                    pair_s, first[:, None, None], axis=2)[:, :, 0]
                dominated = dominated | (
                    (pcol <= d_s) & has[:, None])
                return dominated, selected, count

            dom0 = jnp.zeros((qb, c), bool)
            sel0 = jnp.zeros((qb, c), bool)
            cnt0 = jnp.zeros((qb,), jnp.int32)
            dominated, selected, count = jax.lax.fori_loop(
                0, min(budget, c), step, (dom0, sel0, cnt0))
            # nearest-first backfill of pruned candidates up to budget
            need = budget - count
            fillable = dominated & (~selected) & jnp.isfinite(d_s)
            fill_rank = jnp.cumsum(fillable.astype(jnp.int32), axis=1) - 1
            selected = selected | (
                fillable & (fill_rank < need[:, None]))
            # emit selected (distance order), mapped back through `order`
            sel_prio = jnp.where(selected, iota_c, c)
            neg, picks = jax.lax.top_k(-sel_prio, min(budget, c))
            got = -neg < c
            orig = jnp.take_along_axis(order, picks, axis=1)
            out_pos = jnp.where(
                got, jnp.take_along_axis(safe, orig, axis=1), -1)
            if budget > c:
                out_pos = jnp.pad(out_pos, ((0, 0), (0, budget - c)),
                                  constant_values=-1)
            return out_pos

        cand_blocks = cand_.reshape(blocks, qb, c)
        blk_ids = jnp.arange(blocks, dtype=jnp.int32)
        out = jax.lax.map(one, (blk_ids, cand_blocks))
        return out.reshape(s_rows, budget)

    return run


class _SelectJit:
    """Module-level holder so every dispatch shares one jit cache (a
    per-call closure would retrace the large select program each time)."""

    _fn = None

    def __call__(self, *args):
        if _SelectJit._fn is None:
            _SelectJit._fn = _lazy_select_jit()
        return _SelectJit._fn(*args)


_select_dispatch_jit = _SelectJit()


def _device_select(xd, cand, budget, metric, qb=1024):
    """Blocked device selection over all owners; returns a DEVICE array
    [M, budget]. Owners are the first cand.shape[0] rows of xd."""
    import jax.numpy as jnp

    m = cand.shape[0]
    outs = []
    for s in range(0, m, _SELECT_DISPATCH_ROWS):
        rows = min(_SELECT_DISPATCH_ROWS, m - s)
        pad = -(-rows // qb) * qb - rows
        blk = cand[s:s + rows]
        if pad:
            blk = jnp.pad(blk, ((0, pad), (0, 0)), constant_values=-1)
        out = _device_select_dispatch(xd, blk, s, budget, metric, qb)
        outs.append(out[:rows])
    return jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]


_SYMMETRIZE_JIT = None
_SELF_DROP_JIT = None


def _self_drop_jit(kd, keep: int):
    global _SELF_DROP_JIT
    if _SELF_DROP_JIT is None:
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnames=("keep",))
        def impl(kd_, keep):
            n_ = kd_.shape[0]
            self_col = (kd_ == jnp.arange(n_)[:, None]).astype(jnp.int32)
            order = jnp.argsort(self_col, axis=1, stable=True)
            return jnp.take_along_axis(kd_, order, axis=1)[
                :, :keep].astype(jnp.int32)

        _SELF_DROP_JIT = impl
    return _SELF_DROP_JIT(kd, keep=keep)


def _device_symmetrize(fwd):
    """Union forward links with reverse edges (cap budget each way), on
    device: one sort of the edge list + position-in-group scatter —
    the vectorized twin of the host path below. Jitted ONCE at module
    scope: eager execution pays a dispatch per op, and a per-call jit would retrace every
    build."""
    global _SYMMETRIZE_JIT
    if _SYMMETRIZE_JIT is None:
        import jax

        _SYMMETRIZE_JIT = jax.jit(_device_symmetrize_impl)
    return _SYMMETRIZE_JIT(fwd)


def _device_symmetrize_impl(fwd):
    import jax.numpy as jnp

    m, budget = fwd.shape
    src = jnp.repeat(jnp.arange(m, dtype=jnp.int32), budget)
    dst = fwd.reshape(-1)
    dst = jnp.where(dst >= 0, dst, m)  # dead edges sort to the end
    order = jnp.argsort(dst, stable=True)
    dst_s, src_s = dst[order], src[order]
    starts = jnp.searchsorted(dst_s, jnp.arange(m, dtype=jnp.int32))
    pos = jnp.arange(dst_s.shape[0], dtype=jnp.int32) - starts[
        jnp.clip(dst_s, 0, m - 1)]
    keep = (dst_s < m) & (pos < budget)
    union = jnp.full((m, 2 * budget), -1, jnp.int32)
    union = union.at[:, :budget].set(fwd)
    flat = union.reshape(-1)
    tgt = jnp.where(keep, dst_s * 2 * budget + budget + pos,
                    m * 2 * budget)
    flat = flat.at[tgt].set(src_s, mode="drop")
    union = flat.reshape(m, 2 * budget)
    # dedup per row (first occurrence wins)
    srt_idx = jnp.argsort(union, axis=1, stable=True)
    srt_val = jnp.take_along_axis(union, srt_idx, axis=1)
    dup_sorted = jnp.concatenate([
        jnp.zeros((m, 1), bool),
        (srt_val[:, 1:] == srt_val[:, :-1]) & (srt_val[:, 1:] >= 0)],
        axis=1)
    dup = jnp.zeros_like(dup_sorted).at[
        jnp.arange(m)[:, None], srt_idx].set(dup_sorted)
    return jnp.where(dup, -1, union)


def _host_knn(sub: np.ndarray, k_eff: int, metric: str,
              block: int = 4096) -> np.ndarray:
    """Small member sets (upper layers) knn on host BLAS — avoids a fresh
    XLA compile per layer shape (each costs seconds)."""
    n = len(sub)
    if metric == "l2-squared":
        sq = np.einsum("nd,nd->n", sub, sub)
    out = np.empty((n, k_eff), dtype=np.int64)
    for s in range(0, n, block):
        qb = sub[s:s + block]
        dots = qb @ sub.T
        if metric == "l2-squared":
            d = sq[s:s + block, None] - 2.0 * dots + sq[None, :]
        elif metric == "dot":
            d = -dots
        elif metric in ("cosine", "cosine-dot"):
            d = 1.0 - dots
        elif metric == "manhattan":
            d = np.abs(qb[:, None, :] - sub[None, :, :]).sum(-1)
        else:
            d = (qb[:, None, :] != sub[None, :, :]).sum(-1).astype(np.float32)
        part = np.argpartition(d, k_eff - 1, axis=1)[:, :k_eff]
        pd = np.take_along_axis(d, part, axis=1)
        out[s:s + block] = np.take_along_axis(
            part, np.argsort(pd, axis=1, kind="stable"), axis=1)
    return out


def _device_knn(sub: np.ndarray, k_eff: int, metric: str,
                query_block: int = 8192, chunk_size: int = 65536,
                return_device: bool = False):
    """Full-corpus knn in ONE device dispatch: lax.map over fixed-shape
    query blocks inside a single jit — per-block host round trips each
    cost a dispatch + fetch, which adds up over 1M rows.

    ``return_device=True`` keeps everything on the chip and returns
    (xd_padded, knn_ids_device) so the device link pipeline can run
    without the ~0.5 GB knn download + re-upload."""
    import jax
    import jax.numpy as jnp

    from weaviate_tpu.engine.store import SCAN_SELECTION
    from weaviate_tpu.ops.pallas_kernels import recommended
    from weaviate_tpu.ops.topk import chunked_topk_distances

    n = len(sub)
    use_pallas = recommended()
    if not use_pallas:
        # the XLA CPU scan materializes [qb, chunk] distance transients in
        # RAM — bound them (~64 MB) for the large-layer CPU fallback path
        query_block = min(query_block, 1024)
        chunk_size = min(chunk_size, 16384)
    cs = min(chunk_size, 1 << (n - 1).bit_length())
    pad_rows = -(-n // cs) * cs - n
    x = np.pad(sub, ((0, pad_rows), (0, 0)))
    valid = np.arange(n + pad_rows) < n
    # host-level slices of a few query blocks each: one giant program over
    # 1M queries reproducibly crashes the TPU worker, and per-slice fetches
    # stay small. Queries are dynamic-sliced FROM the device-resident
    # corpus (they ARE corpus rows) — zero query uploads. On the pallas
    # path the distance kernel's [qb, chunk] tile must fit scoped VMEM
    # (8192 x 65536 does not, on the v5e), so blocks are capped at 1024
    # queries, keeping the slice size by raising the block count.
    blocks_per_slice = 8
    if use_pallas and query_block > 1024:
        if query_block % 1024 == 0:
            blocks_per_slice *= query_block // 1024
        query_block = 1024
    # a slice may not exceed the padded corpus (small layers: the
    # dynamic_slice of queries comes FROM the corpus rows)
    while blocks_per_slice > 1 and \
            blocks_per_slice * query_block > n + pad_rows:
        blocks_per_slice //= 2
    if query_block > n + pad_rows:
        query_block = n + pad_rows
    slice_rows = blocks_per_slice * query_block

    @functools.partial(jax.jit, static_argnames=("k", "cs", "metric"))
    def knn_slice(xscan, vd, norms, start, k, cs, metric):
        qs = jax.lax.dynamic_slice(
            xscan, (start, 0), (slice_rows, xscan.shape[1]))
        qb = qs.reshape(blocks_per_slice, query_block, xscan.shape[1])

        def one(qblk):
            _d, i = chunked_topk_distances(
                qblk, xscan, k=k, chunk_size=cs,
                metric=metric, valid=vd, x_sq_norms=norms,
                selection=SCAN_SELECTION, use_pallas=use_pallas)
            return i
        return jax.lax.map(one, qb).reshape(slice_rows, k)

    xd = jnp.asarray(x)
    vd = jnp.asarray(valid)
    # the scan runs bf16 on the Pallas distance kernel; candidate ids
    # then feed the select stages, which also run at scan precision
    # (bf16 inputs, f32 accumulation): float32 rows would take the
    # kernel's multi-pass HIGHEST matmul.
    xscan = xd.astype(jnp.bfloat16) if use_pallas else xd
    # build-time scratch is the dominant transient HBM consumer at 1M
    # rows — ledger-tracked for exactly as long as the array lives, so
    # peak watermarks and /v1/debug/memory see bulk builds
    from weaviate_tpu.runtime.hbm_ledger import ledger as _hbm

    _hbm.track("build_scratch", xscan)
    norms = jnp.sum(xd.astype(jnp.float32) ** 2, axis=-1)
    norms_arg = norms if metric == "l2-squared" else None
    if return_device:
        parts = []
        for s in range(0, n, slice_rows):
            start = min(s, max(n + pad_rows - slice_rows, 0))
            ids = knn_slice(xscan, vd, norms_arg, start, k_eff, cs, metric)
            parts.append(ids[s - start: s - start + min(slice_rows, n - s)])
        knn_dev = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        # hand back the SCAN-precision corpus (bf16 on the pallas path):
        # the select stages gather from it and run their pair matmuls at
        # the MXU's native width with f32 accumulation, so the f32 copy
        # can be freed now (it is half the pipeline's HBM at 1M rows)
        return xscan, knn_dev
    out = np.empty((n, k_eff), dtype=np.int64)
    for s in range(0, n, slice_rows):
        # clamp the window inside the padded corpus; overlap re-computes a
        # few rows rather than compiling a second (tail) shape
        start = min(s, max(n + pad_rows - slice_rows, 0))
        ids = knn_slice(xscan, vd, norms_arg, start, k_eff, cs, metric)
        take = np.asarray(ids[s - start: s - start + min(slice_rows, n - s)],
                          dtype=np.int64)
        out[s: s + len(take)] = take
    return out


def _knn_graph(vectors: np.ndarray, members: np.ndarray, knn_k: int,
               metric: str) -> np.ndarray:
    """For each member, its knn_k nearest OTHER members (positions into
    ``members``)."""
    sub = vectors[members]
    n = len(sub)
    k_eff = min(knn_k + 1, n)
    supported = metric in ("l2-squared", "dot", "cosine", "cosine-dot")
    # host BLAS for small layers (zero compiles); device path above the
    # backend's ceiling — on CPU backends that's the XLA chunked scan
    # (exact top_k lowering), no longer the unconditional O(n^2 d) numpy
    # pass that made large CPU builds crawl
    host_cap = _HOST_KNN_MAX if _device_backend() else _CPU_HOST_KNN_MAX
    if not supported or n <= host_cap:
        if not supported and n > _CPU_HOST_KNN_MAX:
            logger.warning(
                "hnsw bulk build: %d-row layer falls back to the exact "
                "O(n^2 d) host BLAS knn — metric %r has no device scan",
                n, metric)
        out = _host_knn(sub, k_eff, metric)
    else:
        out = _device_knn(sub, k_eff, metric)
    # drop self-hits, keep knn_k columns: stable-sort by is_self pushes
    # non-self candidates to the front preserving distance order
    self_col = out == np.arange(n)[:, None]
    order = np.argsort(self_col, axis=1, kind="stable")
    res = np.take_along_axis(out, order, axis=1)[:, : min(knn_k, n - 1)]
    return res


def _device_link_layer(vectors: np.ndarray, members: np.ndarray,
                       knn_k: int, budget: int, metric: str) -> np.ndarray:
    """Fully device-resident knn -> select -> symmetrize -> select for one
    layer: intermediates ([M, C] candidate tensors, ~0.5-1 GB at 1M rows)
    never leave the device; only the final [M, budget] link table comes
    back. Selects run at scan precision (bf16 on TPU, f32 accumulation)
    — recall parity is pinned by the bench ef sweep. Returns positions
    into ``members`` (-1 padded)."""
    import os
    import time as _time

    trace = os.environ.get("WEAVIATE_TPU_BUILD_TRACE") == "1"

    def _t(label, fn):
        t0 = _time.perf_counter()
        out = fn()
        # force REAL execution before dispatching the next stage: letting
        # the whole pipeline queue up behind async dispatch was measured
        # 2x slower end-to-end at 300k rows (pathological queue drain);
        # a tiny data-dependent fetch is a completion probe that cannot
        # return early. Costs one fetch per stage.
        probe = out[-1] if isinstance(out, tuple) else out
        np.asarray(probe.ravel()[0])
        if trace:
            print(f"    [build-trace] {label:12s} "
                  f"{_time.perf_counter()-t0:7.2f}s", flush=True)
        return out

    sub = vectors[members]
    n = len(sub)
    k_eff = min(knn_k + 1, n)
    xd, knn_dev = _t("knn", lambda: _device_knn(
        sub, k_eff, metric, return_device=True))

    # drop self-hits on device (stable sort by is-self keeps distance
    # order); module-level jit — eager ops each pay a dispatch,
    # per-call closures retrace every build
    knn_dev = _t("self_drop", lambda: _self_drop_jit(
        knn_dev, min(knn_k, n - 1)))
    fwd = _t("select1", lambda: _device_select(xd, knn_dev, budget, metric))
    union = _t("symmetrize", lambda: _device_symmetrize(fwd))
    final = _t("select2", lambda: _device_select(xd, union, budget, metric))
    # fetch int32 — an int64 copy would double a ~0.5 GB download at
    # 1M; the fetch is sliced so copies overlap
    return _t("download", lambda: _parallel_fetch(final))


def _parallel_fetch(arr, chunk_rows: int = 65536, workers: int = 4):
    n = arr.shape[0]
    if n <= chunk_rows:
        return np.asarray(arr)
    import concurrent.futures as cf

    with cf.ThreadPoolExecutor(workers) as ex:
        parts = list(ex.map(lambda s: np.asarray(arr[s:s + chunk_rows]),
                            range(0, n, chunk_rows)))
    return np.concatenate(parts)


def bulk_build(index, doc_ids, vectors: np.ndarray, knn_k: int = 64,
               query_block: int = 1024) -> None:
    """Populate an EMPTY HNSWIndex from scratch at device speed.

    Layer l links every node with level >= l against the other members of
    that layer using exact kNN candidates + the diversity heuristic +
    symmetrize/prune. Per-link WAL writes are skipped; one condensed
    snapshot lands at the end (same durability fixed point,
    condensor.go:27).
    """
    from weaviate_tpu.runtime.compile_cache import ensure_compile_cache

    ensure_compile_cache()  # link-pipeline jits are seconds each, cold
    doc_ids = np.asarray(doc_ids, dtype=np.int64)
    vectors = index._norm(np.asarray(vectors, dtype=np.float32))
    n = len(vectors)
    if len(doc_ids) != n:
        raise ValueError(f"{len(doc_ids)} ids != {n} vectors")
    if len(index) != 0:
        raise RuntimeError("bulk_build requires an empty index")
    with index._lock:
        index._grow(n)
        # vectorized geometric level sampling (a per-node Python RNG loop
        # costs seconds at 1M); seeded from the index RNG for determinism
        rng = np.random.default_rng(int(index._rng.random() * 2**63))
        levels = (-np.log(np.maximum(rng.random(n), 1e-12))
                  * index._ml).astype(np.int32)
        index._vecs[:n] = vectors
        index._levels[:n] = levels
        index._doc_ids[:n] = doc_ids
        index._id_to_slot = {int(d): s for s, d in enumerate(doc_ids)}
        index._count = n
        max_level = int(levels.max())
        for layer in range(max_level + 1):
            members = np.nonzero(levels >= layer)[0]
            if len(members) == 0:
                continue
            if len(members) == 1:
                s = int(members[0])
                links = index._links[s]
                while len(links) <= layer:
                    links.append(np.empty(0, dtype=np.int32))
                continue
            budget = index.m0 if layer == 0 else index.m
            use_device = (
                len(members) > _HOST_KNN_MAX
                and index.metric in ("l2-squared", "dot",
                                     "cosine", "cosine-dot")
                and _device_backend())
            if use_device:
                # device-scan selection cost scales ~linearly with k
                # (k=65 ran 5x the k=10 scan) and 48 candidates measured
                # recall-equivalent to 64 at 300k/1M (0.99 @ ef=24;
                # symmetrize refills the m0 budget with reverse edges).
                # Host BLAS knn below is exact and cheap at its sizes —
                # it keeps the caller's full candidate count (the PQ-ADC
                # traversal is sensitive to thinner graphs there).
                fwd = _device_link_layer(vectors, members, min(48, knn_k),
                                         budget, index.metric)
            else:
                knn = _knn_graph(vectors, members, knn_k, index.metric)
                fwd = _link_layer(index, vectors, members, knn, budget,
                                  query_block)
            _write_links(index, members, fwd, layer)
        # entrypoint: any node at the top level
        top = int(np.nonzero(levels == max_level)[0][0])
        index._ep = top
        index._max_level = max_level
        # vectors/levels/links were written past the native mirror — one
        # batched re-upload on next use
        index._native_dirty = True
        if index._log is not None:
            index.condense()


def _host_select(sub, owner_pos, cand_idx, budget, metric, query_block):
    """Blocked host-side heuristic selection (small layers / non-MXU
    metrics). Returns [M, budget] member positions, -1 padded."""
    m_count, c = cand_idx.shape
    out = np.full((m_count, budget), -1, dtype=np.int64)
    for s in range(0, m_count, query_block):
        blk = cand_idx[s:s + query_block]
        valid = blk >= 0
        safe = np.clip(blk, 0, len(sub) - 1)
        cvecs = sub[safe]
        pair = _pairwise_block(cvecs, metric)
        cand_d = _owner_dists(sub[owner_pos[s:s + query_block]], cvecs,
                              metric)
        sel = _batched_heuristic(cand_d, pair, budget, valid=valid)
        take = sel >= 0
        safe_sel = np.clip(sel, 0, c - 1)
        out[s:s + query_block] = np.where(
            take, np.take_along_axis(safe, safe_sel, axis=1), -1)
    return out


def _link_layer(index, vectors, members, knn, budget, query_block):
    """Heuristic-select forward links, symmetrize, shrink to budget.
    ``knn`` holds positions into ``members``; returns [M, budget] positions
    into ``members`` (-1 padded)."""
    metric = index.metric
    m_count, c = knn.shape
    sub = vectors[members]
    owner_pos = np.arange(m_count)

    # selection runs on HOST BLAS: the device fori_loop select is
    # gather-heavy, and
    # the knn scan — where the FLOPs are — already ran on the MXU
    fwd = _host_select(sub, owner_pos, knn, budget, metric, query_block)

    # symmetrize: reverse edges via one argsort pass, then cap the union
    # at 2*budget nearest before the final heuristic prune
    src = np.repeat(np.arange(m_count), budget)
    dst = fwd.reshape(-1)
    live = dst >= 0
    src, dst = src[live], dst[live]
    order = np.argsort(dst, kind="stable")
    dst_sorted, src_sorted = dst[order], src[order]
    starts = np.searchsorted(dst_sorted, np.arange(m_count))
    c2 = budget
    union = np.full((m_count, budget + c2), -1, dtype=np.int64)
    union[:, :budget] = fwd
    # vectorized ragged fill: position-within-group scatter, capped at c2
    if len(dst_sorted):
        pos_in_group = np.arange(len(dst_sorted)) - starts[dst_sorted]
        keep = pos_in_group < c2
        union[dst_sorted[keep], budget + pos_in_group[keep]] = \
            src_sorted[keep]
    # dedup rows keeping the first occurrence (stable argsort groups equal
    # values in original order, so repeats after the first flag as dups)
    srt_idx = np.argsort(union, axis=1, kind="stable")
    srt_val = np.take_along_axis(union, srt_idx, axis=1)
    dup_sorted = np.zeros_like(srt_val, dtype=bool)
    dup_sorted[:, 1:] = (srt_val[:, 1:] == srt_val[:, :-1]) & \
        (srt_val[:, 1:] >= 0)
    dup = np.zeros_like(dup_sorted)
    np.put_along_axis(dup, srt_idx, dup_sorted, axis=1)
    union[dup] = -1
    # final shrink runs the FULL diversity heuristic over the capped union
    # — nearest-truncation here was 30% cheaper but collapsed recall@10
    # from 1.00 to 0.69 on 200k gaussian (the diversity property of the
    # reverse-merge is load-bearing, exactly why the reference's
    # connectNeighbor shrink path re-runs its heuristic)
    return _host_select(sub, owner_pos, union, budget, metric, query_block)


def _write_links(index, members, links_pos, layer):
    """Store [M, budget] member-position links as slot-id arrays."""
    for i, slot in enumerate(members.tolist()):
        row = links_pos[i]
        row = row[row >= 0]
        slots = members[row].astype(np.int32)
        lk = index._links[slot]
        while len(lk) <= layer:
            lk.append(np.empty(0, dtype=np.int32))
        lk[layer] = slots
