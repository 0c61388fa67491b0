"""Shared device candidate-slot gather/rescore plane (ISSUE 16).

One idea, two memory regimes: score a BOUNDED candidate set instead of
the whole corpus, entirely on device, and return exact top-k over it.
Candidate sets arrive as static padded int32 slot tensors (-1 = empty
slot), so every consumer compiles to the same gather → matmul → fused
top-k shape regardless of how many candidates are actually live:

- ``gather_rescore_topk`` — PER-QUERY candidate sets ``[B, C]`` (IVF
  multi-probe unions, residual-PQ rescore oversets, ISSUE-3 posting
  candidates later): one batched row gather ``[B, C, d]``, one einsum
  distance, masked exact top-k. Per-query allow bitmasks (the PR 3
  block-strided ``allow_bits`` format) fold per CANDIDATE via
  ``allow_bits_for_ids`` — a word gather per slot, never a dense
  ``[B, capacity]`` unpack.
- ``rescore_tail`` — the same gather as the LAST step of a compressed
  scan's own program (``bq_topk``, ``pq_topk``, ``sq_topk`` and their
  kin), against the store's resident float32 rows.
- ``shared_candidates_topk`` — ONE candidate set shared by the whole
  batch (the low-selectivity filter cutover in ``engine/store.py``):
  gather the bucket once ``[C, d]``, run the standard chunked scan over
  the dense bucket, and remap bucket-local winners back to global slots
  ON DEVICE (so the host finish step only pads — no host remap).

The reference engine has no equivalent: its HNSW walk re-reads
neighbours pointer-by-pointer from an in-RAM graph. Here the candidate
set is materialized as one gather so the MXU sees a dense matmul
(SURVEY §7 step 5 — "recast the walk as gather-matmuls").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from weaviate_tpu.ops.distances import MASKED_DISTANCE, normalize
from weaviate_tpu.ops.pallas_kernels import allow_bits_for_ids
from weaviate_tpu.ops.topk import gathered_topk_distances, topk_smallest


@functools.partial(jax.jit, static_argnames=("k",))
def masked_candidate_topk(vals, ids, k: int):
    """The candidate plane's shared finishing move: exact top-k over
    ``(vals [B, M], ids [B, M])`` where dead entries already carry
    ``MASKED_DISTANCE``, with masked winners normalized to ``-1`` ids so
    every consumer (dense rescore, IVF probe unions, the hybridplane's
    sparse/fused legs) hands the SAME (dist, -1) tail convention to its
    finish step. Ties resolve to the lower index (``lax.top_k``)."""
    fd, fi = topk_smallest(vals, ids, k)
    fi = jnp.where(fd >= MASKED_DISTANCE, -1, fi)
    return fd, fi


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def gather_rescore_topk(q, cand_idx, rows, k: int, metric: str, *,
                        ids_of_row=None, row_norms=None, valid=None,
                        allow_bits=None):
    """Exact top-k over per-query candidate sets, one gather-matmul.

    ``q`` [B, d] f32; ``cand_idx`` [B, C] (or [1, C], broadcast) int32
    gather indices into ``rows`` [N, d]; negative indices are empty
    padding. ``ids_of_row`` [N] int32 optionally maps row positions to
    the GLOBAL ids reported in the result (and folded against
    ``allow_bits``) — IVF passes flattened list positions as
    ``cand_idx`` and ``list_slots`` as ``ids_of_row``; plain rescore
    passes slot ids directly and omits it. ``valid`` [N] bool masks dead
    rows; ``allow_bits`` [B or 1, W] uint32 is the packed per-query
    allow mask over global ids. Returns ``(dists [B, k'], ids [B, k'])``
    ascending with ``k' = min(k, C)``; empty/masked tail is
    ``(MASKED_DISTANCE, -1)``. Cosine queries are normalized here; ``rows`` are
    expected pre-normalized (the store invariant).
    """
    b = q.shape[0]
    n = rows.shape[0]
    c = cand_idx.shape[1]
    idx = jnp.broadcast_to(cand_idx, (b, c))
    safe = jnp.clip(idx, 0, n - 1)
    live = (idx >= 0) & (idx < n)
    g = rows[safe].astype(jnp.float32)                    # [B, C, d]
    q32 = q.astype(jnp.float32)
    if metric in ("cosine", "cosine-dot"):
        q32 = normalize(q32)
    if metric == "l2-squared" and row_norms is None:
        # the rows are here: subtract, then square (as the host rescore
        # does), so nothing cancels between two large norms
        diff = q32[:, None, :] - g
        d = jnp.sum(diff * diff, axis=-1)
    else:
        # float32 rows state float32 arithmetic: at Precision.DEFAULT the
        # chip makes ONE bf16 pass over float32 operands (distances off
        # by 1e-3 to 1e-2, which no CPU run shows); the flat scan asks
        # the same of its float32 rows (ops/distances.py _dot_matrix)
        dots = jnp.einsum(
            "bd,bcd->bc", q32, g, preferred_element_type=jnp.float32,
            precision=(jax.lax.Precision.HIGHEST
                       if rows.dtype == jnp.float32
                       else jax.lax.Precision.DEFAULT))
        if metric == "l2-squared":
            q_norms = jnp.sum(q32 * q32, axis=-1, keepdims=True)
            d = jnp.maximum(
                q_norms - 2.0 * dots + row_norms[safe].astype(jnp.float32),
                0.0)
        elif metric == "dot":
            d = -dots
        else:  # cosine family: rows and q unit-norm -> distance 1 - cos
            d = 1.0 - dots
    if ids_of_row is not None:
        ids = jnp.where(live, ids_of_row[safe], -1)
    else:
        ids = jnp.where(live, idx, -1)
    ok = live & (ids >= 0)
    if valid is not None:
        ok = ok & valid[safe]
    if allow_bits is not None:
        ok = ok & allow_bits_for_ids(allow_bits, ids)
    d = jnp.where(ok, d, MASKED_DISTANCE)
    return masked_candidate_topk(d, ids, min(k, c))


def rescore_tail(fd, fi, q, rows, k: int, metric: str, *, valid=None,
                 allow_bits=None):
    """How a compressed scan's program ENDS where the store's float32
    rows are resident (``rows`` [N, >= d]; None: the scan's own ``(fd,
    fi)`` go back as they are, for a rescore on the host or none): the
    scan's candidates ``fi`` [B, k_cand] (-1 = none) are gathered,
    scored exactly against ``q`` [B, d] and cut to the request's ``k``,
    so one program a dispatch runs and [B, k] crosses to the host.
    ``valid`` / ``allow_bits`` are the scan's own masks: a candidate
    the scan kept only to fill k_cand (fewer live or allowed rows than
    that) stays out of the answer."""
    if rows is None:
        return fd, fi
    # the resident rows are as wide as whole lanes, zeros past d
    q = jnp.pad(q, ((0, 0), (0, rows.shape[1] - q.shape[1])))
    return gather_rescore_topk(q, fi.astype(jnp.int32), rows, k, metric,
                               valid=valid, allow_bits=allow_bits)


@functools.partial(
    jax.jit, static_argnames=("k", "metric", "use_pallas", "selection"))
def shared_candidates_topk(q, cand_slots, rows, k: int, metric: str, *,
                           row_norms=None, valid=None, use_pallas=False,
                           selection: str = "exact"):
    """Top-k over ONE candidate slot set shared by the whole batch.

    ``cand_slots`` [C] int32 global slots (-1 padding, C a power of
    two); the bucket is gathered ONCE to ``[C, d]`` and scanned with the
    standard chunked kernel, then
    bucket-local winner positions remap to global slots on device via
    ``row_ids`` — callers get global ids straight off the handle. This
    is the low-selectivity gathered path: total work is O(B·C), not
    O(B·N), and C tracks the allow-list size.

    ONE program (``jit_shared_candidates_topk`` on the device's module
    line): the clip, the two compares, the row / norm / valid gathers
    and the scan were nine programs, eight of them eager one-op
    dispatches from the batcher's worker (PERF.md, PR 40). The ops and
    their order are the eager path's, so the answers are bit-equal.
    """
    n = rows.shape[0]
    slots = jnp.asarray(cand_slots, dtype=jnp.int32)
    safe = jnp.clip(slots, 0, n - 1)
    live = (slots >= 0) & (slots < n)
    g_rows = jnp.where(live[:, None], rows[safe], 0)
    g_valid = live if valid is None else live & valid[safe]
    g_norms = None
    if metric == "l2-squared":
        g_norms = (row_norms[safe].astype(jnp.float32)
                   if row_norms is not None
                   else jnp.sum(g_rows.astype(jnp.float32) ** 2, axis=-1))
    return gathered_topk_distances(
        q, g_rows, k=min(k, slots.shape[0]), chunk_size=slots.shape[0],
        metric=metric, valid=g_valid, x_sq_norms=g_norms,
        use_pallas=use_pallas, selection=selection, row_ids=slots)
