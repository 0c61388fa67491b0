"""IVF as the served ``dynamic`` class defines it, in plain numpy: the
definition ``tests/test_dynamic_served.py`` holds the program to. No JAX,
nothing imported from ``weaviate_tpu``; float64 wherever something is
summed.

Textbook IVF: k-means centroids, every row in the posting list of its
nearest centroid, a query scans the ``nprobe`` lists whose centroids are
nearest to it and answers with the exact top-k over their rows. What the
store does otherwise (``weaviate_tpu/engine/ivf.py`` documents each), and
this file with it:

1. **Lists have a capacity.** The lists are one padded tensor ``[nlist,
   cap, d]``. When the lists are (re)built from the rows held (``build``),
   a list with more than ``cap`` nearest rows keeps the ``cap`` CLOSEST to
   its centroid (ties: the lower row position) and spills the others, in
   order of their distance to it, each to the nearest other centroid whose
   list has room; the room of a list is what its OWN nearest rows leave,
   reckoned before any spill, less the spilled rows it has taken (overfull
   lists in ascending order). Afterwards rows arrive in folds of the delta
   buffer (``insert``): each goes to its nearest centroid's list, or, that
   one full, to the nearest other list with room, in arrival order.
2. **A delta buffer.** Rows not yet folded into the lists are scanned
   exactly beside the probe and merged into the same top-k (``search``'s
   ``delta`` argument): no row is ever unsearchable.
3. **Cosine on the sphere.** Rows are kept unit-length, the centroids are
   scaled to unit length after k-means, membership and the choice of lists
   go by squared Euclidean distance between those unit vectors, and the
   distance returned is 1 - the dot product.
4. **The centroids are the index's own.** k-means is not re-derived here:
   the comparison is given the centroids the program trained, and checks
   everything downstream of them (membership, spill, probe, top-k,
   distances).
5. No deletes (a freed position is refilled before a list's tail grows;
   the served test makes none), and no growth of ``cap`` (the store doubles
   it when EVERY list is full; ``insert`` raises instead).

6. **The cutoff** (upstream's ``flatSearchCutoff``, which its ``hnsw``
   index has and a ``dynamic`` class configures under ``hnsw``). A filter
   that allows FEWER rows than ``flat_search_cutoff`` is not probed at
   all: the answer is the exhaustive scan over the allowed rows, wherever
   they lie (lists or delta). The count is the filter's own, so the
   answer does not depend on what else was asked at the same time. 0
   turns the rule off.

A filter (``allowed``: bool over row positions) at or over the cutoff
removes rows from the candidates of both legs; it does not change which
lists are probed."""

from __future__ import annotations

import numpy as np


def prepare(vectors: np.ndarray, metric: str) -> np.ndarray:
    """Rows as the metric compares them, float64: unit rows for cosine."""
    v = np.asarray(vectors, dtype=np.float64)
    if metric == "cosine":
        v = v / np.maximum(np.sqrt((v * v).sum(-1, keepdims=True)), 1e-30)
    elif metric != "l2-squared":
        raise ValueError(f"no IVF reference for metric {metric!r}")
    return v


def centroid_distances(rows: np.ndarray, centroids: np.ndarray) -> np.ndarray:
    """[N, nlist] squared Euclidean distances, float64, by subtraction."""
    out = np.empty((len(rows), len(centroids)))
    for s in range(0, len(rows), 1024):
        diff = rows[s:s + 1024, None, :] - centroids[None, :, :]
        out[s:s + 1024] = (diff * diff).sum(-1)
    return out


def build(rows: np.ndarray, centroids: np.ndarray, cap: int) -> np.ndarray:
    """List of each row after a (re)build from ``rows`` (prepared). ->
    int64 [N]. Departure 1, first half."""
    d = centroid_distances(rows, centroids)
    member = d.argmin(1)
    counts = np.bincount(member, minlength=len(centroids))
    room = np.clip(cap - counts, 0, None)
    for lst in np.flatnonzero(counts > cap):
        mine = np.flatnonzero(member == lst)
        order = mine[np.lexsort((mine, d[mine, lst]))]
        for r in order[cap:]:
            for t in np.argsort(np.where(np.arange(len(centroids)) == lst,
                                         np.inf, d[r]), kind="stable"):
                if room[t] > 0:
                    member[r] = t
                    room[t] -= 1
                    break
            else:
                raise ValueError(f"no list has room for row {r} at cap {cap}")
    return member


def insert(member: np.ndarray, rows: np.ndarray, centroids: np.ndarray,
           cap: int) -> np.ndarray:
    """``rows`` (prepared) folded in after the rows of ``member``, in
    order. -> the longer membership. Departure 1, second half."""
    fill = np.bincount(member, minlength=len(centroids))
    d = centroid_distances(rows, centroids)
    out = np.empty(len(rows), dtype=np.int64)
    for i in range(len(rows)):
        for t in np.argsort(d[i], kind="stable"):
            if fill[t] < cap:
                out[i] = t
                fill[t] += 1
                break
        else:
            raise ValueError("every list is full: the store would double "
                             "cap here, this reference does not")
    return np.concatenate([member, out])


def search(queries: np.ndarray, k: int, nprobe: int, metric: str,
           centroids: np.ndarray, rows: np.ndarray, member: np.ndarray,
           delta: np.ndarray | None = None,
           allowed: np.ndarray | None = None,
           flat_search_cutoff: int = 0):
    """Top-k of each query over the rows of its ``nprobe`` nearest lists
    plus the delta's rows; under the cutoff (departure 6) over every
    allowed row.

    ``rows`` [N, d] are all rows by position, raw; ``member`` [M] gives
    the list of the first M of them (those folded into lists), ``delta``
    the positions still in the delta buffer; ``allowed`` bool [N]. ->
    (positions [Q, k] int64, -1 where fewer than k candidates; distances
    [Q, k] float64, ascending, inf there)."""
    if (allowed is not None and flat_search_cutoff
            and int(np.count_nonzero(allowed)) < flat_search_cutoff):
        return exact(queries, k, metric, rows, allowed)
    q = prepare(queries, metric)
    x = prepare(rows, metric)
    c = np.asarray(centroids, dtype=np.float64)
    probes = np.argsort(centroid_distances(q, c), axis=1,
                        kind="stable")[:, :nprobe]
    in_delta = np.zeros(len(x), dtype=bool)
    if delta is not None:
        in_delta[np.asarray(delta, dtype=np.int64)] = True
    listed = np.full(len(x), -1, dtype=np.int64)
    listed[:len(member)] = member
    listed[in_delta] = -1
    ok = np.ones(len(x), dtype=bool) if allowed is None else allowed
    ids = np.full((len(q), k), -1, dtype=np.int64)
    dists = np.full((len(q), k), np.inf)
    for r in range(len(q)):
        cand = np.flatnonzero((np.isin(listed, probes[r]) | in_delta) & ok)
        if metric == "cosine":
            d = 1.0 - x[cand] @ q[r]
        else:
            diff = x[cand] - q[r]
            d = (diff * diff).sum(-1)
        top = np.lexsort((cand, d))[:k]
        ids[r, :len(top)] = cand[top]
        dists[r, :len(top)] = d[top]
    return ids, dists


def exact(queries: np.ndarray, k: int, metric: str, rows: np.ndarray,
          allowed: np.ndarray | None = None):
    """The exhaustive scan: what a class under its threshold answers, and
    what recall is counted against."""
    return search(queries, k, 1, metric, np.zeros((1, rows.shape[1])), rows,
                  np.zeros(len(rows), dtype=np.int64), allowed=allowed)
