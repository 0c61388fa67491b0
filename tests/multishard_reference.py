"""Exact nearest neighbours over a whole corpus, for the tests to hold a
collection of several shards against.

Numpy only, float64, nothing of the program imported, and no notion of a
shard: a collection's answer is defined over the UNION of its shards' rows,
so the definition scans them all as one array. What upstream computes is
``objectVectorSearch`` (adapters/repos/db/index.go:1541): every shard
answers its own top k, the lists are joined, sorted by distance and cut to
k (index.go:1644-1648). Over exact shard answers that is the exact top k
of the union, which is what this file computes directly.

Departures from upstream's merge, each deliberate:

- no shards and no per-shard top k: one scan. A collection that dropped a
  shard's answer, cut a shard's list short of k, or merged on a wrong key
  would agree with a per-shard reference that made the same mistake;
- float64 throughout, where a shard computes in float32: the comparison
  allows a float32 rounding (1e-5 relative) and nothing more;
- ties by position in the corpus (a stable sort), where upstream's sort of
  the joined lists leaves equal distances in any order: tests that plant
  equal vectors compare the tied positions as a set;
- an object is one row: upstream's lists could briefly name an object
  twice (a replica move); here an id can only appear once, and the tests
  ask the same of the program;
- the filter is a bool mask over the corpus, applied before the top k, as
  upstream applies an allow list inside each shard's search.
"""

from __future__ import annotations

import numpy as np


def distances(rows: np.ndarray, query: np.ndarray, metric: str) -> np.ndarray:
    """float64 [N]: ``l2-squared`` or ``cosine`` (1 - cos) to ``query``."""
    x = np.asarray(rows, np.float64)
    q = np.asarray(query, np.float64)
    if metric == "l2-squared":
        diff = x - q
        return np.einsum("nd,nd->n", diff, diff)
    if metric == "cosine":
        norms = np.linalg.norm(x, axis=1) * np.linalg.norm(q)
        return 1.0 - (x @ q) / np.where(norms > 0, norms, 1.0)
    raise ValueError(f"no reference for metric {metric!r}")


def top_k(rows: np.ndarray, query: np.ndarray, k: int, metric: str,
          allowed: np.ndarray | None = None):
    """-> (positions [<= k] int64 ascending by distance, distances f64):
    the exact top ``k`` of the rows ``allowed`` marks (all, if None)."""
    d = distances(rows, query, metric)
    where = np.arange(len(d)) if allowed is None else \
        np.flatnonzero(np.asarray(allowed, bool))
    order = where[np.argsort(d[where], kind="stable")[:k]]
    return order.astype(np.int64), d[order]
