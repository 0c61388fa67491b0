"""A plain product quantizer, for the tests to hold the device one against.

Numpy only, no batching, nothing of the program imported: a codebook is
``[m, k, ds]`` float32 (m segments, k centroids of ds dims each), a code is
the index of the nearest centroid in each segment, a query's look-up table
holds its distance to every centroid of every segment, and the asymmetric
distance (ADC) of a row is the SUM over segments of the table entries its
code names (reference product_quantization.go:440). The device scan
(``ops/pq.py::pq_topk``) looks each chunk's codes up once for all queries
(a lane gather on the chip, ``jnp.take`` elsewhere: the centroid's own
float32 either way), and multiplies the reconstructed rows by the queries
instead; because the segments are orthogonal the two are the same number
up to rounding.
"""

from __future__ import annotations

import numpy as np

# (segments, dims a segment, centroids) the 8-bit look-up is held to: the
# benchmark cell's, this tree's default (d/8 segments of 8), fewer centroids,
# a count that is no power of two
GEOMETRIES_8BIT = [(96, 1, 256), (12, 8, 256), (24, 4, 64), (8, 4, 17)]


def _segment_distances(codebook: np.ndarray, vectors: np.ndarray):
    """Yields, for each segment, [n, k]: every row's squared distance to
    every centroid of that segment."""
    m, _, ds = codebook.shape
    segs = np.asarray(vectors, np.float32).reshape(len(vectors), m, ds)
    for s in range(m):
        diff = segs[:, s, None, :] - codebook[s][None, :, :]
        yield s, (diff * diff).sum(-1)


def encode(codebook: np.ndarray, vectors: np.ndarray) -> np.ndarray:
    """-> codes [n, m] uint8: the nearest centroid of each segment (the
    lowest index among equals)."""
    codes = np.empty((len(vectors), codebook.shape[0]), np.uint8)
    for s, d in _segment_distances(codebook, vectors):
        codes[:, s] = np.argmin(d, axis=1)
    return codes


def reconstruct(codebook: np.ndarray, codes: np.ndarray) -> np.ndarray:
    """-> [n, m * ds] float32: each segment's centroid as its code names it,
    ``codebook[s, codes[:, s]]``, a value moved and nothing computed."""
    m = codebook.shape[0]
    return codebook[np.arange(m)[None, :], codes.astype(np.int64)].reshape(
        len(codes), -1)


def encode_margin(codebook: np.ndarray, vectors: np.ndarray,
                  codes: np.ndarray) -> np.ndarray:
    """[n, m]: how much farther the centroid ``codes`` names is than the
    nearest one, in squared distance (0 where it IS the nearest)."""
    out = np.empty(codes.shape, np.float32)
    for s, d in _segment_distances(codebook, vectors):
        out[:, s] = d[np.arange(len(d)), codes[:, s]] - d.min(axis=1)
    return out


def lookup_table(codebook: np.ndarray, query: np.ndarray,
                 metric: str) -> np.ndarray:
    """-> [m, k]: the query's distance to every centroid, by segment.
    ``cosine`` takes a unit query and unit rows: 1 - q.x, the 1 added once
    by ``adc``; ``dot`` is -q.x, the same table without the 1."""
    m, _, ds = codebook.shape
    q = np.asarray(query, np.float32).reshape(m, 1, ds)
    if metric == "l2-squared":
        diff = q - codebook
        return (diff * diff).sum(-1)
    if metric in ("cosine", "dot"):
        return -(q * codebook).sum(-1)
    raise ValueError(f"no plain PQ table for metric {metric!r}")


def adc(codes: np.ndarray, table: np.ndarray, metric: str) -> np.ndarray:
    """-> [n] float32: each row's distance as the sum over segments of
    the table entries its code names."""
    total = np.zeros(len(codes), np.float32)
    for s in range(table.shape[0]):
        total += table[s, codes[:, s]]
    return total + np.float32(1.0) if metric == "cosine" else total


def candidates(codebook, codes, query, metric: str, n: int,
               valid: np.ndarray | None = None):
    """-> (rows [n] ascending by ADC distance, every row's ADC distance)."""
    dist = adc(codes, lookup_table(codebook, query, metric), metric)
    if valid is not None:
        dist = np.where(valid, dist, np.float32(np.inf))
    return np.argsort(dist, kind="stable")[:n], dist


def search(codebook, codes, rows, query, metric: str, k: int,
           rescore_limit: int, valid: np.ndarray | None = None):
    """The whole compressed search: the ``rescore_limit * k`` best rows by
    ADC distance, then their exact float32 distances, then the best k.
    -> (row indices [k], distances [k])."""
    cand, _ = candidates(codebook, codes, query, metric, rescore_limit * k,
                         valid)
    full = np.asarray(rows, np.float32)[cand]
    q = np.asarray(query, np.float32)
    if metric == "cosine":
        exact = np.float32(1.0) - full @ q
    else:
        exact = ((full - q) ** 2).sum(-1)
    order = np.argsort(exact, kind="stable")[:k]
    return cand[order], exact[order]
