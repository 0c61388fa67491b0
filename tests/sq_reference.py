"""A plain scalar quantizer, for the tests to hold the device one against.

Numpy only, no batching, nothing of the program imported. After upstream's
compressionhelpers/scalar_quantization.go as its documentation describes it
(``vectorIndexConfig.sq``, v1.26+), written from memory; the reference fork
predates it. The range is two float32 scalars for the whole space: ``a``
the least component of the training rows, ``b`` the greatest less ``a``. A
component becomes one byte, the query is encoded like a row (symmetric),
and every distance is an integer sum and the two scalars. With
``s = b / 255`` and D dimensions:

    l2-squared = s^2 * sum (cq - cx)^2
    dot        = D a^2 + a s (sum cq + sum cx) + s^2 * sum cq cx
    cosine     = 1 - dot, on unit rows (normalised BEFORE they are encoded)

Departures from upstream, each deliberate:

- the byte is ``clip(floor((x - a) * f32(255 / b)), 0, 255)``: the scale is
  divided once, in float64, and MULTIPLIED in, where upstream's formula
  reads ``(x - a) / b * 255``. A float32 multiply rounds the same in numpy,
  XLA:CPU and on the TPU; a divide inside a device program need not. A code
  may so differ from upstream's by one level where a component sits on a
  level's edge, which no distance here notices;
- the code sums are taken in int64 here (int32 on the device: they fit,
  62.4M at 960 dimensions) and turned into a float32 ONCE, at the end: what
  the device does, and what makes the comparison exact. A scan that
  accumulated in float32 or bfloat16 would round on the way (the sums pass
  2^24) and differ;
- a constant training set gets ``b`` = 1, so that nothing divides by zero;
- upstream's ``rescoreLimit`` is a count of candidates (default 20); here,
  as for pq and bq in this tree, it multiplies k.
"""

from __future__ import annotations

import numpy as np


def fit(vectors: np.ndarray) -> tuple[np.float32, np.float32]:
    """-> (a, b): the least component, and the greatest less the least."""
    v = np.asarray(vectors, np.float32)
    a = np.float32(v.min())
    b = np.float32(v.max()) - a
    return a, (b if b > 0 else np.float32(1.0))


def scale_of(b) -> np.float32:
    return np.float32(255.0 / float(b))


def step_of(b) -> np.float32:
    return np.float32(float(b) / 255.0)


def encode(a, b, vectors: np.ndarray) -> np.ndarray:
    """-> codes [n, d] uint8; rows outside ``[a, a + b]`` clip."""
    v = np.asarray(vectors, np.float32)
    return np.clip(np.floor((v - np.float32(a)) * scale_of(b)),
                   0, 255).astype(np.uint8)


def decode(a, b, codes: np.ndarray) -> np.ndarray:
    return np.float32(a) + step_of(b) * codes.astype(np.float32)


def code_scores(q_codes: np.ndarray, codes: np.ndarray,
                metric: str) -> np.ndarray:
    """-> [n] int64, exact: sum (cq - cx)^2 for l2-squared, sum cq cx for
    dot and cosine."""
    q = q_codes.astype(np.int64)[None, :]
    c = codes.astype(np.int64)
    if metric == "l2-squared":
        return ((q - c) ** 2).sum(-1)
    if metric in ("dot", "cosine"):
        return (q * c).sum(-1)
    raise ValueError(f"no plain SQ score for metric {metric!r}")


def distances(a, b, q_codes: np.ndarray, codes: np.ndarray,
              metric: str) -> np.ndarray:
    """-> [n] float32: the code distance, one rounding after the exact
    integer sums, in the order the docstring's formulas are written."""
    a, s = np.float32(a), step_of(b)
    scores = code_scores(q_codes, codes, metric).astype(np.float32)
    if metric == "l2-squared":
        return scores * (s * s)
    dim = codes.shape[1]
    sums = (int(q_codes.astype(np.int64).sum())
            + codes.astype(np.int64).sum(-1)).astype(np.float32)
    dot = (np.float32(dim) * a * a + (a * s) * sums + (s * s) * scores)
    return np.float32(1.0) - dot if metric == "cosine" else -dot


def candidates(a, b, codes, query, metric: str, n: int,
               valid: np.ndarray | None = None):
    """-> (rows [n] ascending by code distance, every row's distance,
    every row's integer score)."""
    q_codes = encode(a, b, np.asarray(query, np.float32)[None])[0]
    dist = distances(a, b, q_codes, codes, metric)
    if valid is not None:
        dist = np.where(valid, dist, np.float32(np.inf))
    return (np.argsort(dist, kind="stable")[:n], dist,
            code_scores(q_codes, codes, metric))


def search(a, b, codes, rows, query, metric: str, k: int,
           rescore_limit: int, valid: np.ndarray | None = None):
    """The whole compressed search: the ``rescore_limit * k`` best rows by
    code distance, then their exact distances in float64 from the full
    rows, then the best k. -> (row indices [k], distances [k])."""
    cand, _, _ = candidates(a, b, codes, query, metric, rescore_limit * k,
                            valid)
    full = np.asarray(rows, np.float64)[cand]
    q = np.asarray(query, np.float64)
    if metric == "cosine":
        exact = 1.0 - full @ q
    elif metric == "dot":
        exact = -(full @ q)
    else:
        exact = ((full - q) ** 2).sum(-1)
    order = np.argsort(exact, kind="stable")[:k]
    return cand[order], exact[order]
