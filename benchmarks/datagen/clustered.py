"""Mixture of gaussians, as ``chip_smoke.py``'s ``clustered`` makes it (real
embeddings cluster; i.i.d. gaussian is the adversarial floor for a
compressed index): ``rows // members`` centres, at most 65,536; each row is
a centre plus ``spread`` x N(0, 1). Queries are drawn round the same
centres. Integer properties are uniform on [lo, hi)."""

from __future__ import annotations

import numpy as np


def generate(rng: np.random.Generator, rows: int, dim: int, params: dict):
    """-> (corpus [rows, dim] f32, {property: int64 [rows]}, queries
    [params.queries, dim] f32)."""
    n_clusters = min(65536, max(16, rows // params["members"]))
    centers = rng.standard_normal((n_clusters, dim), dtype=np.float32)
    spread = np.float32(params["spread"])

    def draw(n):
        out = rng.standard_normal((n, dim), dtype=np.float32)
        out *= spread
        out += centers[rng.integers(0, n_clusters, n)]
        return out

    corpus = draw(rows)
    props = {name: rng.integers(lo, hi, rows)
             for name, (lo, hi) in sorted(params["int_props"].items())}
    return corpus, props, draw(params["queries"])
