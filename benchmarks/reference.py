"""The plain reference and the comparison that decides ``correct``.

Pure numpy; imports nothing of the program and takes nothing it made. The
reference is exact f32/f64 top-k over the seeded corpus, masked by each
request's filter. ``judge`` compares every reply of the window with it and
returns the numbers compared, each beside its limit. ``lower_precision`` is
the control: the same reference with the corpus rounded to the next
precision below the one the configuration states; put in the program's
place it has to come out not correct (tests/test_control.py)."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from traffic import allowed

BLOCK = 256   # queries per block of the distance matrix


def prepare(vectors: np.ndarray, metric: str) -> np.ndarray:
    """Rows as the metric compares them (unit rows for cosine), f32."""
    v = np.asarray(vectors, dtype=np.float32)
    if metric == "cosine":
        norms = np.sqrt((v.astype(np.float64) ** 2).sum(-1, keepdims=True))
        v = (v / np.maximum(norms, 1e-30)).astype(np.float32)
    elif metric != "l2-squared":
        raise ValueError(f"no reference for metric {metric!r}")
    return v


def block_distances(q: np.ndarray, c: np.ndarray, c_sq, metric: str):
    """[Q, N] f32 distances of prepared queries and rows."""
    dots = q @ c.T
    if metric == "cosine":
        return 1.0 - dots
    return (q * q).sum(-1)[:, None] - 2.0 * dots + c_sq[None, :]


def exact_distances(q: np.ndarray, rows: np.ndarray, metric: str):
    """f64 distances [R, k] of prepared queries [R, d] to their own
    prepared rows [R, k, d]."""
    q = q.astype(np.float64)
    rows = rows.astype(np.float64)
    if metric == "cosine":
        return 1.0 - np.einsum("rkd,rd->rk", rows, q)
    return ((rows - q[:, None, :]) ** 2).sum(-1)


def kth_distances(queries, corpus, metric, k, masks, wanted):
    """Exact k-th best distance for each wanted (query index, mask key).

    ``masks`` maps a mask key to a bool [N] (None: every row); ``wanted`` is
    a set of (query index, key). A block's f32 matrix picks k + 8
    candidates, whose distances are then taken again in f64. -> dict."""
    q_all = sorted({qi for qi, _ in wanted})
    c_sq = (corpus * corpus).sum(-1) if metric == "l2-squared" else None
    blocks = [q_all[i:i + BLOCK] for i in range(0, len(q_all), BLOCK)]

    def one(block):
        out = {}
        d = block_distances(queries[block], corpus, c_sq, metric)
        for key, mask in masks.items():
            rows = [r for r, qi in enumerate(block) if (qi, key) in wanted]
            if not rows:
                continue
            dm = d[rows] if mask is None else np.where(mask, d[rows], np.inf)
            cand = np.argpartition(dm, k + 8, axis=1)[:, :k + 8]
            for j, r in enumerate(rows):
                c = cand[j][np.isfinite(dm[j, cand[j]])]
                exact = np.sort(exact_distances(queries[block[r]][None, :],
                                                corpus[c][None], metric)[0])
                out[(block[r], key)] = (exact[min(k, len(exact)) - 1]
                                        if len(exact) else np.inf)
        return out

    result = {}
    with ThreadPoolExecutor(6) as pool:
        for part in pool.map(one, blocks):
            result.update(part)
    return result


def judge(replies: dict, queries, corpus, props, metric: str, k: int,
          flt: dict | None, limits: dict) -> dict:
    """Every reply of the window against the reference.

    ``replies``: arrays query [R], bound [R] (-1: no filter), n_results
    [R], ids [R, k], dists [R, k], failed [R]. -> {"numbers": {name:
    {"value", "limit", "ok"}}, "correct": bool, "recall_at_k": float}."""
    queries = prepare(queries, metric)
    corpus = prepare(corpus, metric)
    n_rows = len(corpus)
    ids, dists = replies["ids"], replies["dists"]
    bounds = replies["bound"]
    masks = {-1: None}
    if flt is not None:
        column = props[flt["property"]]
        masks = {int(b): allowed(flt, column, int(b))
                 for b in np.unique(bounds)}
    # shape: k distinct known ids that satisfy the reply's own filter
    whole = (~replies["failed"]) & (replies["n_results"] == k)
    whole &= ((ids >= 0) & (ids < n_rows)).all(axis=1)
    srt = np.sort(ids, axis=1)
    whole &= (srt[:, 1:] != srt[:, :-1]).all(axis=1)
    for b, mask in masks.items():
        if mask is not None:
            rows = whole & (bounds == b)
            whole[rows] &= mask[ids[rows]].all(axis=1)
    good = np.flatnonzero(whole)
    # each returned distance against that id's own exact distance
    worst_err = 0.0
    truth = kth_distances(queries, corpus, metric, k, masks,
                          {(int(replies["query"][r]), int(bounds[r]))
                           for r in good})
    hits = 0
    scale_floor = limits["distance_scale_floor"]
    step = max(1, min(1024, (1 << 25) // (k * corpus.shape[1])))
    for start in range(0, len(good), step):
        rr = good[start:start + step]
        exact = exact_distances(queries[replies["query"][rr]],
                                corpus[ids[rr]], metric)
        err = np.abs(dists[rr] - exact) / np.maximum(np.abs(exact),
                                                     scale_floor)
        worst_err = max(worst_err, float(err.max()))
        kth = np.array([truth[(int(replies["query"][r]), int(bounds[r]))]
                        for r in rr])
        hits += int((exact <= kth[:, None] * (1 + 1e-6) + 1e-9).sum())
    recall = hits / float(k * len(good)) if len(good) else 0.0
    numbers = {
        "malformed_replies": _cmp(int(len(ids) - len(good)), 0, "max"),
        "distance_error_max": _cmp(worst_err, limits["distance_error_max"],
                                   "max"),
        "recall_at_k": _cmp(recall, limits["recall_at_k_min"], "min"),
    }
    return {"numbers": numbers, "recall_at_k": recall,
            "correct": all(n["ok"] for n in numbers.values())}


def _cmp(value, limit, kind: str) -> dict:
    ok = value <= limit if kind == "max" else value >= limit
    return {"value": value, "limit": limit, "kind": kind, "ok": bool(ok)}


def judge_readback(objects: list[dict], rows, corpus, props) -> dict:
    """Objects read back by id against what was sent: vector and every
    property, exactly (f32 vectors survive the wire bit for bit)."""
    bad = 0
    for obj, i in zip(objects, rows):
        same = obj is not None and np.array_equal(
            np.asarray(obj.get("vector", []), dtype=np.float32), corpus[i])
        same = same and all(obj["properties"].get(name) == int(col[i])
                            for name, col in props.items())
        bad += not same
    return _cmp(bad, 0, "max")


# -- the control --------------------------------------------------------------


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """f32 rounded to the nearest bfloat16 (ties to even), kept as f32."""
    bits = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    rounded = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


LOWER = {"float32": np.asarray, "bfloat16": to_bfloat16}


def lower_precision(queries, corpus, props, metric, k, flt, pairs,
                    precision: str) -> dict:
    """Replies as the reference gives them with the corpus held in
    ``precision``: for each (query index, filter value) of ``pairs`` the
    top k under the rounded rows' distances, with those distances."""
    q = prepare(queries, metric)
    low = LOWER[precision](prepare(corpus, metric))
    c_sq = (low * low).sum(-1) if metric == "l2-squared" else None
    n = len(pairs)
    out = {"query": np.array([p[0] for p in pairs], np.int32),
           "bound": np.array([p[1] for p in pairs], np.int64),
           "failed": np.zeros(n, bool), "n_results": np.full(n, k, np.int32),
           "ids": np.zeros((n, k), np.int64), "dists": np.zeros((n, k))}
    for start in range(0, n, BLOCK):
        rows = range(start, min(start + BLOCK, n))
        d = block_distances(q[out["query"][rows]], low, c_sq, metric)
        if flt is not None:
            allow = allowed(flt, props[flt["property"]][None, :],
                            out["bound"][rows, None])
            d = np.where(allow, d, np.inf)
        top = np.argpartition(d, k, axis=1)[:, :k]
        order = np.argsort(np.take_along_axis(d, top, axis=1), axis=1)
        top = np.take_along_axis(top, order, axis=1)
        out["ids"][rows] = top
        out["dists"][rows] = np.take_along_axis(d, top, axis=1)
    return out
