"""Config #2 shape: glove-100-angular nearVector (1M x 100, cosine).

BASELINE config #2 pairs hnsw+cosine on glove-100; the TPU serving path
for angular data is the same flat scan with rows normalized at insert
and the dot kernel (reference cosine-dot distancer, cosine_dist.go).
Measures chained device time + recall vs exact f32 cosine.
"""

from __future__ import annotations

import json
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from weaviate_tpu.ops.topk import chunked_topk_distances

    n, dim, k, batch = 1_000_000, 100, 10, 1024
    chunk = 65536
    rng = np.random.default_rng(0)
    corpus = rng.standard_normal((n, dim)).astype(np.float32)
    corpus /= np.linalg.norm(corpus, axis=1, keepdims=True)
    queries = rng.standard_normal((batch, dim)).astype(np.float32)
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)

    # exact cosine ground truth (top-k by -dot on normalized rows)
    gt = np.empty((batch, k), np.int64)
    for i in range(batch):
        d = -(corpus @ qn[i])
        gt[i] = np.argpartition(d, k)[:k]
    log("ground truth done")

    n_pad = -(-n // chunk) * chunk
    padded = np.zeros((n_pad, dim), np.float32)
    padded[:n] = corpus
    x = jax.device_put(jnp.asarray(padded, dtype=jnp.bfloat16))
    valid = jnp.asarray(np.arange(n_pad) < n)
    q_dev = jax.device_put(jnp.asarray(qn))

    def step(off, q_, x_, v_):
        return chunked_topk_distances(
            q_, x_, k=k, chunk_size=chunk, metric="cosine",
            valid=v_, id_offset=off, selection="approx")

    d, i = step(jnp.int32(0), q_dev, x, valid)
    ids = np.asarray(i)
    recall = float(np.mean([len(set(ids[r]) & set(gt[r])) / k
                            for r in range(batch)]))
    log(f"recall@{k} vs exact cosine: {recall:.4f}")

    # measure + subtract the fetch RTT and amortize over 101 reps
    # (round-2 used reps=10 with no subtraction: ~+11 ms inflation)
    @jax.jit
    def _triv(s):
        return s + 1.0

    np.asarray(_triv(jnp.float32(0)))
    _rtts = []
    for _ in range(5):
        _t0 = time.perf_counter()
        np.asarray(_triv(jnp.float32(1)))
        _rtts.append(time.perf_counter() - _t0)
    rtt_s = float(np.median(_rtts))
    log(f"fetch RTT: {rtt_s*1e3:.1f} ms (subtracted)")

    reps = 100

    @jax.jit
    def chained(q_, x_, v_):
        # taint the query with the carried distances so the scan cannot
        # be hoisted out of the timing loop (id_offset alone only feeds
        # the returned ids)
        def body(_i, carry):
            zero = carry[0][0, 0] * 0.0
            d_, _ = step(zero.astype(jnp.int32), q_ + zero, x_, v_)
            return (d_,)
        d0, _ = step(jnp.int32(0), q_, x_, v_)
        (d_,) = jax.lax.fori_loop(0, reps, body, (d0,))
        return d_

    np.asarray(chained(q_dev, x, valid))
    t0 = time.perf_counter()
    np.asarray(chained(q_dev, x, valid))
    ms = max(time.perf_counter() - t0 - rtt_s, 0.0) / (reps + 1) * 1e3
    log(f"device {ms:.2f} ms/scan -> {batch/(ms/1e3):.0f} qps")
    print(json.dumps({
        "metric": "angular_knn_1M_100d_cosine",
        "device_batch_ms": round(ms, 2),
        "qps": round(batch / (ms / 1e3)),
        "recall_at_10": round(recall, 4),
        "batch": batch,
    }), flush=True)


if __name__ == "__main__":
    main()
