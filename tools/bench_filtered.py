"""Filtered-search policy measurement (VERDICT r4 item 9).

Masked full scan vs gather-then-scan across selectivities on the real
chip: the full scan's cost is selectivity-independent, the gather path's
is O(|allowed|) — this tool measures the crossover that sets the
engine/store.py policy (gather below ~50% selectivity within a 1 GB
padded-bucket HBM budget) and the recall-parity of both paths. Chained
hoist-proof device timing (BASELINE methodology).

Usage: python tools/bench_filtered.py [--n 1000000] [--dim 128]
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--reps", type=int, default=201)
    args = ap.parse_args()

    import numpy as np

    import jax
    import jax.numpy as jnp

    from weaviate_tpu.engine.store import DeviceVectorStore

    rng = np.random.default_rng(0)
    store = DeviceVectorStore(dim=args.dim, metric="l2-squared")
    xs = rng.standard_normal((args.n, args.dim)).astype(np.float32)
    for s in range(0, args.n, 131072):
        store.add(xs[s:s + 131072])
    # the timing loops below read store.vectors/valid/sq_norms directly,
    # bypassing the flush-on-read of the store's public methods
    store.flush_staged()
    qs = rng.standard_normal((args.batch, args.dim)).astype(np.float32)

    # chained hoist-proof device timing (BASELINE methodology): R
    # executions inside ONE jit, each iteration's query tainted by the
    # previous distances, one fetch, RTT subtracted
    trivial = jax.jit(lambda x: x + 1.0)
    np.asarray(trivial(jnp.float32(0)))
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        np.asarray(trivial(jnp.float32(1)))
        rtts.append(time.perf_counter() - t0)
    rtt = float(np.median(rtts))

    def chained_ms(step_fn, arrays):
        @jax.jit
        def chained(*arrs):
            def body(_i, carry):
                zero = carry[0][0, 0] * 0.0
                # taint EVERY integer/slot operand too — a loop-invariant
                # slot array lets XLA hoist the gather itself
                tainted = tuple(
                    a if a is None else a + zero.astype(a.dtype)
                    if jnp.issubdtype(a.dtype, jnp.floating)
                    or jnp.issubdtype(a.dtype, jnp.integer)
                    else a
                    for a in arrs)
                d_, _ = step_fn(*tainted)
                return (d_,)

            d0, _ = step_fn(*arrs)
            (dd,) = jax.lax.fori_loop(0, args.reps, body, (d0,))
            return dd

        np.asarray(chained(*arrays))
        t0 = time.perf_counter()
        np.asarray(chained(*arrays))
        el = time.perf_counter() - t0 - rtt
        if el <= 0:
            log(f"WARNING: elapsed within RTT jitter ({el*1e3:.2f} ms) — "
                "reading unreliable, raise --reps")
            el = 1e-6
        return el / (args.reps + 1)

    out = {"metric": "filtered_search", "n": args.n, "dim": args.dim,
           "batch": args.batch, "rtt_ms": round(rtt * 1e3, 1),
           "points": {}}
    for sel in (0.001, 0.01, 0.0625, 0.10, 0.5):
        m = max(args.k, int(args.n * sel))
        allowed = np.sort(rng.choice(args.n, m, replace=False))
        mask = np.zeros(store.capacity, dtype=bool)
        mask[allowed] = True

        # ground truth on the filtered subset
        sub = xs[allowed]
        d_gt = ((qs[:8, None, :] - sub[None, :, :]) ** 2).sum(-1)
        gt = allowed[np.argsort(d_gt, axis=1)[:, :args.k]]

        from weaviate_tpu.ops.topk import chunked_topk_distances

        valid_dev = jnp.logical_and(store.valid, jnp.asarray(mask))
        qs_dev = jnp.asarray(qs)
        cs = min(store.chunk_size, store.capacity)

        t_mask = chained_ms(
            lambda q_, x_, v_, n_: chunked_topk_distances(
                q_, x_, k=args.k, chunk_size=cs, metric="l2-squared",
                valid=v_, x_sq_norms=n_, use_pallas=store.use_pallas,
                selection=store.selection),
            (qs_dev, store.vectors, valid_dev, store.sq_norms))

        # gather path: slot gather + dense scan inside the chain (the
        # gather IS part of the per-query cost)
        bucket = 1 << max(7, (m - 1).bit_length())
        slot_buf = np.zeros(bucket, dtype=np.int32)
        slot_buf[:m] = allowed
        vmask = np.zeros(bucket, dtype=bool)
        vmask[:m] = True
        slots_dev = jnp.asarray(slot_buf)
        vmask_dev = jnp.asarray(vmask)

        def gather_step(q_, x_, s_, vm_, n_):
            rows = x_[s_]
            vg = jnp.logical_and(store.valid[s_], vm_)
            ng = None if n_ is None else n_[s_]
            return chunked_topk_distances(
                q_, rows, k=min(args.k, bucket), chunk_size=bucket,
                metric="l2-squared", valid=vg, x_sq_norms=ng,
                use_pallas=store.use_pallas, selection=store.selection)

        t_gather = chained_ms(
            gather_step,
            (qs_dev, store.vectors, slots_dev, vmask_dev, store.sq_norms))
        d_g, i_g = store._search_gathered(qs[:8], args.k, allowed, False)
        rec = np.mean([len(set(i_g[r].tolist()) & set(gt[r].tolist()))
                       / args.k for r in range(8)])
        point = {"allowed": m,
                 "masked_ms": round(t_mask * 1e3, 3),
                 "gather_ms": round(t_gather * 1e3, 3),
                 "gather_recall": round(float(rec), 4)}
        out["points"][f"{sel:g}"] = point
        log(f"sel {sel:g} ({m} rows): masked {point['masked_ms']} ms, "
            f"gather {point['gather_ms']} ms, recall {rec:.4f}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
