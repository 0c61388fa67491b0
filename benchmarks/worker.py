"""One load-generating process: some of a cell's clients, one thread each,
over one gRPC channel. Started by run.py; never imports JAX.

Protocol, one JSON object per line. stdin: first the job (port, collection,
the traffic mix itself, k, seed, this process's client ids, the query pool's
file), then phases ``{"phase": name, "clients": n, "t0": epoch, "t1": epoch,
"burst": m|null, "out": path|null}`` and at last ``{"exit": true}``. Clients
with id < n send from t0 until t1: a closed loop's client sends its next
request on the reply, an open loop's when it is due (traffic.py). With
``burst`` m each of them instead sends its next m requests at once at t0 and
waits for the replies (warm-up: the batcher then drains many together).
stdout: ``{"ready": true}``, then one ``{"done": name, "sent": n, "failed":
n}`` per phase; with ``out`` every request of the phase is saved there
(.npz)."""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic  # noqa: E402
import wire  # noqa: E402


def run_client(grpc, job, mix, queries, client, t0, t1, burst, start_at,
               sink):
    """One client for one phase; appends a record per request to ``sink``:
    (client, plan index, due epoch, latency s from when it was due, query
    index, filter value or -1, ids, distances, error or None, seconds sent
    after it was due)."""
    q_idx, bounds, gaps = traffic.client_plan(mix, job["seed"], client,
                                              len(queries))
    flt = mix.get("filter")

    def request(i):
        j = i % traffic.PLAN_LEN
        bound = int(bounds[j]) if bounds is not None else -1
        return j, bound, grpc.search_request(
            job["collection"], queries[q_idx[j]], mix["request"], job["k"],
            flt, bound)

    if burst:
        reqs = [request(start_at + n)[2] for n in range(burst)]
        time.sleep(max(0.0, t0 - time.time()))
        for n, call in enumerate([grpc.search_future(r) for r in reqs]):
            try:
                grpc.parse(call.result())
                err = None
            except Exception as e:  # noqa: BLE001
                err = repr(e)
            sink.append((client, start_at + n, t0, time.time() - t0, -1, -1,
                         [], [], err, 0.0))
        return start_at + burst
    i = start_at
    due = t0
    while True:
        j, bound, req = request(i)
        if gaps is not None:
            due += gaps[j]
        if due >= t1:
            break
        wait = due - time.time()
        if wait > 0:
            time.sleep(wait)
        now = time.time()
        if gaps is None:    # closed: due when the client is free to send
            due = now
            if due >= t1:
                break
        late = now - due
        tick = time.perf_counter()
        try:
            ids, dists = grpc.search(req)
            err = None
        except Exception as e:  # noqa: BLE001 — a failed request is a result
            ids, dists, err = [], [], repr(e)
        sink.append((client, i, due, late + time.perf_counter() - tick,
                     int(q_idx[j]), bound, ids, dists, err, late))
        i += 1
    return i


def save(path, records, k):
    n = len(records)
    ids = np.full((n, k), -1, np.int64)
    dists = np.full((n, k), np.nan, np.float64)
    n_results = np.zeros(n, np.int32)
    for r, rec in enumerate(records):
        m = min(len(rec[6]), k)
        n_results[r] = len(rec[6])
        ids[r, :m] = rec[6][:m]
        dists[r, :m] = rec[7][:m]
    np.savez(path,
             client=np.array([r[0] for r in records], np.int32),
             due=np.array([r[2] for r in records], np.float64),
             latency=np.array([r[3] for r in records], np.float64),
             query=np.array([r[4] for r in records], np.int32),
             bound=np.array([r[5] for r in records], np.int64),
             failed=np.array([r[8] is not None for r in records], bool),
             late=np.array([r[9] for r in records], np.float64),
             n_results=n_results, ids=ids, dists=dists)


def main() -> int:
    job = json.loads(sys.stdin.readline())
    mix = traffic.check(job["mix"])
    queries = np.load(job["pool"])
    grpc = wire.Grpc(job["port"])
    position = dict.fromkeys(job["clients"], 0)
    print(json.dumps({"ready": True}), flush=True)
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd.get("exit"):
            break
        active = [c for c in job["clients"] if c < cmd["clients"]]
        sinks = {c: [] for c in active}

        def body(c):
            position[c] = run_client(grpc, job, mix, queries, c, cmd["t0"],
                                     cmd["t1"], cmd.get("burst"), position[c],
                                     sinks[c])

        threads = [threading.Thread(target=body, args=(c,)) for c in active]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        records = [r for c in active for r in sinks[c]]
        errors = [r[8] for r in records if r[8] is not None]
        if cmd.get("out"):
            save(cmd["out"], records, job["k"])
        print(json.dumps({"done": cmd["phase"], "sent": len(records),
                          "failed": len(errors),
                          "first_error": errors[0] if errors else None}),
              flush=True)
    grpc.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
