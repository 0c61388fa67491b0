"""End-to-end server benchmark: import + query through the real APIs.

Reference: test/benchmark/benchmark_sift.go — imports a SIFT-shaped corpus
through the batch API against a running server, then times nearVector
queries and checks the results against brute force (import success rate
and 10-NN correctness are the pass criteria, :34-57).

Usage:
    python tools/bench_e2e.py [--n 100000] [--dim 128] [--queries 200]
                              [--url host:port]   # default: in-process

Prints a JSON summary line. Unlike bench.py (kernel-level headline), this
measures the full serving path: REST batch import -> gRPC Search.
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
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--queries", type=int, default=200)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--batch", type=int, default=500)
    ap.add_argument("--rest-import", action="store_true",
                    help="import via REST batch JSON (reference CI harness "
                         "path) instead of gRPC binary")
    ap.add_argument(
        "--url", default="",
        help="REST address of a running server; requires --grpc-port")
    ap.add_argument("--grpc-port", type=int, default=0)
    ap.add_argument(
        "--concurrency", type=str, default="32",
        help="closed-loop concurrent gRPC streams for the served-load "
             "measurement (0 disables; comma list sweeps a QPS-vs-streams "
             "curve, e.g. 32,64,128,256)")
    ap.add_argument("--load-queries", type=int, default=1024,
                    help="total queries across the concurrent streams")
    ap.add_argument("--null-device", action="store_true",
                    help="replace the device batch fn with a constant-time "
                         "stub to isolate the serving-fabric latency "
                         "(co-located p50 = fabric p50 + device ms)")
    ap.add_argument("--native-plane", action="store_true",
                    help="serve gRPC through the C++ data plane "
                         "(csrc/dataplane.cpp) and drive the served-load "
                         "phase with the native load generator")
    args = ap.parse_args()
    if args.native_plane:
        import os as _os

        _os.environ["WEAVIATE_TPU_NATIVE_DATAPLANE"] = "1"
    if args.url and not args.grpc_port:
        ap.error("--url mode also needs --grpc-port (queries run over "
                 "gRPC)")

    import numpy as np

    rng = np.random.default_rng(0)
    corpus = rng.standard_normal((args.n, args.dim)).astype(np.float32)
    queries = rng.standard_normal((args.queries, args.dim)).astype(np.float32)

    server = None
    if args.url:
        rest_addr = args.url
        grpc_port = args.grpc_port
    else:
        import tempfile

        from weaviate_tpu.config import ServerConfig
        from weaviate_tpu.server import Server

        server = Server(ServerConfig(
            data_path=tempfile.mkdtemp(prefix="bench-e2e-"),
            rest_port=0, grpc_port=0, disable_telemetry=True)).start()
        rest_addr = server.rest.address
        grpc_port = server.grpc.port

    from weaviate_tpu.api.client import Client

    client = Client(rest_addr, timeout=300.0)
    client.create_class({
        "class": "Bench",
        "vectorIndexType": "flat",
        "vectorIndexConfig": {"distance": "l2-squared",
                              "storage_dtype": "bfloat16"},
        "properties": [{"name": "seq", "dataType": ["int"]}]})

    # ---- import ----------------------------------------------------------
    # default: gRPC BatchObjects with binary vector_bytes — the modern
    # client path (reference clients v4 import over gRPC; vectors never
    # round-trip through JSON text). --rest-import forces the REST batch
    # JSON path of the reference CI harness.
    t0 = time.perf_counter()
    ok = 0
    if args.rest_import:
        for start in range(0, args.n, args.batch):
            chunk = corpus[start:start + args.batch]
            results = client.batch_objects([
                {"class": "Bench", "properties": {"seq": start + i},
                 "vector": row.tolist()}
                for i, row in enumerate(chunk)])
            ok += sum(1 for r in results
                      if r["result"]["status"] == "SUCCESS")
    else:
        import uuid as uuid_mod

        import grpc as grpc_lib

        from weaviate_tpu.api.grpc import v1_pb2 as pbi
        from weaviate_tpu.api.grpc.server import _SERVICE

        chan_i = grpc_lib.insecure_channel(
            f"127.0.0.1:{grpc_port}",
            options=[("grpc.max_send_message_length", 64 << 20),
                     ("grpc.max_receive_message_length", 64 << 20)])
        batch_rpc = chan_i.unary_unary(
            f"/{_SERVICE}/BatchObjects",
            request_serializer=pbi.BatchObjectsRequest.SerializeToString,
            response_deserializer=pbi.BatchObjectsReply.FromString)
        for start in range(0, args.n, args.batch):
            chunk = corpus[start:start + args.batch]
            req = pbi.BatchObjectsRequest()
            for i, row in enumerate(chunk):
                bo = req.objects.add(collection="Bench",
                                     uuid=str(uuid_mod.uuid4()))
                bo.vector_bytes = row.astype("<f4").tobytes()
                bo.properties.non_ref_properties.update(
                    {"seq": start + i})
            reply = batch_rpc(req)
            ok += len(chunk) - len(reply.errors)
        chan_i.close()
    import_s = time.perf_counter() - t0
    success_rate = ok / args.n
    log(f"import: {args.n} objects in {import_s:.1f}s "
        f"({args.n/import_s:.0f} obj/s), success {success_rate:.3%}")

    # ---- query through gRPC (the latency-critical path) -------------------
    import grpc as grpc_lib

    from weaviate_tpu.api.grpc import v1_pb2 as pb
    from weaviate_tpu.api.grpc.server import _SERVICE

    chan = grpc_lib.insecure_channel(f"127.0.0.1:{grpc_port}")
    search = chan.unary_unary(
        f"/{_SERVICE}/Search",
        request_serializer=pb.SearchRequest.SerializeToString,
        response_deserializer=pb.SearchReply.FromString)

    def query(vec):
        req = pb.SearchRequest(collection="Bench", limit=args.k,
                               uses_123_api=True)
        req.near_vector.vector_bytes = vec.astype("<f4").tobytes()
        req.metadata.uuid = True
        req.metadata.distance = True
        return search(req)

    query(queries[0])  # warm (compile; registers with the native plane)
    if args.native_plane and server is not None and hasattr(
            server.grpc, "warm_collection"):
        if server.grpc.wait_registered("Bench"):
            t_w = time.perf_counter()
            server.grpc.warm_collection("Bench")  # joins the auto-warm
            log(f"native plane reply cache warm after "
                f"{time.perf_counter() - t_w:.1f}s")
        else:
            log("WARNING: collection never fast-path registered — "
                "served numbers below are FALLBACK-path numbers")
    lat = []
    hits_by_query = []
    for q in queries:
        t0 = time.perf_counter()
        reply = query(q)
        lat.append(time.perf_counter() - t0)
        hits_by_query.append([
            int(r.properties.non_ref_props.fields["seq"].int_value)
            for r in reply.results])
    lat = np.asarray(lat)

    # ---- correctness vs brute force (reference: nrSearchResults check) ----
    qn = (queries ** 2).sum(-1)[:, None]
    cn = (corpus ** 2).sum(-1)[None, :]
    recall_n = 0
    for i in range(args.queries):
        d = qn[i] - 2 * queries[i] @ corpus.T + cn[0]
        gt = set(np.argpartition(d, args.k)[: args.k].tolist())
        recall_n += len(gt & set(hits_by_query[i]))
    recall = recall_n / (args.queries * args.k)

    # ---- served load: concurrent closed-loop clients ----------------------
    # VERDICT r2 item 6: does the dynamic query batcher
    # (runtime/query_batcher.py) actually coalesce under load and hold the
    # latency envelope? N threads hammer gRPC Search back-to-back; the
    # batcher stats report achieved batch sizes. Reference serving claim:
    # README.md:34 / benchmark_sift.go:38-57.
    served = {}
    # --null-device: swap every live query batcher's batch_fn for a
    # constant-time stub. What remains is the serving FABRIC — gRPC
    # parse, batcher queueing, coalescing, reply build — i.e. the part
    # of p50 that is NOT the device. Served p50 ~= fabric p50 + the
    # chained device ms from bench.py.
    if args.null_device and server is not None:
        import numpy as _np

        def _null_batch(queries, k, allow=None):
            b = len(queries)
            return (_np.zeros((b, k), dtype=_np.int64),
                    _np.zeros((b, k), dtype=_np.float32))

        query(queries[0])  # force batcher construction
        for col in server.db.collections.values():
            for shard in col.shards.values():
                for b_ in shard._query_batchers.values():
                    b_._batch_fn = _null_batch
                    b_._async_fn = None  # null device = sync null path
                if args.native_plane:
                    _cid = _np.tile(_np.arange(args.k, dtype=_np.int64),
                                    (256, 1))
                    _cd = _np.tile(_np.linspace(0.01, 0.1, args.k,
                                                dtype=_np.float32), (256, 1))
                    _cn = _np.full(256, args.k, _np.int64)

                    def _null_batch2(qs, k, vec_name="", _i=_cid, _d=_cd,
                                     _n=_cn):
                        b = len(qs)
                        return _i[:b, :k], _d[:b, :k], _n[:b]

                    shard.vector_search_batch = _null_batch2
                    # the pipelined plane tries the async twin first —
                    # null it so the patched sync path is taken
                    shard.vector_search_batch_async = (
                        lambda qs, k, vec_name="": None)
    stream_counts = [int(c) for c in str(args.concurrency).split(",")
                     if int(c) > 0]
    if args.native_plane and server is not None and not hasattr(
            server.grpc, "dp"):
        # the plane silently fell back to the Python server (no
        # libnghttp2 / auth configured) — measure that honestly instead
        log("WARNING: native plane not active; using Python load gen")
        args.native_plane = False
    if args.native_plane and stream_counts:
        # native load generator: with one core a Python client saturates
        # long before the C++ plane does
        from weaviate_tpu.native import dataplane as dpn

        head = pb.SearchRequest(collection="Bench", limit=args.k,
                                uses_123_api=True)
        head.metadata.uuid = True
        head.metadata.distance = True
        hb = head.SerializeToString()
        for n_streams in stream_counts:
            conns = max(1, min(16, n_streams // 4))
            per = max(1, n_streams // conns)
            f0, b0 = server.grpc.dp.stats() if server is not None else (0, 0)
            st = dpn.bench(grpc_port, conns=conns, streams=per,
                           duration_ms=8000, dim=args.dim, request_head=hb)
            f1, b1 = server.grpc.dp.stats() if server is not None else (0, 0)
            point = {"streams": conns * per,
                     "served_qps": round(st["qps"], 1),
                     "p50_ms": round(st["p50_ms"], 2),
                     "p95_ms": round(st["p95_ms"], 2),
                     "fast_path": f1 - f0, "fallback": b1 - b0,
                     "errors": st["errors"]}
            log(f"served load (native, {conns}x{per} streams): "
                f"{point['served_qps']} qps, p50 {point['p50_ms']} ms, "
                f"p95 {point['p95_ms']} ms, fast {point['fast_path']} "
                f"fallback {point['fallback']}")
            served = point if len(stream_counts) == 1 else {
                **({} if not isinstance(served, dict) else served),
                str(conns * per): point}
        stream_counts = []
    for n_streams in stream_counts:
        import threading

        qpool = rng.standard_normal(
            (args.load_queries, args.dim)).astype(np.float32)
        lat_lock = threading.Lock()
        load_lat = []
        cursor = [0]

        def worker():
            while True:
                with lat_lock:
                    i = cursor[0]
                    if i >= args.load_queries:
                        return
                    cursor[0] += 1
                t0 = time.perf_counter()
                query(qpool[i])
                dt = time.perf_counter() - t0
                with lat_lock:
                    load_lat.append(dt)

        # batcher stats before/after (in-process mode only)
        batchers = []
        if server is not None:
            for col in server.db.collections.values():
                for shard in col.shards.values():
                    batchers.extend(shard._query_batchers.values())
        before = [(b.dispatches, b.batched_queries) for b in batchers]
        threads = [threading.Thread(target=worker)
                   for _ in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        ll = np.asarray(load_lat) if load_lat else np.asarray([0.0])
        point = {
            "streams": n_streams,
            "served_qps": round(args.load_queries / wall, 1),
            "p50_ms": round(float(np.percentile(ll, 50)) * 1e3, 2),
            "p95_ms": round(float(np.percentile(ll, 95)) * 1e3, 2),
        }
        if server is not None:
            batchers = []
            for col in server.db.collections.values():
                for shard in col.shards.values():
                    batchers.extend(shard._query_batchers.values())
            disp = sum(b.dispatches for b in batchers) - sum(
                d for d, _ in before)
            bq = sum(b.batched_queries for b in batchers) - sum(
                q for _, q in before)
            if disp:
                point["dispatches"] = disp
                point["avg_batch"] = round(bq / disp, 2)
        log(f"served load ({n_streams} streams): "
            f"{point['served_qps']} qps, p50 {point['p50_ms']} ms, "
            f"p95 {point['p95_ms']} ms, avg batch "
            f"{point.get('avg_batch', 'n/a')}")
        served = point if len(stream_counts) == 1 else {
            **({} if not isinstance(served, dict) else served),
            str(n_streams): point}

    print(json.dumps({
        "metric": "e2e_server_knn",
        "n": args.n, "dim": args.dim, "k": args.k,
        "import_objects_per_s": round(args.n / import_s, 1),
        "import_success_rate": round(success_rate, 4),
        "query_p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
        "query_p95_ms": round(float(np.percentile(lat, 95)) * 1e3, 2),
        "qps_single_stream": round(1.0 / float(np.median(lat)), 1),
        "recall_at_k": round(recall, 4),
        "served_load": served,
    }), flush=True)

    chan.close()
    if server is not None:
        server.stop()


if __name__ == "__main__":
    main()
