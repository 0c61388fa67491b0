"""Chip smoke: the served path, once, on the TPU, through the entry points a
user calls.

    python chip_smoke.py              # one chip: phases P1-P5 below
    python chip_smoke.py --chips 4    # four chips: a collection's shards
                                      # on chips of their own (P7), then
                                      # the mesh-sharded phase (P6)

Starts a real ``weaviate_tpu.server.Server`` in this process on loopback
ports (the pattern of benchmarks/, which mirrors the reference's
test/benchmark/benchmark_sift.go) with a fresh temp data dir and seeded
data, and talks to it over sockets only: schema + deletes + reads over
REST, import + search over gRPC, hybrid over GraphQL. Each phase checks its
answers against a plain numpy reference on the same data and prints one
JSON line; any failed check raises, so the run exits non-zero. The last
line on success is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

This is the only process that touches JAX. Without a TPU it exits non-zero
at the device check; ``--rehearse`` (with ``--rows``) lets the CPU walk the
same code at a tiny size and can never print the success line. The numbers
printed here are smoke observations, not benchmark results.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import threading
import time
import uuid as uuid_mod
from concurrent.futures import ThreadPoolExecutor

import numpy as np

K = 10
SIFT_ROWS = 1_000_000   # SIFT1M shape (BASELINE.json config 1)
SIFT_DIM = 128
ADA_ROWS = 131_072
ADA_DIM = 768
PASSAGE_ROWS = 65_536
VOCAB = 20_000
TOKENS_PER_DOC = 30
N_CLIENTS = 8


def emit(**obj) -> None:
    print(json.dumps(obj), flush=True)


def check(cond, what: str, **ctx) -> None:
    if not cond:
        raise AssertionError(f"{what} {ctx}" if ctx else what)


def report(phase: str, problems: list[str], **obs) -> None:
    """One JSON line per phase: its observations and whether its checks
    passed; a phase with problems then fails the run."""
    emit(phase=phase, ok=not problems, **obs,
         **({"problems": problems} if problems else {}))
    check(not problems, f"{phase}: " + "; ".join(problems))


# -- seeded data --------------------------------------------------------------


def clustered(rng, n: int, dim: int, centers=None, spread: float = 0.35,
              members: int = 8):
    """Mixture of gaussians as ``benchmarks/datagen/clustered.py`` makes it
    (real embeddings cluster; i.i.d. gaussian is the adversarial floor for
    the compressed phase): ``n // members`` centers, at most 65536.
    Returns (rows, centers)."""
    if centers is None:
        n_clusters = min(65536, max(16, n // members))
        centers = rng.standard_normal((n_clusters, dim)).astype(np.float32)
    assign = rng.integers(0, len(centers), n)
    rows = centers[assign] + spread * rng.standard_normal(
        (n, dim)).astype(np.float32)
    return rows.astype(np.float32), centers


def obj_uuid(i: int) -> str:
    return str(uuid_mod.UUID(int=i + 1))


def row_of(uid: str) -> int:
    return uuid_mod.UUID(uid).int - 1


def exact_topk(queries, corpus, k, allow=None):
    """Plain numpy l2-squared top-k: [Q, k] row ids. ``allow`` [Q, N] or
    [N] bool restricts candidates."""
    d = ((queries ** 2).sum(-1)[:, None] - 2.0 * queries @ corpus.T
         + (corpus ** 2).sum(-1)[None, :])
    if allow is not None:
        d = np.where(allow, d, np.inf)
    part = np.argpartition(d, k, axis=1)[:, :k]
    order = np.argsort(np.take_along_axis(d, part, axis=1), axis=1)
    return np.take_along_axis(part, order, axis=1)


def recall(found: list[list[int]], truth) -> float:
    hit = sum(len(set(f) & set(t.tolist())) for f, t in zip(found, truth))
    return hit / float(len(found) * truth.shape[1])


# -- the socket clients -------------------------------------------------------


class Wire:
    """REST + gRPC clients of one in-process server."""

    def __init__(self, server):
        import grpc

        from weaviate_tpu.api.client import Client
        from weaviate_tpu.api.grpc import v1_pb2 as pb
        from weaviate_tpu.api.grpc.server import _SERVICE

        self.pb = pb
        self.rest_addr = server.rest.address
        self.rest = Client(self.rest_addr, timeout=600.0)
        self.chan = grpc.insecure_channel(
            f"127.0.0.1:{server.grpc.port}",
            options=[("grpc.max_send_message_length", 64 << 20),
                     ("grpc.max_receive_message_length", 64 << 20)])
        self._batch = self.chan.unary_unary(
            f"/{_SERVICE}/BatchObjects",
            request_serializer=pb.BatchObjectsRequest.SerializeToString,
            response_deserializer=pb.BatchObjectsReply.FromString)
        self._search = self.chan.unary_unary(
            f"/{_SERVICE}/Search",
            request_serializer=pb.SearchRequest.SerializeToString,
            response_deserializer=pb.SearchReply.FromString)

    def close(self):
        self.chan.close()

    def import_rows(self, collection, vectors, props, batch):
        """gRPC BatchObjects import over one stream (the shard lock
        serialises writers; four streams measured no faster); row i gets
        ``obj_uuid(i)``. Returns (seconds, objects/s). Every object must
        be acknowledged."""
        n = len(props)
        if vectors is not None:
            vectors = np.ascontiguousarray(vectors, dtype="<f4")
        t0 = time.perf_counter()
        for start in range(0, n, batch):
            req = self.pb.BatchObjectsRequest()
            for i in range(start, min(start + batch, n)):
                bo = req.objects.add(collection=collection, uuid=obj_uuid(i))
                if vectors is not None:
                    bo.vector_bytes = vectors[i].tobytes()
                bo.properties.non_ref_properties.update(props[i])
            reply = self._batch(req)
            check(len(reply.errors) == 0, "import errors", start=start,
                  first=str(reply.errors[:1]))
        dt = time.perf_counter() - t0
        return dt, n / dt

    def search(self, collection, vec, k=K, bucket_lt=None):
        """nearVector over gRPC Search -> (row ids, distances)."""
        req = self.pb.SearchRequest(collection=collection, limit=k,
                                    uses_123_api=True)
        req.near_vector.vector_bytes = vec.astype("<f4").tobytes()
        req.metadata.uuid = True
        req.metadata.distance = True
        if bucket_lt is not None:
            req.filters.operator = self.pb.Filters.OPERATOR_LESS_THAN
            req.filters.target.property = "bucket"
            req.filters.value_int = int(bucket_lt)
        reply = self._search(req)
        return ([row_of(r.metadata.id) for r in reply.results],
                [r.metadata.distance for r in reply.results])

    def counters(self) -> dict:
        """The counters the smoke reads, scraped from GET /v1/metrics."""
        import http.client

        host, _, port = self.rest_addr.partition(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=60)
        try:
            conn.request("GET", "/v1/metrics")
            text = conn.getresponse().read().decode()
        finally:
            conn.close()
        want = {
            "filtered_batched":
                "weaviate_tpu_query_batcher_filtered_batched_total",
            "hybrid_batched":
                "weaviate_tpu_query_batcher_hybrid_batched_total",
            "dispatches": "weaviate_tpu_query_batcher_compile_bucket_total",
            "cache_hit":
                'weaviate_tpu_compile_cache_events_total{event="hit"}',
            "cache_miss":
                'weaviate_tpu_compile_cache_events_total{event="miss"}',
        }
        out = dict.fromkeys(want, 0)
        for line in text.splitlines():
            for key, name in want.items():
                if line.startswith(name + " ") or (
                        "{" not in name and line.startswith(name + "{")):
                    out[key] += int(float(line.rsplit(" ", 1)[1]))
        return out


def corpus_placement(server, collection: str) -> dict:
    """Where the collection's corpus array lives (in-process handle)."""
    shard = next(iter(server.db.collections[collection].shards.values()))
    store = shard.vector_indexes[""].store
    arr = getattr(store, "codes", None)
    if arr is None:
        arr = store.vectors
    return {"array": "codes" if hasattr(store, "codes") else "vectors",
            "shape": list(arr.shape), "dtype": str(arr.dtype),
            "devices": sorted(str(d) for d in arr.sharding.device_set)}


def concurrent_filtered(wire, collection, queries, bounds):
    """One filtered nearVector per client, released together."""
    gate = threading.Barrier(len(bounds))

    def one(c):
        gate.wait()
        return wire.search(collection, queries[c], bucket_lt=bounds[c])

    with ThreadPoolExecutor(len(bounds)) as pool:
        return list(pool.map(one, range(len(bounds))))


def filtered_phase(wire, name, collection, corpus, buckets, queries,
                   recall_floor, dist_rtol):
    """8 concurrent differently-filtered clients (P2 / P5). Each answer,
    coalesced and issued alone: k ids that satisfy the client's own filter,
    each with the distance numpy gives that id (an answer routed to the
    wrong request cannot pass), recall vs numpy masked exact. The two
    agree on the distance of every id they share and on >= 90 % of ids:
    a lone 10 % filter takes the store's gathered program and a coalesced
    one the bitmask program, and under the default ``approx`` selection
    two programs may keep different near-ties on the TPU (on the CPU
    ``approx`` lowers to exact and the two are identical). The batcher's
    filtered_batched counter must have moved."""
    t0 = time.perf_counter()
    bounds = [10 + c for c in range(N_CLIENTS)]  # ~10 %, a mask per client
    before = wire.counters()
    t_first = time.perf_counter()
    together = concurrent_filtered(wire, collection, queries, bounds)
    first_s = time.perf_counter() - t_first
    after = wire.counters()
    alone = [wire.search(collection, queries[c], bucket_lt=bounds[c])
             for c in range(N_CLIENTS)]
    allow = buckets[None, :] < np.asarray(bounds)[:, None]
    truth = exact_topk(queries[:N_CLIENTS], corpus, K, allow)
    shared = 0
    for c in range(N_CLIENTS):
        for how, (ids, dists) in (("coalesced", together[c]),
                                  ("alone", alone[c])):
            check(len(ids) == K and len(set(ids)) == K,
                  f"{name}: short or repeated result", client=c, how=how)
            check(all(buckets[i] < bounds[c] for i in ids),
                  f"{name}: id outside its filter", client=c, how=how)
            exact = ((queries[c][None, :] - corpus[ids]) ** 2).sum(-1)
            check(np.allclose(dists, exact, rtol=dist_rtol, atol=1e-2),
                  f"{name}: a returned distance is not that id's distance",
                  client=c, how=how, got=dists, exact=exact.tolist())
        both = set(together[c][0]) & set(alone[c][0])
        shared += len(both)
        d_tog = dict(zip(*together[c]))
        d_alone = dict(zip(*alone[c]))
        check(np.allclose([d_tog[i] for i in both],
                          [d_alone[i] for i in both], rtol=1e-5, atol=1e-4),
              f"{name}: coalesced and solo distances differ", client=c)
    agreement = shared / float(N_CLIENTS * K)
    rec = recall([t[0] for t in together], truth)
    rec_alone = recall([a[0] for a in alone], truth)
    batched = after["filtered_batched"] - before["filtered_batched"]
    problems = []
    if batched <= 0:
        problems.append("filtered_batched did not move")
    if min(rec, rec_alone) < recall_floor:
        problems.append("recall below floor")
    if agreement < 0.9:
        problems.append("coalesced and solo answers disagree")
    report(name, problems, clients=N_CLIENTS, recall_at_10=rec,
           recall_at_10_alone=rec_alone, recall_floor=recall_floor,
           coalesced_vs_alone_id_agreement=agreement,
           filtered_batched=batched,
           dispatches=after["dispatches"] - before["dispatches"],
           first_answer_seconds=first_s, seconds=time.perf_counter() - t0)


# -- one chip: P1-P5 through the server ---------------------------------------


def run_served(args, rng) -> None:
    from weaviate_tpu.config import ServerConfig
    from weaviate_tpu.runtime.compile_cache import cache_dir
    from weaviate_tpu.server import Server

    data_dir = tempfile.mkdtemp(prefix="chip-smoke-")
    server = Server(ServerConfig(data_path=data_dir, rest_port=0, grpc_port=0,
                                 disable_telemetry=True)).start()
    wire = Wire(server)
    try:
        p1_to_p3(args, rng, server, wire)
        p4_hybrid(args, rng, server, wire)
        p5_compressed(args, rng, server, wire)
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        c = wire.counters()
        emit(phase="totals", ok=True,
             peak_bytes_in_use=stats.get("peak_bytes_in_use"),
             compile_cache_dir=cache_dir(),
             compile_cache_hits=c["cache_hit"],
             compile_cache_misses=c["cache_miss"])
    finally:
        wire.close()
        server.stop()
        shutil.rmtree(data_dir, ignore_errors=True)


def p1_to_p3(args, rng, server, wire) -> None:
    n = args.rows or SIFT_ROWS
    corpus, centers = clustered(rng, n, SIFT_DIM)
    buckets = rng.integers(0, 100, n)
    queries, _ = clustered(rng, 64, SIFT_DIM, centers)

    # P1: flat, the main path
    t0 = time.perf_counter()
    wire.rest.create_class({
        "class": "Sift", "vectorIndexType": "flat",
        "vectorIndexConfig": {"distance": "l2-squared"},
        "properties": [{"name": "bucket", "dataType": ["int"]}]})
    import_s, rate = wire.import_rows(
        "Sift", corpus, [{"bucket": int(b)} for b in buckets], batch=4096)
    t1 = time.perf_counter()
    first = wire.search("Sift", queries[0])[0]
    first_s = time.perf_counter() - t1
    found = [first] + [wire.search("Sift", q)[0] for q in queries[1:]]
    truth = exact_topk(queries, corpus, K)
    rec = recall(found, truth)
    problems = []
    if not all(len(f) == K for f in found):
        problems.append("short result")
    if rec < 0.98:
        problems.append("recall below 0.98")
    report("P1_flat", problems, rows=n, dim=SIFT_DIM, queries=64,
           import_seconds=import_s, import_objects_per_s=rate,
           first_answer_seconds=first_s, recall_at_10=rec,
           corpus=corpus_placement(server, "Sift"),
           seconds=time.perf_counter() - t0)

    # P2: filtered, coalesced
    filtered_phase(wire, "P2_filtered", "Sift", corpus, buckets, queries,
                   recall_floor=0.98, dist_rtol=1e-3)

    # P3: delete
    t0 = time.perf_counter()
    n_del = min(1000, n // 8)
    gone = np.zeros(n, bool)
    gone[truth[:16, :4].ravel()] = True  # answers the queries gave
    perm = rng.permutation(n)
    gone[perm[~gone[perm]][:n_del - int(gone.sum())]] = True
    doomed = [int(i) for i in np.flatnonzero(gone)]
    for i in doomed:
        wire.rest.delete_object("Sift", obj_uuid(i))
    again = [wire.search("Sift", q)[0] for q in queries[:16]]
    check(not any(gone[i] for f in again for i in f),
          "P3: a deleted id came back")
    rec3 = recall(again, exact_topk(queries[:16], corpus, K, ~gone))
    from weaviate_tpu.api.client import RestError

    kept = [int(i) for i in perm[~gone[perm]][:32]]
    for i in kept:  # acknowledged objects read back by id
        obj = wire.rest.get_object("Sift", obj_uuid(i))
        check(obj["properties"]["bucket"] == int(buckets[i]),
              "P3: property read back differs", row=i)
        check(np.allclose(obj["vector"], corpus[i]),
              "P3: vector read back differs", row=i)
    for i in doomed[:8]:
        try:
            wire.rest.get_object("Sift", obj_uuid(i))
        except RestError as e:
            check(e.status == 404, "P3: deleted object not a 404", row=i)
        else:
            raise AssertionError(f"P3: deleted object {i} still readable")
    report("P3_delete",
           ["recall below 0.98 after deletes"] if rec3 < 0.98 else [],
           deleted=len(doomed), queries=16, recall_at_10=rec3,
           read_back=len(kept), seconds=time.perf_counter() - t0)


def p4_hybrid(args, rng, server, wire) -> None:
    t0 = time.perf_counter()
    n = max(2048, args.rows // 16) if args.rows else PASSAGE_ROWS
    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    vocab = sorted({"".join(rng.choice(letters, rng.integers(4, 10)))
                    for _ in range(VOCAB + VOCAB // 4)})[:VOCAB]
    vocab = [vocab[i] for i in rng.permutation(len(vocab))]
    p = 1.0 / np.arange(1, len(vocab) + 1)
    # 10..50 tokens, 30 on average: with one fixed length BM25's length
    # norm is constant, hundreds of docs tie at the best sparse score, and
    # which 100 of them make a leg's over-fetch is arbitrary on either path
    lengths = rng.integers(TOKENS_PER_DOC - 20, TOKENS_PER_DOC + 21, n)
    tokens = rng.choice(len(vocab), size=(n, TOKENS_PER_DOC + 20),
                        p=p / p.sum())
    texts = [" ".join(vocab[t] for t in row[:m])
             for row, m in zip(tokens, lengths)]
    wire.rest.create_class({
        "class": "Passages", "vectorIndexType": "flat",
        "vectorizer": "text2vec-bigram",
        "moduleConfig": {"text2vec-bigram": {"vectorizeClassName": False}},
        "properties": [{"name": "text", "dataType": ["text"]}]})
    import_s, rate = wire.import_rows(
        "Passages", None, [{"text": t} for t in texts], batch=1024)
    shards = list(server.db.collections["Passages"].shards.values())
    dim = shards[0].vector_indexes[""].dim
    # mid-frequency words: every query has sparse matches, none matches all
    words = rng.integers(20, min(2000, len(vocab)), size=(16, 3))
    gql = ('{ Get { Passages(limit: %d, hybrid: {query: "%s", alpha: 0.5, '
           'fusionType: relativeScoreFusion}) { _additional { id score } } } }')

    def ask(q: int) -> dict:
        body = wire.rest.graphql(
            gql % (K, " ".join(vocab[w] for w in words[q])))
        check(not body.get("errors"), "P4: graphql errors",
              errors=body.get("errors"))
        return {row_of(r["_additional"]["id"]): float(r["_additional"]["score"])
                for r in body["data"]["Get"]["Passages"]}

    before = wire.counters()
    t1 = time.perf_counter()
    first = ask(0)
    first_s = time.perf_counter() - t1
    with ThreadPoolExecutor(4) as pool:
        device = [first] + list(pool.map(ask, range(1, 16)))
    after = wire.counters()
    for s in shards:   # the host reference path, same public surface
        s.device_hybrid = False
    try:
        host = [ask(q) for q in range(16)]
    finally:
        for s in shards:
            s.device_hybrid = True
    ties = 0
    disagreements = []
    for q, (dev, ref) in enumerate(zip(device, host)):
        check(len(dev) == K and len(ref) == K, "P4: short result", query=q)
        kth = min(ref.values())
        for i in set(dev) ^ set(ref):  # only k-th-place ties may differ
            score = dev.get(i, ref.get(i))
            if abs(score - kth) <= 1e-5:
                ties += 1
            else:
                disagreements.append(
                    {"query": q, "id": i, "score": score, "kth": kth,
                     "in": "device" if i in dev else "host"})
        for i in set(dev) & set(ref):
            if abs(dev[i] - ref[i]) > 1e-5:
                disagreements.append({"query": q, "id": i, "device": dev[i],
                                      "host": ref[i]})
    batched = after["hybrid_batched"] - before["hybrid_batched"]
    problems = [] if batched > 0 else ["hybrid_batched did not move"]
    if disagreements:
        problems.append(f"device and host answers differ: {disagreements}")
    report("P4_hybrid", problems, rows=n, dim=dim, queries=16,
           import_seconds=import_s, import_objects_per_s=rate,
           first_answer_seconds=first_s,
           kth_tie_swaps=ties, hybrid_batched=batched,
           corpus=corpus_placement(server, "Passages"),
           seconds=time.perf_counter() - t0)


def p5_compressed(args, rng, server, wire) -> None:
    n = max(4096, args.rows // 8) if args.rows else ADA_ROWS
    # 128 members per cluster, not the default 8: a 10 % filter that is
    # independent of the clusters must leave a neighbourhood (~13 rows)
    # behind. With 8, under one member survives the filter, the masked
    # top-10 is other clusters' rows at near-equal distances — the i.i.d.
    # floor again — and no sign-bit code can rank them (see CHANGES.md).
    corpus, centers = clustered(rng, n, ADA_DIM, members=128)
    buckets = rng.integers(0, 100, n)
    queries, _ = clustered(rng, N_CLIENTS, ADA_DIM, centers)
    wire.rest.create_class({
        "class": "Ada", "vectorIndexType": "flat",
        "vectorIndexConfig": {"distance": "l2-squared",
                              "bq": {"enabled": True}},
        "properties": [{"name": "bucket", "dataType": ["int"]}]})
    import_s, rate = wire.import_rows(
        "Ada", corpus, [{"bucket": int(b)} for b in buckets], batch=1024)
    emit(phase="P5_import", ok=True, rows=n, dim=ADA_DIM,
         import_seconds=import_s, import_objects_per_s=rate,
         corpus=corpus_placement(server, "Ada"))
    # a sanity floor for the store's default rescoring, not a target
    filtered_phase(wire, "P5_compressed_filtered", "Ada", corpus, buckets,
                   queries, recall_floor=0.80, dist_rtol=2e-2)


# -- four chips: the sharded phase and its comparison, nothing else -----------


def run_sharded(args, rng) -> None:
    import jax

    from weaviate_tpu.db.database import Database
    from weaviate_tpu.parallel.mesh import default_mesh
    from weaviate_tpu.runtime.compile_cache import (cache_dir,
                                                    ensure_compile_cache)
    from weaviate_tpu.schema.config import (CollectionConfig, Property,
                                            VectorConfig, VectorIndexConfig)

    ensure_compile_cache()
    n = args.rows or SIFT_ROWS
    corpus, centers = clustered(rng, n, SIFT_DIM)
    queries, _ = clustered(rng, 64, SIFT_DIM, centers)
    truth = exact_topk(queries, corpus, K)
    mesh = default_mesh()
    check(mesh is not None and mesh.devices.size == 4,
          "--chips 4 needs a mesh over four devices",
          devices=len(jax.devices()))
    answers = {}
    for label, m in (("sharded", mesh), ("single", None)):
        t0 = time.perf_counter()
        data_dir = tempfile.mkdtemp(prefix=f"chip-smoke-{label}-")
        db = Database(data_dir, mesh=m)
        try:
            col = db.create_collection(CollectionConfig(
                name="Sift",
                properties=[Property(name="bucket", data_type="int")],
                vectors=[VectorConfig(dim=SIFT_DIM, index=VectorIndexConfig(
                    index_type="flat", metric="l2-squared"))]))
            t1 = time.perf_counter()
            for s in range(0, n, 8192):
                res = col.batch_put([
                    {"uuid": obj_uuid(i), "properties": {"bucket": i % 100},
                     "vector": corpus[i]}
                    for i in range(s, min(s + 8192, n))])
                check(all(r["status"] == "SUCCESS" for r in res),
                      "import errors", start=s)
            import_s = time.perf_counter() - t1
            t1 = time.perf_counter()
            out = [col.near_vector(q, k=K) for q in queries]
            query_s = time.perf_counter() - t1
            answers[label] = [{row_of(r.uuid): float(r.distance) for r in o}
                              for o in out]
            rec = recall([list(a) for a in answers[label]], truth)
            store = next(iter(col.shards.values())).vector_indexes[""].store
            arr, valid = store.vectors, store.valid
            place = {"devices": sorted(str(d) for d in arr.sharding.device_set),
                     "shard_rows": [int(s.data.shape[0])
                                    for s in arr.addressable_shards],
                     "shard_live_rows": [int(np.asarray(s.data).sum())
                                         for s in valid.addressable_shards]}
            problems = []
            if rec < 0.98:
                problems.append("recall below 0.98")
            if len(place["devices"]) != (4 if m is not None else 1):
                problems.append("corpus on the wrong number of devices")
            if m is not None:
                if any(r != arr.shape[0] // 4 for r in place["shard_rows"]):
                    problems.append(
                        "a shard does not hold a quarter of the rows")
                live = place["shard_live_rows"]
                if min(live) == 0 or sum(live) != n:
                    problems.append(
                        "live rows are not spread over the devices")
            report(f"P6_{label}", problems, rows=n, dim=SIFT_DIM, queries=64,
                   import_seconds=import_s,
                   import_objects_per_s=n / import_s, query_seconds=query_s,
                   recall_at_10=rec, corpus=place,
                   compile_cache_dir=cache_dir(),
                   seconds=time.perf_counter() - t0)
        finally:
            db.close()
            shutil.rmtree(data_dir, ignore_errors=True)
    # per-shard approx selection may pick different near-ties: agreement,
    # not identity
    shared = total = 0
    for a, b in zip(answers["sharded"], answers["single"]):
        both = set(a) & set(b)
        shared += len(both)
        total += K
        check(np.allclose([a[i] for i in both], [b[i] for i in both],
                          rtol=1e-4, atol=1e-3),
              "sharded and single-device distances differ")
    report("P6_agreement",
           [] if shared / total >= 0.98 else ["id agreement below 0.98"],
           id_agreement=shared / total)


def run_placed(args, rng) -> None:
    """P7: an eight-shard collection through the served path on a host
    of several chips: the program spreads the shards evenly over the
    local devices (runtime/placement.py), every shard's arrays lie on
    its device, the answers are the union's exact top k and
    ``/v1/nodes`` names each shard's device. The smoke to run FIRST on
    four chips."""
    import jax

    from weaviate_tpu.config import ServerConfig
    from weaviate_tpu.runtime import placement
    from weaviate_tpu.server import Server

    n = min(args.rows or PASSAGE_ROWS, PASSAGE_ROWS)
    corpus, centers = clustered(rng, n, ADA_DIM)
    queries, _ = clustered(rng, 64, ADA_DIM, centers)
    data_dir = tempfile.mkdtemp(prefix="chip-smoke-placed-")
    t0 = time.perf_counter()
    server = Server(ServerConfig(data_path=data_dir, rest_port=0, grpc_port=0,
                                 disable_telemetry=True)).start()
    wire = Wire(server)
    try:
        wire.rest.create_class({
            "class": "Passages", "vectorIndexType": "flat",
            "vectorIndexConfig": {"distance": "cosine"},
            "shardingConfig": {"desiredCount": 8},
            "properties": [{"name": "bucket", "dataType": ["int"]}]})
        import_s, rate = wire.import_rows(
            "Passages", corpus, [{"bucket": i % 100} for i in range(n)],
            batch=1024)
        found, dists = zip(*[wire.search("Passages", q) for q in queries])
        # eight clients at once: the fan-out's programs on every chip
        with ThreadPoolExecutor(N_CLIENTS) as pool:
            again = list(pool.map(
                lambda q: wire.search("Passages", q)[0], queries))
        unit = corpus / np.linalg.norm(corpus, axis=1, keepdims=True)
        qunit = queries / np.linalg.norm(queries, axis=1, keepdims=True)
        exact = 1.0 - qunit.astype(np.float64) @ unit.astype(np.float64).T
        truth = np.argsort(exact, axis=1, kind="stable")[:, :K]
        rec = recall(list(found), truth)
        worst = max(abs(d - exact[q, i]) for q in range(len(queries))
                    for i, d in zip(found[q], dists[q]))
        shards = server.db.collections["Passages"].shards
        held: dict[str, list[str]] = {}
        stray = []
        for name, shard in sorted(shards.items()):
            held.setdefault(placement.label(shard.device), []).append(name)
            store = shard.vector_indexes[""].store
            for attr, arr in vars(store).items():
                if isinstance(arr, jax.Array) and \
                        arr.devices() != {shard.device}:
                    stray.append(f"{name}.{attr}")
        nodes = wire.rest.request("GET", "/v1/nodes", {"output": "verbose"})
        named = {d["name"]: d.get("device")
                 for d in nodes["nodes"][0].get("shards", [])
                 if d.get("class") == "Passages"}
        local = len(jax.local_devices())
        problems = []
        if not all(len(f) == K for f in found):
            problems.append("short result")
        if rec < 0.99:   # the configuration's limit (approx selection)
            problems.append("recall below 0.99")
        if worst > 1e-4:
            problems.append("a distance is off by more than 1e-4")
        if [sorted(f) for f in again] != [sorted(f) for f in found]:
            problems.append("concurrent answers differ from serial ones")
        if len(held) != min(local, 8) or \
                {len(v) for v in held.values()} != {8 // min(local, 8)}:
            problems.append("shards are not spread evenly over the devices")
        if stray:
            problems.append("an array is not on its shard's device")
        if named != {name: placement.label(s.device)
                     for name, s in shards.items()}:
            problems.append("/v1/nodes does not name the shards' devices")
        report("P7_placed", problems, rows=n, dim=ADA_DIM, queries=64,
               shards_by_device=held, local_devices=local,
               import_seconds=import_s, import_objects_per_s=rate,
               recall_at_10=rec, distance_error_max=float(worst),
               stray_arrays=stray, dispatches=wire.counters()["dispatches"],
               seconds=time.perf_counter() - t0)
    finally:
        wire.close()
        server.stop()
        shutil.rmtree(data_dir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: a collection's shards a chip each, then the "
                         "mesh-sharded phase and its comparison")
    ap.add_argument("--rows", type=int, default=0,
                    help="rehearsal: rows of the main collection (the "
                         "others scale with it); default is the full size")
    ap.add_argument("--rehearse", action="store_true",
                    help="rehearsal: walk the code on whatever backend JAX "
                         "finds; never prints the success line off a TPU")
    args = ap.parse_args()

    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{device['platform']!r}); nothing was run", file=sys.stderr)
        return 2
    check(device["count"] == args.chips or not on_chip,
          "device count does not match --chips", **device)

    from weaviate_tpu import native
    from weaviate_tpu.native import dataplane

    libs = {"weaviate_native": native.available(),
            "wvdataplane": dataplane.available()}
    emit(phase="start", ok=True, jax=jax.__version__, device=device,
         seed=args.seed, native=libs, gxx=shutil.which("g++"))
    if shutil.which("g++"):
        # same toolchain as where these build: a silent numpy/Python
        # fallback here would be a different program
        check(all(libs.values()), "a native library did not build", **libs)

    rng = np.random.default_rng(args.seed)
    if args.chips == 4:
        run_placed(args, rng)
        run_sharded(args, rng)
    else:
        run_served(args, rng)
    total_s = time.perf_counter() - t_start
    if not on_chip:
        emit(ok=False, rehearsal=True, checks_passed=True, device=device,
             seconds=total_s)
        return 0
    emit(phase="done", ok=True, seconds=total_s, rows=args.rows or None)
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
