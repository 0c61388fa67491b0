"""The gRPC Search reply as ``_fill_result`` builds it, a result at a
time: the oracle the native reply encoder is held to
(tests/test_reply_encoder.py), and the served drive both sides of the
``WEAVIATE_TPU_NO_NATIVE`` comparison run (``python tests/reply_reference.py
<dir>`` prints its answers as JSON)."""

from __future__ import annotations

import json
import sys
import time
import types

import numpy as np

from weaviate_tpu.api.grpc import server as grpc_server
from weaviate_tpu.api.grpc import v1_pb2 as pb
from weaviate_tpu.db.collection import SearchResult
from weaviate_tpu.schema.config import Property
from weaviate_tpu.storage.objects import StorageObject


def collection(props: dict[str, str], name: str = "Doc"):
    """What the two encoders read of a collection: its name and its
    properties' types."""
    return types.SimpleNamespace(config=types.SimpleNamespace(
        name=name, properties=[Property(name=n, data_type=t)
                               for n, t in props.items()]))


def stored(i: int, properties: dict, vectors: dict | None = None,
           created: int = 1_700_000_000_000) -> bytes:
    return StorageObject(
        uuid=f"00000000-0000-4000-8000-{i:012x}", doc_id=i,
        properties=properties, vectors=vectors or {},
        creation_time_ms=created + i,
        last_update_time_ms=created + 2 * i + 1).to_bytes()


def hits(frames, distances=None, scores=None) -> list[SearchResult]:
    """Results as ``Collection._attach_objects`` leaves them: a frame a
    hit, None for one whose object has gone since the search."""
    return [SearchResult(
        uuid=f"00000000-0000-4000-8000-{i:012x}", frame=frame,
        distance=None if distances is None else distances[i],
        score=None if scores is None else scores[i])
        for i, frame in enumerate(frames)]


def _parts(req):
    return (req.metadata if req.HasField("metadata") else None,
            req.properties if req.HasField("properties") else None)


def python_reply(col, results, req) -> "pb.SearchReply":
    """The tail of ``GrpcServer._search`` for a plain Search, as it was
    before the native encoder and as it still runs wherever that one
    declines: ``took`` left unset."""
    meta_req, props_req = _parts(req)
    dtype_of = {p.name: p.data_type for p in col.config.properties}
    reply = pb.SearchReply()
    for r in results:
        if r.object is None:
            continue
        grpc_server.GrpcServer._fill_result(
            None, col, reply.results.add(), r.object, r, meta_req,
            props_req, dtype_of)
    return reply


def native_reply(col, results, req):
    """-> (the parsed reply with ``took`` cleared, "") or (None, why)."""
    meta_req, props_req = _parts(req)
    raw, why = grpc_server._native_reply(col, results, meta_req, props_req,
                                         time.perf_counter())
    if raw is None:
        return None, why
    reply = pb.SearchReply.FromString(raw)
    assert reply.took > 0.0
    reply.took = 0.0
    return reply, why


# -- the served drive ---------------------------------------------------------

ROWS, DIM = 240, 16


def fill(db, name: str = "Served"):
    """A class of every type the encoder writes, half of it flushed to a
    segment; deterministic uuids and vectors (the times are the clock's:
    no request of :func:`requests` asks for them)."""
    from weaviate_tpu.schema.config import CollectionConfig, VectorConfig

    col = db.create_collection(CollectionConfig(
        name=name,
        properties=[Property(name="title", data_type="text"),
                    Property(name="bucket", data_type="int"),
                    Property(name="ratio", data_type="number"),
                    Property(name="live", data_type="boolean"),
                    Property(name="seen", data_type="date"),
                    Property(name="ref", data_type="uuid"),
                    Property(name="tags", data_type="text[]"),
                    Property(name="counts", data_type="int[]")],
        vectors=[VectorConfig(), VectorConfig(name="aux")]))
    rng = np.random.default_rng(17)
    vecs = rng.standard_normal((ROWS, DIM)).astype(np.float32)
    aux = rng.standard_normal((ROWS, 5)).astype(np.float32)
    for lo in range(0, ROWS, 120):
        col.batch_put([
            {"uuid": f"00000000-0000-4000-8000-{i:012x}",
             "properties": {
                 "title": f"döc {i} ✓", "bucket": i - 100,
                 "ratio": i / 7.0, "live": i % 2 == 0,
                 "seen": f"2024-01-{1 + i % 28:02d}T00:00:00Z",
                 "ref": f"11111111-2222-4333-8444-{i:012x}",
                 "tags": ["a", f"t{i % 3}"], "counts": [i, -i, 2 ** 40 + i]},
             "vector": vecs[i], "vectors": {"aux": aux[i]}}
            for i in range(lo, lo + 120)])
        if lo == 0:
            for s in col.sharding.shard_names:
                col._load_shard(s).objects.flush()
    return col, vecs


def requests(vecs, name: str = "Served") -> list["pb.SearchRequest"]:
    out = []
    for k, shape in ((1, "plain"), (10, "plain"), (100, "plain"),
                     (10, "vectors"), (10, "subset"), (10, "bare")):
        req = pb.SearchRequest(collection=name, limit=k, uses_123_api=True)
        req.near_vector.vector_bytes = (vecs[k] + 0.01).astype(
            "<f4").tobytes()
        if shape != "bare":
            req.metadata.uuid = True
            req.metadata.distance = True
        if shape == "vectors":
            req.metadata.vector = True
            req.metadata.certainty = True
            req.metadata.vectors.extend(["aux", "absent"])
        if shape == "subset":
            req.properties.non_ref_properties.extend(["bucket", "tags"])
        out.append(req)
    return out


def encoded() -> dict[str, float]:
    """``weaviate_tpu_grpc_reply_encode_total`` as it stands, by
    ``path/reason``: the registry lives as long as the process, so
    callers take deltas."""
    return {f"{path}/{why}": child.value
            for (path, why), child in grpc_server._REPLY_ENCODED.items()}


def served_answers(data_dir: str) -> dict:
    """Serve :func:`fill` over a socket, send :func:`requests`, and say
    what came back (``took`` cleared) and which encoder counted."""
    import grpc

    from weaviate_tpu.db.database import Database

    db = Database(data_dir)
    _col, vecs = fill(db)
    server = grpc_server.GrpcServer(db).start()
    channel = grpc.insecure_channel(f"127.0.0.1:{server.port}")
    search = channel.unary_unary(
        "/weaviate.v1.Weaviate/Search",
        request_serializer=pb.SearchRequest.SerializeToString,
        response_deserializer=pb.SearchReply.FromString)
    before = encoded()
    replies = []
    try:
        for req in requests(vecs):
            reply = search(req, timeout=60)
            reply.took = 0.0
            replies.append(reply.SerializeToString(deterministic=True).hex())
    finally:
        channel.close()
        server.stop()
        db.close()
    after = encoded()
    return {"replies": replies,
            "encoded": {k: after[k] - before[k] for k in after}}


if __name__ == "__main__":
    json.dump(served_answers(sys.argv[1]), sys.stdout)
