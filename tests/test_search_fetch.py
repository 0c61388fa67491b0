"""A Search reads its reply's objects in ONE batched read a local shard.

The reference is the fetch as it was: ``get_object`` a result. A gRPC
Search's serialised reply must be byte-equal to one built that way, the
batched read must be the only object read on the path, and a fault at
the chaos point ``kv.get_many`` must fail that reply alone."""

from __future__ import annotations

import copy
import time

import grpc
import numpy as np
import pytest

from weaviate_tpu.api.grpc import v1_pb2 as pb
from weaviate_tpu.api.grpc.server import GrpcServer
from weaviate_tpu.cluster import remote as remote_mod
from weaviate_tpu.db.collection import Collection
from weaviate_tpu.db.database import Database
from weaviate_tpu.runtime import faultline, tracing
from weaviate_tpu.schema.config import (CollectionConfig, Property,
                                        ShardingConfig, VectorConfig)
from weaviate_tpu.storage.kv import Bucket

DIM, ROWS = 16, 360


def _attach_per_result(self, results) -> None:
    """``Collection._attach_objects`` as it was: one point read a
    result of a local shard (the remote branch is not the reference's
    business: these collections are all local)."""
    for r in results:
        if r.object is None:
            r.object = self._load_shard(r.shard).get_object(r.uuid)


def _fill(db, name: str, shards: int = 1):
    col = db.create_collection(CollectionConfig(
        name=name,
        properties=[Property(name="title", data_type="text"),
                    Property(name="bucket", data_type="int"),
                    Property(name="tags", data_type="text[]")],
        vectors=[VectorConfig(), VectorConfig(name="aux")],
        sharding=ShardingConfig(desired_count=shards)))
    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((ROWS, DIM)).astype(np.float32)
    aux = rng.standard_normal((ROWS, 5)).astype(np.float32)
    for lo in range(0, ROWS, 120):
        col.batch_put([
            {"properties": {"title": f"doc {i}", "bucket": i % 100,
                            "tags": ["a", f"t{i % 3}"]},
             "vector": vecs[i], "vectors": {"aux": aux[i]}}
            for i in range(lo, lo + 120)])
        if lo < 240:  # two segments a shard; the last third in memtables
            for s in col.sharding.shard_names:
                col._load_shard(s).objects.flush()
    return col, vecs


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    db = Database(str(tmp_path_factory.mktemp("fetch")))
    one, vecs = _fill(db, "One")
    eight, _ = _fill(db, "Eight", shards=8)
    server = GrpcServer(db).start()
    channel = grpc.insecure_channel(f"127.0.0.1:{server.port}")
    search = channel.unary_unary(
        "/weaviate.v1.Weaviate/Search",
        request_serializer=pb.SearchRequest.SerializeToString,
        response_deserializer=pb.SearchReply.FromString)
    yield {"db": db, "One": one, "Eight": eight, "vecs": vecs,
           "search": search}
    channel.close()
    server.stop()
    db.close()


def _request(collection: str, vec, k: int, shape: str) -> "pb.SearchRequest":
    req = pb.SearchRequest(collection=collection, limit=k)
    req.near_vector.vector_bytes = np.asarray(vec, "<f4").tobytes()
    if shape == "plain":  # what the benchmark's cells send
        req.metadata.uuid = True
        req.metadata.distance = True
    elif shape == "vector":
        req.metadata.uuid = True
        req.metadata.vector = True
    elif shape == "named_vectors":
        req.metadata.uuid = True
        req.metadata.vectors.extend(["aux", "absent"])
    elif shape == "timestamps":
        req.metadata.creation_time_unix = True
        req.metadata.last_update_time_unix = True
        req.metadata.certainty = True
    elif shape == "properties":
        req.metadata.uuid = True
        req.properties.non_ref_properties.extend(["bucket", "tags"])
    else:
        raise AssertionError(shape)
    return req


def _wire(reply: "pb.SearchReply") -> bytes:
    reply = copy.deepcopy(reply)
    reply.took = 0.0
    return reply.SerializeToString(deterministic=True)


def _reference(served, req, monkeypatch) -> "pb.SearchReply":
    with monkeypatch.context() as m:
        m.setattr(Collection, "_attach_objects", _attach_per_result)
        return served["search"](req, timeout=30)


@pytest.mark.parametrize("shape", ["plain", "vector", "named_vectors",
                                   "timestamps", "properties"])
@pytest.mark.parametrize("k", [1, 10, 100])
def test_reply_is_byte_equal_to_a_get_object_a_result(served, monkeypatch,
                                                      k, shape):
    req = _request("One", served["vecs"][k % ROWS] + 0.01, k, shape)
    want = _reference(served, req, monkeypatch)
    got = served["search"](req, timeout=30)
    assert len(got.results) == k == len(want.results)
    assert _wire(got) == _wire(want)
    if shape == "vector":
        assert all(len(r.metadata.vector_bytes) == 4 * DIM
                   for r in got.results)
    if shape == "named_vectors":
        assert all([v.name for v in r.metadata.vectors] == ["aux"]
                   for r in got.results)
    if shape == "properties":
        assert all(set(r.properties.non_ref_props.fields) ==
                   {"bucket", "tags"} for r in got.results)
    if shape == "plain":  # `properties` unset: every non-reference one
        assert all(set(r.properties.non_ref_props.fields) ==
                   {"title", "bucket", "tags"} for r in got.results)


@pytest.mark.parametrize("k", [10, 100])
def test_eight_local_shards_reply_unchanged(served, monkeypatch, k):
    req = _request("Eight", served["vecs"][7] + 0.01, k, "plain")
    want = _reference(served, req, monkeypatch)
    got = served["search"](req, timeout=30)
    assert len(got.results) == k
    assert _wire(got) == _wire(want)


class _Reads:
    """Counts object reads under a block: ``get_many`` batches and point
    ``get`` calls, by bucket directory."""

    def __init__(self, monkeypatch):
        self.batches: list[tuple[str, int]] = []
        self.points: list[str] = []
        get_many, get = Bucket.get_many, Bucket.get

        def counted_many(bucket, keys, routes=None):
            self.batches.append((bucket.dir, len(keys)))
            return get_many(bucket, keys, routes)

        def counted_get(bucket, key):
            self.points.append(bucket.dir)
            return get(bucket, key)

        monkeypatch.setattr(Bucket, "get_many", counted_many)
        monkeypatch.setattr(Bucket, "get", counted_get)


@pytest.mark.parametrize("name,k", [("One", 10), ("One", 100),
                                    ("Eight", 10), ("Eight", 100)])
def test_one_batched_read_a_local_shard_and_no_point_read(
        served, monkeypatch, name, k):
    req = _request(name, served["vecs"][11] + 0.01, k, "plain")
    with monkeypatch.context() as m:
        reads = _Reads(m)
        reply = served["search"](req, timeout=30)
    assert len(reply.results) == k
    assert reads.points == []  # no Bucket.get anywhere on the path
    dirs = [d for d, _n in reads.batches]
    assert len(dirs) == len(set(dirs))  # ONE read a shard
    assert sum(n for _d, n in reads.batches) == k
    assert len(dirs) == 1 if name == "One" else 1 < len(dirs) <= 8
    # the same through the trace: one kv.get_many span a local shard,
    # and objects.fetch says what the reads did
    col = served[name]
    with tracing.trace("t", force=True):
        hits = col.near_vector(served["vecs"][11] + 0.01, k=k)
    spans = tracing.recent_traces(1)[0]["spans"]
    many = [s for s in spans if s["name"] == "kv.get_many"]
    (fetch,) = [s for s in spans if s["name"] == "objects.fetch"]
    shards = {h.shard for h in hits}
    assert len(many) == len(shards) == fetch["attrs"]["reads"] \
        == fetch["attrs"]["shards"]
    assert fetch["attrs"]["n"] == k
    resolved = sum(s["attrs"][p] for s in many
                   for p in ("memtable", "array", "scalar"))
    assert resolved == k
    assert fetch["attrs"]["array_keys"] == sum(s["attrs"]["array"]
                                               for s in many)
    assert fetch["attrs"]["scalar_keys"] == sum(s["attrs"]["scalar"]
                                                for s in many)
    if name == "One":  # a batch of 10 or 100 over fixed-width segments:
        # at most the LAST key still missing is searched alone
        assert fetch["attrs"]["scalar_keys"] <= 1
        assert fetch["attrs"]["array_keys"] > 0


def test_object_deleted_between_search_and_fetch_is_left_out(served,
                                                             monkeypatch):
    col = served["One"]
    req = _request("One", served["vecs"][200] + 0.01, 10, "plain")
    before = served["search"](req, timeout=30)
    victim = before.results[3].metadata.id
    attach = Collection._attach_objects

    def delete_then_attach(self, results):
        # the object store loses the object after the index answered
        shard = self._load_shard(results[3].shard)
        assert results[3].uuid == victim
        shard.objects.delete(victim.encode())
        attach(self, results)
        assert results[3].object is None

    raw = col._load_shard(col.sharding.shard_names[0]).objects.get(
        victim.encode())
    try:
        with monkeypatch.context() as m:
            m.setattr(Collection, "_attach_objects", delete_then_attach)
            got = served["search"](req, timeout=30)
    finally:
        col._load_shard(col.sharding.shard_names[0]).objects.put(
            victim.encode(), raw)
    ids = [r.metadata.id for r in got.results]
    assert victim not in ids and len(ids) == 9
    assert ids == [r.metadata.id for r in before.results if
                   r.metadata.id != victim]
    assert _wire(served["search"](req, timeout=30)) == _wire(before)


@pytest.mark.parametrize("action", ["corrupt", "error", "latency"])
def test_fault_at_kv_get_many_fails_that_reply_alone(served, action):
    """The chaos point sits on the request path now: a corrupt value or
    an injected error fails THAT reply with a status, nothing hangs, and
    the next request is served."""
    req = _request("One", served["vecs"][50] + 0.01, 10, "plain")
    healthy = served["search"](req, timeout=30)
    kwargs = {"latency_s": 0.05} if action == "latency" else {}
    t0 = time.perf_counter()
    with faultline.injected(
            "kv.get_many", action=action, times=1,
            match=lambda a: a.get("bucket") == "objects", **kwargs) as sched:
        if action == "latency":
            assert _wire(served["search"](req, timeout=30)) == _wire(healthy)
            assert time.perf_counter() - t0 >= 0.045
        else:
            with pytest.raises(grpc.RpcError) as err:
                served["search"](req, timeout=30)
            assert err.value.code() in (grpc.StatusCode.INVALID_ARGUMENT,
                                        grpc.StatusCode.INTERNAL)
            assert err.value.code() != grpc.StatusCode.DEADLINE_EXCEEDED
        assert sched.injected == 1
    assert time.perf_counter() - t0 < 20
    assert _wire(served["search"](req, timeout=30)) == _wire(healthy)


class _Loopback:
    """Two in-process nodes: remote shard operations go straight into
    the other ``Database`` and are counted."""

    def __init__(self):
        self.dbs: dict = {}
        self.calls: list[tuple[str, str, int]] = []

    def _shard(self, node, collection, shard):
        return self.dbs[node].get_collection(collection)._load_shard(shard)

    def put_objects(self, node, collection, shard, raws):
        from weaviate_tpu.storage.objects import StorageObject

        self._shard(node, collection, shard).put_object_batch(
            [StorageObject.from_bytes(r) for r in raws])

    def search_shard(self, node, collection, shard, **payload):
        return remote_mod._incoming_search(
            self._shard(node, collection, shard), payload)["results"]

    def get_objects(self, node, collection, shard, uuids):
        self.calls.append(("get_objects", shard, len(uuids)))
        objects = self._shard(node, collection, shard).objects
        return [objects.get(u.encode()) for u in uuids]


def test_remote_branch_still_makes_one_get_objects_a_remote_shard(
        tmp_path, monkeypatch):
    remote = _Loopback()
    nodes = ["n0", "n1"]
    dbs = {n: Database(str(tmp_path / n), local_node=n,
                       nodes_provider=lambda: nodes, remote=remote)
           for n in nodes}
    remote.dbs = dbs
    try:
        cfg = CollectionConfig(
            name="Spread",
            properties=[Property(name="bucket", data_type="int")],
            sharding=ShardingConfig(desired_count=8))
        col0 = dbs["n0"].create_collection(cfg)
        dbs["n1"].create_collection(
            copy.deepcopy(cfg), sharding_state=copy.deepcopy(col0.sharding))
        local = [s for s in col0.sharding.shard_names if col0._is_local(s)]
        assert 0 < len(local) < 8
        rng = np.random.default_rng(9)
        vecs = rng.standard_normal((240, DIM)).astype(np.float32)
        col0.batch_put([{"properties": {"bucket": i}, "vector": v}
                               for i, v in enumerate(vecs)])
        q = vecs[17] + 0.01
        results = col0.near_vector(q, k=40, include_objects=False)
        assert len(results) == 40 and all(r.object is None for r in results)
        by_shard: dict[str, int] = {}
        for r in results:
            by_shard[r.shard] = by_shard.get(r.shard, 0) + 1
        far = {s: n for s, n in by_shard.items() if s not in local}
        near = {s: n for s, n in by_shard.items() if s in local}
        assert far and near
        with monkeypatch.context() as m:
            reads = _Reads(m)
            remote.calls.clear()
            col0._attach_objects(results)
            mine = [(d, n) for d, n in reads.batches
                    if str(tmp_path / "n0") in d]
        # one get_objects a remote shard, one batched read a local one
        assert sorted((s, n) for _op, s, n in remote.calls) == \
            sorted(far.items())
        assert sorted(n for _d, n in mine) == sorted(near.values())
        assert not [d for d in reads.points if str(tmp_path / "n0") in d]
        for r in results:
            assert r.object is not None and r.object.uuid == r.uuid
            assert r.object.properties["bucket"] == int(np.argmin(
                ((vecs - r.object.vector) ** 2).sum(1)))
    finally:
        for d in dbs.values():
            d.close()
