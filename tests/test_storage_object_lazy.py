"""A ``StorageObject`` read from storage decodes its vectors at their
first read. Every reader of ``from_bytes`` that touches ``vector`` /
``vectors`` must get the arrays the eager decoder gave it, bit for bit:
the codec itself, REST GET, GraphQL, aggregation, backup, replication's
digests and driftwatch's ground truth."""

from __future__ import annotations

import struct
import uuid as uuid_mod

import msgpack
import numpy as np
import pytest

from weaviate_tpu.api.client import Client
from weaviate_tpu.api.rest import RestServer
from weaviate_tpu.db.database import Database
from weaviate_tpu.schema.config import (CollectionConfig, Property,
                                        VectorConfig)
from weaviate_tpu.storage.objects import StorageObject

_HEADER = struct.Struct("<BQQQ16s")


def eager_from_bytes(data: bytes) -> StorageObject:
    """The decoder as it was before vectors went lazy: the reference."""
    _version, doc_id, ctime, mtime, uid = _HEADER.unpack_from(data, 0)
    off = _HEADER.size
    (n_vecs,) = struct.unpack_from("<I", data, off)
    off += 4
    vectors = {}
    for _ in range(n_vecs):
        (nlen,) = struct.unpack_from("<H", data, off)
        off += 2
        name = data[off : off + nlen].decode("utf-8")
        off += nlen
        (dim,) = struct.unpack_from("<I", data, off)
        off += 4
        vectors[name] = np.frombuffer(data, dtype="<f4", count=dim,
                                      offset=off).copy()
        off += 4 * dim
    (plen,) = struct.unpack_from("<I", data, off)
    off += 4
    return StorageObject(
        uuid=str(uuid_mod.UUID(bytes=uid)), doc_id=doc_id,
        properties=msgpack.unpackb(data[off : off + plen], raw=False),
        vectors=vectors, creation_time_ms=ctime, last_update_time_ms=mtime)


def _bits(vectors: dict) -> dict:
    return {k: (v.dtype.str, v.shape, v.tobytes())
            for k, v in vectors.items()}


DIM, AUX = 24, 7


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    """One collection with a default and a named vector, 60 objects
    spread over two segments and a memtable; the vectors hold every kind
    of float32 bit pattern a copy could mangle."""
    root = tmp_path_factory.mktemp("lazy")
    db = Database(str(root / "data"))
    col = db.create_collection(CollectionConfig(
        name="Doc",
        properties=[Property(name="n", data_type="int"),
                    Property(name="title", data_type="text")],
        vectors=[VectorConfig(), VectorConfig(name="aux")]))
    rng = np.random.default_rng(7)
    vecs = rng.standard_normal((60, DIM)).astype(np.float32)
    vecs[0, :4] = [0.0, -0.0, np.float32(1e-42), np.float32(1e15)]
    aux = rng.standard_normal((60, AUX)).astype(np.float32)
    uuids = []
    shard = None
    for i in range(60):
        uuids.append(col.put_object({"n": i, "title": f"t{i % 5}"},
                                    vector=vecs[i], vectors={"aux": aux[i]}))
        if i in (24, 49):
            shard = shard or col._load_shard(col.sharding.shard_names[0])
            shard.objects.flush()
    assert shard.objects.segment_count == 2
    srv = RestServer(db)
    srv.start()
    yield {"db": db, "col": col, "shard": shard, "uuids": uuids,
           "vecs": vecs, "aux": aux, "client": Client(srv.address),
           "root": root}
    srv.stop()
    db.close()


def _stored(env, i: int) -> dict:
    return {"": env["vecs"][i], "aux": env["aux"][i]}


def _read_codec(env):
    out = {}
    for key, raw in env["shard"].objects.iter_items():
        lazy, eager = StorageObject.from_bytes(raw), eager_from_bytes(raw)
        assert lazy.__dict__["_vectors"] is None  # nothing decoded yet
        assert (lazy.uuid, lazy.doc_id, lazy.properties,
                lazy.creation_time_ms, lazy.last_update_time_ms) == (
            eager.uuid, eager.doc_id, eager.properties,
            eager.creation_time_ms, eager.last_update_time_ms)
        assert _bits(lazy.vectors) == _bits(eager.vectors)
        assert lazy.vectors is lazy.vectors  # decoded once, kept
        assert lazy.vector.flags.writeable and lazy.vector.flags.owndata
        assert lazy.to_bytes() == raw == eager.to_bytes()
        out[key.decode()] = lazy.vectors
    return out


def _read_rest_get(env):
    out = {}
    for u in env["uuids"]:
        body = env["client"].request(
            "GET", f"/v1/objects/Doc/{u}", params={"include": "vector"})
        out[u] = {"": np.asarray(body["vector"], np.float32),
                  "aux": np.asarray(body["vectors"]["aux"], np.float32)}
    return out


def _read_graphql(env):
    got = env["client"].graphql(
        "{ Get { Doc(limit: 100) { n _additional { id vector "
        "vectors { aux } } } } }")
    assert "errors" not in got, got
    return {r["_additional"]["id"]: {
        "": np.asarray(r["_additional"]["vector"], np.float32),
        "aux": np.asarray(r["_additional"]["vectors"]["aux"], np.float32)}
        for r in got["data"]["Get"]["Doc"]}


def _read_search(env):
    out = {}
    for i in (0, 30, 59):  # a segment each and the memtable
        hit = env["col"].near_vector(env["vecs"][i], k=1)[0]
        out[hit.uuid] = hit.object.vectors
    return out


def _read_aggregation(env):
    # aggregation decodes every object and reads no vector: its answer
    # must not depend on whether they were decoded
    agg = env["col"].aggregate(properties={"n": ["count", "sum", "mean"]})
    assert agg["meta"]["count"] == 60
    assert agg["properties"]["n"]["sum"] == sum(range(60))
    grouped = env["col"].aggregate(properties={"n": ["count"]},
                                   group_by="title")
    assert sorted(g["groupedBy"]["value"] for g in grouped["groups"]) == \
        [f"t{i}" for i in range(5)]
    return {u: o.vectors for u in env["uuids"][:5]
            for o in [env["col"].get_object(u)]}


def _read_backup(env):
    from weaviate_tpu.backup import SUCCESS, BackupManager
    from weaviate_tpu.modules import Provider
    from weaviate_tpu.modules.backup_backends import FilesystemBackend

    provider = Provider(env["db"])
    provider.register(FilesystemBackend(),
                      {"path": str(env["root"] / "backups")})
    mgr = BackupManager(env["db"], provider)
    mgr.start_backup("filesystem", "lazy1", wait=True)
    assert mgr.backup_status("filesystem", "lazy1")["status"] == SUCCESS
    other = Database(str(env["root"] / "restored"))
    try:
        provider2 = Provider(other)
        provider2.register(FilesystemBackend(),
                           {"path": str(env["root"] / "backups")})
        mgr2 = BackupManager(other, provider2)
        mgr2.start_restore("filesystem", "lazy1", wait=True)
        assert mgr2.restore_status("filesystem", "lazy1")["status"] \
            == SUCCESS
        col = other.get_collection("Doc")
        # the restored index was rebuilt from lazily decoded objects
        hit = col.near_vector(env["vecs"][3], k=1)[0]
        assert hit.uuid == env["uuids"][3] and hit.distance < 1e-5
        return {u: col.get_object(u).vectors for u in env["uuids"]}
    finally:
        other.close()


def _read_replication_digest(env):
    out = {}
    for u in env["uuids"]:
        raw = env["shard"].objects.get(u.encode())
        digest = env["shard"].object_digest(u)
        assert digest["hash"] == eager_from_bytes(raw).content_hash()
        out[u] = StorageObject.from_bytes(raw).vectors
    return out


def _read_driftwatch(env):
    out = {}
    row = np.empty(DIM, np.float32)
    for key, raw in env["shard"].objects.iter_items():
        obj = StorageObject.from_bytes(raw)
        assert StorageObject.read_vector_into(raw, "", row) == obj.doc_id
        assert row.tobytes() == obj.vector.tobytes()
        out[key.decode()] = obj.vectors
    return out


_READERS = {"codec": _read_codec, "rest_get": _read_rest_get,
            "graphql": _read_graphql, "search": _read_search,
            "aggregation": _read_aggregation, "backup": _read_backup,
            "replication_digest": _read_replication_digest,
            "driftwatch": _read_driftwatch}


@pytest.mark.parametrize("reader", sorted(_READERS))
def test_reader_gets_the_stored_vectors_bit_for_bit(env, reader):
    got = _READERS[reader](env)
    assert got
    index = {u: i for i, u in enumerate(env["uuids"])}
    for u, vectors in got.items():
        want = _stored(env, index[u])
        assert set(vectors) == {"", "aux"}
        for name in want:
            assert vectors[name].dtype == np.float32
            assert vectors[name].tobytes() == want[name].tobytes(), \
                (reader, u, name)


@pytest.mark.parametrize("names", [(), ("",), ("", "aux", "häßlich"),
                                   ("only-named",)])
def test_codec_round_trip_by_vector_set(names):
    rng = np.random.default_rng(len(names))
    obj = StorageObject(
        uuid=str(uuid_mod.uuid4()), doc_id=11, properties={"a": [1, "x"]},
        vectors={n: rng.standard_normal(5 + i).astype(np.float32)
                 for i, n in enumerate(names)})
    raw = obj.to_bytes()
    lazy, eager = StorageObject.from_bytes(raw), eager_from_bytes(raw)
    assert lazy.uuid == eager.uuid == obj.uuid
    assert _bits(lazy.vectors) == _bits(eager.vectors) == _bits(obj.vectors)
    assert (lazy.vector is None) == ("" not in names)
    assert lazy.content_hash() == eager.content_hash()
    # a write through the property lands in the decoded dict
    fresh = StorageObject.from_bytes(raw)
    fresh.vector = [1.0, 2.0]
    assert set(fresh.vectors) == set(names) | {""}
    assert fresh.to_bytes() != raw
    replaced = StorageObject.from_bytes(raw)
    replaced.vectors = {}
    assert replaced.vectors == {} and replaced.vector is None


@pytest.mark.parametrize("cut", ["header", "vector", "properties"])
def test_truncated_frame_raises(cut):
    raw = StorageObject(
        uuid=str(uuid_mod.uuid4()), properties={"k": "v" * 40},
        vectors={"": np.ones(16, np.float32)}).to_bytes()
    short = {"header": raw[:20], "vector": raw[:_HEADER.size + 30],
             "properties": raw[:-10]}[cut]
    with pytest.raises((ValueError, struct.error)):
        StorageObject.from_bytes(short)
