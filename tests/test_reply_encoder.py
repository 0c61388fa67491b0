"""A plain gRPC Search's reply is encoded from the stored frames of its
results in ONE native call (``native.search_reply_encode``).

The oracle is ``_fill_result``, a result at a time
(tests/reply_reference.py): the native reply must PARSE equal to it,
field for field, for every type the encoder writes; whatever it does not
write it declines, and the Python path answers the whole request. Over a
socket: the counter says which encoder answered and why, an acknowledged
write is in the next reply, a frame that cannot be decoded fails its
request alone, and the readers' series are stamped as before."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import grpc
import msgpack
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import reply_reference as ref  # noqa: E402 — the oracle, tests/

from weaviate_tpu import native  # noqa: E402
from weaviate_tpu.api.grpc import server as grpc_server  # noqa: E402
from weaviate_tpu.api.grpc import v1_pb2 as pb  # noqa: E402
from weaviate_tpu.db.database import Database  # noqa: E402
from weaviate_tpu.runtime import tailboard  # noqa: E402
from weaviate_tpu.runtime.metrics import (  # noqa: E402
    request_stage_seconds)
from weaviate_tpu.schema.config import (CollectionConfig,  # noqa: E402
                                        Property)
from weaviate_tpu.storage.objects import StorageObject  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="the native library did not build")


def _plain(**metadata) -> "pb.SearchRequest":
    req = pb.SearchRequest(collection="Doc", uses_123_api=True)
    for field, on in metadata.items():
        setattr(req.metadata, field, on)
    return req


def _both(col, frames, req, distances=None, scores=None):
    """(native, python) replies over the same stored frames."""
    got, why = ref.native_reply(col, ref.hits(frames, distances, scores), req)
    want = ref.python_reply(col, ref.hits(frames, distances, scores), req)
    return got, why, want


# -- (a) every type the encoder writes, against _fill_result ------------------

I63 = 2 ** 63
VALUES = [
    ("text", "plain"), ("text", ""), ("text", "héllo ✓ 😀 \x00 tail"),
    ("text", "x" * 300), ("text", None), ("text", 7), ("text", True),
    ("int", 0), ("int", -1), ("int", I63 - 1), ("int", -I63),
    ("int", 7.0), ("int", 3.9), ("int", -3.9), ("int", True),
    ("int", None), ("int", "seven"),
    ("number", 1.5), ("number", 0.0), ("number", -0.0), ("number", 1e308),
    ("number", 3), ("number", 2 ** 64 - 1), ("number", -I63),
    ("number", np.float32(0.1).item()),
    ("boolean", True), ("boolean", False), ("boolean", None),
    ("date", "2024-02-29T12:00:00.5+01:00"), ("date", ""),
    ("uuid", "11111111-2222-4333-8444-555555555555"),
    ("text[]", ["a", "ü", ""]), ("text[]", []), ("text[]", ["solo"]),
    ("text[]", ["w" * 40] * 20),
    ("int[]", [1, -2, 2 ** 62]), ("int[]", [1.0, 2.9, -2.9]), ("int[]", []),
    ("int[]", [True, 2]), ("int[]", list(range(-20, 20))),
    ("number[]", [1.5, 2]), ("number[]", [1, 2]), ("number[]", [0.5]),
    ("number[]", [True, 1.5]), ("number[]", [2 ** 64 - 1, 0.5]),
    ("boolean[]", [True, False, True]), ("boolean[]", [False]),
    ("date[]", ["2024-01-01T00:00:00Z", "2025-01-01T00:00:00Z"]),
    ("uuid[]", ["11111111-2222-4333-8444-555555555555",
                "aaaaaaaa-bbbb-4ccc-8ddd-eeeeeeeeeeee"]),
    ("date[]", [1, 2]), ("uuid[]", []), ("uuid[]", [1.5, 2.5]),
]


@pytest.mark.parametrize("dtype,value", VALUES,
                         ids=[f"{t}-{i}" for i, (t, _v) in enumerate(VALUES)])
def test_a_value_parses_equal_to_fill_result(dtype, value):
    col = ref.collection({"p": dtype, "other": "text"})
    frames = [ref.stored(0, {"p": value, "other": "o"}),
              ref.stored(1, {"other": "only"}),   # p missing
              ref.stored(2, {}),                  # no property at all
              ref.stored(3, {"p": value})]
    got, why, want = _both(col, frames, _plain(uuid=True))
    assert why == "" and got == want
    assert len(got.results) == 4
    # the same value under a key the class does not name: dtype None
    bare = ref.collection({"other": "text"})
    got, why, want = _both(bare, frames, _plain(uuid=True))
    assert why == "" and got == want


DECLINED = [
    ("text", {"a": 1}), ("text", {"latitude": 1.0, "longitude": 2.0}),
    ("text", b"raw"), ("date", 20240101), ("date", 1.5),
    ("int", I63), ("int", float("nan")), ("int", float("inf")),
    ("int", 1e19), ("text[]", [1, "a"]), ("text[]", ["a", None]),
    ("text[]", [["nested"]]), ("text[]", [{"k": "v"}]),
    ("int[]", [1, "2"]), ("int[]", [I63]), ("int[]", [float("nan")]),
    ("number[]", [1.5, "x"]), ("date[]", ["2024-01-01T00:00:00Z", 3]),
    ("text", msgpack.Timestamp(5)),
]


@pytest.mark.parametrize("dtype,value", DECLINED,
                         ids=[f"{t}-{i}" for i, (t, _v) in enumerate(DECLINED)])
def test_a_value_the_encoder_does_not_write_is_declined_whole(dtype, value):
    """One such value in one frame of a reply: no part of the reply is
    native (the Python path then answers or raises, as it always did)."""
    col = ref.collection({"p": dtype})
    frames = [ref.stored(0, {"p": "fine" if dtype == "text" else None}),
              ref.stored(1, {"p": value})]
    got, why = ref.native_reply(col, ref.hits(frames), _plain(uuid=True))
    assert got is None and why == "value"


def _rich(n: int):
    rng = np.random.default_rng(n)
    col = ref.collection({"title": "text", "bucket": "int",
                          "tags": "text[]", "when": "date"})
    frames = [ref.stored(
        i, {"title": f"t{i}", "bucket": float(i), "tags": ["x", f"y{i}"],
            "when": "2024-01-01T00:00:00Z", "unnamed": i * 0.5},
        {"": rng.standard_normal(24).astype(np.float32),
         "aux": rng.standard_normal(5).astype(np.float32),
         "ümlaut": rng.standard_normal(3).astype(np.float32)})
        for i in range(n)]
    dists = [float(np.float32(0.03 * i)) for i in range(n)]
    scores = [float(np.float32(1.0 / (1 + i))) for i in range(n)]
    return col, frames, dists, scores


FLAGS = ["uuid", "vector", "creation_time_unix", "last_update_time_unix",
         "distance", "certainty", "score", "explain_score", "is_consistent"]


@pytest.mark.parametrize("flag", FLAGS + ["vectors", "all", "none", "unset"])
def test_each_metadata_flag_alone_and_all_together(flag):
    col, frames, dists, scores = _rich(7)
    req = _plain()
    if flag == "vectors":
        req.metadata.vectors.extend(["aux", "absent", "", "ümlaut"])
    elif flag == "all":
        for f in FLAGS:
            setattr(req.metadata, f, True)
        req.metadata.vectors.extend(["ümlaut", "aux"])
    elif flag == "none":
        req.metadata.SetInParent()  # a MetadataRequest that asks nothing
    elif flag != "unset":           # unset: no MetadataRequest at all
        setattr(req.metadata, flag, True)
    got, why, want = _both(col, frames, req, dists, scores)
    assert why == "" and got == want
    if flag in ("none", "explain_score", "is_consistent"):
        assert not any(r.HasField("metadata") for r in got.results)
    if flag == "unset":
        assert all(r.metadata.id for r in got.results)
    if flag in ("vector", "all"):
        assert all(len(r.metadata.vector_bytes) == 96 for r in got.results)


@pytest.mark.parametrize("shape", ["distance_none", "score_none", "mixed",
                                   "far", "zero", "no_vector"])
def test_distances_and_scores_present_or_not(shape):
    col, frames, dists, scores = _rich(6)
    req = _plain(uuid=True, distance=True, certainty=True, score=True,
                 vector=True)
    if shape == "distance_none":
        dists = None                       # bm25: no result has one
    elif shape == "score_none":
        scores = None
    elif shape == "mixed":                 # hybrid: a leg's results lack one
        dists = [d if i % 2 else None for i, d in enumerate(dists)]
        scores = [None if i % 3 else s for i, s in enumerate(scores)]
    elif shape == "far":                   # certainty clamps at 0
        dists = [2.5 + d for d in dists]
    elif shape == "zero":
        dists, scores = [0.0] * 6, [0.0] * 6
    elif shape == "no_vector":             # an object stored without one
        frames = [ref.stored(i, {"title": "t"}) for i in range(6)]
    got, why, want = _both(col, frames, req, dists, scores)
    assert why == "" and got == want


@pytest.mark.parametrize("shape", ["subset", "all_flag", "empty_list",
                                   "unknown_names", "one", "subset_and_flag"])
def test_requested_properties(shape):
    col, frames, _d, _s = _rich(5)
    req = _plain(uuid=True)
    names = {"subset": ["bucket", "tags"], "one": ["when"],
             "unknown_names": ["nope", "title", "unnamed"],
             "subset_and_flag": ["bucket"]}.get(shape, [])
    req.properties.non_ref_properties.extend(names)
    if shape in ("all_flag", "subset_and_flag"):
        req.properties.return_all_nonref_properties = True
    if shape == "empty_list":
        req.properties.SetInParent()
    got, why, want = _both(col, frames, req)
    assert why == "" and got == want
    keys = {k for r in got.results for k in r.properties.non_ref_props.fields}
    assert keys == ({"bucket", "tags"} if shape == "subset" else
                    {"when"} if shape == "one" else
                    {"title", "unnamed"} if shape == "unknown_names" else
                    {"title", "bucket", "tags", "when", "unnamed"})


@pytest.mark.parametrize("k", [1, 10, 100, 0])
def test_k_results_and_one_gone_since_the_search(k):
    col, frames, dists, _s = _rich(k)
    req = _plain(uuid=True, distance=True)
    got, why, want = _both(col, frames, req, dists)
    assert why == "" and got == want and len(got.results) == k
    if k > 1:  # the object of one hit was deleted between search and fetch
        gone = list(frames)
        gone[k // 2] = None
        got, why, want = _both(col, gone, req, dists)
        assert why == "" and got == want and len(got.results) == k - 1
        ids = [r.metadata.id for r in got.results]
        assert f"00000000-0000-4000-8000-{k // 2:012x}" not in ids


def test_random_objects_parse_equal():
    """A few hundred random property dicts over every written type."""
    rng = np.random.default_rng(52)
    types_ = ["text", "int", "number", "boolean", "date", "uuid", "text[]",
              "int[]", "number[]", "boolean[]", "date[]", "uuid[]"]
    col = ref.collection({f"p{i}": t for i, t in enumerate(types_)})

    def text():
        return "".join(chr(int(c)) for c in rng.choice(
            [0x41, 0x7a, 0xe9, 0x4e2d, 0x1f600, 0x20], rng.integers(0, 12)))

    def scalar(t):
        return {"text": text, "date": text, "uuid": text,
                "int": lambda: int(rng.integers(-2 ** 62, 2 ** 62)),
                "number": lambda: float(rng.standard_normal() * 1e6),
                "boolean": lambda: bool(rng.integers(2))}[t]()

    frames = []
    for i in range(300):
        props = {}
        for j, t in enumerate(types_):
            roll = rng.integers(10)
            if roll == 0:
                continue
            if roll == 1:
                props[f"p{j}"] = None
            elif t.endswith("[]"):
                props[f"p{j}"] = [scalar(t[:-2])
                                  for _ in range(rng.integers(0, 6))]
            else:
                props[f"p{j}"] = scalar(t)
        frames.append(ref.stored(i, props, {"": rng.standard_normal(
            4).astype(np.float32)}))
    req = _plain(uuid=True, vector=True, creation_time_unix=True)
    got, why, want = _both(col, frames, req)
    assert why == "" and got == want


# -- (e) frames that cannot be decoded ----------------------------------------


def _python_outcome(col, frames, req):
    try:
        return ref.python_reply(col, ref.hits(frames), req)
    except Exception as e:  # noqa: BLE001 — whatever from_bytes raises
        return type(e)


def test_a_truncated_or_wrong_version_frame_is_never_encoded():
    col, frames, _d, _s = _rich(3)
    req = _plain(uuid=True, vector=True)
    req.metadata.vectors.append("aux")
    whole = frames[1]
    assert ref.native_reply(col, ref.hits(frames), req)[1] == ""
    for cut in range(len(whole)):
        bad = [frames[0], whole[:cut], frames[2]]
        got, why = ref.native_reply(col, ref.hits(bad), req)
        want = _python_outcome(col, bad, req)
        # where from_bytes raises, the encoder has declined; where it
        # answers (a cut inside the vectors it never read), so may the
        # encoder, and then they agree
        if isinstance(want, type):
            assert got is None and why == "value", cut
        elif got is not None:
            assert got == want, cut
    versioned = bytes([2]) + whole[1:]
    assert ref.native_reply(col, ref.hits([versioned]), req) == (None, "value")
    with pytest.raises(ValueError, match="version"):
        ref.python_reply(col, ref.hits([versioned]), req)


def test_random_damage_never_crashes_and_never_disagrees():
    col, frames, _d, _s = _rich(2)
    req = _plain(uuid=True, creation_time_unix=True)
    rng = np.random.default_rng(9)
    head = len(frames[0]) - 80  # the properties' end of the frame
    agreed = declined = 0
    for _ in range(1500):
        bad = bytearray(frames[0])
        for _ in range(rng.integers(1, 4)):
            bad[int(rng.integers(head, len(bad)))] = int(rng.integers(256))
        got, _why = ref.native_reply(col, ref.hits([bytes(bad)]), req)
        want = _python_outcome(col, [bytes(bad)], req)
        if got is None:
            declined += 1
            continue
        assert not isinstance(want, type), bytes(bad[head:])
        assert got == want, bytes(bad[head:])
        agreed += 1
    assert agreed > 100 and declined > 100


def test_invalid_utf8_is_refused():
    col = ref.collection({"p": "text"})
    good = ref.stored(0, {"p": "abcd"})
    for junk in (b"\xff\xfe\xfd\xfc", b"\xc0\x80ab", b"\xed\xa0\x80a",
                 b"\xf4\x90\x80\x80", b"ab\xe2\x82"):
        bad = good.replace(b"abcd", junk)
        assert ref.native_reply(col, ref.hits([bad]), _plain())[0] is None
        with pytest.raises(UnicodeDecodeError):
            StorageObject.from_bytes(bad)


# -- (f) an object read after a native reply ----------------------------------


def test_object_after_a_native_reply_is_from_bytes_of_the_frame():
    col, frames, dists, _s = _rich(4)
    results = ref.hits(frames, dists)
    got, why = ref.native_reply(col, results, _plain(uuid=True))
    assert why == "" and len(got.results) == 4
    for r, frame in zip(results, frames):
        assert r._object is None and r.frame is frame  # nothing decoded
        obj = r.object
        want = StorageObject.from_bytes(frame)
        assert obj.uuid == r.uuid == want.uuid
        assert (obj.doc_id, obj.properties, obj.creation_time_ms,
                obj.last_update_time_ms) == (
            want.doc_id, want.properties, want.creation_time_ms,
            want.last_update_time_ms)
        assert obj.vectors.keys() == want.vectors.keys()
        assert all(np.array_equal(obj.vectors[n], want.vectors[n])
                   for n in want.vectors)
        assert r.object is obj                         # once
    # an object someone SET has no frame to encode: the Python path
    results[1].object = StorageObject.from_bytes(frames[1])
    assert results[1].frame is None
    assert ref.native_reply(col, results, _plain()) == (None, "value")


# -- served over a socket ------------------------------------------------------


class _Modules:
    """Just enough of a module provider for rerank and generative."""

    def rerank(self, _config, _query, docs):
        return [float(len(d)) for d in docs]

    def generate_single(self, _config, prompt, props):
        return f"{prompt}:{sorted(props)}"


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    db = Database(str(tmp_path_factory.mktemp("reply")))
    _col, vecs = ref.fill(db)
    db.create_collection(CollectionConfig(name="Geo", properties=[
        Property(name="title", data_type="text"),
        Property(name="place", data_type="geoCoordinates")]))
    geo = db.get_collection("Geo")
    for i in range(8):
        geo.put_object({"title": f"g{i}", "place": {
            "latitude": 1.0 + i, "longitude": 2.0}}, vector=vecs[i])
    server = grpc_server.GrpcServer(db, modules=_Modules()).start()
    channel = grpc.insecure_channel(f"127.0.0.1:{server.port}")
    call = channel.unary_unary(
        "/weaviate.v1.Weaviate/Search",
        request_serializer=pb.SearchRequest.SerializeToString,
        response_deserializer=pb.SearchReply.FromString)
    yield {"db": db, "col": db.get_collection("Served"), "vecs": vecs,
           "search": lambda req: call(req, timeout=60)}
    channel.close()
    server.stop()
    db.close()


class _Encoded:
    """The counter's deltas over a block, by ``path/reason``."""

    def __enter__(self):
        self._before = ref.encoded()
        return self

    def __exit__(self, *exc):
        self.by = {k: int(v - self._before[k])
                   for k, v in ref.encoded().items() if v != self._before[k]}


def _near(served, name="Served", k=5, row=3, **metadata):
    req = pb.SearchRequest(collection=name, limit=k, uses_123_api=True)
    req.near_vector.vector_bytes = (served["vecs"][row] + 0.01).astype(
        "<f4").tobytes()
    req.metadata.uuid = True
    for field, on in metadata.items():
        setattr(req.metadata, field, on)
    return req


def test_served_replies_are_native_and_equal_the_python_path(served,
                                                             monkeypatch):
    reqs = ref.requests(served["vecs"])
    with _Encoded() as native_side:
        got = [served["search"](r) for r in reqs]
    assert native_side.by == {"native/": len(reqs)}
    with monkeypatch.context() as m, _Encoded() as python_side:
        m.setattr(native, "available", lambda: False)
        want = [served["search"](r) for r in reqs]
    assert python_side.by == {"python/no_native": len(reqs)}
    for g, w, req in zip(got, want, reqs):
        assert len(g.results) == req.limit
        assert g.took > 0.0
        g.took = w.took = 0.0
        assert g == w


@pytest.mark.parametrize("shape", ["group_by", "rerank", "generative",
                                   "legacy", "fetch"])
def test_a_request_that_is_not_plain_takes_the_python_path_whole(served,
                                                                 shape):
    req = _near(served, distance=True)
    if shape == "group_by":
        req.group_by.path.append("bucket")
        req.group_by.number_of_groups = 3
        req.group_by.objects_per_group = 2
    elif shape == "rerank":
        req.rerank.property = "title"
    elif shape == "generative":
        req.generative.single_response_prompt = "say"
    elif shape == "legacy":
        req.uses_123_api = False
    elif shape == "fetch":
        req.ClearField("near_vector")
    with _Encoded() as seen:
        reply = served["search"](req)
    assert seen.by == {"python/request": 1}
    if shape == "group_by":
        assert len(reply.group_by_results) == 3 and not reply.results
    else:
        assert len(reply.results) == 5
    if shape == "rerank":
        assert all(r.metadata.rerank_score_present for r in reply.results)
    if shape == "generative":
        assert all(r.metadata.generative_present for r in reply.results)
    if shape == "legacy":
        assert all(r.properties.non_ref_properties.fields
                   for r in reply.results)


def test_a_class_with_a_type_the_encoder_does_not_write(served):
    with _Encoded() as seen:
        reply = served["search"](_near(served, "Geo"))
    assert seen.by == {"python/schema": 1}
    assert all(r.properties.non_ref_props.fields["place"].HasField(
        "geo_value") for r in reply.results)
    # the request cannot return the geo property: the class's other
    # types are written, so the native encoder answers
    req = _near(served, "Geo")
    req.properties.non_ref_properties.append("title")
    with _Encoded() as seen:
        reply = served["search"](req)
    assert seen.by == {"native/": 1}
    assert all(set(r.properties.non_ref_props.fields) == {"title"}
               for r in reply.results)


def test_a_stored_value_outside_the_rules_takes_the_python_path(served):
    """An object whose ``title`` holds a map (auto-schema would have
    called it an object): the reply it is in is the Python path's."""
    col = served["col"]
    uid = "00000000-0000-4000-8000-0000000000f0"
    col.put_object({"title": {"nested": "map"}, "bucket": 1},
                   vector=served["vecs"][3] + 0.01, uuid=uid)
    try:
        with _Encoded() as seen:
            reply = served["search"](_near(served))
        assert seen.by == {"python/value": 1}
        hit = next(r for r in reply.results if r.metadata.id == uid)
        assert hit.properties.non_ref_props.fields["title"].HasField(
            "object_value")
    finally:
        col.delete_object(uid)
    with _Encoded() as seen:
        served["search"](_near(served))
    assert seen.by == {"native/": 1}


def test_an_acknowledged_write_is_in_the_next_native_reply(served):
    """(d) put, update of a property, delete: no cache of objects,
    frames or replies stands between a write and the next Search."""
    col = served["col"]
    uid = "00000000-0000-4000-8000-0000000000f1"
    vec = served["vecs"][9] * 3.0  # far from every row but itself
    req = pb.SearchRequest(collection="Served", limit=3, uses_123_api=True)
    req.near_vector.vector_bytes = vec.astype("<f4").tobytes()
    req.metadata.uuid = True
    req.metadata.last_update_time_unix = True

    def top():
        with _Encoded() as seen:
            reply = served["search"](req)
        assert seen.by == {"native/": 1}
        return reply.results[0]

    assert top().metadata.id != uid
    col.put_object({"title": "first", "bucket": 1}, vector=vec, uuid=uid)
    first = top()
    assert first.metadata.id == uid
    assert first.properties.non_ref_props.fields["title"].text_value == "first"
    time.sleep(0.002)
    col.put_object({"title": "second", "bucket": -5}, vector=vec, uuid=uid)
    second = top()
    assert second.metadata.id == uid
    fields = second.properties.non_ref_props.fields
    assert fields["title"].text_value == "second"
    assert fields["bucket"].int_value == -5
    assert second.metadata.last_update_time_unix > \
        first.metadata.last_update_time_unix
    col.delete_object(uid)
    assert top().metadata.id != uid


def test_a_damaged_frame_fails_its_request_alone(served):
    """(e) over the socket: the chaos point ``kv.get_many`` corrupts the
    frames of one read; that request fails as it did when ``from_bytes``
    met them at the fetch, the next one is answered, the process lives."""
    from weaviate_tpu.runtime import faultline

    req = _near(served, distance=True)
    with faultline.injected(
            "kv.get_many", action="corrupt", times=1,
            match=lambda a: a.get("bucket") == "objects") as sched:
        with _Encoded() as seen, pytest.raises(grpc.RpcError) as err:
            served["search"](req)
        assert sched.injected == 1
    assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    assert "version" in err.value.details()
    assert seen.by == {"python/value": 1}
    with _Encoded() as seen:
        assert len(served["search"](req).results) == 5
    assert seen.by == {"native/": 1}


def test_the_readers_series_are_stamped_once_a_native_search(served):
    """(g) one ``fetch``, one ``reply``, one ``handler_cpu`` observation
    a Search, and the additive stages still sum to the residency."""
    every = tailboard.REQUEST_STAGES + tailboard.REQUEST_EXTRAS

    def read():
        tailboard.flush()
        return {s: (request_stage_seconds.labels("grpc.search", s).count,
                    request_stage_seconds.labels("grpc.search", s).total)
                for s in every}

    base = read()
    n = 9
    with _Encoded() as seen:
        for i in range(n):
            served["search"](_near(served, k=10, row=i, distance=True))
    assert seen.by == {"native/": n}
    deadline = time.time() + 10.0
    while time.time() < deadline:
        now = read()
        if now["server_residency"][0] - base["server_residency"][0] == n:
            break
        time.sleep(0.02)
    assert {s: now[s][0] - base[s][0] for s in every} == {s: n for s in every}
    total = {s: now[s][1] - base[s][1] for s in every}
    assert total["fetch"] > 0 and total["reply"] > 0
    assert 0 < total["handler_cpu"] < total["server_residency"]
    assert sum(total[s] for s in tailboard.REQUEST_STAGES) == \
        pytest.approx(total["server_residency"], rel=1e-9)


def test_the_span_of_the_request_says_which_encoder(served):
    from weaviate_tpu.runtime import tracing

    def root_of(req):
        tracing.clear_traces()
        served["search"](req)
        deadline = time.time() + 5.0
        while time.time() < deadline:
            for tr in tracing.recent_traces(5):
                for sp in tr["spans"]:
                    if sp["name"] == "grpc.Search":
                        return sp
            time.sleep(0.01)
        raise AssertionError("no grpc.Search span")

    assert root_of(_near(served))["attrs"]["reply_path"] == "native"
    legacy = _near(served)
    legacy.uses_123_api = False
    assert root_of(legacy)["attrs"]["reply_path"] == "python"


def test_without_the_native_library_the_served_answers_are_unchanged(
        tmp_path):
    """(c) the same drive in a process that has ``WEAVIATE_TPU_NO_NATIVE=1``
    and in this one: the same bytes (``took`` cleared), the other
    encoder."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env.update({"WEAVIATE_TPU_NO_NATIVE": "1", "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.path.dirname(here) + os.pathsep
                + env.get("PYTHONPATH", "")})
    out = subprocess.run(
        [sys.executable, os.path.join(here, "reply_reference.py"),
         str(tmp_path / "without")],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    without = json.loads(out.stdout[out.stdout.index("{"):])
    with_native = ref.served_answers(str(tmp_path / "with"))
    n = len(with_native["replies"])
    assert with_native["encoded"]["native/"] == n
    assert without["encoded"]["python/no_native"] == n
    assert without["encoded"]["native/"] == 0
    assert without["replies"] == with_native["replies"]
