"""Scalar quantization as a deployment (ISSUE 32): what only ``sq`` has.
The lifecycle it shares with ``pq`` (exact below ``trainingLimit``, one
fit, the swap under import, restart) runs in ``tests/test_pq_lifecycle.py``,
whose ``life`` fixture takes the quantizer as a parameter. Here: the keys
and what is refused, the device codes and integer scores against the plain
numpy quantizer (``tests/sq_reference.py``), the masked scan, rows outside
the trained range, and the served path over REST and gRPC.
CPU, small sizes: nothing here is a device time."""

import copy
import json

import numpy as np
import pytest

import sq_reference
from test_pq_lifecycle import (GIST, K, LIMIT, class_json, clustered,
                               index_of, judged, put, reference, uid)
from weaviate_tpu.api.client import Client, RestError
from weaviate_tpu.api.rest import (RestServer, class_to_wire,
                                   config_from_json)
from weaviate_tpu.db.database import Database
from weaviate_tpu.engine.quantized import QuantizedVectorStore
from weaviate_tpu.engine.store import DeviceVectorStore
from weaviate_tpu.runtime import tracing
from weaviate_tpu.schema.config import CollectionConfig

DIM = 32


@pytest.fixture(autouse=True)
def _leave_no_sampled_trace_behind():
    yield
    tracing.clear_traces()


def sq_class(name="Gist", **kw) -> dict:
    return class_json(name, quantizer="sq", **kw)


# -- the keys: parse, validate, round-trip, refuse ---------------------------


@pytest.mark.parametrize("sq,limit,rescore", [
    ({"enabled": True}, 100_000, 16),
    ({"enabled": True, "trainingLimit": 5000}, 5000, 16),
    ({"enabled": True, "trainingLimit": 1, "rescoreLimit": 4}, 1, 4),
], ids=["defaults", "limit", "rescore"])
def test_the_sq_keys_round_trip(sq, limit, rescore):
    cfg = config_from_json({"class": "C", "vectorIndexType": "flat",
                            "vectorIndexConfig": {"sq": sq}})
    cfg.validate()
    ix = cfg.vector_config("").index
    assert (ix.quantization, ix.sq_training_limit, ix.rescore_limit,
            ix.training_limit) == ("sq", limit, rescore, limit)
    assert not ix.compress_due(limit - 1) and ix.compress_due(limit)
    wire = class_to_wire(cfg)["vectorIndexConfig"]
    assert wire["sq"] == {"enabled": True, "trainingLimit": limit,
                          "rescoreLimit": rescore}
    assert wire["pq"]["enabled"] is False and wire["bq"]["enabled"] is False
    again = config_from_json(class_to_wire(cfg)).vector_config("").index
    stored = CollectionConfig.from_dict(
        json.loads(json.dumps(cfg.to_dict()))).vector_config("").index
    assert again == ix and stored == ix


REFUSED = {
    "pq+sq": ({"vectorIndexConfig": {"pq": {"enabled": True},
                                     "sq": {"enabled": True}}}, "pq and sq"),
    "bq+sq": ({"vectorIndexConfig": {"bq": {"enabled": True},
                                     "sq": {"enabled": True}}}, "bq and sq"),
    "pq+bq": ({"vectorIndexConfig": {"pq": {"enabled": True},
                                     "bq": {"enabled": True}}}, "bq and pq"),
    "hnsw": ({"vectorIndexType": "hnsw",
              "vectorIndexConfig": {"sq": {"enabled": True}}}, "hnsw"),
    "ivf": ({"vectorIndexType": "ivf",
             "vectorIndexConfig": {"sq": {"enabled": True}}}, "ivf"),
    "epochs": ({"vectorIndexConfig": {"sq": {"enabled": True},
                                      "epoch_rows": 64}}, "epoch_rows"),
    "prefix": ({"vectorIndexConfig": {"sq": {"enabled": True},
                                      "prefix_bits": 128}}, "prefix_bits"),
    "manhattan": ({"vectorIndexConfig": {"sq": {"enabled": True},
                                         "distance": "manhattan"}},
                  "manhattan"),
    "limit-not-int": ({"vectorIndexConfig": {
        "sq": {"enabled": True, "trainingLimit": "many"}}}, "trainingLimit"),
    "limit-zero": ({"vectorIndexConfig": {
        "sq": {"enabled": True, "trainingLimit": 0}}}, "trainingLimit"),
    # a compression this tree does not know is named, not dropped: before
    # this PR sq itself was such a key, and built a float32 class with a 200
    "rq": ({"vectorIndexConfig": {"rq": {"enabled": True, "bits": 8}}},
           "vectorIndexConfig.rq"),
    "whatever-next": ({"vectorIndexConfig": {"zq": {"enabled": True}}},
                      "vectorIndexConfig.zq"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_what_cannot_be_honoured_is_refused(case):
    body, match = REFUSED[case]
    with pytest.raises(ValueError, match=match):
        config_from_json(dict({"class": "C", "vectorIndexType": "flat"},
                              **body)).validate()


def test_a_disabled_or_absent_block_is_not_a_request():
    cfg = config_from_json({"class": "C", "vectorIndexConfig": {
        "rq": {"enabled": False}, "sq": {"enabled": False},
        "skip": True, "cleanupIntervalSeconds": 300}})
    cfg.validate()
    assert cfg.vector_config("").index.quantization is None


def test_rest_answers_422_and_writes_sq_back(tmp_path):
    db = Database(str(tmp_path))
    srv = RestServer(db)
    srv.start()
    try:
        client = Client(srv.address)
        for case in ("pq+sq", "hnsw", "rq"):
            body, match = REFUSED[case]
            with pytest.raises(RestError) as e:
                client.create_class(dict({"class": "Bad",
                                          "vectorIndexType": "flat"}, **body))
            assert e.value.status == 422 and match in str(e.value), case
        assert "Bad" not in db.collections
        client.create_class(sq_class("Kept"))
        wire = client.get_class("Kept")["vectorIndexConfig"]
        assert wire["sq"] == {"enabled": True, "trainingLimit": LIMIT,
                              "rescoreLimit": 16}
        ix = db.get_collection("Kept").config.vector_config("").index
        assert ix.quantization == "sq"
    finally:
        srv.stop()
        db.close()


@pytest.mark.parametrize("body,match", [
    ({"threshold": 0}, "threshold"),
    ({"threshold": "many"}, "threshold"),
    ({"threshold": True}, "threshold"),
    ({"hnsw": {"pq": {"enabled": True}}}, "vectorIndexConfig.hnsw.pq"),
    ({"flat": {"bq": {"enabled": True}}}, "vectorIndexConfig.flat.bq"),
], ids=["zero", "not-int", "bool", "hnsw.pq", "flat.bq"])
def test_a_dynamic_class_refuses_what_it_cannot_honour(body, match):
    """ISSUE 43: upstream's ``threshold`` is read (it used to be written
    back and never read), and a compression nested under one of upstream's
    two regimes is refused, not dropped."""
    with pytest.raises(ValueError, match=match):
        config_from_json({"class": "C", "vectorIndexType": "dynamic",
                          "vectorIndexConfig": body}).validate()


def test_rest_takes_stores_and_gives_back_a_dynamic_threshold(tmp_path):
    db = Database(str(tmp_path))
    srv = RestServer(db)
    srv.start()
    try:
        client = Client(srv.address)
        for bad in ({"threshold": 0}, {"hnsw": {"pq": {"enabled": True}}}):
            with pytest.raises(RestError) as e:
                client.create_class({"class": "Bad",
                                     "vectorIndexType": "dynamic",
                                     "vectorIndexConfig": bad})
            assert e.value.status == 422, bad
        assert "Bad" not in db.collections
        client.create_class({
            "class": "Grows", "vectorIndexType": "dynamic",
            "vectorIndexConfig": {"distance": "cosine", "threshold": 2500,
                                  "hnsw": {"pq": {"enabled": False}},
                                  "flat": {"vectorCacheMaxObjects": 10}}})
        wire = client.get_class("Grows")
        assert wire["vectorIndexType"] == "dynamic"
        assert wire["vectorIndexConfig"]["threshold"] == 2500
        ix = db.get_collection("Grows").config.vector_config("").index
        assert (ix.index_type, ix.flat_to_ann_threshold, ix.metric) == (
            "dynamic", 2500, "cosine")
        stored = CollectionConfig.from_dict(json.loads(json.dumps(
            db.get_collection("Grows").config.to_dict())))
        assert stored.vector_config("").index.flat_to_ann_threshold == 2500
        # the native key still passes; absent, the default stands
        again = config_from_json({"class": "C", "vectorIndexType": "dynamic",
                                  "vectorIndexConfig": {
                                      "flat_to_ann_threshold": 77}})
        assert again.vector_config("").index.flat_to_ann_threshold == 77
        assert config_from_json({"class": "C", "vectorIndexType": "dynamic"}
                                ).vector_config("").index \
            .flat_to_ann_threshold == 10_000
    finally:
        srv.stop()
        db.close()


def test_a_mesh_sharded_database_refuses_the_class(tmp_path):
    from weaviate_tpu.parallel.mesh import make_mesh

    db = Database(str(tmp_path), mesh=make_mesh())
    try:
        with pytest.raises(ValueError, match="mesh"):
            db.create_collection(config_from_json(sq_class()))
        assert "Gist" not in db.collections
    finally:
        db.close()


@pytest.mark.parametrize("kw,match", [
    (dict(mesh="mesh"), "mesh"),
    (dict(prefix_bits=128), "prefix_bits"),
    (dict(metric="hamming"), "hamming"),
    (dict(dim=20_000), "int32"),
], ids=["mesh", "prefix", "metric", "too-wide"])
def test_the_store_refuses_what_it_cannot_scan(kw, match):
    if kw.get("mesh"):
        from weaviate_tpu.parallel.mesh import make_mesh

        kw = dict(mesh=make_mesh())
    with pytest.raises(ValueError, match=match):
        QuantizedVectorStore(**dict(dict(dim=DIM, quantization="sq"), **kw))


def test_an_epoch_store_refuses_sq():
    from weaviate_tpu.engine.epochs import EpochStore

    with pytest.raises(ValueError, match="epoch"):
        EpochStore(dim=DIM, epoch_rows=64, quantization="sq")


# -- the device quantizer against the plain one ------------------------------


def far_apart(seed: int, rows: int, dim: int):
    """Rows and queries whose components sit at both ends of the range, so
    that code sums pass 2^24 at 960 dimensions (what float32 holds exactly)
    and a scan that accumulated in floats would show."""
    rng = np.random.default_rng(seed)
    ends = rng.integers(0, 2, (rows + 16, dim)).astype(np.float32) * 8 - 4
    ends += 0.35 * rng.standard_normal(ends.shape).astype(np.float32)
    return ends[:rows], ends[rows:]


@pytest.mark.parametrize("metric", ["l2-squared", "cosine"])
@pytest.mark.parametrize("dim,data", [(DIM, "clustered"),
                                      (960, "far-apart")])
def test_device_codes_and_scores_equal_the_plain_quantizers(dim, data,
                                                            metric):
    """``sq_encode``: the bytes, bit for bit. ``_code_scores`` (what
    ``sq_topk``'s chunk body calls): every integer, equal. ``sq_topk``: the
    distances of its candidates equal to the reference's one rounding of
    those integers, and the candidate set equal but among rows whose
    distance IS the cut's (ties bounded by value)."""
    import jax
    import jax.numpy as jnp

    from weaviate_tpu.ops import sq as sq_ops

    rows = 2048
    corpus, queries = (clustered(7 + dim, rows, dim) if data == "clustered"
                       else far_apart(7, rows, dim))
    queries = queries[:16]
    if metric == "cosine":
        corpus = reference.prepare(corpus, metric)
        queries = reference.prepare(queries, metric)
    quantizer = sq_ops.sq_fit(corpus[:1024])
    a, b = sq_reference.fit(corpus[:1024])
    assert (quantizer.a, quantizer.b) == (a, b)
    assert float(quantizer.params[1]) == sq_reference.scale_of(b)
    params = quantizer.params
    codes, terms = (np.asarray(x) for x in sq_ops.sq_encode(
        jnp.asarray(corpus), params, metric))
    plain = sq_reference.encode(a, b, corpus)
    assert codes.dtype == np.int8 and terms.dtype == np.int32
    assert np.array_equal(codes.view(np.uint8) ^ 0x80, plain)
    want_terms = (((plain.astype(np.int64) - 128) ** 2).sum(-1)
                  if metric == "l2-squared" else plain.astype(np.int64).sum(-1))
    assert np.array_equal(terms, want_terms)

    q_plain = sq_reference.encode(a, b, queries)
    q_codes = jax.jit(sq_ops._encode_rows)(jnp.asarray(queries), params)
    assert np.array_equal(np.asarray(q_codes).view(np.uint8) ^ 0x80, q_plain)
    scores = np.asarray(jax.jit(
        sq_ops._code_scores, static_argnames="metric")(
        q_codes, sq_ops.sq_row_terms(q_codes, metric), jnp.asarray(codes),
        jnp.asarray(terms), metric=metric))
    want = np.stack([sq_reference.code_scores(qc, plain, metric)
                     for qc in q_plain])
    assert scores.dtype == np.int32 and np.array_equal(scores, want)
    if data == "far-apart":
        assert want.max() > 2 ** 24     # past what a float sum keeps exact

    n_cand = 16 * K
    dists, ids = sq_ops.sq_topk(
        jnp.asarray(queries), jnp.asarray(codes), jnp.asarray(terms), params,
        k=n_cand, chunk_size=512, metric=metric)
    dists, ids = np.asarray(dists), np.asarray(ids)
    for r, q in enumerate(queries):
        order, dist, _ = sq_reference.candidates(a, b, plain, q, metric,
                                                 n_cand)
        got = set(ids[r].tolist())
        assert len(got) == n_cand and -1 not in got
        if metric == "l2-squared":
            # integer -> float32, times s^2: one rounding, the same one
            assert np.array_equal(dists[r], dist[ids[r]])
        else:
            # D a^2, a s (sums) and s^2 (scores) nearly cancel: float32
            np.testing.assert_allclose(dists[r], dist[ids[r]], atol=1e-5)
        cut = dist[order[-1]]
        for row in got ^ set(order.tolist()):
            assert abs(dist[row] - cut) <= 1e-5 * max(1.0, abs(cut)), (
                r, row, dist[row], cut)


@pytest.mark.parametrize("metric", ["l2-squared", "cosine"])
def test_the_masked_scan_returns_allowed_live_rows_only(metric):
    """A shared mask, per-query masks (``allow_bits`` inside ``sq_topk``)
    and deleted rows, against the plain quantizer's masked search."""
    corpus, queries = clustered(41, 1024)
    queries = queries[:8]
    store = QuantizedVectorStore(dim=DIM, metric=metric, quantization="sq",
                                 capacity=1024, chunk_size=256)
    store.train(corpus[:512])
    store.add(corpus)
    gone = np.arange(0, 1024, 7)
    store.delete(gone)
    rng = np.random.default_rng(5)
    masks = rng.random((len(queries), store.capacity)) < 0.3
    masks[0] = False                      # a filter that matches nothing
    live = np.ones(store.capacity, bool)
    live[gone] = False
    a, b = store.sq_quantizer[:2]
    plain = np.asarray(store.codes).view(np.uint8) ^ 0x80
    unit = np.array(store.rescore_rows)[:, :DIM]
    d_rows, i_rows = store.search(queries, K, allow_mask=masks)
    d_one, i_one = store.search(queries, K, allow_mask=masks[1])
    for r, q in enumerate(reference.prepare(queries, metric) if
                          metric == "cosine" else queries):
        for got_d, got_i, mask in ((d_rows[r], i_rows[r], masks[r]),
                                   (d_one[r], i_one[r], masks[1])):
            found = got_i[got_i >= 0]
            assert mask[found].all() and live[found].all()
            want, want_d = sq_reference.search(
                a, b, plain, unit, q, metric, K, store.rescore_limit,
                mask & live)
            n = int((mask & live).sum())
            assert len(found) == min(K, n)
            if n:
                assert set(found.tolist()) == set(want[:len(found)].tolist())
                np.testing.assert_allclose(got_d[:len(found)],
                                           want_d[:len(found)], atol=1e-4)


def test_rows_outside_the_trained_range_clip_and_are_still_found():
    """Trained on rows in [-1, 1]; later rows reach 40. Their codes
    saturate, several share one code, and the float32 rescore tells them
    apart and returns their exact distances."""
    rng = np.random.default_rng(9)
    inside = rng.uniform(-1, 1, (512, DIM)).astype(np.float32)
    outside = (inside[:64] * 40).astype(np.float32)
    store = QuantizedVectorStore(dim=DIM, metric="l2-squared",
                                 quantization="sq", capacity=1024,
                                 chunk_size=256)
    store.train(inside)
    assert tuple(store.sq_quantizer[:2]) == sq_reference.fit(inside)
    slots = np.concatenate([store.add(inside), store.add(outside)])
    codes = np.asarray(store.codes).view(np.uint8)[slots[512:]] ^ 0x80
    assert np.isin(codes, (0, 255)).mean() > 0.9     # clipped
    assert np.array_equal(codes, sq_reference.encode(
        *store.sq_quantizer[:2], outside))
    for j in (0, 17, 63):
        d, i = store.search(outside[j], 3)
        assert i[0] == slots[512 + j] and d[0] < 1e-3
        # clipped rows look alike to the codes (the candidates are a
        # draw among them); what comes back carries its exact distance
        exact = ((outside[j] - np.concatenate([inside, outside])) ** 2
                 ).sum(-1)
        where = {int(s_): n for n, s_ in enumerate(slots)}
        assert d[1] == pytest.approx(exact[where[int(i[1])]], rel=1e-5)


def test_a_codes_only_snapshot_restores_codes_and_terms():
    corpus, queries = clustered(43, 600)
    store = QuantizedVectorStore(dim=DIM, quantization="sq", capacity=1024,
                                 chunk_size=256, rescore="none")
    store.train(corpus[:300])
    store.add(corpus)
    store.delete([5, 6])
    snap = store.snapshot()
    assert "vectors" not in snap and snap["codes"].dtype == np.int8
    twin = QuantizedVectorStore.restore(snap)
    assert tuple(twin.sq_quantizer[:2]) == tuple(store.sq_quantizer[:2])
    live = np.nonzero(snap["valid"])[0]
    assert np.array_equal(np.asarray(twin.codes)[live],
                          np.asarray(store.codes)[live])
    assert np.array_equal(np.asarray(twin.row_terms)[live],
                          np.asarray(store.row_terms)[live])
    d0, i0 = store.search(queries[:4], K)
    d1, i1 = twin.search(queries[:4], K)
    assert np.array_equal(i0, i1) and np.array_equal(d0, d1)
    # code distances in the rows' own units: near the exact ones
    exact = ((queries[0] - corpus[i0[0]]) ** 2).sum(-1)
    np.testing.assert_allclose(d0[0], exact, rtol=0.05)


def test_compaction_and_growth_carry_the_third_layout():
    corpus, _ = clustered(47, 3000)
    store = QuantizedVectorStore(dim=DIM, quantization="sq", capacity=512,
                                 chunk_size=256)
    store.train(corpus[:512])
    store.add(corpus)                              # grows 512 -> 4096
    assert store.codes.shape == (4096, DIM)
    assert store.row_terms.shape == (4096,)
    plain = sq_reference.encode(*store.sq_quantizer[:2], corpus)
    assert np.array_equal(
        np.asarray(store.codes)[:3000].view(np.uint8) ^ 0x80, plain)
    store.delete(np.arange(0, 3000, 2))
    mapping = store.compact()
    kept = np.arange(1, 3000, 2)
    assert np.array_equal(mapping[kept], np.arange(1500))
    assert np.array_equal(
        np.asarray(store.codes)[:1500].view(np.uint8) ^ 0x80, plain[kept])
    assert np.array_equal(
        np.asarray(store.row_terms)[:1500],
        ((plain[kept].astype(np.int64) - 128) ** 2).sum(-1))
    d, i = store.search(corpus[2999], 1)
    assert i[0] == 1499 and d[0] < 1e-5


# -- through the collection and over the wire --------------------------------


def test_a_filter_on_the_compressed_class(tmp_path):
    from weaviate_tpu.filters.filters import Filter, Operator

    corpus, queries = clustered(51, 1500)
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(sq_class()))
        for start in range(0, 1500, 500):
            put(col, corpus, range(start, start + 500))
        assert index_of(col).store.quantization == "sq"
        where = Filter.where("bucket", Operator.LESS_THAN, 30)
        allowed = np.arange(1500) % 100 < 30
        for q in queries[:8]:
            res = col.near_vector(q, k=K, where=where,
                                  include_objects=False)
            exact = np.where(allowed, ((q - corpus) ** 2).sum(-1), np.inf)
            assert [r.uuid for r in res] == [
                uid(int(i)) for i in np.argsort(exact)[:K]]
    finally:
        db.close()


def test_a_dynamic_class_compresses_and_stays_flat(tmp_path):
    """Its upgrade threshold (600) lies under the training limit: the IVF
    index it would upgrade into has no sq form, so it stays flat (as
    dynamic + bq does) and the limit finds full rows to fit on."""
    corpus, queries = clustered(53, 1500)
    db = Database(str(tmp_path))
    try:
        cfg = config_from_json(sq_class(index_type="dynamic"))
        cfg.vectors[0].index.flat_to_ann_threshold = 600
        col = db.create_collection(cfg)
        for start in range(0, 1500, 500):
            put(col, corpus, range(start, start + 500))
            assert judged(col, queries, corpus[:start + 500],
                          GIST)["correct"]
        idx = index_of(col)
        assert idx.compressed and not idx.upgraded
        assert idx.store.quantization == "sq"
    finally:
        db.close()


def test_a_live_update_to_sq_defers_and_later_fires(tmp_path):
    corpus, queries = clustered(57, 1500)
    db = Database(str(tmp_path))
    try:
        plain = sq_class()
        plain["vectorIndexConfig"].pop("sq")
        col = db.create_collection(config_from_json(plain))
        put(col, corpus, range(0, 600))
        new = copy.deepcopy(col.config)
        new.vectors[0].index.quantization = "sq"
        new.vectors[0].index.sq_training_limit = LIMIT
        db.update_collection(new)
        assert col.config.vectors[0].index.training_limit == LIMIT
        assert type(index_of(col).store) is DeviceVectorStore
        put(col, corpus, range(600, 1500))
        store = index_of(col).store
        assert isinstance(store, QuantizedVectorStore)
        assert tuple(store.sq_quantizer[:2]) == sq_reference.fit(corpus[:LIMIT])
        assert judged(col, queries, corpus, GIST)["correct"]
    finally:
        db.close()


def test_a_served_sq_class_over_rest_and_grpc(tmp_path, monkeypatch):
    """Class over REST, import over gRPC BatchObjects with every request
    sampled, searches over gRPC judged at the configuration's limits, then
    the debug surfaces: spans, series, the HBM ledger's components, the
    explain note."""
    import wire  # benchmarks/wire.py

    from weaviate_tpu.config import ServerConfig
    from weaviate_tpu.server import Server

    monkeypatch.setenv("TRACE_SAMPLE_RATE", "1000")   # >= 1: always
    tracing.reset_policy_for_tests()
    corpus, queries = clustered(59, 1536)
    server = Server(ServerConfig(data_path=str(tmp_path), rest_port=0,
                                 grpc_port=0, disable_telemetry=True)).start()
    try:
        rest, grpc = wire.Rest(server.rest.address), wire.Grpc(server.grpc.port)
        labels = {"quantization": "sq", "stage": "swap"}
        before = rest.metrics().total(
            "weaviate_tpu_index_compress_seconds_count", labels)
        rest.create_class(sq_class())
        grpc.import_rows("Gist", corpus,
                         {"bucket": np.arange(len(corpus)) % 100}, 512)
        page = rest.metrics()
        assert page.total("weaviate_tpu_index_compress_seconds_count",
                          labels) == before + 1
        assert page.total("weaviate_tpu_index_compress_total",
                          {"quantization": "sq", "result": "ok"}) >= 1
        spans = [s for t in json.loads(rest.request(
            "GET", "/v1/debug/traces?limit=50"))["traces"]
            for s in t["spans"]]
        names = {s["name"] for s in spans}
        for name in ("index.compress", "train", "encode", "swap",
                     "store.sq_encode"):
            assert name in names, name
        n = 32
        replies = {"query": np.arange(n, dtype=np.int32),
                   "bound": np.full(n, -1, np.int64),
                   "failed": np.zeros(n, bool),
                   "n_results": np.zeros(n, np.int32),
                   "ids": np.full((n, K), -1, np.int64),
                   "dists": np.full((n, K), np.nan)}
        for r in range(n):
            ids, dists = grpc.search(grpc.search_request(
                "Gist", queries[r], {"metadata": ["uuid", "distance"]}, K,
                None, -1))
            replies["n_results"][r] = len(ids)
            replies["ids"][r, :len(ids)] = ids
            replies["dists"][r, :len(ids)] = dists
        verdict = reference.judge(
            replies, queries, corpus, {"bucket": np.arange(1536) % 100},
            GIST["metric"], K, None, GIST["limits"])
        assert verdict["correct"], verdict["numbers"]
        scans = [s for t in json.loads(rest.request(
            "GET", "/v1/debug/traces?limit=50"))["traces"]
            for s in t["spans"] if s["name"] == "store.quantized_scan"]
        assert scans and all(s["attrs"]["quantization"] == "sq"
                             for s in scans)
        memory = json.loads(rest.request("GET", "/v1/debug/memory"))
        comps = memory["ledger"]["collections"]["Gist"]["components"]
        # the ledger adds up every live store of a collection of this
        # name in the process: at least this shard's 8,192 slots
        assert comps["codes"] >= 8192 * DIM
        assert comps["row_terms"] >= 8192 * 4
        grpc.close()
    finally:
        server.stop()
        tracing.reset_policy_for_tests()


def test_the_explain_note_names_the_quantizer():
    from weaviate_tpu.runtime import kernelscope

    corpus, queries = clustered(61, 600)
    store = QuantizedVectorStore(dim=DIM, quantization="sq", capacity=1024,
                                 chunk_size=256)
    store.train(corpus[:300])
    store.add(corpus)
    with kernelscope.explain_scope({}) as plan:
        store.search(queries[:2], K)
    note = plan["quantized"]
    assert note["quantization"] == "sq" and note["k_cand"] == 16 * K
