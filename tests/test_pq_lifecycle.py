"""Product quantization as a deployment (ISSUE 28): a class created with
``vectorIndexConfig.pq.enabled`` answers exactly from full rows until its
shard holds ``pq.trainingLimit`` vectors, then fits its codebook once on
the first ``trainingLimit`` rows, swaps its store under the import and
goes on compressed: through the normal path (REST class JSON, Collection
import in batches that straddle the limit), judged by the benchmark's own
reference (``benchmarks/reference.py``) at the limits of
``benchmarks/configs/deep-pq-cosine.json``; and the device scan against a
plain numpy quantizer (``tests/pq_reference.py``) on a shared codebook.
The lifecycle is the same for a class created with
``vectorIndexConfig.sq.enabled`` (ISSUE 32: two scalars where pq fits a
codebook), so the ``life`` fixture runs it for that quantizer too, at the
limits of ``benchmarks/configs/gist-sq-l2.json`` and against
``tests/sq_reference.py``; what only sq has is in
``tests/test_sq_lifecycle.py``.
CPU, small sizes: nothing here is a device time."""

import copy
import json
import os
import sys
import threading
import types
import uuid as uuid_mod

import numpy as np
import pytest

import pq_reference
import sq_reference
from weaviate_tpu.api.client import Client, RestError
from weaviate_tpu.api.rest import (RestServer, class_to_wire,
                                   config_from_json)
from weaviate_tpu.db.database import Database
from weaviate_tpu.engine.quantized import QuantizedVectorStore
from weaviate_tpu.engine.store import DeviceVectorStore
from weaviate_tpu.runtime import metrics, tracing
from weaviate_tpu.schema.config import CollectionConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import reference  # noqa: E402 — the benchmark's plain reference and judge

with open(os.path.join(REPO, "benchmarks", "configs",
                       "deep-pq-cosine.json")) as _f:
    DEEP = json.load(_f)
with open(os.path.join(REPO, "benchmarks", "configs",
                       "gist-sq-l2.json")) as _f:
    GIST = json.load(_f)
# the configuration a quantizer's lifecycle is judged by
CONFIG_OF = {"pq": DEEP, "sq": GIST}

DIM, K = 32, DEEP["k"]
LIMIT, ROWS, BATCH = 1024, 2400, 300    # batch 4 (rows 900..1199) crosses
CROSSING = range(900, 1200)


@pytest.fixture(autouse=True)
def _leave_no_sampled_trace_behind():
    """The trace ring is the process's: tests of other files, run later by
    the same worker, assert on what it holds."""
    yield
    tracing.clear_traces()


def uid(i: int) -> str:
    return str(uuid_mod.UUID(int=i + 1))


def row_of(u: str) -> int:
    return uuid_mod.UUID(u).int - 1


def clustered(seed: int, rows: int, dim: int = DIM):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((rows // 8, dim)).astype(np.float32)
    draw = lambda n: (centers[rng.integers(0, len(centers), n)]  # noqa: E731
                      + 0.35 * rng.standard_normal((n, dim))
                      ).astype(np.float32)
    return draw(rows), draw(64)


def class_json(name: str, segments: int = DIM, limit: int = LIMIT,
               index_type: str = "flat", quantizer: str = "pq") -> dict:
    """The configuration's own class, cut to the test's width and limit."""
    klass = copy.deepcopy(CONFIG_OF[quantizer]["class"])
    klass["class"] = name
    klass["vectorIndexType"] = index_type
    klass["vectorIndexConfig"][quantizer].update(trainingLimit=limit)
    if quantizer == "pq":
        klass["vectorIndexConfig"]["pq"].update(segments=segments)
    return klass


def put(col, corpus, rows):
    out = col.batch_put([{"uuid": uid(i), "properties": {"bucket": i % 100},
                          "vector": corpus[i]} for i in rows])
    assert all(r["status"] == "SUCCESS" for r in out)


def index_of(col):
    return next(iter(col.shards.values())).vector_indexes[""]


def replies_of(col, queries, which):
    """near_vector answers in the shape ``reference.judge`` takes."""
    n = len(which)
    out = {"query": np.asarray(which, np.int32),
           "bound": np.full(n, -1, np.int64), "failed": np.zeros(n, bool),
           "n_results": np.zeros(n, np.int32),
           "ids": np.full((n, K), -1, np.int64),
           "dists": np.full((n, K), np.nan)}
    for r, qi in enumerate(which):
        res = col.near_vector(queries[qi], k=K, include_objects=False)
        out["n_results"][r] = len(res)
        out["ids"][r, :len(res)] = [row_of(x.uuid) for x in res]
        out["dists"][r, :len(res)] = [x.distance for x in res]
    return out


def judged(col, queries, corpus_so_far, cfg=DEEP):
    props = {"bucket": np.arange(len(corpus_so_far)) % 100}
    return reference.judge(
        replies_of(col, queries, range(len(queries))), queries,
        corpus_so_far, props, cfg["metric"], K, None, cfg["limits"])


# -- the two keys: parse, validate, round-trip -------------------------------


@pytest.mark.parametrize("pq,limit,encoder", [
    ({"enabled": True}, 100_000, "kmeans"),
    ({"enabled": True, "trainingLimit": 5000}, 5000, "kmeans"),
    ({"enabled": True, "trainingLimit": 256, "centroids": 256,
      "encoder": {"type": "kmeans", "distribution": "log-normal"}},
     256, "kmeans"),
], ids=["defaults", "limit", "encoder"])
def test_training_limit_and_encoder_round_trip(pq, limit, encoder):
    cfg = config_from_json({"class": "C", "vectorIndexType": "flat",
                            "vectorIndexConfig": {"pq": pq}})
    cfg.validate()
    ix = cfg.vector_config("").index
    assert (ix.quantization, ix.pq_training_limit, ix.pq_encoder) == (
        "pq", limit, encoder)
    wire = class_to_wire(cfg)["vectorIndexConfig"]["pq"]
    assert wire["trainingLimit"] == limit
    assert wire["encoder"] == {"type": encoder}
    # the wire form parses back to the same config, and so does the
    # persisted (snake_case) schema
    again = config_from_json(class_to_wire(cfg)).vector_config("").index
    stored = CollectionConfig.from_dict(
        json.loads(json.dumps(cfg.to_dict()))).vector_config("").index
    assert again == ix and stored == ix


@pytest.mark.parametrize("pq,match", [
    ({"enabled": True, "encoder": {"type": "tile"}}, "kmeans"),
    ({"enabled": True, "encoder": "kmeans"}, "object"),
    ({"enabled": True, "centroids": 256, "trainingLimit": 100},
     "trainingLimit"),
    ({"enabled": True, "trainingLimit": "many"}, "trainingLimit"),
], ids=["tile", "encoder-not-object", "limit-under-centroids",
        "limit-not-int"])
def test_bad_training_limit_or_encoder_is_refused(pq, match):
    with pytest.raises(ValueError, match=match):
        config_from_json({"class": "C", "vectorIndexType": "flat",
                          "vectorIndexConfig": {"pq": pq}}).validate()


def test_rest_refuses_a_tile_encoder_and_writes_the_keys_back(tmp_path):
    db = Database(str(tmp_path))
    srv = RestServer(db)
    srv.start()
    try:
        client = Client(srv.address)
        bad = class_json("Tile")
        bad["vectorIndexConfig"]["pq"]["encoder"] = {"type": "tile"}
        with pytest.raises(RestError) as e:
            client.create_class(bad)
        assert e.value.status == 422
        client.create_class(class_json("Kept"))
        pq = client.get_class("Kept")["vectorIndexConfig"]["pq"]
        assert pq["trainingLimit"] == LIMIT
        assert pq["encoder"] == {"type": "kmeans"}
    finally:
        srv.stop()
        db.close()


# -- the lifecycle through the normal path -----------------------------------


@pytest.fixture(scope="module",
                params=[("pq", DIM), ("pq", DIM // 4), ("sq", DIM)],
                ids=["m=dim", "m=dim/4", "sq"])
def life(request, tmp_path_factory):
    """One import through REST class JSON + Collection batches, with what
    the tests look at kept from each moment of it; once a quantizer."""
    quantizer, segments = request.param
    cfg = CONFIG_OF[quantizer]
    data_dir = str(tmp_path_factory.mktemp(f"{quantizer}{segments}"))
    corpus, queries = clustered(28 + segments + (quantizer == "sq"), ROWS)
    db = Database(data_dir)
    srv = RestServer(db)
    srv.start()
    out = types.SimpleNamespace(quantizer=quantizer, cfg=cfg,
                                segments=segments, corpus=corpus,
                                queries=queries, data_dir=data_dir)
    try:
        Client(srv.address).create_class(
            class_json("Deep", segments, quantizer=quantizer))
        out.wire = Client(srv.address).get_class("Deep")["vectorIndexConfig"]
        col = db.get_collection("Deep")
        for start in range(0, CROSSING.start, BATCH):
            put(col, corpus, range(start, start + BATCH))
        out.store_before = type(index_of(col).store)
        out.before = judged(col, queries, corpus[:CROSSING.start], cfg)
        out.before_ids = replies_of(col, queries, range(16))["ids"]
        # searches from a second thread while the crossing batch trains,
        # encodes and swaps under the index's lock
        stop, seen, errors = threading.Event(), [], []

        def searcher():
            i = 0
            while not stop.is_set():
                try:
                    seen.append(len(col.near_vector(
                        queries[i % len(queries)], k=K,
                        include_objects=False)))
                except Exception as e:  # noqa: BLE001 — the test's subject
                    errors.append(repr(e))
                i += 1

        t = threading.Thread(target=searcher)
        t.start()
        with tracing.trace("test.import", force=True):
            put(col, corpus, CROSSING)
        stop.set()
        t.join()
        out.concurrent = (seen, errors)
        out.trace = next(tr for tr in tracing.recent_traces(50)
                         if any(s["name"] == "index.compress"
                                for s in tr["spans"]))
        out.store_after = index_of(col).store
        out.crossing = [
            (col.near_vector(corpus[i], k=1, include_objects=False),
             col.get_object(uid(i))) for i in CROSSING]
        with tracing.trace("test.import", force=True):
            put(col, corpus, range(CROSSING.stop, CROSSING.stop + BATCH))
        out.later_trace = tracing.recent_traces(1)[0]
        for start in range(CROSSING.stop + BATCH, ROWS, BATCH):
            put(col, corpus, range(start, start + BATCH))
        out.after = judged(col, queries, corpus, cfg)
        out.after_replies = replies_of(col, queries, range(len(queries)))
        idx = index_of(col)
        # what the quantizer fitted: pq's centroids, sq's two scalars
        out.codebook = quantizer_state(idx.store)
        out.codes = np.asarray(idx.store.codes).copy()
        out.slot_of = dict(idx._id_to_slot)
        # the float32 rows the rescore reads: resident on the device in a
        # store a Server builds, with no host copy (tests/test_device_rescore)
        assert idx.store._host_vectors is None
        out.unit_rows = np.array(idx.store.rescore_rows)[:, :DIM]
        out.rescore_limit = idx.store.rescore_limit
    finally:
        srv.stop()
        db.close()
    return out


def quantizer_state(store) -> np.ndarray:
    if store.quantization == "sq":
        return np.asarray(store.sq_quantizer[:2], np.float32)
    return np.asarray(store.codebook.centroids).copy()


def test_before_the_limit_answers_are_exact(life):
    assert life.store_before is DeviceVectorStore
    assert life.before["correct"], life.before["numbers"]
    assert life.before["recall_at_k"] == 1.0
    # the same ids, in the same order, as the reference's own top k
    exact = reference.lower_precision(
        life.queries, life.corpus[:CROSSING.start], {}, life.cfg["metric"],
        K, None, [(i, -1) for i in range(16)], "float32")
    assert np.array_equal(life.before_ids, exact["ids"])


def test_the_store_is_swapped_once_the_limit_is_crossed(life):
    store = life.store_after
    assert isinstance(store, QuantizedVectorStore) and store.trained
    # the float32 rows that rescore stay on the device, beside the codes
    assert store.rescore_mode() == "fused"
    assert store.rescore_rows.dtype == np.float32
    assert store.rescore_rows.shape == (store.capacity, 128)  # whole lanes
    assert store.quantization == life.quantizer
    assert life.wire[life.quantizer]["enabled"] is True
    assert life.wire[life.quantizer]["trainingLimit"] == LIMIT
    if life.quantizer == "sq":
        # one signed byte a dimension, and the int32 a row adds to a scan
        assert life.codes.dtype == np.int8
        assert life.codes.shape == (store.capacity, DIM)
        assert store.row_terms.dtype == np.int32
        assert store.row_terms.shape == (store.capacity,)
        assert life.codebook.shape == (2,)
        return
    assert life.codes.dtype == np.uint8
    assert life.codes.shape == (store.capacity, life.segments)
    assert life.codebook.shape == (life.segments, 256, DIM // life.segments)


def test_after_the_limit_answers_pass_the_configurations_limits(life):
    assert life.after["correct"], life.after["numbers"]
    assert life.after["recall_at_k"] >= \
        life.cfg["limits"]["recall_at_k_min"]


def test_every_object_of_the_crossing_batch_is_found_and_readable(life):
    for i, (hits, obj) in zip(CROSSING, life.crossing):
        assert [h.uuid for h in hits] == [uid(i)], i
        assert abs(hits[0].distance) < 1e-5
        assert obj is not None and obj.properties["bucket"] == i % 100
        assert np.array_equal(np.asarray(obj.vector, np.float32),
                              life.corpus[i])


def test_searches_during_the_swap_all_return(life):
    seen, errors = life.concurrent
    assert not errors, errors[:3]
    assert seen and all(n == K for n in seen)


def test_the_compression_is_traced_stage_by_stage(life):
    spans = {s["name"]: s for s in life.trace["spans"]}
    assert spans["index.compress"]["attrs"]["quantization"] == \
        life.quantizer
    for child in ("train", "encode", "swap"):
        assert spans[child]["parent_id"] == \
            spans["index.compress"]["span_id"], child
    # fitted on the FIRST trainingLimit rows, everything held encoded
    assert spans["train"]["attrs"]["rows_trained"] == LIMIT
    assert spans["encode"]["attrs"]["rows_encoded"] == CROSSING.stop
    # a later batch is encoded as it arrives, under the import's spans
    later = [s for s in life.later_trace["spans"]
             if s["name"] == f"store.{life.quantizer}_encode"]
    assert [s["attrs"]["rows"] for s in later] == [BATCH]


def test_the_compression_is_on_the_metrics_page(life):
    page = metrics.registry.expose()
    for stage in ("train", "encode", "swap"):
        assert (f'weaviate_tpu_index_compress_seconds_count{{quantization='
                f'"{life.quantizer}",stage="{stage}"}}') in page
    assert (f'weaviate_tpu_index_compress_total{{quantization='
            f'"{life.quantizer}",result="ok"}}') in page


def test_a_restart_gives_the_same_codebook_and_the_same_answers(life):
    db = Database(life.data_dir)
    try:
        col = db.get_collection("Deep")
        col.near_vector(life.queries[0], k=K)   # loads the shard
        store = index_of(col).store
        assert isinstance(store, QuantizedVectorStore)
        assert np.array_equal(quantizer_state(store), life.codebook)
        if life.quantizer == "sq":
            # the same two scalars give the same bytes, slot for slot
            assert dict(index_of(col)._id_to_slot) == life.slot_of
            assert np.array_equal(np.asarray(store.codes), life.codes)
        again = replies_of(col, life.queries, range(len(life.queries)))
        assert np.array_equal(again["ids"], life.after_replies["ids"])
        # rows that arrived compressed were normalised on the host, and
        # the rebuild normalises every row on the device: an ulp apart
        np.testing.assert_allclose(again["dists"],
                                   life.after_replies["dists"], atol=1e-6)
    finally:
        db.close()


def test_the_whole_search_agrees_with_the_plain_quantizer(life):
    """The served answers against tests/pq_reference.py's search on the
    store's own codebook and codes: the same ids but where the plain
    quantizer's candidate cut falls on near-equal ADC distances."""
    slot_row = np.full(len(life.codes), -1)
    for doc, slot in life.slot_of.items():
        slot_row[slot] = doc    # doc ids are the import's row numbers
    valid = slot_row >= 0
    agree = 0
    metric = life.cfg["metric"]
    for r, qi in enumerate(life.after_replies["query"]):
        q = reference.prepare(life.queries[qi][None], metric)[0]
        if life.quantizer == "sq":
            slots, _ = sq_reference.search(
                *life.codebook, life.codes.view(np.uint8) ^ 0x80,
                life.unit_rows, q, metric, K, life.rescore_limit, valid)
        else:
            slots, _ = pq_reference.search(
                life.codebook, life.codes, life.unit_rows, q, metric,
                K, life.rescore_limit, valid)
        agree += len(set(slot_row[slots].tolist())
                     & set(life.after_replies["ids"][r].tolist()))
    assert agree >= 0.99 * K * len(life.after_replies["query"])


# -- the device scan against the plain quantizer, on a shared codebook -------


@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
@pytest.mark.parametrize("segments", [DIM, DIM // 4],
                         ids=["m=dim", "m=dim/4"])
def test_device_adc_candidates_match_the_plain_quantizer(segments, metric):
    """``pq_encode`` and ``pq_topk`` at 256 centroids against
    tests/pq_reference.py given the SAME codebook (and, for the scan, the
    same codes). Ties are bounded by value: a code may differ only where
    the two centroids are equally near within 1e-5, and the two candidate
    sets may differ only in rows whose plain ADC distance lies within 1e-4
    of the plain cut (two rows with one code have one distance; the device
    sums in another order)."""
    import jax.numpy as jnp

    from weaviate_tpu.ops.pq import pq_encode, pq_fit, pq_topk

    corpus, queries = clustered(7 + segments, 2048)
    if metric == "cosine":
        corpus = reference.prepare(corpus, metric)
        queries = reference.prepare(queries, metric)
    book = pq_fit(corpus[:1024], m=segments, k=256, iters=4, seed=0)
    cents = np.asarray(book.centroids)
    codes = pq_encode(book, corpus)
    plain = pq_reference.encode(cents, corpus)
    differ = codes != plain
    assert differ.mean() < 0.01
    assert (pq_reference.encode_margin(cents, corpus, codes)[differ]
            < 1e-5).all()
    n_cand = 16 * K
    _, ids = pq_topk(jnp.asarray(queries), jnp.asarray(codes),
                     book.centroids, k=n_cand, chunk_size=1024,
                     metric=metric)
    ids = np.asarray(ids)
    for r, q in enumerate(queries):
        want, dist = pq_reference.candidates(cents, codes, q, metric, n_cand)
        cut = dist[want[-1]]
        got = set(ids[r].tolist())
        assert len(got) == n_cand and -1 not in got
        for row in got ^ set(want.tolist()):
            assert abs(dist[row] - cut) < 1e-4, (r, row, dist[row], cut)


# -- async indexing, a live update, a failure --------------------------------


def test_async_indexing_reaches_the_same_gate(tmp_path):
    corpus, queries = clustered(3, 1600)
    db = Database(str(tmp_path), async_indexing=True)
    try:
        col = db.create_collection(config_from_json(class_json("Deep")))
        for start in range(0, 1600, 400):
            put(col, corpus, range(start, start + 400))
            # read-your-writes while the queue drains, trained or not
            hit = col.near_vector(corpus[start + 399], k=1,
                                  include_objects=False)
            assert hit[0].uuid == uid(start + 399)
        shard = next(iter(col.shards.values()))
        shard.flush()
        store = index_of(col).store
        assert isinstance(store, QuantizedVectorStore) and store.trained
        assert judged(col, queries, corpus)["correct"]
    finally:
        db.close()


def test_a_live_update_below_the_limit_defers_and_later_fires(tmp_path):
    corpus, queries = clustered(5, 1500)
    db = Database(str(tmp_path))
    try:
        plain = class_json("Deep")
        plain["vectorIndexConfig"].pop("pq")
        col = db.create_collection(config_from_json(plain))
        put(col, corpus, range(0, 600))
        new = copy.deepcopy(col.config)
        new.vectors[0].index.quantization = "pq"
        new.vectors[0].index.pq_centroids = 256
        new.vectors[0].index.pq_training_limit = LIMIT
        db.update_collection(new)
        # the config sticks, the store waits for its rows
        assert col.config.vectors[0].index.quantization == "pq"
        assert type(index_of(col).store) is DeviceVectorStore
        assert judged(col, queries, corpus[:600])["recall_at_k"] == 1.0
        put(col, corpus, range(600, 1500))
        assert isinstance(index_of(col).store, QuantizedVectorStore)
        assert judged(col, queries, corpus)["correct"]
    finally:
        db.close()


@pytest.mark.parametrize("index_type", ["flat", "dynamic"])
def test_no_pq_class_ever_holds_rows_it_cannot_search(tmp_path, index_type):
    """Below the limit, at it and past it, for the two index types that
    used to build an untrained quantized store at creation."""
    corpus, queries = clustered(11, 1500)
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(
            class_json("Deep", index_type=index_type)))
        for start in range(0, 1500, 500):
            put(col, corpus, range(start, start + 500))
            store = index_of(col).store
            assert getattr(store, "trained", True)
            assert judged(col, queries, corpus[:start + 500])["correct"]
        assert index_of(col).compressed
    finally:
        db.close()


def test_a_failed_compression_is_counted_and_tried_again(tmp_path,
                                                         monkeypatch, caplog):
    from weaviate_tpu.engine.flat import FlatIndex

    corpus, queries = clustered(13, 1500)
    failed = metrics.index_compress_total.labels("pq", "failed")
    was = failed.value
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(class_json("Deep")))
        real = FlatIndex.compress

        def broken(self, *a, **kw):
            raise RuntimeError("no device memory for the codebook")

        monkeypatch.setattr(FlatIndex, "compress", broken)
        with caplog.at_level("ERROR"):
            put(col, corpus, range(0, 1200))    # acknowledged all the same
        assert failed.value == was + 1
        assert "compression failed" in caplog.text
        assert type(index_of(col).store) is DeviceVectorStore
        assert judged(col, queries, corpus[:1200])["recall_at_k"] == 1.0
        monkeypatch.setattr(FlatIndex, "compress", real)
        put(col, corpus, range(1200, 1500))     # the next batch tries again
        assert isinstance(index_of(col).store, QuantizedVectorStore)
    finally:
        db.close()


# -- the swap itself: what the lock is held for ------------------------------


def test_compress_catches_up_with_writes_made_while_it_fitted(monkeypatch):
    """The codebook is fitted and the rows are encoded OUTSIDE the index's
    lock; what is added, overwritten or deleted meanwhile is caught up
    under it, before the swap. Here the 'meanwhile' is made to happen: the
    fit itself writes to the index."""
    from weaviate_tpu.engine.flat import FlatIndex

    corpus, _ = clustered(17, 1600)
    idx = FlatIndex(dim=DIM, metric="cosine", capacity=1024)
    idx.add_batch(np.arange(1000), corpus[:1000])
    real_train = QuantizedVectorStore.train

    def train_and_write(self, vectors=None, **kw):
        real_train(self, vectors, **kw)
        assert not idx._lock._is_owned()        # searches are not held up
        idx.add_batch(np.arange(1000, 1600), corpus[1000:1600])  # grows
        idx.add_batch([7], corpus[1599:1600])                    # overwrite
        idx.delete(11, 1200)

    monkeypatch.setattr(QuantizedVectorStore, "train", train_and_write)
    with tracing.trace("test.compress", force=True):
        idx.compress("pq", pq_segments=DIM, pq_centroids=256,
                     training_limit=512)
    spans = {s["name"]: s for s in tracing.recent_traces(1)[0]["spans"]}
    assert spans["train"]["attrs"]["rows_trained"] == 512
    assert spans["encode"]["attrs"]["rows_encoded"] == 1000
    # 599 of the 600 new rows (1200 came and went), row 7 again, 11 gone
    assert spans["swap"]["attrs"]["rows_caught_up"] == 599 + 1 + 1
    assert idx.compressed and len(idx) == 1598
    for doc, row in ((7, 1599), (999, 999), (1000, 1000), (1598, 1598)):
        ids, dists = idx.search_by_vector(corpus[row], k=2)
        assert doc in ids[:2].tolist() and dists[0] < 1e-5, (doc, ids)
    for gone in (11, 1200):
        ids, _ = idx.search_by_vector(corpus[gone], k=3)
        assert gone not in ids.tolist()


def test_compress_starts_over_when_a_compaction_renumbered_the_slots(
        monkeypatch):
    from weaviate_tpu.engine.flat import FlatIndex

    corpus, _ = clustered(19, 1200)
    idx = FlatIndex(dim=DIM, metric="cosine", capacity=2048)
    idx.add_batch(np.arange(1200), corpus)
    real_train = QuantizedVectorStore.train
    calls = []

    def train_and_compact(self, vectors=None, **kw):
        real_train(self, vectors, **kw)
        if not calls:
            idx.delete(*range(0, 100))
            idx.compact()
        calls.append(len(vectors))

    monkeypatch.setattr(QuantizedVectorStore, "train", train_and_compact)
    idx.compress("pq", pq_segments=DIM // 4, pq_centroids=256,
                 training_limit=1024)
    assert calls == [1024, 1024] and idx.compressed and len(idx) == 1100
    for doc in (100, 640, 1199):
        ids, dists = idx.search_by_vector(corpus[doc], k=1)
        assert ids.tolist() == [doc] and dists[0] < 1e-5


# -- over the wire: REST class, gRPC import, the two debug surfaces ----------


def test_a_served_import_shows_the_compression_on_both_surfaces(
        tmp_path, monkeypatch):
    """The whole normal path on a real ``Server``: class over REST, import
    over gRPC BatchObjects (the benchmark's own wire client) with every
    request sampled, then ``/v1/debug/traces`` and ``/v1/metrics``."""
    import wire  # benchmarks/wire.py

    from weaviate_tpu.config import ServerConfig
    from weaviate_tpu.server import Server

    monkeypatch.setenv("TRACE_SAMPLE_RATE", "1000")   # >= 1: always
    tracing.reset_policy_for_tests()
    corpus, queries = clustered(23, 1536)
    server = Server(ServerConfig(data_path=str(tmp_path), rest_port=0,
                                 grpc_port=0, disable_telemetry=True)).start()
    try:
        rest, grpc = wire.Rest(server.rest.address), wire.Grpc(server.grpc.port)
        before = rest.metrics().total(
            "weaviate_tpu_index_compress_seconds_count",
            {"quantization": "pq", "stage": "swap"})
        rest.create_class(class_json("Deep"))
        grpc.import_rows("Deep", corpus,
                         {"bucket": np.arange(len(corpus)) % 100}, 512)
        page = rest.metrics()
        assert page.total("weaviate_tpu_index_compress_seconds_count",
                          {"quantization": "pq", "stage": "swap"}) \
            == before + 1
        assert page.total("weaviate_tpu_index_compress_seconds_sum",
                          {"quantization": "pq", "stage": "train"}) > 0
        traces = json.loads(rest.request("GET",
                                         "/v1/debug/traces?limit=50"))
        spans = {s["name"] for t in traces["traces"] for s in t["spans"]}
        for name in ("index.compress", "train", "encode", "swap",
                     "store.pq_encode"):
            assert name in spans, name
        req = grpc.search_request("Deep", queries[0],
                                  {"metadata": ["uuid", "distance"]}, K,
                                  None, -1)
        ids, dists = grpc.search(req)
        assert len(ids) == K and dists == sorted(dists)
        grpc.close()
    finally:
        server.stop()
        tracing.reset_policy_for_tests()
