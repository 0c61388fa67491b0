"""A collection's shards on chips of their own (ISSUE 41): each shard's
vector indexes live on ONE local device, chosen by the program
(``runtime/placement.py``), a ``near_vector`` over the collection is PR
34's one fan-out and one host merge over programs that run on as many
devices at once, and the answers are those of the host-exact reference
(``tests/multishard_reference.py``: numpy, float64, no shards, no
devices) and, id for id and distance for distance, those of the same
collection built with ONE visible device.

Runs on the CPU's eight forced devices (``tests/conftest.py``); a host of
one or four chips is shown the rule by patching
``placement.local_devices``, never through an option."""

from __future__ import annotations

import uuid as uuid_mod

import jax
import numpy as np
import pytest

import multishard_reference as ref
from weaviate_tpu.api.rest import config_from_json
from weaviate_tpu.db.database import Database
from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.filters.filters import Filter, Operator
from weaviate_tpu.runtime import (hbm_ledger, memwatch, metrics, placement,
                                  tailboard, tracing)

ROWS, DIM, SHARDS, K = 4096, 32, 8, 10
KINDS = ("flat", "bq", "pq", "sq")
#: a shard's share of ROWS is ~512: the quantizers train at 256 a shard,
#: so the compress happens UNDER the import
QUANT = {"flat": {},
         "bq": {"bq": {"enabled": True}},
         "pq": {"pq": {"enabled": True, "segments": 8, "centroids": 16,
                       "trainingLimit": 256}},
         "sq": {"sq": {"enabled": True, "trainingLimit": 256}}}


def _uuid(i: int) -> str:
    return str(uuid_mod.UUID(int=i + 1))


def klass(name: str, kind: str = "flat", shards: int = SHARDS) -> dict:
    return {"class": name, "vectorIndexType": "flat",
            "vectorIndexConfig": dict({"distance": "cosine"}, **QUANT[kind]),
            "shardingConfig": {"desiredCount": shards},
            "properties": [{"name": "bucket", "dataType": ["int"]},
                           {"name": "home", "dataType": ["int"]}]}


def clustered(seed: int, rows: int = ROWS, dim: int = DIM):
    """Rows round 64 centres, queries round the same centres: a
    compressed scan has neighbours to find."""
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((64, dim)).astype(np.float32)
    rows_ = centres[rng.integers(0, 64, rows)] + 0.35 * rng.standard_normal(
        (rows, dim)).astype(np.float32)
    queries = centres[rng.integers(0, 64, 24)] + 0.35 * rng.standard_normal(
        (24, dim)).astype(np.float32)
    return rows_.astype(np.float32), queries.astype(np.float32)


def fill(col, rows, batch: int = 512):
    """Import in batches, so that a quantizer's limit is crossed by one
    of them. ``home`` is the ordinal of the shard a row is routed to."""
    home = np.array([int(col.sharding.shard_for(_uuid(i)).rsplit("-", 1)[1])
                     for i in range(len(rows))])
    for start in range(0, len(rows), batch):
        done = col.batch_put([
            {"uuid": _uuid(i), "vector": rows[i],
             "properties": {"bucket": i % 100, "home": int(home[i])}}
            for i in range(start, min(start + batch, len(rows)))])
        assert all(r["status"] == "SUCCESS" for r in done)
    return home


def answers(col, queries, k: int = K, where=None):
    """-> (ids [Q, k] as row numbers, distances [Q, k])."""
    ids = np.full((len(queries), k), -1, np.int64)
    dists = np.full((len(queries), k), np.inf, np.float64)
    for q, query in enumerate(queries):
        found = col.near_vector(query, k=k, include_objects=False,
                                where=where)
        ids[q, :len(found)] = [uuid_mod.UUID(r.uuid).int - 1 for r in found]
        dists[q, :len(found)] = [r.distance for r in found]
    return ids, dists


def device_arrays(obj) -> dict:
    """Every jax array an object holds as an attribute, by name (a
    quantizer's device half too)."""
    out = {name: a for name, a in vars(obj).items()
           if isinstance(a, jax.Array)}
    codebook = getattr(obj, "codebook", None)
    if codebook is not None:
        out["codebook"] = codebook.centroids
    quantizer = getattr(obj, "sq_quantizer", None)
    if quantizer is not None:
        out["sq_params"] = quantizer.params
    return out


def assert_on(device, arrays: dict, what: str) -> None:
    assert arrays, what
    for name, a in arrays.items():
        assert a.devices() == {device}, (what, name, a.devices(), device)
        assert a.committed, (what, name)


def assert_index_on_its_device(shard) -> None:
    """Every device array of the shard's store, of its epochs' stores and
    of its filter-operand cache lies on the shard's device."""
    idx = shard.vector_indexes[""]
    assert idx.device == shard.device is not None
    store = idx.store
    stores = [ep.store for ep in store.epochs] \
        if idx.epoch_store is not None else [store]
    for st in stores:
        assert st.device == shard.device
        assert_on(shard.device, device_arrays(st),
                  f"{shard.name} {type(st).__name__}")
    cache = idx._operands
    if cache is not None:
        kept = {f"entry{i}.{part}": a
                for i, e in enumerate(cache._entries.values())
                for part, a in (("bits", e.bits), ("slots", e.slots))
                if a is not None}
        if cache._ones is not None:
            kept["ones"] = cache._ones
        if kept:
            assert_on(shard.device, kept, f"{shard.name} operands")


@pytest.fixture
def fresh_placement(monkeypatch):
    """The rule starts from nothing: shards other tests left open in this
    process do not count."""
    monkeypatch.setattr(placement, "_held", {})


def host_of(n: int, monkeypatch) -> list:
    devices = jax.local_devices()[:n]
    monkeypatch.setattr(placement, "local_devices", lambda: devices)
    return devices


# -- (a) the answers ------------------------------------------------------------


@pytest.fixture(scope="module")
def corpus():
    return clustered(41)


@pytest.fixture(scope="module")
def spread(tmp_path_factory, corpus):
    """The eight-shard collection over the eight devices."""
    db = Database(str(tmp_path_factory.mktemp("spread")))
    col = db.create_collection(config_from_json(klass("Spread")))
    home = fill(col, corpus[0])
    yield col, home
    db.close()


def test_the_shards_lie_on_different_devices(spread):
    col, _ = spread
    devices = [s.device for s in col.shards.values()]
    assert len(set(devices)) == min(SHARDS, len(jax.local_devices()))
    assert len(set(devices)) > 1    # not all on the default one


@pytest.mark.parametrize("k", [1, 10, 100])
def test_answers_are_the_references(spread, corpus, k):
    col, _ = spread
    rows, queries = corpus
    ids, dists = answers(col, queries, k)
    for q, query in enumerate(queries):
        want, want_d = ref.top_k(rows, query, k, "cosine")
        np.testing.assert_allclose(dists[q], want_d, rtol=1e-5, atol=1e-6)
        # a float32 near-tie may swap two neighbours: compare as sets
        # wherever the reference's distances are closer than a rounding
        assert len(set(ids[q])) == k
        far = np.abs(np.diff(want_d)) > 4e-6
        settled = np.concatenate(([True], far)) & np.concatenate((far, [True]))
        assert (ids[q][settled] == want[settled]).all()
        assert set(ids[q]) == set(want) or not far[-1]


def test_answers_are_those_of_one_visible_device(
        spread, corpus, tmp_path, monkeypatch, fresh_placement):
    col, _ = spread
    rows, queries = corpus
    (only,) = host_of(1, monkeypatch)
    db = Database(str(tmp_path))
    one = db.create_collection(config_from_json(klass("OneChip")))
    try:
        fill(one, rows)
        assert {s.device for s in one.shards.values()} == {only}
        for k in (1, K):
            ids, dists = answers(col, queries, k)
            ids1, dists1 = answers(one, queries, k)
            assert np.array_equal(ids, ids1)
            assert np.array_equal(dists, dists1)
    finally:
        db.close()


def test_a_dynamic_class_on_another_device_answers_as_on_the_default_one(
        corpus, tmp_path, monkeypatch, fresh_placement):
    """ISSUE 43: the ANN index is placed like every other store. A
    ``dynamic`` class past its threshold on a chip that is not the default
    one (the list tensors, the centroids and the delta buffer committed
    there) gives the ids and distances the same class gives on a host of
    one visible device, with rows in the delta and after the fold."""
    rows, queries = corpus
    dynamic = {"class": "Dyn", "vectorIndexType": "dynamic",
               "vectorIndexConfig": {"distance": "cosine", "threshold": 1024},
               "properties": [{"name": "bucket", "dataType": ["int"]},
                              {"name": "home", "dataType": ["int"]}]}

    def lived(path, first=None):
        db = Database(str(path))
        if first is not None:    # takes the default device
            db.create_collection(config_from_json(klass(first, shards=1)))
        col = db.create_collection(config_from_json(dynamic))
        fill(col, rows)
        (shard,) = col.shards.values()
        idx = shard.vector_indexes[""]
        assert idx.upgraded and idx.store._delta_slots
        got = [answers(col, queries)]
        assert db.cycles.run_now("epoch-maintenance")
        assert not idx.store._delta_slots
        got.append(answers(col, queries))
        return db, shard, got

    db, shard, there = lived(tmp_path / "second", first="Before")
    try:
        assert shard.device == jax.local_devices()[1]
        store = shard.vector_indexes[""].store
        assert store.device == store.delta.device == shard.device
        assert_on(shard.device, device_arrays(store), "the lists")
        assert_on(shard.device, device_arrays(store.delta), "the delta")
    finally:
        db.close()
    monkeypatch.setattr(placement, "_held", {})
    (only,) = host_of(1, monkeypatch)
    db, shard, here = lived(tmp_path / "default")
    try:
        assert shard.device == only
        for (ids, dists), (ids1, dists1) in zip(there, here):
            assert np.array_equal(ids, ids1)
            assert np.allclose(dists, dists1, rtol=0, atol=1e-6)
    finally:
        db.close()


# -- (b) every array on the shard's device, through its life ---------------------


@pytest.fixture(scope="module", params=KINDS)
def lived(request, tmp_path_factory, corpus):
    """One collection a store kind, through an import that crosses the
    quantizer's limit, filtered searches, close and reopen."""
    kind = request.param
    path = str(tmp_path_factory.mktemp("lived" + kind))
    rows, queries = corpus
    db = Database(path)
    col = db.create_collection(config_from_json(klass("Lived", kind)))
    fill(col, rows)
    broad = Filter.where("bucket", Operator.LESS_THAN, 50)
    narrow = Filter.where("bucket", Operator.LESS_THAN, 1)
    out = {"kind": kind, "db": db, "col": col, "path": path,
           "filters": (broad, narrow)}
    out["plain"] = answers(col, queries)
    out["broad"] = answers(col, queries, where=broad)
    out["narrow"] = answers(col, queries, where=narrow)
    yield out
    out["db"].close()


def test_after_import_compress_and_filtered_search(lived):
    from weaviate_tpu.engine.quantized import QuantizedVectorStore

    col = lived["col"]
    assert len({s.device for s in col.shards.values()}) > 1
    for shard in col.shards.values():
        store = shard.vector_indexes[""].store
        assert isinstance(store, QuantizedVectorStore) == (
            lived["kind"] != "flat"), lived["kind"]
        assert_index_on_its_device(shard)


def test_a_filtered_search_keeps_its_operands_on_the_device(lived, corpus):
    """A memoised clause's packed row and slot list are kept in HBM (PR
    40): on the shard's chip, not the default one."""
    col = lived["col"]
    broad, narrow = lived["filters"]
    for where in (broad, narrow, broad, narrow):
        answers(col, corpus[1][:4], where=where)
    kept = 0
    for shard in col.shards.values():
        cache = shard.vector_indexes[""]._operands
        if cache is not None:
            kept += len(cache._entries)
        assert_index_on_its_device(shard)
    assert kept or lived["kind"] != "flat"


def test_filtered_answers_are_the_references(lived, corpus):
    rows, queries = corpus
    bucket = np.arange(ROWS) % 100
    exact = lived["kind"] == "flat"
    for name, limit in (("broad", 50), ("narrow", 1)):
        ids, dists = lived[name]
        allowed = bucket < limit
        hits = total = 0
        for q, query in enumerate(queries):
            want, want_d = ref.top_k(rows, query, K, "cosine", allowed)
            got = ids[q][ids[q] >= 0]
            assert allowed[got].all()
            hits += len(set(got) & set(want))
            total += len(want)
            if exact:
                np.testing.assert_allclose(dists[q][:len(want)], want_d,
                                           rtol=1e-5, atol=1e-6)
        assert hits / total >= (1.0 if exact else 0.85), (name, hits, total)


def test_after_close_and_reopen(lived, corpus):
    """Restart from disk: each shard is given a device again, its store
    is rebuilt (and compressed again) there, and the answers are those
    from before."""
    lived["db"].close()
    db = lived["db"] = Database(lived["path"])
    col = lived["col"] = db.get_collection("Lived")
    assert len({s.device for s in col.shards.values()}) > 1
    for shard in col.shards.values():
        assert_index_on_its_device(shard)
    ids, dists = answers(col, corpus[1])
    if lived["kind"] in ("flat", "bq", "sq"):
        # the same rows in the same slots give the same answers bit for
        # bit (a pq codebook is fitted again, from another k-means seed
        # order, and may settle elsewhere)
        assert np.array_equal(ids, lived["plain"][0])
    else:
        agree = np.mean([len(set(a) & set(b)) / K
                         for a, b in zip(ids, lived["plain"][0])])
        assert agree >= 0.9


@pytest.mark.parametrize("kind", KINDS + ("epochs",))
def test_a_store_stays_on_its_device_through_growth(kind):
    """Engine level, on a device that is not the default: growth past two
    doublings, deletes, the three filter forms, compress, compact,
    snapshot and restore."""
    device = jax.local_devices()[5]
    rng = np.random.default_rng(7)
    rows = rng.standard_normal((300, DIM)).astype(np.float32)
    with hbm_ledger.owner("Grown", "s0", device=device):
        kwargs = {"quantization": "bq"} if kind == "bq" else {}
        if kind == "epochs":
            kwargs["epoch_rows"] = 64
        idx = FlatIndex(DIM, metric="cosine", capacity=64, **kwargs)
        idx.add_batch(np.arange(200), rows[:200])
        if kind in ("pq", "sq"):
            extra = {"pq_segments": 8, "pq_centroids": 16} \
                if kind == "pq" else {}
            idx.compress(kind, training_limit=128, **extra)
        idx.add_batch(np.arange(200, 300), rows[200:])
    assert idx.device == device

    def stores():
        es = idx.epoch_store
        return [ep.store for ep in es.epochs] if es is not None \
            else [idx.store]

    def check(when):
        for st in stores():
            assert st.device == device
            assert_on(device, device_arrays(st), f"{kind} {when}")

    assert idx.store.capacity >= 256 or kind == "epochs"
    check("after growth")
    idx.delete(*range(0, 40))
    allow = np.zeros(300, dtype=bool)
    allow[50:60] = True
    ids, _ = idx.search_by_vector(rows[55], 5, allow)
    assert set(ids) <= set(range(50, 60)) and 55 in ids
    ids, _ = idx.search_by_vector_batch(rows[100:104], 5)
    assert [int(r[0]) for r in ids] == [100, 101, 102, 103]
    per_query = [allow, None, np.arange(120, 140), allow]
    ids, _ = idx.search_by_vector_batch(rows[100:104], 5, per_query)
    assert set(ids[0][ids[0] >= 0]) <= set(range(50, 60))
    assert ids[1][0] == 101 and set(ids[2][ids[2] >= 0]) <= set(
        range(120, 140))
    handle = idx.search_by_vector_batch_async(rows[100:104], 5)
    if handle is not None:
        inner = handle
        while not inner.arrays and inner._parent is not None:
            inner = inner._parent
        assert_on(device, {f"result{i}": a
                           for i, a in enumerate(inner.arrays)},
                  f"{kind} results")
        handle.result()
    check("after searches")
    if kind != "epochs":
        idx.compact()
        check("after compact")
    with hbm_ledger.owner("Grown", "s0", device=device):
        again = FlatIndex.restore(idx.snapshot())
    assert again.device == device
    for st in ([ep.store for ep in again.epoch_store.epochs]
               if again.epoch_store is not None else [again.store]):
        assert_on(device, device_arrays(st), f"{kind} restored")
    ids, _ = again.search_by_vector(rows[150], 3)
    assert ids[0] == 150


def test_outside_any_shard_a_store_takes_the_default_device():
    """Tests, tools and a mesh build stores in no owner scope: nothing is
    committed, as before."""
    idx = FlatIndex(DIM, capacity=64)
    assert idx.device is None and idx.store.device is None
    assert not idx.store.vectors.committed
    assert idx.twin_shapes() is not None


@pytest.mark.parametrize("kind", KINDS)
def test_on_a_host_of_one_chip_nothing_is_committed(
        tmp_path, monkeypatch, fresh_placement, corpus, kind):
    """One visible device: every shard names it, and its arrays lie there
    UNCOMMITTED, made by the calls a one-chip server always made
    (``placement.commits``): the same programs, the same persistent-cache
    keys, the same timing a dispatch. Through growth, the compress under
    import and a filtered search's operand cache."""
    (only,) = host_of(1, monkeypatch)
    rows, queries = corpus
    db = Database(str(tmp_path))
    col = db.create_collection(config_from_json(klass("Sole", kind, 2)))
    try:
        fill(col, rows[:1024])
        answers(col, queries[:4],
                where=Filter.where("bucket", Operator.LESS_THAN, 50))
        for shard in col.shards.values():
            idx = shard.vector_indexes[""]
            assert shard.device == idx.device == idx.store.device == only
            held = device_arrays(idx.store)
            cache = idx._operands
            if cache is not None:
                held.update({f"entry{i}": e.bits for i, e in enumerate(
                    cache._entries.values()) if e.bits is not None})
            assert held
            for name, a in held.items():
                assert a.devices() == {only}, (kind, name)
                assert not a.committed, (kind, name)
        ids, _ = answers(col, queries[:4], k=1)
        assert (ids >= 0).all()
    finally:
        db.close()


# -- (c) the rule ------------------------------------------------------------------


@pytest.mark.parametrize("shards,devices,want", [
    (8, 8, [1] * 8), (8, 4, [2] * 4), (3, 4, [1, 1, 1, 0]), (8, 1, [8]),
    (5, 2, [3, 2])])
def test_shards_are_even_over_devices(tmp_path, monkeypatch,
                                      fresh_placement, shards, devices,
                                      want):
    host = host_of(devices, monkeypatch)
    db = Database(str(tmp_path))
    try:
        col = db.create_collection(config_from_json(
            klass("Even", shards=shards)))
        got = [s.device for s in col.shards.values()]
        assert [got.count(d) for d in host] == want
        # in the order the shards are made: ordinal i on device i mod n
        names = sorted(col.shards, key=lambda n: int(n.rsplit("-", 1)[1]))
        assert [col.shards[n].device for n in names] == [
            host[i % devices] for i in range(shards)]
        assert placement.held() == {d.id: n for d, n in zip(host, want) if n}
    finally:
        db.close()
    assert placement.held() == {}


def test_a_second_collection_goes_on_where_the_first_stopped(
        tmp_path, monkeypatch, fresh_placement):
    host = host_of(4, monkeypatch)
    db = Database(str(tmp_path))
    try:
        first = db.create_collection(config_from_json(
            klass("First", shards=3)))
        second = db.create_collection(config_from_json(
            klass("Second", shards=3)))
        assert [s.device for s in first.shards.values()].count(host[3]) == 0
        mine = [second.shards[n].device for n in sorted(
            second.shards, key=lambda n: int(n.rsplit("-", 1)[1]))]
        assert mine == [host[3], host[0], host[1]]
        assert sorted(placement.held().values()) == [1, 1, 2, 2]
        # one-shard collections do not pile up on chip 0 either
        ones = [db.create_collection(config_from_json(
            klass(f"One{i}", shards=1))) for i in range(6)]
        assert sorted(placement.held().values()) == [3, 3, 3, 3]
        assert len({next(iter(c.shards.values())).device
                    for c in ones[:4]}) == 4
        # a dropped collection gives its devices back
        db.delete_collection("First")
        assert sorted(placement.held().values()) == [2, 2, 2, 3]
    finally:
        db.close()


def test_a_mesh_sharded_shard_takes_no_device(tmp_path, fresh_placement):
    from weaviate_tpu.parallel.mesh import make_mesh

    db = Database(str(tmp_path), mesh=make_mesh())
    try:
        col = db.create_collection(config_from_json(klass("Meshed", shards=2)))
        assert [s.device for s in col.shards.values()] == [None, None]
        assert placement.held() == {}
        rows, _ = clustered(3, 256)
        fill(col, rows, batch=256)
        for shard in col.shards.values():
            idx = shard.vector_indexes[""]
            assert idx.device is None and idx.store.mesh is not None
            assert len(idx.store.vectors.devices()) == len(jax.devices())
    finally:
        db.close()


# -- (d) a filter that empties shards ----------------------------------------------


@pytest.mark.parametrize("homes", [(3,), (0, 7), (1, 2, 5, 6)])
def test_a_filter_that_empties_some_shards(spread, corpus, homes):
    """Only the shards named by ``home`` have an allowed row; the others,
    each on a device of its own, answer with nothing, and the merge is
    the reference's top k of the allowed rows."""
    col, home = spread
    rows, queries = corpus
    where = None
    for h in homes:
        clause = Filter.where("home", Operator.EQUAL, h)
        where = clause if where is None else Filter.or_(where, clause)
    allowed = np.isin(home, homes)
    assert 0 < allowed.sum() < ROWS
    names = {f"shard-{h}" for h in homes}
    assert len({col.shards[n].device for n in col.shards
                if n not in names}) > 1
    for k in (K, 2000):
        ids, dists = answers(col, queries[:8], k, where)
        for q, query in enumerate(queries[:8]):
            want, want_d = ref.top_k(rows, query, k, "cosine", allowed)
            got = ids[q][ids[q] >= 0]
            assert len(got) == len(want) and allowed[got].all()
            np.testing.assert_allclose(dists[q][:len(want)], want_d,
                                       rtol=1e-5, atol=1e-6)
            assert set(got[:5]) == set(want[:5]) or k != K


# -- (e) the ledger and the watchdog, a device ---------------------------------------


def test_the_ledgers_bytes_a_device_add_up(spread):
    col, _ = spread
    led = hbm_ledger.ledger
    booked: dict[str, int] = {}
    for e in led.top(10 ** 6):
        if e["collection"] == "Spread" and e["component"] == "corpus":
            booked[e["device"]] = booked.get(e["device"], 0) + e["nbytes"]
    held: dict[str, int] = {}
    for shard in col.shards.values():
        store = shard.vector_indexes[""].store
        label = placement.label(shard.device)
        held[label] = held.get(label, 0) + sum(
            int(a.nbytes) for a in device_arrays(store).values())
    assert booked == held and "" not in booked
    by_device = led.device_bytes()
    assert sum(by_device.values()) == led.total_bytes()
    for label, nbytes in held.items():
        assert by_device[label] >= nbytes
    page = metrics.registry.expose()
    for label in held:
        assert f'weaviate_tpu_hbm_device_bytes{{device="{label}"}}' in page


def test_ledger_entries_carry_the_owners_device():
    led = hbm_ledger.HBMLedger()
    device = jax.local_devices()[2]
    with hbm_ledger.owner("C", "s", device=device):
        a = led.register("corpus", 1000)
    b = led.register("corpus", 300, collection="C", shard="t", device=device)
    c = led.register("executables", 50)
    assert led.device_bytes() == {"cpu:2": 1300, "": 50}
    led.update(a, 400)
    assert led.device_bytes()["cpu:2"] == 700
    led.release(b)
    led.release(a)
    assert led.device_bytes() == {"": 50}
    led.release(c)
    assert led.device_bytes() == {} and led.total_bytes() == 0


STATS = {"cpu:0": {"bytesInUse": 950, "bytesLimit": 1000},
         "cpu:1": {"bytesInUse": 100, "bytesLimit": 1000},
         "cpu:2": {"bytesInUse": 100, "bytesLimit": 4000}}


@pytest.mark.parametrize("device,nbytes,fits", [
    (None, 10, False),    # nobody named: the fullest chip answers
    (0, 10, False),       # the full chip refuses
    (1, 700, True),       # an empty chip takes what the full one could not
    (1, 850, False),      # up to ITS watermark
    (2, 3000, True),      # each chip's own limit
    (5, 10, False)])      # a chip without stats: as if nobody was named
def test_admission_asks_the_chip_the_bytes_are_for(monkeypatch, device,
                                                   nbytes, fits):
    monkeypatch.setattr(memwatch, "_probe_device_stats", lambda: dict(STATS))
    monkeypatch.setattr(memwatch, "_stats_failed_at", None)
    mon = memwatch.MemoryMonitor(ledger=hbm_ledger.HBMLedger(),
                                 high_watermark=0.9, low_watermark=0.8)
    dev = None if device is None else jax.local_devices()[device]
    assert mon.device_fits(nbytes, device=dev) == fits
    if fits:
        mon.check_device_alloc(nbytes, device=dev)
    else:
        with pytest.raises(memwatch.InsufficientMemoryError):
            mon.check_device_alloc(nbytes, device=dev)


def test_without_allocator_stats_the_ledger_answers_a_device(monkeypatch):
    monkeypatch.setattr(memwatch, "_probe_device_stats", lambda: {})
    led = hbm_ledger.HBMLedger()
    full, empty = jax.local_devices()[1], jax.local_devices()[2]
    led.register("corpus", 900, collection="C", shard="a", device=full)
    mon = memwatch.MemoryMonitor(device_limit_bytes=1000, ledger=led)
    assert mon.device_in_use(device=full) == 900
    assert mon.device_in_use(device=empty) == 0
    assert mon.device_in_use() == 900
    assert mon.device_fits(500, device=empty)
    assert not mon.device_fits(500, device=full)
    assert not mon.device_fits(500)


# -- a program size met on one chip is built on the others --------------------------


def test_a_size_met_on_one_chip_is_built_on_its_twins(monkeypatch):
    twins = placement.Twins()
    monkeypatch.setattr(placement, "twins", twins)
    warmed = []
    warm = FlatIndex.warm_twin

    def recorded(self, size):
        warm(self, size)
        warmed.append((self.device.id, size))

    monkeypatch.setattr(FlatIndex, "warm_twin", recorded)
    indexes = []
    for d in (1, 2, 2, 3):
        with hbm_ledger.owner("Twin", f"s{len(indexes)}",
                              device=jax.local_devices()[d]):
            indexes.append(FlatIndex(DIM, capacity=256))
    with hbm_ledger.owner("Twin", "other", device=jax.local_devices()[4]):
        other = FlatIndex(DIM * 2, capacity=256)    # other shapes: no twin
    for idx in indexes + [other]:
        idx.add_batch(np.arange(8), np.ones((8, idx.dim), np.float32))

    def wait():
        import time
        t0 = time.time()
        while not twins.idle():
            assert time.time() - t0 < 60
            time.sleep(0.01)

    q = np.zeros((4, DIM), np.float32)
    indexes[0].search_by_vector_batch_async(q, K).result()
    wait()
    # one index a device, the dispatching device left out, once a size
    assert sorted(warmed) == [(2, (4, K)), (3, (4, K))]
    indexes[1].search_by_vector_batch_async(q, K).result()
    indexes[3].search_by_vector_batch_async(q, K).result()
    wait()
    assert len(warmed) == 2
    # another size is met on the chip that holds two of them: the two
    # other chips build it; a filtered dispatch asks nothing of anyone
    indexes[2].search_by_vector_batch_async(q[:2], K).result()
    indexes[2].search_by_vector_batch_async(
        q[:3], K, [np.ones(8, bool), None, None]).result()
    wait()
    assert sorted(warmed[2:]) == [(1, (2, K)), (3, (2, K))]
    # a store that grew is no twin any more
    indexes[0].add_batch(np.arange(8, 600),
                         np.ones((592, DIM), np.float32))
    indexes[0].search_by_vector_batch_async(q[:1], K).result()
    wait()
    assert len(warmed) == 4


# -- the tracing says which device ------------------------------------------------


def test_counters_records_spans_and_nodes_name_the_device(spread, corpus):
    col, _ = spread
    labels = {placement.label(s.device) for s in col.shards.values()}
    with tracing.trace("test.placement", force=True):
        col.near_vector(corpus[1][0], k=K, include_objects=False)
        spans = tracing.current_timing()
    seen = {s["attrs"].get("device") for s in spans
            if s["name"] == "flat.search_batch"}
    assert seen and seen <= labels
    page = metrics.registry.expose()
    for label in labels:
        assert any(
            line.startswith("weaviate_tpu_query_batcher_compile_bucket_total{")
            and f'device="{label}"' in line for line in page.splitlines())
    # a plain request is ONE dispatch of the collection's drain (ISSUE
    # 42), whose eight programs span the chips: its record names none. A
    # filtered one rides the shards' own batchers, a record a chip
    assert col._drains[""].batcher._device_label == ""
    col.near_vector(corpus[1][0], k=K, include_objects=False,
                    where=Filter.where("bucket", Operator.LESS_THAN, 50))
    tailboard.flush()
    records = [r for r in tailboard.debug_flight()["dispatches"]
               if r.get("plane") == "batcher"]
    assert {r.get("device") for r in records} >= labels | {""}
    from weaviate_tpu.runtime import kernelscope

    by_chip = kernelscope.snapshot()["devices"]
    assert set(by_chip) >= labels
    assert all(by_chip[d]["dispatches"] >= 1 for d in labels)
    for shard in col.shards.values():
        shard.maintenance()
    page = metrics.registry.expose()
    for name, shard in col.shards.items():
        assert (f'weaviate_tpu_vector_index_hbm_bytes{{collection="Spread",'
                f'shard="{name}",vector="default",'
                f'device="{placement.label(shard.device)}"}}') in page


def test_nodes_names_each_shards_device(spread):
    from weaviate_tpu.api.rest import RestServer

    col, _ = spread
    srv = RestServer.__new__(RestServer)
    srv.db = type("Db", (), {
        "list_collections": lambda self: ["Spread"],
        "get_collection": lambda self, name: col})()
    details = srv._local_shard_details()
    assert {d["name"]: d["device"] for d in details} == {
        name: placement.label(s.device) for name, s in col.shards.items()}
