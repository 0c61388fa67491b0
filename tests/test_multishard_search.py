"""A collection of eight shards on one node answers nearVector as ONE
corpus: held, id for id, against ``tests/multishard_reference.py`` (numpy,
float64, no notion of a shard), serial and under 32 threads; the fan-out
holds no pool thread a shard and is bounded by the request's one deadline
(ISSUE 34). Since ISSUE 42 a plain request over the eight local shards is
ONE item on the collection's drain (``db/drain.py``), which launches every
member index's scan; a filtered one is one item a shard, as before
(``tests/test_collection_drain.py`` holds the two routes side by side)."""

from __future__ import annotations

import threading
import time
import uuid as uuid_mod

import numpy as np
import pytest

import multishard_reference as ref
from weaviate_tpu.db.database import Database
from weaviate_tpu.filters.filters import Filter, Operator
from weaviate_tpu.runtime import retry, tracing
from weaviate_tpu.runtime.metrics import (deadline_exceeded_total,
                                          fanout_shards_total)
from weaviate_tpu.schema.config import (CollectionConfig, Property,
                                        ShardingConfig, VectorConfig,
                                        VectorIndexConfig)

ROWS, DIM, SHARDS = 4000, 32, 8
METRICS = ("cosine", "l2-squared")
#: reference distances closer than this (relative) may come in either
#: order from a float32 scan
TIE = 2e-6


def _uuid(i: int) -> str:
    return str(uuid_mod.UUID(int=i + 1))


class World:
    """Seeded rows, imported into an eight-shard collection a metric and
    a one-shard collection, with three vectors planted twice, each time
    on two different shards."""

    def __init__(self, path: str):
        rng = np.random.default_rng(34)
        self.rows = rng.standard_normal((ROWS, DIM)).astype(np.float32)
        self.queries = rng.standard_normal((48, DIM)).astype(np.float32)
        self.uuids = [_uuid(i) for i in range(ROWS)]
        self.pos = {u: i for i, u in enumerate(self.uuids)}
        self.db = Database(path)
        self.multi = {m: self._collection("Multi" + m[:2].title(), m, SHARDS)
                      for m in METRICS}
        self.single = self._collection("Single", "cosine", 1)
        sharding = self.multi["cosine"].sharding
        self.home = np.array([int(sharding.shard_for(u).rsplit("-", 1)[1])
                              for u in self.uuids])
        self.twins = []
        for a in (5, 6, 7):
            b = int(np.flatnonzero(self.home != self.home[a])[100 * a])
            self.rows[b] = self.rows[a]
            self.twins.append((a, b))
        self.bucket = np.arange(ROWS) % 100
        for col in (*self.multi.values(), self.single):
            done = col.batch_put([
                {"uuid": self.uuids[i], "vector": self.rows[i],
                 "properties": {"bucket": int(self.bucket[i]),
                                "home": int(self.home[i])}}
                for i in range(ROWS)])
            assert all(r["status"] == "SUCCESS" for r in done)

    def _collection(self, name: str, metric: str, shards: int):
        return self.db.create_collection(CollectionConfig(
            name=name,
            properties=[Property(name="bucket", data_type="int"),
                        Property(name="home", data_type="int")],
            sharding=ShardingConfig(desired_count=shards),
            vectors=[VectorConfig(index=VectorIndexConfig(
                index_type="flat", metric=metric))]))

    def ids(self, results) -> list[int]:
        return [self.pos[r.uuid] for r in results]


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    w = World(str(tmp_path_factory.mktemp("multishard")))
    yield w
    w.db.close()


def assert_is_the_top_k(world, results, query, k, metric, allowed=None):
    """``results`` are the reference's top k, id for id (near-equal
    reference distances in either order), an id never twice, distances to
    1e-5 relative."""
    want, want_d = ref.top_k(world.rows, query, k + 8, metric, allowed)
    got = world.ids(results)
    n = min(k, len(want))
    assert len(got) == n
    assert len(set(got)) == len(got)
    np.testing.assert_allclose([r.distance for r in results], want_d[:n],
                               rtol=1e-5, atol=1e-6)
    i = 0
    while i < n:
        j = i + 1
        while j < len(want) and want_d[j] - want_d[j - 1] <= \
                TIE * max(1.0, abs(want_d[j])):
            j += 1
        group = set(want[i:j].tolist())
        assert set(got[i:min(j, n)]) <= group, (i, j, got, want)
        if j <= n:
            assert set(got[i:j]) == group
        i = j


@pytest.mark.parametrize("k", [1, 10, 100])
@pytest.mark.parametrize("metric", METRICS)
def test_the_union_of_eight_shards_answers_like_one_corpus(world, metric, k):
    col = world.multi[metric]
    for q in world.queries[:12]:
        found = col.near_vector(q, k=k, include_objects=False)
        assert_is_the_top_k(world, found, q, k, metric)
        assert {r.shard for r in found} <= set(col.shards)


@pytest.mark.parametrize("k", [600, ROWS + 50])
@pytest.mark.parametrize("metric", METRICS)
def test_k_larger_than_a_shards_rows(world, metric, k):
    """A shard holds about 500 rows: it answers with all it has, and the
    merge still returns the corpus's top k (all rows, where k is larger
    than the corpus)."""
    col = world.multi[metric]
    assert max(s.object_count() for s in col.shards.values()) < 600
    q = world.queries[12]
    assert_is_the_top_k(world, col.near_vector(q, k=k, include_objects=False),
                        q, k, metric)


@pytest.mark.parametrize("prop,op,value", [
    ("home", Operator.LESS_THAN, 3),       # five shards have nothing
    ("home", Operator.EQUAL, 5),           # seven have nothing
    ("bucket", Operator.LESS_THAN, 1),     # 1 %: the solo gathered path
    ("bucket", Operator.LESS_THAN, 50),
    ("bucket", Operator.LESS_THAN, 0)])    # nothing anywhere
@pytest.mark.parametrize("metric", METRICS)
def test_a_filter_that_empties_some_shards(world, metric, prop, op, value):
    column = getattr(world, prop)
    allowed = column < value if op == Operator.LESS_THAN else column == value
    for q in world.queries[13:17]:
        found = world.multi[metric].near_vector(
            q, k=10, include_objects=False,
            where=Filter.where(prop, op, value))
        assert_is_the_top_k(world, found, q, 10, metric, allowed)
        assert all(allowed[i] for i in world.ids(found))


@pytest.mark.parametrize("metric", METRICS)
def test_equal_vectors_on_two_shards_are_both_returned(world, metric):
    for a, b in world.twins:
        assert world.home[a] != world.home[b]
        found = world.multi[metric].near_vector(world.rows[a], k=10,
                                                include_objects=False)
        got = world.ids(found)
        assert set(got[:2]) == {a, b} and len(set(got)) == 10
        assert found[0].distance == pytest.approx(found[1].distance,
                                                  abs=1e-6)
        assert_is_the_top_k(world, found, world.rows[a], 10, metric)


@pytest.mark.parametrize("metric", METRICS)
def test_32_threads_at_once_give_the_serial_answers(world, metric):
    col = world.multi[metric]
    k = 10
    serial = [world.ids(col.near_vector(q, k=k, include_objects=False))
              for q in world.queries]
    got: dict = {}
    errors = []

    def client(c):
        try:
            for n in range(6):
                j = (c * 7 + n * 5) % len(world.queries)
                got[c, j] = world.ids(col.near_vector(
                    world.queries[j], k=k, include_objects=False))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(c,)) for c in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(got) == 32 * 6
    for (_c, j), ids in got.items():
        assert ids == serial[j]
    # coalesced: the collection's drain saw the 32 at once
    b = col._drains[""].batcher
    assert b.batched_queries > b.dispatches


@pytest.mark.parametrize("metric", METRICS)
def test_every_uuid_sits_on_its_shard_and_the_counts_add_up(world, metric):
    col = world.multi[metric]
    assert len(col.shards) == SHARDS
    counts = {name: shard.object_count()
              for name, shard in col.shards.items()}
    assert sum(counts.values()) == ROWS
    assert counts == {f"shard-{h}": int((world.home == h).sum())
                      for h in range(SHARDS)}
    for i in range(0, ROWS, 37):
        name = col.sharding.shard_for(world.uuids[i])
        assert col.shards[name].exists(world.uuids[i])
        assert sum(s.exists(world.uuids[i]) for s in col.shards.values()) == 1


def test_one_shard_answers_as_before(world):
    """``desiredCount: 1``: the collection's answer is its one shard's,
    bit for bit: ids in the shard's order, the float32 distances as they
    came back, no fan-out counted."""
    col = world.single
    (name, shard), = col.shards.items()
    fanned = fanout_shards_total.labels("Single").value
    for q in world.queries[:8]:
        ids, dists = shard.vector_search(q, 10)
        found = col.near_vector(q, k=10, include_objects=False)
        assert [r.uuid for r in found] == [shard._doc_to_uuid[i]
                                           for i in ids.tolist()]
        assert [r.distance for r in found] == dists.tolist()
        assert {r.shard for r in found} == {name}
        assert_is_the_top_k(world, found, q, 10, "cosine")
    assert fanout_shards_total.labels("Single").value == fanned
    assert not col._pool._threads


def _batchers(col) -> dict:
    """The batchers a plain ``near_vector`` on ``col`` queues on: the
    collection's drain where it has several local shards, else its one
    shard's own."""
    if len(col.shards) > 1:
        return {"drain": col._drain_for("", dict(col.shards)).batcher}
    return {name: shard._query_batcher("", shard.vector_indexes[""])
            for name, shard in col.shards.items()}


class _Gate:
    """Holds the workers of a collection's batchers at their next
    dispatch and counts what each has taken out of its queue."""

    def __init__(self, col):
        self.open = threading.Event()
        self.held = {}
        self.batchers = _batchers(col)
        for name, b in self.batchers.items():
            self.held[name] = 0
            b._dispatch = self._gated(name, b._dispatch)

    def _gated(self, name, dispatch):
        def held(drained, rec=None):
            self.held[name] += len(drained)
            self.open.wait()
            return dispatch(drained, rec)

        return held

    def enqueued(self) -> dict:
        return {name: self.held[name] + len(b._queue)
                for name, b in self.batchers.items()}

    def release(self):
        self.open.set()
        for b in self.batchers.values():
            del b._dispatch


def test_a_request_holds_no_pool_thread_and_queues_one_item(world):
    """32 concurrent requests, the worker held: the collection's drain
    holds 32 items, ONE a request (one a shard a request until ISSUE 42),
    no shard's own batcher holds any, and the collection's pool has
    started no thread."""
    col = world.multi["cosine"]
    col.near_vector(world.queries[0], k=10, include_objects=False)
    gate = _Gate(col)
    fanned = fanout_shards_total.labels(col.config.name).value
    got = {}
    try:
        threads = [threading.Thread(
            target=lambda c=c: got.__setitem__(c, world.ids(col.near_vector(
                world.queries[c], k=10, include_objects=False))))
            for c in range(32)]
        for t in threads:
            t.start()
        deadline = time.time() + 20.0
        while time.time() < deadline and \
                set(gate.enqueued().values()) != {32}:
            time.sleep(0.01)
        assert gate.enqueued() == {"drain": 32}
        assert not any(len(b._queue) for shard in col.shards.values()
                       for b in shard._query_batchers.values())
        assert not col._pool._threads
        assert not got
    finally:
        gate.release()
    for t in threads:
        t.join()
    for c in range(32):
        want, _ = ref.top_k(world.rows, world.queries[c], 10, "cosine")
        assert got[c] == want.tolist()
    assert fanout_shards_total.labels(col.config.name).value - fanned == \
        32 * SHARDS


@pytest.mark.parametrize("which", ["single", "multi"])
def test_a_spent_deadline_is_typed_once_and_leaves_nothing_queued(world,
                                                                  which):
    """One deadline bounds the whole fan-out: with every worker held the
    request gets ``DeadlineExceeded`` when its budget is spent, counted
    once, as a one-shard request does, and its items have left the
    queues."""
    col = world.single if which == "single" else world.multi["cosine"]
    col.near_vector(world.queries[0], k=10, include_objects=False)
    gate = _Gate(col)
    try:
        # one request a worker, so that every worker is held at its gate
        # and the next request's items stay queued
        first = threading.Thread(target=col.near_vector, args=(
            world.queries[1],), kwargs={"k": 10, "include_objects": False})
        first.start()
        deadline = time.time() + 20.0
        while time.time() < deadline and \
                set(gate.held.values()) != {1}:
            time.sleep(0.01)
        counted = deadline_exceeded_total.labels("batcher").value
        t0 = time.perf_counter()
        with retry.deadline(0.2), pytest.raises(retry.DeadlineExceeded):
            col.near_vector(world.queries[2], k=10, include_objects=False)
        assert 0.19 < time.perf_counter() - t0 < 2.0
        assert deadline_exceeded_total.labels("batcher").value - counted == 1
        assert [len(b._queue) for b in gate.batchers.values()] == [0]
        with retry.deadline(1e-9), pytest.raises(retry.DeadlineExceeded):
            col.near_vector(world.queries[2], k=10, include_objects=False)
    finally:
        gate.release()
    first.join()
    found = col.near_vector(world.queries[2], k=10, include_objects=False)
    assert_is_the_top_k(world, found, world.queries[2], 10, "cosine")


class _Boom(RuntimeError):
    pass


class _Down:
    """One shard's scans raise: the entry points of its index (what the
    collection's drain launches, resolved per dispatch) and of its own
    batcher (what a one-shard request rides)."""

    def __init__(self, shard):
        self.idx = shard.vector_indexes[""]
        self.b = shard._query_batcher("", self.idx)

    def __enter__(self):
        def boom(*_a, **_k):
            raise _Boom("shard down")

        self.saved = self.b._batch_fn, self.b._async_fn
        self.b._batch_fn, self.b._async_fn = boom, None
        self.idx.search_by_vector_batch = boom
        self.idx.search_by_vector_batch_async = boom

    def __exit__(self, *_exc):
        self.b._batch_fn, self.b._async_fn = self.saved
        del self.idx.search_by_vector_batch
        del self.idx.search_by_vector_batch_async


@pytest.mark.parametrize("which", ["single", "multi"])
def test_a_shard_that_raises_fails_the_request(world, which):
    """The error of one shard's dispatch reaches the caller as itself,
    with one shard and with eight."""
    col = world.single if which == "single" else world.multi["cosine"]
    with _Down(list(col.shards.values())[-1]), pytest.raises(_Boom):
        col.near_vector(world.queries[3], k=10, include_objects=False)
    found = col.near_vector(world.queries[3], k=10, include_objects=False)
    assert_is_the_top_k(world, found, world.queries[3], 10, "cosine")


@pytest.mark.parametrize("which", ["raises", "deadline"])
def test_a_failed_fan_out_keeps_every_shard_in_the_trace(world, which):
    """A shard that raises, or a spent budget with the workers held: the
    ``shard.vector_search`` span of every shard is closed into the
    request's trace, those the request never finished included."""
    col = world.multi["cosine"]
    col.near_vector(world.queries[0], k=10, include_objects=False)
    if which == "raises":
        with _Down(list(col.shards.values())[0]), \
                tracing.trace("t", force=True), pytest.raises(_Boom):
            col.near_vector(world.queries[3], k=10, include_objects=False)
    else:
        gate = _Gate(col)
        try:
            with tracing.trace("t", force=True), retry.deadline(0.2), \
                    pytest.raises(retry.DeadlineExceeded):
                col.near_vector(world.queries[3], k=10,
                                include_objects=False)
        finally:
            gate.release()
    spans = [s for s in tracing.recent_traces(1)[0]["spans"]
             if s["name"] == "shard.vector_search"]
    assert sorted(s["attrs"]["shard"] for s in spans) == \
        sorted(col.shards)


@pytest.mark.parametrize("n_local", [0, 1, 3])
def test_local_and_remote_shards_share_the_one_fan_out(world, n_local):
    """A collection with shards on other nodes: every request over more
    than one shard takes ``_fan_out``. Remote shards are the pool's, a
    local shard is enqueued from the request's own thread, and the
    fan-out stages and counters are for several LOCAL shards alone."""
    from weaviate_tpu.db import collection as collection_mod

    col = world.multi["cosine"]
    local = set(list(col.shards)[:n_local])
    began, remote_on, staged = [], [], []

    def remote_search(name, vector, k, **_kw):
        remote_on.append(threading.current_thread())
        ids, dists = col.shards[name].vector_search(vector, k)
        return [{"uuid": col.shards[name]._doc_to_uuid[i], "distance": d}
                for i, d in zip(ids.tolist(), dists.tolist())]

    real_begin = collection_mod.Shard.vector_search_begin

    def begin(self, *a, **kw):
        began.append((self.name, threading.current_thread()))
        return real_begin(self, *a, **kw)

    real_fanout = collection_mod.tailboard.fanout
    fanned = fanout_shards_total.labels(col.config.name).value
    col._is_local = lambda name: name in local
    col._remote_search_degraded = remote_search
    collection_mod.Shard.vector_search_begin = begin
    collection_mod.tailboard.fanout = lambda *a: staged.append(a)
    try:
        found = col.near_vector(world.queries[5], k=10,
                                include_objects=False)
    finally:
        del col._is_local, col._remote_search_degraded
        collection_mod.Shard.vector_search_begin = real_begin
        collection_mod.tailboard.fanout = real_fanout
    assert_is_the_top_k(world, found, world.queries[5], 10, "cosine")
    assert sorted(n for n, _ in began) == sorted(local)
    assert {t for _, t in began} <= {threading.current_thread()}
    assert len(remote_on) == SHARDS - n_local
    assert threading.current_thread() not in remote_on
    assert len(staged) == (1 if n_local > 1 else 0)
    assert fanout_shards_total.labels(col.config.name).value - fanned == \
        (n_local if n_local > 1 else 0)


def test_the_merge_builds_results_for_the_winners_alone(world):
    """8 x k shard answers, k looked up: the merge resolves uuids and
    builds results for what it returns."""
    from weaviate_tpu.db import collection as collection_mod

    col = world.multi["cosine"]
    built = []
    real = collection_mod._ShardHits.__getitem__

    def counting(self, pos):
        built.append((self.name, pos))
        return real(self, pos)

    collection_mod._ShardHits.__getitem__ = counting
    try:
        found = col.near_vector(world.queries[4], k=10,
                                include_objects=False)
    finally:
        collection_mod._ShardHits.__getitem__ = real
    assert len(found) == 10
    assert len(built) == 10     # the walk stops at k
