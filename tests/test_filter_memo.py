"""The filter's leaf memo and ``Shard.allow_mask`` outside ``Shard._lock``
(PR 33): parity with the evaluation as it stood (``filter_reference``),
isolation from a write in progress (I1), read-only masks on every
consumer's path (I3), the counter, the byte cap.
"""

import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import filter_reference  # noqa: E402 — the evaluation before the memo, tests/

from weaviate_tpu.db.database import Database  # noqa: E402
from weaviate_tpu.filters import Filter, Operator  # noqa: E402
from weaviate_tpu.filters.filters import compute_allow_mask  # noqa: E402
from weaviate_tpu.runtime import tracing  # noqa: E402
from weaviate_tpu.runtime.metrics import filter_leaf_total  # noqa: E402
from weaviate_tpu.schema.config import (  # noqa: E402
    CollectionConfig, DataType, InvertedIndexConfig, Property, VectorConfig,
)
from weaviate_tpu.storage.objects import StorageObject  # noqa: E402
from weaviate_tpu.text.inverted import InvertedIndex  # noqa: E402

N = 400  # doc i has bucket i % 100: `bucket < p` allows p % of them
SELECTIVITIES = (0, 1, 10, 50, 99, 100)


def _props(bucket: int) -> dict:
    """One object's properties: for each selectivity p an int flag
    (``q<p>``), a tag (``t<p>``), a word (``m<p>``) and a null
    (``n<p>``) that hold exactly where ``bucket < p``."""
    props = {"bucket": bucket,
             "tags": ["all"] + [f"t{p}" for p in SELECTIVITIES if bucket < p],
             "marks": " ".join(["mark"] + [f"m{p}" for p in SELECTIVITIES
                                           if bucket < p])}
    for p in SELECTIVITIES:
        props[f"q{p}"] = 1 if bucket < p else 0
        props[f"n{p}"] = None if bucket < p else 7
    return props


def _config(name="Item", vectors=False):
    props = [Property(name="bucket", data_type=DataType.INT),
             Property(name="tags", data_type=DataType.TEXT_ARRAY),
             Property(name="marks", data_type=DataType.TEXT)]
    for p in SELECTIVITIES:
        props.append(Property(name=f"q{p}", data_type=DataType.INT))
        props.append(Property(name=f"n{p}", data_type=DataType.INT))
    return CollectionConfig(
        name=name, properties=props,
        vectors=[VectorConfig()] if vectors else [],
        inverted=InvertedIndexConfig(index_null_state=True))


def _uuid(i: int) -> str:
    return f"00000000-0000-0000-0000-{i:012d}"


def _fill(col, n=N, vectors=False, seed=3):
    rng = np.random.default_rng(seed)
    col.batch_put([
        {"uuid": _uuid(i), "properties": _props(i % 100),
         **({"vector": rng.standard_normal(8)} if vectors else {})}
        for i in range(n)])
    return list(col.shards.values())[0]


def _lt(p):
    return Filter.where("bucket", Operator.LESS_THAN, p)


# operator -> (selectivity p -> a filter that allows p % of the base data)
OPERATORS = {
    "Equal": lambda p: Filter.where(f"q{p}", Operator.EQUAL, 1),
    "NotEqual": lambda p: Filter.where(f"q{p}", Operator.NOT_EQUAL, 0),
    "LessThan": _lt,
    "LessThanEqual": lambda p: Filter.where(
        "bucket", Operator.LESS_THAN_EQUAL, p - 1),
    "GreaterThan": lambda p: Filter.where(
        "bucket", Operator.GREATER_THAN, 99 - p),
    "GreaterThanEqual": lambda p: Filter.where(
        "bucket", Operator.GREATER_THAN_EQUAL, 100 - p),
    "ContainsAny": lambda p: Filter.where(
        "tags", Operator.CONTAINS_ANY, [f"t{p}", "t0"]),
    "ContainsAll": lambda p: Filter.where(
        "tags", Operator.CONTAINS_ALL, [f"t{p}", "all"]),
    "IsNullTrue": lambda p: Filter.where(f"n{p}", Operator.IS_NULL, True),
    "IsNullFalse": lambda p: Filter.where(f"n{p}", Operator.IS_NULL, False),
    "Like": lambda p: Filter.where("marks", Operator.LIKE, f"?{p}"),
    "AndOrNot": lambda p: Filter.and_(
        Filter.or_(_lt(p), Filter.where(f"q{p}", Operator.EQUAL, 1)),
        Filter.not_(Filter.or_(
            Filter.where("bucket", Operator.GREATER_THAN_EQUAL, p),
            Filter.where(f"n{p}", Operator.IS_NULL, False)))),
    "OrAndNot": lambda p: Filter.or_(
        Filter.and_(_lt(p), Filter.where("tags", Operator.CONTAINS_ANY,
                                         ["all"])),
        Filter.and_(Filter.where(f"q{p}", Operator.EQUAL, 1),
                    Filter.not_(Filter.where("marks", Operator.LIKE,
                                             "nomatch*")))),
}

# the share an operator's filter allows where it is not p itself
SHARE = {"IsNullFalse": lambda p: 100 - p}


def _insert(col, shard):
    col.batch_put([{"uuid": _uuid(N + i), "properties": _props(i * 7 % 100)}
                   for i in range(10, 45)])


def _delete(col, shard):
    for i in (0, 1, 10, 50, 99, 150, 399):
        assert col.delete_object(_uuid(i))


def _update_across(col, shard):
    # bucket 5 -> 75 and 80 -> 3: each crosses the 10 % and 50 % bounds
    col.put_object(_props(75), uuid=_uuid(5))
    col.put_object(_props(3), uuid=_uuid(80))


def _reconcile(col, shard):
    shard._inverted.reconcile_doc_count(shard._inverted.doc_count + 3)


def _flush(col, shard):
    for b in shard.store.buckets():
        b.flush()


def _compact(col, shard):
    # two segments a bucket, then one
    _flush(col, shard)
    _insert(col, shard)
    _flush(col, shard)
    assert shard._inverted.numeric_bucket.segment_count >= 2
    shard._inverted.numeric_bucket.compact()
    shard._inverted.filter_bucket.compact()
    assert shard._inverted.numeric_bucket.segment_count == 1


MUTATIONS = {"none": None, "insert": _insert, "delete": _delete,
             "update_across": _update_across, "reconcile": _reconcile,
             "flush": _flush, "compact": _compact}


@pytest.fixture(scope="module", params=list(MUTATIONS))
def mutated(request, tmp_path_factory):
    """The base data; every (operator, selectivity) mask memoised; then
    one mutation; then every filter's FIRST mask after it, taken before
    any test empties the memo: what the memo held must not be served."""
    db = Database(str(tmp_path_factory.mktemp(f"memo-{request.param}")))
    col = db.create_collection(_config())
    shard = _fill(col)
    before = shard.doc_id_space
    for make in OPERATORS.values():
        for p in SELECTIVITIES:
            shard.allow_mask(make(p))
    mutate = MUTATIONS[request.param]
    if mutate is not None:
        mutate(col, shard)
    if request.param in ("insert", "update_across", "compact"):
        assert shard.doc_id_space > before
    firsts = {(op, p): shard.allow_mask(make(p))
              for op, make in OPERATORS.items() for p in SELECTIVITIES}
    yield request.param, firsts, shard
    db.close()


def _clear_memo(shard):
    inv = shard._inverted
    with inv._lock:
        inv._leaf_memo.clear()
        inv._leaf_memo_bytes = 0


def _reference(shard, f):
    with shard._lock:
        return filter_reference.reference_mask(f, shard._inverted,
                                               shard.doc_id_space)


@pytest.mark.parametrize("p", SELECTIVITIES)
@pytest.mark.parametrize("op", list(OPERATORS))
def test_mask_equals_locked_reference(mutated, op, p):
    """(a) After the mutation the first call (whatever the memo held),
    a miss and a hit equal the evaluation as it stood, bit for bit."""
    mutation, firsts, shard = mutated
    f = OPERATORS[op](p)
    ref = _reference(shard, f)
    assert ref.dtype == np.bool_ and len(ref) == shard.doc_id_space
    first = firsts[op, p]
    _clear_memo(shard)
    miss = shard.allow_mask(f)
    hit = shard.allow_mask(f)
    for got in (first, miss, hit):
        assert got.dtype == np.bool_
        assert np.array_equal(got, ref)
    if mutation == "none":
        assert int(ref.sum()) == N * SHARE.get(op, lambda p: p)(p) // 100


def _served():
    """hit / miss / locked of ``weaviate_tpu_filter_leaf_total`` now."""
    return tuple(filter_leaf_total.labels(r).value
                 for r in ("hit", "miss", "locked"))


@pytest.fixture
def items(tmp_path):
    db = Database(str(tmp_path))
    col = db.create_collection(_config(vectors=True))
    shard = _fill(col, vectors=True)
    yield db, col, shard
    db.close()


def test_a_leaf_filter_is_the_shared_read_only_mask(items):
    """(c) I3: a memoised mask refuses a write, and a hit hands out the
    very array the miss built."""
    _db, _col, shard = items
    miss = shard.allow_mask(_lt(50))
    hit = shard.allow_mask(_lt(50))
    assert hit is miss and not miss.flags.writeable
    with pytest.raises(ValueError):
        miss[0] = True
    with pytest.raises(ValueError):
        miss &= False
    # a combination is a new array; its leaves stay as they were
    both = shard.allow_mask(Filter.and_(_lt(50), _lt(10)))
    assert both is not miss and int(both.sum()) == N // 10
    assert int(miss.sum()) == N // 2


@pytest.mark.parametrize("path", ["near_vector", "bm25", "hybrid",
                                  "aggregate", "batch_delete",
                                  "classification"])
def test_consumers_run_on_a_memoised_mask(items, path):
    """(c) I3: every path a request's mask reaches runs on the shared
    read-only array (second call: a hit) and answers as on the first."""
    db, col, shard = items
    f = _lt(10)
    q = np.random.default_rng(5).standard_normal(8).astype(np.float32)

    def run():
        if path == "near_vector":
            return [r.uuid for r in col.near_vector(q, k=5, where=f)]
        if path == "bm25":
            return [r.uuid for r in col.bm25("mark", k=5, where=f)]
        if path == "hybrid":
            return sorted(r.uuid for r in col.hybrid("mark", vector=q, k=5,
                                                     where=f))
        if path == "aggregate":
            return col.aggregate(where=f)["meta"]["count"]
        if path == "batch_delete":
            return col.batch_delete(f, dry_run=True)["matches"]
        from weaviate_tpu.classification import ClassificationManager

        unlabeled, labeled = ClassificationManager(db)._split(
            col, ["bucket"], f, f)
        return len(unlabeled), len(labeled)

    before = _served()
    first = run()
    second = run()
    after = _served()
    assert first == second
    assert after[0] > before[0], "the second call did not hit the memo"
    assert after[2] == before[2]
    if path == "aggregate":
        assert first == N // 10
    if path == "batch_delete":
        assert first == N // 10
        assert col.batch_delete(f)["successful"] == N // 10
        assert int(shard.allow_mask(f).sum()) == 0
    if path == "classification":
        assert first == (0, N // 10)


def _operands(path):
    from weaviate_tpu.runtime.metrics import filter_operand_total

    return {r: filter_operand_total.labels(path, r).value
            for r in ("hit", "miss", "shared", "uncached")}


def _moved(path, before):
    return {r: int(v - before[r]) for r, v in _operands(path).items()
            if v != before[r]}


# p = 50 allows half the rows: a row of a coalesced dispatch's bitmask;
# p = 1 allows 4 <= capacity / 64: solo, the gathered slot list
@pytest.mark.parametrize("p, path", [(50, "bitmask"), (1, "gathered")])
def test_a_memo_drop_alone_makes_the_operand_a_miss(items, p, path):
    """(PR 40) The vector index keeps a memoised mask's device operands
    under the mask OBJECT. A write that moves no slot of the vector index
    (an object without a vector) still drops the memo, so the next
    request brings a NEW object: a miss there, by the key alone."""
    _db, col, shard = items
    idx = shard.vector_indexes[""]
    q = np.random.default_rng(6).standard_normal(8).astype(np.float32)

    def search():
        before = _operands(path)
        got = [r.uuid for r in col.near_vector(q, k=5, where=_lt(p))]
        return got, _moved(path, before)

    first, moved = search()
    assert moved == {"miss": 1}
    assert search() == (first, {"hit": 1})
    gen, kept = idx._slot_gen, shard.allow_mask(_lt(p))
    col.put_object(_props(0), uuid=_uuid(N + 7))          # no vector
    assert idx._slot_gen == gen                 # the slot table stood still
    assert shard.allow_mask(_lt(p)) is not kept  # the memo did not
    assert search() == (first, {"miss": 1})
    assert search() == (first, {"hit": 1})


def test_a_combined_filter_is_never_kept(items):
    """(PR 40) ``a AND b`` is a new writeable array a request: its leaves
    hit the memo, its device operand is built for that dispatch alone."""
    _db, col, shard = items
    both = Filter.and_(_lt(50), Filter.where("bucket",
                                             Operator.GREATER_THAN_EQUAL, 5))
    q = np.random.default_rng(7).standard_normal(8).astype(np.float32)
    first = [r.uuid for r in col.near_vector(q, k=5, where=both)]
    leaves, before = _served(), _operands("bitmask")
    assert [r.uuid for r in col.near_vector(q, k=5, where=both)] == first
    assert _moved("bitmask", before) == {"uncached": 1}
    assert _served()[0] == leaves[0] + 2        # both leaves: hits
    assert shard.vector_indexes[""]._operands.resident[0] == 0


def test_counter_and_span_count_leaf_look_ups(items):
    """(d) Four distinct clauses, forty requests: 4 misses, 36 hits; a
    write between two equal requests: a second miss. The span carries
    the same counts."""
    _db, col, shard = items
    before = _served()
    for _round in range(10):
        for b in (1, 10, 50, 99):
            shard.allow_mask(_lt(b))
    after = _served()
    assert (after[0] - before[0], after[1] - before[1],
            after[2] - before[2]) == (36, 4, 0)
    col.put_object(_props(42), uuid=_uuid(N + 1))
    with tracing.trace("t", force=True):
        shard.allow_mask(_lt(50))
        shard.allow_mask(_lt(50))
    again = _served()
    assert (again[0] - after[0], again[1] - after[1]) == (1, 1)
    spans = sorted((s for s in tracing.recent_traces(1)[0]["spans"]
                    if s["name"] == "shard.allow_mask"),
                   key=lambda s: s["start_ms"])
    assert [(s["attrs"]["leaf_hits"], s["attrs"]["leaf_misses"],
             s["attrs"]["locked"]) for s in spans] == [
                 (0, 1, False), (1, 0, False)]
    # NotEqual looks two leaves up: the value's and the live docs'
    shard.allow_mask(Filter.where("q10", Operator.NOT_EQUAL, 0))
    assert _served()[1] - again[1] == 2


def test_a_write_in_progress_sends_the_evaluation_under_the_lock(items):
    """I1's fall-back: with the write generation odd (a mutating section
    is running) the filter is evaluated under ``Shard._lock`` and counted
    ``locked``; a section that began during the build discards it."""
    _db, _col, shard = items
    ref = _reference(shard, _lt(50))
    before = _served()
    with shard._lock, shard._writing():
        assert shard._write_gen & 1
        with shard._lock, shard._writing():  # nested: still odd
            assert shard._write_gen & 1
        assert shard._write_gen & 1
        got = shard.allow_mask(_lt(50))
    assert not shard._write_gen & 1
    assert np.array_equal(got, ref)
    assert _served()[2] - before[2] == 1

    # a write section that runs while the mask is built
    inv = shard._inverted
    real = inv.leaf_mask

    def build_across_a_write(key, size, build, stats):
        with shard._lock, shard._writing():
            pass
        return real(key, size, build, stats)

    inv.leaf_mask = build_across_a_write
    try:
        got = shard.allow_mask(_lt(10))
    finally:
        del inv.leaf_mask
    assert np.array_equal(got, _reference(shard, _lt(10)))
    assert _served()[2] - before[2] == 2


def test_isolation_under_fire(tmp_path):
    """(b) I1: one writer flips object A's bucket between two values that
    both satisfy ``bucket < 50`` and object B's across the bound, each an
    update (unindex + index: a new doc id) inside one write section; eight
    readers evaluate the filter for two seconds. In every mask A has
    exactly one live doc id, and B is there only with a value that
    qualifies: no mask saw the instant between the unindex and the
    index."""
    db = Database(str(tmp_path))
    col = db.create_collection(_config())
    shard = _fill(col, n=100)
    static = shard.doc_id_space
    static_ref = _reference(shard, _lt(50))[:static].copy()
    history = {}  # doc id -> (object, bucket), written after each put

    def put(name, uuid, bucket):
        obj = StorageObject(uuid=uuid, properties=_props(bucket))
        (doc_id,) = shard.put_object_batch([obj])
        history[doc_id] = (name, bucket)

    put("A", _uuid(1000), 10)
    put("B", _uuid(1001), 30)
    stop = threading.Event()
    seen, errors = [set() for _ in range(8)], []

    def writer():
        i = 0
        while not stop.is_set():
            put("A", _uuid(1000), (10, 20)[i & 1])
            put("B", _uuid(1001), (70, 30)[i & 1])
            i += 1
            time.sleep(0.0005)  # let unlocked evaluations happen too

    def reader(k):
        try:
            while not stop.is_set():
                mask = shard.allow_mask(_lt(50))
                if not np.array_equal(mask[:static], static_ref):
                    errors.append("static part differs")
                seen[k].add(tuple(np.flatnonzero(mask[static:]) + static))
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    before = _served()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=reader, args=(k,)) for k in range(8)]
    try:
        for t in threads:
            t.start()
        time.sleep(2.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    tails = set().union(*seen)
    assert len(tails) > 2, "the readers saw no write"
    for tail in tails:
        who = [history[d] for d in tail]
        assert [b for n, b in who if n == "A"] in ([10], [20]), who
        assert [b for n, b in who if n == "B"] in ([], [30]), who
    after = _served()
    assert after[0] + after[1] > before[0] + before[1]
    db.close()


def test_byte_cap_evicts_the_least_recently_used(tmp_path, monkeypatch):
    """(e) The memo never holds more than its cap, and what leaves is the
    mask looked up longest ago."""
    db = Database(str(tmp_path))
    col = db.create_collection(_config())
    shard = _fill(col)
    inv = shard._inverted
    size = shard.doc_id_space  # one mask is `size` bytes
    monkeypatch.setattr(InvertedIndex, "LEAF_MEMO_MAX_BYTES", 3 * size + 7)
    for b in (10, 20, 30):
        shard.allow_mask(_lt(b))
    shard.allow_mask(_lt(10))  # 20 is now the least recently used
    shard.allow_mask(_lt(40))
    assert inv._leaf_memo_bytes == 3 * size <= inv.LEAF_MEMO_MAX_BYTES
    held = {k[-1] for k in inv._leaf_memo}
    assert held == {10.0, 30.0, 40.0}
    before = _served()
    shard.allow_mask(_lt(20))  # a miss again; 30 leaves
    shard.allow_mask(_lt(10))
    assert _served()[1] - before[1] == 1
    assert {k[-1] for k in inv._leaf_memo} == {10.0, 40.0, 20.0}
    assert inv._leaf_memo_bytes == sum(
        m.nbytes for m in inv._leaf_memo.values()) == 3 * size
    # a mask larger than the cap is served and not kept
    monkeypatch.setattr(InvertedIndex, "LEAF_MEMO_MAX_BYTES", size - 1)
    _clear_memo(shard)
    assert int(shard.allow_mask(_lt(50)).sum()) == N // 2
    assert len(inv._leaf_memo) == 0 and inv._leaf_memo_bytes == 0
    db.close()


@pytest.mark.parametrize("limit", [4096, 8])
def test_numeric_range_ids_stays_sorted_and_unique(tmp_path, monkeypatch,
                                                   limit):
    """(f) ``numeric_range_ids`` still hands its callers sorted unique
    ids, on both sides of the switch from a read a key to one merged
    walk (``RANGE_KEYS_CACHED``); an array property lists a doc under
    several values and still once in the result."""
    monkeypatch.setattr(InvertedIndex, "RANGE_KEYS_CACHED", limit)
    db = Database(str(tmp_path))
    cfg = _config()
    cfg.properties.append(Property(name="sizes",
                                   data_type=DataType.INT_ARRAY))
    col = db.create_collection(cfg)
    col.batch_put([{"uuid": _uuid(i),
                    "properties": {**_props(i % 100),
                                   "sizes": [i % 7, i % 11, 3]}}
                   for i in range(N)])
    shard = list(col.shards.values())[0]
    inv = shard._inverted
    for prop, lo, hi in (("bucket", None, 50.0), ("bucket", 10.0, 20.0),
                         ("bucket", None, 5.0), ("sizes", 2.0, None),
                         ("sizes", None, 100.0), ("bucket", 200.0, None)):
        got = inv.numeric_range_ids(prop, lo, hi)
        want = filter_reference.numeric_range_ids(inv, prop, lo, hi)
        assert got.dtype == np.uint64 and np.array_equal(got, want)
        assert np.all(got[1:] > got[:-1])
    f = Filter.where("sizes", Operator.GREATER_THAN_EQUAL, 3)
    assert np.array_equal(shard.allow_mask(f), _reference(shard, f))
    assert int(shard.allow_mask(f).sum()) == N
    db.close()


def test_mask_length_is_part_of_the_leaf(items):
    """A caller that names another size (``compute_allow_mask`` takes
    it) is never handed a mask of the wrong length."""
    _db, _col, shard = items
    inv = shard._inverted
    full = compute_allow_mask(_lt(50), inv, shard.doc_id_space)
    short = compute_allow_mask(_lt(50), inv, 100)
    long = compute_allow_mask(_lt(50), inv, shard.doc_id_space + 50)
    assert (len(full), len(short), len(long)) == (N, 100, N + 50)
    assert np.array_equal(short, full[:100])
    assert np.array_equal(long[:N], full) and not long[N:].any()
    # True, 1 and 1.0 are one dictionary key and three filter values
    one = compute_allow_mask(Filter.where("q50", Operator.EQUAL, 1), inv, N)
    true = compute_allow_mask(Filter.where("q50", Operator.EQUAL, True),
                              inv, N)
    assert int(one.sum()) == N // 2 and int(true.sum()) == 0
