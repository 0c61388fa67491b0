"""The exact rescore of a compressed store on the device (ISSUE 38).

A ``QuantizedVectorStore`` keeps ONE full-precision tier: float32 rows in
HBM beside the codes wherever the memory watchdog grants them (the scan's
own program then ends with the gather, the exact distances and the final
top-k), float32 rows on the host where it does not. Both tiers must give
the same answers from the same rows; which one a store holds is decided by
residency alone.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.engine.quantized import QuantizedVectorStore, _row_lanes
from weaviate_tpu.ops.candidates import gather_rescore_topk
from weaviate_tpu.runtime.memwatch import MemoryMonitor
from weaviate_tpu.runtime.metrics import rescore_dispatch_total

DIM, ROWS = 64, 2048
# every single-device scan entry point a store can reach
KINDS = {
    "bq": dict(quantization="bq"),
    "bq-prefix": dict(quantization="bq", prefix_bits=128),
    "pq": dict(quantization="pq", pq_centroids=256, pq_segments=16),
    "pq4": dict(quantization="pq"),
    "sq": dict(quantization="sq"),
}
SERVED = ("bq", "pq", "sq")     # jit_bq_topk, jit_pq_topk, jit_sq_topk


def corpus(seed: int, rows: int = ROWS, dim: int = DIM):
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((rows // 16, dim)).astype(np.float32)
    x = (np.repeat(centres, 16, axis=0)
         + 0.3 * rng.standard_normal((rows, dim)).astype(np.float32))
    q = x[rng.choice(rows, 16, replace=False)] \
        + 0.1 * rng.standard_normal((16, dim)).astype(np.float32)
    return x, q


def build(kind: str, metric: str, x: np.ndarray, **kw) -> QuantizedVectorStore:
    args = dict(KINDS[kind])
    dim = x.shape[1]
    args.update(kw)
    st = QuantizedVectorStore(dim=dim, metric=metric, capacity=1024,
                              chunk_size=1024, **args)
    if st.quantization != "bq":
        # a quantizer wants 256 rows or more: a small store borrows them
        st.train(x[: len(x) // 2] if len(x) >= 512
                 else corpus(99, dim=dim)[0])
    st.add(x)
    return st


def pair(kind: str, metric: str, x: np.ndarray, **kw):
    """-> (device tier, host tier) over the same rows."""
    dev = build(kind, metric, x, **kw)
    host = build(kind, metric, x, rescore="host", **kw)
    assert dev.rescore_mode() == "fused" and dev._host_vectors is None
    assert host.rescore_mode() == "post" and host.rescore_rows is None
    return dev, host


def assert_same_answers(got, want):
    """Equal ids, ties apart; distances within 1e-5 (of max(d, 1))."""
    (gd, gi), (wd, wi) = got, want
    assert gd.shape == wd.shape and gi.shape == wi.shape
    tol = 1e-5 * np.maximum(np.abs(wd), 1.0)
    live = wi >= 0
    assert np.array_equal(gi >= 0, live)
    assert np.all(np.abs(gd - wd)[live] <= tol[live])
    for r, c in zip(*np.nonzero(gi != wi)):
        # two rows at one distance may come back in either order
        near = [abs(wd[r, c] - wd[r, j]) for j in (c - 1, c + 1)
                if 0 <= j < wd.shape[1]]
        assert min(near) <= 2 * tol[r, c], (r, c, gi[r], wi[r])
        assert gi[r, c] in wi[r] or c == wd.shape[1] - 1


@pytest.fixture(scope="module")
def stores():
    cache = {}

    def get(kind, metric):
        if (kind, metric) not in cache:
            dim = 256 if "prefix" in kind else DIM
            x, q = corpus(11, dim=dim)
            cache[kind, metric] = (*pair(kind, metric, x), x, q)
        return cache[kind, metric]

    return get


# -- the two tiers agree ------------------------------------------------------

@pytest.mark.parametrize("k", [10, 100])
@pytest.mark.parametrize("b", [1, 16])
@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
@pytest.mark.parametrize("kind", SERVED)
def test_device_tier_answers_as_the_host_tier(stores, kind, metric, b, k):
    dev, host, _, q = stores(kind, metric)
    assert_same_answers(dev.search(q[:b], k), host.search(q[:b], k))


@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
@pytest.mark.parametrize("kind", ["bq-prefix", "pq4"])
def test_the_other_entry_points_end_with_the_same_tail(stores, kind, metric):
    dev, host, _, q = stores(kind, metric)
    assert_same_answers(dev.search(q, 10), host.search(q, 10))


@pytest.mark.parametrize("kind", SERVED)
def test_a_single_query_comes_back_unbatched(stores, kind):
    dev, host, _, q = stores(kind, "l2-squared")
    d, i = dev.search(q[0], 10)
    assert d.shape == (10,) and i.shape == (10,)
    wd, wi = host.search(q[0], 10)
    assert_same_answers((d[None], i[None]), (wd[None], wi[None]))


@pytest.mark.parametrize("kind", SERVED)
def test_fewer_live_rows_than_candidates_pad_with_minus_one(kind):
    """40 rows, k = 10: the scan's 160 candidate places are mostly -1
    padding; k = 64: so is the answer's tail."""
    x, q = corpus(3, rows=64)
    dev, host = pair(kind, "l2-squared", x[:40])
    assert_same_answers(dev.search(q, 10), host.search(q, 10))
    d, i = dev.search(q, 64)
    assert (i[:, :40] >= 0).all() and (i[:, 40:] == -1).all()
    assert all(len(set(r[:40].tolist())) == 40 for r in i)


@pytest.mark.parametrize("kind", SERVED)
def test_after_deletes(kind):
    x, q = corpus(5)
    dev, host = pair(kind, "cosine", x)
    gone = np.arange(0, ROWS, 3)
    for st in (dev, host):
        st.delete(gone)
    got = dev.search(q, 10)
    assert not np.isin(got[1], gone).any()
    assert_same_answers(got, host.search(q, 10))


@pytest.mark.parametrize("kind", SERVED)
def test_deleted_rows_never_fill_an_answer(kind):
    """All but 6 rows deleted: their slots still hold rows on the device,
    and the scan still hands them on as candidates of no distance."""
    x, q = corpus(6, rows=256)
    dev = build(kind, "l2-squared", x)
    dev.delete(np.arange(6, 256))
    d, i = dev.search(q, 10)
    assert ((i[:, :6] >= 0) & (i[:, :6] < 6)).all() and (i[:, 6:] == -1).all()


@pytest.mark.parametrize("kind", SERVED)
def test_after_a_grow(kind):
    """1,024 slots double twice under ``add``: the resident rows grow with
    the codes (``grow_rows``) and keep what they held."""
    x, q = corpus(7, rows=4096)
    dev, host = pair(kind, "l2-squared", x[:1000])
    for st in (dev, host):
        st.add(x[1000:])
    assert dev.capacity == 4096
    assert dev.rescore_rows.shape == (4096, _row_lanes(DIM))
    assert dev._host_vectors is None
    assert_same_answers(dev.search(q, 10), host.search(q, 10))
    assert np.array_equal(dev.get(np.arange(4096)), host.get(np.arange(4096)))


@pytest.mark.parametrize("kind", SERVED)
def test_after_compact(kind):
    x, q = corpus(8)
    dev, host = pair(kind, "cosine", x)
    gone = np.arange(1, ROWS, 2)
    for st in (dev, host):
        st.delete(gone)
        mapping = st.compact()
    assert dev.capacity == 1024 and dev.live_count() == ROWS // 2
    assert dev.rescore_rows.shape[0] == 1024 and dev._host_vectors is None
    assert mapping[0] == 0 and mapping[1] == -1
    assert_same_answers(dev.search(q, 10), host.search(q, 10))


@pytest.mark.parametrize("kind", list(KINDS))
def test_under_a_per_query_filter(stores, kind):
    dev, host, x, q = stores(kind, "l2-squared")
    rng = np.random.default_rng(13)
    masks = rng.random((len(q), dev.capacity)) < 0.4
    masks[2] = False                      # a filter that matches nothing
    masks[3, :] = False
    masks[3, 100:104] = True              # fewer allowed rows than k
    got = dev.search(q, 10, allow_mask=masks)
    for r, ids in enumerate(got[1]):
        assert masks[r][ids[ids >= 0]].all()
    assert (got[1][2] == -1).all()
    assert sorted(got[1][3][got[1][3] >= 0].tolist()) == [100, 101, 102, 103]
    keep = [r for r in range(len(q)) if r != 3]   # (the host tier's own
    want = host.search(q, 10, allow_mask=masks)   # answer there is not held)
    assert_same_answers((got[0][keep], got[1][keep]),
                        (want[0][keep], want[1][keep]))
    # one mask for the whole batch folds into the live-row mask instead
    assert_same_answers(dev.search(q, 10, allow_mask=masks[0]),
                        host.search(q, 10, allow_mask=masks[0]))


# -- nothing is kept on the host ----------------------------------------------

@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
@pytest.mark.parametrize("kind", SERVED)
def test_get_reads_the_device_rows(stores, kind, metric):
    dev, host, x, _ = stores(kind, metric)
    slots = np.array([0, 5, 77, ROWS - 1, 5])
    got = dev.get(slots)
    assert got.shape == (5, DIM) and got.dtype == np.float32
    assert np.array_equal(got, host.get(slots))     # bit for bit
    if metric == "l2-squared":
        assert np.array_equal(got, x[slots])
    # the rows past ``dim`` are zeros: they add nothing to any distance
    assert dev.rescore_rows.dtype == jnp.float32
    assert not np.asarray(dev.rescore_rows[:, DIM:]).any()


@pytest.mark.parametrize("kind", SERVED)
def test_snapshot_and_restore_with_no_host_copy(stores, kind):
    dev, host, x, q = stores(kind, "cosine")
    snap = dev.snapshot()
    assert snap["vectors"].shape == (dev.capacity, DIM)
    assert np.array_equal(snap["vectors"], host.snapshot()["vectors"])
    back = QuantizedVectorStore.restore(snap)
    assert back.rescore_mode() == "fused" and back._host_vectors is None
    assert back.count == dev.count
    assert np.array_equal(np.asarray(back.codes), np.asarray(dev.codes))
    assert_same_answers(back.search(q, 10), dev.search(q, 10))


def test_an_index_compresses_into_resident_rows():
    """``FlatIndex.compress`` (what a pq or sq class does at its training
    limit) and a bq index from its first row: float32 rows on the device,
    none on the host, and the answers carry doc ids as before."""
    x, q = corpus(17, rows=1024)
    ids = np.arange(1024) + 5000
    flat = FlatIndex(dim=DIM, metric="l2-squared", capacity=1024)
    flat.add_batch(ids, x)
    want = flat.search_by_vector_batch(q, 5)[0]
    for quantization in ("pq", "sq"):
        idx = FlatIndex(dim=DIM, metric="l2-squared", capacity=1024)
        idx.add_batch(ids, x)
        idx.compress(quantization=quantization, pq_centroids=256,
                     pq_segments=DIM)
        assert idx.store.rescore_mode() == "fused"
        assert idx.store._host_vectors is None
        assert np.array_equal(idx.search_by_vector_batch(q, 5)[0], want)
    bq = FlatIndex(dim=DIM, metric="l2-squared", capacity=1024,
                   quantization="bq")
    bq.add_batch(ids, x)
    assert bq.store.rescore_mode() == "fused"
    assert bq.store._host_vectors is None


# -- the residency rule --------------------------------------------------------

def tiers_counted():
    return {t: rescore_dispatch_total.labels(t).value
            for t in ("device", "host")}


def test_the_counter_says_which_tier_rescored(stores):
    dev, host, _, q = stores("bq", "cosine")
    before = tiers_counted()
    dev.search(q, 10)
    dev.search(q[:1], 10)
    host.search(q, 10)
    after = tiers_counted()
    assert after["device"] - before["device"] == 2   # one a dispatch
    assert after["host"] - before["host"] == 1
    codes_only = QuantizedVectorStore(dim=DIM, quantization="bq",
                                      capacity=1024, rescore="none")
    codes_only.add(np.ones((4, DIM), np.float32))
    codes_only.search(q, 2)
    assert tiers_counted() == after                  # nothing to rescore


@pytest.mark.parametrize("kind", SERVED)
def test_a_tiny_budget_keeps_the_rows_on_the_host(kind, caplog):
    """The watchdog decides, never a name: the same constructor call under
    a device budget the rows do not fit into builds the host tier."""
    x, q = corpus(19)
    roomy = build(kind, "l2-squared", x)
    with caplog.at_level(logging.WARNING, "weaviate_tpu.engine.quantized"):
        tight = build(kind, "l2-squared", x, memwatch=MemoryMonitor(
            device_limit_bytes=1024 * _row_lanes(DIM) * 4))
    assert "stay on the host" in caplog.text
    assert roomy.rescore_mode() == "fused"
    assert tight.rescore_mode() == "post" and tight.rescore_rows is None
    assert tight._host_vectors.shape == (tight.capacity, DIM)
    before = tiers_counted()
    got = tight.search(q, 10)
    after = tiers_counted()
    assert after["host"] - before["host"] == 1
    assert after["device"] == before["device"]
    assert_same_answers(got, roomy.search(q, 10))


def test_a_grow_past_the_watermark_moves_the_rows_to_the_host(caplog):
    """Room for 2,048 slots of rows, not for 4,096 (the ledger's total is
    what the CPU backend's watchdog reads, so the budget is set over
    what is registered when the store is built)."""
    from weaviate_tpu.runtime import hbm_ledger

    x, q = corpus(23, rows=4096)
    row_bytes = _row_lanes(DIM) * 4
    mw = MemoryMonitor(
        device_limit_bytes=hbm_ledger.ledger.total_bytes() + 3000 * row_bytes,
        high_watermark=1.0)
    st = build("bq", "l2-squared", x[:1000], memwatch=mw)
    host = build("bq", "l2-squared", x[:1000], rescore="host")
    assert st.rescore_mode() == "fused" and st.capacity == 1024
    with caplog.at_level(logging.WARNING, "weaviate_tpu.engine.quantized"):
        for s in (st, host):
            s.add(x[1000:])
    assert "move to the host" in caplog.text
    assert st.capacity == 4096 and st.rescore_mode() == "post"
    assert st.rescore_rows is None
    assert np.array_equal(st._host_vectors, host._host_vectors)
    assert_same_answers(st.search(q, 10), host.search(q, 10))
    # the watchdog was only asked: nothing refused, nothing latched
    assert not mw.under_pressure


def test_device_fits_is_check_device_alloc_as_a_question():
    from weaviate_tpu.runtime.hbm_ledger import HBMLedger
    from weaviate_tpu.runtime.memwatch import InsufficientMemoryError

    led = HBMLedger()
    mw = MemoryMonitor(device_limit_bytes=1000, ledger=led,
                       high_watermark=0.9, low_watermark=0.5)
    assert mw.device_fits(900) and not mw.device_fits(901)
    led.register("corpus", 600, collection="c", shard="s")
    assert mw.device_fits(300) and not mw.device_fits(301)
    assert not mw.under_pressure
    with pytest.raises(InsufficientMemoryError):
        mw.check_device_alloc(400)
    assert mw.under_pressure and not mw.device_fits(1)   # latched: no
    assert MemoryMonitor(ledger=led).device_fits(1 << 50) or \
        MemoryMonitor(ledger=led).device_budget() is not None


def test_a_shard_hands_its_watchdog_to_the_store(tmp_path):
    """What a ``Server`` builds: a bq class from its first row, under the
    database's own ``MemoryMonitor``; the same class under a budget with
    no room keeps its rows on the host, with no option set anywhere."""
    from weaviate_tpu.db.database import Database
    from weaviate_tpu.schema.config import (CollectionConfig, Property,
                                            VectorConfig, VectorIndexConfig)

    x, q = corpus(29, rows=256)
    found = {}
    from weaviate_tpu.runtime.hbm_ledger import HBMLedger

    # 1 MB: room for an import batch (64 KB), none for 8,192 slots of rows
    for name, limit in (("Roomy", None), ("Tight", 1 << 20)):
        mw = MemoryMonitor(device_limit_bytes=limit, ledger=HBMLedger())
        db = Database(str(tmp_path / name), memory_monitor=mw)
        try:
            col = db.create_collection(CollectionConfig(
                name=name, properties=[Property("n", "int")],
                vectors=[VectorConfig(index=VectorIndexConfig(
                    index_type="flat", metric="l2-squared",
                    quantization="bq"))]))
            col.batch_put([
                {"uuid": f"00000000-0000-0000-0000-{i:012d}",
                 "properties": {"n": i}, "vector": x[i]}
                for i in range(256)])
            shard = next(iter(col.shards.values()))
            store = shard.vector_indexes[""].store
            assert store._memwatch is mw
            found[name] = (store.rescore_mode(),
                           [r.uuid for r in col.near_vector(q[0], k=5)])
        finally:
            db.close()
    assert found["Roomy"][0] == "fused" and found["Tight"][0] == "post"
    assert found["Roomy"][1] == found["Tight"][1]


# -- float32 rows, float32 arithmetic ------------------------------------------

def contractions(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    contractions(inner, out)
    return out


@pytest.mark.parametrize("metric", ["cosine", "dot", "l2-squared"])
def test_the_rescore_contracts_at_highest_precision(metric):
    """On the v5e ``Precision.DEFAULT`` is ONE bf16 pass over float32
    operands: distances off by 1e-3 to 1e-2 against a limit of 1e-4, and
    no CPU run shows it. So the program is read, not run: every
    contraction over float32 rows asks for HIGHEST (l2-squared subtracts
    and squares, and contracts nothing)."""
    q = jnp.zeros((4, 128), jnp.float32)
    cand = jnp.zeros((4, 32), jnp.int32)
    for dtype, want in ((jnp.float32, jax.lax.Precision.HIGHEST),
                        (jnp.bfloat16, jax.lax.Precision.DEFAULT)):
        rows = jnp.zeros((256, 128), dtype)
        jaxpr = jax.make_jaxpr(
            lambda q, c, r: gather_rescore_topk(q, c, r, 8, metric))(
                q, cand, rows)
        dots = contractions(jaxpr.jaxpr, [])
        assert bool(dots) == (metric != "l2-squared")
        for eqn in dots:
            prec = eqn.params["precision"]
            prec = prec if isinstance(prec, tuple) else (prec, prec)
            assert all(p == want for p in prec), (dtype, eqn.params)


@pytest.mark.parametrize("kind", SERVED)
def test_the_served_program_holds_the_rescore(stores, kind):
    """One program a dispatch: the scan's own jitted entry point, traced
    with the rows, contains the row gather and (cosine) the HIGHEST
    contraction; nothing runs after it."""
    from weaviate_tpu.ops import bq, pq, sq

    dev, _, _, q = stores(kind, "cosine")
    qd = jnp.asarray(q)
    if kind == "bq":
        fn = lambda: bq.bq_topk(                       # noqa: E731
            bq.bq_encode(qd), dev.codes, k=160, valid=dev.valid,
            rescore_q=qd, rescore_rows=dev.rescore_rows, rescore_k=10,
            rescore_metric="cosine")
    elif kind == "pq":
        fn = lambda: pq.pq_topk(                       # noqa: E731
            qd, dev.codes, dev.codebook.centroids, k=160, chunk_size=1024,
            metric="cosine", valid=dev.valid,
            rescore_rows=dev.rescore_rows, rescore_k=10)
    else:
        fn = lambda: sq.sq_topk(                       # noqa: E731
            qd, dev.codes, dev.row_terms, dev.sq_quantizer.params, k=160,
            chunk_size=1024, metric="cosine", valid=dev.valid,
            rescore_rows=dev.rescore_rows, rescore_k=10)
    jaxpr = jax.make_jaxpr(fn)()
    assert len(jaxpr.jaxpr.eqns) == 1 or kind == "bq"   # bq_encode + scan
    assert jaxpr.jaxpr.eqns[-1].params["name"] == f"{kind}_topk"
    d, i = fn()
    assert d.shape == (16, 10) and i.shape == (16, 10)
    highest = [e for e in contractions(jaxpr.jaxpr, [])
               if e.params["precision"] is not None
               and jax.lax.Precision.HIGHEST in (
                   e.params["precision"]
                   if isinstance(e.params["precision"], tuple)
                   else (e.params["precision"],))]
    assert highest, "no float32 contraction in the scan's program"
    assert_same_answers((np.asarray(d), np.asarray(i)), dev.search(q, 10))
