"""A ``dynamic`` class as a deployment (ISSUE 43): the benchmark's own
class (``benchmarks/configs/glove-dynamic-cosine.json``, threshold cut to
2,048) through a ``Server`` over REST + gRPC, held to the plain IVF
reference (``tests/ivf_reference.py``) given the index's own centroids.

One scenario, walked once (the ``served`` fixture), and what it saw is
asserted case by case: exact answers below the threshold; after it, the
membership rule, the probe, the delta leg and their merge, plain and under
``bucket < b``; rows found from the delta and, after the fold, from the
lists; recall against the exact scan; the bfloat16 class that has to FAIL
the distance tolerance; the keys given back; spans, series and the ledger.
CPU, 100-d, 24,576 rows: nothing here is a device time."""

import copy
import json
import os
import sys

import numpy as np
import pytest

import ivf_reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))
import run  # noqa: E402 — the harness: its module loader
import wire  # noqa: E402 — the benchmark's socket clients

with open(os.path.join(REPO, "benchmarks", "configs",
                       "glove-dynamic-cosine.json")) as _f:
    GLOVE = json.load(_f)

DIM, K, METRIC = GLOVE["dim"], GLOVE["k"], GLOVE["metric"]
THRESHOLD, BATCH, ROWS = 2048, 1024, 24 * 1024
# what the write path does with these sizes (engine/ivf.py): trained at
# the upgrade (2,048 rows), a full delta of 8,192 folded at 10,240 rows,
# where the corpus is five times the trained one: a retrain; the next full
# delta at 18,432 is a fold; 6,144 rows are left in the delta
REBUILT_AT, FOLDED_AT = 10240, 18432
TOLERANCE = GLOVE["limits"]["distance_error_max"]
FLOOR = GLOVE["limits"]["distance_scale_floor"]
FILTER = {"property": "bucket", "operator": "less_than"}
BOUNDS = (-1, 50, 10)   # -1: no filter
# the class names no flatSearchCutoff, so upstream's default holds: at
# 24,576 rows both filters allow fewer rows (12,288 and 2,458) and are
# answered exactly, never by the probe (ISSUE 51)
CUTOFF = 40_000


def glove_class(name="Glove", **index_config) -> dict:
    klass = copy.deepcopy(GLOVE["class"])
    klass["class"] = name
    klass["vectorIndexConfig"].update(threshold=THRESHOLD, **index_config)
    return klass


def clustered(seed: int, rows: int, queries: int = 64):
    """The harness's generator at the configuration's own parameters."""
    datagen = run.load_module(os.path.join(
        REPO, "benchmarks", "datagen", GLOVE["generator"] + ".py"),
        "dynamic_datagen")
    params = dict(GLOVE["generator_params"], queries=queries)
    return datagen.generate(np.random.default_rng([seed, 1]), rows, DIM,
                            params)


def import_rows(grpc, collection, corpus, buckets, start, stop):
    """BatchObjects in the configuration's batches; row i is
    ``wire.obj_uuid(i)`` (``wire.Grpc.import_rows`` starts at row 0)."""
    for s in range(start, stop, BATCH):
        req = grpc.pb.BatchObjectsRequest()
        for i in range(s, min(s + BATCH, stop)):
            bo = req.objects.add(collection=collection, uuid=wire.obj_uuid(i))
            bo.vector_bytes = corpus[i].astype("<f4").tobytes()
            bo.properties.non_ref_properties.update(
                {"bucket": int(buckets[i])})
        reply = grpc._batch(req)
        assert not len(reply.errors), reply.errors[:1]


def answers(grpc, collection, queries, bound):
    """-> (positions [Q, K], distances [Q, K]) as served."""
    ids = np.full((len(queries), K), -1, np.int64)
    dists = np.full((len(queries), K), np.inf)
    for r, q in enumerate(queries):
        got_i, got_d = grpc.search(grpc.search_request(
            collection, q, {"metadata": ["uuid", "distance"]}, K,
            None if bound < 0 else FILTER, bound))
        ids[r, :len(got_i)] = got_i
        dists[r, :len(got_d)] = got_d
    return ids, dists


def distance_error(got, want) -> float:
    """The benchmark's number: widest |returned - exact| over max(|exact|,
    the scale floor), over finite entries."""
    ok = np.isfinite(want)
    return float((np.abs(got[ok] - want[ok])
                  / np.maximum(np.abs(want[ok]), FLOOR)).max())


def same_answers(got, want) -> None:
    """Served ids and distances equal the reference's: distances within
    the benchmark's tolerance place by place, ids equal but where two
    candidates tie within it (float32 against float64 may order a tie
    either way, or put either of two tied rows at the k-th place)."""
    (got_i, got_d), (want_i, want_d) = got, want
    assert got_i.shape == want_i.shape
    assert ((got_i >= 0) == (want_i >= 0)).all()
    assert distance_error(got_d, want_d) <= TOLERANCE
    for r in np.flatnonzero((got_i != want_i).any(axis=1)):
        for j in np.flatnonzero(got_i[r] != want_i[r]):
            near = np.abs(want_d[r] - want_d[r, j]) <= TOLERANCE * max(
                abs(want_d[r, j]), FLOOR)
            assert got_i[r, j] in want_i[r, near] or j == K - 1, (
                r, j, got_i[r], want_i[r], want_d[r])


def store_facts(index) -> dict:
    store = index._impl.store
    with store._lock:
        lists = {slot: loc[1] // store.list_cap
                 for slot, loc in store._slot_loc.items()
                 if loc[0] == "list"}
        delta = sorted(slot for slot, loc in store._slot_loc.items()
                       if loc[0] == "delta")
        doc_of_slot = np.asarray(index._impl._slot_to_id)
        return {"nlist": store.nlist, "list_cap": store.list_cap,
                "nprobe": store._effective_nprobe(),
                "centroids": np.array(store._centroids_np),
                "lists": lists, "delta": delta, "doc_of_slot": doc_of_slot,
                "retrains": store.retrain_count,
                "dtype": str(np.dtype(store.list_vecs.dtype))}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from weaviate_tpu.config import ServerConfig
    from weaviate_tpu.runtime import tracing
    from weaviate_tpu.server import Server

    patch = pytest.MonkeyPatch()
    patch.setenv("TRACE_SAMPLE_RATE", "1000")   # >= 1: always
    tracing.reset_policy_for_tests()
    corpus, props, queries = clustered(43, ROWS)
    buckets = props["bucket"]
    seen = {"corpus": corpus, "buckets": buckets, "queries": queries}
    server = Server(ServerConfig(
        data_path=str(tmp_path_factory.mktemp("dynamic")), rest_port=0,
        grpc_port=0, disable_telemetry=True)).start()
    try:
        # the walk owns the maintenance ticks: the scheduler's own
        # ``epoch-maintenance`` tick (every 5-10 s) falls, on a loaded
        # machine, between the import and the walk's first tick, finds
        # the writes paused one tick earlier than the walk reckons and
        # folds the delta's tail before the with-delta state is read
        with server.db.cycles._lock:
            server.db.cycles._callbacks["epoch-maintenance"].active = False
        rest = wire.Rest(server.rest.address)
        grpc = wire.Grpc(server.grpc.port)
        seen["page_before"] = rest.metrics()
        seen["spans"] = []

        def keep_spans():
            # the ring is short: read it before the searches fill it
            seen["spans"] += [s for t in json.loads(rest.request(
                "GET", "/v1/debug/traces?limit=400"))["traces"]
                for s in t["spans"]]

        rest.create_class(glove_class())
        seen["schema"] = json.loads(rest.request("GET", "/v1/schema/Glove"))

        def index():
            shard = next(iter(server.db.collections["Glove"].shards.values()))
            return shard.vector_indexes[""]

        # below the threshold
        import_rows(grpc, "Glove", corpus, buckets, 0, BATCH)
        seen["upgraded_below"] = index().upgraded
        seen["below"] = {b: answers(grpc, "Glove", queries, b)
                         for b in BOUNDS}
        # through the upgrade, a retrain and a fold
        import_rows(grpc, "Glove", corpus, buckets, BATCH, ROWS)
        keep_spans()
        # a tick that comes while the rows still arrive (the last batch a
        # moment ago, by the clock) leaves the delta's tail where it is
        seen["tick_while_writing"] = index().maintain(tick=True)
        seen["delta_after_first_tick"] = len(store_facts(index())["delta"])
        seen["upgraded"] = index().upgraded
        seen["with_delta"] = dict(
            store_facts(index()),
            answers={b: answers(grpc, "Glove", queries, b) for b in BOUNDS})
        newest = np.arange(ROWS - 8, ROWS)
        seen["newest_from_delta"] = answers(grpc, "Glove", corpus[newest], -1)
        # the fold a maintenance pass makes once the writes have paused
        with tracing.trace("maintenance", force=True):
            assert server.db.cycles.run_now("epoch-maintenance")
        keep_spans()
        seen["folded"] = dict(
            store_facts(index()),
            answers={b: answers(grpc, "Glove", queries, b) for b in BOUNDS})
        seen["newest_from_lists"] = answers(grpc, "Glove", corpus[newest], -1)
        seen["newest"] = newest
        # the control: the same class at the next precision below
        rest.create_class(glove_class("GloveBf16", storage_dtype="bfloat16"))
        import_rows(grpc, "GloveBf16", corpus, buckets, 0, 4 * BATCH)
        shard = next(iter(server.db.collections["GloveBf16"].shards.values()))
        seen["bf16"] = dict(store_facts(shard.vector_indexes[""]),
                            answers=answers(grpc, "GloveBf16", queries, -1))
        seen["page"] = rest.metrics()
        keep_spans()
        seen["memory"] = json.loads(rest.request("GET", "/v1/debug/memory"))
        grpc.close()
        yield seen
    finally:
        server.stop()
        patch.undo()
        tracing.reset_policy_for_tests()
        tracing.clear_traces()


def allowed(seen, bound, rows=ROWS):
    return None if bound < 0 else seen["buckets"][:rows] < bound


# -- (vi) the keys -----------------------------------------------------------


def test_the_threshold_is_given_back_as_sent(served):
    assert served["schema"]["vectorIndexType"] == "dynamic"
    assert served["schema"]["vectorIndexConfig"]["threshold"] == THRESHOLD
    assert served["schema"]["vectorIndexConfig"]["distance"] == "cosine"


# -- (i) exact until the threshold -------------------------------------------


@pytest.mark.parametrize("bound", BOUNDS)
def test_below_the_threshold_answers_are_the_exact_scans(served, bound):
    assert served["upgraded_below"] is False
    want = ivf_reference.exact(served["queries"], K, METRIC,
                               served["corpus"][:BATCH],
                               allowed(served, bound, BATCH))
    same_answers(served["below"][bound], want)


# -- (ii), (iii) after it: the reference given the index's centroids ---------


@pytest.fixture(scope="module")
def membership(served):
    """The lists as the reference fills them from the history the sizes
    above fix: rebuilt from the first 10,240 rows, 8,192 folded, and for
    the last state the delta's 6,144 folded too."""
    facts = served["folded"]
    x = ivf_reference.prepare(served["corpus"], METRIC)
    cents = facts["centroids"].astype(np.float64)
    member = ivf_reference.build(x[:REBUILT_AT], cents, facts["list_cap"])
    member = ivf_reference.insert(member, x[REBUILT_AT:FOLDED_AT], cents,
                                  facts["list_cap"])
    return {"with_delta": member,
            "folded": ivf_reference.insert(member, x[FOLDED_AT:], cents,
                                           facts["list_cap"])}


def test_the_import_went_through_the_upgrade_one_retrain_and_one_fold(served):
    a, b = served["with_delta"], served["folded"]
    assert served["upgraded"] is True
    assert (a["retrains"], b["retrains"]) == (1, 1)
    assert a["delta"] == list(range(FOLDED_AT, ROWS)) and b["delta"] == []
    assert len(a["lists"]) == FOLDED_AT and len(b["lists"]) == ROWS
    # the partition followed the corpus: 128 lists at 2,048 rows would
    # have stayed 128 before this PR
    assert a["nlist"] == b["nlist"] == 256
    assert (a["centroids"] == b["centroids"]).all()
    # slots are append-order and doc ids were handed out in import order
    assert (b["doc_of_slot"][:ROWS] == np.arange(ROWS)
            + b["doc_of_slot"][0]).all()


def test_a_tick_leaves_the_delta_alone_while_writes_arrive(served):
    """A tick that finds the last write a moment old answers "work
    left" and folds nothing; the pass that finds the writes paused folds
    (here ``run_now``, which is not a tick)."""
    assert served["tick_while_writing"] is True
    assert served["delta_after_first_tick"] == ROWS - FOLDED_AT


@pytest.mark.parametrize("state", ["with_delta", "folded"])
def test_the_lists_hold_what_the_membership_rule_says(served, membership,
                                                      state):
    lists = served[state]["lists"]
    mine = np.array([lists[s] for s in range(len(membership[state]))])
    assert (mine == membership[state]).all(), np.flatnonzero(
        mine != membership[state])[:10]


@pytest.mark.parametrize("bound", BOUNDS)
@pytest.mark.parametrize("state", ["with_delta", "folded"])
def test_served_answers_equal_the_reference(served, membership, state, bound):
    facts = served[state]
    want = ivf_reference.search(
        served["queries"], K, facts["nprobe"], METRIC, facts["centroids"],
        served["corpus"], membership[state], delta=facts["delta"],
        allowed=allowed(served, bound), flat_search_cutoff=CUTOFF)
    same_answers(facts["answers"][bound], want)


def test_new_rows_are_found_from_the_delta_and_then_from_the_lists(served):
    for key in ("newest_from_delta", "newest_from_lists"):
        ids, dists = served[key]
        assert (ids[:, 0] == served["newest"]).all(), key
        assert (np.abs(dists[:, 0]) <= 1e-5).all(), key


# -- (iv) recall --------------------------------------------------------------


@pytest.mark.parametrize("state", ["with_delta", "folded"])
def test_recall_at_the_default_probe(served, state):
    want_i, want_d = ivf_reference.exact(served["queries"], K, METRIC,
                                         served["corpus"])
    got_i, _ = served[state]["answers"][-1]
    x = ivf_reference.prepare(served["corpus"], METRIC)
    q = ivf_reference.prepare(served["queries"], METRIC)
    own = 1.0 - np.einsum("qkd,qd->qk", x[got_i], q)
    recall = float((own <= want_d[:, -1:] * (1 + 1e-6) + 1e-9).mean())
    assert recall >= GLOVE["limits"]["recall_at_k_min"], recall
    assert served[state]["nprobe"] * 8 == served[state]["nlist"]


@pytest.mark.parametrize("bound", [b for b in BOUNDS if b >= 0])
@pytest.mark.parametrize("state", ["with_delta", "folded"])
def test_filtered_recall_against_the_exact_filtered_topk(served, state,
                                                         bound):
    """Equality with the reference holds the program to the RULE; this
    holds the rule to the truth: the exact top-k over the rows the filter
    allows. Until ISSUE 51 nothing did, and a probe of an eighth of the
    lists under a 1 % filter found two thirds of it."""
    ok = allowed(served, bound)
    want_i, want_d = ivf_reference.exact(served["queries"], K, METRIC,
                                         served["corpus"], ok)
    got_i, _ = served[state]["answers"][bound]
    assert (got_i >= 0).all() and ok[got_i].all()
    x = ivf_reference.prepare(served["corpus"], METRIC)
    q = ivf_reference.prepare(served["queries"], METRIC)
    own = 1.0 - np.einsum("qkd,qd->qk", x[got_i], q)
    recall = float((own <= want_d[:, -1:] * (1 + 1e-6) + 1e-9).mean())
    assert recall >= GLOVE["limits"]["recall_at_k_min"], recall
    assert int(ok.sum()) < CUTOFF and recall == 1.0


# -- (v) the tolerance is tight enough ----------------------------------------


def test_bfloat16_rows_fail_the_distance_tolerance(served):
    facts = served["bf16"]
    assert facts["dtype"] == "bfloat16"
    rows = 4 * BATCH
    want_i, want_d = ivf_reference.exact(served["queries"], K, METRIC,
                                         served["corpus"][:rows])
    got_i, got_d = facts["answers"]
    x = ivf_reference.prepare(served["corpus"][:rows], METRIC)
    q = ivf_reference.prepare(served["queries"], METRIC)
    own = 1.0 - np.einsum("qkd,qd->qk", x[got_i], q)
    assert distance_error(got_d, own) > 10 * TOLERANCE
    sound_i, sound_d = served["folded"]["answers"][-1]
    x = ivf_reference.prepare(served["corpus"], METRIC)
    own = 1.0 - np.einsum("qkd,qd->qk", x[sound_i], q)
    assert distance_error(sound_d, own) <= TOLERANCE / 10


# -- spans, series, ledger ------------------------------------------------------


def test_spans_of_the_upgrade_the_trainings_the_folds_and_the_probe(served):
    by_name = {}
    for s in served["spans"]:
        by_name.setdefault(s["name"], []).append(s.get("attrs", {}))
    assert by_name["dynamic.upgrade"][0]["rows"] >= THRESHOLD
    trains = by_name["ivf.train"]
    assert {(t["rows"], t["nlist"], t["retrain"]) for t in trains} >= {
        (THRESHOLD, 128, False), (REBUILT_AT, 256, True)}
    folds = [f for f in by_name["ivf.flush_delta"] if "rows" in f]
    assert {f["rows"] for f in folds} >= {8192, ROWS - FOLDED_AT}
    assert all(f["spilled"] >= 0 for f in folds)
    probes = [a for a in by_name["ivf.search"] if a.get("nprobe")]
    assert probes and all(
        a["candidates"] == a["nprobe"] * a["list_cap"] and "delta_rows" in a
        and a["gather"] == "slab" for a in probes)


def test_series_of_the_index(served):
    before, page = served["page_before"], served["page"]
    stages = {st: page.total("weaviate_tpu_ivf_maintain_seconds_count",
                             {"stage": st})
              - before.total("weaviate_tpu_ivf_maintain_seconds_count",
                             {"stage": st})
              for st in ("upgrade", "train", "flush")}
    # two classes: two upgrades, their first trainings and Glove's retrain,
    # Glove's two folds (GloveBf16's delta holds 2,048 rows still)
    assert stages == {"upgrade": 2, "train": 3, "flush": 2}
    moved = {s: page.total(s) - before.total(s) for s in (
        "weaviate_tpu_ivf_queries_total",
        "weaviate_tpu_ivf_probed_lists_total",
        "weaviate_tpu_ivf_candidate_rows_total",
        "weaviate_tpu_ivf_probe_programs_total")}
    assert all(v > 0 for v in moved.values()), moved
    assert moved["weaviate_tpu_ivf_probed_lists_total"] \
        >= 16 * moved["weaviate_tpu_ivf_queries_total"]
    labels = {"collection": "Glove"}
    facts = served["folded"]
    assert page.total("weaviate_tpu_ivf_lists", labels) == facts["nlist"]
    assert page.total("weaviate_tpu_ivf_list_capacity",
                      labels) == facts["list_cap"]
    assert page.total("weaviate_tpu_ivf_delta_rows", labels) == 0
    assert page.total("weaviate_tpu_ivf_live_rows", labels) == ROWS


def test_the_ledger_lists_the_list_tensors(served):
    comps = served["memory"]["ledger"]["collections"]["Glove"]["components"]
    facts = served["folded"]
    positions = facts["nlist"] * facts["list_cap"]
    assert comps["list_vecs"] >= positions * DIM * 4
    assert comps["list_slots"] >= positions * 4
    assert comps["list_norms"] >= positions * 4
    assert comps["list_valid"] >= positions
    assert comps["centroids"] >= facts["nlist"] * DIM * 4
