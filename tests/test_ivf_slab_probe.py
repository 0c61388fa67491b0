"""The IVF probe reads each probed posting list as the slab it is, in ONE
program a chunk of queries (ISSUE 47).

Three things are held here, on the CPU (nothing in this file is a device
time):

1. The slab program answers as the per-POSITION probe it replaced:
   ``per_position`` below is that probe, kept as the definition (the
   flattened positions of the probed lists through the shared candidate
   plane, ``ops/candidates.py gather_rescore_topk``). Ids equal, ties
   included; distances within 1e-6.
2. A search over an empty delta buffer dispatches one program a chunk and
   no eager one-op program beside it, counted from the profiler's own
   ``PjitFunction(...)`` events against ``ivf_probe_programs_total``, and
   the program is still called what the benchmark's reader looks for.
3. The store's answers are the plain reference's (``ivf_reference.py``).
"""

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ivf_reference
from weaviate_tpu.engine import ivf
from weaviate_tpu.ops.candidates import gather_rescore_topk
from weaviate_tpu.ops.distances import (MASKED_DISTANCE, normalize,
                                        pairwise_distance)
from weaviate_tpu.ops.pallas_kernels import mask_pad_cols, pack_allow_bitmask
from weaviate_tpu.runtime import metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NLIST, DIM = 16, 20
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def per_position(q, centroids, c_norms, list_vecs, list_valid, list_slots,
                 list_norms, allow_bits, k, nprobe, metric, use_allow):
    """The probe as it stood until ISSUE 47: ``nprobe * cap`` flat
    positions a query handed to the candidate plane."""
    nlist, cap, dim = list_vecs.shape
    b = q.shape[0]
    q32 = q.astype(jnp.float32)
    if metric in ("cosine", "cosine-dot"):
        q32 = normalize(q32)
    cd = pairwise_distance(q32, centroids, metric="l2-squared",
                           x_sq_norms=c_norms)
    _, probes = jax.lax.top_k(-cd, nprobe)
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, nprobe, cap), 2)
    flat = (probes[:, :, None].astype(jnp.int32) * cap
            + pos).reshape(b, nprobe * cap)
    return gather_rescore_topk(
        q32, flat, list_vecs.reshape(nlist * cap, dim), k, metric,
        ids_of_row=list_slots.reshape(nlist * cap),
        row_norms=list_norms.reshape(nlist * cap),
        valid=list_valid.reshape(nlist * cap),
        allow_bits=allow_bits if use_allow else None)


def make_lists(seed, cap, dtype, metric, b):
    """Posting lists as a store leaves them after folds and deletes: lists
    filled to different lengths, two wholly empty, holes inside the filled
    part (``list_valid`` false over a STALE slot, as ``delete`` leaves it),
    -1 slots past the fill, and rows that occur twice under two slots (a
    tie at every rank they reach). -> the probe's operands and queries that
    lie beside live rows."""
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((NLIST, cap, DIM)).astype(np.float32)
    fill = rng.integers(1, cap + 1, NLIST)
    fill[[1, NLIST - 2]] = 0
    filled = np.arange(cap)[None, :] < fill[:, None]
    valid = filled & (rng.random((NLIST, cap)) > 0.15)
    slots = np.full((NLIST, cap), -1, np.int32)
    slots[filled] = rng.permutation(4 * NLIST * cap)[:filled.sum()]
    live = np.argwhere(valid)
    for src, dst in rng.permutation(len(live))[:2 * (len(live) // 6)].reshape(
            -1, 2):
        vecs[tuple(live[dst])] = vecs[tuple(live[src])]
    if metric == "cosine":
        vecs /= np.linalg.norm(vecs, axis=-1, keepdims=True)
    vecs[~filled] = 0.0
    list_vecs = jnp.asarray(vecs).astype(DTYPES[dtype])
    norms = jnp.sum(list_vecs.astype(jnp.float32) ** 2, axis=-1)
    cents = rng.standard_normal((NLIST, DIM)).astype(np.float32)
    if metric == "cosine":
        cents /= np.linalg.norm(cents, axis=-1, keepdims=True)
    queries = (vecs[tuple(live[rng.integers(0, len(live), b)].T)]
               + 0.05 * rng.standard_normal((b, DIM))).astype(np.float32)
    return {"q": jnp.asarray(queries), "centroids": jnp.asarray(cents),
            "c_norms": jnp.asarray((cents * cents).sum(-1)),
            "list_vecs": list_vecs, "list_valid": jnp.asarray(valid),
            "list_slots": jnp.asarray(slots), "list_norms": norms,
            "live_slots": slots[valid], "capacity": 4 * NLIST * cap}


def allow_operand(kind, lists, b, seed):
    """-> (``allow_bits``, ``use_allow``, bool [b or 1, capacity])."""
    if kind == "none":
        return jnp.zeros((1, 16), jnp.uint32), False, None
    rng = np.random.default_rng(seed)
    rows = 1 if kind == "shared" else b
    allow = rng.random((rows, lists["capacity"])) < 0.5
    bits = pack_allow_bitmask(allow, mask_pad_cols(lists["capacity"]))
    return jnp.asarray(bits), True, allow


def both(lists, bits, k, nprobe, metric, use_allow):
    args = (lists["q"], lists["centroids"], lists["c_norms"],
            lists["list_vecs"], lists["list_valid"], lists["list_slots"],
            lists["list_norms"], bits, k, nprobe, metric, use_allow)
    got = ivf._ivf_probe_topk(*args)
    want = per_position(*args)
    return [np.asarray(a) for a in got], [np.asarray(a) for a in want]


#        metric        dtype       b   nprobe  k      cap  allow
CASES = [
    # every metric at both stored widths
    ("l2-squared", "float32", 5, 8, 10, 8, "none"),
    ("cosine", "float32", 5, 8, 10, 8, "none"),
    ("dot", "float32", 5, 8, 10, 8, "none"),
    ("l2-squared", "bfloat16", 5, 8, 10, 8, "none"),
    ("cosine", "bfloat16", 5, 8, 10, 8, "none"),
    ("dot", "bfloat16", 5, 8, 10, 8, "none"),
    # one query, and a full chunk of sixteen
    ("l2-squared", "float32", 1, 8, 10, 8, "none"),
    ("cosine", "float32", 1, 8, 10, 8, "none"),
    ("dot", "float32", 1, 8, 10, 8, "none"),
    ("l2-squared", "float32", 16, 8, 10, 8, "none"),
    ("cosine", "float32", 16, 8, 10, 8, "none"),
    ("dot", "bfloat16", 16, 8, 10, 8, "none"),
    # one list probed, and every list
    ("l2-squared", "float32", 5, 1, 10, 8, "none"),
    ("cosine", "float32", 5, 1, 10, 8, "none"),
    ("l2-squared", "float32", 5, NLIST, 10, 8, "none"),
    ("cosine", "bfloat16", 5, NLIST, 10, 8, "none"),
    # k of one, and k over every candidate (the tail is masked)
    ("l2-squared", "float32", 5, 8, 1, 8, "none"),
    ("cosine", "float32", 5, 8, 1, 8, "none"),
    ("l2-squared", "float32", 5, 8, 64, 8, "none"),
    ("cosine", "float32", 5, 8, 64, 8, "none"),
    ("dot", "float32", 16, NLIST, 128, 8, "none"),
    # a filter: one mask for the batch, and one a query
    ("l2-squared", "float32", 5, 8, 10, 8, "shared"),
    ("cosine", "float32", 5, 8, 10, 8, "shared"),
    ("dot", "float32", 5, 8, 10, 8, "shared"),
    ("l2-squared", "float32", 5, 8, 10, 8, "per_query"),
    ("cosine", "float32", 5, 8, 10, 8, "per_query"),
    ("dot", "float32", 5, 8, 10, 8, "per_query"),
    ("cosine", "bfloat16", 16, 8, 64, 8, "per_query"),
    ("l2-squared", "bfloat16", 1, NLIST, 10, 8, "shared"),
    # the served cell's list capacity
    ("l2-squared", "float32", 16, 8, 10, 512, "none"),
    ("cosine", "float32", 16, 8, 10, 512, "none"),
    ("dot", "float32", 5, 8, 10, 512, "none"),
    ("cosine", "float32", 16, 8, 10, 512, "per_query"),
    ("cosine", "bfloat16", 5, 1, 1, 512, "shared"),
    ("l2-squared", "float32", 1, NLIST, 8192, 512, "none"),
]


@pytest.mark.parametrize("metric,dtype,b,nprobe,k,cap,allow", CASES)
def test_the_slab_probe_answers_as_the_per_position_probe(
        metric, dtype, b, nprobe, k, cap, allow):
    seed = CASES.index((metric, dtype, b, nprobe, k, cap, allow))
    lists = make_lists(seed, cap, dtype, metric, b)
    bits, use_allow, allowed = allow_operand(allow, lists, b, seed)
    (got_d, got_i), (want_d, want_i) = both(lists, bits, k, nprobe, metric,
                                            use_allow)
    assert got_i.dtype == np.int32 and got_d.dtype == np.float32
    assert got_i.shape == want_i.shape == (b, min(k, nprobe * cap))
    assert np.array_equal(got_i, want_i)
    # 1e-6 of what was summed: an l2 distance is the difference of the
    # norms and the product, so its rounding is theirs
    scale = np.maximum(1.0, np.abs(want_d))
    if metric == "l2-squared":
        scale = np.maximum(scale, float(lists["list_norms"].max())
                           + (np.asarray(lists["q"]) ** 2).sum(-1)[:, None])
    assert (np.abs(got_d - want_d) <= 1e-6 * scale).all()
    # the tail of a k past the live candidates, and nothing dead in front
    dead = got_i < 0
    assert (got_d[dead] == MASKED_DISTANCE).all()
    assert (got_d[~dead] < MASKED_DISTANCE).all()
    assert (np.diff(got_d, axis=1) >= 0).all()
    if k >= nprobe * cap:
        assert dead[:, -1].all()      # two lists are empty, others have holes
    assert np.isin(got_i[~dead], lists["live_slots"]).all()
    if allowed is not None:
        rows = np.broadcast_to(allowed, (b, allowed.shape[1]))
        for r in range(b):
            assert rows[r, got_i[r][~dead[r]]].all()


@pytest.mark.parametrize("metric", ["l2-squared", "cosine", "dot"])
@pytest.mark.parametrize("cap", [8, 512])
def test_an_id_is_returned_at_most_once(metric, cap):
    lists = make_lists(100 + cap, cap, "float32", metric, 16)
    bits, use_allow, _ = allow_operand("none", lists, 16, 0)
    (_, got_i), _ = both(lists, bits, NLIST * cap, NLIST, metric, use_allow)
    live = np.sort(lists["live_slots"])
    for row in got_i:
        # every list is probed and k covers every position: each live
        # slot once, then the masked tail
        assert np.array_equal(np.sort(row[row >= 0]), live)


# -- (2) one program a chunk ---------------------------------------------------


def programs_of(fn, tmp_path):
    """Names of the jitted programs ``fn`` calls, in order, from the
    profiler's ``PjitFunction(<name>)`` host events (one a call of a
    jitted function, an eager ``jnp`` operation included; a call shows as
    two nested events, counted once)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    events = sorted(
        (ev.start_ns, -ev.duration_ns, ev.name)
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines for ev in line.events
        if ev.name.startswith("PjitFunction("))
    names, end = [], -1.0
    for start, neg_duration, name in events:
        if start >= end:          # not nested in the call before it
            names.append(name[len("PjitFunction("):-1])
            end = start - neg_duration
    return names


@pytest.fixture(scope="module")
def folded_store():
    """A trained store whose delta buffer is empty: the served cell's
    state in every window."""
    rng = np.random.default_rng(47)
    store = ivf.IVFStore(dim=DIM, metric="cosine", nlist=NLIST,
                         train_threshold=512, delta_threshold=256)
    corpus = rng.standard_normal((1024, DIM)).astype(np.float32)
    store.add(corpus)
    store.flush_delta()
    assert store.trained and store.delta.live_count() == 0
    return store, corpus, rng.standard_normal((48, DIM)).astype(np.float32)


@pytest.mark.parametrize("b", [1, 16, 20, 48])
def test_a_search_over_an_empty_delta_is_one_program_a_chunk(
        folded_store, tmp_path, b):
    store, _, queries = folded_store
    want = store.search(queries[:b], 5)        # warm: nothing compiles below
    counter = metrics.ivf_probe_programs_total.labels()
    before = counter.value
    names = programs_of(lambda: store.search_async(queries[:b], 5).result(),
                        tmp_path)
    chunks = -(-b // store.query_chunk)
    assert counter.value - before == chunks
    # a ``jnp.zeros``, an ``astype`` or a ``concatenate`` round the probe
    # would stand here as ``broadcast_in_dim``, ``convert_element_type``,
    # ``concatenate``: each an Execute of its own on the chip
    assert names == ["_ivf_probe_topk"] * chunks
    got = store.search(queries[:b], 5)
    assert np.array_equal(got[1], want[1]) and got[1].shape == (b, 5)


def test_the_program_keeps_the_name_the_benchmark_reads():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "glove-dynamic-cosine.json")) as f:
        patterns = json.load(f)["scan_programs"]
    # the device's module line names a program ``jit_<function>``
    name = "jit_" + ivf._ivf_probe_topk.__name__
    assert any(re.search(p, name) for p in patterns), (name, patterns)
    assert hasattr(ivf._ivf_probe_topk, "lower")     # the jitted function


# -- (3) the served path against the plain reference ---------------------------


@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
@pytest.mark.parametrize("state", ["folded", "with_delta"])
def test_the_store_answers_as_the_plain_reference(metric, state):
    rng = np.random.default_rng(7)
    corpus = (rng.standard_normal((64, 1, DIM))
              + 0.3 * rng.standard_normal((64, 24, DIM))).reshape(
                  -1, DIM).astype(np.float32)
    corpus = corpus[rng.permutation(len(corpus))]
    queries = corpus[rng.integers(0, len(corpus), 20)] \
        + 0.1 * rng.standard_normal((20, DIM)).astype(np.float32)
    store = ivf.IVFStore(dim=DIM, metric=metric, nlist=NLIST,
                         train_threshold=1024, delta_threshold=4096)
    store.add(corpus[:1024])                   # trains: 16 lists
    store.add(corpus[1024:1400])
    store.flush_delta()
    if state == "with_delta":
        store.add(corpus[1400:])
    rows = corpus[:store.count]
    with store._lock:
        member = np.full(len(rows), -1, np.int64)
        delta = []
        for slot, loc in store._slot_loc.items():
            if loc[0] == "list":
                member[slot] = loc[1] // store.list_cap
            else:
                delta.append(slot)
    assert (len(delta) > 0) == (state == "with_delta")
    k, nprobe = 10, 4
    got_d, got_i = store.search(queries, k, nprobe=nprobe)
    want_i, want_d = ivf_reference.search(
        queries, k, nprobe, metric, store._centroids_np, rows, member,
        delta=delta)
    assert ((got_i >= 0) == (want_i >= 0)).all()
    close = 1e-5 * np.maximum(1.0, np.abs(want_d))
    assert (np.abs(got_d - want_d) <= close).all()
    # float32 against float64 may order two rows either way that tie
    # within rounding: an id that differs stands beside its equal
    for r, j in np.argwhere(got_i != want_i):
        tied = np.abs(want_d[r] - want_d[r, j]) <= close[r, j]
        assert got_i[r, j] in want_i[r, tied] or j == k - 1, (r, j)
    for row in got_i:
        assert len(set(row.tolist())) == k
