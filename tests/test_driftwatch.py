"""Driftwatch (ISSUE 19): online recall & perf drift detection.

Covers the three legs end to end: band classification of live
telemetry (``runtime/bands``: verdict statuses, counts and the
cross-fingerprint refusal), canary determinism + epoch-change
ground-truth invalidation against a real Database, and the two
sabotage-validated incident paths the acceptance criteria name —
faultline latency at ``batcher.dispatch`` tripping a ``live`` finding
and a wrong id mapping (a sabotaged retrain in miniature) tripping a
``canary`` recall finding — each flipping component health, snapshotting
the flight recorder, and replayable offline via ``python -m
tools.driftwatch``.
"""

import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from weaviate_tpu.db.database import Database
from weaviate_tpu.runtime import (degrade, driftwatch, faultline,
                                  kernelscope, tailboard)
from weaviate_tpu.schema.config import CollectionConfig


# -- leg 2 units: band classification ------------------------------------------


def _section(ewma_ms: float) -> dict:
    return {"residency": {"flat/b8/k16": {"ewma_ms": ewma_ms,
                                          "last_ms": ewma_ms,
                                          "n": 5, "source": "wall"}},
            "counters": {"compile_miss_per_cycle_p1": 1.0,
                         "overlap_per_cycle_p1": 1.0}}


def test_live_classification_is_benchkeeper_band_math():
    """pass / regression / stale out of driftwatch's classifier for a
    self-sealed baseline: each entry's status and normalized delta, and
    the verdict's counts and gate."""
    from weaviate_tpu.runtime import bands

    fp = {"platform": "cpu"}
    baseline = driftwatch.seal_live_baseline(_section(2.0), fp)
    bands.validate_baseline(baseline, "<test>")

    for value, want, delta in (
            (2.5, "pass", 0.25),         # +25% inside the 75% band
            (20.0, "regression", 9.0),   # +900%
            (0.2, "stale", -0.9)):       # -90% unexplained
        verdict = driftwatch.classify_live(_section(value), baseline, fp)
        assert verdict["refused"] is None
        assert [(r["id"], r["status"], r["delta_frac"])
                for r in verdict["entries"]] == [
            ("live.residency.flat/b8/k16", want, delta),
            ("live.compile_miss_per_cycle", "pass", 0.0)]
        counts = {"passed": 1, "regressions": 0, "stale": 0, "missing": 0}
        counts[{"pass": "passed", "regression": "regressions",
                "stale": "stale"}[want]] += 1
        assert verdict["checked"] == 2
        assert {k: verdict[k] for k in counts} == counts
        assert verdict["ok"] is (want == "pass")


def test_live_leg_classifies_without_the_checkout_on_the_path(tmp_path):
    """The package is the whole of what a server needs: with only
    ``weaviate_tpu`` importable (no ``tools``, no repo root on
    ``sys.path``) a cycle's live leg seals its baseline, persists it and
    classifies against it."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    site = tmp_path / "site"
    site.mkdir()
    os.symlink(os.path.join(repo, "weaviate_tpu"), site / "weaviate_tpu")
    script = """
import importlib.util, json, sys
assert importlib.util.find_spec("tools") is None, sys.path
from weaviate_tpu.runtime import driftwatch, kernelscope
driftwatch.configure(data_dir=sys.argv[1], enabled=True)
for _ in range(8):
    kernelscope.record_dispatch("flat", 8, 16, 0.002, "wall")
driftwatch.run_cycle()
print(json.dumps(driftwatch.snapshot()["live"]))
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(site))
    proc = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path / "data")],
        capture_output=True, text=True, env=env, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    live = json.loads(proc.stdout.splitlines()[-1])
    assert live["baselineError"] is None
    assert live["baselineSource"] == "sealed:" + str(
        tmp_path / "data" / "driftwatch" / "live_baseline.json")
    assert {t["id"]: t["status"] for t in live["trends"]} == {
        "live.residency.flat/b8/k16": "pass",
        "live.compile_miss_per_cycle": "pass"}


def test_refused_fingerprint_matches_cli_and_does_not_flip_health():
    """A baseline sealed on another rig REFUSES comparison exactly like
    the CLI (no entries compared), surfaces as a finding, and must NOT
    flip health — refusal is a configuration fact, not an incident."""
    baseline = driftwatch.seal_live_baseline(_section(2.0),
                                             {"platform": "tpu"})
    verdict = driftwatch.classify_live(_section(50.0), baseline,
                                       {"platform": "cpu"})
    assert verdict["refused"] and not verdict["ok"]
    assert verdict["entries"] == []  # nothing was band-checked
    findings = driftwatch._live_findings(verdict)
    assert [f["kind"] for f in findings] == ["refused"]
    assert not findings[0]["flips_health"]


def test_stale_is_visible_but_not_an_incident():
    baseline = driftwatch.seal_live_baseline(_section(2.0),
                                             {"platform": "cpu"})
    verdict = driftwatch.classify_live(_section(0.2), baseline,
                                       {"platform": "cpu"})
    findings = driftwatch._live_findings(verdict)
    kinds = {f["kind"]: f["flips_health"] for f in findings}
    assert kinds == {"stale": False}


def test_cold_compile_poisoned_ewma_is_not_sealed():
    """A variant whose EWMA is still decaying from the cold-compile
    first dispatch (ewma >> latest sample) must NOT be sealed: freezing
    the inflated level as the band masks every regression below it and
    emits spurious 'improved' findings as it decays. A converged sibling
    in the same section still seals."""
    sec = _section(2.0)
    sec["residency"]["flat/b8/k16"].update(ewma_ms=50.0, last_ms=0.5)
    assert driftwatch.seal_live_baseline(sec, {"platform": "cpu"}) is None

    sec["residency"]["flat/b1/k16"] = {"ewma_ms": 0.6, "last_ms": 0.5,
                                       "n": 9, "source": "drain"}
    baseline = driftwatch.seal_live_baseline(sec, {"platform": "cpu"})
    sealed = {e["id"] for e in baseline["entries"]}
    assert "live.residency.flat/b1/k16" in sealed
    assert "live.residency.flat/b8/k16" not in sealed


# -- canary lifecycle against a real Database ---------------------------------


def _mk_db(path, n=32, dim=8, seed=7):
    db = Database(str(path))
    db.create_collection(CollectionConfig(name="Drift"))
    col = db.get_collection("Drift")
    rng = np.random.default_rng(seed)
    for _ in range(n):
        col.put_object({}, vector=rng.standard_normal(dim)
                       .astype(np.float32))
    return db, col


def _only_canary(snap):
    assert len(snap["canaries"]) == 1, snap["canaries"]
    return next(iter(snap["canaries"].values()))


def test_canary_determinism_across_restart(tmp_path):
    """Same seed + same corpus => same probe set and perfect recall,
    across a full close/reopen (the registration rides the shard's
    index-restore path, and the probe RNG must not depend on insert
    order or process state)."""
    db, _ = _mk_db(tmp_path)
    assert db.cycles.run_now("driftwatch")
    first = _only_canary(driftwatch.snapshot())
    assert first["last"]["recall"] == 1.0
    assert first["last"]["probes"] == 8
    db.close()
    assert driftwatch.snapshot()["canaries"] == {}  # close unregisters

    db2 = Database(str(tmp_path))
    try:
        db2.cycles.run_now("driftwatch")
        again = _only_canary(driftwatch.snapshot())
        assert again["probe_doc_ids"] == first["probe_doc_ids"]
        assert again["last"]["recall"] == 1.0
    finally:
        db2.close()


def test_epoch_change_reseals_ground_truth(tmp_path):
    """Growing the corpus changes the epoch token, so the next cycle
    recomputes probes + host-exact ground truth over the NEW corpus —
    recall stays honest instead of comparing against a dead snapshot."""
    db, col = _mk_db(tmp_path)
    try:
        db.cycles.run_now("driftwatch")
        before = _only_canary(driftwatch.snapshot())
        rng = np.random.default_rng(99)
        for _ in range(32):
            col.put_object({}, vector=rng.standard_normal(8)
                           .astype(np.float32))
        db.cycles.run_now("driftwatch")
        after = _only_canary(driftwatch.snapshot())
        assert after["epoch_token"] != before["epoch_token"]
        # the reseal sampled the doubled corpus (fixed seed: the new
        # probe set provably includes post-growth doc ids)
        assert after["probe_doc_ids"] != before["probe_doc_ids"]
        assert after["last"]["recall"] == 1.0
    finally:
        db.close()


def test_oversized_corpus_is_skipped_with_reason(tmp_path, monkeypatch):
    monkeypatch.setenv("WEAVIATE_TPU_DRIFT_CANARY_MAX_ROWS", "4")
    db, _ = _mk_db(tmp_path)
    try:
        db.cycles.run_now("driftwatch")
        c = _only_canary(driftwatch.snapshot())
        assert "over WEAVIATE_TPU_DRIFT_CANARY_MAX_ROWS" in c["skipped"]
        assert driftwatch.snapshot()["gateOk"]  # skipped != incident
    finally:
        db.close()


# -- canary lifecycle: seal on quiescence, on stated clocks --------------------


def _seal_counts():
    from weaviate_tpu.runtime import metrics

    out = {t: metrics.canary_seals_total.labels(t).value
           for t in ("quiet", "interval", "forced")}
    out["seconds"] = sum(metrics.canary_seal_seconds_total.labels(t).value
                         for t in ("quiet", "interval", "forced"))
    out["deferrals"] = metrics.canary_deferrals_total.labels().value
    return out


def _delta(before):
    return {k: v - before[k] for k, v in _seal_counts().items()}


def _grow(col, n, dim=8, seed=99):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        col.put_object({}, vector=rng.standard_normal(dim)
                       .astype(np.float32))


def test_first_look_after_the_token_held_still_seals_quiet(tmp_path):
    """The look reads tokens only; the first one that finds a changed
    token QUIET_S old gives that canary its cycle: sealed on the whole
    corpus with trigger ``quiet``, then probed, as at a tick."""
    db, _ = _mk_db(tmp_path)
    try:
        was = _seal_counts()
        assert driftwatch.look(now=100.0) is True      # first sight: moved
        assert driftwatch.look(now=101.0) is True      # still for 1 s only
        assert _delta(was)["quiet"] == 0
        c = _only_canary(driftwatch.snapshot())
        assert c["epoch_token"] is None and c["skipped"] is None
        (canary,) = driftwatch._canaries.values()
        read, during = canary.corpus_fn, []

        def corpus_fn():
            during.append(_only_canary(driftwatch.snapshot())["epoch_token"])
            return read()

        canary.corpus_fn = corpus_fn
        assert driftwatch.look(now=100.0 + driftwatch.QUIET_S) is False
        assert during == [None]        # the token is published with the seal
        d = _delta(was)
        assert (d["quiet"], d["interval"], d["forced"]) == (1, 0, 0)
        assert d["seconds"] > 0 and d["deferrals"] == 0
        c = _only_canary(driftwatch.snapshot())
        assert "32" in c["epoch_token"] and c["skipped"] is None
        assert len(c["probe_doc_ids"]) == 8
        assert c["last"]["recall"] == 1.0 and len(c["history"]) == 1
        assert driftwatch.snapshot()["cycle"] == 1
        # sealed on this token: further looks do nothing and back off
        assert driftwatch.look(now=500.0) is False
        assert _delta(was)["quiet"] == 1
    finally:
        db.close()


def test_scheduled_cycle_defers_a_moving_canary(tmp_path):
    """A tick that finds the token different from the one its previous
    look saw leaves the canary alone: no ground truth, no probes, the
    public state as it was but for ``last["deferred"]`` — ``skipped``
    stays None and ``epoch_token`` the last SEALED token, so a reader
    that waits for the seal of the whole corpus is not talked past."""
    db, col = _mk_db(tmp_path)
    try:
        assert driftwatch.run_cycle(now=0.0)           # forced: sealed on 32
        sealed = _only_canary(driftwatch.snapshot())
        assert sealed["last"]["recall"] == 1.0
        was = _seal_counts()
        _grow(col, 8)
        assert driftwatch.run_cycle(scheduled=True, now=30.0)
        c = _only_canary(driftwatch.snapshot())
        assert c["skipped"] is None
        assert c["epoch_token"] == sealed["epoch_token"]
        assert c["probe_doc_ids"] == sealed["probe_doc_ids"]
        assert c["last"]["deferred"]["cycles"] == 1
        assert "40" in c["last"]["deferred"]["token"]
        assert c["last"]["recall"] == 1.0              # the older reading
        assert len(c["history"]) == len(sealed["history"])  # no probe ran
        d = _delta(was)
        assert d["deferrals"] == 1
        assert d["quiet"] + d["interval"] + d["forced"] == 0
        assert driftwatch.snapshot()["gateOk"]
        # the writes have stopped: the tick after seals (an interval has
        # passed since the last seal) and probes the new corpus
        assert driftwatch.run_cycle(scheduled=True, now=60.0)
        c = _only_canary(driftwatch.snapshot())
        assert "40" in c["epoch_token"] and "deferred" not in c["last"]
        assert c["last"]["recall"] == 1.0
        assert _delta(was)["quiet"] == 1
    finally:
        db.close()


def test_deferred_canary_keeps_its_open_finding(tmp_path):
    """A deferred canary was not probed, so it was not judged: an open
    recall finding must neither close nor be counted a second time."""
    from weaviate_tpu.runtime import metrics

    db, col = _mk_db(tmp_path)
    try:
        driftwatch.run_cycle(now=0.0)
        idx = _shard(col).vector_indexes[""]
        live = int(len(idx))
        idx._slot_to_id[:live] = np.roll(idx._slot_to_id[:live], 1)
        driftwatch.run_cycle(now=1.0)
        assert not driftwatch.snapshot()["gateOk"]
        opened = metrics.drift_findings_total.labels("canary",
                                                     "recall").value
        _grow(col, 1)
        driftwatch.run_cycle(scheduled=True, now=31.0)  # moving: deferred
        snap = driftwatch.snapshot()
        assert not snap["gateOk"]
        assert "drift:canary" in degrade.health()["unhealthy"]
        assert metrics.drift_findings_total.labels(
            "canary", "recall").value == opened
    finally:
        db.close()


def test_look_runs_only_the_canary_that_went_quiet(tmp_path):
    """Tenants are shards and each has a canary: a look costs a token
    read a canary, and the cycle it starts is the quiet canary's alone.
    The other is neither probed nor judged: its open finding stays."""
    db, col = _mk_db(tmp_path)
    try:
        db.create_collection(CollectionConfig(name="Other"))
        other = db.get_collection("Other")
        _grow(other, 16)
        driftwatch.run_cycle(now=0.0)                  # both sealed
        idx = _shard(col).vector_indexes[""]
        live = int(len(idx))
        idx._slot_to_id[:live] = np.roll(idx._slot_to_id[:live], 1)
        driftwatch.run_cycle(now=1.0)                  # Drift: recall finding
        assert not driftwatch.snapshot()["gateOk"]
        before = driftwatch.snapshot()["canaries"]
        _grow(other, 4)
        driftwatch.look(now=40.0)
        driftwatch.look(now=42.0)                      # Other: quiet
        after = driftwatch.snapshot()
        drift = next(k for k in before if k.startswith("Drift/"))
        moved = next(k for k in before if k.startswith("Other/"))
        assert len(after["canaries"][drift]["history"]) \
            == len(before[drift]["history"])
        assert len(after["canaries"][moved]["history"]) \
            == len(before[moved]["history"]) + 1
        assert "20" in after["canaries"][moved]["epoch_token"]
        assert not after["gateOk"]
        assert [f["key"] for f in after["findings"]
                if f["leg"] == "canary"] == [f"canary:{drift}:recall"]
    finally:
        db.close()


def test_never_quiet_stream_reseals_within_the_stated_bound(tmp_path):
    """Writes before every tick: MAX_DEFERRALS ticks defer, the next one
    seals what is there (trigger ``interval``) and probes it, so the
    ground truth is never older than MAX_DEFERRALS + 1 intervals."""
    db, col = _mk_db(tmp_path)
    try:
        driftwatch.run_cycle(now=0.0)
        was = _seal_counts()
        for tick in range(1, driftwatch.MAX_DEFERRALS + 1):
            _grow(col, 1, seed=tick)
            driftwatch.run_cycle(scheduled=True, now=30.0 * tick)
            c = _only_canary(driftwatch.snapshot())
            assert c["last"]["deferred"]["cycles"] == tick
            assert "32" in c["epoch_token"]
        assert _delta(was)["deferrals"] == driftwatch.MAX_DEFERRALS
        assert _delta(was)["interval"] == 0
        _grow(col, 1, seed=77)
        driftwatch.run_cycle(scheduled=True,
                             now=30.0 * (driftwatch.MAX_DEFERRALS + 1))
        c = _only_canary(driftwatch.snapshot())
        rows = 32 + driftwatch.MAX_DEFERRALS + 1
        assert str(rows) in c["epoch_token"]
        assert "deferred" not in c["last"] and c["last"]["recall"] == 1.0
        d = _delta(was)
        assert (d["interval"], d["quiet"], d["forced"]) == (1, 0, 0)
        assert d["deferrals"] == driftwatch.MAX_DEFERRALS
    finally:
        db.close()


def test_quiet_seals_come_at_most_once_an_interval(tmp_path):
    """Writes in short bursts cost a seal an interval, not a seal a
    burst: a look that finds the token quiet again sooner waits."""
    db, col = _mk_db(tmp_path)
    try:
        driftwatch.look(now=0.0)
        driftwatch.look(now=2.0)                       # sealed on 32
        was = _seal_counts()
        _grow(col, 1)
        assert driftwatch.look(now=3.0) is True
        assert driftwatch.look(now=5.5) is True        # quiet, but too soon
        assert _delta(was)["quiet"] == 0
        assert "32" in _only_canary(driftwatch.snapshot())["epoch_token"]
        assert driftwatch.look(now=2.0 + driftwatch.interval_s() + 1.0) \
            is False
        assert _delta(was)["quiet"] == 1
        assert "33" in _only_canary(driftwatch.snapshot())["epoch_token"]
    finally:
        db.close()


def test_run_now_seals_at_once_and_the_tick_is_the_scheduled_cycle(tmp_path):
    """``cycles.run_now("driftwatch")`` is the unconditional entry: no
    look has seen this corpus, it is sealed and probed all the same
    (trigger ``forced``). What the scheduler itself calls is the
    scheduled cycle, which defers at first sight."""
    db, col = _mk_db(tmp_path)
    try:
        was = _seal_counts()
        assert db.cycles.run_now("driftwatch")
        c = _only_canary(driftwatch.snapshot())
        assert "32" in c["epoch_token"] and c["last"]["recall"] == 1.0
        assert _delta(was)["forced"] == 1
        _grow(col, 4)
        cb = db.cycles._callbacks["driftwatch"]
        cb.run()                                       # as the scheduler does
        assert _delta(was)["deferrals"] == 1
        assert "32" in _only_canary(driftwatch.snapshot())["epoch_token"]
        assert db.cycles.run_now("driftwatch")
        assert _delta(was)["forced"] == 2
        assert "36" in _only_canary(driftwatch.snapshot())["epoch_token"]
        assert "driftwatch-look" in db.cycles.stats()
    finally:
        db.close()


def test_oversized_corpus_is_skipped_before_any_read(tmp_path, monkeypatch):
    monkeypatch.setenv("WEAVIATE_TPU_DRIFT_CANARY_MAX_ROWS", "4")
    db, col = _mk_db(tmp_path)
    try:
        shard = _shard(col)

        def no_read(*a, **k):
            raise AssertionError("the object store was read")

        monkeypatch.setattr(shard.objects, "iter_items", no_read)
        monkeypatch.setattr(shard, "objects_by_doc_ids", no_read)
        driftwatch.run_cycle(now=0.0)
        c = _only_canary(driftwatch.snapshot())
        assert "32 rows over WEAVIATE_TPU_DRIFT_CANARY_MAX_ROWS" \
            in c["skipped"]
        # skipped on this token: later looks and ticks have nothing to do
        was = _seal_counts()
        driftwatch.look(now=10.0)
        driftwatch.look(now=50.0)
        driftwatch.run_cycle(scheduled=True, now=60.0)
        d = _delta(was)
        assert d["quiet"] + d["interval"] + d["forced"] == 0
    finally:
        db.close()


# -- the one cheap pass: same rows, same ground truth --------------------------


def _oracle_corpus(shard, vec_name):
    """The ground truth's rows as they were read before the one-pass
    reader: a point look-up and a full ``StorageObject`` a doc."""
    idx = shard.vector_indexes[vec_name]
    doc_ids = sorted(int(d) for d in idx._id_to_slot)
    ids, vecs = [], []
    for d, obj in zip(doc_ids, shard.objects_by_doc_ids(doc_ids)):
        v = None if obj is None else obj.vectors.get(vec_name)
        if v is not None:
            ids.append(d)
            vecs.append(np.asarray(v, dtype=np.float32))
    return np.asarray(ids, dtype=np.int64), np.stack(vecs)


def test_ground_truth_equals_the_point_lookup_oracle(tmp_path):
    """Same seed, same corpus => the probe set and every probe's
    ground-truth ids are what the N-point-look-up seal computed."""
    db, col = _mk_db(tmp_path, n=200)
    try:
        shard = _shard(col)
        for u in list(shard._doc_to_uuid.values())[5:40:7]:
            col.delete_object(u)
        shard.objects.flush()                           # segments + memtable
        _grow(col, 30)
        driftwatch.run_cycle(now=0.0)
        (c,) = driftwatch._canaries.values()
        ids, vecs = _oracle_corpus(shard, "")
        got_ids, got_vecs = c.corpus_fn()
        assert got_ids.tolist() == ids.tolist()
        assert got_vecs.tobytes() == vecs.tobytes()
        n = len(ids)
        sel = np.sort(driftwatch._probe_rng(c.key).choice(
            n, size=8, replace=False))
        assert c.probe_ids.tolist() == ids[sel].tolist()
        d = np.asarray(shard._host_pairwise(vecs[sel], vecs, "l2-squared"),
                       dtype=np.float64)
        top = np.argsort(d, axis=1, kind="stable")[:, :driftwatch.CANARY_K]
        assert [g.tolist() for g in c.gt] == [ids[t].tolist() for t in top]
    finally:
        db.close()


@pytest.mark.parametrize("vec_name", ["", "title"])
def test_one_pass_corpus_matches_from_bytes(tmp_path, vec_name):
    """corpus_fn's rows are bit for bit ``StorageObject.from_bytes(raw)
    .vectors[name]``, for the unnamed and a named vector, with objects
    that lack that vector and a deleted doc left out."""
    from weaviate_tpu.storage.objects import StorageObject

    db = Database(str(tmp_path))
    db.create_collection(CollectionConfig(name="Drift"))
    col = db.get_collection("Drift")
    rng = np.random.default_rng(3)
    try:
        uuids = []
        for i in range(24):
            vectors = {"title": rng.standard_normal(6).astype(np.float32),
                       "body": rng.standard_normal(5).astype(np.float32)}
            if i % 4 == 0:
                del vectors["title"]                    # lacks the named one
            vec = None if i % 3 == 0 else \
                rng.standard_normal(8).astype(np.float32)  # lacks the unnamed
            uuids.append(col.put_object({"n": i}, vector=vec,
                                        vectors=vectors))
        col.delete_object(uuids[1])
        col.delete_object(uuids[2])
        shard = _shard(col)
        (c,) = [c for c in driftwatch._canaries.values()
                if c.key.endswith("/" + (vec_name or "-"))]
        ids, vecs = c.corpus_fn()
        want = {}
        for _key, raw in shard.objects.iter_items():
            obj = StorageObject.from_bytes(raw)
            if vec_name in obj.vectors:
                want[obj.doc_id] = obj.vectors[vec_name]
        assert ids.tolist() == sorted(want)
        assert len(ids) == {"": 14, "title": 16}[vec_name]
        for d, row in zip(ids.tolist(), vecs):
            assert row.tobytes() == want[d].tobytes()
    finally:
        db.close()


def _obj(vectors):
    from weaviate_tpu.storage.objects import StorageObject

    return StorageObject(uuid="00000000-0000-0000-0000-00000000002a",
                         doc_id=42, properties={"a": [1, "x"], "b": None},
                         vectors=vectors)


_V = {name: np.random.default_rng(n).standard_normal(d).astype(np.float32)
      for n, (name, d) in enumerate([("", 8), ("body", 5), ("title", 8)])}


@pytest.mark.parametrize("vectors,name,found", [
    ({"": _V[""]}, "", True),
    ({"": _V[""], "body": _V["body"], "title": _V["title"]}, "", True),
    ({"": _V[""], "body": _V["body"], "title": _V["title"]}, "title", True),
    ({"body": _V["body"], "title": _V["title"]}, "title", True),
    ({"": _V[""]}, "title", False),                    # no such vector
    ({"body": _V["body"]}, "", False),                 # no unnamed vector
    ({}, "", False),                                   # no vector at all
    ({"title": _V["body"]}, "title", False),           # another length
], ids=["unnamed", "unnamed-among-named", "named", "named-without-unnamed",
        "named-missing", "unnamed-missing", "no-vectors", "other-length"])
def test_vector_only_reader_matches_from_bytes(vectors, name, found):
    """``read_vector_into`` beside ``from_bytes``: the same bits in the
    caller's row and the doc id, or None with the row untouched."""
    from weaviate_tpu.storage.objects import StorageObject

    raw = _obj(vectors).to_bytes()
    out = np.full(8, np.float32(7.0))
    got = StorageObject.read_vector_into(raw, name, out)
    if found:
        assert got == 42
        assert out.tobytes() == \
            StorageObject.from_bytes(raw).vectors[name].tobytes()
    else:
        assert got is None
        assert out.tobytes() == np.full(8, np.float32(7.0)).tobytes()


# -- sabotage-validated incidents (acceptance criteria) -----------------------


def _shard(col):
    (shard,) = col.shards.values()
    return shard


def _searches(shard, n, dim=8, seed=3):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        shard.vector_search(rng.standard_normal(dim)
                            .astype(np.float32), 10)


def _dispatches(n, residency_s, k=64):
    """``n`` dispatch records with STATED stamps, through the one entry
    point the batcher's residency goes through (its flight record ->
    ``kernelscope.fold_dispatch``): the residency a cycle classifies is
    what the stamps say, whatever the machine's load."""
    for _ in range(n):
        rec = tailboard.record_dispatch(
            "batcher", tailboard.new_dispatch("batcher", "flat"),
            batch=4, b_pad=4, k=k, queue_depth=0, wait_ms=0.1,
            stamps={"exec": 50.0, "fetch1": 50.0 + residency_s})
        kernelscope.fold_dispatch(rec, "drain")


def test_injected_dispatch_latency_trips_live_finding(tmp_path, monkeypatch):
    """The e2e incident chain: dispatch latency inflates the kernelscope
    residency EWMA past the self-sealed band => typed ``live``
    regression finding => ``drift:live`` unhealthy => flight-recorder
    snapshot on disk => clean dispatches decay it and clear it all.

    The residency is fed from the dispatch record with injected stamps
    (2 ms clean, 32 ms slowed): wall-clock residency of real dispatches
    converged inside the 75 % band only on an idle machine, and this
    test runs beside five other workers. The canary is kept out (its
    probes ride the real batcher on the real clock). That a real
    ``batcher.dispatch`` latency reaches the record is the last block's,
    which needs a lower bound only."""
    monkeypatch.setenv("WEAVIATE_TPU_DRIFT_CANARY_MAX_ROWS", "4")
    db, col = _mk_db(tmp_path)
    try:
        _dispatches(40, 0.002)
        db.cycles.run_now("driftwatch")
        snap = driftwatch.snapshot()
        assert snap["gateOk"] and snap["live"]["baselineSource"]

        _dispatches(8, 0.032)
        db.cycles.run_now("driftwatch")

        snap = driftwatch.snapshot()
        assert not snap["gateOk"]
        live = [f for f in snap["findings"]
                if f["leg"] == "live" and f["kind"] == "regression"]
        assert live and live[0]["flips_health"]
        assert live[0]["key"] == "live:live.residency.flat/b4/k64:regression"
        # 2 + 30 * (1 - 0.8 ** 8): the EWMA of the stated samples, exactly
        assert live[0]["baseline"] == 2.0
        assert live[0]["value"] == pytest.approx(26.9668, abs=1e-3)
        assert not degrade.health()["healthy"]
        assert "drift:live" in degrade.health()["unhealthy"]
        assert glob.glob(str(tmp_path / "flightrecorder" / "flight-*"))

        # heal: clean dispatches decay the EWMA back inside the band
        _dispatches(40, 0.002)
        db.cycles.run_now("driftwatch")
        snap = driftwatch.snapshot()
        assert snap["gateOk"], snap["findings"]
        assert degrade.health()["healthy"]

        # and a real injected latency does land in a real dispatch's
        # record (at least the injected 30 ms, however loaded the box)
        faultline.arm("batcher.dispatch", "latency", latency_s=0.03,
                      every=1)
        _searches(_shard(col), 2)
        faultline.disarm()
        real = [r for r in tailboard.debug_flight()["dispatches"]
                if r.get("k") == 16]
        assert real and all(r["device_ms"] >= 30.0 for r in real)
    finally:
        faultline.disarm()
        db.close()


def test_sabotaged_id_mapping_trips_canary_recall_finding(tmp_path):
    """A sabotaged retrain in miniature: permute the index's
    slot->doc-id mapping so the serving path returns wrong ids. The
    corpus size (epoch token) is unchanged, so the sealed ground truth
    stands — and the very next canary cycle catches the recall collapse
    that no throughput metric would ever see."""
    db, col = _mk_db(tmp_path)
    try:
        db.cycles.run_now("driftwatch")
        assert _only_canary(driftwatch.snapshot())["last"]["recall"] == 1.0

        shard = _shard(col)
        idx = shard.vector_indexes[""]
        live = int(len(idx))
        idx._slot_to_id[:live] = np.roll(idx._slot_to_id[:live], 1)

        db.cycles.run_now("driftwatch")
        snap = driftwatch.snapshot()
        assert not snap["gateOk"]
        recall_findings = [f for f in snap["findings"]
                           if f["leg"] == "canary"
                           and f["kind"] == "recall"]
        assert recall_findings and recall_findings[0]["flips_health"]
        assert _only_canary(snap)["last"]["recall"] < 0.5
        assert "drift:canary" in degrade.health()["unhealthy"]
        assert glob.glob(str(tmp_path / "flightrecorder" / "flight-*"))

        # undo the sabotage: the same probe set scores clean again
        idx._slot_to_id[:live] = np.roll(idx._slot_to_id[:live], -1)
        db.cycles.run_now("driftwatch")
        assert driftwatch.snapshot()["gateOk"]
        assert degrade.health()["healthy"]
    finally:
        db.close()


# -- history ring + offline replay --------------------------------------------


def test_history_ring_and_offline_replay(tmp_path, monkeypatch):
    """Every cycle appends one JSONL record under <data_dir>/driftwatch
    and ``python -m tools.driftwatch`` re-classifies them offline
    against the node's sealed baseline with ``runtime/bands`` exit-code
    semantics (0 clean, 1 regressed cycle or open canary finding).

    The residency here is that of real dispatches on the real clock
    (the canary's probes ride the batcher in every cycle), beside five
    other workers: the band sealed for THIS test is 20x wide, so a
    loaded machine's jitter between the two cycles is no regression,
    and the doctored excursion below is over 100x."""
    monkeypatch.setenv("WEAVIATE_TPU_DRIFT_LIVE_BAND", "20")
    db, col = _mk_db(tmp_path)
    try:
        shard = _shard(col)
        _searches(shard, 6)
        db.cycles.run_now("driftwatch")
        db.cycles.run_now("driftwatch")
    finally:
        db.close()
    hist = tmp_path / "driftwatch" / "history.jsonl"
    records = [json.loads(line)
               for line in hist.read_text().splitlines()]
    assert len(records) == 2
    assert all(r["gate_ok"] for r in records)
    assert records[0]["canaries"][0]["recall"] == 1.0
    assert (tmp_path / "driftwatch" / "live_baseline.json").exists()

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    clean = subprocess.run(
        [sys.executable, "-m", "tools.driftwatch", str(tmp_path)],
        capture_output=True, text=True, env=env)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "GATE PASS" in clean.stdout

    # doctor the newest record into a 100x residency excursion: replay
    # must classify it as a regression and exit 1 — triage works from
    # the ring alone, no node required
    doctored = json.loads(json.dumps(records[-1]))
    for v in doctored["live"]["metrics"]["residency"].values():
        v["ewma_ms"] = (v["ewma_ms"] or 0.0) * 100 + 1000.0
    with open(hist, "a") as f:
        f.write(json.dumps(doctored) + "\n")
    bad = subprocess.run(
        [sys.executable, "-m", "tools.driftwatch", str(tmp_path),
         "--json"],
        capture_output=True, text=True, env=env)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    verdicts = [json.loads(line) for line in bad.stdout.splitlines()]
    assert verdicts[-1]["regressions"] >= 1


def test_drift_debug_endpoint_serves_snapshot(tmp_path):
    """/v1/debug/drift is in the endpoint table and serves the verdict
    plane (the generic index round-trip test covers listing parity)."""
    from weaviate_tpu.api.client import Client
    from weaviate_tpu.api.rest import DEBUG_ENDPOINTS, RestServer

    assert "drift" in DEBUG_ENDPOINTS
    db, _ = _mk_db(tmp_path)
    srv = RestServer(db)
    srv.start()
    try:
        db.cycles.run_now("driftwatch")
        out = Client(srv.address).request("GET", "/v1/debug/drift")
        assert out["gateOk"] is True and out["cycle"] == 1
        assert _only_canary(out)["last"]["recall"] == 1.0
    finally:
        srv.stop()
        db.close()


def test_gate_gauge_defaults_healthy_on_scrape():
    """A node that never ran a cycle must scrape gate=1 — a default-0
    gauge would page on every fresh boot."""
    from weaviate_tpu.runtime import metrics

    body, _ = metrics.scrape()
    assert b"weaviate_tpu_drift_gate_ok 1.0" in body
