"""Driftwatch (ISSUE 19): online recall & perf drift detection.

Covers the three legs end to end: band-classification parity with the
benchkeeper CLI (same core.compare, same verdict statuses, same
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


# -- leg 2 units: parity with the benchkeeper CLI -----------------------------


def _section(ewma_ms: float) -> dict:
    return {"residency": {"flat/b8/k16": {"ewma_ms": ewma_ms,
                                          "last_ms": ewma_ms,
                                          "n": 5, "source": "wall"}},
            "counters": {"compile_miss_per_cycle_p1": 1.0,
                         "overlap_per_cycle_p1": 1.0}}


def test_live_classification_is_benchkeeper_band_math():
    """pass / regression / stale out of driftwatch's classifier must be
    the literal benchkeeper verdict for the same synthetic run — one
    band implementation, not a lookalike."""
    from tools.benchkeeper import core as bk

    fp = {"platform": "cpu"}
    baseline = driftwatch.seal_live_baseline(_section(2.0), fp)
    bk.validate_baseline(baseline, "<test>")

    for value, want in ((2.5, "pass"),        # +25% inside the 75% band
                        (20.0, "regression"),  # +900%
                        (0.2, "stale")):       # -90% unexplained
        verdict = driftwatch.classify_live(_section(value), baseline, fp)
        direct = bk.compare({"env_fingerprint": fp,
                             "sections": {"live": _section(value)}},
                            baseline)
        by_id = {r["id"]: r["status"] for r in verdict["entries"]}
        assert by_id["live.residency.flat/b8/k16"] == want
        assert [(r["id"], r["status"], r["delta_frac"])
                for r in verdict["entries"]] \
            == [(r["id"], r["status"], r["delta_frac"])
                for r in direct["entries"]]


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
    against the node's sealed baseline with benchkeeper exit-code
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
