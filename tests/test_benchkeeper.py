"""benchkeeper gate semantics (ISSUE 6): synthetic BENCH JSON pairs.

The gate's contract, pinned metric by metric: within-band passes,
device_ms regressions fail with a reason AND the section's noise
telemetry, wall-only noise inside the wide band passes, out-of-band
improvements flag the baseline stale, mismatched env fingerprints
refuse comparison outright, missing gated metrics fail, and
--update-baseline lands on per-metric medians without touching
reasons/bands. Pure JSON in, verdict out — no jax, no device."""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from tools.benchkeeper import core as bk  # noqa: E402

FP = {"jax": "0.4.37", "platform": "tpu", "device_count": 1,
      "mesh_shape": [1], "dtype": "bf16"}


def make_run(device_ms=0.5, qps=10000.0, retries=0, fp=None):
    fp = FP if fp is None else fp
    sec = lambda wall, dev, **extra: {  # noqa: E731
        "ok": True, "rc": 0, "wall_ms": wall, "device_ms": dev,
        "host_ms": round(wall - dev, 3), "attempts_used": 1,
        "attempt_wall_ms": [wall], "transient_retries": retries,
        "env_fingerprint": fp, **extra}
    return {
        "env_fingerprint": fp,
        "sections": {
            "flat_headline": sec(30000.0, 2000.0, qps=qps),
            "device_steady": sec(2000.0, 1500.0, stats={
                "flat_bf16_b64": {"device_batch_ms": device_ms,
                                  "qps": 121000}}),
        },
    }


BASELINE = {
    "fingerprint": {"platform": "tpu", "dtype": "bf16"},
    "entries": [
        {"id": "device_steady.flat_bf16_b64.device_batch_ms",
         "section": "device_steady",
         "metric": "stats.flat_bf16_b64.device_batch_ms",
         "value": 0.5, "band": 0.15, "direction": "lower",
         "kind": "device", "unit": "ms",
         "reason": "device-attributed chained scan; tight band"},
        {"id": "flat_headline.qps", "section": "flat_headline",
         "metric": "qps", "value": 10000.0, "band": 0.40,
         "direction": "higher", "kind": "wall", "unit": "qps",
         "reason": "host-inclusive e2e; wide band"},
    ],
}


def baseline():
    return bk.validate_baseline(copy.deepcopy(BASELINE))


# -- band math ----------------------------------------------------------------


def test_pass_within_band():
    v = bk.compare(make_run(device_ms=0.55, qps=9200.0), baseline())
    assert v["ok"] is True and v["refused"] is None
    assert v["checked"] == 2 and v["passed"] == 2
    assert all(r["status"] == "pass" for r in v["entries"])


def test_device_ms_regression_fails_with_reason_and_noise():
    v = bk.compare(make_run(device_ms=1.2, retries=3), baseline())
    assert v["ok"] is False and v["regressions"] == 1
    bad = [r for r in v["entries"] if r["status"] == "regression"]
    assert len(bad) == 1
    r = bad[0]
    assert r["id"] == "device_steady.flat_bf16_b64.device_batch_ms"
    assert r["kind"] == "device"
    assert r["delta_frac"] == pytest.approx(1.4)  # (1.2-0.5)/0.5
    # reasoned: the entry's reason rides the gate failure
    assert "tight band" in r["gate_reason"]
    # noise telemetry attached: retry counts + wall/device/host split
    assert r["noise"]["transient_retries"] == 3
    assert r["noise"]["device_ms"] == 1500.0
    assert r["noise"]["wall_ms"] == 2000.0
    assert r["noise"]["host_ms"] == 500.0
    assert r["noise"]["attempt_wall_ms"] == [2000.0]


def test_wall_noise_within_wide_band_passes():
    """A 30% e2e QPS droop is inside the wall band (host noise), and
    must NOT fail the gate while device numbers hold."""
    v = bk.compare(make_run(qps=7000.0), baseline())
    assert v["ok"] is True
    qps_row = next(r for r in v["entries"]
                   if r["id"] == "flat_headline.qps")
    assert qps_row["status"] == "pass"
    assert qps_row["delta_frac"] == pytest.approx(0.3)


def test_wall_regression_beyond_wide_band_fails():
    v = bk.compare(make_run(qps=5000.0), baseline())
    assert v["ok"] is False
    assert next(r for r in v["entries"]
                if r["id"] == "flat_headline.qps")["status"] == "regression"


def test_stale_improvement_detection():
    """An unexplained improvement beyond band means the baseline no
    longer describes the system — flagged stale, gate fails, and the
    report points at --update-baseline."""
    v = bk.compare(make_run(device_ms=0.3), baseline())
    assert v["ok"] is False and v["stale"] == 1 and v["regressions"] == 0
    row = next(r for r in v["entries"] if r["status"] == "stale")
    assert "--update-baseline" in row["gate_reason"]


def test_mismatched_fingerprint_refuses_comparison():
    cpu_fp = {**FP, "platform": "cpu"}
    v = bk.compare(make_run(fp=cpu_fp), baseline())
    assert v["ok"] is False and v["refused"] is not None
    assert v["entries"] == []  # never compared
    assert any("platform" in m for m in v["refused"]["mismatched"])


def test_fingerprint_subset_matching_ignores_unnamed_keys():
    """The baseline names platform+dtype only; a jax version bump must
    not refuse comparison."""
    v = bk.compare(make_run(fp={**FP, "jax": "0.5.0"}), baseline())
    assert v["refused"] is None


def test_missing_section_fails_with_section_error():
    run = make_run()
    run["sections"]["device_steady"] = {
        "ok": False, "rc": 1, "error": "RuntimeError('device runtime died')",
        "attempts_used": 2, "attempt_wall_ms": [900.0, 850.0],
        "transient_retries": 5, "env_fingerprint": FP}
    v = bk.compare(run, baseline())
    assert v["ok"] is False and v["missing"] == 1
    row = next(r for r in v["entries"] if r["status"] == "missing")
    assert "device runtime died" in row["gate_reason"]
    # the crashed section's partial attempt timings still surface
    assert row["noise"]["attempt_wall_ms"] == [900.0, 850.0]
    assert row["noise"]["transient_retries"] == 5


# -- baseline discipline ------------------------------------------------------


def test_baseline_entry_requires_reason():
    bad = copy.deepcopy(BASELINE)
    bad["entries"][0]["reason"] = "  "
    with pytest.raises(bk.BaselineError, match="reason"):
        bk.validate_baseline(bad)


def test_baseline_entry_requires_positive_band_and_known_direction():
    bad = copy.deepcopy(BASELINE)
    bad["entries"][0]["band"] = 0
    with pytest.raises(bk.BaselineError, match="band"):
        bk.validate_baseline(bad)
    bad = copy.deepcopy(BASELINE)
    bad["entries"][1]["direction"] = "sideways"
    with pytest.raises(bk.BaselineError, match="direction"):
        bk.validate_baseline(bad)


def test_update_baseline_median_behavior():
    runs = [make_run(device_ms=v, qps=q)
            for v, q in ((0.62, 9000.0), (0.58, 12000.0), (0.70, 11000.0))]
    new_base, warnings = bk.update_baseline(runs, baseline())
    assert warnings == []
    dev = next(e for e in new_base["entries"]
               if e["section"] == "device_steady")
    qps = next(e for e in new_base["entries"]
               if e["section"] == "flat_headline")
    assert dev["value"] == pytest.approx(0.62)   # median of .62/.58/.70
    assert qps["value"] == pytest.approx(11000.0)
    # discipline preserved: bands/reasons/directions never touched
    assert dev["band"] == 0.15 and "tight band" in dev["reason"]
    # fingerprint adopted for exactly the keys the baseline names
    assert new_base["fingerprint"] == {"platform": "tpu", "dtype": "bf16"}


def test_update_baseline_refuses_mixed_rigs():
    runs = [make_run(), make_run(fp={**FP, "platform": "cpu"})]
    with pytest.raises(bk.BaselineError, match="disagree"):
        bk.update_baseline(runs, baseline())


def test_update_baseline_refuses_cross_rig_overwrite():
    """The destructive write path mirrors the compare path's refusal:
    a wrong-rig run must not silently replace every TPU reference
    number — rig migration needs the explicit flag."""
    cpu_run = make_run(fp={**FP, "platform": "cpu"})
    with pytest.raises(bk.BaselineError, match="different rig"):
        bk.update_baseline([cpu_run], baseline())
    new_base, _ = bk.update_baseline([cpu_run], baseline(),
                                     allow_fingerprint_change=True)
    assert new_base["fingerprint"]["platform"] == "cpu"


def test_update_baseline_warns_on_absent_metric():
    run = make_run()
    del run["sections"]["flat_headline"]
    new_base, warnings = bk.update_baseline([run], baseline())
    assert any("flat_headline.qps" in w for w in warnings)
    # untouched reference value, not zero/None
    assert next(e for e in new_base["entries"]
                if e["id"] == "flat_headline.qps")["value"] == 10000.0


# -- CLI exit codes + verdict artifact ----------------------------------------


def _cli(tmp_path, run, extra=()):
    bpath = tmp_path / "baseline.json"
    rpath = tmp_path / "run.json"
    vpath = tmp_path / "verdict.json"
    bpath.write_text(json.dumps(BASELINE))
    rpath.write_text(json.dumps(run))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.benchkeeper", str(rpath),
         "--baseline", str(bpath), "--verdict-path", str(vpath), *extra],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=60)
    verdict = (json.loads(vpath.read_text())
               if vpath.exists() else None)
    return proc, verdict


def test_cli_pass_exit0_and_verdict_artifact(tmp_path):
    proc, verdict = _cli(tmp_path, make_run())
    assert proc.returncode == 0, proc.stderr
    assert "GATE PASS" in proc.stdout
    assert verdict["ok"] is True and verdict["checked"] == 2


def test_cli_regression_exit1_with_attributed_report(tmp_path):
    proc, verdict = _cli(tmp_path, make_run(device_ms=1.3, retries=2))
    assert proc.returncode == 1
    # reasoned, section-attributed, device/wall split visible
    assert "FAIL regression" in proc.stdout
    assert "device_steady.flat_bf16_b64.device_batch_ms" in proc.stdout
    assert "device-timed" in proc.stdout
    assert "tight band" in proc.stdout
    assert "transient_retries=2" in proc.stdout
    assert "host " in proc.stdout
    assert verdict["ok"] is False


def test_cli_fingerprint_mismatch_exit2(tmp_path):
    proc, _ = _cli(tmp_path, make_run(fp={**FP, "platform": "cpu"}))
    assert proc.returncode == 2
    assert "REFUSED" in proc.stdout


def test_cli_json_output(tmp_path):
    proc, _ = _cli(tmp_path, make_run(), extra=("--json",))
    assert proc.returncode == 0
    out = json.loads(proc.stdout)
    assert out["ok"] is True and len(out["entries"]) == 2


# -- /v1/debug/perf + weaviate_tpu_bench_* gauges -----------------------------


def test_debug_perf_endpoint_and_gauges(tmp_path, monkeypatch):
    """The last gate verdict and per-section trend deltas are visible
    from the serving process: GET /v1/debug/perf + Prometheus gauges,
    the same surface as the HBM ledger."""
    import urllib.request

    # persist a failing verdict where perfgate will look
    verdict = bk.compare(make_run(device_ms=1.2, retries=3), baseline())
    vpath = tmp_path / "last_verdict.json"
    bk.write_verdict(verdict, str(vpath))
    monkeypatch.setenv("BENCHKEEPER_VERDICT_PATH", str(vpath))

    from weaviate_tpu.api.rest import RestServer
    from weaviate_tpu.db.database import Database

    db = Database(str(tmp_path / "data"))
    srv = RestServer(db)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://{srv.address}/v1/debug/perf") as resp:
            assert resp.status == 200
            out = json.loads(resp.read())
        assert out["gate"]["ok"] is False
        assert out["gate"]["regressions"] == 1
        row = next(t for t in out["trends"]
                   if t["status"] == "regression")
        assert row["id"] == "device_steady.flat_bf16_b64.device_batch_ms"
        assert row["deltaFrac"] == 1.4
        assert row["noise"]["transient_retries"] == 3
        # same numbers on the Prometheus surface
        with urllib.request.urlopen(
                f"http://{srv.address}/v1/metrics") as resp:
            exp = resp.read().decode()
        assert "weaviate_tpu_bench_gate_ok 0.0" in exp
        assert "weaviate_tpu_bench_gate_regressions 1.0" in exp
        assert ('weaviate_tpu_bench_delta_frac{entry='
                '"device_steady.flat_bf16_b64.device_batch_ms"} 1.4'
                in exp)
    finally:
        srv.stop()
        db.close()


def test_debug_perf_without_verdict_reports_plainly(tmp_path, monkeypatch):
    monkeypatch.setenv("BENCHKEEPER_VERDICT_PATH",
                       str(tmp_path / "nope.json"))
    from weaviate_tpu.runtime import perfgate

    snap = perfgate.snapshot()
    assert snap["verdict"] is None
    assert "tools.benchkeeper" in snap["note"]


def test_metrics_scrape_alone_publishes_gauges(tmp_path, monkeypatch):
    """A scrape-only Prometheus setup must see the perf-gate gauges:
    the /v1/metrics handler refreshes from the on-disk verdict without
    anyone ever reading /v1/debug/perf."""
    import urllib.request

    verdict = bk.compare(make_run(device_ms=1.2, retries=1), baseline())
    vpath = tmp_path / "last_verdict.json"
    bk.write_verdict(verdict, str(vpath))
    monkeypatch.setenv("BENCHKEEPER_VERDICT_PATH", str(vpath))

    from weaviate_tpu.api.rest import RestServer
    from weaviate_tpu.db.database import Database

    db = Database(str(tmp_path / "data"))
    srv = RestServer(db)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://{srv.address}/v1/metrics") as resp:
            exp = resp.read().decode()
        assert "weaviate_tpu_bench_gate_ok 0.0" in exp
        assert "weaviate_tpu_bench_gate_regressions 1.0" in exp
    finally:
        srv.stop()
        db.close()


def test_refused_comparison_does_not_clobber_verdict(tmp_path):
    """A REFUSED comparison is noise, not signal — it must not replace
    the last real verdict (which would read as a gate failure on the
    debug/gauge surface)."""
    proc, verdict = _cli(tmp_path, make_run())
    assert proc.returncode == 0 and verdict["ok"] is True
    run = make_run(fp={**FP, "platform": "cpu"})
    (tmp_path / "run.json").write_text(json.dumps(run))
    proc2 = subprocess.run(
        [sys.executable, "-m", "tools.benchkeeper",
         str(tmp_path / "run.json"), "--baseline",
         str(tmp_path / "baseline.json"), "--verdict-path",
         str(tmp_path / "verdict.json")],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=60)
    assert proc2.returncode == 2
    kept = json.loads((tmp_path / "verdict.json").read_text())
    assert kept["ok"] is True and kept["refused"] is None


def test_delta_series_survives_unit_change():
    """The stale-series sweep keys value gauges on (entry, unit) but
    delta gauges on entry alone: a unit rename must drop the old value
    series without deleting the just-republished delta series."""
    from weaviate_tpu.runtime import perfgate
    from weaviate_tpu.runtime.metrics import registry

    eid = "unit_change_probe.metric"
    mk = lambda unit, val, d: {  # noqa: E731
        "ok": True, "entries": [
            {"id": eid, "unit": unit, "value": val, "delta_frac": d}]}
    perfgate.publish_metrics(mk("ms", 1.0, 0.1))
    perfgate.publish_metrics(mk("qps", 2.0, 0.2))
    exp = registry.expose()
    assert (f'weaviate_tpu_bench_delta_frac{{entry="{eid}"}} 0.2'
            in exp)
    assert f'entry="{eid}",unit="qps"' in exp
    assert f'entry="{eid}",unit="ms"' not in exp
    # a fully vanished entry still drops both series
    perfgate.publish_metrics({"ok": True, "entries": []})
    assert f'entry="{eid}"' not in registry.expose()


def test_update_baseline_validates_and_preserves_file_on_error(tmp_path):
    """--update-baseline re-validates the rewritten baseline BEFORE
    touching the checked-in file: a median that rounds to 0.0 exits 2
    and leaves the original intact (and the write is atomic — no .tmp
    debris)."""
    bpath = tmp_path / "baseline.json"
    bpath.write_text(json.dumps(BASELINE))
    rpath = tmp_path / "run.json"
    rpath.write_text(json.dumps(make_run(device_ms=1e-6)))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.benchkeeper", str(rpath),
         "--baseline", str(bpath), "--update-baseline"],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=60)
    assert proc.returncode == 2
    assert "nonzero" in proc.stderr
    assert json.loads(bpath.read_text()) == BASELINE  # untouched
    assert not (tmp_path / "baseline.json.tmp").exists()


def test_smoke_without_device_metrics_fails_plainly(monkeypatch):
    """The smoke battery doctors a device_ms entry; a run with no
    device-timed metrics must raise the clean error, not a bare
    StopIteration."""
    from tools.benchkeeper import smoke

    run = smoke.synthetic_run()
    del run["sections"]["device_steady"]
    monkeypatch.setattr(smoke, "synthetic_run", lambda: run)
    with pytest.raises(RuntimeError, match="no device-timed metrics"):
        smoke.run_smoke(bench=False)


def test_checked_in_baseline_is_valid_and_tpu_scoped():
    """The shipped baseline must load (reasons everywhere) and must be
    fingerprint-scoped so CPU CI can never 'regress' TPU numbers."""
    base = bk.load_baseline(bk.default_baseline_path())
    assert base["fingerprint"].get("platform") == "tpu"
    assert all(e["kind"] in ("device", "wall") for e in base["entries"])
    # a CPU run is refused, not failed
    v = bk.compare(make_run(fp={**FP, "platform": "cpu"}), base)
    assert v["refused"] is not None
