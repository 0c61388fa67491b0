"""Band math (``weaviate_tpu/runtime/bands.py``): synthetic run/baseline pairs.

The contract, pinned metric by metric: within-band passes, device_ms
regressions fail with a reason AND the section's noise telemetry,
wall-only noise inside the wide band passes, out-of-band improvements
flag the baseline stale, mismatched env fingerprints refuse comparison
outright, missing gated metrics fail, and a baseline entry without a
reason, a positive band or a known direction does not load. Pure JSON
in, verdict out: no jax, no device."""

from __future__ import annotations

import copy

import pytest

from weaviate_tpu.runtime import bands

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
    return bands.validate_baseline(copy.deepcopy(BASELINE))


# -- band math ----------------------------------------------------------------


def test_pass_within_band():
    v = bands.compare(make_run(device_ms=0.55, qps=9200.0), baseline())
    assert v["ok"] is True and v["refused"] is None
    assert v["checked"] == 2 and v["passed"] == 2
    assert all(r["status"] == "pass" for r in v["entries"])


def test_device_ms_regression_fails_with_reason_and_noise():
    v = bands.compare(make_run(device_ms=1.2, retries=3), baseline())
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
    v = bands.compare(make_run(qps=7000.0), baseline())
    assert v["ok"] is True
    qps_row = next(r for r in v["entries"]
                   if r["id"] == "flat_headline.qps")
    assert qps_row["status"] == "pass"
    assert qps_row["delta_frac"] == pytest.approx(0.3)


def test_wall_regression_beyond_wide_band_fails():
    v = bands.compare(make_run(qps=5000.0), baseline())
    assert v["ok"] is False
    assert next(r for r in v["entries"]
                if r["id"] == "flat_headline.qps")["status"] == "regression"


def test_stale_improvement_detection():
    """An unexplained improvement beyond band means the baseline no
    longer describes the system — flagged stale, gate fails, and the
    report says to seal a new one."""
    v = bands.compare(make_run(device_ms=0.3), baseline())
    assert v["ok"] is False and v["stale"] == 1 and v["regressions"] == 0
    row = next(r for r in v["entries"] if r["status"] == "stale")
    assert "seal a new baseline" in row["gate_reason"]


def test_mismatched_fingerprint_refuses_comparison():
    cpu_fp = {**FP, "platform": "cpu"}
    v = bands.compare(make_run(fp=cpu_fp), baseline())
    assert v["ok"] is False and v["refused"] is not None
    assert v["entries"] == []  # never compared
    assert any("platform" in m for m in v["refused"]["mismatched"])


def test_fingerprint_subset_matching_ignores_unnamed_keys():
    """The baseline names platform+dtype only; a jax version bump must
    not refuse comparison."""
    v = bands.compare(make_run(fp={**FP, "jax": "0.5.0"}), baseline())
    assert v["refused"] is None


def test_missing_section_fails_with_section_error():
    run = make_run()
    run["sections"]["device_steady"] = {
        "ok": False, "rc": 1, "error": "RuntimeError('device runtime died')",
        "attempts_used": 2, "attempt_wall_ms": [900.0, 850.0],
        "transient_retries": 5, "env_fingerprint": FP}
    v = bands.compare(run, baseline())
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
    with pytest.raises(bands.BaselineError, match="reason"):
        bands.validate_baseline(bad)


def test_baseline_entry_requires_positive_band_and_known_direction():
    bad = copy.deepcopy(BASELINE)
    bad["entries"][0]["band"] = 0
    with pytest.raises(bands.BaselineError, match="band"):
        bands.validate_baseline(bad)
    bad = copy.deepcopy(BASELINE)
    bad["entries"][1]["direction"] = "sideways"
    with pytest.raises(bands.BaselineError, match="direction"):
        bands.validate_baseline(bad)
