"""bench.py section harness: a mid-run section failure must not take down
the run — rc=0, every completed section present in the final stdout JSON,
and the partial-results file updated incrementally (the BENCH_r05 failure
mode was rc=1 / parsed: null after one transient device error).

Plus the ISSUE 6 attribution contract: every section entry carries
{wall_ms, device_ms, host_ms, transient_retries, attempt_wall_ms,
env_fingerprint}, failed sections still emit their per-attempt wall
timings, and `python -m tools.benchkeeper --smoke` (the perf-gate
machinery self-test over a REAL tiny bench run) is green on CPU."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(extra_env, sections):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_N="2048",
        BENCH_BATCH="64",
        BENCH_CHUNK="1024",
        BENCH_SECTION_RETRIES="1",
        BENCH_SECTIONS=",".join(sections),
        BENCH_WATCHDOG_S="600",
    )
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=570, env=env, cwd=REPO)
    return proc


def test_bench_partial_results_on_injected_failure(tmp_path):
    json_path = str(tmp_path / "partial.json")
    proc = _run_bench(
        {"BENCH_FAIL_SECTION": "cpu_baseline",
         "BENCH_JSON_PATH": json_path},
        ["setup", "cpu_baseline", "device_setup", "flat_headline"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    secs = out["sections"]
    assert secs["setup"]["ok"] is True
    assert secs["cpu_baseline"]["ok"] is False
    assert "injected" in secs["cpu_baseline"]["error"]
    assert secs["cpu_baseline"]["attempts"] == 2  # retried with backoff
    # a section that exhausts retries still emits its per-attempt wall
    # timings — crashed runs contribute noise statistics to benchkeeper
    failed_walls = secs["cpu_baseline"]["attempt_wall_ms"]
    assert len(failed_walls) == 2
    assert all(isinstance(w, (int, float)) and w >= 0 for w in failed_walls)
    assert "env_fingerprint" in secs["cpu_baseline"]
    # sections after the failure still ran and landed in the JSON
    assert secs["device_setup"]["ok"] is True
    assert secs["flat_headline"]["ok"] is True
    assert out["failed_sections"] == ["cpu_baseline"]
    # headline qps still measured (recall needs the failed ground truth)
    assert out["value"] > 0
    assert out.get("recall_at_10") is None
    # incremental file holds the same sections (crash resilience)
    with open(json_path) as f:
        disk = json.load(f)
    assert set(disk["sections"]) == set(secs)


def test_bench_selection_microbench_section(tmp_path):
    proc = _run_bench(
        {}, ["setup", "device_setup", "selection_microbench"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    mb = out["sections"]["selection_microbench"]
    assert mb["ok"] is True, mb
    for key in ("exact_ms", "approx_ms", "fused_ms", "scan_floor_ms",
                "fused_over_approx_overhead"):
        assert key in mb
    # fused selection is exact: ids match the exact path bit-for-bit
    assert mb["fused_vs_exact_id_match"] == 1.0
    assert mb["device_numbers"] is False  # CPU CI — interpret mechanics
    # ISSUE 6 attribution contract on a successful section: device time
    # (summed bench.* device_sync spans) split from host wall time
    for sec in (mb, out["sections"]["device_setup"]):
        assert sec["wall_ms"] > 0
        assert sec["device_ms"] >= 0
        assert sec["host_ms"] >= 0
        assert sec["wall_ms"] >= sec["device_ms"]
        assert abs(sec["wall_ms"] - sec["device_ms"] - sec["host_ms"]) < 0.01
        assert sec["attempt_wall_ms"] == [sec["wall_ms"]]
        fp = sec["env_fingerprint"]
        assert fp["platform"] == "cpu" and fp["device_count"] >= 1
        assert fp["dtype"] == "bf16" and fp["jax"]
    # the chained-scan device fetches actually attributed device time
    assert mb["device_ms"] > 0
    # run-level fingerprint for benchkeeper's like-for-like refusal
    assert out["env_fingerprint"]["platform"] == "cpu"


def test_benchkeeper_smoke_gate_end_to_end(tmp_path):
    """`python -m tools.benchkeeper --smoke`: a REAL tiny bench run on
    CPU feeds the gate battery (self-compare passes, doctored device_ms
    regression fails reasoned+attributed, stale improvement flagged,
    fingerprint mismatch refused, exit codes correct). The ISSUE 6
    acceptance criterion, verbatim."""
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_N="2048",
        BENCH_BATCH="64",
        BENCH_CHUNK="1024",
        BENCH_SECTION_RETRIES="0",
        BENCH_WATCHDOG_S="500",
    )
    proc = subprocess.run(
        [sys.executable, "-m", "tools.benchkeeper", "--smoke"],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "smoke OK" in proc.stderr
    # the injected regression leg produced a reasoned, section-
    # attributed report splitting device time from host wall time
    assert "FAIL regression" in proc.stdout
    assert "device-timed" in proc.stdout
    assert "section noise" in proc.stdout
    assert "STALE improvement" in proc.stdout
    assert "REFUSED" in proc.stdout
