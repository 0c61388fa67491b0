"""benchkeeper --smoke: the gate machinery self-test.

Real perf numbers need the TPU rig, but the gate itself — bench JSON
parsing, metric extraction, band math, regression/stale/missing
verdicts, fingerprint refusal, --update-baseline medians, CLI exit
codes — must be exercised on every PR, on CPU, in tier-1. Smoke mode
does exactly that:

1. obtain a bench run: a REAL ``bench.py`` subprocess on tiny shapes
   under ``JAX_PLATFORMS=cpu`` (so the attribution fields are produced
   by the actual harness), or a canned synthetic run with
   ``--synthetic`` (hermetic, no jax import — what
   ``__graft_entry__.dryrun_benchkeeper`` uses);
2. derive a baseline from that run (device-timed metrics get tight
   bands, wall metrics wide ones — values equal the run's own, so the
   self-comparison must pass);
3. run the battery: self-compare passes (exit 0) → a doctored
   regression fails with a reasoned, section-attributed report
   splitting device_ms from host wall time (exit 1) → a doctored
   improvement flags the baseline stale (exit 1) → a doctored
   fingerprint refuses comparison (exit 2) → a dropped section fails
   as missing (exit 1) → --update-baseline across three doctored runs
   lands on the median.

Exit 0 iff every step behaved.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import tempfile

from tools.benchkeeper.core import (EXIT_GATE_FAIL, EXIT_OK, EXIT_REFUSED,
                                    compare, load_baseline, main,
                                    repo_root, validate_baseline)

#: wall-gated metrics derived when present: (section, metric, unit)
_WALL_SPECS = (("flat_headline", "qps", "qps"),
               ("flat_headline", "p50_batch_ms", "ms"))
_DEVICE_BAND = 0.25
_WALL_BAND = 0.50


def log(*a) -> None:
    print("[benchkeeper-smoke]", *a, file=sys.stderr, flush=True)


def synthetic_run() -> dict:
    """A canned bench results JSON shaped exactly like bench.py output
    (attribution fields included) — the hermetic smoke substrate."""
    fp = {"jax": "0.0-synthetic", "platform": "cpu", "device_count": 1,
          "mesh_shape": [1], "dtype": "bf16"}
    mk = lambda wall, dev, **extra: {  # noqa: E731
        "ok": True, "rc": 0, "seconds": round(wall / 1e3, 2),
        "wall_ms": wall, "device_ms": dev,
        "host_ms": round(wall - dev, 3), "attempts_used": 1,
        "attempt_wall_ms": [wall], "transient_retries": 0,
        "env_fingerprint": fp, **extra}
    return {
        "metric": "flat_knn_qps_synth1M_128d_k10",
        "value": 10539.6, "unit": "qps",
        "env_fingerprint": fp,
        "bench_repeats": 1,
        "sections": {
            "flat_headline": mk(31000.0, 2300.0, qps=10539.6,
                                p50_batch_ms=97.16, recall_at_10=0.992),
            "device_steady": mk(2100.0, 1050.0, stats={
                "flat_bf16_b64": {"device_batch_ms": 0.528,
                                  "qps": 121127},
                "flat_bf16_b256": {"device_batch_ms": 0.801,
                                   "qps": 319414},
            }),
        },
    }


def bench_run() -> dict:
    """Run the real bench.py on tiny shapes, CPU, fast sections.
    Pre-set BENCH_* env vars win (the tier-1 test shrinks them)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("BENCH_N", "2048")
    env.setdefault("BENCH_BATCH", "64")
    env.setdefault("BENCH_CHUNK", "1024")
    env.setdefault("BENCH_SECTION_RETRIES", "1")
    env.setdefault("BENCH_WATCHDOG_S", "540")
    env.setdefault("BENCH_SECTIONS",
                   "setup,device_setup,flat_headline,device_steady")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo_root(), "bench.py")],
        capture_output=True, text=True, timeout=560, env=env,
        cwd=repo_root())
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench.py exited {proc.returncode}: {proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def derive_baseline(run: dict) -> dict:
    """Baseline whose reference values ARE the run's values: device-
    timed chained-scan metrics with tight bands, wall metrics wide."""
    entries = []
    secs = run.get("sections") or {}
    for sec, metric, unit in _WALL_SPECS:
        v = (secs.get(sec) or {}).get(metric)
        if isinstance(v, (int, float)):
            entries.append({
                "id": f"{sec}.{metric}", "section": sec, "metric": metric,
                "value": float(v), "band": _WALL_BAND,
                "direction": "lower" if unit == "ms" else "higher",
                "kind": "wall", "unit": unit,
                "reason": "smoke-derived wall reading (host-inclusive "
                          "— wide band)"})
    stats = (secs.get("device_steady") or {}).get("stats") or {}
    for tag, row in sorted(stats.items()):
        v = row.get("device_batch_ms") if isinstance(row, dict) else None
        if isinstance(v, (int, float)):
            entries.append({
                "id": f"device_steady.{tag}.device_batch_ms",
                "section": "device_steady",
                "metric": f"stats.{tag}.device_batch_ms",
                "value": float(v), "band": _DEVICE_BAND,
                "direction": "lower", "kind": "device", "unit": "ms",
                "reason": "smoke-derived device-attributed chained scan "
                          "(tight band)"})
    if not entries:
        raise RuntimeError("smoke run produced no gateable metrics")
    fp = run.get("env_fingerprint") or {}
    return validate_baseline({
        "notes": "smoke-derived; never checked in",
        "fingerprint": {k: fp.get(k) for k in ("platform", "dtype")
                        if k in fp},
        "entries": entries,
    })


def _set_metric(run: dict, section: str, metric: str, fn) -> dict:
    out = copy.deepcopy(run)
    node = out["sections"][section]
    parts = metric.split(".")
    for p in parts[:-1]:
        node = node[p]
    node[parts[-1]] = fn(node[parts[-1]])
    return out


def run_smoke(bench: bool = True) -> int:
    failures: list[str] = []

    def check(name: str, cond: bool, detail: str = "") -> None:
        if cond:
            log(f"PASS {name}")
        else:
            failures.append(name)
            log(f"FAIL {name}" + (f": {detail}" if detail else ""))

    log("obtaining bench run "
        + ("(real bench.py, tiny shapes, JAX_PLATFORMS=cpu)" if bench
           else "(synthetic)"))
    run = bench_run() if bench else synthetic_run()
    base = derive_baseline(run)
    dev_entry = next(
        (e for e in base["entries"] if e["kind"] == "device"), None)
    if dev_entry is None:
        raise RuntimeError(
            "smoke run produced no device-timed metrics (device_steady "
            "missing from BENCH_SECTIONS?) — the battery doctors a "
            "device_ms entry, so it needs at least one")
    sec, metric = dev_entry["section"], dev_entry["metric"]

    with tempfile.TemporaryDirectory(prefix="benchkeeper-smoke-") as td:
        bpath = os.path.join(td, "baseline.json")
        vpath = os.path.join(td, "verdict.json")

        def cli(run_obj, extra=()) -> int:
            rpath = os.path.join(td, "run.json")
            with open(rpath, "w") as f:
                json.dump(run_obj, f)
            return main([rpath, "--baseline", bpath, "--verdict-path",
                         vpath, *extra])

        with open(bpath, "w") as f:
            json.dump(base, f)

        # 1. self-comparison: every metric equals its reference -> pass
        check("self-comparison passes (exit 0)",
              cli(run) == EXIT_OK)
        check("verdict artifact written",
              os.path.exists(vpath)
              and json.load(open(vpath)).get("ok") is True)

        # 2. doctored regression on a DEVICE-attributed metric
        worse = _set_metric(run, sec, metric,
                            lambda v: v * (1 + 3 * dev_entry["band"]))
        verdict = compare(worse, load_baseline(bpath))
        bad = [r for r in verdict["entries"]
               if r["status"] == "regression"]
        check("injected device_ms regression fails the gate (exit 1)",
              cli(worse) == EXIT_GATE_FAIL and not verdict["ok"])
        check("regression is reasoned and section-attributed",
              bool(bad) and bad[0]["id"] == dev_entry["id"]
              and bad[0]["reason"] and "device_ms" in bad[0]["noise"]
              and "wall_ms" in bad[0]["noise"],
              json.dumps(bad[:1]))

        # 3. doctored improvement -> stale baseline
        better = _set_metric(run, sec, metric,
                             lambda v: v / (1 + 3 * dev_entry["band"]))
        verdict = compare(better, load_baseline(bpath))
        check("out-of-band improvement flags the baseline stale",
              cli(better) == EXIT_GATE_FAIL
              and any(r["status"] == "stale"
                      for r in verdict["entries"]))

        # 4. mismatched fingerprint refuses comparison
        alien = copy.deepcopy(run)
        alien["env_fingerprint"] = {
            **(alien.get("env_fingerprint") or {}),
            "platform": "tpu-unicorn"}
        check("fingerprint mismatch refuses comparison (exit 2)",
              cli(alien) == EXIT_REFUSED)

        # 5. dropped section -> missing metric fails the gate
        partial = copy.deepcopy(run)
        partial["sections"].pop(sec)
        check("missing gated section fails the gate (exit 1)",
              cli(partial) == EXIT_GATE_FAIL)

        # 6. --update-baseline: median across three runs
        v0 = float(dev_entry["value"])
        paths = []
        for i, scale in enumerate((0.9, 1.0, 1.1)):
            p = os.path.join(td, f"median{i}.json")
            with open(p, "w") as f:
                json.dump(_set_metric(run, sec, metric,
                                      lambda v: v * scale), f)
            paths.append(p)
        rc = main([*paths, "--baseline", bpath, "--update-baseline"])
        new_val = next(e["value"] for e in load_baseline(bpath)["entries"]
                       if e["id"] == dev_entry["id"])
        check("--update-baseline lands on the per-metric median",
              rc == EXIT_OK and abs(new_val - v0) < 1e-6 * max(v0, 1.0),
              f"median {new_val} vs expected {v0}")

    if failures:
        log(f"smoke FAILED: {failures}")
        return 1
    log("smoke OK: parsing, band math, stale detection, fingerprint "
        "refusal, exit codes all behaved")
    return 0
