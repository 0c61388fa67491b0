"""benchkeeper driver: baseline, band math, verdicts, CLI.

A fresh ``BENCH_rNN.json`` (bench.py output) is compared against a
checked-in ``tools/benchkeeper/baseline.json`` of per-metric reference
numbers. The discipline mirrors ``tools/graftlint/baseline.json``:

- every baseline entry carries a MANDATORY non-empty ``reason`` — a
  number nobody can explain gates nothing;
- entries are fingerprint-scoped: the baseline names the environment
  its numbers were measured in (jax version, platform, device count,
  mesh shape, dtype — any subset), and a run whose ``env_fingerprint``
  differs on any named key is REFUSED outright (exit 2), never
  compared — a CPU smoke run "regressing" a TPU baseline is noise, not
  signal;
- a regression beyond an entry's tolerance band fails the gate (exit 1)
  with the entry's reason AND the offending section's retry/noise
  telemetry (transient_retries, attempts_used, attempt_wall_ms, the
  wall/device/host split), so a host-flake failure is
  distinguishable from a kernel regression at a glance;
- an unexplained IMPROVEMENT beyond band flags the entry STALE and
  also fails the gate — yesterday's reference number no longer
  describes the system, so the gate is not actually gating; rerun
  ``--update-baseline`` (ideally with BENCH_REPEATS>1 runs) to adopt
  the new level on purpose;
- ``--update-baseline run1.json [run2.json ...]`` rewrites each
  entry's reference value to the per-metric MEDIAN across the given
  runs (reasons, bands, directions are preserved — only the numbers
  move), and adopts the runs' fingerprint.

Band semantics: ``delta_frac`` is normalized so positive = regressing
direction (slower scan, lower QPS). ``kind: "device"`` entries gate on
device-attributed milliseconds with tight bands (the chained-jit
timings host noise cannot inflate); ``kind: "wall"`` entries gate on
host-inclusive wall readings with wide bands.

Exit codes: 0 gate passed, 1 gate failed (regression / stale /
missing metric), 2 comparison refused (fingerprint mismatch, invalid
baseline, unreadable input).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

EXIT_OK = 0
EXIT_GATE_FAIL = 1
EXIT_REFUSED = 2

#: fields every baseline entry must carry (reason must be non-empty)
_REQUIRED = ("id", "section", "metric", "value", "band", "direction",
             "kind", "reason")
_DIRECTIONS = ("lower", "higher")
_KINDS = ("device", "wall")


class BaselineError(ValueError):
    pass


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def default_baseline_path() -> str:
    return os.path.join(repo_root(), "tools", "benchkeeper",
                        "baseline.json")


def default_verdict_path() -> str:
    return os.environ.get(
        "BENCHKEEPER_VERDICT_PATH",
        os.path.join(repo_root(), "tools", "benchkeeper",
                     "last_verdict.json"))


# -- baseline -----------------------------------------------------------------


def validate_baseline(base: dict, path: str = "<baseline>") -> dict:
    if not isinstance(base, dict) or not isinstance(
            base.get("entries"), list):
        raise BaselineError(
            f"{path}: baseline must be an object with an 'entries' list")
    fp = base.get("fingerprint", {})
    if not isinstance(fp, dict):
        raise BaselineError(f"{path}: 'fingerprint' must be an object")
    seen: set[str] = set()
    for e in base["entries"]:
        if not isinstance(e, dict):
            raise BaselineError(f"{path}: entry {e!r} is not an object")
        for k in _REQUIRED:
            v = e.get(k)
            if v is None or (isinstance(v, str) and not v.strip()):
                raise BaselineError(
                    f"{path}: entry {e.get('id', e)!r} missing {k!r} "
                    "(every gated number needs an explicit band, "
                    "direction, kind and a reason)")
        if e["direction"] not in _DIRECTIONS:
            raise BaselineError(
                f"{path}: entry {e['id']!r} direction must be one of "
                f"{_DIRECTIONS}")
        if e["kind"] not in _KINDS:
            raise BaselineError(
                f"{path}: entry {e['id']!r} kind must be one of {_KINDS}")
        if not isinstance(e["band"], (int, float)) \
                or isinstance(e["band"], bool) or e["band"] <= 0:
            raise BaselineError(
                f"{path}: entry {e['id']!r} band must be a positive "
                "fraction")
        if not isinstance(e["value"], (int, float)) \
                or isinstance(e["value"], bool) or e["value"] == 0:
            raise BaselineError(
                f"{path}: entry {e['id']!r} value must be a nonzero "
                "number (deltas are fractions OF the reference)")
        if e["id"] in seen:
            raise BaselineError(f"{path}: duplicate entry id {e['id']!r}")
        seen.add(e["id"])
    return base


def load_baseline(path: str) -> dict:
    try:
        with open(path) as f:
            base = json.load(f)
    except OSError as e:
        raise BaselineError(f"{path}: unreadable baseline ({e})")
    except ValueError as e:
        raise BaselineError(f"{path}: invalid JSON ({e})")
    return validate_baseline(base, path)


def load_run(path: str) -> dict:
    """A bench results JSON: either the one-line stdout object or a
    BENCH_rNN.json driver wrapper holding it under 'parsed'."""
    with open(path) as f:
        run = json.load(f)
    if isinstance(run, dict) and "sections" not in run \
            and isinstance(run.get("parsed"), dict):
        run = run["parsed"]
    if not isinstance(run, dict) or not isinstance(
            run.get("sections"), dict):
        raise ValueError(f"{path}: not a bench results JSON "
                         "(no 'sections' object)")
    return run


# -- extraction ---------------------------------------------------------------


def run_fingerprint(run: dict) -> dict:
    """Run-level env fingerprint, falling back to any section's copy —
    a mid-run-crash partial JSON has no top level. Sections recorded
    before jax initialized carry a ``platform: "uninitialized"`` stub;
    a later section's real fingerprint wins over it, so partial
    artifacts from the r05 crash class stay comparable. Pre-fingerprint
    runs return {} and match only an empty baseline fingerprint."""
    fp = run.get("env_fingerprint")
    if isinstance(fp, dict) and fp \
            and fp.get("platform") != "uninitialized":
        return fp
    stub = fp if isinstance(fp, dict) else None
    for sec in (run.get("sections") or {}).values():
        fp = sec.get("env_fingerprint") if isinstance(sec, dict) else None
        if isinstance(fp, dict) and fp:
            if fp.get("platform") != "uninitialized":
                return fp
            stub = stub or fp
    return stub or {}


def fingerprint_mismatches(base_fp: dict, fp: dict) -> list[str]:
    """Keys the baseline fingerprint names whose run value differs.
    The baseline may name a SUBSET (e.g. only platform+dtype) so that
    e.g. a jax patch bump doesn't orphan every reference number — but
    every key it does name must match exactly."""
    return [f"{k}: baseline={base_fp[k]!r} run={fp.get(k)!r}"
            for k in sorted(base_fp) if fp.get(k) != base_fp[k]]


def extract_metric(run: dict, entry: dict):
    """Resolve entry['metric'] as a dotted path inside the section's
    results dict. Returns (value, section_entry) — value None when the
    section or metric is absent."""
    sec = (run.get("sections") or {}).get(entry["section"])
    if not isinstance(sec, dict):
        return None, None
    node = sec
    for part in str(entry["metric"]).split("."):
        if not isinstance(node, dict) or part not in node:
            return None, sec
        node = node[part]
    if not isinstance(node, (int, float)) or isinstance(node, bool):
        return None, sec
    return float(node), sec


def _noise(sec: dict | None) -> dict:
    """The section's retry/noise telemetry, attached to every verdict
    entry so a regression report shows how hard the rig fought back."""
    if not isinstance(sec, dict):
        return {}
    out = {}
    for k in ("wall_ms", "device_ms", "host_ms", "transient_retries",
              "attempts_used", "attempt_wall_ms", "rc", "error"):
        if k in sec:
            out[k] = sec[k]
    return out


# -- comparison ---------------------------------------------------------------


def compare(run: dict, baseline: dict, *, runs: list[str] | None = None,
            baseline_path: str | None = None) -> dict:
    """-> verdict dict. ``verdict['ok']`` is the gate; ``refused`` set
    (and ok False) when the fingerprints are incomparable."""
    fp = run_fingerprint(run)
    verdict = {
        "ok": True,
        "refused": None,
        "fingerprint": fp,
        "baseline_path": baseline_path,
        "runs": runs or [],
        "generated_at": time.time(),
        "checked": 0, "passed": 0, "regressions": 0, "stale": 0,
        "missing": 0,
        "entries": [],
    }
    mism = fingerprint_mismatches(baseline.get("fingerprint", {}), fp)
    if mism:
        verdict["ok"] = False
        verdict["refused"] = {
            "reason": "env_fingerprint mismatch — runs are only ever "
                      "compared like-for-like",
            "mismatched": mism,
            "baseline_fingerprint": baseline.get("fingerprint", {}),
            "run_fingerprint": fp,
        }
        return verdict
    for e in baseline["entries"]:
        value, sec = extract_metric(run, e)
        row = {
            "id": e["id"], "section": e["section"], "metric": e["metric"],
            "kind": e["kind"], "unit": e.get("unit", ""),
            "direction": e["direction"], "band": float(e["band"]),
            "baseline": float(e["value"]), "value": value,
            "reason": e["reason"], "noise": _noise(sec),
        }
        verdict["checked"] += 1
        if value is None:
            row["status"] = "missing"
            row["gate_reason"] = (
                "gated metric absent from the run — the section "
                + ("failed: " + str(sec.get("error"))
                   if isinstance(sec, dict) and sec.get("error")
                   else "was skipped or its shape changed")
                + "; a gate that cannot read its number cannot pass")
            verdict["missing"] += 1
            verdict["ok"] = False
        else:
            base_v = float(e["value"])
            # normalized so positive = regressing direction
            if e["direction"] == "lower":
                delta = (value - base_v) / base_v
            else:
                delta = (base_v - value) / base_v
            row["delta_frac"] = round(delta, 4)
            if delta > row["band"]:
                row["status"] = "regression"
                row["gate_reason"] = (
                    f"{e['metric']} regressed "
                    f"{abs(delta) * 100:.1f}% beyond the ±"
                    f"{row['band'] * 100:.0f}% band — {e['reason']}")
                verdict["regressions"] += 1
                verdict["ok"] = False
            elif delta < -row["band"]:
                row["status"] = "stale"
                row["gate_reason"] = (
                    f"{e['metric']} improved "
                    f"{abs(delta) * 100:.1f}% beyond the ±"
                    f"{row['band'] * 100:.0f}% band — the baseline no "
                    "longer describes the system; adopt the new level "
                    "with --update-baseline (median of BENCH_REPEATS "
                    "runs) or explain the anomaly")
                verdict["stale"] += 1
                verdict["ok"] = False
            else:
                row["status"] = "pass"
                verdict["passed"] += 1
        verdict["entries"].append(row)
    return verdict


# -- update-baseline ----------------------------------------------------------


def update_baseline(runs: list[dict], baseline: dict, *,
                    allow_fingerprint_change: bool = False,
                    ) -> tuple[dict, list[str]]:
    """New baseline with each entry's value replaced by the per-metric
    median across ``runs``; bands/directions/kinds/reasons untouched.
    Returns (new_baseline, warnings). All runs must agree on the keys
    the CURRENT baseline fingerprint names (no mixing rigs into one
    median), AND must match the current baseline on those keys unless
    ``allow_fingerprint_change`` — the compare path REFUSES cross-rig
    comparisons, so the destructive write path must not silently accept
    one wrong-rig run overwriting every reference number. The new
    baseline adopts the first run's values for those same keys."""
    if not runs:
        raise ValueError("update-baseline needs at least one run")
    fps = [run_fingerprint(r) for r in runs]
    named = sorted(baseline.get("fingerprint", {})) or sorted(fps[0])
    for fp in fps[1:]:
        diff = [k for k in named if fp.get(k) != fps[0].get(k)]
        if diff:
            raise BaselineError(
                "update-baseline runs disagree on fingerprint keys "
                f"{diff} — medians across different rigs are fiction")
    mism = fingerprint_mismatches(baseline.get("fingerprint", {}), fps[0])
    if mism and not allow_fingerprint_change:
        raise BaselineError(
            "update-baseline runs come from a different rig than the "
            "current baseline (" + "; ".join(mism) + ") — pass "
            "--allow-fingerprint-change to migrate the baseline to the "
            "new rig on purpose")
    warnings: list[str] = []
    out = {k: v for k, v in baseline.items() if k != "entries"}
    out["fingerprint"] = {k: fps[0].get(k) for k in named}
    entries = []
    for e in baseline["entries"]:
        vals = [v for v, _ in (extract_metric(r, e) for r in runs)
                if v is not None]
        e = dict(e)
        if vals:
            e["value"] = round(statistics.median(vals), 4)
        else:
            warnings.append(
                f"{e['id']}: metric absent from every given run — "
                "reference value left unchanged (fix the section or "
                "delete the entry)")
        entries.append(e)
    out["entries"] = entries
    return out, warnings


# -- kernel explain (ISSUE 17) ------------------------------------------------


def load_capture_file(path: str) -> dict:
    """A kernelscope capture JSON (the ``/v1/debug/profile`` record
    shape: ``kernels`` ranked by ``device_ms`` + ``total_device_ms``)."""
    with open(path) as f:
        cap = json.load(f)
    if not isinstance(cap, dict) or not isinstance(
            cap.get("kernels"), list):
        raise ValueError(f"{path}: not a kernelscope capture JSON "
                         "(no 'kernels' list)")
    return cap


def attach_kernel_explain(verdict: dict, captures: list[dict],
                          paths: list[str] | None = None) -> dict:
    """Fold per-kernel device-ms evidence into a gate verdict: with two
    or more captures, the FIRST is the reference and the LAST the
    current run — per-kernel deltas ranked by absolute movement say
    WHICH compiled kernel a wall-level regression lives in. One capture
    attaches its ranking alone (no deltas). Mutates and returns
    ``verdict``."""
    if not captures:
        return verdict
    before, after = captures[0], captures[-1]

    def _ms(cap: dict) -> dict:
        return {str(k.get("kernel")): float(k.get("device_ms") or 0.0)
                for k in cap.get("kernels", ()) if isinstance(k, dict)}

    after_ms = _ms(after)
    explain = {
        "captures": [c.get("id") for c in captures],
        "paths": list(paths or []),
        "total_device_ms": after.get("total_device_ms"),
    }
    if len(captures) >= 2:
        before_ms = _ms(before)
        rows = []
        for name in sorted(set(before_ms) | set(after_ms)):
            b, a = before_ms.get(name, 0.0), after_ms.get(name, 0.0)
            row = {"kernel": name, "before_ms": round(b, 3),
                   "after_ms": round(a, 3),
                   "delta_ms": round(a - b, 3)}
            if b > 0:
                row["delta_frac"] = round((a - b) / b, 4)
            rows.append(row)
        rows.sort(key=lambda r: -abs(r["delta_ms"]))
        explain["total_device_ms_before"] = before.get("total_device_ms")
        explain["kernels"] = rows
    else:
        explain["kernels"] = [
            {"kernel": k.get("kernel"), "after_ms": k.get("device_ms")}
            for k in after.get("kernels", ()) if isinstance(k, dict)]
    verdict["kernel_explain"] = explain
    return verdict


# -- verdict artifact ---------------------------------------------------------


def _atomic_write_json(path: str, obj: dict) -> None:
    """tmp + os.replace so a crash mid-write never leaves a truncated
    artifact (shared by the verdict and the baseline rewrite)."""
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=2)
        f.write("\n")
    os.replace(tmp, path)


def write_verdict(verdict: dict, path: str) -> None:
    """Persist the gate verdict where the serving process can find it
    (runtime/perfgate.py loads it for GET /v1/debug/perf and the
    weaviate_tpu_bench_* gauges)."""
    _atomic_write_json(path, verdict)


# -- report -------------------------------------------------------------------


def _fmt_value(v, unit: str) -> str:
    if v is None:
        return "—"
    s = f"{v:,.3f}".rstrip("0").rstrip(".")
    return f"{s} {unit}".strip()


def render(verdict: dict, out=None) -> None:
    out = out or sys.stdout
    p = lambda *a: print(*a, file=out)  # noqa: E731
    if verdict.get("refused"):
        r = verdict["refused"]
        p("benchkeeper: REFUSED —", r["reason"])
        for m in r["mismatched"]:
            p(f"  fingerprint {m}")
        return
    tags = {"pass": "pass", "regression": "FAIL regression",
            "stale": "STALE improvement", "missing": "FAIL missing"}
    for row in verdict["entries"]:
        kind = "device-timed" if row["kind"] == "device" else "wall-timed"
        head = (f"  [{tags[row['status']]}] {row['id']} ({kind}, band ±"
                f"{row['band'] * 100:.0f}%): "
                f"{_fmt_value(row['value'], row['unit'])} vs baseline "
                f"{_fmt_value(row['baseline'], row['unit'])}")
        if row.get("delta_frac") is not None:
            head += f" (delta {row['delta_frac'] * +100:+.1f}%)"
        p(head)
        if row["status"] != "pass":
            p(f"      {row.get('gate_reason', row['reason'])}")
            n = row.get("noise") or {}
            if n:
                bits = []
                if "wall_ms" in n:
                    bits.append(f"wall {n['wall_ms']:.0f}ms")
                if "device_ms" in n:
                    bits.append(f"device {n['device_ms']:.0f}ms")
                if "host_ms" in n:
                    bits.append(f"host {n['host_ms']:.0f}ms")
                for k in ("transient_retries", "attempts_used"):
                    if k in n:
                        bits.append(f"{k}={n[k]}")
                if "attempt_wall_ms" in n:
                    bits.append(f"attempt_wall_ms={n['attempt_wall_ms']}")
                if "error" in n:
                    bits.append(f"error={n['error']}")
                p("      section noise: " + ", ".join(bits))
    ke = verdict.get("kernel_explain")
    if ke:
        n = len(ke.get("captures") or ())
        p(f"  kernel explain ({n} capture{'' if n == 1 else 's'}, total "
          f"{_fmt_value(ke.get('total_device_ms'), 'ms')} device):")
        for row in (ke.get("kernels") or ())[:8]:
            if "delta_ms" in row:
                line = (f"    {row['kernel']}: "
                        f"{_fmt_value(row['before_ms'], 'ms')} -> "
                        f"{_fmt_value(row['after_ms'], 'ms')} "
                        f"(delta {row['delta_ms']:+.3f} ms")
                if row.get("delta_frac") is not None:
                    line += f", {row['delta_frac'] * 100:+.1f}%"
                p(line + ")")
            else:
                p(f"    {row['kernel']}: "
                  f"{_fmt_value(row.get('after_ms'), 'ms')}")
    p(f"benchkeeper: {verdict['checked']} checked, "
      f"{verdict['passed']} passed, {verdict['regressions']} regressions, "
      f"{verdict['stale']} stale, {verdict['missing']} missing -> "
      + ("GATE PASS" if verdict["ok"] else "GATE FAIL"))


# -- CLI ----------------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchkeeper",
        description="Perf-regression gate over bench.py results: "
                    "device-attributed metrics vs a reasoned, "
                    "fingerprint-scoped baseline with tolerance bands.")
    ap.add_argument("runs", nargs="*",
                    help="bench results JSON (one to gate; several with "
                         "--update-baseline for a median)")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default tools/benchkeeper/"
                         "baseline.json)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite baseline reference values to the "
                         "per-metric median across the given runs")
    ap.add_argument("--allow-fingerprint-change", action="store_true",
                    help="with --update-baseline: permit the runs' env "
                         "fingerprint to differ from the current "
                         "baseline's (intentional rig migration)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="print the verdict as JSON instead of text")
    ap.add_argument("--verdict-path", default=None,
                    help="where to persist the gate verdict for "
                         "/v1/debug/perf (default BENCHKEEPER_VERDICT_"
                         "PATH or tools/benchkeeper/last_verdict.json; "
                         "'-' disables)")
    ap.add_argument("--explain", nargs="+", metavar="CAPTURE",
                    default=None,
                    help="kernelscope capture JSONs (GET /v1/debug/"
                         "profile?ms=N records) to attach to the "
                         "verdict: with two+, per-kernel device-ms "
                         "deltas (first=reference, last=current) say "
                         "which compiled kernel a regression lives in")
    ap.add_argument("--smoke", action="store_true",
                    help="self-test the gate machinery end-to-end on a "
                         "tiny CPU bench run (parsing, band math, stale "
                         "detection, fingerprint refusal, exit codes)")
    ap.add_argument("--synthetic", action="store_true",
                    help="with --smoke: use a canned synthetic run "
                         "instead of invoking bench.py (fast, hermetic)")
    args = ap.parse_args(argv)

    if args.smoke:
        from tools.benchkeeper.smoke import run_smoke

        return run_smoke(bench=not args.synthetic)

    baseline_path = args.baseline or default_baseline_path()
    try:
        baseline = load_baseline(baseline_path)
        runs = [load_run(p) for p in args.runs]
    except (BaselineError, ValueError, OSError) as e:
        print(f"benchkeeper: error: {e}", file=sys.stderr)
        return EXIT_REFUSED
    if not runs:
        print("benchkeeper: error: give at least one bench results JSON "
              "(or --smoke)", file=sys.stderr)
        return EXIT_REFUSED

    if args.update_baseline:
        try:
            new_base, warnings = update_baseline(
                runs, baseline,
                allow_fingerprint_change=args.allow_fingerprint_change)
            # re-validate BEFORE touching the checked-in file: a median
            # that rounds to 0.0 would otherwise write a baseline every
            # future load rejects
            validate_baseline(new_base, baseline_path)
        except (BaselineError, ValueError) as e:
            print(f"benchkeeper: error: {e}", file=sys.stderr)
            return EXIT_REFUSED
        # insertion order preserved on purpose: the rewrite's diff must
        # show only the value/fingerprint changes, not a key reshuffle
        _atomic_write_json(baseline_path, new_base)
        for w in warnings:
            print(f"benchkeeper: warning: {w}", file=sys.stderr)
        print(f"benchkeeper: baseline rewritten from {len(runs)} run"
              f"{'' if len(runs) == 1 else 's'} (per-metric median) -> "
              f"{baseline_path}")
        return EXIT_OK

    if len(runs) > 1:
        print("benchkeeper: error: gate one run at a time (multiple "
              "runs are for --update-baseline medians)", file=sys.stderr)
        return EXIT_REFUSED
    verdict = compare(runs[0], baseline, runs=list(args.runs),
                      baseline_path=baseline_path)
    if args.explain:
        try:
            captures = [load_capture_file(p) for p in args.explain]
        except (OSError, ValueError) as e:
            print(f"benchkeeper: error: {e}", file=sys.stderr)
            return EXIT_REFUSED
        attach_kernel_explain(verdict, captures, paths=list(args.explain))
    vp = args.verdict_path or default_verdict_path()
    # a REFUSED comparison is noise, not signal — it must not clobber
    # the last real verdict (and read as a gate failure on the
    # /v1/debug/perf + gauge surface)
    if vp != "-" and not verdict.get("refused"):
        try:
            write_verdict(verdict, vp)
        except OSError as e:
            print(f"benchkeeper: warning: could not persist verdict "
                  f"({e})", file=sys.stderr)
    if args.as_json:
        print(json.dumps(verdict, indent=2))
    else:
        render(verdict)
    if verdict.get("refused"):
        return EXIT_REFUSED
    return EXIT_OK if verdict["ok"] else EXIT_GATE_FAIL


if __name__ == "__main__":
    raise SystemExit(main())
