"""Band math: a run's numbers against a reasoned, fingerprint-scoped baseline.

Driftwatch's live-telemetry leg (``runtime/driftwatch.py``) and its
offline replay (``python -m tools.driftwatch``) classify one run with
this module, so a node and the replay of its history cannot disagree.

A *run* is ``{"env_fingerprint": {...}, "sections": {name: {...}}}``.
A *baseline* is a JSON file (the format of
``<data_dir>/driftwatch/live_baseline.json`` and of whatever
``WEAVIATE_TPU_DRIFT_BASELINE`` names)::

    {"fingerprint": {"platform": "tpu", ...},
     "entries": [{"id", "section", "metric", "value", "band",
                  "direction", "kind", "reason", "unit"?}, ...]}

- every entry carries a MANDATORY non-empty ``reason``: a number nobody
  can explain gates nothing;
- the baseline names the environment its numbers were measured in (any
  subset of the run's fingerprint keys), and a run that differs on any
  named key is REFUSED, never compared: a CPU run "regressing" a TPU
  baseline is noise, not signal;
- ``metric`` is a dotted path inside the section; ``delta_frac`` is
  normalized so positive = the regressing direction. Beyond the band
  that way the entry is a ``regression``; beyond it the other way it is
  ``stale`` (the reference no longer describes the system, so it gates
  nothing); an unreadable metric is ``missing``. Any of the three makes
  the verdict not ``ok``. ``kind`` says what clock the number is on
  (``device`` tight bands, ``wall`` wide ones).

Exit codes of a tool that gates on a verdict: 0 passed, 1 failed
(regression / stale / missing), 2 refused (fingerprint mismatch,
invalid baseline, unreadable input).
"""

from __future__ import annotations

import json
import os
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


# -- extraction ---------------------------------------------------------------


def run_fingerprint(run: dict) -> dict:
    """The environment the run was measured in. A run that names none
    returns {} and matches only an empty baseline fingerprint."""
    fp = run.get("env_fingerprint")
    return fp if isinstance(fp, dict) else {}


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


def compare(run: dict, baseline: dict, *,
            baseline_path: str | None = None) -> dict:
    """-> verdict dict. ``verdict['ok']`` is the gate; ``refused`` set
    (and ok False) when the fingerprints are incomparable."""
    fp = run_fingerprint(run)
    verdict = {
        "ok": True,
        "refused": None,
        "fingerprint": fp,
        "baseline_path": baseline_path,
        "runs": [],
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
                    "longer describes the system; seal a new baseline "
                    "at the new level or explain the anomaly")
                verdict["stale"] += 1
                verdict["ok"] = False
            else:
                row["status"] = "pass"
                verdict["passed"] += 1
        verdict["entries"].append(row)
    return verdict


# -- baseline file ------------------------------------------------------------


def _atomic_write_json(path: str, obj: dict) -> None:
    """tmp + os.replace so a crash mid-write never leaves a truncated
    baseline."""
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=2)
        f.write("\n")
    os.replace(tmp, path)


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
        p("driftwatch: REFUSED —", r["reason"])
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
    p(f"driftwatch: {verdict['checked']} checked, "
      f"{verdict['passed']} passed, {verdict['regressions']} regressions, "
      f"{verdict['stale']} stale, {verdict['missing']} missing -> "
      + ("GATE PASS" if verdict["ok"] else "GATE FAIL"))
