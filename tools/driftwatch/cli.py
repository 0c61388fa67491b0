"""driftwatch CLI — replay a history ring against a baseline file.

Each history record already carries the raw live-telemetry section
(kernelscope residency EWMAs, memcpy estimator, per-cycle counters) and
the environment fingerprint it was measured under, so classification is
exactly what the runtime did: rebuild the synthetic one-section run and
hand it to ``weaviate_tpu.runtime.bands.compare`` — same band math, same
verdict statuses, same cross-fingerprint refusal. Canary records are
summarized as a recall/residency trend alongside.

Exit codes (``runtime/bands``): 0 = every replayed cycle gates clean,
1 = at least one cycle regressed (or an open canary finding), 2 = usage
or refused comparison.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from weaviate_tpu.runtime import bands


def _load_history(path: str) -> list[dict]:
    """The ring rotates one generation (``history.jsonl.1``) — replay
    reads the rotated tail first so cycles stay chronological."""
    records: list[dict] = []
    for p in (path + ".1", path):
        if not os.path.exists(p):
            continue
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    records.append(json.loads(line))
                except json.JSONDecodeError:
                    continue  # a torn tail from a crash mid-append
    return records


def _cycle_run(rec: dict) -> dict | None:
    """Rebuild the synthetic one-section run the runtime classified."""
    metrics = (rec.get("live") or {}).get("metrics")
    if not metrics:
        return None
    return {"env_fingerprint": rec.get("fingerprint") or {},
            "sections": {"live": metrics}}


def _canary_line(rec: dict) -> str:
    bits = []
    for c in rec.get("canaries", ()):
        key = c.get("key", "?")
        if "skipped" in c:
            bits.append(f"{key}: skipped ({c['skipped']})")
        elif "recall" in c:
            bits.append(f"{key}: recall {c['recall']:.3f} "
                        f"(ref {c.get('ref_recall', 0):.3f}), "
                        f"device {c.get('device_ms', 0):.2f}ms")
    return "; ".join(bits)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="driftwatch",
        description="Replay a driftwatch JSONL history ring offline, "
                    "re-classifying each cycle's live telemetry against "
                    "a baseline file.")
    ap.add_argument("history", nargs="?",
                    help="path to history.jsonl (or a data dir "
                         "containing driftwatch/history.jsonl)")
    ap.add_argument("--baseline",
                    help="baseline file to classify against "
                         "(default: live_baseline.json next to the "
                         "history file — the node's own sealed bands)")
    ap.add_argument("--last", type=int, default=0, metavar="N",
                    help="replay only the last N cycles")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON verdict per cycle instead of "
                         "the rendered report")
    args = ap.parse_args(argv)

    path = args.history or "."
    if os.path.isdir(path):
        nested = os.path.join(path, "driftwatch", "history.jsonl")
        path = nested if os.path.exists(nested) \
            else os.path.join(path, "history.jsonl")
    if not os.path.exists(path) and not os.path.exists(path + ".1"):
        print(f"driftwatch: no history at {path}", file=sys.stderr)
        return bands.EXIT_REFUSED

    baseline_path = args.baseline or os.path.join(
        os.path.dirname(path) or ".", "live_baseline.json")
    try:
        baseline = bands.load_baseline(baseline_path)
    except (bands.BaselineError, OSError) as e:
        print(f"driftwatch: cannot load baseline {baseline_path}: {e}",
              file=sys.stderr)
        return bands.EXIT_REFUSED

    records = _load_history(path)
    if args.last > 0:
        records = records[-args.last:]
    if not records:
        print(f"driftwatch: history at {path} is empty", file=sys.stderr)
        return bands.EXIT_REFUSED

    worst = bands.EXIT_OK
    for rec in records:
        run = _cycle_run(rec)
        head = (f"cycle {rec.get('cycle', '?')} @ {rec.get('t', 0):.0f} "
                f"(recorded gate_ok={rec.get('gate_ok')})")
        canary_open = any(f.get("leg") == "canary"
                          for f in rec.get("findings", ()))
        if run is None:
            if args.json:
                print(json.dumps({"cycle": rec.get("cycle"),
                                  "skipped": "no live metrics"}))
            else:
                print(head + ": no live metrics recorded")
            worst = max(worst, bands.EXIT_GATE_FAIL if canary_open
                        else bands.EXIT_OK)
            continue
        verdict = bands.compare(run, baseline, baseline_path=baseline_path)
        if args.json:
            verdict["cycle"] = rec.get("cycle")
            verdict["canaries"] = rec.get("canaries", [])
            print(json.dumps(verdict))
        else:
            print(head)
            cl = _canary_line(rec)
            if cl:
                print("  canaries: " + cl)
            bands.render(verdict)
        if verdict.get("refused"):
            worst = max(worst, bands.EXIT_REFUSED)
        elif not verdict["ok"] or canary_open:
            worst = max(worst, bands.EXIT_GATE_FAIL)
    return worst


if __name__ == "__main__":
    raise SystemExit(main())
