"""From a profiler trace to the numbers the benchmark reports.

Device planes only (``/device:TPU:n``): the host's threads are in the same
file and are never counted as device time. Busy time is the union of the
intervals of the plane's op line, so nested and overlapping events count
once. Per-program time is the sum of the module line's events by name. The
longest idle gaps are named after the host-plane event that covers most of
each. ``load`` needs JAX (``jax.profiler.ProfileData``); ``reduce`` works
on the plain structure ``load`` returns, which is also how the recorded
trace under tests/recorded is kept:

    [{"name": plane, "lines": [{"name": line, "events": [[name, start_ns,
      duration_ns], ...]}]}]"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
GAPS_NAMED = 200   # only the longest gaps are matched against host events


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [[e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Sorted, merged [start, end) intervals."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def program_name(event_name: str) -> str:
    """``jit_scan(1234567)`` -> ``jit_scan``: the fingerprint changes with
    every shape, the program's name does not."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce(planes: list[dict]) -> dict:
    """-> window_s, busy_s (mean over device planes), programs {name:
    [seconds, executions]}, device_ops and idle_gaps (top 10 each, [name,
    seconds]), device_planes."""
    first = min((e[1] for p in planes for ln in p["lines"]
                 for e in ln["events"]), default=0)
    last = max((e[1] + e[2] for p in planes for ln in p["lines"]
                for e in ln["events"]), default=0)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    host_events = [e for p in planes if not DEVICE_PLANE.match(p["name"])
                   for ln in p["lines"] for e in ln["events"]]
    busy_ns = []
    programs: dict[str, list[float]] = {}
    ops: dict[str, float] = {}
    gaps: list[tuple[int, int]] = []
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        op_events = lines.get(OPS_LINE, [])
        merged = union([(s, s + d) for _, s, d in op_events])
        busy_ns.append(sum(e - s for s, e in merged))
        edges = [first] + [t for iv in merged for t in iv] + [last]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, _, dur in op_events:
            ops[name] = ops.get(name, 0.0) + dur / 1e9
        for name, _, dur in lines.get(MODULES_LINE, []):
            entry = programs.setdefault(program_name(name), [0.0, 0])
            entry[0] += dur / 1e9
            entry[1] += 1
    by_host: dict[str, float] = {}
    names = sorted({e[0] for e in host_events})
    code = {n: i for i, n in enumerate(names)}
    h_name = np.array([code[e[0]] for e in host_events], dtype=np.int64)
    h_start = np.array([e[1] for e in host_events], dtype=np.int64)
    h_end = h_start + np.array([e[2] for e in host_events], dtype=np.int64)
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:GAPS_NAMED]:
        name = "no host event"
        if names:
            overlap = np.minimum(h_end, g1) - np.maximum(h_start, g0)
            cover = np.bincount(h_name, np.maximum(overlap, 0),
                                minlength=len(names))
            if cover.max() > 0:
                name = names[int(cover.argmax())]
        by_host[name] = by_host.get(name, 0.0) + (g1 - g0) / 1e9
    return {
        "window_s": (last - first) / 1e9,
        "busy_s": sum(busy_ns) / 1e9 / len(devices) if devices else 0.0,
        "device_planes": [p["name"] for p in devices],
        "programs": programs,
        "device_ops": _top(ops),
        "idle_gaps": _top(by_host),
    }


def _top(table: dict[str, float]) -> list[list]:
    return [[name[:120], seconds] for name, seconds in
            sorted(table.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce_file(path: str) -> dict:
    out = reduce(load(path))
    out["xplane"] = path
    return out
