"""trace_reduce on planes that carry PR 25's ``wtpu.<stage>`` annotations: the
program's own names on the profiler's clock. A file of its own: the accepted
benchmark's files are not edited."""

import copy
import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def planes():
    with open(os.path.join(HERE, "recorded",
                           "v5e_sift_flat_3_dispatches.json")) as f:
        return json.load(f)


def line(planes, plane, name):
    p = next(p for p in planes if p["name"] == plane)
    return next(ln["events"] for ln in p["lines"] if ln["name"] == name)


# -- the program's own names on the profiler's clock (PR 25) ------------------


@pytest.fixture(scope="module")
def annotated():
    """A few ms of a traced run of the filtered mix on a TPU v5 lite with
    PR 25's ``wtpu.<stage>`` annotations on the batcher's worker and the
    drain thread (recorded by PR 25's builder; names cut to 40
    characters, stage annotations that span the cut clipped to it)."""
    with open(os.path.join(HERE, "recorded",
                           "v5e_sift-flat-l2_filtered_wtpu.json")) as f:
        return json.load(f)


def test_idle_gaps_carry_the_programs_stage_names(annotated):
    out = trace_reduce.reduce(annotated)
    assert out["device_planes"] == ["/device:TPU:0"]
    named = {name: s for name, s in out["idle_gaps"]}
    stages = {n: s for n, s in named.items() if n.startswith("wtpu.")}
    assert stages, named
    # the annotations win the gaps: most of the idle time is under them
    idle = out["window_s"] - out["busy_s"]
    assert sum(stages.values()) > 0.5 * idle
    assert sum(named.values()) == pytest.approx(idle, rel=1e-6)
    assert named.get("no host event", 0.0) < 0.05 * idle


def test_annotations_sit_on_two_host_threads_and_never_overlap(annotated):
    """One worker and one drain thread carry every ``wtpu.*`` event, and
    on a thread the events are leaf-level: none overlaps the next."""
    host = [ln for p in annotated if not trace_reduce.DEVICE_PLANE.match(
        p["name"]) for ln in p["lines"]]
    carrying = [ln for ln in host
                if any(e[0].startswith("wtpu.") for e in ln["events"])]
    assert 1 <= len(carrying) <= 2
    for ln in carrying:
        events = sorted((e for e in ln["events"]
                         if e[0].startswith("wtpu.")), key=lambda e: e[1])
        for (_, s0, d0), (_, s1, _d1) in zip(events, events[1:]):
            assert s0 + d0 <= s1 + 1000     # 1 us of rounding at the cut
    names = {e[0] for ln in carrying for e in ln["events"]
             if e[0].startswith("wtpu.")}
    assert "wtpu.launch" in names and "wtpu.slot_wait" not in names


def test_a_gap_is_named_after_an_annotation_laid_over_it(planes):
    """On the older recorded plane (no annotations): lay one stage over
    its longest gap on a thread of its own and the gap takes its name."""
    base = trace_reduce.reduce(planes)
    ops = line(planes, "/device:TPU:0", "XLA Ops")
    merged = trace_reduce.union([(s, s + d) for _, s, d in ops])
    g0, g1 = max(zip((e for _, e in merged), (s for s, _ in merged[1:])),
                 key=lambda g: g[1] - g[0])
    more = copy.deepcopy(planes)
    host = next(p for p in more if p["name"] == "/host:CPU")
    host["lines"].append({"name": "qb-transfer", "events": [
        ["wtpu.rescore", g0 - 10, g1 - g0 + 20]]})
    out = trace_reduce.reduce(more)
    assert dict(out["idle_gaps"])["wtpu.rescore"] >= (g1 - g0) / 1e9
    assert out["busy_s"] == base["busy_s"]
