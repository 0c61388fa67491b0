"""trace_reduce on a small recorded trace (7 ms of `sift-flat-l2.c32` on a
TPU v5 lite, PR 24's first traced run: three dispatches of
``jit_chunked_topk_distances``, names cut to 40 characters), and the peaks
and kernel costs the roofline share is made from."""

import copy
import json
import os

import numpy as np
import pytest

import kernel_costs
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


@pytest.mark.parametrize("intervals,merged", [
    ([(0, 10), (20, 30)], [(0, 10), (20, 30)]),
    ([(0, 10), (5, 30)], [(0, 30)]),
    ([(0, 100), (5, 30), (40, 50)], [(0, 100)]),
    ([(20, 30), (0, 10), (10, 20)], [(0, 30)]),
    ([], []),
])
def test_union(intervals, merged):
    assert trace_reduce.union(intervals) == merged


def test_busy_is_the_union_of_the_device_op_line(planes):
    out = trace_reduce.reduce(planes)
    assert out["device_planes"] == ["/device:TPU:0"]
    ops = line(planes, "/device:TPU:0", "XLA Ops")
    span = max(s + d for _, s, d in ops) + 1
    busy = np.zeros(span, bool)            # one cell per nanosecond
    for _, start, dur in ops:
        busy[start:start + dur] = True
    assert out["busy_s"] == pytest.approx(busy.sum() / 1e9, rel=1e-9)
    # nested ops (a while loop and its body) must count once
    assert out["busy_s"] < sum(d for _, _, d in ops) / 1e9
    assert 0 < out["busy_s"] < out["window_s"]


def test_program_time_is_the_module_line_by_name(planes):
    out = trace_reduce.reduce(planes)
    mods = line(planes, "/device:TPU:0", "XLA Modules")
    seconds, executions = out["programs"]["jit_chunked_topk_distances"]
    assert executions == 3
    assert seconds == pytest.approx(sum(d for _, _, d in mods) / 1e9)
    assert list(out["programs"]) == ["jit_chunked_topk_distances"]


def test_host_threads_are_never_device_time(planes):
    base = trace_reduce.reduce(planes)
    more = copy.deepcopy(planes)
    host = next(p for p in more if p["name"] == "/host:CPU")
    host["lines"].append({"name": "python3", "events": [
        ["XLA Ops lookalike", 0, int(base["window_s"] * 1e9)]]})
    out = trace_reduce.reduce(more)
    assert out["busy_s"] == base["busy_s"]
    assert out["programs"] == base["programs"]


def test_idle_gaps_are_named_after_host_events_and_sum_to_idle(planes):
    out = trace_reduce.reduce(planes)
    assert 1 <= len(out["idle_gaps"]) <= 10
    host_names = {e[0] for p in planes if p["name"] == "/host:CPU"
                  for ln in p["lines"] for e in ln["events"]}
    assert all(name in host_names or name == "no host event"
               for name, _ in out["idle_gaps"])
    idle = out["window_s"] - out["busy_s"]
    assert sum(s for _, s in out["idle_gaps"]) == pytest.approx(idle,
                                                                rel=1e-6)
    assert len(out["device_ops"]) == 10


def test_a_trace_without_a_device_plane_has_no_busy_time(planes):
    out = trace_reduce.reduce([p for p in planes
                               if p["name"] == "/host:CPU"])
    assert out["device_planes"] == [] and out["busy_s"] == 0.0


def test_peaks_known_and_unknown():
    v5e = kernel_costs.peaks("TPU v5 lite")
    assert v5e == {"bf16_flops": 197e12, "int8_ops": 393e12,
                   "hbm_bytes_per_s": 819e9}
    for kind in ("TPU v9", "cpu", "source"):
        with pytest.raises(KeyError):
            kernel_costs.peaks(kind)


def test_scan_costs_from_shapes():
    peak = kernel_costs.peaks("TPU v5 lite")
    flat = {"arrays": {"vectors": {"shape": [262144, 128],
                                   "dtype": "float32"}}}
    cost = kernel_costs.scan_cost(flat, 8, 16)
    assert cost["flops"] == 2 * 8 * 262144 * 128
    assert cost["bytes"] == 262144 * 128 * 4 + 8 * 128 * 4 + 8 * 16 * 8
    seconds, by = kernel_costs.least_seconds(cost, peak)
    assert by == "bytes" and seconds == pytest.approx(cost["bytes"] / 819e9)
    bq = {"arrays": {"codes": {"shape": [131072, 24], "dtype": "uint32"}},
          "rescore_limit": 16}
    cost = kernel_costs.scan_cost(bq, 4, 16)
    assert cost["int_ops"] == 4 * 131072 * 768
    assert cost["bytes"] == 131072 * 96 + 4 * 96 + 4 * 16 * 8
    with_rows = dict(bq, arrays=dict(bq["arrays"], rescore_rows={
        "shape": [131072, 768], "dtype": "bfloat16"}))
    more = kernel_costs.scan_cost(with_rows, 4, 16)
    assert more["bytes"] == cost["bytes"] + 4 * 256 * 768 * 2
    with pytest.raises(ValueError):
        kernel_costs.scan_cost({"arrays": {}}, 1, 16)
