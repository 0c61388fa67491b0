"""The scalar-quantized scan dispatches' share of their roofline over the
traced window: ``pq_scan_roofline_pct.py``'s reading (summed module-line
time of the configuration's ``scan_programs`` against the least time of
exactly those executions, padded batch sizes in the proportion
``compile_bucket_total`` moved while the trace ran), with the operations
and bytes of an 8-bit SQ scan (``kernel_costs_sq.py``). A share over 100 %
is a fault of the count and fails the run. None where the store holds no
one-byte-a-dimension codes (a parent that drops ``sq`` and serves float32
rows, a class still under its trainingLimit)."""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import kernel_costs  # noqa: E402
import kernel_costs_sq  # noqa: E402

BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"


def read(ctx):
    trace, marks = ctx["trace"], ctx["trace_marks"]
    patterns = ctx["config"].get("scan_programs")
    sq = ctx["config"]["class"].get("vectorIndexConfig", {}).get("sq") or {}
    codes = ctx["store"]["arrays"].get("codes")
    if (not trace or not patterns or "after" not in marks
            or not sq.get("enabled") or codes is None
            or codes["dtype"] != "int8"
            or codes["shape"][1] != ctx["config"]["dim"]
            or ctx["mix"].get("filter") is not None):
        return None
    hit = [(sec, n) for name, (sec, n) in trace["programs"].items()
           if any(re.search(p, name) for p in patterns)]
    device_s = sum(sec for sec, _ in hit)
    executions = sum(n for _, n in hit)
    moved = {b: marks["after"].by_label(BUCKETS, "b").get(b, 0.0) - v0
             for b, v0 in marks["before"].by_label(BUCKETS, "b").items()}
    moved = {int(b): n for b, n in moved.items() if n > 0}
    if device_s <= 0 or not moved:
        return None
    peak = kernel_costs.peaks(ctx["device"]["kind"])
    total = sum(moved.values())
    least = 0.0
    bound_by = {}
    for b, n in moved.items():
        seconds, by = kernel_costs.least_seconds(
            kernel_costs_sq.scan_cost(ctx["store"], b, ctx["k"]), peak)
        least += executions * (n / total) * seconds
        bound_by[by] = bound_by.get(by, 0) + n
    share = 100.0 * least / device_s
    print(json.dumps({"sq_scan_roofline": {
        "device_s": device_s, "executions": executions, "least_s": least,
        "bound_by": bound_by, "dispatches_by_padded_batch": moved}}),
        flush=True)
    if share > 100.0:
        raise RuntimeError(f"sq_scan_roofline_pct {share:.1f} % is over "
                           f"100: the operations or bytes are counted too "
                           f"high")
    return share
