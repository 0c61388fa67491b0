"""The scan dispatches' share of their roofline over the traced window.

Device time: the summed module-line time of the programs whose names match
the configuration's ``scan_programs`` (trace_reduce.py). Least time: for
exactly those executions, ``kernel_costs.scan_cost`` at the shapes
dispatched (the store's resident arrays as serve.py describes them; the
padded batch sizes in the proportion the batcher's ``compile_bucket_total``
moved while the trace ran) against ``peaks.json``. A share over 100 % is a
fault of the count and fails the run."""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import kernel_costs  # noqa: E402

BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"


def read(ctx):
    trace, marks = ctx["trace"], ctx["trace_marks"]
    patterns = ctx["config"].get("scan_programs")
    if not trace or not patterns or "after" not in marks:
        return None
    if ctx["mix"].get("filter") is not None:
        # a highly selective filter is dispatched solo over gathered rows:
        # the same program name at another shape, which the trace cannot
        # tell apart yet (PERF.md, for the tracing issue): nothing sound
        return None
    hit = [(sec, n) for name, (sec, n) in trace["programs"].items()
           if any(re.search(p, name) for p in patterns)]
    device_s = sum(sec for sec, _ in hit)
    executions = sum(n for _, n in hit)
    if device_s <= 0:
        return None
    moved = {b: marks["after"].by_label(BUCKETS, "b").get(b, 0.0) - v0
             for b, v0 in marks["before"].by_label(BUCKETS, "b").items()}
    moved = {int(b): n for b, n in moved.items() if n > 0}
    if not moved:
        return None
    peak = kernel_costs.peaks(ctx["device"]["kind"])
    total = sum(moved.values())
    least = 0.0
    bound_by = {}
    for b, n in moved.items():
        seconds, by = kernel_costs.least_seconds(
            kernel_costs.scan_cost(ctx["store"], b, ctx["k"]), peak)
        least += executions * (n / total) * seconds
        bound_by[by] = bound_by.get(by, 0) + n
    share = 100.0 * least / device_s
    print(json.dumps({"scan_roofline": {
        "device_s": device_s, "executions": executions, "least_s": least,
        "bound_by": bound_by, "dispatches_by_padded_batch": moved}}),
        flush=True)
    if share > 100.0:
        raise RuntimeError(f"scan_roofline_pct {share:.1f} % is over 100: "
                           f"the operations or bytes are counted too high")
    return share
