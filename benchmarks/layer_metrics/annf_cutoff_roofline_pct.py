"""The exact route's programs' share of their roofline over the traced
window: the least time of the traced executions of the configuration's
``cutoff_programs`` (``kernel_costs_ivf_filtered.cutoff_cost``: the allowed
live rows read once at their stored width, 2 x block x rows x dim
operations; ``kernel_costs.py``'s peaks) over their summed module-line
time.

How many allowed rows a program gathered comes from the server's own
counters over the traced stretch: ``weaviate_tpu_ivf_cutoff_rows_total``
over ``weaviate_tpu_ivf_cutoff_programs_total`` (rows a program, one
program a distinct mask a dispatch), the block a program scores from
``compile_bucket_total`` (the padded batch of the dispatches that moved).
The cost is linear in both, so the means cost what the programs cost. A
share over 100 % is a fault of the count and fails the run. None where the
program has no such counters (the parent), no posting lists, or the route
did not run in the trace."""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import kernel_costs  # noqa: E402
import kernel_costs_ivf_filtered  # noqa: E402

BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"
ROWS = "weaviate_tpu_ivf_cutoff_rows_total"
PROGRAMS = "weaviate_tpu_ivf_cutoff_programs_total"


def read(ctx):
    trace, marks = ctx["trace"], ctx["trace_marks"]
    patterns = ctx["config"].get("cutoff_programs")
    if (not trace or not patterns or "after" not in marks
            or "list_vecs" not in ctx["store"]["arrays"]):
        return None
    before, after = marks["before"], marks["after"]
    rows, programs = (after.total(s) - before.total(s)
                      for s in (ROWS, PROGRAMS))
    hit = [(sec, n) for name, (sec, n) in trace["programs"].items()
           if any(re.search(p, name) for p in patterns)]
    device_s = sum(sec for sec, _ in hit)
    executions = sum(n for _, n in hit)
    moved = {b: after.by_label(BUCKETS, "b").get(b, 0.0) - v0
             for b, v0 in before.by_label(BUCKETS, "b").items()}
    moved = {int(b): n for b, n in moved.items() if n > 0}
    if device_s <= 0 or programs <= 0 or rows <= 0 or not moved:
        return None
    block = sum(b * n for b, n in moved.items()) / sum(moved.values())
    seconds, by = kernel_costs.least_seconds(
        kernel_costs_ivf_filtered.cutoff_cost(
            ctx["store"], block, ctx["k"], rows / programs),
        kernel_costs.peaks(ctx["device"]["kind"]))
    least = executions * seconds
    share = 100.0 * least / device_s
    print(json.dumps({"annf_cutoff_roofline": {
        "device_s": device_s, "executions": executions,
        "rows_a_program": rows / programs, "block": block,
        "least_s": least, "bound_by": by}}), flush=True)
    if share > 100.0:
        raise RuntimeError(f"annf_cutoff_roofline_pct {share:.1f} % is over "
                           f"100: the operations or bytes are counted too "
                           f"high")
    return share
