"""The masked probe's programs' share of their roofline over the traced
window, in a cell where only part of a dispatch's rows probe: the least
time of the DISPATCHES the traced executions of the configuration's
``probe_programs`` made up (``kernel_costs_ivf_filtered.probe_cost``, which
is ``kernel_costs_ivf.probe_cost``: the centroids and every distinct probed
list read once a dispatch) over their summed module-line time.

``ivf_probe_roofline_pct`` takes a dispatch's probe block for the
batcher's padded batch; here the rows whose filter is under
``flatSearchCutoff`` leave the block, so the block comes from the server's
own counters over the traced stretch: ``weaviate_tpu_ivf_queries_total``
(rows that probed) over ``weaviate_tpu_ivf_probe_dispatches_total``
(dispatches that probed), and the executions become dispatches by
``..._probe_dispatches_total`` over ``..._probe_programs_total``. A share
over 100 % is a fault of the count and fails the run. None where the
program has no such counters (the parent), no posting lists, or nothing
probed in the trace."""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import kernel_costs  # noqa: E402
import kernel_costs_ivf_filtered  # noqa: E402

PROGRAMS = "weaviate_tpu_ivf_probe_programs_total"
DISPATCHES = "weaviate_tpu_ivf_probe_dispatches_total"
QUERIES = "weaviate_tpu_ivf_queries_total"
PROBED = "weaviate_tpu_ivf_probed_lists_total"


def read(ctx):
    trace, marks = ctx["trace"], ctx["trace_marks"]
    patterns = ctx["config"].get("probe_programs")
    if (not trace or not patterns or "after" not in marks
            or "list_vecs" not in ctx["store"]["arrays"]):
        return None
    before, after = marks["before"], marks["after"]
    programs, dispatches, queries, probed = (
        after.total(s) - before.total(s)
        for s in (PROGRAMS, DISPATCHES, QUERIES, PROBED))
    hit = [(sec, n) for name, (sec, n) in trace["programs"].items()
           if any(re.search(p, name) for p in patterns)]
    device_s = sum(sec for sec, _ in hit)
    executions = sum(n for _, n in hit)
    if device_s <= 0 or programs <= 0 or dispatches <= 0 or queries <= 0:
        return None
    seconds, by = kernel_costs.least_seconds(
        kernel_costs_ivf_filtered.probe_cost(
            ctx["store"], queries / dispatches, ctx["k"], probed / queries),
        kernel_costs.peaks(ctx["device"]["kind"]))
    traced = executions * dispatches / programs
    least = traced * seconds
    share = 100.0 * least / device_s
    print(json.dumps({"annf_probe_roofline": {
        "device_s": device_s, "executions": executions,
        "dispatches": traced, "rows_a_dispatch": queries / dispatches,
        "nprobe": probed / queries, "least_s": least, "bound_by": by}}),
        flush=True)
    if share > 100.0:
        raise RuntimeError(f"annf_probe_roofline_pct {share:.1f} % is over "
                           f"100: the operations or bytes are counted too "
                           f"high")
    return share
