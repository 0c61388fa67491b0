"""The IVF probe programs' share of their roofline over the traced window:
the summed module-line time of the configuration's ``scan_programs`` (the
probe program; the delta buffer's scan and the merge are other programs)
against the least time of the DISPATCHES those executions made up
(``kernel_costs_ivf.py``: operations and bytes of a probe of padded batch
b; ``kernel_costs.py``'s peaks).

A dispatch of the batcher may run as several probe programs (the store
cuts a block into chunks of queries), so the traced executions are turned
into dispatches by the ratio the server's own counters moved in while the
trace ran: ``compile_bucket_total`` (dispatches, by padded batch) over
``weaviate_tpu_ivf_probe_programs_total``. ``nlist``, ``cap`` and the
stored width come from ``describe``'s ``list_vecs``; the lists probed a
query from ``weaviate_tpu_ivf_probed_lists_total`` over
``weaviate_tpu_ivf_queries_total``. A share over 100 % is a fault of the
count and fails the run. None where the program has no such counters or
the store no posting lists (the parent; a class still under its
threshold)."""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import kernel_costs  # noqa: E402
import kernel_costs_ivf  # noqa: E402

BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"
PROGRAMS = "weaviate_tpu_ivf_probe_programs_total"
QUERIES = "weaviate_tpu_ivf_queries_total"
PROBED = "weaviate_tpu_ivf_probed_lists_total"


def read(ctx):
    trace, marks = ctx["trace"], ctx["trace_marks"]
    patterns = ctx["config"].get("scan_programs")
    if (not trace or not patterns or "after" not in marks
            or "list_vecs" not in ctx["store"]["arrays"]):
        return None
    before, after = marks["before"], marks["after"]
    programs, queries, probed = (after.total(s) - before.total(s)
                                 for s in (PROGRAMS, QUERIES, PROBED))
    hit = [(sec, n) for name, (sec, n) in trace["programs"].items()
           if any(re.search(p, name) for p in patterns)]
    device_s = sum(sec for sec, _ in hit)
    executions = sum(n for _, n in hit)
    moved = {b: after.by_label(BUCKETS, "b").get(b, 0.0) - v0
             for b, v0 in before.by_label(BUCKETS, "b").items()}
    moved = {int(b): n for b, n in moved.items() if n > 0}
    if device_s <= 0 or not moved or programs <= 0 or queries <= 0:
        return None
    nprobe = probed / queries
    dispatches = executions * sum(moved.values()) / programs
    peak = kernel_costs.peaks(ctx["device"]["kind"])
    total = sum(moved.values())
    least = 0.0
    bound_by = {}
    for b, n in moved.items():
        seconds, by = kernel_costs.least_seconds(
            kernel_costs_ivf.probe_cost(ctx["store"], b, ctx["k"], nprobe),
            peak)
        least += dispatches * (n / total) * seconds
        bound_by[by] = bound_by.get(by, 0) + n
    share = 100.0 * least / device_s
    print(json.dumps({"ivf_probe_roofline": {
        "device_s": device_s, "executions": executions,
        "dispatches": dispatches, "nprobe": nprobe, "least_s": least,
        "bound_by": bound_by, "dispatches_by_padded_batch": moved}}),
        flush=True)
    if share > 100.0:
        raise RuntimeError(f"ivf_probe_roofline_pct {share:.1f} % is over "
                           f"100: the operations or bytes are counted too "
                           f"high")
    return share
