"""The flat scan's share of its roofline where a collection's shards lie
on the chips of one host, two or more chips at work at once.

``scan_roofline_pct.py``'s reading, imported and not copied, with these
operands: device time is the summed module-line time of the
configuration's ``scan_programs`` over the traced window, which
``trace_reduce`` sums over EVERY device plane, as it sums their
executions; least time is ``kernel_costs.scan_cost`` for exactly those
executions at ONE shard's resident shapes, the FIRST shard's as
``serve.describe`` gives them (every shard of the cell holds [65536,
768] f32: 201.3 MB, 0.25 ms at the HBM roofline, bytes-bound at every
batch size), the padded batch sizes in the proportion
``compile_bucket_total`` moved while the trace ran, summed over the
batchers and chips. A share a dispatch, so the number of chips cancels:
what it says is how near ONE chip's scan runs to ITS memory, whichever
chip ran it. Over 100 % is a fault of the count and fails the run. None
where the configuration names one chip or the store holds no float
rows."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx):
    if int(ctx["config"].get("chips", 1)) < 2 or \
            "vectors" not in ctx["store"]["arrays"]:
        return None
    spec = importlib.util.spec_from_file_location(
        "layer_scan_roofline_pct", os.path.join(HERE, "scan_roofline_pct.py"))
    one_chip = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(one_chip)
    return one_chip.read(ctx)
