"""The flat scan's share of its roofline where a collection's rows lie in
several shards on one chip: every request is a scan a shard, each over
that shard's rows alone, dispatched by that shard's own batcher.

``scan_roofline_pct.py``'s reading, unchanged, with these operands: device
time is the summed module-line time of the configuration's
``scan_programs`` over the traced window, the executions of ALL the
shards' batchers (they run one program name); least time is
``kernel_costs.scan_cost`` for exactly those executions at ONE shard's
resident shapes, which ``serve.describe`` gives (the FIRST shard's: every
shard of the cell holds [32768, 768]), the padded batch sizes in the
proportion ``compile_bucket_total`` moved while the trace ran (that
counter carries no shard label: it is the sum over the batchers). A share
over 100 % is a fault of the count and fails the run. None where the
configuration has one shard (that is ``scan_roofline_pct``'s cell) or the
store holds no float rows."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx):
    if int(ctx["config"].get("shards", 1)) < 2 or \
            "vectors" not in ctx["store"]["arrays"]:
        return None
    spec = importlib.util.spec_from_file_location(
        "layer_scan_roofline_pct", os.path.join(HERE, "scan_roofline_pct.py"))
    one_shard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(one_shard)
    return one_shard.read(ctx)
