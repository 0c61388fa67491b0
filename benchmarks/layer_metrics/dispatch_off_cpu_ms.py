"""What the two dispatch threads wait a dispatch INSIDE stages that do
not wait by design: the sum over ``assemble``, ``mask_pack``,
``deliver`` and ``finish`` of wall less CPU
(``weaviate_tpu_dispatch_stage_seconds_sum`` less
``weaviate_tpu_dispatch_stage_cpu_seconds_sum``: a ``time.thread_time()``
stamp beside each wall stamp, runtime/tailboard.py) over the dispatches
of the window (``compile_bucket_total``), in ms. A thread stamps its CPU
clock on one side in a few of each kind, so every series' CPU sum is
scaled by that series' own wall count over its CPU count. Those four
stages are the threads' own Python (``assemble`` is the worker's
remainder: it holds every re-acquisition of the interpreter after
``idle``, ``slot_wait`` and ``launch``), so what they spend off a core
is the interpreter lock or a missing core, never the device.

Where the kernel moves a thread's CPU clock in 10-ms ticks (the chip's
hosts do) a series' CPU sum is a count of ticks: over ``MIN_STAMPED``
stamped sides it is as likely nothing as one tick too many, so under
that many stamped ``assemble`` sides (every worker side has one) there
is no reading. The value is NOT cut at 0: a scaled CPU that passes its
wall says the scale is wrong, and should show. None where the program
stamps no CPU, as the parent does not."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cpu_ms_per_search as account  # noqa: E402

WALL = "weaviate_tpu_dispatch_stage_seconds"
CPU = "weaviate_tpu_dispatch_stage_cpu_seconds"
STAGES = ("assemble", "mask_pack", "deliver", "finish")
MIN_STAMPED = 8


def read(ctx):
    count = account.dispatches(ctx)
    if count <= 0:
        return None
    if account.moved(ctx, CPU + "_count",
                     {"stage": "assemble"}) < MIN_STAMPED:
        return None
    waited = 0.0
    for name, labels, _ in ctx["after"].series:
        if name != CPU + "_count" or labels.get("stage") not in STAGES:
            continue
        stamped = account.moved(ctx, name, labels)
        if stamped <= 0:
            continue
        sides = account.moved(ctx, WALL + "_count", labels)
        waited += (account.moved(ctx, WALL + "_sum", labels)
                   - account.moved(ctx, CPU + "_sum", labels)
                   * sides / stamped)
    return waited / count * 1000.0
