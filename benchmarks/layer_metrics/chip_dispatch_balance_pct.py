"""How evenly a collection's dispatches fall over the chips of the host:
dispatches on the least-used local device over dispatches on the
most-used, x 100, over the window.

Read from the batcher's dispatch counter (``compile_bucket_total``) by
its ``device`` label, which names the chip a batcher's index lies on
(weaviate_tpu/runtime/placement.py). A local device that served nothing
reads 0: the run's ``device.count`` says how many there are. None where
the counter carries no ``device`` label (a program that places nothing,
as the parent: everything runs on the default device there), and where
nothing was dispatched."""

BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"


def read(ctx):
    before = ctx["before"].by_label(BUCKETS, "device")
    after = ctx["after"].by_label(BUCKETS, "device")
    moved = {device: n - before.get(device, 0.0)
             for device, n in after.items() if device}
    if not moved or max(moved.values()) <= 0:
        return None
    if len(moved) < int(ctx["device"]["count"]):
        return 0.0
    return 100.0 * min(moved.values()) / max(moved.values())
