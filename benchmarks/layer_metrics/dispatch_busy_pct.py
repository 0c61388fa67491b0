"""Share of the batcher worker's wall time over the window in which it was
not waiting: 1 - (``idle`` + ``slot_wait``) / ``worker_wall``, from the sums
of the server's ``weaviate_tpu_dispatch_stage_seconds`` (one worker thread a
batcher; its stages never overlap and cover its wall time). ``idle`` is the
wait on an empty queue, ``slot_wait`` the wait for the transfer window (the
drain thread is then the busy one).

It names no direction worth chasing alone: a worker is busy because the host
is slow (filtered cell) or because load is high, and idle because clients are
few (c1) or because they wait for the drain (BQ cell). Read it beside
``launch_ms`` and ``batch_occupancy``. None where the program has no such
series (a parent before PR 25)."""

SERIES = "weaviate_tpu_dispatch_stage_seconds_sum"
WAITS = ("idle", "slot_wait")


def read(ctx):
    before, after = ctx["before"], ctx["after"]

    def moved(stage):
        labels = {"stage": stage}
        return after.total(SERIES, labels) - before.total(SERIES, labels)

    wall = moved("worker_wall")
    if wall <= 0:
        return None
    return 100.0 * (1.0 - sum(moved(s) for s in WAITS) / wall)
