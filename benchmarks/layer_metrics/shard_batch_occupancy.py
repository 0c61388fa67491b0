"""Shard searches per coalesced dispatch over the window, where a
collection's rows lie in several shards on one node: the shard searches
that fanned-out requests enqueued (``fanout_shards_total``: a request
over eight local shards adds eight) over the dispatch count of all the
shards' batchers (``compile_bucket_total``, which carries no shard label:
it is the sum over the batchers), both as the server counts them. That is
what one batcher holds when it goes to the device. ``batch_occupancy``
reads a request once (one ``queue_wait`` a request, the critical path's)
over those same dispatches, so on such a cell it reads this number over
the number of shards. None where nothing fanned out: a collection of one
shard, or a program without the counter, as the parent has not."""

BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"
FANNED = "weaviate_tpu_fanout_shards_total"


def read(ctx):
    before, after = ctx["before"], ctx["after"]
    dispatches = after.total(BUCKETS) - before.total(BUCKETS)
    searches = after.total(FANNED) - before.total(FANNED)
    if dispatches <= 0 or searches <= 0:
        return None
    return searches / dispatches
