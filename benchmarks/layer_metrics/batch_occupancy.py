"""Queries per coalesced dispatch over the window: Search replies that
rode in a coalesced dispatch over the batcher's dispatch count
(``compile_bucket_total``), both as the server counts them. A filtered request that the batcher sent solo
(a highly selective filter takes the store's gathered program) is in
neither: solo = Searches - ``filtered_batched_total`` where every request
of the mix carries a filter."""

BUCKETS = "weaviate_tpu_query_batcher_compile_bucket_total"
FILTERED = "weaviate_tpu_query_batcher_filtered_batched_total"
SEARCHES = "weaviate_tpu_request_phase_seconds_count"
LABELS = {"operation": "grpc.search", "phase": "queue_wait"}


def read(ctx):
    before, after = ctx["before"], ctx["after"]
    dispatches = after.total(BUCKETS) - before.total(BUCKETS)
    if dispatches <= 0:
        return None
    replies = after.total(SEARCHES, LABELS) - before.total(SEARCHES, LABELS)
    if ctx["mix"].get("filter") is not None:
        solo = replies - (after.total(FILTERED) - before.total(FILTERED))
        replies -= max(0.0, solo)
    return replies / dispatches
