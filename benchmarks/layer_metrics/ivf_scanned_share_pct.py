"""What the index is for: the share of the corpus a query's probe gathers.
Padded list positions gathered (``weaviate_tpu_ivf_candidate_rows_total``)
over queries probed (``weaviate_tpu_ivf_queries_total``; both count the
rows of the padded block, so padding cancels), as a share of the live rows
the index holds (gauge ``weaviate_tpu_ivf_live_rows``), x 100, over the
window. A flat scan reads 100; the delta buffer's rows, scanned exactly
beside the probe, are not in it. None where the program has no such
counters (the parent) or no query was probed."""

QUERIES = "weaviate_tpu_ivf_queries_total"
CANDIDATES = "weaviate_tpu_ivf_candidate_rows_total"
LIVE = "weaviate_tpu_ivf_live_rows"


def read(ctx):
    before, after = ctx["before"], ctx["after"]
    queries = after.total(QUERIES) - before.total(QUERIES)
    live = after.total(LIVE)
    if queries <= 0 or live <= 0:
        return None
    gathered = after.total(CANDIDATES) - before.total(CANDIDATES)
    return 100.0 * gathered / queries / live
