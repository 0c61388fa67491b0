"""Share of the filtered requests an ANN index answered by the EXACT route
under upstream's ``flatSearchCutoff`` (their filter allows fewer live rows
than the cutoff: the allowed rows are gathered and scored, the probe is
bypassed), of all filtered requests it answered, x 100, over the window:
``weaviate_tpu_ivf_filtered_requests_total{route="flat_cutoff"}`` over the
sum of its two routes. The mix's bounds {1, 10, 50, 99} at 262,144 rows
put two on each side of 40,000, so the cell reads 50. None where the
program has no such series (the parent) or no filtered request reached an
ANN index (a class still under its threshold)."""

SERIES = "weaviate_tpu_ivf_filtered_requests_total"


def read(ctx):
    before, after = ctx["before"], ctx["after"]
    moved = {route: n - before.by_label(SERIES, "route").get(route, 0.0)
             for route, n in after.by_label(SERIES, "route").items()}
    total = sum(moved.values())
    if total <= 0:
        return None
    return 100.0 * moved.get("flat_cutoff", 0.0) / total
