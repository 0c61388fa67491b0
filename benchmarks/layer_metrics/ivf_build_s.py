"""Seconds the class spent building and keeping its ANN index during
set-up: the sum of ``weaviate_tpu_ivf_maintain_seconds`` over its three
stages, ``upgrade`` (the flat rows moved into a fresh IVF index at the
threshold, less the training inside it), ``train`` (k-means, assignment,
the lists rebuilt: the first training and every retrain) and ``flush``
(the delta buffer folded into the lists), from the page scraped at the
window's START, because the steps lie before the window (a delta over the
window would read 0). None where the program has no such series (the
parent, a class that is still under its threshold)."""

SERIES = "weaviate_tpu_ivf_maintain_seconds_sum"


def read(ctx):
    page = ctx["before"]
    if not any(name == SERIES for name, _, _ in page.series):
        return None
    return page.total(SERIES)
