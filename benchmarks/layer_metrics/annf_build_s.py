"""Seconds the class spent building and keeping its ANN index during
set-up, at 768 dimensions: the sum of ``weaviate_tpu_ivf_maintain_seconds``
over its three stages (``upgrade``, ``train``: the first training and every
retrain, ``flush``: a delta folded into the lists), from the page scraped
at the window's START (the steps lie before the window). The series
``ivf_build_s`` reads, under a name of its own because that metric lists
another cell and no accepted list may be edited. None where the program has
no such series or the class is still under its threshold."""

SERIES = "weaviate_tpu_ivf_maintain_seconds_sum"


def read(ctx):
    page = ctx["before"]
    if not any(name == SERIES for name, _, _ in page.series):
        return None
    return page.total(SERIES)
