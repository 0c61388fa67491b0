"""Seconds the class spent compressing itself (snapshot, codebook fit,
encoding of every row held, swap) during set-up: the sum of
``weaviate_tpu_index_compress_seconds`` over its stages, from the page
scraped at the window's START, because the step lies before the window (a
delta over the window would read 0). None where the program has no such
series (a parent without it, a class that never compressed)."""

SERIES = "weaviate_tpu_index_compress_seconds_sum"


def read(ctx):
    page = ctx["before"]
    if not any(name == SERIES for name, _, _ in page.series):
        return None
    return page.total(SERIES)
