"""Seconds the class spent compressing itself to one-byte codes (snapshot,
range fit, encoding of every row held, swap) during set-up: the sum of
``weaviate_tpu_index_compress_seconds{quantization="sq"}`` over its stages,
from the page scraped at the window's START, because the step lies before
the window (a delta over the window would read 0). None where the program
has no such series (a parent that drops ``sq``, a class that never
compressed)."""

SERIES = "weaviate_tpu_index_compress_seconds_sum"
LABELS = {"quantization": "sq"}


def read(ctx):
    page = ctx["before"]
    if not any(name == SERIES and labels.get("quantization") == "sq"
               for name, labels, _ in page.series):
        return None
    return page.total(SERIES, LABELS)
