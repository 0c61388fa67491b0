"""Share of a window's filter-operand look-ups that found their operand on
the device: ``weaviate_tpu_filter_operand_total`` with result ``hit`` (kept
from an earlier dispatch) or ``shared`` (the same mask object as an earlier
row of this dispatch) over all four results (``miss``: translated, packed,
uploaded and kept; ``uncached``: built on the host for this dispatch
alone), both paths (``bitmask``: a probe row's packed mask; ``gathered``: an
exact-route slot list), x 100. With the masks of the mix memoised and no
write in the window it reads 100; a program that packs every dispatch's
block on the host (the parent on an IVF store) reads 0. None where no
filtered row was counted."""

SERIES = "weaviate_tpu_filter_operand_total"


def read(ctx):
    before, after = ctx["before"], ctx["after"]
    moved = {result: n - before.by_label(SERIES, "result").get(result, 0.0)
             for result, n in after.by_label(SERIES, "result").items()}
    total = sum(moved.values())
    if total <= 0:
        return None
    return 100.0 * (moved.get("hit", 0.0) + moved.get("shared", 0.0)) / total
