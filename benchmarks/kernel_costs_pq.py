"""Operations and bytes one dispatch of the 8-bit PQ scan needs, from its
shapes. Kept with the benchmark, beside ``kernel_costs.py`` (whose ``codes``
are BQ's 1-bit words and whose peaks and ``least_seconds`` are used here
unchanged), so that no later PR can move them.

Counted per dispatch of padded batch ``b`` over a PQ store whose resident
arrays ``describe`` lists (serve.py), as the ALGORITHM has to do it (a
per-query look-up table and a sum over segments), not as one kernel happens
to (``ops/pq.py::pq_topk`` reconstructs rows and multiplies, which costs
more and is what a share under 100 % shows):

- table build: every query against every centroid of every segment,
  2 * b * dim * centroids multiply-adds;
- scan: one table entry added per segment per row, b * rows * segments
  adds, held with the table build against the bf16 peak (there is no
  published f32 peak: that understates the least time, never overstates);
- bytes: every resident code read once (rows * segments, one byte each),
  the tables written and read once (b * segments * centroids * 4), the
  queries and the candidates."""

from __future__ import annotations


def scan_cost(store: dict, dim: int, centroids: int, b: int, k: int) -> dict:
    """-> {"flops", "int_ops", "bytes"} of one dispatch. ``store`` is what
    serve.py describes (it lists no codebook: ``dim`` and ``centroids``
    are the configuration's); ``k`` is the request's, the scan returns
    ``rescore_limit * k`` candidates."""
    codes = store["arrays"].get("codes")
    if codes is None or codes["dtype"] != "uint8":
        raise ValueError(f"no PQ scan cost for a store with arrays "
                         f"{sorted(store['arrays'])}")
    rows, segments = codes["shape"]
    cand = (store.get("rescore_limit") or 1) * k
    return {"flops": 2.0 * b * dim * centroids + float(b) * rows * segments,
            "int_ops": 0.0,
            "bytes": float(rows * segments + b * segments * centroids * 4
                           + b * dim * 4 + b * cand * 8)}
