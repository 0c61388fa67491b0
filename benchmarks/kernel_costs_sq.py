"""Operations and bytes one dispatch of the 8-bit scalar-quantized scan
needs, from its shapes. Kept with the benchmark, beside ``kernel_costs.py``
(whose peaks and ``least_seconds`` are used here unchanged) and
``kernel_costs_pq.py``, so that no later PR can move them.

Counted per dispatch of padded batch ``b`` over an SQ store whose resident
arrays ``describe`` lists (serve.py), as the ALGORITHM has to do it, whatever
implements it (``ops/sq.py::sq_topk`` today: one int8 x int8 -> int32
contraction a chunk):

- scan: every encoded query against every resident code, one multiply-add
  a dimension, 2 * b * rows * dim integer operations, held against the int8
  peak;
- bytes: every resident code read once (rows * dim, one byte each), the
  rows' int32 terms read once (4 * rows), the queries (b * dim * 4, float32
  as they arrive) and the candidates (distance + id)."""

from __future__ import annotations


def scan_cost(store: dict, b: int, k: int) -> dict:
    """-> {"flops", "int_ops", "bytes"} of one dispatch. ``store`` is what
    serve.py describes; ``k`` is the request's, the scan returns
    ``rescore_limit * k`` candidates."""
    codes = store["arrays"].get("codes")
    if codes is None or codes["dtype"] != "int8":
        raise ValueError(f"no SQ scan cost for a store with arrays "
                         f"{sorted(store['arrays'])}")
    rows, dim = codes["shape"]
    cand = (store.get("rescore_limit") or 1) * k
    return {"flops": 0.0, "int_ops": 2.0 * b * rows * dim,
            "bytes": float(rows * dim + 4 * rows + b * dim * 4
                           + b * cand * 8)}
