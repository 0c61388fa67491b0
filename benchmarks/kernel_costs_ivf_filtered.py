"""Operations and bytes the two routes of a FILTERED search on an IVF store
need, from their shapes. Kept with the benchmark, beside ``kernel_costs.py``
(whose peaks and ``least_seconds`` are used here unchanged) and
``kernel_costs_ivf.py`` (whose probe count is used here unchanged), so that
no later PR can move them.

A filtered request is answered by one of two routes (upstream's
``flatSearchCutoff``):

- **the exact route** (a filter that allows fewer live rows than the
  cutoff): the allowed rows are scored against the queries and nothing
  else is read. Counted per PROGRAM, which answers one distinct mask for a
  block of ``b`` query rows over ``rows`` allowed rows, as the ALGORITHM
  has to do it, whatever implements it (``engine/ivf.py::
  _ivf_flat_cutoff_topk`` today: the slot list looked up in the slot map,
  one row gather, one matmul, one top-k):
  operations 2 * b * rows * dim (float32 products held against the bf16
  peak, as everywhere: that understates the least time, never overstates
  it); bytes: every allowed row ONCE at its stored width (dim * the lists'
  dtype) with its position in the slot map (4), the queries (b * dim * 4)
  and the answers (b * k * 8). ``rows`` are the LIVE allowed rows, not the
  pow2 bucket the program pads them to: the padding is the
  implementation's.
- **the masked probe** (a filter that allows more): what
  ``kernel_costs_ivf.probe_cost`` counts for a dispatch whose probe block
  holds ``b`` rows; the packed allow bits (capacity / 8 bytes a row) are
  not counted, which can only understate the least time.

Both are bytes-bound at every batch the cells reach (16 operations a byte
at b = 32 against the v5e's 240)."""

from __future__ import annotations

import kernel_costs_ivf

_BYTES = {"float32": 4, "bfloat16": 2}


def cutoff_cost(store: dict, b: float, k: int, rows: float) -> dict:
    """-> {"flops", "int_ops", "bytes"} of one exact-route program over
    ``rows`` allowed live rows for a block of ``b`` query rows. ``store``
    is what serve.py describes."""
    lists = store["arrays"].get("list_vecs")
    if lists is None or lists["dtype"] not in _BYTES:
        raise ValueError(f"no exact-route cost for a store with arrays "
                         f"{sorted(store['arrays'])}")
    dim = lists["shape"][2]
    return {"flops": 2.0 * b * rows * dim, "int_ops": 0.0,
            "bytes": float(rows * (dim * _BYTES[lists["dtype"]] + 4)
                           + b * dim * 4 + b * k * 8)}


def probe_cost(store: dict, b: float, k: int, nprobe: float) -> dict:
    """One dispatch's masked probe over a probe block of ``b`` rows."""
    return kernel_costs_ivf.probe_cost(store, b, k, nprobe)
