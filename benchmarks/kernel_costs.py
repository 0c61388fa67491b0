"""Operations and bytes a scan dispatch needs, from its shapes, and the
chip's peaks. Kept with the benchmark so that no later PR can move them.

Counted per dispatch of padded batch ``b`` over a store whose resident
arrays ``describe`` lists (serve.py): what the algorithm has to do, not
what a particular kernel happens to do.

- ``DeviceVectorStore`` (flat): every resident row is read once in the
  store's dtype, the queries are read, 2*b*rows*dim multiply-adds run. An
  f32 matmul has no published peak of its own, so its operations are held
  against the bf16 peak: that understates the least time, never overstates
  it.
- ``QuantizedVectorStore`` with BQ: every resident code word is read once
  (dim/8 bytes a row), b*rows*dim bit operations (xor + popcount) run and
  are held against the int8 peak; where the rescore rows live on the
  device, ``rescore_limit * k`` of them are gathered per query in their
  dtype and 2*dim multiply-adds run on each."""

from __future__ import annotations

import json
import os

_BYTES = {"float32": 4, "bfloat16": 2, "uint32": 4, "uint8": 1, "bool": 1}


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"peaks.json with its source")
    return table[device_kind]


def scan_cost(store: dict, b: int, k: int) -> dict:
    """-> {"flops", "int_ops", "bytes"} of one dispatch."""
    arrays = store["arrays"]
    if "vectors" in arrays:
        rows, dim = arrays["vectors"]["shape"]
        width = _BYTES[arrays["vectors"]["dtype"]]
        return {"flops": 2.0 * b * rows * dim, "int_ops": 0.0,
                "bytes": float(rows * dim * width + b * dim * 4
                               + b * k * 8)}
    if "codes" in arrays:
        rows, words = arrays["codes"]["shape"]
        dim = words * 32
        cost = {"flops": 0.0, "int_ops": float(b) * rows * dim,
                "bytes": float(rows * words * 4 + b * words * 4 + b * k * 8)}
        if "rescore_rows" in arrays:
            cand = (store.get("rescore_limit") or 1) * k
            r_dim = arrays["rescore_rows"]["shape"][1]
            cost["flops"] += 2.0 * b * cand * r_dim
            cost["bytes"] += float(b * cand * r_dim
                                   * _BYTES[arrays["rescore_rows"]["dtype"]])
        return cost
    raise ValueError(f"no scan cost for a store with arrays {sorted(arrays)}")


def least_seconds(cost: dict, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    bounds = {"flops": cost["flops"] / peak["bf16_flops"],
              "int_ops": cost["int_ops"] / peak["int8_ops"],
              "bytes": cost["bytes"] / peak["hbm_bytes_per_s"]}
    by = max(bounds, key=bounds.get)
    return bounds[by], by
