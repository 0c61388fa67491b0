"""Operations and bytes one dispatch of the IVF probe needs, from its
shapes. Kept with the benchmark, beside ``kernel_costs.py`` (whose peaks
and ``least_seconds`` are used here unchanged), so that no later PR can
move them.

Counted per dispatch of padded batch ``b`` over an IVF store whose posting
lists ``describe`` lists as ``list_vecs`` ``[nlist, cap, dim]`` (serve.py),
probing ``nprobe`` lists a query, as the ALGORITHM has to do it, whatever
implements it (``engine/ivf.py::_ivf_probe_topk`` today: a centroid matmul,
a top-``nprobe``, a gather of ``nprobe * cap`` padded positions a query and
a matmul over them, in programs of at most 16 queries):

- operations: every query against every centroid, 2 * b * nlist * dim, and
  against every position of the lists it probes, 2 * b * nprobe * cap * dim
  (a position is a slot of the padded list, live or not: the lists are
  dense tensors, and a program that skipped the dead ones would have to
  know them first). Float32 products are held against the bf16 peak, as
  ``kernel_costs.py`` holds a flat scan's: that understates the least
  time, never overstates it;
- bytes: the centroids once (nlist * dim * 4), and every probed row ONCE,
  at its stored width (dim * the lists' dtype) with its slot (4), its
  cached norm (4) and its valid flag (1). Once means once a DISPATCH: a
  list that two queries of one dispatch both probe has to be read once, so
  the rows counted are those of min(b * nprobe, nlist) lists, the most
  DISTINCT lists the dispatch can touch. (The program today gathers each
  query's lists apart, b * nprobe * cap rows, and splits a dispatch of 32
  into two programs: both are the implementation's, not the algorithm's,
  and a later PR that changes the gather is held to the same count.) The
  queries (b * dim * 4) and the answers (b * k * 8) on top.

The delta buffer's exact scan and the merge of the two legs are other
programs and are not counted here, on either side of the share."""

from __future__ import annotations

_BYTES = {"float32": 4, "bfloat16": 2}


def probe_cost(store: dict, b: int, k: int, nprobe: float) -> dict:
    """-> {"flops", "int_ops", "bytes"} of one dispatch of padded batch
    ``b``. ``store`` is what serve.py describes; ``nprobe`` is the lists a
    query probed, as the program's counters give it."""
    lists = store["arrays"].get("list_vecs")
    if lists is None or lists["dtype"] not in _BYTES:
        raise ValueError(f"no IVF probe cost for a store with arrays "
                         f"{sorted(store['arrays'])}")
    nlist, cap, dim = lists["shape"]
    row = dim * _BYTES[lists["dtype"]] + 4 + 4 + 1
    distinct = min(b * nprobe, nlist)
    return {"flops": 2.0 * b * nlist * dim + 2.0 * b * nprobe * cap * dim,
            "int_ops": 0.0,
            "bytes": float(nlist * dim * 4 + distinct * cap * row
                           + b * dim * 4 + b * k * 8)}
