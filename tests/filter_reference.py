"""The filter evaluation as it stood before the leaf memo (PR 33), kept
as the reference ``tests/test_filter_memo.py`` holds the served path to:
every request walks the buckets, a range concatenates and sorts its
values' ids, nothing is remembered. It reads the inverted index's buckets
and per-key accessors only, never ``leaf_mask`` or
``numeric_range_parts``. Call it under ``Shard._lock``, as
``Shard.allow_mask`` did. ``WithinGeoRange`` is not here: the memo leaves
it alone.
"""

import fnmatch
import re

import numpy as np

from weaviate_tpu import native
from weaviate_tpu.filters import Operator
from weaviate_tpu.schema.config import DataType
from weaviate_tpu.text.inverted import _SEP, _enc_f64, parse_date
from weaviate_tpu.text.tokenizer import tokenize


def numeric_range_ids(inv, prop, lo, hi, lo_incl=True, hi_incl=False):
    """Sorted unique doc ids with a value in the range: one merged LSM
    walk, one concatenate, one ``np.unique``."""
    pfx = prop.encode() + _SEP
    start = pfx if lo is None else pfx + _enc_f64(lo) + (
        b"" if lo_incl else b"\x00")
    stop = pfx + b"\xff" * 9 if hi is None else pfx + _enc_f64(hi) + (
        b"\x00" if hi_incl else b"")
    parts = []
    for _k, v in inv.numeric_bucket.iter_range(start, stop):
        ids = native.difference_sorted(v["add"], v["del"])
        if len(ids):
            parts.append(ids)
    if not parts:
        return np.empty(0, np.uint64)
    return np.unique(np.concatenate(parts))


def _from_ids(ids, size):
    mask = np.zeros(size, dtype=bool)
    arr = np.asarray(ids).astype(np.int64, copy=False)
    arr = arr[arr < size]
    mask[arr] = True
    return mask


def _full(inv, size):
    return _from_ids(inv.all_docs(), size)


def reference_mask(f, inv, size):
    op = f.operator
    if op in Operator.LOGICAL:
        masks = [reference_mask(o, inv, size) for o in f.operands]
        out = masks[0]
        for m in masks[1:]:
            out = (out & m) if op == Operator.AND else (out | m)
        return out if op != Operator.NOT else _full(inv, size) & ~out
    prop = f.prop
    if op == Operator.IS_NULL:
        null_mask = _from_ids(inv.null_ids(prop), size)
        return null_mask if f.value else _full(inv, size) & ~null_mask
    if op in Operator.RANGE:
        t = f.value
        t = float(parse_date(t) if isinstance(t, str) else t)
        lo, hi, lo_incl, hi_incl = {
            Operator.GREATER_THAN: (t, None, False, False),
            Operator.GREATER_THAN_EQUAL: (t, None, True, False),
            Operator.LESS_THAN: (None, t, True, False),
            Operator.LESS_THAN_EQUAL: (None, t, True, True)}[op]
        return _from_ids(numeric_range_ids(inv, prop, lo, hi, lo_incl,
                                           hi_incl), size)
    if op == Operator.LIKE:
        rx = re.compile(fnmatch.translate(str(f.value).lower()))
        mask = np.zeros(size, dtype=bool)
        for token, ids in inv.text_vocab(prop):
            if rx.match(token.lower()):
                mask |= _from_ids(ids, size)
        return mask
    values = f.value if isinstance(f.value, (list, tuple)) else [f.value]
    masks = [_match_value(inv, prop, v, size) for v in values]
    out = masks[0]
    for m in masks[1:]:
        out = (out & m) if op == Operator.CONTAINS_ALL else (out | m)
    return _full(inv, size) & ~out if op == Operator.NOT_EQUAL else out


def _match_value(inv, prop, value, size):
    if isinstance(value, bool):
        return _from_ids(inv.filterable_ids(prop, value), size)
    if isinstance(value, (int, float)):
        return _from_ids(inv.filterable_ids(prop, float(value)), size)
    if not isinstance(value, str):
        return np.zeros(size, dtype=bool)
    sch = inv.config.property(prop)
    if sch is not None and sch.data_type in (DataType.DATE,
                                             DataType.DATE_ARRAY):
        try:
            return _from_ids(inv.filterable_ids(prop, parse_date(value)),
                             size)
        except ValueError:
            return np.zeros(size, dtype=bool)
    if sch is not None and sch.data_type in (DataType.UUID,
                                             DataType.UUID_ARRAY):
        return _from_ids(inv.filterable_ids(prop, value), size)
    tokens = tokenize(value, sch.tokenization if sch is not None else "word")
    if not tokens:
        return np.zeros(size, dtype=bool)
    out = _from_ids(inv.filterable_ids(prop, tokens[0]), size)
    for t in tokens[1:]:
        out = out & _from_ids(inv.filterable_ids(prop, t), size)
    return out
