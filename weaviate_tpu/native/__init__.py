"""ctypes bindings for the native host-runtime library (csrc/).

The reference keeps its runtime in Go with hand-written SIMD only for
distances; our TPU compute path is JAX/Pallas, and the host-side hot loops
— doc-id set algebra, posting-block codecs, cross-shard merge, the storage
object's frame encoder on the import path and, on the read path, the
encoder of a plain gRPC Search's reply from the stored frames of its
results (``search_reply_encode``) — live in C++
(csrc/weaviate_native.cpp). Loading strategy:

1. use ``libweaviate_native.so`` next to this file if present,
2. else try to build it with g++ (one-time, ~1s, cached on disk; nothing
   is installed),
3. else fall back to the numpy / Python implementations (same semantics,
   used on machines without a toolchain and as the conformance oracle:
   the ones below, and for the reply encoder ``_fill_result`` of
   api/grpc/server.py, which answers whatever request the encoder
   declines as well).

``available()`` reports which path is active; set ``WEAVIATE_TPU_NO_NATIVE=1``
to force the numpy fallbacks (used by tests to cross-check both paths).
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libweaviate_native.so")
_SRC = os.path.join(os.path.dirname(_HERE), os.pardir, "csrc",
                    "weaviate_native.cpp")

_lib = None
_tried = False
_lock = threading.Lock()


def build_and_load(src: str, so: str, link: list[str] | None = None):
    """Compile-if-stale + atomic-replace + dlopen for a native library.
    Shared by this loader and the data-plane loader (dataplane.py).
    Returns the CDLL or None (numpy/Python fallback is safer than a
    stale-ABI .so)."""
    if os.environ.get("WEAVIATE_TPU_NO_NATIVE"):
        return None
    src = os.path.abspath(src)
    stale = (
        os.path.exists(so) and os.path.exists(src)
        and os.path.getmtime(src) > os.path.getmtime(so)
    )
    if not os.path.exists(so) or stale:
        if os.path.exists(src):
            try:
                # build to a per-pid temp path and rename into place:
                # os.replace is atomic, so concurrent processes never
                # dlopen a half-written library
                tmp = f"{so}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O3", "-fPIC", "-shared", "-std=c++17",
                     "-o", tmp, src] + (link or []),
                    check=True, capture_output=True, timeout=120,
                    cwd=os.path.dirname(src),
                )
                os.replace(tmp, so)
            except Exception:
                return None
    if not os.path.exists(so):
        return None
    try:
        return ctypes.CDLL(so)
    except OSError:
        return None


def _load():
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        lib = build_and_load(_SRC, _SO)
        if lib is None:
            return None
        u64p = ctypes.POINTER(ctypes.c_uint64)
        i64p = ctypes.POINTER(ctypes.c_int64)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f32p = ctypes.POINTER(ctypes.c_float)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64 = ctypes.c_int64
        i32 = ctypes.c_int32
        for name, args, res in [
            ("wn_intersect_u64", [u64p, i64, u64p, i64, u64p], i64),
            ("wn_union_u64", [u64p, i64, u64p, i64, u64p], i64),
            ("wn_difference_u64", [u64p, i64, u64p, i64, u64p], i64),
            ("wn_membership_i64", [i64p, i64, u64p, i64, u8p], None),
            ("wn_varint_encode_u64", [u64p, i64, u8p], i64),
            ("wn_varint_decode_u64", [u8p, i64, u64p, i64], i64),
            ("wn_merge_topk", [f32p, i64p, i64, i64, i64, f32p, i64p], None),
            ("wn_analyze_batch",
             [u8p, i64p, i64, ctypes.c_int32, i64p, i64p, i64p], i64),
            ("wn_analyze_fetch",
             [u8p, i64p, i64p, i64p, ctypes.POINTER(ctypes.c_uint32), i64p],
             None),
            ("wn_varint_encode_many", [u64p, i64p, i64, u8p, i64p], i64),
            ("wn_storobj_encode_batch",
             [u8p, i64p, u8p, i64p, f32p, i32, i64p, i64p, i64p, i64,
              u8p, i64p], i64),
            ("wn_pt_new", [i32], ctypes.c_void_p),
            ("wn_pt_free", [ctypes.c_void_p], None),
            ("wn_pt_bytes", [ctypes.c_void_p], i64),
            ("wn_pt_count", [ctypes.c_void_p], i64),
            ("wn_pt_map_columns",
             [ctypes.c_void_p, u8p, i64, u8p, i64p, i64, i64p, i64p,
              ctypes.POINTER(ctypes.c_uint32),
              ctypes.POINTER(ctypes.c_uint32), i32], i64),
            ("wn_pt_map_delete",
             [ctypes.c_void_p, u8p, i64, u8p, i64p, i64, i64p, i64p], None),
            ("wn_pt_roar",
             [ctypes.c_void_p, u8p, i64, u8p, i64p, i64, i64p, u64p, i32,
              i32], i64),
            ("wn_pt_tomb", [ctypes.c_void_p, u8p, i64], None),
            ("wn_pt_items", [ctypes.c_void_p, u8p, i64, u8p, i64], i64),
            ("wn_pt_get", [ctypes.c_void_p, u8p, i64], i64),
            ("wn_pt_fetch", [u8p], None),
            ("wn_hnsw_new", [i32, i32], ctypes.c_void_p),
            ("wn_hnsw_free", [ctypes.c_void_p], None),
            ("wn_hnsw_reset", [ctypes.c_void_p, i64], None),
            ("wn_hnsw_set_vectors", [ctypes.c_void_p, i64, i64, f32p], None),
            ("wn_hnsw_set_links", [ctypes.c_void_p, i64, i32, i32, i32p],
             None),
            ("wn_hnsw_set_links_batch",
             [ctypes.c_void_p, i64, i64p, i32p, i32p, i32p], None),
            ("wn_hnsw_clear_links", [ctypes.c_void_p, i64], None),
            ("wn_hnsw_set_tombstones", [ctypes.c_void_p, i64p, i64, i32],
             None),
            ("wn_hnsw_search_layer",
             [ctypes.c_void_p, f32p, i64, i32, i64p, f32p, i64, i64p, f32p],
             i64),
            ("wn_hnsw_search",
             [ctypes.c_void_p, f32p, i64, i64, i64, i32, u8p, i64p, f32p],
             i64),
        ]:
            fn = getattr(lib, name)
            fn.argtypes = args
            fn.restype = res
        _bind_reply_encoder()
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _u64(a) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(a, dtype=np.uint64))


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


# ---- sorted uint64 set algebra -------------------------------------------


def intersect_sorted(a, b) -> np.ndarray:
    """Intersection of two ascending unique uint64 arrays."""
    a, b = _u64(a), _u64(b)
    lib = _load()
    if lib is None or min(len(a), len(b)) == 0:
        return np.intersect1d(a, b, assume_unique=True)
    out = np.empty(min(len(a), len(b)), dtype=np.uint64)
    n = lib.wn_intersect_u64(_ptr(a, ctypes.c_uint64), len(a),
                             _ptr(b, ctypes.c_uint64), len(b),
                             _ptr(out, ctypes.c_uint64))
    return out[:n]


def union_sorted(a, b) -> np.ndarray:
    a, b = _u64(a), _u64(b)
    lib = _load()
    if lib is None:
        return np.union1d(a, b)
    out = np.empty(len(a) + len(b), dtype=np.uint64)
    n = lib.wn_union_u64(_ptr(a, ctypes.c_uint64), len(a),
                         _ptr(b, ctypes.c_uint64), len(b),
                         _ptr(out, ctypes.c_uint64))
    return out[:n]


def difference_sorted(a, b) -> np.ndarray:
    """a \\ b for ascending unique uint64 arrays."""
    a, b = _u64(a), _u64(b)
    lib = _load()
    if lib is None or len(a) == 0:
        return np.setdiff1d(a, b, assume_unique=True)
    out = np.empty(len(a), dtype=np.uint64)
    n = lib.wn_difference_u64(_ptr(a, ctypes.c_uint64), len(a),
                              _ptr(b, ctypes.c_uint64), len(b),
                              _ptr(out, ctypes.c_uint64))
    return out[:n]


def membership(vals, allow_sorted) -> np.ndarray:
    """Bool mask: vals[i] >= 0 and vals[i] in allow_sorted (ascending u64).

    The doc-id AllowList test of filtered vector search
    (reference: helpers/allow_list.go consumed in flat/index.go:319)."""
    vals = np.ascontiguousarray(np.asarray(vals, dtype=np.int64))
    allow = _u64(allow_sorted)
    lib = _load()
    if lib is None:
        return (vals >= 0) & np.isin(vals, allow.astype(np.int64))
    out = np.empty(len(vals), dtype=np.uint8)
    lib.wn_membership_i64(_ptr(vals, ctypes.c_int64), len(vals),
                          _ptr(allow, ctypes.c_uint64), len(allow),
                          _ptr(out, ctypes.c_uint8))
    return out.astype(bool)


# ---- varint delta codec ---------------------------------------------------


def _varint_encode_py(vals) -> bytes:
    out = bytearray()
    prev = 0
    for v in vals.tolist():
        d = v - prev
        prev = v
        while d >= 0x80:
            out.append((d & 0x7F) | 0x80)
            d >>= 7
        out.append(d)
    return bytes(out)


def varint_encode(vals) -> bytes:
    """Ascending uint64 -> delta + LEB128 bytes (posting-block codec)."""
    vals = _u64(vals)
    if len(vals) <= 16:
        # the ctypes FFI round-trip costs ~15us — for the tiny bitmaps the
        # inverted index writes per unique value, pure Python wins big
        return _varint_encode_py(vals)
    lib = _load()
    if lib is None:
        return _varint_encode_py(vals)
    out = np.empty(len(vals) * 10 or 1, dtype=np.uint8)
    n = lib.wn_varint_encode_u64(_ptr(vals, ctypes.c_uint64), len(vals),
                                 _ptr(out, ctypes.c_uint8))
    return out[:n].tobytes()


def varint_decode(buf: bytes, count_hint: int | None = None) -> np.ndarray:
    """Decode a varint-delta block. ``count_hint`` is the declared element
    count from the surrounding record; a block holding MORE values than
    declared raises (corrupt/truncated data) rather than over- or
    under-reading — the count field is untrusted on-disk input."""
    lib = None if len(buf) <= 32 else _load()  # FFI overhead > tiny decode
    if lib is None:
        out, prev, d, shift = [], 0, 0, 0
        for byte in buf:
            if shift > 63:
                raise ValueError("corrupt varint block: over-long varint")
            d |= (byte & 0x7F) << shift
            if byte & 0x80:
                shift += 7
            else:
                prev += d
                out.append(prev)
                d, shift = 0, 0
        if count_hint is not None and len(out) != count_hint:
            raise ValueError(
                f"corrupt varint block: {len(out)} values, "
                f"{count_hint} declared")
        return np.asarray(out, dtype=np.uint64)
    arr = np.ascontiguousarray(np.frombuffer(buf, dtype=np.uint8))
    # every value takes >= 1 byte, so len(buf) bounds the count — the
    # declared count is untrusted and must never size an allocation alone
    cap = len(buf) if count_hint is None else min(count_hint, len(buf))
    out = np.empty(max(cap, 1), dtype=np.uint64)
    n = lib.wn_varint_decode_u64(_ptr(arr, ctypes.c_uint8), len(arr),
                                 _ptr(out, ctypes.c_uint64), cap)
    if n < 0:
        raise ValueError("corrupt varint block: over-long varint")
    if count_hint is not None and n != count_hint:
        raise ValueError(
            f"corrupt varint block: {n} values, {count_hint} declared")
    return out[:n]


# ---- cross-shard top-k merge ----------------------------------------------


def merge_topk_host(dists: np.ndarray, ids: np.ndarray, k: int):
    """Merge [L, len] ascending per-shard candidates into global top-k.

    ids < 0 mark dead tail slots. Returns (dists [k] f32, ids [k] i64),
    padded with (3e38, -1). The host half of the scatter-gather reduce
    (reference: index.go:1644-1648) when shards answer over the network
    rather than over ICI."""
    dists = np.ascontiguousarray(np.asarray(dists, dtype=np.float32))
    ids = np.ascontiguousarray(np.asarray(ids, dtype=np.int64))
    if dists.ndim == 1:
        dists, ids = dists[None, :], ids[None, :]
    lib = _load()
    if lib is None:
        flat_d, flat_i = dists.ravel(), ids.ravel()
        live = flat_i >= 0
        flat_d, flat_i = flat_d[live], flat_i[live]
        order = np.argsort(flat_d, kind="stable")[:k]
        out_d = np.full(k, 3.0e38, dtype=np.float32)
        out_i = np.full(k, -1, dtype=np.int64)
        out_d[: len(order)] = flat_d[order]
        out_i[: len(order)] = flat_i[order]
        return out_d, out_i
    out_d = np.empty(k, dtype=np.float32)
    out_i = np.empty(k, dtype=np.int64)
    lib.wn_merge_topk(_ptr(dists, ctypes.c_float), _ptr(ids, ctypes.c_int64),
                      dists.shape[0], dists.shape[1], k,
                      _ptr(out_d, ctypes.c_float), _ptr(out_i, ctypes.c_int64))
    return out_d, out_i


# ---- batch storobj frame encoder ------------------------------------------


def storobj_encode_batch(uuid_strs: list[bytes], props_blobs: list[bytes],
                         vectors: np.ndarray, doc_ids: np.ndarray,
                         created_ms: np.ndarray, updated_ms: np.ndarray):
    """Encode N storage-object value frames (single unnamed vector each)
    in one native call; byte-identical to StorageObject.to_bytes.

    ``uuid_strs``: canonical-form uuid strings as bytes; ``props_blobs``:
    caller-msgpacked property dicts; ``vectors``: [n, dim] f32.
    Returns a list of ``bytes`` frames, or None when the native library
    is unavailable or a uuid fails the fast parse (callers fall back to
    the Python encoder).
    """
    lib = _load()
    if lib is None:
        return None
    n, dim = vectors.shape
    uuids = b"".join(uuid_strs)
    uoffs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(u) for u in uuid_strs], out=uoffs[1:])
    props = b"".join(props_blobs)
    poffs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum([len(b) for b in props_blobs], out=poffs[1:])
    # fixed part: 41 header + 4 n_vecs + 2 name_len + 4 dim + 4 props_len
    frame_offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.diff(poffs) + (55 + 4 * dim), out=frame_offs[1:])
    out = np.empty(int(frame_offs[-1]), dtype=np.uint8)
    vectors = np.ascontiguousarray(vectors, dtype=np.float32)
    doc_ids = np.ascontiguousarray(doc_ids, dtype=np.int64)
    created_ms = np.ascontiguousarray(created_ms, dtype=np.int64)
    updated_ms = np.ascontiguousarray(updated_ms, dtype=np.int64)
    ub = np.frombuffer(uuids, dtype=np.uint8) if uuids else \
        np.empty(0, np.uint8)
    pb = np.frombuffer(props, dtype=np.uint8) if props else \
        np.empty(0, np.uint8)
    rc = lib.wn_storobj_encode_batch(
        _ptr(ub, ctypes.c_uint8), _ptr(uoffs, ctypes.c_int64),
        _ptr(pb, ctypes.c_uint8), _ptr(poffs, ctypes.c_int64),
        _ptr(vectors, ctypes.c_float), ctypes.c_int32(dim),
        _ptr(doc_ids, ctypes.c_int64), _ptr(created_ms, ctypes.c_int64),
        _ptr(updated_ms, ctypes.c_int64), ctypes.c_int64(n),
        _ptr(out, ctypes.c_uint8), _ptr(frame_offs, ctypes.c_int64))
    if rc != 0:
        return None
    # one copy per frame (ndarray slices are views; .tobytes() on each
    # materializes just that frame — no whole-buffer duplicate)
    return [out[frame_offs[i]:frame_offs[i + 1]].tobytes()
            for i in range(n)]


# ---- Search reply encoder -------------------------------------------------

#: what a property's DataType is to the encoder (csrc ``T_*``): the kinds
#: ``api/grpc/server.py::_to_value`` tells apart; everything else it
#: writes (text, number, boolean and their arrays) is REPLY_OTHER
(REPLY_OTHER, REPLY_INT, REPLY_DATE, REPLY_UUID, REPLY_INT_ARRAY,
 REPLY_DATE_ARRAY, REPLY_UUID_ARRAY) = range(7)
#: the MetadataRequest as ``flags`` (csrc ``F_*``); REPLY_META: the
#: request carries one at all (without it a result has its id alone)
(REPLY_META, REPLY_ID, REPLY_VECTOR, REPLY_CREATED, REPLY_UPDATED,
 REPLY_DISTANCE, REPLY_CERTAINTY, REPLY_SCORE) = (1 << b for b in range(8))

_SPEC_HEAD = struct.Struct("<IfB")
_U16 = struct.Struct("<H")
_reply_encode = None


def _bind_reply_encoder() -> None:
    """``wn_search_reply_encode`` through a SECOND handle on the library
    that keeps the interpreter lock across the call (``PyDLL``): the call
    is tens of microseconds on a request thread, and a thread that lets
    the lock go under 32 others waits a switch interval or more to have
    it back (a ctypes merge cost a fan-out 20 ms a request that way on
    the chip's host: PERF.md section 6, PR 34)."""
    global _reply_encode
    fn = ctypes.PyDLL(_SO).wn_search_reply_encode
    # the int64 and double arrays arrive struct-packed, as bytes: a
    # tenth of what a ctypes array costs to fill
    fn.argtypes = [ctypes.POINTER(ctypes.c_char_p), ctypes.c_char_p,
                   ctypes.c_int64, ctypes.c_char_p, ctypes.c_char_p,
                   ctypes.c_char_p, ctypes.c_char_p,
                   ctypes.c_char_p, ctypes.c_int64,
                   ctypes.POINTER(ctypes.c_void_p)]
    fn.restype = ctypes.c_int64
    _reply_encode = fn


def _packed_names(names, kinds=None) -> bytes:
    parts = [_U16.pack(len(names))]
    for i, name in enumerate(names):
        raw = name.encode("utf-8")
        if kinds is not None:
            parts.append(bytes((kinds[i],)))
        parts += (_U16.pack(len(raw)), raw)
    return b"".join(parts)


def search_reply_spec(collection: str, flags: int, vectors, props,
                      wanted, took: float) -> bytes:
    """The request's and the class's part of a reply, as
    :func:`search_reply_encode` takes it: ``flags`` the REPLY_* bits of
    the MetadataRequest, ``vectors`` its named vectors, ``props`` the
    class's ``(name, REPLY_* kind)`` pairs, ``wanted`` the requested
    property names (None: all), ``took`` the reply's."""
    return b"".join((
        _SPEC_HEAD.pack(flags, took, wanted is None),
        _packed_names((collection,)), _packed_names(vectors),
        _packed_names([p[0] for p in props], [p[1] for p in props]),
        _packed_names(wanted or ())))


def _optional_doubles(values, n: int):
    """-> (double[n] packed, presence bytes) of a list of float-or-None."""
    if values is None:
        return None, None
    if None in values:
        return (struct.pack(f"<{n}d", *[v or 0.0 for v in values]),
                bytes([v is not None for v in values]))
    return struct.pack(f"<{n}d", *values), b"\x01" * n


def search_reply_encode(frames: list[bytes], spec: bytes,
                        distances=None, scores=None) -> bytes | None:
    """The bytes of a ``weaviate.v1.SearchReply`` over the stored
    ``frames`` (``StorageObject.to_bytes``) of a Search's results, in ONE
    native call: what ``_fill_result`` builds a result, field for field
    (its fallback, and the oracle of tests/test_reply_encoder.py), with
    no object, dict or message a result. ``distances`` / ``scores``: a
    float or None a frame. None where the library is absent, where a
    frame holds a value the encoder does not write (a map, a bin, a
    number under a date) and where a frame cannot be walked: the caller
    answers the WHOLE request by the Python path, which answers or
    raises as it always did."""
    if _load() is None:
        return None
    n = len(frames)
    dists, has_dist = _optional_doubles(distances, n)
    scrs, has_score = _optional_doubles(scores, n)
    out = ctypes.c_void_p()
    try:
        pointers = (ctypes.c_char_p * n)(*frames)
    except TypeError:  # a frame that is not ``bytes``: not ours to read
        return None
    size = _reply_encode(
        pointers, struct.pack(f"<{n}q", *map(len, frames)), n,
        dists, has_dist, scrs, has_score, spec, len(spec),
        ctypes.byref(out))
    # the bytes are the calling thread's until its next call
    return None if size < 0 else ctypes.string_at(out, size)


# ---- batch text analyzer --------------------------------------------------

_MODE_BY_TOKENIZATION = {"word": 0, "lowercase": 1, "whitespace": 2,
                         "field": 3}


def analyze_batch(values: list[str], tokenization: str):
    """Tokenize + accumulate a batch of ASCII text values in ONE native
    call (the import hot loop — reference inverted/analyzer.go per put).

    Returns (terms [list of str, sorted], entry_offs [nterms+1],
    entry_rows [E], entry_tfs [E], row_tokens [nrows]) — for each term,
    entries rows/tfs slice [entry_offs[t]:entry_offs[t+1]] give the value
    indices containing it and their term frequencies (rows ascending).
    Returns None when the native library is unavailable (callers fall
    back to the Python tokenizer).
    """
    lib = _load()
    if lib is None:
        return None
    mode = _MODE_BY_TOKENIZATION[tokenization]
    blob = "".join(values).encode("ascii")
    offs = np.zeros(len(values) + 1, dtype=np.int64)
    np.cumsum([len(v) for v in values], out=offs[1:])
    nterms = ctypes.c_int64()
    nentries = ctypes.c_int64()
    termbytes = ctypes.c_int64()
    blob_arr = np.frombuffer(blob, dtype=np.uint8) if blob else \
        np.zeros(1, dtype=np.uint8)
    lib.wn_analyze_batch(
        _ptr(np.ascontiguousarray(blob_arr), ctypes.c_uint8),
        _ptr(offs, ctypes.c_int64), len(values), mode,
        ctypes.byref(nterms), ctypes.byref(nentries), ctypes.byref(termbytes))
    nt, ne, tb = nterms.value, nentries.value, termbytes.value
    terms_blob = np.empty(max(tb, 1), dtype=np.uint8)
    term_offs = np.empty(nt + 1, dtype=np.int64)
    entry_offs = np.empty(nt + 1, dtype=np.int64)
    entry_rows = np.empty(max(ne, 1), dtype=np.int64)
    entry_tfs = np.empty(max(ne, 1), dtype=np.uint32)
    row_tokens = np.empty(max(len(values), 1), dtype=np.int64)
    lib.wn_analyze_fetch(
        _ptr(terms_blob, ctypes.c_uint8), _ptr(term_offs, ctypes.c_int64),
        _ptr(entry_offs, ctypes.c_int64), _ptr(entry_rows, ctypes.c_int64),
        _ptr(entry_tfs, ctypes.c_uint32), _ptr(row_tokens, ctypes.c_int64))
    raw = terms_blob.tobytes()
    # terms stay BYTES: every consumer (posting keys, cache keys) wants
    # prefix + term as bytes — decoding to str here forced an immediate
    # re-encode per term on the import hot path
    terms = [raw[term_offs[t]:term_offs[t + 1]] for t in range(nt)]
    return (terms, entry_offs, entry_rows[:ne], entry_tfs[:ne],
            row_tokens[:len(values)])


def varint_encode_many(arrays: list[np.ndarray]):
    """Encode many ascending-u64 blocks in one call.

    Returns list of bytes per block (Python fallback when no native lib).
    """
    lib = _load()
    if lib is None or not arrays:
        return [varint_encode(a) for a in arrays]
    concat = np.concatenate([_u64(a) for a in arrays]) if arrays else \
        np.empty(0, np.uint64)
    offs = np.zeros(len(arrays) + 1, dtype=np.int64)
    np.cumsum([len(a) for a in arrays], out=offs[1:])
    out = np.empty(max(int(offs[-1]) * 10, 1), dtype=np.uint8)
    lens = np.empty(len(arrays), dtype=np.int64)
    total = lib.wn_varint_encode_many(
        _ptr(np.ascontiguousarray(concat) if len(concat) else
             np.zeros(1, np.uint64), ctypes.c_uint64),
        _ptr(offs, ctypes.c_int64), len(arrays),
        _ptr(out, ctypes.c_uint8), _ptr(lens, ctypes.c_int64))
    blob = out[:total].tobytes()
    res = []
    pos = 0
    for n in lens.tolist():
        res.append(blob[pos:pos + n])
        pos += n
    return res


# ---- HNSW graph walker (csrc wn_hnsw_*) ----------------------------------

# engine/hnsw.py metric names -> native metric ids (csrc hnsw_dist)
_HNSW_METRIC_IDS = {"l2-squared": 0, "dot": 1, "cosine": 2, "cosine-dot": 2,
                    "manhattan": 3, "hamming": 4}


def hnsw_supported(metric: str) -> bool:
    return available() and metric in _HNSW_METRIC_IDS


class HnswNative:
    """Native mirror of an HNSW graph.

    The graph-search hot loop (reference search.go:173-341) runs in C++
    over a mirrored copy of the Python graph; engine/hnsw.py keeps the
    mirror current incrementally (_set_links / vector writes /
    tombstones) and re-uploads in one batched sync after bulk mutations.
    There is deliberately NO numpy fallback here — when the native lib
    is absent the engine keeps its original Python walker, which IS the
    fallback (and the conformance oracle in tests/test_hnsw.py).
    """

    def __init__(self, dim: int, metric: str):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.dim = int(dim)
        self._h = ctypes.c_void_p(
            lib.wn_hnsw_new(self.dim, _HNSW_METRIC_IDS[metric]))

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.wn_hnsw_free(self._h)
                self._h = None
        except Exception:
            pass

    def reset(self, cap: int):
        self._lib.wn_hnsw_reset(self._h, int(cap))

    def set_vectors(self, slot0: int, vecs: np.ndarray):
        vecs = np.ascontiguousarray(vecs, dtype=np.float32)
        if vecs.ndim == 1:
            vecs = vecs[None, :]
        self._lib.wn_hnsw_set_vectors(self._h, int(slot0), len(vecs),
                                      _ptr(vecs, ctypes.c_float))

    def set_links(self, slot: int, layer: int, neigh: np.ndarray):
        neigh = np.ascontiguousarray(neigh, dtype=np.int32)
        self._lib.wn_hnsw_set_links(self._h, int(slot), int(layer),
                                    len(neigh), _ptr(neigh, ctypes.c_int32))

    def set_links_batch(self, slots: np.ndarray, layers: np.ndarray,
                        counts: np.ndarray, neigh: np.ndarray):
        slots = np.ascontiguousarray(slots, dtype=np.int64)
        layers = np.ascontiguousarray(layers, dtype=np.int32)
        counts = np.ascontiguousarray(counts, dtype=np.int32)
        neigh = np.ascontiguousarray(neigh, dtype=np.int32)
        self._lib.wn_hnsw_set_links_batch(
            self._h, len(slots), _ptr(slots, ctypes.c_int64),
            _ptr(layers, ctypes.c_int32), _ptr(counts, ctypes.c_int32),
            _ptr(neigh, ctypes.c_int32))

    def clear_links(self, slot: int):
        self._lib.wn_hnsw_clear_links(self._h, int(slot))

    def set_tombstones(self, slots, val: bool = True):
        slots = np.ascontiguousarray(slots, dtype=np.int64)
        if len(slots) == 0:
            return
        self._lib.wn_hnsw_set_tombstones(self._h, _ptr(slots, ctypes.c_int64),
                                         len(slots), 1 if val else 0)

    def search_layer(self, q: np.ndarray, ef: int, layer: int,
                     ep_slots: np.ndarray, ep_dists: np.ndarray):
        """One-layer ef-search (insert path). Returns (dists, slots)
        ascending; tombstoned nodes included, as in the Python walker."""
        q = np.ascontiguousarray(q, dtype=np.float32)
        ep_slots = np.ascontiguousarray(ep_slots, dtype=np.int64)
        ep_dists = np.ascontiguousarray(ep_dists, dtype=np.float32)
        cap = int(ef) + len(ep_slots)
        out_s = np.empty(cap, dtype=np.int64)
        out_d = np.empty(cap, dtype=np.float32)
        n = self._lib.wn_hnsw_search_layer(
            self._h, _ptr(q, ctypes.c_float), int(ef), int(layer),
            _ptr(ep_slots, ctypes.c_int64), _ptr(ep_dists, ctypes.c_float),
            len(ep_slots), _ptr(out_s, ctypes.c_int64),
            _ptr(out_d, ctypes.c_float))
        return out_d[:n], out_s[:n]

    def search(self, q: np.ndarray, k: int, ef: int, ep: int,
               max_level: int, allow: np.ndarray | None = None):
        """Fused query search: greedy descent + layer-0 ef-search +
        live/allowed output filter. Returns (dists, slots) ascending."""
        q = np.ascontiguousarray(q, dtype=np.float32)
        out_s = np.empty(max(int(k), 1), dtype=np.int64)
        out_d = np.empty(max(int(k), 1), dtype=np.float32)
        if allow is not None:
            allow = np.ascontiguousarray(allow, dtype=np.uint8)
            ap = _ptr(allow, ctypes.c_uint8)
        else:
            ap = None
        n = self._lib.wn_hnsw_search(
            self._h, _ptr(q, ctypes.c_float), int(k), int(ef), int(ep),
            int(max_level), ap, _ptr(out_s, ctypes.c_int64),
            _ptr(out_d, ctypes.c_float))
        return out_d[:n], out_s[:n]


# ---- postings memtable (csrc wn_pt_*) ------------------------------------


def _keys_blob(keys: list[bytes]):
    blob = b"".join(keys)
    offs = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k) for k in keys], out=offs[1:])
    return np.frombuffer(blob, dtype=np.uint8) if blob else \
        np.zeros(1, np.uint8), offs


_EMPTY_U8 = None


def _empty_u8():
    global _EMPTY_U8
    if _EMPTY_U8 is None:
        _EMPTY_U8 = np.zeros(1, dtype=np.uint8)
    return _EMPTY_U8


class PostingsTable:
    """Native memtable for the "map" / "roaringset" LSM strategies.

    One instance backs one kv.py _Memtable; the Python dict memtable is
    the fallback (WEAVIATE_TPU_NO_NATIVE=1) and conformance oracle.
    Batched writes return the WAL frame payload produced in the same
    native call; reads come back as msgpack documents in the exact
    shapes kv.py _unpack_value produces.
    """

    def __init__(self, strategy: str):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        self.strategy = strategy
        self._h = ctypes.c_void_p(
            lib.wn_pt_new(0 if strategy == "map" else 1))

    def __del__(self):
        try:
            if getattr(self, "_h", None):
                self._lib.wn_pt_free(self._h)
                self._h = None
        except Exception:
            pass

    @property
    def bytes(self) -> int:
        return self._lib.wn_pt_bytes(self._h)

    def __len__(self) -> int:
        return self._lib.wn_pt_count(self._h)

    def _fetch(self, n: int) -> bytes:
        out = np.empty(max(n, 1), dtype=np.uint8)
        self._lib.wn_pt_fetch(_ptr(out, ctypes.c_uint8))
        return out[:n].tobytes()

    def map_columns(self, keys: list[bytes], entry_offs: np.ndarray,
                    docs: np.ndarray, tfs: np.ndarray, lens: np.ndarray,
                    prefix: bytes = b"", frame: bool = True) -> bytes | None:
        """Apply per-key postings columns; returns the "P" WAL frame."""
        kb, koffs = _keys_blob(keys)
        docs = np.ascontiguousarray(docs, dtype=np.int64)
        tfs = np.ascontiguousarray(tfs, dtype=np.uint32)
        lens = np.ascontiguousarray(lens, dtype=np.uint32)
        entry_offs = np.ascontiguousarray(entry_offs, dtype=np.int64)
        pfx = (np.frombuffer(prefix, dtype=np.uint8) if prefix
               else _empty_u8())
        n = self._lib.wn_pt_map_columns(
            self._h, _ptr(pfx, ctypes.c_uint8), len(prefix),
            _ptr(kb, ctypes.c_uint8), _ptr(koffs, ctypes.c_int64),
            len(keys), _ptr(entry_offs, ctypes.c_int64),
            _ptr(docs if len(docs) else np.zeros(1, np.int64),
                 ctypes.c_int64),
            _ptr(tfs if len(tfs) else np.zeros(1, np.uint32),
                 ctypes.c_uint32),
            _ptr(lens if len(lens) else np.zeros(1, np.uint32),
                 ctypes.c_uint32),
            1 if frame else 0)
        return self._fetch(n) if frame else None

    def map_delete(self, keys: list[bytes], entry_offs: np.ndarray,
                   del_docs: np.ndarray):
        kb, koffs = _keys_blob(keys)
        del_docs = np.ascontiguousarray(del_docs, dtype=np.int64)
        entry_offs = np.ascontiguousarray(entry_offs, dtype=np.int64)
        self._lib.wn_pt_map_delete(
            self._h, _ptr(_empty_u8(), ctypes.c_uint8), 0,
            _ptr(kb, ctypes.c_uint8), _ptr(koffs, ctypes.c_int64),
            len(keys), _ptr(entry_offs, ctypes.c_int64),
            _ptr(del_docs if len(del_docs) else np.zeros(1, np.int64),
                 ctypes.c_int64))

    def roar(self, keys: list[bytes], entry_offs: np.ndarray,
             ids: np.ndarray, is_del: bool = False, prefix: bytes = b"",
             frame: bool = True) -> bytes | None:
        """Apply per-key id blocks (unsorted ok); returns the "R" frame."""
        kb, koffs = _keys_blob(keys)
        ids = np.ascontiguousarray(ids, dtype=np.uint64)
        entry_offs = np.ascontiguousarray(entry_offs, dtype=np.int64)
        pfx = (np.frombuffer(prefix, dtype=np.uint8) if prefix
               else _empty_u8())
        n = self._lib.wn_pt_roar(
            self._h, _ptr(pfx, ctypes.c_uint8), len(prefix),
            _ptr(kb, ctypes.c_uint8), _ptr(koffs, ctypes.c_int64),
            len(keys), _ptr(entry_offs, ctypes.c_int64),
            _ptr(ids if len(ids) else np.zeros(1, np.uint64),
                 ctypes.c_uint64),
            1 if is_del else 0, 1 if frame else 0)
        return self._fetch(n) if frame else None

    def tomb(self, key: bytes):
        kb = np.frombuffer(key, dtype=np.uint8)
        self._lib.wn_pt_tomb(self._h, _ptr(kb, ctypes.c_uint8), len(key))

    def get_packed(self, key: bytes) -> bytes | None:
        """msgpack value for one key (kv.py _unpack_value shape), or None."""
        kb = np.frombuffer(key, dtype=np.uint8) if key else _empty_u8()
        n = self._lib.wn_pt_get(self._h, _ptr(kb, ctypes.c_uint8), len(key))
        if n < 0:
            return None
        return self._fetch(n)

    def packed_items(self, start: bytes | None = None,
                     stop: bytes | None = None):
        """Ascending (key, msgpack-value) pairs in [start, stop)."""
        sb = (np.frombuffer(start, dtype=np.uint8) if start
              else _empty_u8())
        tb = (np.frombuffer(stop, dtype=np.uint8) if stop
              else _empty_u8())
        n = self._lib.wn_pt_items(
            self._h, _ptr(sb, ctypes.c_uint8),
            len(start) if start is not None else -1,
            _ptr(tb, ctypes.c_uint8),
            len(stop) if stop is not None else -1)
        blob = self._fetch(n)
        out = []
        pos = 0
        while pos < len(blob):
            kl = int.from_bytes(blob[pos:pos + 4], "little")
            pos += 4
            k = blob[pos:pos + kl]
            pos += kl
            vl = int.from_bytes(blob[pos:pos + 4], "little")
            pos += 4
            out.append((k, blob[pos:pos + vl]))
            pos += vl
        return out
