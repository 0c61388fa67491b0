"""Ask the v5e's compiler, without a chip, whether the served path's kernels
compile at real widths.

The only file in the repo that describes a TPU. Everything that touches the
described topology lives inside the module-scoped fixtures below — nothing
at import, not ``autouse``, not in ``conftest.py`` — because only one
process may hold the TPU library and every xdist worker imports every test
file. A compile that passes here is not a chip run: nothing executes, no
result or time is checked. ``chip_smoke.py`` is the chip run.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from weaviate_tpu.ops import pallas_kernels as pk

N = 1 << 20  # 1M corpus rows: the served flat collection's scale


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable is written to the persistent cache but
    # cannot be read back without a chip; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """``jit(fn).lower(shapes).compile()`` with every shape on ``sharding``;
    raises what the chip's compiler would raise."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("d", [128, 1536])
def test_distance_block(one_chip, dtype, d):
    fn = functools.partial(pk.distance_block, metric="l2-squared",
                           interpret=False)
    _assert_kernel(_compile(fn, one_chip, ((64, d), dtype), ((8192, d), dtype)))


def test_chunked_topk_approx_1m(one_chip, monkeypatch):
    from weaviate_tpu.ops.topk import chunked_topk_distances

    # the call's interpret=None asks recommended(); the process sees the CPU,
    # so steer it here, in the test, to the branch a TPU process takes
    monkeypatch.setattr(pk, "recommended", lambda: True)
    fn = functools.partial(chunked_topk_distances, k=10, chunk_size=8192,
                           metric="l2-squared", use_pallas=True,
                           selection="approx")
    c = _compile(lambda q, x, v, n: fn(q, x, valid=v, x_sq_norms=n), one_chip,
                 ((64, 128), jnp.bfloat16), ((N, 128), jnp.bfloat16),
                 ((N,), jnp.bool_), ((N,), jnp.float32))
    _assert_kernel(c)


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "allow_bits"])
@pytest.mark.parametrize("b,k,d", [(8, 10, 128), (64, 100, 128), (64, 10, 768)])
def test_chunked_topk_served(one_chip, monkeypatch, masked, b, k, d):
    """The scan every flat store serves (Pallas distance tile, ``"approx"``
    candidates a chunk, exact carry merge), plain and under per-query
    allow bitmasks."""
    from weaviate_tpu.ops.topk import chunked_topk_distances

    monkeypatch.setattr(pk, "recommended", lambda: True)

    def fn(q, x, v, n, *bits):
        return chunked_topk_distances(
            q, x, k=k, chunk_size=8192, metric="l2-squared", valid=v,
            x_sq_norms=n, use_pallas=True, selection="approx",
            allow_bits=bits[0] if bits else None)

    shapes = [((b, d), jnp.bfloat16), ((N, d), jnp.bfloat16),
              ((N,), jnp.bool_), ((N,), jnp.float32)]
    if masked:
        shapes.append(((b, N // 32), jnp.uint32))
    _assert_kernel(_compile(fn, one_chip, *shapes))


def test_shared_candidates_topk_batch(one_chip, monkeypatch):
    """The store's gathered cutover at its widest in ``sift-flat-l2``:
    ONE filter of capacity / 8 rows shared by a padded batch of 32."""
    from weaviate_tpu.ops.candidates import shared_candidates_topk

    monkeypatch.setattr(pk, "recommended", lambda: True)
    rows, d, bucket = 262144, 128, 32768
    fn = functools.partial(shared_candidates_topk, k=10,
                           metric="l2-squared", use_pallas=True,
                           selection="approx")
    c = _compile(lambda q, slots, x, norms, valid: fn(
        q, slots, x, row_norms=norms, valid=valid), one_chip,
        ((32, d), jnp.float32), ((bucket,), jnp.int32),
        ((rows, d), jnp.float32), ((rows,), jnp.float32),
        ((rows,), jnp.bool_))
    _assert_kernel(c)
    assert not re.search(rf"= f32\[{rows},{d}\][^ ]* copy\(", c.as_text())


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "allow_bits"])
@pytest.mark.parametrize("b,d", [(8, 128), (64, 128), (64, 768)])
def test_bq_scan_reduce(one_chip, masked, b, d):
    w = d // 32

    def fn(q, x, v, *bits):
        return pk.bq_scan_reduce(q, x, valid=v, interpret=False,
                                 allow_bits=bits[0] if bits else None)

    shapes = [((b, w), jnp.uint32), ((N, w), jnp.uint32), ((N,), jnp.bool_)]
    if masked:
        shapes.append(((b, N // 32), jnp.uint32))
    _assert_kernel(_compile(fn, one_chip, *shapes))


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "allow_bits"])
@pytest.mark.parametrize("b,d", [(8, 128), (64, 128), (64, 768)])
def test_pq4_scan_reduce(one_chip, masked, b, d):
    m = d // 4  # 4 dims per segment, the store's pq4 default

    def fn(lut, codes, v, *bits):
        return pk.pq4_scan_reduce(lut, codes, valid=v, interpret=False,
                                  allow_bits=bits[0] if bits else None)

    shapes = [((b, m, 16), jnp.float32), ((N, m), jnp.uint8),
              ((N,), jnp.bool_)]
    if masked:
        shapes.append(((b, N // 32), jnp.uint32))
    _assert_kernel(_compile(fn, one_chip, *shapes))


# the served PQ path of a `flat` + `pq` class (ISSUE 28) at the
# deep-pq-cosine configuration's widths: 96 segments x 256 centroids over
# 96 dims. XLA programs, no Pallas kernel: what is asked is whether the
# chip's compiler takes them at these shapes without materialising the
# [rows, segments, centroids] intermediate (6.4 GB at 65,536 rows).
PQ_M, PQ_K, PQ_D = 96, 256, 96


@pytest.mark.parametrize("rows", [4096, 65536],
                         ids=["import-batch", "training-sample"])
def test_pq_encode_and_fit_at_96_segments(one_chip, rows):
    from weaviate_tpu.ops import pq

    book = ((PQ_M, PQ_K, PQ_D // PQ_M), jnp.float32)
    for fn in (functools.partial(pq._assign, m=PQ_M),
               functools.partial(pq._lloyd_step, m=PQ_M, k=PQ_K)):
        c = _compile(fn, one_chip, ((rows, PQ_D), jnp.float32), book)
        assert c.memory_analysis().temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("b", [1, 8, 32])
@pytest.mark.parametrize("m,ds", [(PQ_M, 1), (PQ_D // 8, 8)],
                         ids=["m=96", "m=12-default"])
def test_pq_topk_at_96_segments(one_chip, monkeypatch, m, ds, b):
    """The served 8-bit scan at the cell's geometry and at this tree's
    default one (d/8 segments of 8 dims): the chip's compiler takes it with
    the look-up kernel in it, no [chunk, segments, centroids] one-hot and no
    reconstructed chunk is left in HBM, and no XLA gather over the chunk's
    codes (the 249-ms disease, ISSUE 29) is left in the optimised program."""
    from weaviate_tpu.ops.pq import pq_topk

    # the process sees the CPU: steer the look-up to the branch a TPU takes
    monkeypatch.setattr(pk, "recommended", lambda: True)
    rows, chunk = 262144, 8192

    def fn(q, codes, cent, valid):
        return pq_topk(q, codes, cent, k=160, chunk_size=chunk,
                       metric="cosine", valid=valid)

    c = _compile(fn, one_chip, ((b, PQ_D), jnp.float32),
                 ((rows, m), jnp.uint8), ((m, PQ_K, ds), jnp.float32),
                 ((rows,), jnp.bool_))
    assert c.memory_analysis().temp_size_in_bytes < 256 << 20
    text = c.as_text()
    assert "pq8_lookup" in text and "tpu_custom_call" in text
    for shape in re.findall(r"= \w+\[([\d,]*)\][^ ]* gather\(", text):
        elements = int(np.prod([int(x) for x in shape.split(",") if x]))
        assert elements < chunk * m, f"a gather over [{shape}] is left"


SQ_D = 960                 # gist-sq-l2's width


@pytest.mark.parametrize("rows", [1024, 65536],
                         ids=["one-import-batch", "one-compress-batch"])
def test_sq_encode_at_960_dims(one_chip, rows):
    """What a write of the sq store runs (an import batch of 1,024; a
    piece of the compression's encode stage): float32 rows in, encoded by
    ``sq_encode`` and scattered, codes and terms, into the resident arrays,
    which are donated: no second copy of the codes."""
    from weaviate_tpu.engine.quantized import _scatter_sq_rows
    from weaviate_tpu.ops import sq

    cap = 262144
    for metric in ("l2-squared", "cosine"):
        c = _compile(functools.partial(sq.sq_encode, metric=metric), one_chip,
                     ((rows, SQ_D), jnp.float32), ((3,), jnp.float32))
        assert len(c.output_shardings) == 2 and "s8[" in c.as_text()
        c = _compile(functools.partial(_scatter_sq_rows, metric=metric),
                     one_chip, ((cap, SQ_D), jnp.int8), ((cap,), jnp.int32),
                     ((cap,), jnp.bool_), ((rows,), jnp.int32),
                     ((rows, SQ_D), jnp.float32), ((rows,), jnp.bool_),
                     ((3,), jnp.float32))
        # the v5e compiler scatters int8 rows through ONE relaid-out copy
        # of the codes (1,024 lanes a row), as it does pq's uint8 codes:
        # a write's cost, 0.3 ms of HBM traffic, never a scan's
        assert c.memory_analysis().temp_size_in_bytes < 2 * cap * 1024


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "allow_bits"])
@pytest.mark.parametrize("b", [1, 2, 4, 8, 16, 32])
def test_sq_topk_at_960_dims(one_chip, masked, b):
    """The served scan of ``gist-sq-l2.c32`` at every padded batch: the
    chip's compiler takes the int8 x int8 -> int32 contraction (at b = 1
    it turns the matrix-vector product into an int32 multiply-and-sum on
    the vector unit: as exact), no contraction runs in floats, and neither
    a [b, rows] score matrix nor a copy of the codes is left in HBM (a
    [chunks, chunk, d] view of them cost 268 MB a dispatch: the chip lays
    an int8 [rows, 960] out with the rows on the lanes)."""
    from weaviate_tpu.ops.sq import sq_topk

    rows, chunk = 262144, 8192

    def fn(q, codes, terms, params, valid, bits=None):
        return sq_topk(q, codes, terms, params, k=160, chunk_size=chunk,
                       metric="l2-squared", valid=valid, allow_bits=bits)

    shapes = [((b, SQ_D), jnp.float32), ((rows, SQ_D), jnp.int8),
              ((rows,), jnp.int32), ((3,), jnp.float32),
              ((rows,), jnp.bool_)]
    if masked:
        shapes.append(((b, rows // 32), jnp.uint32))
    c = _compile(fn, one_chip, *shapes)
    # the unpacked allow rows are the masked form's, as in pq_topk
    assert c.memory_analysis().temp_size_in_bytes < (256 if masked else 16) << 20
    text = c.as_text()
    dots = re.findall(r"= (\w+)\[[\d,]*\][^ ]* (?:convolution|dot)\(", text)
    assert set(dots) <= {"s32"} and (dots or b == 1), dots


@pytest.mark.parametrize("b", [1, 16, 32])
@pytest.mark.parametrize("cell", ["cohere-bq-cosine", "deep-pq-cosine",
                                  "gist-sq-l2"])
def test_served_scans_with_the_rescore_tail(one_chip, monkeypatch, cell, b):
    """The three compressed cells' programs as a ``Server`` runs them
    (ISSUE 38): the scan ENDS with the gather of its candidates' float32
    rows, the exact distances and the final top-k. The chip lays a
    float32 [262144, 960] (and a [262144, 96]) out with the ROWS on the
    lanes and a program that gathers rows from one copies ALL of it
    first, every dispatch; rows as wide as whole lanes
    (``engine/quantized.py _row_lanes``) arrive row-major: no copy of
    them is left and the program's temporaries stay small."""
    from weaviate_tpu.engine.quantized import _row_lanes
    from weaviate_tpu.ops.bq import bq_topk
    from weaviate_tpu.ops.pq import pq_topk
    from weaviate_tpu.ops.sq import sq_topk

    monkeypatch.setattr(pk, "recommended", lambda: True)
    f32, chunk = jnp.float32, 8192
    if cell == "cohere-bq-cosine":
        rows, d, k, kc = 131072, 768, 100, 1600

        def fn(q, codes, valid, rr):
            return bq_topk(q[:, :24].astype(jnp.uint32), codes, k=kc,
                           chunk_size=chunk, valid=valid, use_pallas=True,
                           rescore_q=q, rescore_rows=rr, rescore_k=k,
                           rescore_metric="cosine")

        shapes = [((b, d), f32), ((rows, 24), jnp.uint32)]
    elif cell == "deep-pq-cosine":
        rows, d, k, kc = 262144, PQ_D, 10, 160

        def fn(q, codes, cent, valid, rr):
            return pq_topk(q, codes, cent, k=kc, chunk_size=chunk,
                           metric="cosine", valid=valid, rescore_rows=rr,
                           rescore_k=k)

        shapes = [((b, d), f32), ((rows, PQ_M), jnp.uint8),
                  ((PQ_M, PQ_K, 1), f32)]
    else:
        rows, d, k, kc = 262144, SQ_D, 10, 160

        def fn(q, codes, terms, params, valid, rr):
            return sq_topk(q, codes, terms, params, k=kc, chunk_size=chunk,
                           metric="l2-squared", valid=valid,
                           rescore_rows=rr, rescore_k=k)

        shapes = [((b, d), f32), ((rows, d), jnp.int8),
                  ((rows,), jnp.int32), ((3,), f32)]
    lanes = _row_lanes(d)
    c = _compile(fn, one_chip, *shapes, ((rows,), jnp.bool_),
                 ((rows, lanes), f32))
    text = c.as_text()
    assert text.count(f"[{b},{k}]{{1,0") >= 2         # [b, k] goes back
    assert f"f32[{rows},{lanes}]{{1,0" in text        # row-major, as it lies
    assert not re.search(rf"= f32\[{rows},{lanes}\][^ ]* copy\(", text)
    # the gathered candidates ([b, kc, lanes]) are the largest temporary
    assert c.memory_analysis().temp_size_in_bytes < \
        (64 << 20) + 2 * b * kc * lanes * 4


@pytest.mark.parametrize("b", [1, 16])
def test_ivf_probe_at_the_dynamic_cells_shapes(one_chip, b):
    """``glove-dynamic-cosine.c32``'s probe program (ISSUES 43, 47) as
    the served store launches it: 1,024 lists of 512 positions x 100
    float32, 128 lists probed a query, a chunk of at most 16 queries. The
    chip's compiler takes it, the probed lists are gathered as whole
    slabs (``[b * 128, 512, 100]``: one block a list, not one row a
    position) and its temporaries are that block ONCE beside the list
    tensor's one layout copy: a later PR that changes the gather is held
    here first."""
    from weaviate_tpu.engine.ivf import _ivf_probe_topk

    nlist, cap, d, nprobe, k = 1024, 512, 100, 128, 10
    f32 = jnp.float32

    def fn(q, cents, c_norms, vecs, valid, slots, norms, bits):
        return _ivf_probe_topk(q, cents, c_norms, vecs, valid, slots, norms,
                               bits, k, nprobe, "cosine", False)

    c = _compile(fn, one_chip, ((b, d), f32), ((nlist, d), f32),
                 ((nlist,), f32), ((nlist, cap, d), f32),
                 ((nlist, cap), jnp.bool_), ((nlist, cap), jnp.int32),
                 ((nlist, cap), f32), ((1, 16), jnp.uint32))
    text = c.as_text()
    assert text.count(f"[{b},{k}]{{1,0") >= 2          # [b, k] goes back
    assert f"f32[{b * nprobe},{cap},{d}]" in text       # the slab gather
    gathered = b * nprobe * cap * 128 * 4
    assert c.memory_analysis().temp_size_in_bytes < \
        (nlist * cap * 128 * 4) + gathered + (64 << 20)


@pytest.mark.parametrize("bucket", [4096, 32768])
def test_ivf_exact_route_at_the_filtered_cells_shapes(one_chip, bucket):
    """``cohere-dynamic-cosine.filtered-c32``'s exact route under
    ``flatSearchCutoff`` (ISSUE 51) as the served store launches it: a
    block of 32 queries, a slot list of 4,096 or 32,768 (the mix's 1 % and
    10 % filters at 262,144 rows), the slot map over 262,144 slots, lists
    of 1,024 x 512 x 768 float32 and the delta buffer's 16,384 rows. The
    chip's compiler takes it, copies neither the list tensor nor its
    layout, and holds at most the rows it gathers from the lists and
    from the delta beside its arguments."""
    from weaviate_tpu.engine.ivf import _ivf_flat_cutoff_topk

    nlist, cap, d, k, b = 1024, 512, 768, 128, 32
    f32 = jnp.float32

    def fn(q, slots, slot_map, vecs, delta_vecs):
        return _ivf_flat_cutoff_topk(q, slots, slot_map, vecs, delta_vecs,
                                     k, "cosine")

    c = _compile(fn, one_chip, ((b, d), f32), ((bucket,), jnp.int32),
                 ((262144,), jnp.int32), ((nlist, cap, d), f32),
                 ((16384, d), f32))
    text = c.as_text()
    assert text.count(f"[{b},{k}]{{1,0") >= 2          # [b, k] goes back
    assert not re.search(
        rf"= f32\[(?:{nlist},{cap}|{nlist * cap}),{d}\][^ ]* copy\(", text)
    assert c.memory_analysis().temp_size_in_bytes <= \
        2 * bucket * d * 4 + (8 << 20)


@pytest.mark.parametrize("b_pad", [1, 16, 32])
def test_filtered_dispatch_programs(one_chip, monkeypatch, b_pad):
    """The filtered cell's two programs since PR 40, at its shapes
    (262,144 x 128 f32): a coalesced dispatch stacks ``b_pad`` packed
    rows that already lie on the device, and a solo dispatch is ONE
    program that gathers its slot list's rows and scans them. Neither
    may copy the corpus: what they hold beyond their arguments is the
    stacked block, or the gathered bucket."""
    from weaviate_tpu.engine.store import stack_allow_rows
    from weaviate_tpu.ops.candidates import shared_candidates_topk

    monkeypatch.setattr(pk, "recommended", lambda: True)
    rows, d, words = 262144, 128, pk.mask_pad_cols(262144) // 32
    c = _compile(stack_allow_rows, one_chip,
                 *[((words,), jnp.uint32)] * b_pad)
    assert f"u32[{b_pad},{words}]" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes <= b_pad * words * 4
    if b_pad > 1:
        return                      # the solo path dispatches one query
    bucket, k = 4096, 16            # 2,621 allowed rows' pow2 bucket
    fn = functools.partial(shared_candidates_topk, k=k,
                           metric="l2-squared", use_pallas=True,
                           selection="approx")
    c = _compile(lambda q, slots, x, norms, valid: fn(
        q, slots, x, row_norms=norms, valid=valid), one_chip,
        ((1, d), jnp.float32), ((bucket,), jnp.int32),
        ((rows, d), jnp.float32), ((rows,), jnp.float32),
        ((rows,), jnp.bool_))
    text = c.as_text()
    assert not re.search(rf"= f32\[{rows},{d}\][^ ]* copy\(", text)
    assert c.memory_analysis().temp_size_in_bytes < 4 * bucket * d * 4


def test_bq_mxu_block(one_chip):
    fn = functools.partial(pk.bq_mxu_block, interpret=False)
    _assert_kernel(_compile(fn, one_chip, ((64, 24), jnp.uint32),
                            ((8192, 24), jnp.uint32)))


def test_pq4_lut_block(one_chip):
    fn = functools.partial(pk.pq4_lut_block, interpret=False)
    _assert_kernel(_compile(fn, one_chip, ((64, 32, 16), jnp.float32),
                            ((8192, 32), jnp.uint8)))


def test_bm25_block(one_chip):
    b, s, t, c = 32, 8, 4, 8192
    fn = functools.partial(pk.bm25_block, interpret=False)
    f32 = jnp.float32
    _assert_kernel(_compile(
        fn, one_chip,
        ((b, s, c), f32), ((b, s, c), f32), ((b, s), jnp.int32),
        ((b, s), f32), ((b, s), f32), ((b, t), f32),
        ((b,), f32), ((b,), f32), ((b,), f32), ((b, c // 32), jnp.uint32)))


def test_sharded_topk_four_chips(topo, monkeypatch):
    from weaviate_tpu.parallel.mesh import SHARD_AXIS
    from weaviate_tpu.parallel.sharded_search import _sharded_topk_jit

    monkeypatch.setattr(pk, "recommended", lambda: True)
    mesh = Mesh(np.array(topo.devices), (SHARD_AXIS,))
    rows = NamedSharding(mesh, PartitionSpec(SHARD_AXIS))
    repl = NamedSharding(mesh, PartitionSpec())
    n = 4 * N
    sds = jax.ShapeDtypeStruct
    c = _sharded_topk_jit.lower(
        sds((64, 128), jnp.bfloat16, sharding=repl),
        sds((n, 128), jnp.bfloat16, sharding=rows),
        sds((n,), jnp.bool_, sharding=rows),
        sds((n,), jnp.float32, sharding=rows),
        k=10, chunk_size=8192, metric="l2-squared", mesh=mesh,
        use_pallas=True, selection="approx").compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" in text
