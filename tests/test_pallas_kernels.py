"""Conformance tests for the Pallas distance kernels.

Run through the Pallas interpreter on CPU — identical semantics to the
compiled TPU path. Verified against the canonical XLA implementations in
ops.distances (which are themselves verified against numpy), mirroring the
reference's asm-vs-pure-Go distancer tests (distancer/*_test.go).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from weaviate_tpu.ops.distances import MASKED_DISTANCE, normalize, pairwise_distance
from weaviate_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


@pytest.mark.parametrize("metric", ["l2-squared", "dot", "cosine"])
@pytest.mark.parametrize("shape", [(3, 128, 512), (5, 96, 300), (1, 17, 40)])
def test_distance_block_matches_xla(rng, metric, shape):
    b, d, n = shape
    q = rng.standard_normal((b, d), dtype=np.float32)
    x = rng.standard_normal((n, d), dtype=np.float32)
    if metric == "cosine":
        x = np.asarray(normalize(jnp.asarray(x)))
    got = pk.distance_block(jnp.asarray(q), jnp.asarray(x), metric=metric, interpret=True)
    want = pairwise_distance(jnp.asarray(q), jnp.asarray(x), metric=metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-3)


def test_distance_block_masks_invalid(rng):
    q = rng.standard_normal((2, 64), dtype=np.float32)
    x = rng.standard_normal((200, 64), dtype=np.float32)
    valid = np.ones(200, dtype=bool)
    valid[::3] = False
    got = np.asarray(
        pk.distance_block(
            jnp.asarray(q), jnp.asarray(x), valid=jnp.asarray(valid), interpret=True
        )
    )
    assert (got[:, ~valid] >= MASKED_DISTANCE * 0.99).all()
    want = np.asarray(pairwise_distance(jnp.asarray(q), jnp.asarray(x)))
    np.testing.assert_allclose(got[:, valid], want[:, valid], rtol=2e-4, atol=2e-3)


def test_distance_block_precomputed_norms(rng):
    q = rng.standard_normal((4, 128), dtype=np.float32)
    x = rng.standard_normal((512, 128), dtype=np.float32)
    xn = jnp.sum(jnp.asarray(x) ** 2, axis=1)
    got = pk.distance_block(
        jnp.asarray(q), jnp.asarray(x), x_sq_norms=xn, interpret=True
    )
    want = pairwise_distance(jnp.asarray(q), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-3)


def test_distance_block_bf16_storage(rng):
    q = rng.standard_normal((2, 128), dtype=np.float32)
    x = rng.standard_normal((256, 128), dtype=np.float32)
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    got = np.asarray(pk.distance_block(jnp.asarray(q), xb, interpret=True))
    want = np.asarray(pairwise_distance(jnp.asarray(q), xb))
    # bf16 storage: compare against the XLA bf16 path, loose float tolerance.
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=1.0)


def test_bq_hamming_matches_numpy(rng):
    b, n, w = 3, 100, 4  # 4 uint32 words = 128 bits
    q = rng.integers(0, 2**32, size=(b, w), dtype=np.uint32)
    x = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
    got = np.asarray(pk.bq_hamming_block(jnp.asarray(q), jnp.asarray(x), interpret=True))
    want = np.zeros((b, n), dtype=np.float32)
    for i in range(b):
        for j in range(n):
            want[i, j] = bin(int.from_bytes((q[i] ^ x[j]).tobytes(), "little")).count("1")
    np.testing.assert_array_equal(got, want)


def test_unknown_metric_rejected():
    with pytest.raises(ValueError):
        pk.distance_block(jnp.zeros((1, 8)), jnp.zeros((4, 8)), metric="manhattan")


def test_recommended_is_bool():
    assert isinstance(pk.recommended(), bool)


def test_chunked_topk_pallas_path_matches(rng):
    """End-to-end: the scan + top-k path with the Pallas tile kernel enabled
    must return the same neighbors as the XLA path."""
    from weaviate_tpu.ops.topk import chunked_topk_distances

    q = jnp.asarray(rng.standard_normal((3, 64), dtype=np.float32))
    x = jnp.asarray(rng.standard_normal((1024, 64), dtype=np.float32))
    valid = jnp.asarray(rng.random(1024) > 0.1)
    d0, i0 = chunked_topk_distances(q, x, k=10, chunk_size=256, valid=valid)
    d1, i1 = chunked_topk_distances(
        q, x, k=10, chunk_size=256, valid=valid, use_pallas=True
    )
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_allclose(np.asarray(d0), np.asarray(d1), rtol=2e-4, atol=2e-3)


def test_bq_topk_pallas_path_matches(rng):
    from weaviate_tpu.ops import bq as bq_ops

    x = jnp.asarray(rng.standard_normal((512, 64)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((3, 64)).astype(np.float32))
    xw, qw = bq_ops.bq_encode(x), bq_ops.bq_encode(q)
    d0, i0 = bq_ops.bq_topk(qw, xw, k=8, chunk_size=128)
    d1, i1 = bq_ops.bq_topk(qw, xw, k=8, chunk_size=128, use_pallas=True)
    # the pallas path routes candidates through approx_max_k (exact on CPU,
    # 0.95-recall-per-call on real TPU), so require a recall floor plus
    # self-consistency (every returned id carries its true hamming) rather
    # than bit-identical sets
    ham = bq_ops.bq_hamming_np(
        np.ascontiguousarray(np.asarray(qw)),
        np.ascontiguousarray(np.asarray(xw)))
    overlap = 0
    for r in range(i0.shape[0]):
        np.testing.assert_array_equal(
            ham[r, np.asarray(i1)[r]], np.asarray(d1)[r].astype(np.int64))
        overlap += len(set(np.asarray(i0)[r].tolist())
                       & set(np.asarray(i1)[r].tolist()))
    assert overlap >= int(0.75 * i0.shape[0] * 8)


def test_bq_scan_reduce_strided_argmin(rng):
    """v3 scan kernel: packed-merge correctness incl. validity, both
    orientations (interpret mode — compiled conformance runs in bench)."""
    from weaviate_tpu.ops import bq as bq_ops
    from weaviate_tpu.ops.pallas_kernels import bq_scan_reduce

    for (b, n, d, L, tp) in [(8, 2000, 128, 32, False),
                             (5, 700, 96, 8, True),
                             (6, 9000, 768, 64, False),
                             (3, 130, 64, 4, False)]:
        v = rng.standard_normal((n, d)).astype(np.float32)
        q = rng.standard_normal((b, d)).astype(np.float32)
        xw = np.asarray(bq_ops.bq_encode(jnp.asarray(v)))
        qw = np.asarray(bq_ops.bq_encode(jnp.asarray(q)))
        valid = rng.random(n) > 0.3
        xin = jnp.asarray(np.ascontiguousarray(xw.T)) if tp else jnp.asarray(xw)
        vals, ids = bq_scan_reduce(jnp.asarray(qw), xin,
                                   valid=jnp.asarray(valid),
                                   reduce_l=L, interpret=True, transposed=tp)
        vals, ids = np.asarray(vals), np.asarray(ids)
        ham = bq_ops.bq_hamming_np(
            np.ascontiguousarray(qw), np.ascontiguousarray(xw)
        ).astype(np.float32)
        ham[:, ~valid] = np.inf
        for r in range(b):
            live = vals[r] < 1e20
            # every surviving candidate self-consistent + global min kept
            np.testing.assert_array_equal(ham[r, ids[r][live]], vals[r][live])
            assert ham[r].min() == vals[r][live].min()
            assert not np.any(~valid[ids[r][live]])


def test_bq_topk_twostage_matches_full(rng):
    from weaviate_tpu.ops import bq as bq_ops

    n, d, b = 20000, 512, 6
    centers = rng.standard_normal((500, d)).astype(np.float32)
    v = (centers[rng.integers(0, 500, n)]
         + 0.3 * rng.standard_normal((n, d))).astype(np.float32)
    q = (v[rng.integers(0, n, b)]
         + 0.05 * rng.standard_normal((b, d))).astype(np.float32)
    xw = bq_ops.bq_encode(jnp.asarray(v))
    qw = bq_ops.bq_encode(jnp.asarray(q))
    wp = 4  # 128-bit prefix
    xp_t = jnp.asarray(np.ascontiguousarray(np.asarray(xw)[:, :wp].T))
    d_full, i_full = bq_ops.bq_topk(qw, xw, k=10, chunk_size=2000)
    for use_pallas in (True, False):
        d2, i2 = bq_ops.bq_topk_twostage(qw, xw, xp_t, k=10, refine=16,
                                         use_pallas=use_pallas)
        rec = np.mean([
            len(set(np.asarray(i_full)[r].tolist())
                & set(np.asarray(i2)[r].tolist())) / 10
            for r in range(b)])
        assert rec >= 0.85, f"two-stage recall {rec} (use_pallas={use_pallas})"
        # returned distances are true full-width hammings
        ham = bq_ops.bq_hamming_np(
            np.ascontiguousarray(np.asarray(qw)),
            np.ascontiguousarray(np.asarray(xw)))
        for r in range(b):
            ii = np.asarray(i2)[r]
            np.testing.assert_array_equal(
                ham[r, ii[ii >= 0]],
                np.asarray(d2)[r][ii >= 0].astype(np.int64))


def test_quantized_store_prefix_twostage(rng):
    from weaviate_tpu.engine.quantized import QuantizedVectorStore

    n, d = 6000, 256
    centers = rng.standard_normal((200, d)).astype(np.float32)
    v = (centers[rng.integers(0, 200, n)]
         + 0.35 * rng.standard_normal((n, d))).astype(np.float32)
    q = (v[rng.integers(0, n, 5)]
         + 0.05 * rng.standard_normal((5, d))).astype(np.float32)
    gt = np.argsort(
        (q ** 2).sum(-1)[:, None] - 2.0 * q @ v.T + (v ** 2).sum(-1)[None, :],
        axis=1)[:, :10]
    st = QuantizedVectorStore(dim=d, quantization="bq", prefix_bits=128,
                              rescore="host", capacity=1024)
    st.use_pallas = True  # interpret-mode kernels on CPU
    st.add(v)
    assert st.prefix_t is not None and st.prefix_t.shape[0] == 4
    dd, ii = st.search(q, k=10)
    rec = np.mean([len(set(ii[r]) & set(gt[r])) / 10 for r in range(5)])
    assert rec >= 0.9
    # snapshot -> restore keeps the prefix and the results
    st2 = QuantizedVectorStore.restore(st.snapshot())
    st2.use_pallas = True
    assert st2.prefix_t is not None
    dd2, ii2 = st2.search(q, k=10)
    np.testing.assert_array_equal(ii, ii2)
    # a too-wide prefix is refused (would exceed the code width)
    st3 = QuantizedVectorStore(dim=96, quantization="bq", prefix_bits=128)
    assert st3.prefix_t is None
    st3.add(rng.standard_normal((50, 96)).astype(np.float32))  # must not crash


# -- 8-bit PQ look-up (pq8_lookup_block) -------------------------------------

import pq_reference  # noqa: E402 — the plain numpy quantizer, tests/


def _pq8_case(rng, m, ds, k, n):
    cent = rng.standard_normal((m, k, ds)).astype(np.float32)
    cent[0, 0, 0] = -0.0  # a value is moved, not summed: the sign survives
    codes = rng.integers(0, k, (n, m)).astype(np.uint8)
    codes[0] = 0
    codes[1] = k - 1
    return cent, codes, pq_reference.reconstruct(cent, codes)


@pytest.mark.parametrize("n", [1024, 300], ids=["lane-aligned", "ragged"])
@pytest.mark.parametrize("m,ds,k", pq_reference.GEOMETRIES_8BIT)
def test_pq8_lookup_block_is_the_table_lookup(rng, m, ds, k, n):
    """The lane-gather kernel (interpret mode) against numpy's
    ``centroids[s, codes[:, s]]`` and against its jnp twin, bit for bit."""
    from weaviate_tpu.ops.pq import pq_reconstruct

    cent, codes, want = _pq8_case(rng, m, ds, k, n)
    got = np.asarray(pk.pq8_lookup_block(jnp.asarray(codes),
                                         jnp.asarray(cent), interpret=True))
    assert got.dtype == np.float32 and got.shape == want.shape
    assert got.tobytes() == want.tobytes()
    twin = np.asarray(pq_reconstruct(jnp.asarray(codes), jnp.asarray(cent), m))
    assert twin.tobytes() == want.tobytes()


def test_pq8_lookup_block_splits_wide_rows(rng):
    """Over 512 dimensions the sublane axis is cut into blocks of its own
    grid axis (768 -> 2 x 384), each with its rows of the table."""
    cent, codes, want = _pq8_case(rng, 96, 8, 256, 256)
    got = np.asarray(pk.pq8_lookup_block(jnp.asarray(codes),
                                         jnp.asarray(cent), interpret=True))
    assert got.tobytes() == want.tobytes()


def test_pq8_lookup_block_refuses_more_than_256_levels(rng):
    with pytest.raises(ValueError, match="k <= 256"):
        pk.pq8_lookup_block(jnp.zeros((8, 4), jnp.uint8),
                            jnp.zeros((4, 257, 2), jnp.float32),
                            interpret=True)


@pytest.mark.parametrize("metric", ["l2-squared", "dot", "cosine"])
def test_pq_topk_with_the_kernel_matches_its_twin(rng, metric, monkeypatch):
    """The whole scan with the kernel (interpret mode) in the place its
    twin has on the CPU: same candidates, same distances."""
    from weaviate_tpu.ops import pq as pq_ops

    m, ds, k, n = 12, 8, 256, 1024
    cent, codes, _ = _pq8_case(rng, m, ds, k, n)
    q = rng.standard_normal((3, m * ds)).astype(np.float32)
    valid = rng.random(n) > 0.1
    args = (jnp.asarray(q), jnp.asarray(codes), jnp.asarray(cent))
    kw = dict(k=20, chunk_size=256, metric=metric, valid=jnp.asarray(valid))
    want_d, want_i = pq_ops.pq_topk.__wrapped__(*args, **kw)
    monkeypatch.setattr(
        pq_ops, "_rows_from_codes",
        lambda c, t: pk.pq8_lookup_block(c, t, interpret=True))
    got_d, got_i = pq_ops.pq_topk.__wrapped__(*args, **kw)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))
