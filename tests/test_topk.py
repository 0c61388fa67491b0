"""Tests for chunked/merged top-k selection."""

import numpy as np
import pytest
import jax.numpy as jnp

from weaviate_tpu.ops.topk import (
    chunked_topk,
    chunked_topk_distances,
    merge_topk,
    topk_smallest,
)


def brute_topk(q, x, k, metric="l2-squared"):
    """numpy reference in float64: (distances, ids) of the k nearest rows.
    Cosine rows are normalized at insert, the query inside the scan."""
    q, x = q.astype(np.float64), x.astype(np.float64)
    if metric == "l2-squared":
        d = ((q[:, None, :] - x[None, :, :]) ** 2).sum(-1)
    elif metric == "dot":
        d = -q @ x.T
    else:
        d = 1.0 - (q / np.linalg.norm(q, axis=1, keepdims=True)) @ x.T
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(d, ids, axis=1), ids


def test_topk_smallest_sorted(rng):
    d = rng.standard_normal((4, 50)).astype(np.float32)
    ids = np.arange(50, dtype=np.int32)
    td, ti = topk_smallest(jnp.asarray(d), jnp.asarray(ids), 5)
    td, ti = np.asarray(td), np.asarray(ti)
    assert (np.diff(td, axis=1) >= 0).all()
    want = np.sort(d, axis=1)[:, :5]
    np.testing.assert_allclose(td, want, rtol=1e-6)


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("metric", ["l2-squared", "dot", "cosine"])
@pytest.mark.parametrize("k", [1, 10, 37])
def test_chunked_topk_matches_bruteforce(rng, k, metric, use_pallas):
    """The served selector (``"approx"``: oversampled candidates a chunk,
    exact carry merge) against numpy, through the XLA distances and the
    Pallas distance tile (interpret mode here), four chunks."""
    from weaviate_tpu.ops.distances import normalize

    q = rng.standard_normal((5, 48)).astype(np.float32)
    x = rng.standard_normal((512, 48)).astype(np.float32)
    if metric == "cosine":
        x = np.asarray(normalize(jnp.asarray(x)))
    d, i = chunked_topk_distances(
        jnp.asarray(q), jnp.asarray(x), k=k, chunk_size=128, metric=metric,
        use_pallas=use_pallas, selection="approx")
    want_d, want_i = brute_topk(q, x, k, metric)
    np.testing.assert_array_equal(np.asarray(i), want_i)
    np.testing.assert_allclose(np.asarray(d), want_d, rtol=1e-3, atol=1e-3)


def test_chunked_topk_refuses_unknown_selection(rng):
    """The selector has two values; the in-kernel ``"fused"`` one went in
    PR 48 and must not run as something else in silence."""
    q = jnp.asarray(rng.standard_normal((1, 8)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32))
    with pytest.raises(ValueError, match="selection"):
        chunked_topk_distances(q, x, k=4, chunk_size=32, selection="fused")


@pytest.mark.parametrize("use_pallas", [False, True])
def test_chunked_topk_respects_valid_mask(rng, use_pallas):
    q = rng.standard_normal((3, 32)).astype(np.float32)
    x = rng.standard_normal((384, 32)).astype(np.float32)
    valid = rng.random(384) > 0.5
    d, i = chunked_topk_distances(
        jnp.asarray(q), jnp.asarray(x), k=8, chunk_size=128,
        valid=jnp.asarray(valid), use_pallas=use_pallas, selection="approx")
    i = np.asarray(i)
    assert valid[i].all()
    live = np.flatnonzero(valid)
    _, want = brute_topk(q, x[live], 8)
    np.testing.assert_array_equal(i, live[want])


@pytest.mark.parametrize("use_pallas", [False, True])
def test_chunked_topk_k_exceeds_live_rows(rng, use_pallas):
    """Slots past the live rows carry MASKED_DISTANCE: every consumer
    cuts there."""
    q = rng.standard_normal((2, 16)).astype(np.float32)
    x = rng.standard_normal((128, 16)).astype(np.float32)
    valid = np.zeros(128, dtype=bool)
    valid[:5] = True
    d, i = chunked_topk_distances(
        jnp.asarray(q), jnp.asarray(x), k=9, chunk_size=64,
        valid=jnp.asarray(valid), use_pallas=use_pallas, selection="approx")
    d, i = np.asarray(d), np.asarray(i)
    assert (i[:, :5] >= 0).all() and (i[:, :5] < 5).all()
    assert (d[:, :5] < 1e37).all() and (d[:, 5:] > 1e37).all()


@pytest.mark.parametrize("use_pallas", [False, True])
def test_id_offset(rng, use_pallas):
    q = rng.standard_normal((1, 8)).astype(np.float32)
    x = rng.standard_normal((16, 8)).astype(np.float32)
    x = np.concatenate([x, x])  # exact duplicates -> distance ties
    _, i = chunked_topk_distances(
        jnp.asarray(q), jnp.asarray(x), k=6, chunk_size=32, id_offset=1000,
        use_pallas=use_pallas, selection="approx")
    _, want = brute_topk(q, x, 6)
    # ties break to the lower row id, as the stable reference does
    np.testing.assert_array_equal(np.asarray(i), want + 1000)


def test_merge_topk(rng):
    # simulate two shards' partial top-k
    d1 = np.array([[0.1, 0.5, 0.9]], dtype=np.float32)
    i1 = np.array([[3, 7, 9]], dtype=np.int32)
    d2 = np.array([[0.2, 0.3, 1.5]], dtype=np.float32)
    i2 = np.array([[100, 101, 102]], dtype=np.int32)
    d, i = merge_topk(jnp.concatenate([jnp.asarray(d1), jnp.asarray(d2)], axis=1),
                      jnp.concatenate([jnp.asarray(i1), jnp.asarray(i2)], axis=1), 4)
    np.testing.assert_allclose(np.asarray(d)[0], [0.1, 0.2, 0.3, 0.5], rtol=1e-6)
    assert list(np.asarray(i)[0]) == [3, 100, 101, 7]


def test_chunked_topk_indivisible_n(rng):
    # regression: N not a multiple of chunk_size must pad, not collapse to one chunk
    q = rng.standard_normal((2, 16)).astype(np.float32)
    x = rng.standard_normal((101, 16)).astype(np.float32)
    d, i = chunked_topk(jnp.asarray(q), jnp.asarray(x), k=5, chunk_size=32)
    i = np.asarray(i)
    assert (i < 101).all() and (i >= 0).all()
    want = np.argsort(((q[:, None] - x[None]) ** 2).sum(-1), axis=1)[:, :5]
    assert set(i[0]) == set(want[0])
