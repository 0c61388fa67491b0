"""Sharded (multi-device) search over the virtual 8-CPU-device mesh.

Exercises the SPMD path the driver's dryrun validates: row-sharded corpus,
per-device top-k, ICI all_gather merge — vs single-device ground truth.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.engine.store import DeviceVectorStore
from weaviate_tpu.ops.topk import chunked_topk
from weaviate_tpu.parallel import make_mesh, sharded_topk
from weaviate_tpu.parallel.sharded_search import shard_array, replicate_array

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def test_sharded_topk_matches_single_device(rng):
    mesh = make_mesh(8)
    n, d, b, k = 1024, 32, 4, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    valid = np.ones(n, dtype=bool)
    valid[::5] = False

    xs = shard_array(jnp.asarray(x), mesh)
    vs = shard_array(jnp.asarray(valid), mesh)
    qs = replicate_array(jnp.asarray(q), mesh)
    d_sh, i_sh = sharded_topk(qs, xs, vs, None, k=k, chunk_size=128,
                              metric="l2-squared", mesh=mesh)

    d_ref, i_ref = chunked_topk(jnp.asarray(q), jnp.asarray(x), k=k,
                                chunk_size=128, valid=jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(d_sh), np.asarray(d_ref), rtol=1e-4, atol=1e-4)
    assert np.array_equal(np.asarray(i_sh), np.asarray(i_ref))


def test_sharded_store_end_to_end(rng):
    mesh = make_mesh(8)
    store = DeviceVectorStore(dim=16, capacity=256, chunk_size=32, mesh=mesh)
    vecs = rng.standard_normal((100, 16)).astype(np.float32)
    store.add(vecs)
    d, i = store.search(vecs[42], k=5)
    assert i[0] == 42 and d[0] < 1e-3
    store.delete([42])
    d, i = store.search(vecs[42], k=5)
    assert i[0] != 42


def test_sharded_flat_index(rng):
    mesh = make_mesh(8)
    idx = FlatIndex(dim=16, capacity=256, chunk_size=32, mesh=mesh)
    vecs = rng.standard_normal((64, 16)).astype(np.float32)
    idx.add_batch(np.arange(64) + 500, vecs)
    ids, dists = idx.search_by_vector(vecs[10], k=3)
    assert ids[0] == 510

    # results identical to unsharded index on same data
    idx1 = FlatIndex(dim=16, capacity=256, chunk_size=256)
    idx1.add_batch(np.arange(64) + 500, vecs)
    q = rng.standard_normal(16).astype(np.float32)
    ids_a, d_a = idx.search_by_vector(q, k=8)
    ids_b, d_b = idx1.search_by_vector(q, k=8)
    assert list(ids_a) == list(ids_b)
    np.testing.assert_allclose(d_a, d_b, rtol=1e-4, atol=1e-4)


def test_sharded_growth(rng):
    mesh = make_mesh(8)
    store = DeviceVectorStore(dim=8, capacity=16, chunk_size=8, mesh=mesh)
    vecs = rng.standard_normal((200, 8)).astype(np.float32)
    store.add(vecs)
    d, i = store.search(vecs[150], k=1)
    assert i[0] == 150
