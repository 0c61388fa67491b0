"""Mesh-sharded quantized stores (VERDICT r2 item 1 — the north-star
unblock): BQ/PQ codes row-sharded over the 8-device virtual mesh, SPMD
scan + owning-device rescore, vs single-device ground truth.

Reference: compression is per-shard state (hnsw/compress.go:38 inside
usecases/sharding/state.go:28), so compressed classes shard for free — here
that composition must hold on a device mesh.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from weaviate_tpu.engine.flat import FlatIndex
from weaviate_tpu.engine.quantized import QuantizedVectorStore
from weaviate_tpu.parallel import make_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def _clustered(rng, n, d, k=64, spread=0.25):
    centers = rng.standard_normal((k, d)).astype(np.float32)
    out = centers[rng.integers(0, k, n)] + spread * rng.standard_normal(
        (n, d)).astype(np.float32)
    return out.astype(np.float32)


@pytest.mark.parametrize("quantization", ["bq", "pq"])
@pytest.mark.parametrize("rescore", ["host", "device"])
def test_sharded_quantized_recall_vs_exact(rng, quantization, rescore):
    """Sharded compressed scan + exact rescore vs f32 brute force.

    (The sharded and single-replica paths aren't bit-identical by design:
    per-device candidate sets cover different row subsets — each is gated
    against exact ground truth instead.)"""
    mesh = make_mesh(8)
    n, d, k = 512, 64, 10
    # gaussian corpus + near-duplicate queries: the regime where hamming
    # candidate ranking is informative (tightly clustered corpora saturate
    # 64-bit hamming with ties — a quantizer property, not a sharding one)
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    q = (vecs[rng.integers(0, n, 8)]
         + 0.1 * rng.standard_normal((8, d))).astype(np.float32)
    gt = np.argsort(((q[:, None] - vecs[None]) ** 2).sum(-1), axis=1)[:, :k]

    sharded = QuantizedVectorStore(
        dim=d, quantization=quantization, capacity=n, chunk_size=32,
        mesh=mesh, rescore=rescore)
    single = QuantizedVectorStore(
        dim=d, quantization=quantization, capacity=n, chunk_size=32)
    if quantization == "pq":
        sharded.train(vecs)
        single.train(vecs)
    sharded.add(vecs)
    single.add(vecs)

    d_sh, i_sh = sharded.search(q, k)
    d_si, i_si = single.search(q, k)
    rec_sh = np.mean([len(set(i_sh[r]) & set(gt[r])) / k for r in range(len(q))])
    rec_si = np.mean([len(set(i_si[r]) & set(gt[r])) / k for r in range(len(q))])
    # parity gate: sharding must not degrade the quantizer's recall
    # (absolute recall at this dim/data is a quantizer property — the
    # recall at scale is what benchmarks/ checks on the chip)
    assert rec_sh >= rec_si - 0.05, (quantization, rescore, rec_sh, rec_si)
    assert rec_sh >= 0.5, (quantization, rescore, rec_sh)
    # top-1 after exact rescore must match ground truth everywhere
    assert np.array_equal(i_sh[:, 0], gt[:, 0])
    # rescored distances are exact -> ascending
    assert np.all(np.diff(d_sh, axis=1) >= -1e-4)


def test_sharded_quantized_delete_and_update(rng):
    mesh = make_mesh(8)
    n, d = 256, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    store = QuantizedVectorStore(dim=d, quantization="bq", capacity=n,
                                 chunk_size=16, mesh=mesh, rescore="device")
    store.add(vecs)
    d0, i0 = store.search(vecs[7], k=3)
    assert i0[0] == 7
    store.delete([7])
    d1, i1 = store.search(vecs[7], k=3)
    assert 7 not in i1
    # update: slot 9 becomes a copy of (deleted) row 7's vector
    store.set_at([9], vecs[7][None, :])
    d2, i2 = store.search(vecs[7], k=1)
    assert i2[0] == 9 and d2[0] < 1e-2


def test_sharded_quantized_allow_mask(rng):
    mesh = make_mesh(8)
    n, d = 256, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    store = QuantizedVectorStore(dim=d, quantization="bq", capacity=n,
                                 chunk_size=16, mesh=mesh, rescore="device")
    store.add(vecs)
    allow = np.zeros(n, dtype=bool)
    allow[100:120] = True
    _, ids = store.search(vecs[3], k=5, allow_mask=allow)
    assert all(100 <= i < 120 for i in ids if i >= 0)


def test_sharded_flat_index_quantized(rng):
    """FlatIndex(mesh=..., quantization=...) — the VERDICT done-criterion."""
    mesh = make_mesh(8)
    n, d = 320, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = FlatIndex(dim=d, mesh=mesh, quantization="bq", capacity=n,
                    chunk_size=16, rescore="device")
    idx.add_batch(np.arange(n) + 1000, vecs)
    ids, dists = idx.search_by_vector(vecs[50], k=5)
    assert ids[0] == 1050
    idx.delete(1050)
    ids, _ = idx.search_by_vector(vecs[50], k=5)
    assert 1050 not in ids


def test_sharded_runtime_compress(rng):
    """Runtime compress() of a mesh-sharded uncompressed index
    (reference hnsw/compress.go:38 under a sharded class)."""
    mesh = make_mesh(8)
    n, d = 320, 16
    vecs = _clustered(rng, n, d)
    idx = FlatIndex(dim=d, mesh=mesh, capacity=n, chunk_size=16)
    idx.add_batch(np.arange(n), vecs)
    ids_before, _ = idx.search_by_vector(vecs[33], k=10)
    idx.compress(quantization="pq", rescore="device")
    assert idx.compressed
    ids_after, dists = idx.search_by_vector(vecs[33], k=10)
    assert ids_after[0] == 33
    # recall gate: compressed+rescored top-10 keeps >=8 of the exact set
    assert len(set(ids_before) & set(ids_after)) >= 8


def test_sharded_quantized_none_rescore_with_fetch(rng):
    """Codes-only residency (capacity regime) + fetch_fn exact rescore
    from 'durable storage'."""
    mesh = make_mesh(8)
    n, d = 256, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    store = QuantizedVectorStore(
        dim=d, quantization="bq", capacity=n, chunk_size=16, mesh=mesh,
        rescore="none", fetch_fn=lambda ids: vecs[np.clip(ids, 0, n - 1)])
    store.add(vecs)
    assert store._host_vectors is None and store.rescore_rows is None
    d0, i0 = store.search(vecs[11], k=3)
    assert i0[0] == 11 and d0[0] < 1e-6


def test_sharded_quantized_snapshot_restore(rng):
    mesh = make_mesh(8)
    n, d = 256, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    store = QuantizedVectorStore(dim=d, quantization="bq", capacity=n,
                                 chunk_size=16, mesh=mesh, rescore="device")
    store.add(vecs)
    store.delete([5])
    snap = store.snapshot()
    back = QuantizedVectorStore.restore(snap, mesh=mesh)
    d0, i0 = back.search(vecs[99], k=1)
    assert i0[0] == 99
    _, i1 = back.search(vecs[5], k=3)
    assert 5 not in i1
