"""Runtime compression hooks (VERDICT r2 item 9).

HNSWIndex.compress() — ADC traversal + exact rescore (reference
hnsw/compress.go:38-89) — and the schema config-update path that flips a
LIVE class to compressed (config_update.go) with a recall gate.
"""

import numpy as np
import pytest

from weaviate_tpu.db.database import Database
from weaviate_tpu.engine.hnsw import HNSWIndex
from weaviate_tpu.schema.config import (CollectionConfig, Property,
                                        VectorConfig)


def _clustered(rng, n, d, k=64, spread=0.3):
    centers = rng.standard_normal((k, d)).astype(np.float32)
    return (centers[rng.integers(0, k, n)]
            + spread * rng.standard_normal((n, d))).astype(np.float32)


def test_hnsw_runtime_compress_recall(rng):
    n, d = 4000, 32
    vecs = _clustered(rng, n, d)
    idx = HNSWIndex(dim=d, capacity=n, flat_cutoff=0, ef=96)
    idx.BULK_BUILD_MIN = 1024
    idx.add_batch(np.arange(n), vecs)
    q = (vecs[rng.integers(0, n, 60)]
         + 0.05 * rng.standard_normal((60, d))).astype(np.float32)
    before = [idx.search_by_vector(q[r], k=10)[0] for r in range(60)]
    assert not idx.compressed
    idx.compress("pq")
    assert idx.compressed
    after = [idx.search_by_vector(q[r], k=10)[0] for r in range(60)]
    overlap = np.mean([
        len(set(before[r].tolist()) & set(after[r].tolist())) / 10
        for r in range(60)])
    # recall gate vs the uncompressed graph's own results
    assert overlap >= 0.9, overlap
    # exact rescore: top-1 self-hit distance is exact f32, ~0
    ids, dists = idx.search_by_vector(vecs[5], k=1)
    assert ids[0] == 5 and dists[0] < 1e-4


def test_hnsw_compress_then_insert_delete(rng):
    n, d = 2000, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = HNSWIndex(dim=d, capacity=n, flat_cutoff=0)
    idx.BULK_BUILD_MIN = 1024
    idx.add_batch(np.arange(n), vecs)
    idx.compress("pq")
    # inserts encode into the code array
    idx.add(777, vecs[3] + 1e-3)
    ids, _ = idx.search_by_vector(vecs[3], k=3)
    assert 777 in ids.tolist()
    idx.delete(3)
    ids, _ = idx.search_by_vector(vecs[3], k=3)
    assert 3 not in ids.tolist()


def test_hnsw_compress_persistence(tmp_path, rng):
    n, d = 1500, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    idx = HNSWIndex(dim=d, capacity=n, flat_cutoff=0,
                    commit_log_dir=str(tmp_path))
    idx.BULK_BUILD_MIN = 1024
    idx.add_batch(np.arange(n), vecs)
    idx.compress("pq")
    idx.close()
    back = HNSWIndex(dim=d, capacity=n, flat_cutoff=0,
                     commit_log_dir=str(tmp_path))
    assert back.compressed
    ids, dists = back.search_by_vector(vecs[42], k=3)
    assert ids[0] == 42 and dists[0] < 1e-4


@pytest.mark.parametrize("quantization", ["pq", "sq"])
def test_config_update_compresses_live_class(tmp_path, rng, quantization):
    """The reference lifecycle: PUT schema with pq.enabled (or sq.enabled)
    on a LIVE class (config_update.go) → index trains + swaps in place,
    recall gated."""
    db = Database(str(tmp_path))
    col = db.create_collection(CollectionConfig(
        name="Things", properties=[Property(name="t", data_type="text")],
        vectors=[VectorConfig()]))
    n, d = 600, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    import uuid as uuidlib
    uuids = [str(uuidlib.uuid4()) for _ in range(n)]
    for i in range(n):
        col.put_object({"t": f"x{i}"}, vector=vecs[i], uuid=uuids[i])
    res_before = col.near_vector(vecs[50], k=10)
    ids_before = {r.uuid for r in res_before}

    import copy
    new_cfg = copy.deepcopy(col.config)
    new_cfg.vectors[0].index.quantization = quantization
    # the class holds 600 rows: a trainingLimit it has already passed
    setattr(new_cfg.vectors[0].index, f"{quantization}_training_limit", 512)
    db.update_collection(new_cfg)

    shard = list(col.shards.values())[0]
    idx = next(iter(shard.vector_indexes.values()))
    assert idx.compressed and idx.store.quantization == quantization
    res_after = col.near_vector(vecs[50], k=10)
    ids_after = {r.uuid for r in res_after}
    assert res_after[0].uuid == uuids[50]
    assert len(ids_before & ids_after) >= 8
    # disabling is rejected (one-way door)
    new_cfg2 = copy.deepcopy(col.config)
    new_cfg2.vectors[0].index.quantization = None
    with pytest.raises(ValueError, match="cannot be disabled"):
        db.update_collection(new_cfg2)
    db.close()


def test_query_lut_matches_device_pq_lut(rng):
    """The numpy ADC table (_query_lut) must stay equal to the device
    pq_lut it twins (ops/pq.py) for every supported metric."""
    import jax.numpy as jnp

    from weaviate_tpu.ops.pq import pq_fit, pq_lut

    n, d = 400, 16
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    for metric in ("l2-squared", "dot", "cosine"):
        idx = HNSWIndex(dim=d, metric=metric, capacity=n, flat_cutoff=0)
        idx.BULK_BUILD_MIN = 10 ** 9
        book = pq_fit(vecs, m=4, k=16)
        idx._pq_codebook = book
        q = idx._norm(rng.standard_normal(d).astype(np.float32))
        host = idx._query_lut(q)
        dev = np.asarray(pq_lut(jnp.asarray(q[None]), book.centroids,
                                metric, 4))[0]
        np.testing.assert_allclose(host, dev, rtol=1e-4, atol=1e-5)
