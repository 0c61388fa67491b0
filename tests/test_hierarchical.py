"""Two-level ICI+DCN merge parity (ISSUE 13 tentpole acceptance).

The 8-device virtual CPU mesh doubles as a 2x4 "two-host pod"
(make_hierarchical_mesh(n_hosts=2)): the ``host`` axis stands in for
DCN, ``ici`` for the in-host interconnect. Every SPMD search path —
flat / BQ / PQ4 / IVF, unfiltered / shared-valid / per-query-bitmask —
must return BIT-IDENTICAL (distances AND ids) results on the
hierarchical mesh vs the legacy 1-D merge: exact top-k is mergeable,
and both merges derive the same candidate tie order (host-major concat,
level-1-sorted within host — sharded_search._two_level_merge_topk
docstring has the argument).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from weaviate_tpu.ops import bq as bq_ops
from weaviate_tpu.parallel.mesh import make_hierarchical_mesh, make_mesh
from weaviate_tpu.parallel.sharded_search import (
    merge_dcn_candidate_bytes,
    replicate_array,
    shard_array,
    sharded_quantized_topk,
    sharded_topk,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices"
)


def _meshes():
    return make_mesh(8), make_hierarchical_mesh(n_hosts=2)


def _place(mesh, x, valid, q, allow=None):
    out = {
        "x": shard_array(jnp.asarray(x), mesh),
        "valid": shard_array(jnp.asarray(valid), mesh),
        "q": replicate_array(jnp.asarray(q), mesh),
    }
    if allow is not None:
        out["allow"] = shard_array(jnp.asarray(allow), mesh, dim=1)
    return out


def _assert_bit_identical(a, b, what=""):
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]),
                                  err_msg=f"{what}: distances diverge")
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]),
                                  err_msg=f"{what}: ids diverge")


@pytest.mark.parametrize("filtered", ["none", "shared", "per_query"])
def test_flat_two_level_parity(rng, filtered):
    flat, hier = _meshes()
    n, d, b, k = 1024, 32, 4, 10
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    valid = np.ones(n, dtype=bool)
    if filtered == "shared":
        valid[::5] = False
    allow = (rng.random((b, n)) > 0.4) if filtered == "per_query" else None

    outs = []
    for mesh in (flat, hier):
        p = _place(mesh, x, valid, q, allow)
        outs.append(sharded_topk(
            p["q"], p["x"], p["valid"], None, k=k, chunk_size=128,
            metric="l2-squared", mesh=mesh,
            allow_rows=p.get("allow")))
    _assert_bit_identical(outs[0], outs[1], f"flat/{filtered}")


def test_flat_two_level_parity_k_exceeds_live(rng):
    """k wider than the live candidate pool: the inf-padded DCN slices
    must never displace a real or masked candidate."""
    flat, hier = _meshes()
    n, d, b, k = 256, 16, 2, 64
    x = rng.standard_normal((n, d)).astype(np.float32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    valid = np.zeros(n, dtype=bool)
    valid[:40] = True  # 40 live rows << b*k asked
    outs = []
    for mesh in (flat, hier):
        p = _place(mesh, x, valid, q)
        outs.append(sharded_topk(
            p["q"], p["x"], p["valid"], None, k=k, chunk_size=32,
            metric="l2-squared", mesh=mesh))
    _assert_bit_identical(outs[0], outs[1], "flat/k>live")


@pytest.mark.parametrize("filtered", ["none", "per_query"])
def test_bq_two_level_parity(rng, filtered):
    flat, hier = _meshes()
    n, dim, b, k = 1024, 128, 4, 16
    xb = rng.standard_normal((n, dim)).astype(np.float32)
    qv = rng.standard_normal((b, dim)).astype(np.float32)
    codes = np.asarray(bq_ops.bq_encode(jnp.asarray(xb)))
    qw = np.asarray(bq_ops.bq_encode(jnp.asarray(qv)))
    valid = np.ones(n, dtype=bool)
    valid[::9] = False
    allow = (rng.random((b, n)) > 0.3) if filtered == "per_query" else None
    outs = []
    for mesh in (flat, hier):
        kw = {}
        if allow is not None:
            kw["allow_rows"] = shard_array(jnp.asarray(allow), mesh,
                                           dim=1)
        outs.append(sharded_quantized_topk(
            replicate_array(jnp.asarray(qv), mesh),
            replicate_array(jnp.asarray(qw), mesh),
            shard_array(jnp.asarray(codes), mesh),
            shard_array(jnp.asarray(valid), mesh),
            None, None, k=k, k_out=k, chunk_size=128, quantization="bq",
            metric="l2-squared", mesh=mesh, **kw))
    _assert_bit_identical(outs[0], outs[1], f"bq/{filtered}")


def test_bq_two_level_parity_with_rescore(rng):
    """BQ + owning-device exact rescore: the rescored (f32) candidates
    ride the same two-level merge."""
    flat, hier = _meshes()
    n, dim, b, k = 1024, 64, 4, 8
    xb = rng.standard_normal((n, dim)).astype(np.float32)
    qv = rng.standard_normal((b, dim)).astype(np.float32)
    codes = np.asarray(bq_ops.bq_encode(jnp.asarray(xb)))
    qw = np.asarray(bq_ops.bq_encode(jnp.asarray(qv)))
    valid = np.ones(n, dtype=bool)
    rescore = xb.astype(np.float32)
    outs = []
    for mesh in (flat, hier):
        outs.append(sharded_quantized_topk(
            replicate_array(jnp.asarray(qv), mesh),
            replicate_array(jnp.asarray(qw), mesh),
            shard_array(jnp.asarray(codes), mesh),
            shard_array(jnp.asarray(valid), mesh),
            shard_array(jnp.asarray(rescore), mesh),
            None, k=32, k_out=k, chunk_size=128, quantization="bq",
            metric="l2-squared", mesh=mesh))
    _assert_bit_identical(outs[0], outs[1], "bq/rescore")


@pytest.mark.parametrize("filtered", ["none", "per_query"])
def test_pq4_two_level_parity(rng, filtered):
    from weaviate_tpu.ops import pq as pq_ops

    flat, hier = _meshes()
    n, dim, b, k = 512, 32, 4, 12
    xb = rng.standard_normal((n, dim)).astype(np.float32)
    qv = rng.standard_normal((b, dim)).astype(np.float32)
    codebook = pq_ops.pq_fit(xb, m=8, k=16)  # 16 centroids = pq4 regime
    codes = np.asarray(pq_ops.pq_encode(codebook, xb))
    cent = np.asarray(codebook.centroids)
    valid = np.ones(n, dtype=bool)
    allow = (rng.random((b, n)) > 0.3) if filtered == "per_query" else None
    outs = []
    for mesh in (flat, hier):
        kw = {}
        if allow is not None:
            kw["allow_rows"] = shard_array(jnp.asarray(allow), mesh,
                                           dim=1)
        outs.append(sharded_quantized_topk(
            replicate_array(jnp.asarray(qv), mesh), None,
            shard_array(jnp.asarray(codes), mesh),
            shard_array(jnp.asarray(valid), mesh),
            None, replicate_array(jnp.asarray(cent), mesh),
            k=k, k_out=k, chunk_size=128, quantization="pq4",
            metric="l2-squared", mesh=mesh, **kw))
    _assert_bit_identical(outs[0], outs[1], f"pq4/{filtered}")


def test_bq_compact_dcn_block_ids_match(rng):
    """WEAVIATE_TPU_DCN_COMPACT wire format (bf16 distance + uint32
    slot): BQ hamming counts at dim<=256 are bf16-exact, so even the
    compacted hop stays bit-identical."""
    flat, hier = _meshes()
    n, dim, b, k = 1024, 128, 4, 16
    xb = rng.standard_normal((n, dim)).astype(np.float32)
    qv = rng.standard_normal((b, dim)).astype(np.float32)
    codes = np.asarray(bq_ops.bq_encode(jnp.asarray(xb)))
    qw = np.asarray(bq_ops.bq_encode(jnp.asarray(qv)))
    valid = np.ones(n, dtype=bool)
    outs = []
    for mesh, compact in ((flat, False), (hier, True)):
        outs.append(sharded_quantized_topk(
            replicate_array(jnp.asarray(qv), mesh),
            replicate_array(jnp.asarray(qw), mesh),
            shard_array(jnp.asarray(codes), mesh),
            shard_array(jnp.asarray(valid), mesh),
            None, None, k=k, k_out=k, chunk_size=128, quantization="bq",
            metric="l2-squared", mesh=mesh, dcn_compact=compact))
    _assert_bit_identical(outs[0], outs[1], "bq/compact")


def test_ivf_two_level_parity(rng):
    from weaviate_tpu.parallel.sharded_search import sharded_ivf_pq_topk

    flat, hier = _meshes()
    nlist, cap, m, d, b, k = 32, 16, 8, 32, 4, 10
    cent = rng.standard_normal((nlist, d)).astype(np.float32)
    codes = rng.integers(0, 255, (nlist, cap, m)).astype(np.uint8)
    valid = rng.random((nlist, cap)) > 0.2
    slots = np.arange(nlist * cap, dtype=np.int32).reshape(nlist, cap)
    tvals = rng.standard_normal((nlist, cap)).astype(np.float32)
    pqc = rng.standard_normal((m, 256, d // m)).astype(np.float32)
    q = rng.standard_normal((b, d)).astype(np.float32)
    outs = []
    for mesh in (flat, hier):
        outs.append(sharded_ivf_pq_topk(
            replicate_array(jnp.asarray(q), mesh),
            shard_array(jnp.asarray(cent), mesh),
            shard_array(jnp.asarray(codes), mesh),
            shard_array(jnp.asarray(valid), mesh),
            shard_array(jnp.asarray(slots), mesh),
            shard_array(jnp.asarray(tvals), mesh),
            replicate_array(jnp.asarray(pqc), mesh),
            k=k, nprobe=4, metric="l2-squared", mesh=mesh))
    _assert_bit_identical(outs[0], outs[1], "ivf")


def test_device_store_on_hierarchical_mesh(rng):
    """End to end: DeviceVectorStore placed on the 2x4 mesh serves the
    same results as on the flat mesh, and the ledger's host rollup sees
    the sharded arrays split across both hosts."""
    from weaviate_tpu.engine.store import DeviceVectorStore
    from weaviate_tpu.runtime.hbm_ledger import ledger

    flat, hier = _meshes()
    vecs = rng.standard_normal((200, 16)).astype(np.float32)
    qs = vecs[[3, 77]]
    res = []
    for mesh in (flat, hier):
        store = DeviceVectorStore(dim=16, capacity=512, chunk_size=32,
                                  mesh=mesh)
        assert store.n_shards == 8
        store.add(vecs)
        dd, ii = store.search(qs, k=5)
        res.append((np.asarray(dd), np.asarray(ii)))
        del store
    _assert_bit_identical(res[0], res[1], "store e2e")
    roll = ledger.host_rollup(2)
    assert sum(roll.values()) == ledger.total_bytes()


def test_dcn_candidate_bytes_scale_with_hosts_not_devices():
    """Acceptance: per-host DCN candidate traffic is O(hosts*k), not
    O(devices*k) — on the 2x4 mesh the two-level merge ships 1/n_local
    of the flat merge's bytes (k chosen ICI-divisible so padding is
    zero)."""
    hier = make_hierarchical_mesh(n_hosts=2)
    k = 32
    flat_bytes = merge_dcn_candidate_bytes(hier, k, level="flat")
    two_bytes = merge_dcn_candidate_bytes(hier, k, level="two_level")
    assert flat_bytes == 4 * k * 8      # n_local * k pairs to 1 peer host
    assert two_bytes == k * 8           # ONE k-candidate block per host
    assert two_bytes * 4 == flat_bytes  # ratio = n_local
    # compact wire format: 6 B/pair
    assert merge_dcn_candidate_bytes(hier, k, level="two_level",
                                     compact=True) == k * 6
    # single-host degenerate: nothing crosses DCN
    assert merge_dcn_candidate_bytes(make_mesh(8), k) == 0
