"""Sharded brute-force top-k over a device mesh.

The cross-shard query path of the reference — parallel per-shard search plus
a host-side merge (adapters/repos/db/index.go:1576-1648) — becomes one
compiled SPMD program:

    per-device chunked scan  →  local top-k  →  candidate merge
    →  merge top-k (replicated)

On the legacy 1-D ``shard`` mesh the candidate merge is a single
all_gather of [n_shards, B, k] (distance, id) pairs. On the hierarchical
``('host', 'ici')`` mesh (ISSUE 13) it is TWO-LEVEL: an all_gather +
exact reduce over ``ici`` INSIDE each host first, then only the per-host
winner block — sliced over the ICI ranks so exactly one logical copy per
host crosses the wire — all_gathers over ``host``. Cross-host candidate
traffic drops from O(devices*k) to O(hosts*k) pairs per query, which is
the difference between a 1B-vector corpus being DCN-bound or
compute-bound (cross-host DCN bandwidth is orders of magnitude scarcer
than ICI). Results are bit-identical to the 1-D merge: exact top-k is
mergeable, and the host-major candidate order both merges share makes
even distance TIES resolve identically (tests/test_hierarchical.py).

Partition specs are not hand-wired here: every operand resolves through
the regex rule tables in ``parallel/partition.py``
(``match_partition_rules``, the SNIPPETS [1] pattern) — graftlint G8
keeps PartitionSpec literals out of this module.

Allow-mask row alignment contract: ``allow_rows`` is always [B, N_local]
bool, column-sharded over the row axes ROW-ALIGNED with whatever corpus
array the same call scans. Epoch stores (engine/epochs.py) honor this by
column-slicing the global mask to each epoch's LOCAL row space
(compaction-aware through the epoch's slot maps) before dispatching that
epoch's scan — one sliced mask per epoch program, while the per-epoch
candidate sets and their replicated local->global slot maps merge in a
separate tiny program (ops/topk.merge_epoch_topk, this module's ICI
merge pattern turned inward).
"""

from __future__ import annotations

import functools
import os

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh

from weaviate_tpu.ops.pallas_kernels import _MASK_WORDS
from weaviate_tpu.ops.topk import chunked_topk_distances, topk_smallest
from weaviate_tpu.parallel import partition
from weaviate_tpu.parallel.mesh import (
    HOST_AXIS,
    ICI_AXIS,
    SHARD_AXIS,
    is_hierarchical,
    n_row_shards,
)
from weaviate_tpu.runtime import kernelscope, tracing


def dcn_compact_default() -> bool:
    """WEAVIATE_TPU_DCN_COMPACT=1 packs the cross-host candidate block
    as (bf16 distance, uint32 slot) — 6 bytes/candidate instead of 8.
    OFF by default: bf16 rounding can reorder near-tied candidates, so
    the bit-identical-to-1-D parity contract only holds when distances
    are bf16-exact (e.g. BQ hamming counts at dim <= 256)."""
    return os.environ.get("WEAVIATE_TPU_DCN_COMPACT", "0").lower() in (
        "1", "true", "on")


def _shard_index(mesh: Mesh, axis: str):
    """This device's linear row-shard index (host-major on the
    hierarchical mesh, matching the row-contiguous device order)."""
    if is_hierarchical(mesh):
        return (jax.lax.axis_index(HOST_AXIS) * mesh.shape[ICI_AXIS]
                + jax.lax.axis_index(ICI_AXIS))
    return jax.lax.axis_index(axis)


def _ici_merge_topk(d, ids, axis: str, k_out: int):
    """The 1-D cross-shard candidate merge: all_gather [n_shards, B, kk]
    (distance, id) pairs over the single mesh axis, flatten per query,
    exact top-k (the device analog of the reference's host-side merge,
    index.go:1644)."""
    all_d = jax.lax.all_gather(d, axis)
    all_i = jax.lax.all_gather(ids, axis)
    n_sh, b, kk = all_d.shape
    cat_d = jnp.transpose(all_d, (1, 0, 2)).reshape(b, n_sh * kk)
    cat_i = jnp.transpose(all_i, (1, 0, 2)).reshape(b, n_sh * kk)
    return topk_smallest(cat_d, cat_i, min(k_out, n_sh * kk))


def _two_level_merge_topk(d, ids, mesh: Mesh, k_out: int,
                          compact: bool = False):
    """Hierarchical candidate merge: ICI reduce inside the host, then a
    k-way merge of one compact per-host winner block across DCN.

    Level 1 — ICI: all_gather every local device's kk candidates and
    reduce to the host's top-k1 (k1 = min(k_out, n_ici*kk)). This
    collective never leaves the host.

    Level 2 — DCN: the per-host winner block is replicated across the
    host's ICI ranks after level 1, so a naive all_gather over ``host``
    would ship n_ici REDUNDANT copies and erase the win. Instead each
    ICI rank slices its 1/n_ici of the block, the slices all_gather
    over ``host`` (exactly ONE logical copy per host crosses DCN —
    O(hosts*k) candidate pairs), and a cheap second ICI all_gather
    reassembles the full [n_hosts, k1] block on every device for the
    final exact top-k.

    Bit-identity with the 1-D merge: exact top-k is mergeable (a
    candidate dropped by its host's level-1 reduce is outranked by k1
    same-host candidates that precede it in the flat concat order, so
    the flat merge drops it too), and the final concat is host-major
    with level-1-sorted candidates inside each host — the same derived
    tie order the flat merge's shard-major concat produces. Padding
    (the slice split needs k1 % n_ici == 0) uses +inf distances, which
    sort strictly after every real AND every masked candidate, so pads
    can never displace one.

    ``compact`` casts the DCN block to (bf16 distance, uint32 slot) —
    see ``dcn_compact_default`` for the exactness tradeoff. Ids cross
    the wire bitcast to uint32 either way (free, and -1 survives the
    round trip exactly).
    """
    n_hosts = int(mesh.shape[HOST_AXIS])
    n_ici = int(mesh.shape[ICI_AXIS])
    # level 1: ICI all_gather + on-device exact reduce (the
    # merge_epoch_topk survivor-merge pattern from ops/topk.py: concat
    # in source order, one exact top-k over the union)
    all_d = jax.lax.all_gather(d, ICI_AXIS)
    all_i = jax.lax.all_gather(ids, ICI_AXIS)
    _, b, kk = all_d.shape
    cat_d = jnp.transpose(all_d, (1, 0, 2)).reshape(b, n_ici * kk)
    cat_i = jnp.transpose(all_i, (1, 0, 2)).reshape(b, n_ici * kk)
    k1 = min(k_out, n_ici * kk)
    host_d, host_i = topk_smallest(cat_d, cat_i, k1)
    k_final = min(k_out, n_hosts * n_ici * kk)
    if n_hosts == 1:
        return host_d, host_i  # degenerate: k1 == k_final
    # level 2: slice over ICI ranks so ONE logical copy per host
    # crosses DCN
    per_rank = -(-k1 // n_ici)
    pad = per_rank * n_ici - k1
    if pad:
        host_d = jnp.pad(host_d, ((0, 0), (0, pad)),
                         constant_values=jnp.inf)
        host_i = jnp.pad(host_i, ((0, 0), (0, pad)), constant_values=-1)
    if compact:
        host_d = host_d.astype(jnp.bfloat16)
    host_iu = jax.lax.bitcast_convert_type(host_i, jnp.uint32)
    rank = jax.lax.axis_index(ICI_AXIS)
    sl_d = jax.lax.dynamic_slice_in_dim(host_d, rank * per_rank,
                                        per_rank, axis=1)
    sl_i = jax.lax.dynamic_slice_in_dim(host_iu, rank * per_rank,
                                        per_rank, axis=1)
    g_d = jax.lax.all_gather(sl_d, HOST_AXIS)   # the DCN hop
    g_i = jax.lax.all_gather(sl_i, HOST_AXIS)
    a_d = jax.lax.all_gather(g_d, ICI_AXIS)     # cheap on-host regather
    a_i = jax.lax.all_gather(g_i, ICI_AXIS)
    # (ici_rank, host, B, per_rank) -> [B, host-major contiguous blocks]
    cat2_d = jnp.transpose(a_d, (2, 1, 0, 3)).reshape(
        b, n_hosts * n_ici * per_rank)
    cat2_i = jnp.transpose(a_i, (2, 1, 0, 3)).reshape(
        b, n_hosts * n_ici * per_rank)
    cat2_i = jax.lax.bitcast_convert_type(cat2_i, jnp.int32)
    if compact:
        cat2_d = cat2_d.astype(jnp.float32)
    return topk_smallest(cat2_d, cat2_i, k_final)


def _merge_topk_mesh(d, ids, mesh: Mesh, axis: str, k_out: int,
                     compact: bool = False):
    """Mesh-shape dispatch: 1-D flat merge vs hierarchical two-level."""
    if is_hierarchical(mesh):
        return _two_level_merge_topk(d, ids, mesh, k_out, compact=compact)
    return _ici_merge_topk(d, ids, axis, k_out)


def topology_dcn_candidate_bytes(n_hosts: int, n_local: int, k: int,
                                 kk: int | None = None, *,
                                 level: str = "two_level",
                                 compact: bool = False) -> int:
    """Pure topology math: per-query candidate bytes ONE host sends
    across DCN during the merge, for an ``n_hosts x n_local`` pod.
    Rig-independent: the same number for a topology whatever hardware
    it is computed on. ``kk`` is the per-device candidate count (defaults
    to k); ``compact`` counts the bf16+uint32 wire format (6 B/pair vs
    8)."""
    kk = k if kk is None else kk
    if n_hosts <= 1:
        return 0
    if level == "flat":
        # all_gather over the whole axis: each of the host's n_local
        # devices ships kk pairs (f32+int32) to the other hosts
        return n_local * kk * 8 * (n_hosts - 1)
    pair = 6 if compact else 8
    k1 = min(k, n_local * kk)
    per_rank = -(-k1 // n_local)  # ICI-rank slice width (inf-padded)
    return per_rank * n_local * pair * (n_hosts - 1)


def merge_dcn_candidate_bytes(mesh: Mesh, k: int, kk: int | None = None,
                              *, level: str = "auto",
                              compact: bool = False) -> int:
    """``topology_dcn_candidate_bytes`` for a concrete mesh (0 when the
    mesh is single-host)."""
    from weaviate_tpu.parallel.mesh import host_count

    n_hosts = host_count(mesh)
    if n_hosts <= 1:
        return 0
    if level == "auto":
        level = "two_level" if is_hierarchical(mesh) else "flat"
    return topology_dcn_candidate_bytes(
        n_hosts, n_row_shards(mesh) // n_hosts, k, kk, level=level,
        compact=compact)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "chunk_size", "metric", "mesh", "axis", "use_pallas",
        "selection", "dcn_compact",
    ),
)
def _sharded_topk_jit(
    q: jnp.ndarray,
    x: jnp.ndarray,
    valid: jnp.ndarray,
    x_sq_norms: jnp.ndarray | None,
    k: int,
    chunk_size: int,
    metric: str,
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    use_pallas: bool = False,
    selection: str = "exact",
    allow_rows: jnp.ndarray | None = None,
    dcn_compact: bool = False,
):
    """Top-k of q [B,d] against row-sharded corpus x [N,d].

    ``x``/``valid``/``x_sq_norms`` must be row-sharded over the mesh's
    row axes on their leading dim; ``q`` is replicated. ``allow_rows``
    ([B, N] bool — per-query filter masks) is sharded on its COLUMN dim,
    row-aligned with the corpus: each device applies only its own slice;
    the candidate merge is unchanged
    because masked rows simply never become candidates. Returns
    replicated (dists [B,k], global_ids [B,k]) where ids index the
    unsharded [N] row space.
    """
    n = x.shape[0]
    n_shards = n_row_shards(mesh)
    local_rows = n // n_shards

    def local_search(q_, x_, valid_, norms_, allow_):
        shard_idx = _shard_index(mesh, axis)
        d, i = chunked_topk_distances(
            q_,
            x_,
            k=k,
            chunk_size=chunk_size,
            metric=metric,
            valid=valid_,
            x_sq_norms=norms_,
            id_offset=shard_idx * local_rows,
            use_pallas=use_pallas,
            selection=selection,
            allow_rows=allow_,
        )
        return _merge_topk_mesh(d, i, mesh, axis, k, compact=dcn_compact)

    specs = partition.match_partition_rules(
        partition.SEARCH_RULES,
        {"q": q, "x": x, "valid": valid, "x_sq_norms": x_sq_norms,
         "allow_rows": allow_rows},
        mesh)
    in_specs = (specs["q"], specs["x"], specs["valid"],
                specs["x_sq_norms"], specs["allow_rows"])
    out_specs = (partition.replicated_spec(), partition.replicated_spec())
    fn = shard_map(
        local_search,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        check_vma=False,
    )
    return fn(q, x, valid, x_sq_norms, allow_rows)


def sharded_topk(q, x, valid, x_sq_norms, *, k, chunk_size, metric, mesh,
                 axis=SHARD_AXIS, use_pallas=False, selection="exact",
                 allow_rows=None, dcn_compact=None):
    """Span-wrapped dispatch of the SPMD scan + top-k merge program
    (spans can't live inside jit; the wrapper times the host-side
    dispatch and device_sync at the store level attributes execution)."""
    if dcn_compact is None:
        dcn_compact = dcn_compact_default()
    with tracing.span("spmd.sharded_topk", shards=n_row_shards(mesh),
                      k=k, rows=int(x.shape[0]),
                      hierarchical=is_hierarchical(mesh),
                      filtered=allow_rows is not None):
        # EXPLAIN: ICI/DCN merge shape — pure topology ints computed on
        # the host at dispatch (mesh axis sizes), never device reads
        hier = is_hierarchical(mesh)
        kernelscope.explain_note(
            "merge",
            shards=n_row_shards(mesh), hierarchical=bool(hier),
            hosts=int(mesh.shape[HOST_AXIS]) if hier else 1,
            ici=(int(mesh.shape[ICI_AXIS]) if hier
                 else n_row_shards(mesh)),
            dcn_compact=bool(dcn_compact), k=k)
        return _sharded_topk_jit(
            q, x, valid, x_sq_norms, k=k, chunk_size=chunk_size,
            metric=metric, mesh=mesh, axis=axis, use_pallas=use_pallas,
            selection=selection, allow_rows=allow_rows,
            dcn_compact=dcn_compact)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "k_out", "chunk_size", "quantization", "metric", "mesh", "axis",
        "use_pallas", "dcn_compact",
    ),
)
def _sharded_quantized_topk_jit(
    q: jnp.ndarray,
    q_words: jnp.ndarray | None,
    codes: jnp.ndarray,
    valid: jnp.ndarray,
    rescore_rows: jnp.ndarray | None,
    centroids: jnp.ndarray | None,
    k: int,
    k_out: int,
    chunk_size: int,
    quantization: str,
    metric: str,
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    use_pallas: bool = False,
    allow_rows: jnp.ndarray | None = None,
    dcn_compact: bool = False,
):
    """Compressed scan over a row-sharded code array, one SPMD program.

    The reference composes compression with sharding for free because PQ/BQ
    is per-shard state inside each physical shard (hnsw/compress.go:38 under
    usecases/sharding/state.go:28). The TPU analog: codes [N, m|w] live
    row-sharded over the mesh's row axes; each device scans its rows (MXU
    hamming / LUT-ADC), approx-selects ``k`` local candidates, optionally
    rescores them EXACTLY against its own row-sharded ``rescore_rows``
    (bf16 — owning-device rescore, no cross-device vector traffic), and
    the final merge moves only candidate (distance, id) pairs — one
    all_gather on the 1-D mesh, the two-level ICI+DCN reduce on the
    hierarchical one.

    ``q`` is replicated f32 (pre-normalized for cosine); ``q_words`` packed
    query bits for bq. ``allow_rows`` [B, N] bool per-query filter
    masks are COLUMN-sharded row-aligned with the codes; each device
    packs its slice to the kernel bitmask locally. Returns replicated
    (dists [B, k_out], global ids).
    """
    from weaviate_tpu.ops import bq as bq_ops
    from weaviate_tpu.ops import pq as pq_ops
    from weaviate_tpu.ops.distances import MASKED_DISTANCE

    n = codes.shape[0]
    n_shards = n_row_shards(mesh)
    local_rows = n // n_shards
    b = q.shape[0]

    def local_scan(q_, qw_, cent_, codes_, valid_, resc_, allow_=None):
        shard_idx = _shard_index(mesh, axis)
        ab_ = None
        if allow_ is not None:
            from weaviate_tpu.ops.pallas_kernels import (
                pack_allow_bitmask_jnp)

            ab_ = pack_allow_bitmask_jnp(allow_)
        if quantization == "bq":
            d_c, i_c = bq_ops.bq_topk(
                qw_, codes_, k=min(k, local_rows), chunk_size=chunk_size,
                valid=valid_, use_pallas=use_pallas, allow_bits=ab_,
            )
        elif quantization == "pq4":
            d_c, i_c = pq_ops.pq4_topk(
                q_, codes_, cent_, k=min(k, local_rows),
                chunk_size=chunk_size, metric=metric, valid=valid_,
                allow_bits=ab_,
            )
        else:
            d_c, i_c = pq_ops.pq_topk(
                q_, codes_, cent_, k=min(k, local_rows),
                chunk_size=chunk_size, metric=metric, valid=valid_,
                allow_bits=ab_,
            )
        if resc_ is not None:
            # exact rescore of local candidates against local bf16 rows:
            # gather [B, k, d] from this device's shard only
            rows = resc_[jnp.clip(i_c, 0, local_rows - 1)].astype(jnp.float32)
            if metric in ("cosine", "cosine-dot"):
                dd = 1.0 - jnp.einsum("bd,bkd->bk", q_, rows,
                                      preferred_element_type=jnp.float32)
            elif metric == "dot":
                dd = -jnp.einsum("bd,bkd->bk", q_, rows,
                                 preferred_element_type=jnp.float32)
            else:
                diff = q_[:, None, :] - rows
                dd = jnp.sum(diff * diff, axis=-1)
            dd = jnp.where(i_c >= 0, dd, MASKED_DISTANCE)
            d_c, i_c = topk_smallest(dd, i_c, min(k_out, i_c.shape[1]))
        gid = jnp.where(i_c >= 0, i_c + shard_idx * local_rows, -1)
        return _merge_topk_mesh(d_c, gid, mesh, axis, k_out,
                                compact=dcn_compact)

    # assemble args/specs in Python (quantization and rescore/allow
    # presence are static): shard_map can't close over traced arrays and
    # optional operands can't be None, so absent ones become tiny dummies
    qw = q_words if q_words is not None else jnp.zeros((b, 1), jnp.uint32)
    cent = (centroids if centroids is not None
            else jnp.zeros((1, 1, 1), jnp.float32))
    has_resc = rescore_rows is not None
    has_allow = allow_rows is not None
    rule_specs = partition.match_partition_rules(
        partition.QUANTIZED_RULES,
        {"q": q, "q_words": qw, "centroids": cent, "codes": codes,
         "valid": valid, "rescore_rows": rescore_rows,
         "allow_rows": allow_rows},
        mesh)
    args = [q, qw, cent, codes, valid]
    specs = [rule_specs["q"], rule_specs["q_words"],
             rule_specs["centroids"], rule_specs["codes"],
             rule_specs["valid"]]
    if has_resc:
        args.append(rescore_rows)
        specs.append(rule_specs["rescore_rows"])
    if has_allow:
        args.append(allow_rows)
        specs.append(rule_specs["allow_rows"])

    def fn(q_, qw_, cent_, codes_, valid_, *rest):
        resc_ = rest[0] if has_resc else None
        allow_ = rest[-1] if has_allow else None
        return local_scan(q_, qw_, cent_, codes_, valid_, resc_, allow_)

    sharded = shard_map(
        fn, mesh=mesh, in_specs=tuple(specs),
        out_specs=(partition.replicated_spec(),
                   partition.replicated_spec()),
        check_vma=False)
    return sharded(*args)


def sharded_quantized_topk(q, q_words, codes, valid, rescore_rows,
                           centroids, *, k, k_out, chunk_size,
                           quantization, metric, mesh, axis=SHARD_AXIS,
                           use_pallas=False, allow_rows=None,
                           dcn_compact=None):
    """Span-wrapped dispatch of the compressed SPMD scan + merge."""
    if dcn_compact is None:
        dcn_compact = dcn_compact_default()
    with tracing.span("spmd.quantized_topk", shards=n_row_shards(mesh),
                      k=k_out, rows=int(codes.shape[0]),
                      quantization=quantization,
                      hierarchical=is_hierarchical(mesh),
                      filtered=allow_rows is not None):
        hier = is_hierarchical(mesh)
        kernelscope.explain_note(
            "merge",
            shards=n_row_shards(mesh), hierarchical=bool(hier),
            hosts=int(mesh.shape[HOST_AXIS]) if hier else 1,
            ici=(int(mesh.shape[ICI_AXIS]) if hier
                 else n_row_shards(mesh)),
            dcn_compact=bool(dcn_compact), k=k_out)
        return _sharded_quantized_topk_jit(
            q, q_words, codes, valid, rescore_rows, centroids, k=k,
            k_out=k_out, chunk_size=chunk_size, quantization=quantization,
            metric=metric, mesh=mesh, axis=axis, use_pallas=use_pallas,
            allow_rows=allow_rows, dcn_compact=dcn_compact)


def shard_array(arr, mesh: Mesh, dim: int = 0):
    """Place ``arr`` on ``mesh`` row-sharded along ``dim`` (the mesh's
    row axes resolve through partition.row_sharding — 'shard' on the
    1-D mesh, ('host','ici') on the hierarchical one; a custom 1-D
    axis name is honored via row_axes).

    On a multi-process (DCN) mesh, device_put can only target addressable
    devices — each process materializes its own shards from the (process-
    locally identical) host array via make_array_from_callback."""
    sharding = partition.row_sharding(mesh, dim=dim)
    if jax.process_count() > 1:
        arr_np = np.asarray(arr)
        return jax.make_array_from_callback(
            arr_np.shape, sharding, lambda idx: arr_np[idx])
    return jax.device_put(arr, sharding)


def replicate_array_multihost(arr, mesh: Mesh):
    arr_np = np.asarray(arr)
    sharding = partition.replicated_sharding(mesh)
    return jax.make_array_from_callback(
        arr_np.shape, sharding, lambda idx: arr_np[idx])


def grow_rows(arr, pad_rows: int, mesh: Mesh | None):
    """Append ``pad_rows`` zero rows to ``arr`` (leading dim), donated and —
    on a mesh — shard-local: both capacities are shard-aligned so each
    device just extends its own shard. An eager concatenate + re-place
    would funnel the full array through one device (minutes + 2x memory at
    100M-row capacities)."""

    def pad(a):
        return jnp.concatenate(
            [a, jnp.zeros((pad_rows,) + a.shape[1:], dtype=a.dtype)])

    if mesh is None:
        return jax.jit(pad, donate_argnums=0)(arr)
    out_sh = partition.row_sharding(mesh, dim=0)
    return jax.jit(pad, donate_argnums=0, out_shardings=out_sh)(arr)


def sharded_zeros(shape, dtype, mesh: Mesh, dim: int = 0):
    """Allocate a zero array directly in its sharded layout — each device
    materializes only its own shard (a host jnp.zeros + device_put round
    trip copies the full array through one device and takes minutes at
    100M-row capacities)."""
    out_sh = partition.row_sharding(mesh, dim=dim)
    return jax.jit(
        functools.partial(jnp.zeros, shape, dtype), out_shardings=out_sh
    )()


def replicate_array(arr, mesh: Mesh):
    if jax.process_count() > 1:
        return replicate_array_multihost(arr, mesh)
    return jax.device_put(arr, partition.replicated_sharding(mesh))


def tracked_shard_array(arr, mesh: Mesh, dim: int = 0,
                        component: str = "sharded",
                        owner: dict | None = None):
    """shard_array + HBM-ledger registration tied to the array's
    lifetime (weakref finalizer) — the placement helper for transient
    sharded operands like per-query allow masks, where nobody holds a
    release key but the peak watermark should still see the bytes."""
    out = shard_array(arr, mesh, dim=dim)
    from weaviate_tpu.runtime.hbm_ledger import ledger

    ledger.track(component, out, sharding="sharded", **(owner or {}))
    return out


@functools.partial(
    jax.jit,
    static_argnames=("k", "nprobe", "metric", "mesh", "axis",
                     "dcn_compact"),
)
def sharded_ivf_pq_topk(
    q: jnp.ndarray,
    centroids: jnp.ndarray,
    list_codes: jnp.ndarray,
    list_valid: jnp.ndarray,
    list_slots: jnp.ndarray,
    list_tvals: jnp.ndarray,
    pq_centroids: jnp.ndarray,
    k: int,
    nprobe: int,
    metric: str,
    mesh: Mesh,
    axis: str = SHARD_AXIS,
    dcn_compact: bool = False,
):
    """SPMD IVF-PQ probe over LIST-sharded posting lists.

    The 100M-per-chip capacity layout (SURVEY §7): ``centroids``
    [nlist, d], ``list_codes`` [nlist, cap, m], ``list_valid``
    [nlist, cap], ``list_slots`` [nlist, cap], ``list_tvals``
    [nlist, cap] (per-row residual-ADC constant) are all sharded over
    the mesh's row axes on the LIST dim; ``q`` and the PQ codebook are
    replicated. Each device ranks ITS local centroids, probes its local
    top-nprobe lists (so the union covers >= the global top-nprobe;
    recall can only exceed the single-device equivalent), scores codes
    via the chunked one-hot int8 matmul (engine/ivf._ivf_probe_topk_pq),
    and contributes k local candidates to the candidate merge — slots,
    not vectors, cross the interconnect (the SPMD analog of the
    reference's scatter-gather, index.go:1541), and on the hierarchical
    mesh only per-host winners cross DCN.

    NOTE: returned distances are int8-quantized ADC approximations (the
    per-query LUT quantization in engine/ivf adds ~0.4% distance error)
    and are NOT exact-rescored here — the merged candidate SLOTS are the
    contract. Callers that surface distances (or need exact ordering at
    the top) must rescore the merged candidates against full-precision
    rows on the owning device or host, as QuantizedVectorStore.search
    does for the single-device path.
    """
    from weaviate_tpu.engine.ivf import _ivf_probe_topk_pq

    # inline, not the store's cached operand: this function body runs
    # under its own jit trace
    dummy_bits = jnp.zeros((1, _MASK_WORDS), dtype=jnp.uint32)

    def local_probe(q_, cent_, codes_, valid_, slots_, tvals_, pqc_):
        local_nlist = cent_.shape[0]
        cn = jnp.sum(cent_.astype(jnp.float32) ** 2, axis=-1)
        d, s = _ivf_probe_topk_pq(
            q_, cent_, cn, codes_, valid_, slots_, tvals_, pqc_,
            dummy_bits, min(k, local_nlist * codes_.shape[1]),
            min(nprobe, local_nlist), metric, False)
        return _merge_topk_mesh(d, s, mesh, axis, k, compact=dcn_compact)

    specs = partition.match_partition_rules(
        partition.IVF_RULES,
        {"q": q, "centroids": centroids, "list_codes": list_codes,
         "list_valid": list_valid, "list_slots": list_slots,
         "list_tvals": list_tvals, "pq_centroids": pq_centroids},
        mesh)
    fn = shard_map(
        local_probe,
        mesh=mesh,
        in_specs=(specs["q"], specs["centroids"], specs["list_codes"],
                  specs["list_valid"], specs["list_slots"],
                  specs["list_tvals"], specs["pq_centroids"]),
        out_specs=(partition.replicated_spec(),
                   partition.replicated_spec()),
        check_vma=False,
    )
    return fn(q, centroids, list_codes, list_valid, list_slots,
              list_tvals, pq_centroids)
