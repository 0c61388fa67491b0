"""G1 host-sync: stray device->host synchronization in serving hot paths.

A single ``block_until_ready`` / ``np.asarray(device_value)`` / ``.item()``
in the scan or dispatch path serializes every concurrent request behind
one host round-trip — invisible to pytest (CPU JAX is synchronous-ish and
correct either way) and catastrophic under production concurrency. The
reference never has the problem because Go's scan path has no host/device
boundary; ours is all boundary.

Scope: ``engine/``, ``ops/``, ``parallel/`` and ``runtime/query_batcher
.py`` — the modules between a request and the device. ``runtime/
tracing.py`` is allowlisted wholesale: its ``device_sync`` is the ONE
sanctioned sync and fires only on sampled traces.

Mechanics: a per-function taint pass marks names bound to device values —
results of ``jnp.* / jax.* / lax.*`` calls, of known device-returning
helpers (``DEVICE_FUNCS``), and anything derived from them — then flags
host-forcing sinks applied to tainted values. ``jax.block_until_ready``
and ``jax.device_get`` are flagged unconditionally: they have no other
purpose. Intentional API-boundary transfers (a search returning numpy)
are suppressed inline with a reason; that is the contract, not a loophole.
"""

from __future__ import annotations

import ast

from tools.graftlint.core import (Checker, FileContext, Violation,
                                  walk_shallow)

HOT_DIRS = ("weaviate_tpu/engine/", "weaviate_tpu/ops/",
            "weaviate_tpu/parallel/", "weaviate_tpu/text/")
HOT_FILES = ("weaviate_tpu/runtime/query_batcher.py",)
ALLOWLIST = ("weaviate_tpu/runtime/tracing.py",)

#: module roots whose call results live on device
DEVICE_ROOTS = {"jnp", "jax", "lax", "pl", "pltpu"}
#: jax/jnp attributes that do NOT produce device arrays
NON_ARRAY_ATTRS = {"dtype", "shape", "ndim", "default_backend", "devices",
                   "device_count", "local_device_count", "debug",
                   "named_scope", "monitoring", "config", "tree_util",
                   "ShapeDtypeStruct", "CostEstimate", "Precision"}
#: repo helpers whose return values live on device (tuned to this tree)
DEVICE_FUNCS = {
    "chunked_topk_distances", "sharded_topk", "distance_block",
    "bq_hamming_block",
    "bq_mxu_block", "pq4_lut_block", "pq4_recon_block", "shard_array",
    "replicate_array", "tracked_shard_array", "grow_rows", "normalize",
    "pack_allow_bitmask_jnp", "unpack_allow_bitmask", "bq_pack",
    "bq_topk", "bq_topk_twostage", "pq_topk", "pq4_topk",
    "pq_topk_twostage", "topk_distances", "_scatter_rows", "_clear_slots",
    # hybridplane (ops/bm25.py + pallas twin)
    "bm25_neg_scores", "fuse_topk", "hybrid_topk", "masked_candidate_topk",
    "bm25_block",
}
#: attribute reads on a device value that return host scalars/metadata
HOST_ATTRS = {"shape", "dtype", "ndim", "size", "nbytes", "sharding",
              "itemsize"}
#: host-forcing builtins (single-arg); any np.* call on a device value
#: is a sink (numpy coerces the operand to host first)
SYNC_BUILTINS = {"float", "int", "bool"}
METHOD_SINKS = {"item", "tolist"}


def _root_name(node: ast.AST) -> str | None:
    """Leftmost Name of an attribute/subscript/call chain."""
    while isinstance(node, (ast.Attribute, ast.Subscript, ast.Call)):
        node = node.func if isinstance(node, ast.Call) else node.value
    return node.id if isinstance(node, ast.Name) else None


def _attr_chain(node: ast.AST) -> list[str]:
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return parts[::-1]


class _FunctionPass:
    def __init__(self, fn_body: list[ast.stmt]):
        self.body = fn_body
        self.tainted: set[str] = set()

    def _target_names(self, target: ast.AST) -> list[str]:
        if isinstance(target, ast.Name):
            return [target.id]
        if isinstance(target, (ast.Tuple, ast.List)):
            out = []
            for el in target.elts:
                out.extend(self._target_names(el))
            return out
        return []

    def is_device(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Name):
                return fn.id in DEVICE_FUNCS
            if isinstance(fn, ast.Attribute):
                chain = _attr_chain(fn)
                if chain and chain[0] in DEVICE_ROOTS:
                    # jnp.sum(...) etc.; jnp.dtype(...)/jax.devices() are
                    # metadata, and device_get is host by definition
                    if not (set(chain[1:]) & NON_ARRAY_ATTRS) \
                            and chain[-1] not in ("device_get",):
                        return True
                if fn.attr in DEVICE_FUNCS:
                    return True
                # method call on a device value (d.astype(...), t.at[...]
                # .set(...)) stays on device; .item()/.tolist() are sinks
                if fn.attr not in METHOD_SINKS and self.is_device(fn.value):
                    return True
            return False
        if isinstance(node, ast.Attribute):
            if node.attr in HOST_ATTRS:
                return False
            return self.is_device(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_device(node.value)
        if isinstance(node, ast.BinOp):
            return self.is_device(node.left) or self.is_device(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_device(node.operand)
        if isinstance(node, ast.IfExp):
            return self.is_device(node.body) or self.is_device(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.is_device(el) for el in node.elts)
        if isinstance(node, ast.NamedExpr):
            return self.is_device(node.value)
        return False

    def _is_host_pure(self, node: ast.AST) -> bool:
        """RHS that is DEFINITELY a host value: np/numpy-rooted calls
        (np.asarray of a device value returns numpy — the call itself is
        the flagged sink, its RESULT is host) and plain literals.
        Rebinding a name to one of these KILLS its taint, so the
        sanctioned one-suppression boundary pattern
        (``a = np.asarray(a)  # disable=G1`` then host reads of ``a``)
        doesn't demand bogus suppressions downstream."""
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Call):
            root = _root_name(node.func)
            return root in ("np", "numpy")
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(self._is_host_pure(el) for el in node.elts)
        return False

    def apply_assign(self, node: ast.AST) -> None:
        """Gen/kill for one assignment: a device RHS taints the targets,
        a definitely-host RHS untaints them (last write wins)."""
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        else:  # AnnAssign / AugAssign / NamedExpr
            targets, value = [node.target], node.value
        if value is None:
            return
        names = [n for t in targets for n in self._target_names(t)]
        if self.is_device(value):
            self.tainted.update(names)
        elif self._is_host_pure(value) \
                and not isinstance(node, ast.AugAssign):
            self.tainted.difference_update(names)

    def propagate(self) -> None:
        """Line-ordered gen/kill passes to a bounded fixpoint: the
        converged set is a valid region-entry state even with
        loop-carried taint (``x = jnp.f(x)`` inside a for). The checker
        then REPLAYS assignments between sink checks so each call is
        judged against the taint state at its own source position —
        ``a = np.asarray(a)`` flags once (the boundary) and frees every
        later host-side read of ``a``."""
        assigns = [n for n in walk_shallow(self.body)
                   if isinstance(n, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign, ast.NamedExpr))]
        assigns.sort(key=lambda n: (n.lineno, n.col_offset))
        for _ in range(10):
            before = set(self.tainted)
            for node in assigns:
                self.apply_assign(node)
            if self.tainted == before:
                break
        # entry state for the replay: only names whose taint can flow
        # around a loop back-edge (assigned inside a for/while) may be
        # tainted BEFORE their first textual assignment — seeding the
        # full converged set would false-positive on straight-line code
        # that uses a name for host values before a later device rebind
        loop_assigned: set[str] = set()
        for node in walk_shallow(self.body):
            if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                for sub in walk_shallow(node.body + node.orelse):
                    if isinstance(sub, (ast.Assign, ast.AnnAssign,
                                        ast.AugAssign, ast.NamedExpr)):
                        targets = (sub.targets
                                   if isinstance(sub, ast.Assign)
                                   else [sub.target])
                        for t in targets:
                            loop_assigned.update(self._target_names(t))
        self.tainted &= loop_assigned


class HostSyncChecker(Checker):
    id = "G1"
    name = "host-sync"

    def applies_to(self, path: str) -> bool:
        if not path.endswith(".py") or path in ALLOWLIST:
            return False
        return path in HOT_FILES or any(path.startswith(d)
                                        for d in HOT_DIRS)

    def check(self, ctx: FileContext) -> list[Violation]:
        out: list[Violation] = []
        # functions analyzed independently; module-level statements form
        # one pseudo-function
        units: list[list[ast.stmt]] = []
        module_level = [s for s in ctx.tree.body
                        if not isinstance(s, (ast.FunctionDef,
                                              ast.AsyncFunctionDef,
                                              ast.ClassDef))]
        if module_level:
            units.append(module_level)
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                units.append(node.body)
        for body in units:
            fp = _FunctionPass(body)
            fp.propagate()  # converged region-entry taint
            # replay in source order: calls are judged against the taint
            # AT their position; assignments apply gen/kill as we pass
            # them (keyed on the RHS end line so a multi-line RHS's own
            # calls are checked before the write lands)
            events = []
            for node in walk_shallow(body):
                if isinstance(node, ast.Call):
                    events.append((node.lineno, 0, node.col_offset,
                                   "call", node))
                if isinstance(node, (ast.Assign, ast.AnnAssign,
                                     ast.AugAssign, ast.NamedExpr)):
                    end = node.lineno if node.value is None else \
                        getattr(node.value, "end_lineno", node.lineno)
                    events.append((end, 1, node.col_offset,
                                   "assign", node))
            events.sort(key=lambda e: e[:3])
            for _, _, _, kind, node in events:
                if kind == "call":
                    out.extend(self._check_call(ctx, node, fp))
                else:
                    fp.apply_assign(node)
        return out

    def _violation(self, ctx: FileContext, node: ast.AST,
                   msg: str) -> Violation:
        return Violation(self.id, ctx.path, node.lineno, node.col_offset,
                         f"[host-sync] {msg}")

    def _check_call(self, ctx: FileContext, node: ast.Call,
                    fp: _FunctionPass) -> list[Violation]:
        fn = node.func
        # unconditional sync primitives
        if isinstance(fn, ast.Attribute):
            if fn.attr == "block_until_ready":
                return [self._violation(
                    ctx, node,
                    "block_until_ready forces a host round-trip; hot "
                    "paths must stay async (tracing.device_sync is the "
                    "sampled exception)")]
            if fn.attr == "device_get" and _root_name(fn) == "jax":
                return [self._violation(
                    ctx, node,
                    "jax.device_get forces a device->host transfer in a "
                    "hot path")]
            # ANY numpy call applied to a device value syncs: converters
            # (asarray/array) and ufuncs alike (np.sqrt(jnp_val),
            # np.where(dev_mask, ...)) — numpy coerces the operand to a
            # host array first
            if _root_name(fn) in ("np", "numpy") \
                    and any(fp.is_device(a) for a in node.args):
                return [self._violation(
                    ctx, node,
                    f"np.{fn.attr}() on a device value forces a "
                    "device->host transfer; keep the hot path on device "
                    "or move the transfer to the API boundary")]
            # .item()/.tolist() on device values
            if fn.attr in METHOD_SINKS and fp.is_device(fn.value):
                return [self._violation(
                    ctx, node,
                    f".{fn.attr}() on a device value synchronizes the "
                    "stream; hot paths must stay async")]
        elif isinstance(fn, ast.Name):
            if fn.id in SYNC_BUILTINS and len(node.args) == 1 \
                    and fp.is_device(node.args[0]):
                return [self._violation(
                    ctx, node,
                    f"{fn.id}() on a device value blocks on the result; "
                    "hot paths must stay async")]
        return []
