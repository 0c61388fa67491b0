"""G3 pallas-invariants: kernel-level contracts the Mosaic compiler will
not enforce for you.

1. Tile alignment — every literal tile/block size (parameter default or
   call-site kwarg) must be a multiple of the 128-lane width, and in
   mask-consuming functions a multiple of MASK_BLOCK=512: the packed
   allow-bitmask layout unpacks whole 512-column blocks in VMEM, so a
   misaligned tile silently reads the wrong words (the kernels force
   ``tile_n = MASK_BLOCK`` at runtime precisely because of this).
2. VMEM scratch budget — ``scratch_shapes`` entries whose dims resolve
   statically (literals, or names with documented repo bounds like the
   scans' ``max_b = 1024``) must fit the ~16 MB VMEM with headroom
   for operand tiles; an over-budget scratch is a Mosaic compile error
   on REAL hardware only (the interpreter happily allocates anything).
3. No Python loops over traced values inside kernel bodies — ``for i in
   range(n_ref[0])`` either raises at trace time or fully unrolls;
   tile-count loops over static ints are fine, dynamic trip counts
   belong in ``lax.fori_loop``.
"""

from __future__ import annotations

import ast

from tools.graftlint.core import (Checker, FileContext, Violation,
                                  walk_shallow)

LANE = 128
MASK_BLOCK = 512
#: scratch budget: half of the ~16 MB VMEM, leaving room for operand tiles
VMEM_SCRATCH_BUDGET = 8 * 1024 * 1024

#: exact kernel tile-parameter names (the repo's Pallas idiom) — a
#: substring match would drag host-side params like ``block_rows`` into
#: the alignment rule
TILE_PARAMS = {"tile_n", "tile_m", "tile_k", "block_n", "block_m",
               "block_k", "subtile"}
MASK_PARAM_HINTS = ("masked", "allow_bits", "allow_rows", "am", "mask")

DTYPE_BYTES = {
    "float32": 4, "int32": 4, "uint32": 4, "f32": 4, "i32": 4,
    "bfloat16": 2, "float16": 2, "bf16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "float64": 8, "int64": 8,
}
#: documented repo bounds for symbolic scratch dims (ops/pallas_kernels:
#: max_b block cap, the widest carried k, lane-padded k)
DIM_BOUNDS = {"b": 1024, "pb": 1024, "k": 256, "pk": 256, "kk": 256}


def _is_tile_param(name: str) -> bool:
    return name.lower() in TILE_PARAMS


def _fn_handles_masks(fn) -> bool:
    params = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
    return any(p in MASK_PARAM_HINTS for p in params)


def _dim_bytes(node: ast.AST) -> int | None:
    """Static value of one scratch dim, via literal or documented bound."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if isinstance(node, ast.Name):
        return DIM_BOUNDS.get(node.id)
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
        lo = _dim_bytes(node.left)
        hi = _dim_bytes(node.right)
        if lo is not None and hi is not None:
            return lo * hi
    return None


def _dtype_size(node: ast.AST) -> int | None:
    name = None
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    return DTYPE_BYTES.get(name) if name else None


def _is_kernel_fn(fn) -> bool:
    """Heuristic for a Pallas kernel body: majority of params end in
    ``_ref`` (the repo's — and Pallas docs' — naming convention)."""
    params = [a.arg for a in fn.args.args]
    if not params:
        return False
    refs = sum(1 for p in params if p.endswith("_ref"))
    return refs >= 2 and refs * 2 >= len(params)


class PallasChecker(Checker):
    id = "G3"
    name = "pallas-invariants"

    def applies_to(self, path: str) -> bool:
        return path.endswith(".py")

    def _imports_pallas(self, tree: ast.Module) -> bool:
        """Gate on a REAL pallas import, not a substring — a comment
        mentioning pallas must not subject host-side code to kernel
        alignment rules."""
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                if "pallas" in (node.module or ""):
                    return True
                if any("pallas" in a.name for a in node.names):
                    return True
            elif isinstance(node, ast.Import):
                if any("pallas" in a.name for a in node.names):
                    return True
        return False

    def check(self, ctx: FileContext) -> list[Violation]:
        if not self._imports_pallas(ctx.tree):
            return []
        out: list[Violation] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out.extend(self._check_tile_defaults(ctx, node))
                if _is_kernel_fn(node):
                    out.extend(self._check_kernel_loops(ctx, node))
            elif isinstance(node, ast.Call):
                out.extend(self._check_callsite_tiles(ctx, node))
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "pallas_call":
                    out.extend(self._check_scratch(ctx, node))
        return out

    # -- tile alignment -------------------------------------------------------

    def _tile_violation(self, ctx, node, name, value, masked):
        need = MASK_BLOCK if masked else LANE
        why = ("mask-consuming functions unpack whole "
               f"{MASK_BLOCK}-column packed blocks" if masked
               else f"the TPU lane width is {LANE}")
        return Violation(
            self.id, ctx.path, node.lineno, node.col_offset,
            f"[pallas-invariants] {name}={value} is not a multiple of "
            f"{need} — {why}")

    def _check_tile_defaults(self, ctx, fn) -> list[Violation]:
        out = []
        masked = _fn_handles_masks(fn)
        need = MASK_BLOCK if masked else LANE
        args = fn.args
        pos = args.posonlyargs + args.args
        defaults = args.defaults
        pairs = list(zip(pos[len(pos) - len(defaults):], defaults))
        pairs += [(a, d) for a, d in zip(args.kwonlyargs, args.kw_defaults)
                  if d is not None]
        for param, default in pairs:
            if not _is_tile_param(param.arg):
                continue
            if isinstance(default, ast.Constant) \
                    and isinstance(default.value, int):
                v = default.value
                if v <= 0 or v % need:
                    out.append(self._tile_violation(ctx, default,
                                                    param.arg, v, masked))
        return out

    def _check_callsite_tiles(self, ctx, call: ast.Call) -> list[Violation]:
        out = []
        for kw in call.keywords:
            if kw.arg and _is_tile_param(kw.arg) \
                    and isinstance(kw.value, ast.Constant) \
                    and isinstance(kw.value.value, int):
                v = kw.value.value
                if v <= 0 or v % LANE:
                    out.append(self._tile_violation(ctx, kw.value,
                                                    kw.arg, v, False))
        return out

    # -- VMEM scratch budget --------------------------------------------------

    def _check_scratch(self, ctx, call: ast.Call) -> list[Violation]:
        scratch = None
        for kw in call.keywords:
            if kw.arg == "scratch_shapes":
                scratch = kw.value
        if scratch is None or not isinstance(scratch, (ast.List, ast.Tuple)):
            return []
        total = 0
        for entry in scratch.elts:
            if not (isinstance(entry, ast.Call)
                    and isinstance(entry.func, ast.Attribute)
                    and entry.func.attr in ("VMEM", "SMEM")
                    and entry.args):
                continue
            shape = entry.args[0]
            dims: list[int] = []
            ok = True
            if isinstance(shape, (ast.Tuple, ast.List)):
                for d in shape.elts:
                    b = _dim_bytes(d)
                    if b is None:
                        ok = False
                        break
                    dims.append(b)
            else:
                ok = False
            size = _dtype_size(entry.args[1]) if len(entry.args) > 1 else 4
            if not ok or size is None:
                continue
            n = size
            for d in dims:
                n *= d
            total += n
        # ``total`` only sums the statically-resolvable entries, so it is
        # a LOWER bound on real usage — exceeding the budget is always a
        # sound report even when other entries could not be sized
        if total > VMEM_SCRATCH_BUDGET:
            return [Violation(
                self.id, ctx.path, call.lineno, call.col_offset,
                f"[pallas-invariants] scratch_shapes total {total} bytes "
                f"exceeds the {VMEM_SCRATCH_BUDGET}-byte VMEM scratch "
                "budget (Mosaic fails this allocation on real hardware "
                "only — the interpreter will not catch it)")]
        return []

    # -- traced loops in kernels ----------------------------------------------

    def _check_kernel_loops(self, ctx, fn) -> list[Violation]:
        params = {a.arg for a in fn.args.args + fn.args.kwonlyargs}
        out = []
        for node in walk_shallow(fn.body):
            if isinstance(node, ast.For):
                if self._refs_traced(node.iter, params):
                    out.append(Violation(
                        self.id, ctx.path, node.lineno, node.col_offset,
                        "[pallas-invariants] Python for-loop over a "
                        "traced value inside a kernel body — this either "
                        "raises at trace time or fully unrolls; use "
                        "lax.fori_loop for dynamic trip counts"))
            elif isinstance(node, ast.While):
                if self._refs_traced(node.test, params):
                    out.append(Violation(
                        self.id, ctx.path, node.lineno, node.col_offset,
                        "[pallas-invariants] while-loop conditioned on a "
                        "traced value inside a kernel body — use "
                        "lax.while_loop"))
        return out

    def _refs_traced(self, expr: ast.AST, params: set[str]) -> bool:
        """A kernel param referenced by value (not just .shape/.dtype)."""
        parents: dict[ast.AST, ast.AST] = {}
        for node in ast.walk(expr):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        for node in ast.walk(expr):
            if isinstance(node, ast.Name) and node.id in params:
                p = parents.get(node)
                if isinstance(p, ast.Attribute) and p.attr in (
                        "shape", "ndim", "dtype", "size"):
                    continue
                return True
        return False
