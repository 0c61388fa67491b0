"""G7 durability-discipline: persistent-state writes go through fsutil.

The crashpoint tentpole (ISSUE 9) established the fsync ordering rules
in ``storage/fsutil.py`` (fsync-file -> rename -> fsync-dir; delete
covering state only after covered state is durable). Those rules only
hold if nobody reintroduces a bare ``os.replace`` or an un-fsynced
``open(..., "wb")`` on persistent state — which is exactly the kind of
regression a code review misses because the happy path is identical.
This checker gates the directories that own durable state:

- ``os.replace`` calls in ``weaviate_tpu/storage|cluster|engine/`` and
  ``tools/crashtest/`` must live in fsutil itself (the one
  audited implementation). Exception: quarantine renames whose
  destination is a ``... + ".corrupt"`` expression — those move
  evidence aside, they don't create durable state, and routing them
  through atomic_replace would fsync a file we just declared garbage.
- ``open(path, "wb")`` (or mode= keyword) in those directories must sit
  in a function that also calls ``os.fsync`` or
  ``fsutil.atomic_replace`` — a "wb" rewrite whose enclosing function
  never fsyncs anything is a durability hole (the WAL ``reset`` pattern
  passes: it fsyncs conditionally; the old hnsw ``condense`` pattern
  fails: tmp written, never synced).
"""

from __future__ import annotations

import ast

from tools.graftlint.core import Checker, FileContext, Violation

_SCOPES = (
    "weaviate_tpu/storage/",
    "weaviate_tpu/cluster/",
    "weaviate_tpu/engine/",
    "tools/crashtest/",
)
_FSUTIL = "weaviate_tpu/storage/fsutil.py"


class DurabilityChecker(Checker):
    id = "G7"
    name = "durability-discipline"

    def applies_to(self, path: str) -> bool:
        return path.endswith(".py") and path != _FSUTIL and \
            any(path.startswith(s) for s in _SCOPES)

    def check(self, ctx: FileContext) -> list[Violation]:
        out: list[Violation] = []
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            fn_syncs = self._fn_has_sync(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                if self._is_os_replace(node):
                    if not self._is_quarantine_rename(node):
                        out.append(self._violation(
                            ctx, node,
                            "bare os.replace on persistent state — use "
                            "fsutil.atomic_replace (fsync-file -> rename "
                            "-> fsync-dir); a crash after an un-fsynced "
                            "rename leaves a correctly-named garbage "
                            "file"))
                elif self._is_wb_open(node) and not fn_syncs:
                    out.append(self._violation(
                        ctx, node,
                        'open(..., "wb") in a function that never '
                        "fsyncs — write the bytes, fsync them, and "
                        "rename into place via fsutil.atomic_replace "
                        "(or fsync in place for truncate-reset "
                        "patterns)"))
        # module-level calls (outside any function) get the same rules
        for node in self._module_level_calls(ctx.tree):
            if self._is_os_replace(node) and \
                    not self._is_quarantine_rename(node):
                out.append(self._violation(
                    ctx, node,
                    "bare os.replace on persistent state — use "
                    "fsutil.atomic_replace"))
        return out

    # -- recognizers ---------------------------------------------------------

    @staticmethod
    def _module_level_calls(tree: ast.Module):
        """Call nodes not enclosed by any function def."""
        in_fn: set[int] = set()
        for fn in ast.walk(tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for sub in ast.walk(fn):
                    in_fn.add(id(sub))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and id(node) not in in_fn:
                yield node

    @staticmethod
    def _is_os_replace(call: ast.Call) -> bool:
        f = call.func
        return (isinstance(f, ast.Attribute) and f.attr == "replace"
                and isinstance(f.value, ast.Name) and f.value.id == "os")

    @staticmethod
    def _is_quarantine_rename(call: ast.Call) -> bool:
        """os.replace(x, y) where y is <expr> + ".corrupt" (or any
        string constant ending .corrupt) — evidence aside-move, exempt."""
        if len(call.args) < 2:
            return False
        dest = call.args[1]
        if isinstance(dest, ast.BinOp) and isinstance(dest.op, ast.Add):
            dest = dest.right
        return (isinstance(dest, ast.Constant)
                and isinstance(dest.value, str)
                and dest.value.endswith(".corrupt"))

    @staticmethod
    def _is_wb_open(call: ast.Call) -> bool:
        f = call.func
        is_open = (isinstance(f, ast.Name) and f.id == "open") or \
            (isinstance(f, ast.Attribute) and f.attr == "open"
             and isinstance(f.value, ast.Name) and f.value.id in ("io", "os"))
        if not is_open:
            return False
        mode = None
        if len(call.args) >= 2:
            mode = call.args[1]
        for kw in call.keywords:
            if kw.arg == "mode":
                mode = kw.value
        return (isinstance(mode, ast.Constant)
                and isinstance(mode.value, str) and "w" in mode.value
                and "b" in mode.value)

    @classmethod
    def _fn_has_sync(cls, fn) -> bool:
        """Does this function call os.fsync / fsutil.atomic_replace /
        fsutil.fsync_* anywhere (incl. on a wrapped helper it defines)?"""
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute):
                if f.attr == "fsync" and isinstance(f.value, ast.Name) \
                        and f.value.id == "os":
                    return True
                # NOTE: guarded_write is deliberately NOT in this list —
                # it writes (and tears) but never fsyncs; a "wb" writer
                # that only guards still needs an fsync/atomic_replace
                if f.attr in ("atomic_replace", "fsync_file",
                              "fsync_dir") \
                        and isinstance(f.value, ast.Name) \
                        and f.value.id == "fsutil":
                    return True
            elif isinstance(f, ast.Name) and f.id in (
                    "atomic_replace", "fsync_file", "fsync_dir"):
                return True
        return False

    def _violation(self, ctx, node, msg) -> Violation:
        return Violation(self.id, ctx.path, node.lineno, node.col_offset,
                         f"[durability-discipline] {msg}")
