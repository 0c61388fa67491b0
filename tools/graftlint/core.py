"""graftlint driver: shared AST walk, suppressions, cache, baseline, CLI.

The driver parses each file ONCE and hands the tree to every applicable
checker. Checkers return per-file violations plus (optionally) JSON-able
"facts" consumed by a cross-file ``finalize`` pass — that is how the G4
lock-acquisition graph spans modules without re-parsing. Per-file results
are cached by content hash (keyed also on the graftlint sources
themselves, so editing a checker invalidates everything).

Reporting pipeline, in order:

1. inline suppressions   ``# graftlint: disable=G1[,G4]`` on the exact
                         violating line; ``# graftlint: disable-file=ID``
                         (or ``=all``) anywhere in the file
2. baseline              ``baseline.json`` entries grandfather known
                         violations by (check, path, scope, message)
                         fingerprint — line-number independent, so pure
                         code motion does not churn the baseline. Every
                         entry MUST carry a non-empty ``reason``.
3. stale detection       a baseline entry matching nothing is itself an
                         error (the violation was fixed: delete the
                         entry, or run ``--update-baseline`` to prune).
"""

from __future__ import annotations

import argparse
import ast
import hashlib
import json
import os
import re
import sys
from dataclasses import asdict, dataclass, field

CHECK_IDS = ("G1", "G2", "G3", "G4", "G5", "G6", "G7", "G8",
             "G9", "G10", "G11")

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\s]+)")
_SUPPRESS_FILE_RE = re.compile(
    r"#\s*graftlint:\s*disable-file=([A-Za-z0-9_,\s]+)")


@dataclass
class Violation:
    check: str          # "G1".."G5"
    path: str           # repo-relative, forward slashes
    line: int
    col: int
    message: str
    scope: str = ""     # innermost enclosing Class.func qualname

    def fingerprint(self) -> tuple:
        return (self.check, self.path, self.scope, self.message)

    def to_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Violation":
        return cls(**d)


class FileContext:
    """One parsed file, shared by every checker."""

    def __init__(self, path: str, source: str, tree: ast.Module):
        self.path = path            # repo-relative posix path
        self.source = source
        self.lines = source.splitlines()
        self.tree = tree
        self._scopes: list[tuple[int, int, str]] | None = None

    def scope_at(self, line: int) -> str:
        """Innermost Class.func qualname containing ``line``."""
        if self._scopes is None:
            spans: list[tuple[int, int, str]] = []

            def visit(node, prefix):
                for child in ast.iter_child_nodes(node):
                    if isinstance(child, (ast.FunctionDef,
                                          ast.AsyncFunctionDef,
                                          ast.ClassDef)):
                        name = (prefix + "." + child.name
                                if prefix else child.name)
                        end = getattr(child, "end_lineno", child.lineno)
                        spans.append((child.lineno, end, name))
                        visit(child, name)
                    else:
                        visit(child, prefix)

            visit(self.tree, "")
            self._scopes = spans
        best = ""
        best_span = None
        for lo, hi, name in self._scopes:
            if lo <= line <= hi:
                if best_span is None or hi - lo <= best_span:
                    best, best_span = name, hi - lo
        return best


def walk_shallow(body):
    """Walk statements without descending into nested function/class
    definitions (each nested def is analyzed as its own unit) — including
    defs that are direct items of ``body`` itself."""
    stack = [n for n in body
             if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef, ast.Lambda))]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            stack.append(child)


class Checker:
    """Base checker. ``check`` returns per-file violations; ``facts``
    returns an optional JSON-able per-file record for ``finalize``, the
    cross-file pass (violations it returns must carry real path/line so
    inline suppressions still apply). ``finalize`` additionally receives
    the whole-program ``ProgramIndex`` (None when the index extractor is
    not in the active checker set)."""

    id = "G0"
    name = "base"

    def applies_to(self, path: str) -> bool:
        return path.endswith(".py")

    def check(self, ctx: FileContext) -> list[Violation]:
        return []

    def facts(self, ctx: FileContext):
        return None

    def finalize(self, facts: dict[str, object],
                 program: "ProgramIndex | None" = None) -> list[Violation]:
        return []


def all_checkers() -> list[Checker]:
    from tools.graftlint.g1_host_sync import HostSyncChecker
    from tools.graftlint.g2_retrace import RetraceChecker
    from tools.graftlint.g3_pallas import PallasChecker
    from tools.graftlint.g4_locks import LockDisciplineChecker
    from tools.graftlint.g5_metrics import MetricsConventionChecker
    from tools.graftlint.g6_timeouts import TimeoutDisciplineChecker
    from tools.graftlint.g7_durability import DurabilityChecker
    from tools.graftlint.g8_partition import PartitionDisciplineChecker
    from tools.graftlint.g9_threads import ThreadDisciplineChecker
    from tools.graftlint.g10_interhost import InterHostSyncChecker
    from tools.graftlint.g11_config import ConfigSurfaceChecker

    return [ProgramIndexer(), HostSyncChecker(), RetraceChecker(),
            PallasChecker(), LockDisciplineChecker(),
            MetricsConventionChecker(), TimeoutDisciplineChecker(),
            DurabilityChecker(), PartitionDisciplineChecker(),
            ThreadDisciplineChecker(), InterHostSyncChecker(),
            ConfigSurfaceChecker()]


# -- shared lock / receiver machinery (grown out of G4) -----------------------
#
# These used to live in g4_locks.py; the ProgramIndex below and the
# thread-discipline checker both need the same lock-attribute detection,
# "Caller holds" docstring convention and typed-receiver resolution, so
# the repo's locking idiom is modeled in exactly one place.

LOCK_CTORS = {"Lock", "RLock", "Condition", "Semaphore",
              "BoundedSemaphore"}

#: docstring convention marking a helper that runs under the caller's
#: lock. The "under X" branch requires X to be a lock-ish token
#: (ends in lock/cv/mutex) — a doc saying "under _normal operating
#: conditions" must NOT silently exempt the method
CALLER_HOLDS_RE = re.compile(
    r"caller\s+(?:must\s+)?hold|held\s+by\s+(?:the\s+)?caller"
    r"|under\s+`{0,2}(?:self\.)?_?\w*(?:lock|cv|mutex)\b"
    r"|while\s+holding|with\s+`{0,2}_?\w*(?:lock|cv)`{0,2}\s+held",
    re.IGNORECASE)

#: method names too generic to resolve by NAME ALONE on an untyped
#: receiver — file objects, lists, metric children and half the stdlib
#: answer to these, so a name-only match would wire phantom edges into
#: the graph (e.g. ``self._f.flush()`` is not ``Bucket.flush``). Calls
#: on receivers whose class is statically known still resolve.
UNTYPED_STOPLIST = {
    "append", "add", "get", "put", "set", "write", "read", "flush",
    "close", "open", "reset", "clear", "pop", "remove", "update",
    "extend", "insert", "send", "recv", "join", "acquire", "release",
    "wait", "notify", "notify_all", "items", "keys", "values", "copy",
    "index", "count", "sort", "labels", "observe", "inc", "dec", "tell",
    "seek", "info", "debug", "warning", "error", "run", "start", "stop",
    "submit", "result", "cancel", "render", "encode", "decode", "next",
    "register", "track", "search", "delete", "exists", "list", "load",
    "save", "sync", "commit", "apply", "replace", "split", "strip",
}


def _lock_ctor(node: ast.AST) -> str | None:
    """'Lock'/'RLock'/'Condition'/... if node is threading.X(...)."""
    if not isinstance(node, ast.Call):
        return None
    fn = node.func
    if isinstance(fn, ast.Attribute) and fn.attr in LOCK_CTORS \
            and isinstance(fn.value, ast.Name) \
            and fn.value.id in ("threading", "mt", "thread"):
        return fn.attr
    if isinstance(fn, ast.Name) and fn.id in LOCK_CTORS:
        return fn.id
    return None


def _self_attr(node: ast.AST) -> str | None:
    if isinstance(node, ast.Attribute) \
            and isinstance(node.value, ast.Name) \
            and node.value.id == "self":
        return node.attr
    return None


class _ClassLocks:
    def __init__(self, cls: ast.ClassDef, path: str):
        self.cls = cls
        self.path = path
        self.attrs: set[str] = set()        # canonical lock attrs
        self.aliases: dict[str, str] = {}   # cv attr -> underlying lock
        for node in ast.walk(cls):
            if not isinstance(node, ast.Assign):
                continue
            ctor = _lock_ctor(node.value)
            if ctor is None:
                continue
            for tgt in node.targets:
                attr = _self_attr(tgt)
                if attr is None:
                    continue
                call = node.value
                if ctor == "Condition" and call.args:
                    inner = _self_attr(call.args[0])
                    if inner is not None:
                        self.aliases[attr] = inner
                        continue
                self.attrs.add(attr)
        # alias targets must exist as locks; otherwise treat the cv as
        # its own lock
        for cv, inner in list(self.aliases.items()):
            if inner not in self.attrs:
                self.aliases.pop(cv)
                self.attrs.add(cv)

    def canonical(self, attr: str) -> str | None:
        if attr in self.aliases:
            attr = self.aliases[attr]
        return attr if attr in self.attrs else None

    def node_id(self, attr: str) -> str:
        return f"{self.path}:{self.cls.name}.{attr}"


def held_from_docstring(doc: str, cl: _ClassLocks) -> list[str]:
    """For a "Caller holds ..." helper, which class locks its body runs
    under: the lock attrs named in the docstring, else all. Whole-token
    match only — ``_lock`` must not match inside ``_flush_lock`` or the
    graph grows phantom held-edges."""
    named = [a for a in sorted(cl.attrs | set(cl.aliases))
             if re.search(rf"(?<![A-Za-z0-9]){re.escape(a)}"
                          rf"(?![A-Za-z0-9_])", doc)]
    attrs = named or sorted(cl.attrs)
    out = []
    for a in attrs:
        canon = cl.canonical(a)
        if canon:
            out.append(cl.node_id(canon))
    return out


def class_attr_types(cls: ast.ClassDef) -> dict[str, str]:
    """self.<attr> -> ClassName, from ``self.x = ClassName(...)``
    assignments and ``self.x = self._maker()`` where ``_maker``'s
    returns are all ``ClassName(...)`` constructor calls."""
    maker_returns: dict[str, str | None] = {}
    for meth in cls.body:
        if not isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        rets = [n for n in ast.walk(meth)
                if isinstance(n, ast.Return) and n.value is not None]
        names = set()
        for r in rets:
            if isinstance(r.value, ast.Call) \
                    and isinstance(r.value.func, ast.Name) \
                    and r.value.func.id[:1].isupper():
                names.add(r.value.func.id)
            else:
                names.add(None)
        if len(names) == 1 and None not in names:
            maker_returns[meth.name] = names.pop()
    types: dict[str, str] = {}
    for node in ast.walk(cls):
        if not isinstance(node, ast.Assign):
            continue
        for tgt in node.targets:
            attr = _self_attr(tgt)
            if attr is None:
                continue
            v = node.value
            if isinstance(v, ast.Call):
                if isinstance(v.func, ast.Name) \
                        and v.func.id[:1].isupper():
                    types[attr] = v.func.id
                elif isinstance(v.func, ast.Attribute) \
                        and _self_attr(v.func) is not None \
                        and v.func.attr in maker_returns:
                    types[attr] = maker_returns[v.func.attr]
    return types


# -- ProgramIndex: the whole-program call graph -------------------------------
#
# One extractor (the "PI" pseudo-checker) walks every weaviate_tpu
# module once and emits a JSON-able symbol table: per-function call
# edges (receivers resolved through static types where known), direct
# effect sites (device syncs, rpc, fsync) with the lock set held at
# each, thread-spawn sites (threading.Thread / cyclemanager.register /
# TransferPipeline.submit callbacks), host-sink sites applied to call
# results, and a returns-device-value verdict per function (G1's taint
# pass judged at each ``return``). ``ProgramIndex`` joins the per-file
# facts into one graph and computes effect / returns-device summaries
# to a fixpoint, with witness chains for diagnostics. Because facts ride
# the same per-file cache as violations and ``finalize`` always re-runs
# over EVERY file's facts, interprocedural findings are automatically
# whole-program-correct: editing a helper re-derives its facts and the
# next run re-judges every cached caller against the new graph.

#: effect kinds a transfer drain-thread callback must never reach
SYNC_EFFECTS = frozenset({"block_until_ready", "device_get", "result"})
#: blocking-io effect kinds forbidden under db/engine-class locks
IO_EFFECTS = frozenset({"rpc", "fsync"})
#: fsutil entry points that fsync (storage/fsutil.py's public surface)
FSYNC_FUNCS = {"fsync", "fsync_dir", "fsync_file", "atomic_replace",
               "remove_durable"}


def module_name(path: str) -> str:
    """'weaviate_tpu/ops/topk.py' -> 'weaviate_tpu.ops.topk'."""
    p = path[:-3] if path.endswith(".py") else path
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


def _import_base(module: str, path: str, node: ast.ImportFrom):
    """Absolute dotted module an ImportFrom pulls from (None if the
    relative import escapes the tree)."""
    if node.level == 0:
        return node.module
    parts = module.split(".")
    if not path.endswith("/__init__.py"):
        parts = parts[:-1]
    drop = node.level - 1
    if drop:
        if drop > len(parts):
            return None
        parts = parts[: len(parts) - drop]
    base = ".".join(parts)
    if node.module:
        base = f"{base}.{node.module}" if base else node.module
    return base or None


def _ann_type(ann) -> str | None:
    """Class name out of a parameter annotation (Name, 'Str', or the
    last attribute of a dotted annotation)."""
    if isinstance(ann, ast.Name):
        return ann.id
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        return ann.value.strip().split(".")[-1].split("|")[0].strip() or None
    if isinstance(ann, ast.Attribute):
        return ann.attr
    return None


def extract_module_facts(ctx: FileContext) -> dict:
    """Per-module symbol table + per-function summaries (see the
    section comment above for the shape)."""
    from tools.graftlint.g1_host_sync import _FunctionPass

    path, tree = ctx.path, ctx.tree
    mod = module_name(path)

    imports: dict[str, list] = {}   # local name -> [module, orig|None]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    imports[a.asname] = [a.name, None]
                else:
                    top = a.name.split(".")[0]
                    imports.setdefault(top, [top, None])
        elif isinstance(node, ast.ImportFrom):
            base = _import_base(mod, path, node)
            if base is None:
                continue
            for a in node.names:
                if a.name != "*":
                    imports[a.asname or a.name] = [base, a.name]

    module_locks = {tgt.id: f"{path}:{tgt.id}"
                    for node in tree.body
                    if isinstance(node, ast.Assign)
                    and _lock_ctor(node.value)
                    for tgt in node.targets if isinstance(tgt, ast.Name)}

    classes: dict[str, dict] = {}
    functions: dict[str, dict] = {}

    def imported_module(root: str) -> str | None:
        imp = imports.get(root)
        if not imp:
            return None
        return imp[0] if imp[1] is None else f"{imp[0]}.{imp[1]}"

    def effect_kind(call: ast.Call) -> str | None:
        fn = call.func
        if isinstance(fn, ast.Attribute):
            a = fn.attr
            if a == "block_until_ready":
                return "block_until_ready"
            if a == "device_get":
                return "device_get"
            if a == "result" and not call.keywords and len(call.args) <= 1:
                return "result"
            base = fn.value
            root = base.id if isinstance(base, ast.Name) else None
            if root is None:
                return None
            if a == "rpc" and (root == "transport"
                               or (imported_module(root) or "")
                               .endswith("transport")):
                return "rpc"
            if root == "os" and a == "fsync":
                return "fsync"
            if a in FSYNC_FUNCS and (root == "fsutil"
                                     or (imported_module(root) or "")
                                     .endswith("fsutil")):
                return "fsync"
            return None
        if isinstance(fn, ast.Name):
            imp = imports.get(fn.id)
            if imp and imp[1] == fn.id:
                if fn.id == "rpc" and imp[0].endswith("transport"):
                    return "rpc"
                if fn.id in FSYNC_FUNCS and imp[0].endswith("fsutil"):
                    return "fsync"
        return None

    def visit_class(cnode: ast.ClassDef, prefix: str):
        qual = f"{prefix}.{cnode.name}" if prefix else cnode.name
        cl = _ClassLocks(cnode, path)
        at = class_attr_types(cnode)
        classes[qual] = {
            "name": cnode.name,
            "bases": [b.id for b in cnode.bases
                      if isinstance(b, ast.Name)],
            "attr_types": at,
            "locks": sorted(cl.attrs),
        }
        for child in cnode.body:
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit_function(child, qual, cl, at, {})
            elif isinstance(child, ast.ClassDef):
                visit_class(child, qual)

    def visit_function(fnode, prefix: str, cl: _ClassLocks | None,
                       at: dict, outer_types: dict):
        qual = f"{prefix}.{fnode.name}" if prefix else fnode.name
        a = fnode.args
        ltypes = dict(outer_types)
        for arg in a.posonlyargs + a.args + a.kwonlyargs:
            t = _ann_type(arg.annotation)
            if t and t[:1].isupper():
                ltypes[arg.arg] = t
        binds: dict[str, set] = {}   # name -> call refs (or "?") bound

        def call_ref(fn) -> str | None:
            if isinstance(fn, ast.Name):
                return f"n:{fn.id}"
            if not isinstance(fn, ast.Attribute):
                return None
            meth, base = fn.attr, fn.value
            if isinstance(base, ast.Name):
                if base.id == "self" and cl is not None:
                    return f"s:{meth}"
                t = ltypes.get(base.id)
                if t:
                    return f"t:{t}.{meth}"
                return f"m:{base.id}.{meth}"
            battr = _self_attr(base)
            if battr is not None and cl is not None:
                t = at.get(battr)
                if t:
                    return f"t:{t}.{meth}"
            return f"u:{meth}"

        # pre-pass: local var types and name -> sole-call-ref bindings
        for n in walk_shallow(fnode.body):
            if not isinstance(n, (ast.Assign, ast.AnnAssign,
                                  ast.AugAssign)):
                continue
            tgts = n.targets if isinstance(n, ast.Assign) else [n.target]
            names = [t.id for t in tgts if isinstance(t, ast.Name)]
            for t in tgts:
                if isinstance(t, (ast.Tuple, ast.List)):
                    for el in t.elts:
                        if isinstance(el, ast.Name):
                            binds.setdefault(el.id, set()).add("?")
            v = getattr(n, "value", None)
            ref = None
            if isinstance(v, ast.Call):
                ref = call_ref(v.func)
                if isinstance(v.func, ast.Name) \
                        and v.func.id[:1].isupper():
                    for name in names:
                        ltypes[name] = v.func.id
            elif v is not None:
                battr = _self_attr(v)
                if battr is not None:
                    t = at.get(battr)
                    if t:
                        for name in names:
                            ltypes[name] = t
            for name in names:
                binds.setdefault(name, set()).add(ref or "?")

        def name_src(name: str) -> str | None:
            s = binds.get(name)
            if s and len(s) == 1:
                ref = next(iter(s))
                return None if ref == "?" else ref
            return None

        def recv_type(base) -> str | None:
            if isinstance(base, ast.Name):
                return ltypes.get(base.id)
            battr = _self_attr(base)
            if battr is not None and cl is not None:
                return at.get(battr)
            return None

        def recv_text(base) -> str:
            if isinstance(base, ast.Name):
                return base.id
            if isinstance(base, ast.Attribute):
                return base.attr
            return ""

        def cb_ref(expr) -> str | None:
            if isinstance(expr, ast.Call) \
                    and (recv_text(expr.func) == "partial"
                         or (isinstance(expr.func, ast.Name)
                             and expr.func.id == "partial")) \
                    and expr.args:
                return cb_ref(expr.args[0])
            if isinstance(expr, ast.Name):
                return f"n:{expr.id}"
            if isinstance(expr, ast.Attribute):
                return call_ref(expr)
            return None

        fact: dict = {"line": fnode.lineno}
        if cl is not None:
            fact["cls"] = cl.cls.name
        calls: list[list] = []
        effects: list[list] = []
        spawns: list[list] = []
        sinks: list[list] = []

        def lock_id(expr) -> str | None:
            attr = _self_attr(expr)
            if attr is not None and cl is not None:
                canon = cl.canonical(attr)
                return cl.node_id(canon) if canon else None
            if isinstance(expr, ast.Name):
                return module_locks.get(expr.id)
            return None

        def handle_call(call: ast.Call, held: list):
            ref = call_ref(call.func)
            kind = effect_kind(call)
            if kind is not None:
                effects.append([kind, call.lineno, call.col_offset, held])
            if ref is not None:
                calls.append([ref, call.lineno, held])
            fn = call.func
            # thread-role spawn sites
            if isinstance(fn, ast.Attribute):
                base = fn.value
                if fn.attr == "Thread" and isinstance(base, ast.Name) \
                        and base.id in ("threading", "mt", "thread"):
                    tgt = next((kw.value for kw in call.keywords
                                if kw.arg == "target"), None)
                    cb = cb_ref(tgt) if tgt is not None else None
                    spawns.append(["thread", cb, call.lineno])
                elif fn.attr == "register" and len(call.args) >= 2:
                    if recv_type(base) == "CycleManager" \
                            or "cycle" in recv_text(base).lower():
                        spawns.append(["cycle", cb_ref(call.args[1]),
                                       call.lineno])
                elif fn.attr == "submit" and len(call.args) >= 2:
                    if recv_type(base) == "TransferPipeline" \
                            or "transfer" in recv_text(base).lower():
                        spawns.append(["drain", cb_ref(call.args[1]),
                                       call.lineno])
            elif isinstance(fn, ast.Name) and fn.id == "Thread":
                tgt = next((kw.value for kw in call.keywords
                            if kw.arg == "target"), None)
                if tgt is not None:
                    spawns.append(["thread", cb_ref(tgt), call.lineno])
            # host sinks applied to a call result (G10's raw material)
            operand = None
            desc = ""
            if isinstance(fn, ast.Name) and fn.id in ("float", "int",
                                                      "bool") \
                    and len(call.args) == 1:
                operand, desc = call.args[0], f"{fn.id}()"
            elif isinstance(fn, ast.Attribute) \
                    and isinstance(fn.value, ast.Name) \
                    and fn.value.id in ("np", "numpy") and call.args:
                operand, desc = call.args[0], f"np.{fn.attr}()"
            elif isinstance(fn, ast.Attribute) \
                    and fn.attr in ("item", "tolist") and not call.args:
                operand, desc = fn.value, f".{fn.attr}()"
            if operand is not None:
                sref = None
                if isinstance(operand, ast.Call):
                    sref = call_ref(operand.func)
                elif isinstance(operand, ast.Name):
                    sref = name_src(operand.id)
                if sref is not None and sref != ref:
                    sinks.append([sref, call.lineno, call.col_offset,
                                  desc])

        def visit(node, held: list):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit_function(node, qual, cl, at, ltypes)
                return
            if isinstance(node, ast.ClassDef):
                visit_class(node, qual)
                return
            if isinstance(node, ast.Lambda):
                return
            if isinstance(node, (ast.With, ast.AsyncWith)):
                acquired = []
                for it in node.items:
                    for sub in ast.walk(it.context_expr):
                        if isinstance(sub, ast.Call):
                            handle_call(sub, held)
                    lid = lock_id(it.context_expr)
                    if lid is not None and lid not in held:
                        acquired.append(lid)
                inner = held + acquired
                for child in node.body:
                    visit(child, inner)
                return
            if isinstance(node, ast.Call):
                handle_call(node, held)
            for child in ast.iter_child_nodes(node):
                visit(child, held)

        doc = ast.get_docstring(fnode) or ""
        seed: list[str] = []
        if cl is not None and CALLER_HOLDS_RE.search(doc):
            seed = held_from_docstring(doc, cl)
        for child in fnode.body:
            visit(child, seed)

        # returns-device verdict: G1's gen/kill taint, replayed in
        # source order so each ``return`` is judged at its own position
        fp = _FunctionPass(fnode.body)
        fp.propagate()
        events = [n for n in walk_shallow(fnode.body)
                  if isinstance(n, (ast.Assign, ast.AnnAssign,
                                    ast.AugAssign, ast.NamedExpr,
                                    ast.Return))]
        events.sort(key=lambda n: (n.lineno, n.col_offset))
        ret_device = False
        ret_calls: list[str] = []

        def ret_ref(v) -> str | None:
            if isinstance(v, ast.Call):
                return call_ref(v.func)
            if isinstance(v, ast.Name):
                return name_src(v.id)
            return None

        for ev in events:
            if not isinstance(ev, ast.Return):
                fp.apply_assign(ev)
                continue
            v = ev.value
            if v is None:
                continue
            if fp.is_device(v):
                ret_device = True
            elif isinstance(v, (ast.Tuple, ast.List)):
                for el in v.elts:
                    r = ret_ref(el)
                    if r:
                        ret_calls.append(r)
            else:
                r = ret_ref(v)
                if r:
                    ret_calls.append(r)

        if calls:
            fact["calls"] = calls
        if effects:
            fact["effects"] = effects
        if spawns:
            fact["spawns"] = spawns
        if sinks:
            fact["sinks"] = sinks
        if ret_device:
            fact["ret_device"] = True
        if ret_calls:
            fact["ret_calls"] = sorted(set(ret_calls))
        functions[qual] = fact

    for top in tree.body:
        if isinstance(top, (ast.FunctionDef, ast.AsyncFunctionDef)):
            visit_function(top, "", None, {}, {})
        elif isinstance(top, ast.ClassDef):
            visit_class(top, "")

    return {"module": mod, "imports": imports, "classes": classes,
            "functions": functions}


class ProgramIndex:
    """The joined whole-program view over every module's PI facts:
    resolves call references (through typed receivers, imports, and
    globally-unique method names), computes transitive effect and
    returns-device summaries to a fixpoint, and surfaces thread-role
    seeds (spawn sites) for the reachability checkers."""

    def __init__(self, files: dict[str, dict]):
        self.files = files
        self.mod2path: dict[str, str] = {}
        self.fn: dict[str, dict] = {}          # "path::qual" -> fact
        self.classes: dict[str, list] = {}     # name -> [(path, qual, cf)]
        for p, mf in files.items():
            m = mf.get("module")
            if m:
                self.mod2path[m] = p
            for q, ff in mf.get("functions", {}).items():
                self.fn[f"{p}::{q}"] = ff
            for q, cf in mf.get("classes", {}).items():
                self.classes.setdefault(cf["name"], []).append((p, q, cf))
        self.methods_by_name: dict[str, set] = {}
        for fid, ff in self.fn.items():
            q = fid.split("::", 1)[1]
            if "." in q and ff.get("cls"):
                self.methods_by_name.setdefault(
                    q.rsplit(".", 1)[1], set()).add(fid)
        self._edges: dict | None = None
        self._eff: dict | None = None
        self._via: dict = {}
        self._ret: dict | None = None

    @staticmethod
    def path_of(fid: str) -> str:
        return fid.split("::", 1)[0]

    @staticmethod
    def qual_of(fid: str) -> str:
        return fid.split("::", 1)[1]

    # -- reference resolution -------------------------------------------------

    def method_on(self, cls_name: str, meth: str,
                  _seen: set | None = None) -> str | None:
        """Resolve Class.meth through single-inheritance bases; None when
        the class name is not globally unique (never guess)."""
        cands = self.classes.get(cls_name, [])
        if len(cands) != 1:
            return None
        p, q, cf = cands[0]
        fid = f"{p}::{q}.{meth}"
        if fid in self.fn:
            return fid
        _seen = _seen or set()
        if cls_name in _seen:
            return None
        _seen.add(cls_name)
        for b in cf.get("bases", []):
            r = self.method_on(b, meth, _seen)
            if r:
                return r
        return None

    def _unique_method(self, meth: str) -> str | None:
        if meth in UNTYPED_STOPLIST:
            return None
        cands = self.methods_by_name.get(meth, set())
        return next(iter(cands)) if len(cands) == 1 else None

    def resolve(self, ref: str, path: str, qual: str = "",
                cls: str | None = None) -> str | None:
        kind, _, name = ref.partition(":")
        mf = self.files.get(path)
        if kind == "n":
            parts = qual.split(".") if qual else []
            for i in range(len(parts), -1, -1):
                fid = f"{path}::{'.'.join(parts[:i] + [name])}"
                if fid in self.fn:
                    return fid
            imp = (mf or {}).get("imports", {}).get(name)
            if imp and imp[1]:
                tpath = self.mod2path.get(imp[0])
                if tpath and f"{tpath}::{imp[1]}" in self.fn:
                    return f"{tpath}::{imp[1]}"
            return None
        if kind == "s":
            return self.method_on(cls, name) if cls else None
        if kind == "t":
            cname, _, meth = name.partition(".")
            return self.method_on(cname, meth)
        if kind == "m":
            root, _, attr = name.partition(".")
            imp = (mf or {}).get("imports", {}).get(root)
            if imp:
                dotted = imp[0] if imp[1] is None else f"{imp[0]}.{imp[1]}"
                tpath = self.mod2path.get(dotted)
                if tpath and f"{tpath}::{attr}" in self.fn:
                    return f"{tpath}::{attr}"
                return None
            # not an import: an untyped local receiver
            return self._unique_method(attr)
        if kind == "u":
            return self._unique_method(name)
        return None

    def resolve_in(self, fid: str, ref: str) -> str | None:
        p, q = fid.split("::", 1)
        return self.resolve(ref, p, q, self.fn[fid].get("cls"))

    # -- graph + fixpoint summaries -------------------------------------------

    def edges(self) -> dict[str, list]:
        """fid -> [(callee fid, call line), ...] with refs resolved."""
        if self._edges is None:
            e: dict[str, list] = {}
            for fid, ff in self.fn.items():
                out = []
                for c in ff.get("calls", []):
                    callee = self.resolve_in(fid, c[0])
                    if callee is not None and callee != fid:
                        out.append((callee, c[1]))
                e[fid] = out
            self._edges = e
        return self._edges

    def reaches(self, fid: str) -> set:
        """Transitive closure of effect kinds reachable from ``fid``."""
        if self._eff is None:
            eff: dict[str, set] = {}
            for fid2, ff in self.fn.items():
                ks: set = set()
                for k, line, _col, _held in ff.get("effects", []):
                    if k not in ks:
                        ks.add(k)
                        self._via[(fid2, k)] = ("site", line)
                eff[fid2] = ks
            edges = self.edges()
            changed = True
            while changed:
                changed = False
                for fid2, outs in edges.items():
                    mine = eff[fid2]
                    for callee, line in outs:
                        for k in eff.get(callee, ()):
                            if k not in mine:
                                mine.add(k)
                                self._via[(fid2, k)] = ("call", callee,
                                                        line)
                                changed = True
            self._eff = eff
        return self._eff.get(fid, set())

    def witness(self, fid: str, kind: str) -> str:
        """Human-readable chain from ``fid`` to the direct effect site."""
        self.reaches(fid)
        parts, cur = [], fid
        for _ in range(24):
            v = self._via.get((cur, kind))
            if v is None:
                break
            if v[0] == "site":
                # path only, no line: this string lands in violation
                # messages, which are baseline fingerprints — a line
                # number would churn entries on unrelated edits
                parts.append(f"{self.qual_of(cur)} "
                             f"[{self.path_of(cur)}]")
                break
            parts.append(self.qual_of(cur))
            cur = v[1]
        return " -> ".join(parts)

    def reachable(self, fid: str) -> dict[str, tuple | None]:
        """BFS over call edges: reached fid -> (parent fid, call line)."""
        edges = self.edges()
        seen: dict[str, tuple | None] = {fid: None}
        queue = [fid]
        while queue:
            cur = queue.pop(0)
            for callee, line in edges.get(cur, ()):
                if callee not in seen:
                    seen[callee] = (cur, line)
                    queue.append(callee)
        return seen

    def chain(self, reached: dict, fid: str) -> str:
        """Render the BFS parent chain from a reachability seed."""
        parts, cur = [], fid
        for _ in range(24):
            parts.append(self.qual_of(cur))
            parent = reached.get(cur)
            if parent is None:
                break
            cur = parent[0]
        return " <- ".join(parts)

    def returns_device(self, fid: str) -> bool:
        """Does ``fid`` (transitively) return a device value?"""
        if self._ret is None:
            ret = {f: bool(ff.get("ret_device"))
                   for f, ff in self.fn.items()}
            changed = True
            while changed:
                changed = False
                for fid2, ff in self.fn.items():
                    if ret[fid2]:
                        continue
                    for ref in ff.get("ret_calls", ()):
                        cal = self.resolve_in(fid2, ref)
                        if cal is not None and ret.get(cal):
                            ret[fid2] = True
                            changed = True
                            break
            self._ret = ret
        return self._ret.get(fid, False)

    def roles(self) -> list[dict]:
        """Every thread-spawn site: role kind, resolved target, where."""
        out = []
        for fid, ff in self.fn.items():
            for role, ref, line in ff.get("spawns", ()):
                tgt = self.resolve_in(fid, ref) if ref else None
                out.append({"role": role, "target": tgt, "ref": ref,
                            "path": self.path_of(fid), "line": line,
                            "in": self.qual_of(fid)})
        return out


class ProgramIndexer(Checker):
    """Fact extractor only — emits no violations itself. Must be in the
    active checker set for G9/G10 (and any other program-wide checker)
    to see a ProgramIndex in ``finalize``."""

    id = "PI"
    name = "program-index"

    def applies_to(self, path: str) -> bool:
        return (path.endswith(".py")
                and path.startswith("weaviate_tpu/")
                and "test" not in path.rsplit("/", 1)[-1])

    def facts(self, ctx: FileContext):
        return extract_module_facts(ctx)


# -- suppressions -------------------------------------------------------------


def _parse_ids(blob: str) -> set[str]:
    return {p.strip().upper() for p in blob.split(",") if p.strip()}


def suppressions(ctx: FileContext) -> tuple[set[str], dict[int, set[str]]]:
    """(file-level disabled ids, line -> disabled ids). ``all`` (or
    ``ALL``) disables every checker."""
    file_ids: set[str] = set()
    line_ids: dict[int, set[str]] = {}
    for i, line in enumerate(ctx.lines, start=1):
        if "graftlint" not in line:
            continue
        m = _SUPPRESS_FILE_RE.search(line)
        if m:
            file_ids |= _parse_ids(m.group(1))
            continue
        m = _SUPPRESS_RE.search(line)
        if m:
            line_ids.setdefault(i, set()).update(_parse_ids(m.group(1)))
    return file_ids, line_ids


def apply_suppressions(ctx: FileContext,
                       violations: list[Violation]) -> list[Violation]:
    file_ids, line_ids = suppressions(ctx)
    if "ALL" in file_ids:
        return []
    out = []
    for v in violations:
        if v.check in file_ids:
            continue
        ids = line_ids.get(v.line, ())
        if v.check in ids or "ALL" in ids:
            continue
        out.append(v)
    return out


# -- cache --------------------------------------------------------------------


def _tool_hash() -> str:
    """Hash of the graftlint sources: editing any checker invalidates the
    whole cache."""
    h = hashlib.sha1()
    pkg = os.path.dirname(os.path.abspath(__file__))
    for fn in sorted(os.listdir(pkg)):
        if fn.endswith(".py"):
            with open(os.path.join(pkg, fn), "rb") as f:
                h.update(f.read())
    return h.hexdigest()


class Cache:
    def __init__(self, path: str | None, checker_ids: tuple = ()):
        self.path = path
        # keyed on the graftlint sources AND the active checker set — a
        # run with a checkers subset must not poison a later full run
        self.tool = _tool_hash() + ":" + ",".join(sorted(checker_ids))
        self.data: dict = {}
        self.dirty = False
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    loaded = json.load(f)
                if loaded.get("tool") == self.tool:
                    self.data = loaded.get("files", {})
            except (OSError, ValueError):
                self.data = {}

    def get(self, relpath: str, sha: str):
        ent = self.data.get(relpath)
        if ent and ent.get("sha") == sha:
            return ([Violation.from_dict(d) for d in ent["violations"]],
                    ent.get("facts", {}))
        return None

    def put(self, relpath: str, sha: str, violations: list[Violation],
            facts: dict) -> None:
        self.data[relpath] = {
            "sha": sha,
            "violations": [v.to_dict() for v in violations],
            "facts": facts,
        }
        self.dirty = True

    def save(self) -> None:
        if not self.path or not self.dirty:
            return
        try:
            with open(self.path, "w") as f:
                json.dump({"tool": self.tool, "files": self.data}, f)
        except OSError:
            pass


# -- baseline -----------------------------------------------------------------


class BaselineError(ValueError):
    pass


def load_baseline(path: str) -> list[dict]:
    if not path or not os.path.exists(path):
        return []
    with open(path) as f:
        entries = json.load(f)
    if not isinstance(entries, list):
        raise BaselineError(f"{path}: baseline must be a JSON list")
    for e in entries:
        for k in ("check", "path", "message", "reason"):
            if not str(e.get(k, "")).strip():
                raise BaselineError(
                    f"{path}: baseline entry {e!r} missing {k!r} "
                    "(every grandfathered violation needs a reason)")
        if not isinstance(e.get("count", 1), int) or e.get("count", 1) < 1:
            raise BaselineError(
                f"{path}: baseline entry {e!r} has invalid count")
    return entries


def _entry_fingerprint(e: dict) -> tuple:
    return (e["check"], e["path"], e.get("scope", ""), e["message"])


def split_baseline(violations: list[Violation], entries: list[dict]):
    """-> (new_violations, baselined_violations, stale_entries).

    Each entry grandfathers exactly ``count`` occurrences (default 1) of
    its fingerprint. MORE live occurrences than count = the excess are
    NEW violations (adding a second identical sync next to a baselined
    one must not ride its entry); FEWER = some were fixed, so the entry
    is STALE until ``--update-baseline`` rewrites its count."""
    budget = {}
    for e in entries:
        fp = _entry_fingerprint(e)
        budget[fp] = budget.get(fp, 0) + int(e.get("count", 1))
    live_counts: dict[tuple, int] = {}
    new, old = [], []
    for v in sorted(violations, key=lambda v: (v.path, v.line, v.col)):
        fp = v.fingerprint()
        live_counts[fp] = live_counts.get(fp, 0) + 1
        if live_counts[fp] <= budget.get(fp, 0):
            old.append(v)
        else:
            new.append(v)
    stale = [e for e in entries
             if live_counts.get(_entry_fingerprint(e), 0)
             < budget[_entry_fingerprint(e)]]
    return new, old, stale


# -- runner -------------------------------------------------------------------


@dataclass
class Result:
    violations: list[Violation] = field(default_factory=list)  # non-baselined
    baselined: list[Violation] = field(default_factory=list)
    stale: list[dict] = field(default_factory=list)
    errors: list[str] = field(default_factory=list)  # parse failures etc.
    files: int = 0

    @property
    def clean(self) -> bool:
        return not self.violations and not self.stale and not self.errors


def discover(paths: list[str], root: str) -> list[str]:
    """Expand files/dirs into a sorted list of repo-relative .py paths."""
    out: set[str] = set()
    for p in paths:
        absp = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(absp):
            out.add(os.path.relpath(absp, root).replace(os.sep, "/"))
        elif os.path.isdir(absp):
            for dirpath, dirnames, filenames in os.walk(absp):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git")]
                for fn in filenames:
                    if fn.endswith(".py"):
                        rel = os.path.relpath(os.path.join(dirpath, fn),
                                              root)
                        out.add(rel.replace(os.sep, "/"))
    return sorted(out)


def run(paths: list[str], root: str, *, use_cache: bool = True,
        baseline_path: str | None = None,
        checkers: list[Checker] | None = None) -> Result:
    """Analyze ``paths`` (files or directories, relative to ``root``)."""
    checkers = all_checkers() if checkers is None else checkers
    res = Result()
    cache = Cache(os.path.join(root, ".graftlint_cache.json")
                  if use_cache else None,
                  checker_ids=tuple(c.id for c in checkers))
    all_violations: list[Violation] = []
    # facts survive even for cached files — finalize always sees the
    # whole project's graph
    project_facts: dict[str, dict[str, object]] = {c.id: {}
                                                   for c in checkers}
    for rel in discover(paths, root):
        absp = os.path.join(root, rel)
        try:
            with open(absp, encoding="utf-8") as f:
                source = f.read()
        except OSError as e:
            res.errors.append(f"{rel}: unreadable ({e})")
            continue
        sha = hashlib.sha1(source.encode()).hexdigest()
        res.files += 1
        cached = cache.get(rel, sha)
        if cached is not None:
            violations, facts = cached
            all_violations.extend(violations)
            for cid, fact in facts.items():
                if fact is not None:
                    project_facts.setdefault(cid, {})[rel] = fact
            continue
        try:
            tree = ast.parse(source, filename=rel)
        except SyntaxError as e:
            res.errors.append(f"{rel}:{e.lineno}: syntax error: {e.msg}")
            continue
        ctx = FileContext(rel, source, tree)
        violations: list[Violation] = []
        facts: dict[str, object] = {}
        for c in checkers:
            if not c.applies_to(rel):
                continue
            for v in c.check(ctx):
                if not v.scope:
                    v.scope = ctx.scope_at(v.line)
                violations.append(v)
            fact = c.facts(ctx)
            if fact is not None:
                facts[c.id] = fact
                project_facts[c.id][rel] = fact
        violations = apply_suppressions(ctx, violations)
        cache.put(rel, sha, violations, facts)
        all_violations.extend(violations)
    # cross-file pass (lock-order graph, whole-program checkers):
    # re-apply inline suppressions at the reported site. The ProgramIndex
    # is rebuilt from facts EVERY run — cached files contribute their
    # cached facts, so interprocedural verdicts always reflect the whole
    # current program, not just the files that changed.
    program = (ProgramIndex(project_facts["PI"])
               if "PI" in project_facts else None)
    ctx_by_path: dict[str, FileContext] = {}
    for c in checkers:
        extra = c.finalize(project_facts.get(c.id, {}), program)
        for v in extra:
            ctx = ctx_by_path.get(v.path)
            if ctx is None:
                try:
                    with open(os.path.join(root, v.path),
                              encoding="utf-8") as f:
                        src = f.read()
                    ctx = FileContext(v.path, src, ast.parse(src))
                except (OSError, SyntaxError):
                    ctx = None
                ctx_by_path[v.path] = ctx
            if ctx is not None:
                if not v.scope:
                    v.scope = ctx.scope_at(v.line)
                if not apply_suppressions(ctx, [v]):
                    continue
            all_violations.append(v)
    cache.save()

    try:
        entries = load_baseline(baseline_path) if baseline_path else []
    except BaselineError as e:
        res.errors.append(str(e))
        entries = []
    new, old, stale = split_baseline(all_violations, entries)
    new.sort(key=lambda v: (v.path, v.line, v.check))
    res.violations, res.baselined, res.stale = new, old, stale
    return res


def update_baseline(live_violations: list[Violation],
                    baseline_path: str) -> int:
    """Prune: drop entries whose violation no longer exists and shrink
    counts down to the live occurrence count. Never grows an entry —
    excess new occurrences must be fixed or baselined by hand with a
    reason. Returns how many entries were dropped outright."""
    entries = load_baseline(baseline_path)
    live: dict[tuple, int] = {}
    for v in live_violations:
        live[v.fingerprint()] = live.get(v.fingerprint(), 0) + 1
    kept, dropped = [], 0
    for e in entries:
        fp = _entry_fingerprint(e)
        have = int(e.get("count", 1))
        n = min(have, live.get(fp, 0))
        live[fp] = live.get(fp, 0) - n  # consume for duplicate entries
        if n == 0:
            dropped += 1
            continue
        e = dict(e)
        if n == 1:
            e.pop("count", None)
        else:
            e["count"] = n
        kept.append(e)
    with open(baseline_path, "w") as f:
        json.dump(kept, f, indent=2, sort_keys=True)
        f.write("\n")
    return dropped


# -- changed-only fast mode ---------------------------------------------------


def changed_paths(root: str) -> set[str]:
    """Repo-relative paths touched vs HEAD (worktree diff + untracked),
    per git. Empty set when git is unavailable."""
    import subprocess
    out: set[str] = set()
    for args in (["git", "-C", root, "diff", "--name-only", "HEAD"],
                 ["git", "-C", root, "ls-files", "--others",
                  "--exclude-standard"]):
        try:
            r = subprocess.run(args, capture_output=True, text=True,
                               timeout=15)
        except (OSError, subprocess.SubprocessError):
            continue
        if r.returncode == 0:
            out |= {ln.strip() for ln in r.stdout.splitlines()
                    if ln.strip()}
    return out


def filter_changed(res: "Result", changed: set[str]) -> "Result":
    """Keep only findings in changed files. The full program index was
    still built — an interprocedural violation REPORTED in a changed
    file is kept even if its witness chain spans unchanged ones."""
    return Result(
        violations=[v for v in res.violations if v.path in changed],
        baselined=[v for v in res.baselined if v.path in changed],
        stale=[e for e in res.stale if e.get("path") in changed],
        errors=[e for e in res.errors
                if e.split(":", 1)[0] in changed],
        files=res.files)


# -- CLI ----------------------------------------------------------------------


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def default_baseline_path(root: str) -> str:
    return os.path.join(root, "tools", "graftlint", "baseline.json")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="graftlint",
        description="Repo-native static analysis: TPU hot-path and "
                    "lock-discipline invariants (G1..G5).")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files or directories (default: the tier-1 "
                         "gate set — weaviate_tpu, tools/crashtest)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable output")
    ap.add_argument("--update-baseline", action="store_true",
                    help="prune baseline entries whose violation no "
                         "longer exists")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default tools/graftlint/"
                         "baseline.json)")
    ap.add_argument("--no-cache", action="store_true",
                    help="ignore and don't write the per-file cache")
    ap.add_argument("--root", default=None,
                    help="tree root for path scoping (default: this "
                         "checkout; paths are reported relative to it)")
    ap.add_argument("--changed-only", action="store_true",
                    help="pre-commit fast mode: the whole-program index "
                         "is still built, but only findings in files "
                         "changed vs HEAD (plus untracked) are reported")
    ap.add_argument("--env-inventory", action="store_true",
                    help="print the live env-read inventory (G11 scan) "
                         "as JSON and exit")
    ap.add_argument("--update-env-inventory", action="store_true",
                    help="regenerate the literal half of "
                         "tools/graftlint/env_inventory.json from the "
                         "live scan; dynamic entries keep their "
                         "hand-written reasons")
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root) if args.root else repo_root()
    # default = the exact tree test_repo_gate_zero_nonbaselined_violations
    # enforces; a narrower scan would misreport baseline entries for the
    # unscanned tools as stale
    paths = args.paths or ["weaviate_tpu", "tools/crashtest"]
    paths = [p for p in paths
             if os.path.exists(os.path.join(root, p))] or ["weaviate_tpu"]
    baseline_path = args.baseline or default_baseline_path(root)
    checkers = all_checkers()
    res = run(paths, root, use_cache=not args.no_cache,
              baseline_path=baseline_path, checkers=checkers)

    g11 = next((c for c in checkers if c.id == "G11"), None)
    if args.env_inventory and g11 is not None:
        print(json.dumps(g11.live_inventory(), indent=2,
                         sort_keys=True))
        return 0
    if args.update_env_inventory and g11 is not None:
        dropped, missing = g11.update_inventory()
        print(f"graftlint: env inventory regenerated ({dropped} "
              f"dynamic entr{'y' if dropped == 1 else 'ies'} dropped)")
        for d in missing:
            print(f"  unregistered dynamic read: {d['path']} "
                  f"[{d['scope']}] line {d['line']} — add a reasoned "
                  "'dynamic' entry by hand")
        return 0
    if args.changed_only:
        res = filter_changed(res, changed_paths(root))

    if args.update_baseline and os.path.exists(baseline_path):
        pruned = update_baseline(res.baselined + res.violations,
                                 baseline_path)
        res.stale = []
        if not args.as_json:
            print(f"graftlint: pruned {pruned} stale baseline "
                  f"entr{'y' if pruned == 1 else 'ies'}")

    if args.as_json:
        print(json.dumps({
            "files": res.files,
            "violations": [v.to_dict() for v in res.violations],
            "baselined": [v.to_dict() for v in res.baselined],
            "stale_baseline": res.stale,
            "errors": res.errors,
        }, indent=2))
    else:
        for v in res.violations:
            print(f"{v.path}:{v.line}:{v.col}: {v.check} {v.message}")
        for e in res.stale:
            print(f"{e['path']}: stale baseline entry for {e['check']} "
                  f"({e['message']!r}) — violation no longer exists; "
                  "delete it or run --update-baseline")
        for e in res.errors:
            print(f"graftlint: error: {e}", file=sys.stderr)
        n = len(res.violations)
        print(f"graftlint: {res.files} files, {n} violation"
              f"{'' if n == 1 else 's'}"
              + (f", {len(res.baselined)} baselined"
                 if res.baselined else "")
              + (f", {len(res.stale)} STALE baseline entries"
                 if res.stale else ""))
    return 0 if res.clean else 1


if __name__ == "__main__":
    raise SystemExit(main())
