"""The package's import matrix, as a ratchet.

One case per unit of ``weaviate_tpu``: each sub-package, and each
top-level module. An AST walk collects every import the unit makes,
lazy ones inside functions included, and asserts two things:

- nothing leaves the package for the rest of the checkout: no
  ``tools``, ``benchmarks``, ``tests`` or top-level script. A server
  installed without the checkout must lose nothing;
- the units it imports inside the package are exactly ``ALLOWED``, the
  edge set as it is. A new edge is a decision: add it here, in the PR
  that needs it, or do without it; a PR that removes the last import
  behind an edge removes the edge here, so it cannot come back unseen.

``DEBT_D13`` (ROADMAP D13) lists the edges that point the wrong way, a
lower layer reaching into one above it. Each is allowed only from the
file that has it today, so a second one fails.

The last test reads parameter names off the same trees: no callable
from the engine up takes a ``selection``.
"""

from __future__ import annotations

import ast
import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = "weaviate_tpu"
PKG_DIR = os.path.join(REPO_ROOT, PKG)

ALLOWED = {
    "__init__": set(),
    "__main__": {"server"},
    "config": set(),
    "server": {"api", "auth", "cluster", "config", "db", "modules",
               "native", "parallel", "runtime"},
    "api": {"__init__", "auth", "backup", "classification", "cluster",
            "db", "filters", "modules", "native", "parallel", "query",
            "replication", "runtime", "schema", "storage"},
    "auth": set(),
    "backup": {"cluster", "db", "modules", "schema"},
    "classification": {"ops", "storage", "text"},
    "cluster": {"backup", "db", "filters", "query", "replication",
                "runtime", "schema", "storage"},
    "db": {"backup", "cluster", "config", "engine", "filters", "modules",
           "native", "ops", "parallel", "query", "replication", "runtime",
           "schema", "storage", "text"},
    "engine": {"native", "ops", "parallel", "runtime", "storage"},
    "filters": {"schema", "text"},
    "modules": set(),
    "native": set(),
    "ops": {"runtime"},
    "parallel": {"ops", "runtime"},
    "query": set(),
    "replication": {"cluster", "runtime", "storage"},
    "runtime": {"config"},
    "schema": {"ops"},
    "storage": {"native", "runtime"},
    "text": {"native", "runtime", "schema", "storage"},
}

#: (unit, imported unit) -> the one file that may hold that edge
DEBT_D13 = {
    ("parallel", "engine"): "weaviate_tpu/parallel/sharded_search.py",
    ("runtime", "cluster"): "weaviate_tpu/runtime/retry.py",
    ("cluster", "api"): "weaviate_tpu/cluster/node.py",
}


def _units() -> dict[str, list[str]]:
    """unit name -> its source files, from the tree as it is."""
    units: dict[str, list[str]] = {}
    for name in sorted(os.listdir(PKG_DIR)):
        path = os.path.join(PKG_DIR, name)
        if name.endswith(".py"):
            units[name[:-3]] = [path]
        elif os.path.isfile(os.path.join(path, "__init__.py")):
            units[name] = sorted(
                os.path.join(dp, f) for dp, _, fs in os.walk(path)
                for f in fs if f.endswith(".py"))
    return units


UNITS = _units()

#: what else the checkout holds at its root that Python could import
OUTSIDE = {n[:-3] if n.endswith(".py") else n
           for n in os.listdir(REPO_ROOT)
           if n != PKG and (n.endswith(".py") or os.path.isdir(
               os.path.join(REPO_ROOT, n)))}


def _imported_modules(path: str) -> list[str]:
    """Absolute dotted names of everything ``path`` imports. For
    ``from weaviate_tpu import x`` that is ``weaviate_tpu.x``."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    here = os.path.relpath(os.path.dirname(path), REPO_ROOT).split(os.sep)
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - (node.level - 1)] if node.level else []
            mod = ".".join(base + ([node.module] if node.module else []))
            if mod == PKG or not node.module:
                out += [f"{mod}.{a.name}" for a in node.names]
            else:
                out.append(mod)
    return out


def _unit_of(module: str) -> str:
    """The unit a ``weaviate_tpu...`` module name belongs to; a name
    that is no unit is an attribute of the package's ``__init__``."""
    parts = module.split(".")
    return parts[1] if len(parts) > 1 and parts[1] in UNITS else "__init__"


@pytest.mark.parametrize("unit", sorted(set(UNITS) | set(ALLOWED)))
def test_unit_imports_only_what_the_table_allows(unit):
    assert unit in UNITS and unit in ALLOWED, (
        f"{unit}: ALLOWED and the tree name different units")
    leaves, edges = [], {}
    for path in UNITS[unit]:
        rel = os.path.relpath(path, REPO_ROOT)
        for module in _imported_modules(path):
            root = module.split(".")[0]
            if root in OUTSIDE:
                leaves.append(f"{rel}: {module}")
            elif root == PKG and _unit_of(module) != unit:
                edges.setdefault(_unit_of(module), set()).add(rel)
    assert leaves == [], (
        "the package imports from the checkout around it:\n"
        + "\n".join(leaves))
    for (debtor, target), debt_file in DEBT_D13.items():
        if debtor == unit:
            assert edges.pop(target, set()) <= {debt_file}, (
                f"{unit} -> {target} is debt D13, held by {debt_file} "
                "alone")
    assert set(edges) == ALLOWED[unit], (
        f"{unit}: new edges "
        f"{ {t: sorted(edges[t]) for t in set(edges) - ALLOWED[unit]} }, "
        f"edges gone {sorted(ALLOWED[unit] - set(edges))}: ALLOWED is the "
        "edge set as it is")


def test_no_store_or_index_takes_a_selector():
    """Which per-chunk selector a scan uses is decided in ``ops/topk.py``
    and named ONCE (``engine/store.py`` ``SCAN_SELECTION``): nothing from
    the engine up has a parameter a caller could set it by (PR 48)."""
    takers = []
    for unit in ("engine", "db", "schema"):
        for path in UNITS[unit]:
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef, ast.Lambda)):
                    continue
                a = node.args
                names = [x.arg for x in a.posonlyargs + a.args + a.kwonlyargs]
                if "selection" in names:
                    takers.append(f"{os.path.relpath(path, REPO_ROOT)}:"
                                  f"{node.lineno}")
    assert takers == []
