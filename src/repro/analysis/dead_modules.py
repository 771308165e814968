"""Checker DM — modules unreachable from the solver entry points.

The repo began from an LLM-training template; PRs 1-7 grew the solver
(core / kernels / launch.solve / launch.lsq / optim.compression) while
the template's ``models/`` / ``train/`` / ``data/`` stack sat untouched.
Dead modules are not free: they import-cycle into real code during
refactors, show up in grep, and rot silently (the PR-6 crash sweep
started in exactly such a leftover).

Reachability is computed over the ``repro.*`` import graph:

* roots — the solver surface (``repro.core``, ``repro.kernels``,
  ``repro.launch.solve``, ``repro.launch.lsq``, ``repro.launch.mesh``,
  ``repro.launch.serve``, ``repro.serve``, ``repro.optim``,
  ``repro.compat``, ``repro.analysis.lint``, ``repro.tune.autotune``
  — the autotune CLI is the sweep entry point) **plus**
  every ``repro.*`` module imported by ``benchmarks/`` or ``examples/``
  scripts — including imports inside their embedded subprocess-script
  strings (the product surface keeps a module alive; tests do *not* —
  a module only tests import is dead code with a test suite attached);
* an edge ``a -> b`` when module ``a`` imports ``b`` (``import`` /
  ``from`` forms, including ``from pkg import submodule``); importing a
  package reaches its ``__init__``.

* DM1 — a ``src/repro`` module not reachable from any root.
"""
from __future__ import annotations

import ast
import os
import re

from repro.analysis.common import (
    Finding, direct_imports, iter_py_files, module_name_for, parse_file, rel)

NAME = "dead-modules"

ROOT_MODULES = (
    "repro.core",
    "repro.kernels",
    "repro.launch.solve",
    "repro.launch.lsq",
    "repro.launch.mesh",
    "repro.launch.serve",
    "repro.serve",
    "repro.optim",
    "repro.compat",
    "repro.analysis.lint",
    "repro.tune.autotune",
)
SCRIPT_DIRS = ("benchmarks", "examples")

#: imports inside embedded subprocess-script strings (a forced-device
#: script passes e.g. `from repro.kernels import sweep_csr` as a string)
_STR_IMPORT = re.compile(
    r"^\s*(?:from\s+(repro(?:\.\w+)*)\s+import\s+([\w, ]+)"
    r"|import\s+(repro(?:\.\w+)*))", re.MULTILINE)


def _string_imports(tree: ast.AST) -> set[str]:
    found: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and "repro" in node.value:
            for m in _STR_IMPORT.finditer(node.value):
                if m.group(3):
                    found.add(m.group(3))
                else:
                    found.add(m.group(1))
                    for name in m.group(2).split(","):
                        name = name.strip().split(" as ")[0]
                        if name.isidentifier():
                            found.add(f"{m.group(1)}.{name}")
    return found


def _resolve(name: str, modules: set[str]) -> str | None:
    """Map an imported dotted name to an existing module (walking up
    through attribute accesses: ``repro.core.engine.solve`` -> engine)."""
    parts = name.split(".")
    while parts:
        cand = ".".join(parts)
        if cand in modules:
            return cand
        parts.pop()
    return None


def check_repo(root: str, parsed: dict[str, tuple[ast.AST, str]]
               ) -> list[Finding]:
    modules: dict[str, str] = {}   # dotted name -> repo-relative path
    imports: dict[str, set[str]] = {}
    for path, (tree, _src) in parsed.items():
        mod = module_name_for(root, os.path.join(root, path))
        if mod is None:
            continue
        modules[mod] = path
        imports[mod] = direct_imports(tree)

    known = set(modules)
    graph: dict[str, set[str]] = {}
    for mod, raw in imports.items():
        edges = set()
        for name in raw:
            tgt = _resolve(name, known)
            if tgt is not None:
                edges.add(tgt)
        # a submodule implicitly executes its package __init__ chain
        parts = mod.split(".")
        for i in range(1, len(parts)):
            pkg = ".".join(parts[:i])
            if pkg in known:
                edges.add(pkg)
        graph[mod] = edges

    roots: set[str] = set()
    for r in ROOT_MODULES:
        if r in known:
            roots.add(r)
    for d in SCRIPT_DIRS:
        full = os.path.join(root, d)
        if not os.path.isdir(full):
            continue
        for path in iter_py_files(full):
            try:
                tree, _src = parse_file(path)
            except SyntaxError:
                continue
            for name in direct_imports(tree) | _string_imports(tree):
                tgt = _resolve(name, known)
                if tgt is not None:
                    roots.add(tgt)

    reached: set[str] = set()
    frontier = sorted(roots)
    while frontier:
        mod = frontier.pop()
        if mod in reached:
            continue
        reached.add(mod)
        frontier.extend(graph.get(mod, ()))
        # reaching a package reaches its __init__ only; reaching a module
        # also reaches its enclosing packages (handled via graph edges).

    findings = []
    for mod in sorted(known - reached):
        findings.append(Finding(
            code="DM1", path=modules[mod], line=1, symbol=mod,
            message=(f"module {mod} is unreachable from the solver entry "
                     f"points ({', '.join(r for r in ROOT_MODULES if r in known)}) "
                     "and from benchmarks/ and examples/ — prune it or wire "
                     "it into the product surface")))
    return findings


def unreachable_modules(root: str | None = None) -> list[str]:
    """Convenience API for tests: the dotted names DM1 would flag."""
    from repro.analysis.common import repo_root
    root = root or repo_root()
    parsed = {}
    for path in iter_py_files(os.path.join(root, "src", "repro")):
        try:
            tree, src = parse_file(path)
        except SyntaxError:
            continue
        parsed[rel(root, path)] = (tree, src)
    return [f.symbol for f in check_repo(root, parsed)]
