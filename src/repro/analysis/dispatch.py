"""Checker DX — strategy-table exhaustiveness and single-source capability
sets.

The distributed engine dispatches on ``(action, format, sync)`` through
``_DISTRIBUTED_STRATEGIES`` and gates optional features through the
capability frozensets (``_FUSED_STRATEGIES`` / ``_OVERLAP_STRATEGIES`` /
``_COMPRESS_STRATEGIES``).  The PR-3 EllOp hole was exactly a missing
table row; this checker makes that class of drift mechanical:

* DX1 — a capability-set member that is not a strategy kind produced by
  the table (a stale or misspelled entry gates nothing);
* DX2 — a dispatch hole: an ``(action, format)`` pair where both the
  action and the format appear elsewhere in the table but the pair has
  no row under any sync (how the EllOp hole looked);
* DX3 — a capability set with no guard: no ``if ... kind not in <SET>:``
  whose body raises or warns (``raise``, a ``_warn_*`` helper or
  ``warnings.warn``) — requests for the feature would be silently
  ignored or crash instead of failing or downgrading loudly;
* DX4 — a duplicated capability literal: a tuple/set/list of string
  constants somewhere in ``src/repro`` equal (as a set) to one of the
  named capability constants, instead of referencing the constant — the
  hand-maintained copies drift;
* DX5 — the dispatch-miss error path does not enumerate the table
  programmatically (no ``sorted(_DISTRIBUTED_STRATEGIES)`` in the
  function that performs the ``.get``);
* DX6 — a hardcoded variant choice at a dispatch seam: a function that
  references two or more members of a *tuned variant family*
  (``VARIANT_FAMILIES`` — the CSR matvec kernel quartet, the
  fused-vs-scan sweep-engine pair) is choosing between measured
  alternatives, and must consult the tuning table (a ``repro.tune``
  lookup: ``resolve_fused`` / ``matvec_variant`` /
  ``tuned_rows_per_panel`` / ``lookup``) or carry a baseline entry
  justifying the bypass.  ``repro/tune`` (the table's own machinery)
  and ``repro/kernels`` (where the variants are *defined*, not chosen
  between) are exempt.

This is a repo-level checker (``check_repo``): the table lives in one
module but DX4 and DX6 scan every file.
"""
from __future__ import annotations

import ast
import re

from repro.analysis.common import (
    Finding, call_name, const_str_tuple, dotted_name)

NAME = "dispatch"

TABLE_NAME = "_DISTRIBUTED_STRATEGIES"
CONST_NAME = re.compile(r"^_?[A-Z][A-Z0-9_]*$")

#: DX6 — the tuned variant families (family name -> member symbols).
#: Referencing >=2 members of one family in a single function means the
#: function chooses between measured alternatives at runtime.
VARIANT_FAMILIES = {
    "csr_matvec": frozenset({
        "spmv_csr", "spmv_csr_prefetch",
        "spmv_csr_sliced", "spmv_csr_sliced_prefetch"}),
    "sweep_engine": frozenset({
        "_sequential_fused_impl", "_sequential_scan_impl"}),
}

#: DX6 — the ``repro.tune`` lookup entry points that make a variant
#: choice table-driven (matched on the called name's last segment).
TUNE_LOOKUPS = frozenset({
    "resolve_fused", "matvec_variant", "tuned_rows_per_panel", "lookup"})

#: DX6 exemptions: the tuning machinery itself and the kernel modules
#: where the family members are defined.
DX6_EXEMPT = ("repro/tune/", "repro/kernels/")


def _module_constants(tree: ast.AST
                      ) -> dict[str, tuple[tuple[str, ...], int, ast.AST]]:
    """ALL_CAPS module-level string-tuple constants:
    name -> (values, line, value-AST)."""
    out = {}
    for node in tree.body if isinstance(tree, ast.Module) else []:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and CONST_NAME.match(node.targets[0].id):
            vals = const_str_tuple(node.value)
            if vals:
                out[node.targets[0].id] = (vals, node.lineno, node.value)
    return out


def _parse_table(tree: ast.AST) -> dict[tuple[str, str, str], str] | None:
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == TABLE_NAME \
                and isinstance(node.value, ast.Dict):
            table = {}
            for k, v in zip(node.value.keys, node.value.values):
                kt = const_str_tuple(k)
                if kt and len(kt) == 3 and isinstance(v, ast.Constant):
                    table[kt] = v.value
            return table
    return None


def _has_fallback_guard(tree: ast.AST, set_name: str) -> bool:
    for node in ast.walk(tree):
        if not isinstance(node, ast.If):
            continue
        hit = any(
            isinstance(sub, ast.Compare)
            and any(isinstance(op, ast.NotIn) for op in sub.ops)
            and any(dotted_name(c) == set_name for c in sub.comparators)
            for sub in ast.walk(node.test))
        if not hit:
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Raise):
                return True
            if isinstance(sub, ast.Call):
                cn = call_name(sub) or ""
                if cn == "warnings.warn" or cn.split(".")[-1].startswith("_warn_"):
                    return True
    return False


def _get_functions(tree: ast.AST) -> list[ast.FunctionDef]:
    """Functions that perform the ``_DISTRIBUTED_STRATEGIES.get`` lookup."""
    out = []
    for fn in ast.walk(tree):
        if isinstance(fn, ast.FunctionDef) and any(
                isinstance(n, ast.Call)
                and call_name(n) == f"{TABLE_NAME}.get"
                for n in ast.walk(fn)):
            out.append(fn)
    return out


def check_repo(root: str, parsed: dict[str, tuple[ast.AST, str]]
               ) -> list[Finding]:
    findings: list[Finding] = []
    table_path, table, table_tree = None, None, None
    for path, (tree, _src) in parsed.items():
        t = _parse_table(tree)
        if t is not None:
            table_path, table, table_tree = path, t, tree
            break
    if table is None:
        return findings  # no distributed engine in this tree

    constants = _module_constants(table_tree)
    kinds = set(table.values())

    # DX1 — stale capability members
    cap_sets = {n: v for n, (v, _ln, _node) in constants.items()
                if n.endswith("_STRATEGIES")}
    for set_name, members in sorted(cap_sets.items()):
        line = constants[set_name][1]
        for m in members:
            if m not in kinds:
                findings.append(Finding(
                    code="DX1", path=table_path, line=line, symbol=set_name,
                    message=(f"{m!r} is not a strategy kind produced by "
                             f"{TABLE_NAME} (kinds: {sorted(kinds)}) — "
                             "stale capability entry gates nothing")))

    # DX2 — (action, format) holes
    actions = {a for (a, _f, _s) in table}
    formats = {f for (_a, f, _s) in table}
    covered = {(a, f) for (a, f, _s) in table}
    for a in sorted(actions):
        for f in sorted(formats):
            if (a, f) not in covered:
                findings.append(Finding(
                    code="DX2", path=table_path, line=0,
                    symbol=f"{TABLE_NAME}[{a},{f}]",
                    message=(f"dispatch hole: action={a!r} and format={f!r} "
                             "both appear in the table but the pair has no "
                             "row under any sync — add the row or an "
                             "explicit NotImplementedError with rationale")))

    # DX3 — capability sets without a warn-and-downgrade guard
    for set_name in sorted(cap_sets):
        if not _has_fallback_guard(table_tree, set_name):
            findings.append(Finding(
                code="DX3", path=table_path, line=constants[set_name][1],
                symbol=set_name,
                message=(f"no `kind not in {set_name}` guard that raises "
                         "or warns — feature requests outside the set would "
                         "be silently ignored or crash")))

    # DX5 — dispatch-miss error must enumerate the table programmatically
    for fn in _get_functions(table_tree):
        enumerates = any(
            isinstance(n, ast.Call) and call_name(n) == "sorted"
            and n.args and dotted_name(n.args[0]) == TABLE_NAME
            for n in ast.walk(fn))
        if not enumerates:
            findings.append(Finding(
                code="DX5", path=table_path, line=fn.lineno, symbol=fn.name,
                message=(f"dispatches via {TABLE_NAME}.get but the miss "
                         f"path never enumerates sorted({TABLE_NAME}) — "
                         "error messages must list the real table, not a "
                         "hand-maintained string")))

    # DX4 — duplicated capability literals anywhere in the tree.  Two
    # triggers: (a) a literal equal to a named capability constant, and
    # (b) the same >=3-element string-tuple literal appearing at two or
    # more sites (the pre-constant form of the same drift).
    tracked = {n: frozenset(v) for n, (v, _ln, _node) in constants.items()
               if len(v) >= 2}
    defining_nodes = {id(sub) for _n, (_v, _ln, node) in constants.items()
                      for sub in ast.walk(node)}
    occurrences: dict[frozenset, list[tuple[str, int]]] = {}
    for path, (tree, _src) in sorted(parsed.items()):
        consumed: set[int] = set()   # inner displays of frozenset(...) calls
        for node in ast.walk(tree):
            if id(node) in consumed:
                continue
            vals = const_str_tuple(node)
            if not vals or len(vals) < 2:
                continue
            if isinstance(node, ast.Call):
                # one literal, two AST nodes: don't count the wrapped
                # tuple/set display again when the walk reaches it
                consumed.add(id(node.args[0]))
            vset = frozenset(vals)
            hit_constant = False
            for cname, cvals in sorted(tracked.items()):
                if vset != cvals:
                    continue
                hit_constant = True
                if path == table_path and id(node) in defining_nodes:
                    continue  # the defining assignment itself
                findings.append(Finding(
                    code="DX4", path=path, line=node.lineno,
                    symbol=f"literal=={cname}",
                    message=(f"string literal duplicating {cname} "
                             f"({sorted(vset)}) — import the constant from "
                             "the table module so the copies cannot drift")))
            if not hit_constant and len(vset) >= 3:
                occurrences.setdefault(vset, []).append((path, node.lineno))
    for vset, sites in sorted(occurrences.items(),
                              key=lambda kv: sorted(kv[0])):
        if len(sites) < 2:
            continue
        for path, line in sites:
            findings.append(Finding(
                code="DX4", path=path, line=line,
                symbol=f"literal={'|'.join(sorted(vset))}",
                message=(f"string-tuple literal {sorted(vset)} repeated at "
                         f"{len(sites)} sites — hoist it to one named "
                         "constant so the copies cannot drift")))

    # DX6 — hardcoded variant selection bypassing the tuning table
    for path, (tree, _src) in sorted(parsed.items()):
        if any(seg in path for seg in DX6_EXEMPT):
            continue
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            referenced: set[str] = set()
            consults = False
            for node in ast.walk(fn):
                dn = dotted_name(node)
                if dn:
                    referenced.add(dn.split(".")[-1])
                if isinstance(node, ast.Call):
                    cn = (call_name(node) or "").split(".")[-1]
                    if cn in TUNE_LOOKUPS:
                        consults = True
            if consults:
                continue
            for fam, members in sorted(VARIANT_FAMILIES.items()):
                hit = sorted(referenced & members)
                if len(hit) >= 2:
                    findings.append(Finding(
                        code="DX6", path=path, line=fn.lineno,
                        symbol=fn.name,
                        message=(f"references {len(hit)} members of the "
                                 f"tuned {fam!r} variant family ({hit}) "
                                 "without a repro.tune lookup "
                                 f"({'/'.join(sorted(TUNE_LOOKUPS))}) — "
                                 "route the choice through the tuning "
                                 "table or baseline the bypass with a "
                                 "justification")))
    return findings
