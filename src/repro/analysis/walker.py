"""AST collection helpers for the protocol conformance linter.

Everything here is SYNTACTIC: sources are parsed, never imported, so the
linter can analyze a deliberately broken fixture tree (tests feed those
through the ``overrides`` map) without executing it.  The collectors
recognize the repo's three string namespaces by the contexts the runtime
actually uses:

* wire KINDS — ``MessageSpec(..., kind, ...)`` arguments, ``kind=``
  keywords, assignments to ``*_kind`` variables, and comparisons against
  kind-ish expressions;
* worker/response OPS — ``{"op": ...}`` request/response dict literals and
  comparisons against op-ish expressions (``resp["op"] == ...``);
* compat CHECK calls — ``compat.check("<layer>", <feature kwargs>)``.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator, Optional


@dataclass(frozen=True)
class ModuleSource:
    """One parsed source file, keyed by repo-relative path."""

    relpath: str
    text: str
    tree: ast.Module


def load_module(root: Path, relpath: str,
                overrides: Optional[dict] = None) -> ModuleSource:
    """Parse one file, preferring the ``overrides`` map (repo-relative
    path -> source text) so tests can run the linter against mutated or
    broken sources without touching disk."""
    if overrides and relpath in overrides:
        text = overrides[relpath]
    else:
        text = (root / relpath).read_text()
    return ModuleSource(relpath, text, ast.parse(text, filename=relpath))


def iter_src_files(root: Path, overrides: Optional[dict] = None,
                   subdir: str = "src/repro") -> Iterator[str]:
    """Repo-relative paths of every .py under ``subdir``, unioned with any
    override paths in that subtree (an override may add a file that does
    not exist on disk)."""
    seen = set()
    base = root / subdir
    if base.exists():
        for p in sorted(base.rglob("*.py")):
            rel = p.relative_to(root).as_posix()
            seen.add(rel)
            yield rel
    for rel in sorted(overrides or ()):
        if rel.startswith(subdir + "/") and rel.endswith(".py") \
                and rel not in seen:
            yield rel


# -- namespace-aware expression tests ---------------------------------------

def _is_kindish(node: ast.AST) -> bool:
    """Does this expression plausibly hold a wire kind?  Conservative on
    names (exact ``kind`` or ``*_kind``) so ``mode``-style strings
    are never dragged into the kind namespace."""
    if isinstance(node, ast.Name):
        return node.id == "kind" or node.id.endswith("_kind")
    if isinstance(node, ast.Attribute):
        return node.attr == "kind" or node.attr.endswith("_kind")
    if isinstance(node, ast.Subscript):
        sl = node.slice
        return isinstance(sl, ast.Constant) and sl.value == "kind"
    return False


def _is_opish(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        return node.id == "op" or node.id.endswith("_op")
    if isinstance(node, ast.Attribute):
        return node.attr == "op" or node.attr.endswith("_op")
    if isinstance(node, ast.Subscript):
        sl = node.slice
        return isinstance(sl, ast.Constant) and sl.value == "op"
    if isinstance(node, ast.Call):  # resp.get("op")
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "get" and node.args:
            a = node.args[0]
            return isinstance(a, ast.Constant) and a.value == "op"
    return False


def _call_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _str_constants(node: ast.AST) -> Iterator[tuple[str, int]]:
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            yield n.value, n.lineno


# -- collectors -------------------------------------------------------------

def kind_literals(mod: ModuleSource) -> list[tuple[str, int]]:
    """Every string literal used AS a wire kind: MessageSpec's 4th arg /
    ``kind=`` keyword, assignments to kind-named variables, and
    comparisons against kind-ish expressions."""
    out: list[tuple[str, int]] = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call):
            if _call_name(node) == "MessageSpec" and len(node.args) >= 4:
                a = node.args[3]
                if isinstance(a, ast.Constant) and isinstance(a.value, str):
                    out.append((a.value, a.lineno))
            for kw in node.keywords:
                if kw.arg == "kind":
                    for v, ln in _str_constants(kw.value):
                        out.append((v, ln))
        elif isinstance(node, ast.Compare):
            sides = [node.left, *node.comparators]
            if any(_is_kindish(s) for s in sides):
                for s in sides:
                    for v, ln in _str_constants(s):
                        out.append((v, ln))
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            if any(_is_kindish(t) for t in targets) and node.value is not None:
                for v, ln in _str_constants(node.value):
                    out.append((v, ln))
        elif isinstance(node, ast.Dict):
            for k, v in zip(node.keys, node.values):
                if isinstance(k, ast.Constant) and k.value == "kind" \
                        and isinstance(v, ast.Constant) \
                        and isinstance(v.value, str):
                    out.append((v.value, v.lineno))
    return out


def op_literals(mod: ModuleSource) -> dict[str, list[tuple[str, int]]]:
    """Every string literal used AS a wire op, split by context:
    ``"dict"`` — the value at an ``"op"`` key in a dict literal (a request
    being submitted or a response being built); ``"compare"`` — compared
    against an op-ish expression (dispatch/routing)."""
    out: dict[str, list[tuple[str, int]]] = {"dict": [], "compare": []}
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Dict):
            for k, v in zip(node.keys, node.values):
                if isinstance(k, ast.Constant) and k.value == "op" \
                        and isinstance(v, ast.Constant) \
                        and isinstance(v.value, str):
                    out["dict"].append((v.value, v.lineno))
        elif isinstance(node, ast.Compare):
            sides = [node.left, *node.comparators]
            if any(_is_opish(s) for s in sides):
                for s in sides:
                    if not _is_opish(s):
                        for v, ln in _str_constants(s):
                            out["compare"].append((v, ln))
    return out


def registry_constant_ids(mod: ModuleSource,
                          registry_call: str) -> set[int]:
    """``id()`` of every string-constant node inside calls to
    ``registry_call`` (e.g. ``WireKind``) — the registry DECLARING a name
    is not the schedule PRODUCING it, so W003 excludes these."""
    ids: set[int] = set()
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and _call_name(node) == registry_call:
            for n in ast.walk(node):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    ids.add(id(n))
    return ids


def produced_kind_literals(mod: ModuleSource,
                           kinds: set[str]) -> set[str]:
    """Registered kinds that appear as plain string constants OUTSIDE the
    WireKind registry calls — i.e. some schedule constructor actually
    produces a MessageSpec with that kind."""
    registry = registry_constant_ids(mod, "WireKind")
    produced: set[str] = set()
    for n in ast.walk(mod.tree):
        if isinstance(n, ast.Constant) and isinstance(n.value, str) \
                and n.value in kinds and id(n) not in registry:
            produced.add(n.value)
    return produced


def compat_check_calls(mod: ModuleSource) -> list[tuple[str, set, int]]:
    """Every ``compat.check("<layer>", ...)`` (or bare ``check(...)``)
    call: (layer, set of keyword names passed, line)."""
    out: list[tuple[str, set, int]] = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call) or _call_name(node) != "check":
            continue
        f = node.func
        # require compat.check / <mod>.check, or a bare check imported
        # from compat — attribute calls on anything named *compat* or a
        # bare name both count; other ".check" methods are excluded by
        # the first-argument shape below
        if not node.args:
            continue
        layer = node.args[0]
        if not (isinstance(layer, ast.Constant)
                and isinstance(layer.value, str)):
            continue
        if isinstance(f, ast.Attribute) and not (
                isinstance(f.value, ast.Name)
                and "compat" in f.value.id):
            continue
        out.append((layer.value,
                    {kw.arg for kw in node.keywords if kw.arg},
                    node.lineno))
    return out


def function_defs(mod: ModuleSource) -> set[str]:
    """Top-level function names (the costs.py byte-model namespace)."""
    return {n.name for n in mod.tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}


def class_methods(mod: ModuleSource, class_name: str) -> set[str]:
    for n in mod.tree.body:
        if isinstance(n, ast.ClassDef) and n.name == class_name:
            return {m.name for m in n.body
                    if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))}
    return set()
