"""simcheck core: findings, pragmas, scoping helpers, rule registry.

The analyzer is stdlib-``ast`` only (no third-party deps). Each rule
module registers itself here:

* file rules   — ``fn(SourceFile) -> List[Finding]``; run per file
  (units discipline, wall-clock ban, iteration-order determinism).
* global rules — ``fn(List[SourceFile]) -> List[Finding]``; see the
  whole scanned tree at once (event-protocol completeness needs the
  ``EV_*`` definitions in ``scheduler.py`` AND their push/handle sites
  in ``engine.py``).

Suppression levels:

* ``# simcheck: ignore[rule]`` on the offending line — for sites that
  are intentional by design (e.g. the controller's wall-clock default
  ``clock`` for standalone use, which serving rigs replace);
* the checked-in baseline file — for grandfathered findings OUTSIDE
  ``serving/``/``storage/``/``core/`` only. Baseline keys are
  name-based (``path::rule::symbol``), not line-based, so unrelated
  edits don't invalidate them. A baseline entry pointing into a strict
  dir is itself an error: those dirs must stay at zero.
"""
from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Callable, Dict, Iterator, List, Optional, Set, Tuple

#: directories (path components) where findings can never be baselined:
#: fix the code or justify an inline pragma.
STRICT_DIRS = ("serving", "storage", "core")

_PRAGMA_RE = re.compile(r"#\s*simcheck:\s*ignore\[([a-z\-*,\s]+)\]")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str        # posix relpath from the scan root
    line: int
    rule: str
    symbol: str      # stable (line-independent) name for baseline keys
    message: str

    @property
    def key(self) -> str:
        return f"{self.path}::{self.rule}::{self.symbol}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclasses.dataclass
class SourceFile:
    path: str                    # posix relpath from the scan root
    tree: ast.Module
    lines: List[str]
    ignores: Dict[int, Set[str]]   # 1-based line -> suppressed rule ids

    def suppressed(self, line: int, rule: str) -> bool:
        rules = self.ignores.get(line)
        return bool(rules) and (rule in rules or "*" in rules)


def is_strict(path: str) -> bool:
    return any(part in STRICT_DIRS for part in path.split("/"))


def parse_pragmas(lines: List[str]) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, text in enumerate(lines, start=1):
        m = _PRAGMA_RE.search(text)
        if m:
            out[i] = {r.strip() for r in m.group(1).split(",") if r.strip()}
    return out


def load_source(abspath: str, relpath: str) -> SourceFile:
    with open(abspath, "r", encoding="utf-8") as f:
        text = f.read()
    lines = text.splitlines()
    return SourceFile(relpath.replace(os.sep, "/"), ast.parse(text),
                      lines, parse_pragmas(lines))


def discover(root: str) -> List[SourceFile]:
    """All ``.py`` files under ``root`` (a file path is accepted too),
    relpaths taken from ``root`` so baseline keys are root-relative."""
    if os.path.isfile(root):
        return [load_source(root, os.path.basename(root))]
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                ap = os.path.join(dirpath, fn)
                out.append(load_source(ap, os.path.relpath(ap, root)))
    return out


# -- scoping helpers ---------------------------------------------------------

FuncDef = (ast.FunctionDef, ast.AsyncFunctionDef)


def own_nodes(fn: ast.AST) -> Iterator[ast.AST]:
    """Walk a function's body WITHOUT descending into nested function
    definitions — per-scope checks (event-path classification, booking
    completeness) must not credit a nested scope's calls to its parent."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, FuncDef):
            stack.extend(ast.iter_child_nodes(node))


def iter_functions(tree: ast.Module,
                   ) -> List[Tuple[str, ast.AST]]:
    """Every (qualname, def) in the module, nested defs included."""
    out: List[Tuple[str, ast.AST]] = []

    def visit(node: ast.AST, prefix: str) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, FuncDef):
                q = f"{prefix}.{child.name}" if prefix else child.name
                out.append((q, child))
                visit(child, q)
            elif isinstance(child, ast.ClassDef):
                visit(child, f"{prefix}.{child.name}" if prefix
                      else child.name)
            else:
                visit(child, prefix)

    visit(tree, "")
    return out


def enclosing_scopes(tree: ast.Module) -> Dict[ast.AST, str]:
    """node -> qualname of the innermost enclosing function/class
    (module-level nodes map to '<module>'). Used for stable symbols."""
    scopes: Dict[ast.AST, str] = {}

    def visit(node: ast.AST, scope: str) -> None:
        for child in ast.iter_child_nodes(node):
            inner = scope
            if isinstance(child, FuncDef + (ast.ClassDef,)):
                inner = (f"{scope}.{child.name}"
                         if scope != "<module>" else child.name)
            scopes[child] = inner
            visit(child, inner)

    visit(tree, "<module>")
    return scopes


# -- rule registry -----------------------------------------------------------

FILE_RULES: Dict[str, Callable[[SourceFile], List[Finding]]] = {}
GLOBAL_RULES: Dict[str, Callable[[List[SourceFile]], List[Finding]]] = {}


def file_rule(name: str):
    def deco(fn):
        FILE_RULES[name] = fn
        return fn
    return deco


def global_rule(name: str):
    def deco(fn):
        GLOBAL_RULES[name] = fn
        return fn
    return deco


def run_rules(files: List[SourceFile]) -> List[Finding]:
    """All registered rules over the loaded tree, pragma-filtered and
    deduplicated, sorted by (path, line, rule)."""
    by_path = {sf.path: sf for sf in files}
    raw: List[Finding] = []
    for sf in files:
        for fn in FILE_RULES.values():
            raw.extend(fn(sf))
    for fn in GLOBAL_RULES.values():
        raw.extend(fn(files))
    seen, out = set(), []
    for f in raw:
        sf = by_path.get(f.path)
        if sf is not None and sf.suppressed(f.line, f.rule):
            continue
        marker = (f.path, f.line, f.rule, f.symbol)
        if marker not in seen:
            seen.add(marker)
            out.append(f)
    return sorted(out, key=lambda f: (f.path, f.line, f.rule, f.symbol))
