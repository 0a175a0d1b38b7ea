"""metrolint core: findings, repo loading, check registry, baseline.

Checks are plain functions ``check(repo) -> List[Finding]`` registered via
:func:`register_check`.  A :class:`Repo` lazily parses every tracked Python
file once and hands the same ASTs to all checks; checks locate their scope
by *path suffix* (``core/simulator.py``, ``kernels/ops.py``), so the
fixture tests can exercise them on miniature tmp-dir repos with the same
layout as the real tree.

Baseline discipline: a finding's :attr:`Finding.fingerprint` deliberately
excludes the line number (moves must not invalidate suppressions) and
instead keys on ``(check, path, obj, key)`` where ``obj`` is the enclosing
scope's qualname and ``key`` a per-check stable discriminator.
"""
from __future__ import annotations

import ast
import dataclasses
import json
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

BASELINE_NAME = "metrolint.baseline.json"
BASELINE_VERSION = 1

# directories never scanned (vendored/generated/VCS content)
_SKIP_DIRS = {".git", "__pycache__", ".bench_cache", "node_modules",
              ".pytest_cache", ".ruff_cache", ".mypy_cache", "build"}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One invariant violation at ``path:line``."""

    check: str
    path: str  # repo-relative posix path
    line: int
    obj: str  # qualname of the enclosing scope ('' = module level)
    key: str  # stable discriminator within (check, path, obj)
    message: str

    @property
    def fingerprint(self) -> str:
        """Line-independent identity used for baseline matching."""
        return f"{self.check}::{self.path}::{self.obj}::{self.key}"

    def render(self) -> str:
        where = f" [{self.obj}]" if self.obj else ""
        return f"{self.path}:{self.line}: {self.check}:{where} {self.message}"


class Module:
    """One parsed source file (AST parsed lazily, cached)."""

    def __init__(self, root: Path, path: Path) -> None:
        self.abspath = path
        self.relpath = path.relative_to(root).as_posix()
        self._source: Optional[str] = None
        self._tree: Optional[ast.Module] = None
        self._error: Optional[SyntaxError] = None

    @property
    def source(self) -> str:
        if self._source is None:
            self._source = self.abspath.read_text()
        return self._source

    @property
    def tree(self) -> Optional[ast.Module]:
        """Parsed AST, or None when the file does not parse (the syntax
        error is surfaced as its own finding by :func:`run_checks`)."""
        if self._tree is None and self._error is None:
            try:
                self._tree = ast.parse(self.source)
            except SyntaxError as e:  # pragma: no cover - defensive
                self._error = e
        return self._tree

    @property
    def syntax_error(self) -> Optional[SyntaxError]:
        self.tree
        return self._error


class Repo:
    """All Python files under one root, parsed once and shared by checks."""

    def __init__(self, root: Path) -> None:
        self.root = Path(root).resolve()
        self._modules: Optional[List[Module]] = None

    def modules(self) -> List[Module]:
        if self._modules is None:
            out = []
            for p in sorted(self.root.rglob("*.py")):
                rel = p.relative_to(self.root).parts
                if any(part in _SKIP_DIRS for part in rel):
                    continue
                out.append(Module(self.root, p))
            self._modules = out
        return self._modules

    def ending_with(self, *suffixes: str) -> List[Module]:
        """Modules whose repo-relative path ends with any given suffix."""
        return [m for m in self.modules()
                if any(m.relpath.endswith(s) for s in suffixes)]

    def under(self, prefix: str) -> List[Module]:
        """Modules whose repo-relative path starts with ``prefix``."""
        return [m for m in self.modules() if m.relpath.startswith(prefix)]

    def get(self, suffix: str) -> Optional[Module]:
        mods = self.ending_with(suffix)
        return mods[0] if mods else None


# --------------------------------------------------------------- AST helpers
def iter_scopes(tree: ast.Module) -> Iterator[Tuple[str, ast.AST]]:
    """Yield ``(qualname, node)`` for every function/method, walking into
    classes (``Cls.meth``) but not into nested functions (a nested def is
    analyzed as part of its enclosing scope)."""

    def walk(body: Sequence[ast.stmt], prefix: str) -> Iterator:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield prefix + node.name, node
            elif isinstance(node, ast.ClassDef):
                yield from walk(node.body, prefix + node.name + ".")

    yield from walk(tree.body, "")


def attr_chain(node: ast.AST) -> List[str]:
    """``a.b.c`` -> ``['a', 'b', 'c']``; empty when the base is dynamic."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return []


def find_scope(tree: ast.Module, qualname: str) -> Optional[ast.AST]:
    for name, node in iter_scopes(tree):
        if name == qualname:
            return node
    return None


# ------------------------------------------------------------ check registry
CheckFn = Callable[[Repo], List[Finding]]
_CHECKS: Dict[str, CheckFn] = {}
_CHECK_DOCS: Dict[str, str] = {}


def register_check(check_id: str, doc: str) -> Callable[[CheckFn], CheckFn]:
    def deco(fn: CheckFn) -> CheckFn:
        if check_id in _CHECKS:
            raise ValueError(f"duplicate check id {check_id!r}")
        _CHECKS[check_id] = fn
        _CHECK_DOCS[check_id] = doc
        return fn

    return deco


def all_checks() -> Dict[str, str]:
    """check id -> one-line description, in registration order."""
    return dict(_CHECK_DOCS)


def run_checks(root: Path,
               checks: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run the selected (default: all) checks over the repo at ``root``."""
    repo = Repo(Path(root))
    selected = list(checks) if checks else list(_CHECKS)
    unknown = [c for c in selected if c not in _CHECKS]
    if unknown:
        raise ValueError(f"unknown checks {unknown}; have {sorted(_CHECKS)}")
    findings: List[Finding] = []
    for m in repo.modules():
        err = m.syntax_error
        if err is not None:
            findings.append(Finding(
                check="parse", path=m.relpath, line=err.lineno or 1,
                obj="", key="syntax-error", message=f"does not parse: {err}"))
    for cid in selected:
        findings.extend(_CHECKS[cid](repo))
    findings.sort(key=lambda f: (f.path, f.line, f.check, f.key))
    return findings


# ------------------------------------------------------------------ baseline
@dataclasses.dataclass(frozen=True)
class Suppression:
    check: str
    path: str
    obj: str
    key: str
    reason: str

    @property
    def fingerprint(self) -> str:
        return f"{self.check}::{self.path}::{self.obj}::{self.key}"


def load_baseline(path: Path) -> List[Suppression]:
    """Parse the committed baseline; every entry must carry a reason."""
    if not Path(path).exists():
        return []
    doc = json.loads(Path(path).read_text())
    if doc.get("version") != BASELINE_VERSION:
        raise ValueError(f"baseline {path}: unsupported version "
                         f"{doc.get('version')!r}")
    out = []
    for i, e in enumerate(doc.get("suppressions", [])):
        reason = str(e.get("reason", "")).strip()
        if not reason:
            raise ValueError(f"baseline {path}: suppression #{i} has no "
                             "reason — every deliberate deviation must say "
                             "why it is deliberate")
        out.append(Suppression(check=e["check"], path=e["path"],
                               obj=e.get("obj", ""), key=e.get("key", ""),
                               reason=reason))
    return out


def apply_baseline(findings: Sequence[Finding],
                   baseline: Sequence[Suppression]
                   ) -> Tuple[List[Finding], List[Finding],
                              List[Suppression]]:
    """Split into (new, suppressed, stale-suppressions).

    Stale entries — suppressions matching no current finding — are
    reported (and fail the CLI) so the baseline shrinks as findings are
    actually fixed instead of fossilizing."""
    by_fp = {s.fingerprint: s for s in baseline}
    new, suppressed = [], []
    hit = set()
    for f in findings:
        if f.fingerprint in by_fp:
            suppressed.append(f)
            hit.add(f.fingerprint)
        else:
            new.append(f)
    stale = [s for s in baseline if s.fingerprint not in hit]
    return new, suppressed, stale


def write_baseline(path: Path, findings: Sequence[Finding],
                   existing: Sequence[Suppression] = (),
                   default_reason: str = "baselined at adoption; triage"
                   ) -> None:
    """Write a baseline covering ``findings``, preserving the reasons of
    entries already present."""
    reasons = {s.fingerprint: s.reason for s in existing}
    entries = []
    for f in findings:
        entries.append({
            "check": f.check, "path": f.path, "obj": f.obj, "key": f.key,
            "reason": reasons.get(f.fingerprint, default_reason),
        })
    doc = {"version": BASELINE_VERSION, "suppressions": entries}
    Path(path).write_text(json.dumps(doc, indent=1) + "\n")
