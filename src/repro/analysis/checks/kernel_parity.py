"""kernel-parity: every Pallas kernel needs ops wiring, a ref oracle and
an interpret-mode parity test.

The dispatch contract (``kernels/ops.py``): on a TPU, compiled Pallas;
anywhere else, interpret mode when the caller passes ``interpret=True``
and an error otherwise — never the jnp reference from ``kernels/ref.py``.
The CPU test suite never runs compiled Pallas, so what stands between a
kernel edit and silently-wrong TPU results is the interpret-mode parity
test against the ref oracle.  Three rules per
public kernel function in ``kernels/*.py`` (excluding ``ops.py`` /
``ref.py``):

  1. **wired** — some ``ops.py`` function references it (otherwise the
     kernel is dead code the dispatch contract never covers);
  2. **ref twin** — ``kernels/ref.py`` exists and exports oracles;
  3. **parity test** — some test function under ``tests/`` calls one of
     the kernel's dispatchers with ``interpret=True`` (keyword, or the
     positional-``True`` idiom of the flash tests) AND references the
     ``ref`` module in the same function — i.e. an actual interpret-vs-
     oracle comparison, not just a smoke call.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Set

from ..core import Finding, Module, Repo, iter_scopes, register_check

_EXCLUDE = ("ops.py", "ref.py", "__init__.py")


def _kernel_modules(repo: Repo) -> List[Module]:
    return [m for m in repo.modules()
            if "kernels/" in m.relpath
            and not m.relpath.endswith(_EXCLUDE)]


def _public_defs(mod: Module) -> List[ast.FunctionDef]:
    if mod.tree is None:
        return []
    return [n for n in mod.tree.body
            if isinstance(n, ast.FunctionDef)
            and not n.name.startswith("_")]


def _names_used(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _ops_reference_map(ops: Module) -> Dict[str, Set[str]]:
    """ops function name -> every Name it references, with module-level
    ``X = jax.jit(ref.Y)`` aliases resolved one hop and ``D.defvjp(f, b)``
    fwd/bwd bodies merged into ``D`` (the flash custom_vjp idiom)."""
    tree = ops.tree
    if tree is None:
        return {}
    alias_refs: Dict[str, Set[str]] = {}
    fn_refs: Dict[str, Set[str]] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            alias_refs[node.targets[0].id] = _names_used(node.value) | {
                a.attr for a in ast.walk(node.value)
                if isinstance(a, ast.Attribute)}
        elif isinstance(node, ast.FunctionDef):
            fn_refs[node.name] = _names_used(node)
    # defvjp merge
    for node in tree.body:
        if isinstance(node, ast.Expr) and isinstance(node.value, ast.Call):
            call = node.value
            if isinstance(call.func, ast.Attribute) \
                    and call.func.attr == "defvjp" \
                    and isinstance(call.func.value, ast.Name):
                owner = call.func.value.id
                for arg in call.args:
                    if isinstance(arg, ast.Name) and arg.id in fn_refs:
                        fn_refs.setdefault(owner, set()).update(
                            fn_refs[arg.id])
    # one-hop alias resolution: a function referencing _x_jit inherits the
    # names of the module-level assignment that defined it
    for name, refs in fn_refs.items():
        for a, arefs in alias_refs.items():
            if a in refs:
                refs.update(arefs)
    return fn_refs


def _ref_aliases(tree: ast.Module) -> Set[str]:
    """Names in a test file that are bound to ``kernels.ref`` (module
    aliases AND directly-imported oracle functions)."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module.endswith("kernels"):
                for a in node.names:
                    if a.name == "ref":
                        out.add(a.asname or a.name)
            elif node.module.endswith("kernels.ref"):
                for a in node.names:
                    out.add(a.asname or a.name)
    return out


def _is_parity_call(call: ast.Call, dispatchers: Set[str]) -> bool:
    fn = call.func
    name = fn.attr if isinstance(fn, ast.Attribute) else (
        fn.id if isinstance(fn, ast.Name) else "")
    if name not in dispatchers:
        return False
    for kw in call.keywords:
        if kw.arg == "interpret" and isinstance(kw.value, ast.Constant) \
                and kw.value.value is True:
            return True
    return any(isinstance(a, ast.Constant) and a.value is True
               for a in call.args)


def _has_parity_test(repo: Repo, dispatchers: Set[str]) -> bool:
    for mod in repo.under("tests/"):
        tree = mod.tree
        if tree is None:
            continue
        refs = _ref_aliases(tree)
        for _qual, func in iter_scopes(tree):
            local_refs = refs | _ref_aliases_from(func)
            has_call = any(
                isinstance(n, ast.Call) and _is_parity_call(n, dispatchers)
                for n in ast.walk(func))
            if not has_call:
                continue
            uses_ref = any(
                isinstance(n, ast.Name) and n.id in local_refs
                for n in ast.walk(func))
            if uses_ref:
                return True
    return False


def _ref_aliases_from(func: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for node in ast.walk(func):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module.endswith("kernels"):
                out.update(a.asname or a.name for a in node.names
                           if a.name == "ref")
            elif node.module.endswith("kernels.ref"):
                out.update(a.asname or a.name for a in node.names)
    return out


@register_check(
    "kernel-parity",
    "every public Pallas kernel is wired in ops.py, has a ref.py oracle "
    "and an interpret-mode parity test under tests/")
def check(repo: Repo) -> List[Finding]:
    out: List[Finding] = []
    kmods = _kernel_modules(repo)
    if not kmods:
        return out
    ops = repo.get("kernels/ops.py")
    ref = repo.get("kernels/ref.py")
    ops_refs = _ops_reference_map(ops) if ops is not None else {}
    for mod in kmods:
        for fn in _public_defs(mod):
            if ref is None:
                out.append(Finding(
                    check="kernel-parity", path=mod.relpath, line=fn.lineno,
                    obj=fn.name, key="no-ref-module",
                    message="kernels/ref.py is missing — every kernel "
                            "needs a pure-jnp oracle twin"))
                continue
            dispatchers = {name for name, refs in ops_refs.items()
                           if fn.name in refs and not name.startswith("_")}
            if not dispatchers:
                out.append(Finding(
                    check="kernel-parity", path=mod.relpath, line=fn.lineno,
                    obj=fn.name, key="unwired",
                    message=f"public kernel {fn.name!r} is not referenced "
                            "by any ops.py dispatcher — the TPU/interpret/"
                            "jnp dispatch contract never covers it"))
                continue
            if not _has_parity_test(repo, dispatchers | {fn.name}):
                out.append(Finding(
                    check="kernel-parity", path=mod.relpath, line=fn.lineno,
                    obj=fn.name, key="no-parity-test",
                    message=f"no interpret-mode parity test for kernel "
                            f"{fn.name!r}: no test function calls "
                            f"{sorted(dispatchers)} with interpret=True "
                            "and compares against kernels.ref"))
    return out
