#!/usr/bin/env python
"""Single-sparse-executor lint for ``core/mixing.py`` (CI gate).

The sparse backend used to carry two executors: a one-client-per-shard
body and a blocked ``m_local > 1`` body. PR 9 folded them into ONE block
realization (``_make_sparse_exec``), which at ``m_local == 1``
degenerates to the historical one-permute-per-step program — the mesh
HLO pins hold either way. This lint keeps it that way: a second sparse
executor (or a stray ``ppermute`` call site outside it) is a CI
failure, not a review nit, so the duplication cannot
silently grow back.

Checks, all by AST (no imports of jax needed):

  1. exactly one top-level ``*_exec``-named function —
     ``_make_sparse_exec``;
  2. every ``jax.lax.ppermute`` / ``lax.ppermute`` / bare ``ppermute``
     call site lives inside ``_make_sparse_exec``;
  3. every ``jax.lax.pmax`` call site is confined the same way — on the
     2D ``(clients, model)`` mesh the model-axis amax all-reduce is part
     of the per-model-shard wire realization (it makes the quantizer
     scales bitwise shard-count-invariant), so like the boundary
     ppermutes it must not grow call sites outside the one executor.

Usage:  python tools/check_single_executor.py [src/repro/core/mixing.py]

Exit status 1 lists every offender as ``path:line: problem``.
"""
from __future__ import annotations

import ast
import sys
from pathlib import Path

ALLOWED_EXEC_FACTORIES = ["_make_sparse_exec"]
ALLOWED_PPERMUTE_SCOPES = {"_make_sparse_exec"}
ALLOWED_PMAX_SCOPES = ALLOWED_PPERMUTE_SCOPES


def _is_call_to(node: ast.Call, name: str) -> bool:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id == name
    if isinstance(f, ast.Attribute):
        return f.attr == name
    return False


def _is_ppermute_call(node: ast.Call) -> bool:
    return _is_call_to(node, "ppermute")


def check_file(path: Path) -> list[str]:
    tree = ast.parse(path.read_text(), filename=str(path))
    problems = []

    execs = [n for n in tree.body
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
             and n.name.endswith("_exec")]
    names = [n.name for n in execs]
    if names != ALLOWED_EXEC_FACTORIES:
        lines = {n.name: n.lineno for n in execs}
        for extra in sorted(set(names) - set(ALLOWED_EXEC_FACTORIES)):
            problems.append(
                f"{path}:{lines[extra]}: second sparse executor "
                f"{extra!r} — fold it into _make_sparse_exec (the block "
                f"realization is the ONE executor)")
        for missing in sorted(set(ALLOWED_EXEC_FACTORIES) - set(names)):
            problems.append(
                f"{path}:1: expected executor factory {missing!r} "
                f"not found")

    # Map every ppermute call site to its enclosing top-level function.
    for top in tree.body:
        if not isinstance(top, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(top):
            if not isinstance(node, ast.Call):
                continue
            if _is_ppermute_call(node) \
                    and top.name not in ALLOWED_PPERMUTE_SCOPES:
                problems.append(
                    f"{path}:{node.lineno}: ppermute call site in "
                    f"{top.name!r} — wire traffic must go through "
                    f"the block realization in _make_sparse_exec")
            if _is_call_to(node, "pmax") \
                    and top.name not in ALLOWED_PMAX_SCOPES:
                problems.append(
                    f"{path}:{node.lineno}: pmax call site in "
                    f"{top.name!r} — the model-axis amax all-reduce "
                    f"(2D mesh scale consistency) belongs to the block "
                    f"realization in _make_sparse_exec")
    return problems


def main(argv: list[str]) -> int:
    target = Path(argv[0]) if argv else \
        Path(__file__).resolve().parent.parent / "src/repro/core/mixing.py"
    problems = check_file(target)
    for p in problems:
        print(p)
    if problems:
        print(f"\n{len(problems)} violation(s)", file=sys.stderr)
        return 1
    print(f"single-executor lint: {target} clean "
          f"(executor = {ALLOWED_EXEC_FACTORIES[0]})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
