"""kernel-contract: packaging rules for ``src/repro/kernels/<name>/``.

Every kernel package must

1. ship the three-file layout — ``ops.py`` (public jitted wrappers),
   ``kernel.py`` (the Pallas kernel), ``ref.py`` (the jnp oracle);
2. resolve its interpret default through the shared helper
   (``from repro.kernels.common import default_interpret/resolve_interpret``)
   rather than a private copy — one backend-derived interpret selection
   for the whole repo;
3. be exercised by at least one test under ``tests/`` that imports its
   ``reference_*`` oracle (or the ``ref`` module) — the kernel-vs-oracle
   comparison is the repo's correctness contract for compiled TPU runs.

This is a *project* pass: it inspects the tree under the repo root
directly, so it fires even when only a subset of files is linted.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import List, Optional, Sequence, Set

from repro.tools.lint.core import (FileContext, LintPass, Violation,
                                   SKIP_DIRS)

_COMMON = "repro.kernels.common"
_COMMON_NAMES = {"default_interpret", "resolve_interpret", "pallas_mode"}
_REQUIRED_FILES = ("ops.py", "kernel.py", "ref.py")


class KernelContractPass(LintPass):
    name = "kernel-contract"
    description = ("kernels/<name>/ must ship ops/kernel/ref, use the "
                   "shared interpret helper, and have an oracle-backed test")

    def __init__(self, kernels_rel: str = "src/repro/kernels",
                 tests_rel: str = "tests") -> None:
        self.kernels_rel = kernels_rel
        self.tests_rel = tests_rel

    def _oracle_packages(self, tests_dir: Path) -> Set[str]:
        """Kernel package names whose ref oracle some test imports."""
        found: Set[str] = set()
        if not tests_dir.is_dir():
            return found
        for f in tests_dir.rglob("*.py"):
            if any(part in SKIP_DIRS
                   for part in f.relative_to(tests_dir).parts):
                continue
            try:
                tree = ast.parse(f.read_text(), filename=str(f))
            except SyntaxError:
                continue
            for node in ast.walk(tree):
                if isinstance(node, ast.ImportFrom) and node.module and \
                        node.module.startswith("repro.kernels."):
                    parts = node.module.split(".")
                    pkg = parts[2]
                    if len(parts) > 3 and parts[3] == "ref":
                        found.add(pkg)
                        continue
                    for a in node.names:
                        if a.name == "ref" or \
                                a.name.startswith("reference"):
                            found.add(pkg)
                elif isinstance(node, ast.Import):
                    for a in node.names:
                        parts = a.name.split(".")
                        if len(parts) >= 4 and parts[:2] == \
                                ["repro", "kernels"] and parts[3] == "ref":
                            found.add(parts[2])
        return found

    def check_project(self, contexts: Sequence[FileContext],
                      root: Optional[Path]) -> List[Violation]:
        if root is None:
            return []
        kernels_dir = root / self.kernels_rel
        if not kernels_dir.is_dir():
            return []
        oracled = self._oracle_packages(root / self.tests_rel)
        out: List[Violation] = []
        for pkg in sorted(kernels_dir.iterdir()):
            if not pkg.is_dir() or not (pkg / "__init__.py").exists():
                continue
            anchor = str(pkg / "__init__.py")
            missing = [f for f in _REQUIRED_FILES if not (pkg / f).exists()]
            if missing:
                out.append(Violation(
                    path=anchor, line=1, col=0, pass_name=self.name,
                    message=(f"kernel package '{pkg.name}' is missing "
                             f"{', '.join(missing)}; the contract is "
                             f"ops.py (jitted wrappers) + kernel.py "
                             f"(Pallas) + ref.py (jnp oracle)")))
            ops = pkg / "ops.py"
            if ops.exists():
                out.extend(self._check_ops(ops))
            if pkg.name not in oracled:
                out.append(Violation(
                    path=str(ops if ops.exists() else pkg / "__init__.py"),
                    line=1, col=0, pass_name=self.name,
                    message=(f"no test under {self.tests_rel}/ imports "
                             f"'{pkg.name}'s ref oracle (a reference_* "
                             f"name or the ref module); every kernel "
                             f"needs a kernel-vs-oracle test")))
        return out

    def _check_ops(self, ops: Path) -> List[Violation]:
        try:
            tree = ast.parse(ops.read_text(), filename=str(ops))
        except SyntaxError as e:
            return [Violation(path=str(ops), line=e.lineno or 1, col=0,
                              pass_name=self.name,
                              message=f"ops.py does not parse: {e.msg}")]
        out: List[Violation] = []
        imports_common = False
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module == _COMMON \
                    and any(a.name in _COMMON_NAMES for a in node.names):
                imports_common = True
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in _COMMON_NAMES:
                out.append(Violation(
                    path=str(ops), line=node.lineno, col=node.col_offset,
                    pass_name=self.name,
                    message=(f"ops.py defines a private '{node.name}'; "
                             f"use the shared copy in {_COMMON} so the "
                             f"interpret selection stays backend-derived "
                             f"in one place")))
        if not imports_common:
            out.append(Violation(
                path=str(ops), line=1, col=0, pass_name=self.name,
                message=(f"ops.py does not import "
                         f"default_interpret/resolve_interpret from "
                         f"{_COMMON}; interpret defaults must be "
                         f"backend-selected through the shared helper")))
        return out
