"""Pass 3 — AST lint rules for the repo's layering invariants.

Enforces, over ``src/repro``, the invariants the changelog states informally
(run as a pytest in ``tests/test_analysis.py`` and as a CI step via
``python -m repro.analysis.lint``):

  * ``ppermute-site``   — ``lax.ppermute`` may appear only in
                          ``core/overlap.py`` (the single generic schedule
                          executor); every other layer goes through plans;
  * ``semaphore-site``  — semaphore / remote-DMA primitives
                          (``semaphore_signal``, ``semaphore_wait``,
                          ``dma_semaphore``, ``make_async_copy``,
                          ``make_async_remote_copy``) may appear only under
                          ``kernels/``, ``backend/`` and the paper-primitive
                          wrappers in ``core/primitives.py``;
  * ``raw-pallas-call`` — no raw ``pl.pallas_call`` outside ``backend/``;
                          kernels must launch through ``backend.pallas_call``
                          so the emulated/Mosaic target switch stays in one
                          place;
  * ``tune-import``     — ``repro.tune`` may be imported (at module level or
                          inside a function) only by ``parallel/context.py``
                          and ``tune/`` itself: the tuner sits above
                          ``core/``, and the context is the one place the
                          model stack chooses a design point.
"""
from __future__ import annotations

import argparse
import ast
import dataclasses
from pathlib import Path
from typing import List, Optional, Sequence

__all__ = ["Violation", "lint_source", "lint_file", "lint_tree", "main"]

_SEM_PRIMITIVES = frozenset(
    {
        "semaphore_signal",
        "semaphore_wait",
        "dma_semaphore",
        "make_async_copy",
        "make_async_remote_copy",
        "get_barrier_semaphore",
    }
)

# rule -> relative paths (or dir prefixes ending in "/") allowed to match
_ALLOWED = {
    "ppermute-site": ("core/overlap.py",),
    "semaphore-site": ("kernels/", "backend/", "core/primitives.py"),
    "raw-pallas-call": ("backend/",),
    "tune-import": ("parallel/context.py", "tune/"),
}


@dataclasses.dataclass(frozen=True)
class Violation:
    path: str  # relative to the repro package root
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _allowed(rule: str, relpath: str) -> bool:
    return any(
        relpath == entry or (entry.endswith("/") and relpath.startswith(entry))
        for entry in _ALLOWED[rule]
    )


def _imports_tune(node: ast.AST) -> bool:
    """Does this import statement bring in ``repro.tune`` or a submodule?"""
    def is_tune(module: str) -> bool:
        return module == "repro.tune" or module.startswith("repro.tune.")

    if isinstance(node, ast.Import):
        return any(is_tune(alias.name) for alias in node.names)
    if isinstance(node, ast.ImportFrom) and node.module and not node.level:
        return is_tune(node.module) or (
            node.module == "repro" and any(a.name == "tune" for a in node.names))
    return False


def lint_source(source: str, relpath: str) -> List[Violation]:
    """Lint one module's source; ``relpath`` is relative to ``src/repro``."""
    violations: List[Violation] = []
    tree = ast.parse(source, filename=relpath)
    for node in ast.walk(tree):
        if _imports_tune(node) and not _allowed("tune-import", relpath):
            violations.append(
                Violation(
                    relpath,
                    node.lineno,
                    "tune-import",
                    "repro.tune imported outside parallel/context.py and "
                    "tune/ — choose the channel in ParallelContext and pass "
                    "compile_overlap an explicit BlockChannel",
                )
            )
        if not isinstance(node, ast.Attribute):
            continue
        base = node.value.id if isinstance(node.value, ast.Name) else None
        if node.attr == "ppermute" and not _allowed("ppermute-site", relpath):
            violations.append(
                Violation(
                    relpath,
                    node.lineno,
                    "ppermute-site",
                    f"{base or '?'}.ppermute outside core/overlap.py — route "
                    "collectives through the plan executor",
                )
            )
        elif node.attr in _SEM_PRIMITIVES and not _allowed("semaphore-site", relpath):
            violations.append(
                Violation(
                    relpath,
                    node.lineno,
                    "semaphore-site",
                    f"{base or '?'}.{node.attr} outside kernels/, backend/ or "
                    "core/primitives.py",
                )
            )
        elif (
            node.attr == "pallas_call"
            and base != "backend"
            and not _allowed("raw-pallas-call", relpath)
        ):
            violations.append(
                Violation(
                    relpath,
                    node.lineno,
                    "raw-pallas-call",
                    f"raw {base or '?'}.pallas_call outside backend/ — use "
                    "backend.pallas_call",
                )
            )
    return violations


def lint_file(path: Path, root: Path) -> List[Violation]:
    relpath = path.relative_to(root).as_posix()
    return lint_source(path.read_text(), relpath)


def lint_tree(root: Optional[Path] = None) -> List[Violation]:
    """Lint every module under ``src/repro`` (the default root)."""
    root = root or Path(__file__).resolve().parents[1]
    violations: List[Violation] = []
    for path in sorted(root.rglob("*.py")):
        violations.extend(lint_file(path, root))
    return violations


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="Layering lint: ppermute/semaphore/pallas_call call-sites "
        "and repro.tune imports.",
    )
    p.add_argument("root", nargs="?", default=None, help="package root (default: src/repro)")
    args = p.parse_args(argv)
    violations = lint_tree(Path(args.root) if args.root else None)
    for v in violations:
        print(v)
    print(f"{len(violations)} violation(s)")
    return 1 if violations else 0


if __name__ == "__main__":
    raise SystemExit(main())
