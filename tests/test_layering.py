"""Package layering of ``src/repro``: which packages each one may import.

``ALLOWED`` is the only copy of the rule.  Imports are read with ``ast``,
lazy function-level ones included; nothing is imported, so the check is
fast and has no side effects.  ``KNOWN_EXCEPTIONS`` lists the arrows that
break the table today (ROADMAP.md, Design); an exception that no longer
exists fails its own case, so the list can only shrink.
"""
import ast
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"

_BASE = ("obs", "data", "checkpoint", "optim", "compile_cache")
ALLOWED = {
    **{p: set() for p in _BASE},
    "kernels": set(),
    "perception": {"data"},
    "core": {"data", "kernels", "obs", "perception"},
    "index": {"core", "kernels", "obs"},
    "server": {"core", "index", "kernels", "obs"},
    "serving": {"core", "index", "kernels", "obs"},
}
ALLOWED["sim"] = set(ALLOWED)

# (module or package under src/repro, package it imports against the table)
KNOWN_EXCEPTIONS = [
    ("kernels/ref.py", "core"),   # the lift_compact oracle uses core.geometry
    ("core", "index"),            # the cluster plan dispatches via index.search
]


def _package(path: Path) -> str:
    rel = path.relative_to(SRC)
    return rel.parts[0] if len(rel.parts) > 1 else rel.stem


def _imported_packages(path: Path) -> set:
    """Top-level ``repro`` packages that one module imports."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{path}: relative import"
            if node.module == "repro":
                names = [f"repro.{a.name}" for a in node.names]
            else:
                names = [node.module]
        else:
            continue
        out |= {n.split(".")[1] for n in names if n.startswith("repro.")}
    return out


def _modules(prefix: str = ""):
    base = SRC / prefix
    return [base] if base.suffix == ".py" else sorted(base.rglob("*.py"))


def _excused(path: Path, target: str) -> bool:
    rel = path.relative_to(SRC).as_posix()
    return any(t == target and (rel == p or rel.startswith(p + "/"))
               for p, t in KNOWN_EXCEPTIONS)


PACKAGES = sorted({_package(p) for p in _modules()})


@pytest.mark.parametrize("pkg", PACKAGES)
def test_package_imports_follow_the_table(pkg):
    assert pkg in ALLOWED, f"{pkg}: add it to ALLOWED"
    bad = []
    for path in _modules():
        if _package(path) != pkg:
            continue
        for target in sorted(_imported_packages(path) - {pkg}):
            if target not in ALLOWED[pkg] and not _excused(path, target):
                bad.append(f"{path.relative_to(SRC)} -> {target}")
    assert not bad, f"{pkg} may import only {sorted(ALLOWED[pkg])}: {bad}"


def test_known_exceptions_still_exist():
    for prefix, target in KNOWN_EXCEPTIONS:
        assert any(target in _imported_packages(p) for p in _modules(prefix)), \
            f"{prefix} no longer imports {target}: drop the exception"
