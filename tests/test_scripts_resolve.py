"""Every name a script imports from this repo exists.

Static: each file is parsed, never run. A ``from eventstreamgpt_tpu... import
name`` or ``from benchmark... import name``, at module level or inside a
function, must name a module that imports and an attribute it has (or a
submodule): what a PR that deletes modules needs to check of itself, for the
scripts no other test drives.
"""

import ast
import importlib
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
FILES = [
    *sorted(p for p in (REPO / "scripts").glob("*.py") if p.name != "__init__.py"),
    REPO / "chip_smoke.py",
    REPO / "__graft_entry__.py",
]
OURS = ("eventstreamgpt_tpu", "benchmark")


def _has(module, name: str) -> bool:
    if hasattr(module, name):
        return True
    try:
        importlib.import_module(f"{module.__name__}.{name}")
    except ImportError:
        return False
    return True


@pytest.mark.parametrize("path", FILES, ids=lambda p: p.name)
def test_imports_resolve(path):
    missing = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.ImportFrom) and node.level == 0 and node.module.split(".")[0] in OURS:
            module = importlib.import_module(node.module)
            missing += [
                f"{path.name}:{node.lineno}: {node.module} has no {alias.name}"
                for alias in node.names
                if alias.name != "*" and not _has(module, alias.name)
            ]
    assert not missing, "\n".join(missing)
