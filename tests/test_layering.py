"""The layers under the step import one way (ARCHITECTURE.md opens with the
drawing): ``ops/`` knows no mesh and no model, ``parallel/`` no model, and
``models/`` neither the engine nor a trainer.  Read from the source text with
``ast``: every ``import`` / ``from`` of ``ray_tpu.<package>`` at any depth,
function-level imports too.  Needs no JAX.
"""

from __future__ import annotations

import ast
import functools
import os

import pytest

ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "ray_tpu")

# One case an edge that must not exist: (the importing package, the imported).
FORBIDDEN = [
    ("ops", "parallel"), ("ops", "models"), ("ops", "serve"), ("ops", "train"),
    ("parallel", "models"), ("parallel", "serve"), ("parallel", "train"),
    ("models", "serve"), ("models", "train"),
]


def _imported(node, package_parts):
    """The dotted ``ray_tpu...`` names an import statement reaches, a
    relative import resolved against the importing file's package."""
    if isinstance(node, ast.Import):
        return [alias.name for alias in node.names]
    base = node.module or ""
    if node.level:
        up = package_parts[:len(package_parts) - (node.level - 1)]
        base = ".".join(up + ([base] if base else []))
    # ``from ray_tpu import ops`` names a package by the alias
    return [base] + [f"{base}.{alias.name}" for alias in node.names]


@functools.cache
def _edges():
    """{(package, imported package): ["file:line", ...]} over ``ray_tpu/``."""
    found = {}
    for folder, _, files in os.walk(ROOT):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            rel = os.path.relpath(path, os.path.dirname(ROOT))
            parts = rel[:-len(".py")].split(os.sep)
            package_parts = parts[:-1]
            if len(parts) < 3:
                continue                 # a module of ray_tpu/ itself
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if not isinstance(node, (ast.Import, ast.ImportFrom)):
                    continue
                reached = {target.split(".")[1]
                           for target in _imported(node, package_parts)
                           if target.startswith("ray_tpu.")}
                for package in reached:
                    found.setdefault((parts[1], package), []).append(
                        f"{rel}:{node.lineno}")
    return found


@pytest.mark.parametrize("package,imported", FORBIDDEN,
                         ids=[f"{a}->{b}" for a, b in FORBIDDEN])
def test_a_lower_layer_imports_no_higher_one(package, imported):
    assert os.path.isdir(os.path.join(ROOT, package))
    assert os.path.isdir(os.path.join(ROOT, imported))
    assert _edges().get((package, imported), []) == []
    # the scan sees what it is asked about: the stack's downward edges exist
    assert ("models", "ops") in _edges() and ("models", "parallel") in _edges()
