"""The arrows of the module map, read from the sources.

One case an edge that must not exist: an import of one part of
``torchft_tpu`` by another, wherever in the file it stands (a lazy import
inside a function is an import).  The program does not depend on its tools
(``analysis``), the lower layers do not reach into a tier above them
(``serving``), and nothing imports a benchmark.
"""

import ast
import functools
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "torchft_tpu")

PARTS = sorted(
    name[:-3] if name.endswith(".py") else name
    for name in os.listdir(PKG)
    if name not in ("__init__.py", "__pycache__")
)

# (who, what it may not import): a part is ``name`` (torchft_tpu/name.py or
# torchft_tpu/name/), "*" is every file of the package; a target is a
# dotted prefix.  ``utils`` imports nothing of the package but ``_native``.
FORBIDDEN = [
    ("utils", f"torchft_tpu.{part}")
    for part in PARTS
    if part not in ("utils", "_native")
] + [
    ("coordination", "torchft_tpu.serving"),
    ("checkpointing", "torchft_tpu.serving"),
    ("manager", "torchft_tpu.analysis"),
    ("parallel", "torchft_tpu.analysis"),
    ("coordination", "torchft_tpu.analysis"),
    ("ops", "torchft_tpu.manager"),
    ("ops", "torchft_tpu.checkpointing"),
    ("ops", "torchft_tpu.serving"),
    ("*", "bench"),
    ("*", "benchmarks"),
]

# The one exception, by file: the TORCHFT_PLAN_VERIFY hook, which validates
# a live plan (``plan_ir`` adapts it, ``plan_verify`` judges it) where it is
# committed and which only tests/conftest.py arms.  ROADMAP D9.
PLAN_VERIFY_HOOK = {
    "torchft_tpu/ops/collectives.py",
    "torchft_tpu/checkpointing/http_transport.py",
    "torchft_tpu/serving/replica.py",
}


def _files(part):
    if part == "*":
        top = PKG
    else:
        top = os.path.join(PKG, part)
        if not os.path.isdir(top):
            return [top + ".py"]
    return sorted(
        os.path.join(d, f)
        for d, _dirs, names in os.walk(top)
        for f in names
        if f.endswith(".py")
    )


@functools.lru_cache(maxsize=None)
def _imports(path):
    """Every module a file imports, as (dotted name, line): ``from a.b
    import c`` gives both ``a.b`` and ``a.b.c``, since ``c`` may be a
    module; a relative import is resolved against the file's package."""
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read(), path)
    package = os.path.relpath(os.path.dirname(path), ROOT).split(os.sep)
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found += [(alias.name, node.lineno) for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package[: len(package) - (node.level - 1)]
                base = ".".join(up + ([base] if base else []))
            found.append((base, node.lineno))
            found += [(f"{base}.{alias.name}", node.lineno) for alias in node.names]
    return tuple(found)


def _under(name, prefix):
    return name == prefix or name.startswith(prefix + ".")


def test_the_parts_named_exist():
    for part, _target in FORBIDDEN:
        assert all(os.path.isfile(f) for f in _files(part)) and _files(part), part


@pytest.mark.parametrize("part,target", FORBIDDEN, ids=lambda v: v.replace("torchft_tpu.", ""))
def test_edge_does_not_exist(part, target):
    found = [
        f"{os.path.relpath(path, ROOT)}:{line} imports {name}"
        for path in _files(part)
        for name, line in _imports(path)
        if _under(name, target)
    ]
    assert not found, found


def test_the_plan_verify_hook_is_the_only_way_up_into_analysis():
    """Outside ``analysis`` itself the package imports it in three files,
    and there only the hook's two modules (ROADMAP D9)."""
    hook = {"torchft_tpu.analysis.plan_ir", "torchft_tpu.analysis.plan_verify"}
    found = {}
    for path in _files("*"):
        rel = os.path.relpath(path, ROOT).replace(os.sep, "/")
        if rel.startswith("torchft_tpu/analysis/"):
            continue
        for name, _line in _imports(path):
            if _under(name, "torchft_tpu.analysis") and name != "torchft_tpu.analysis":
                found.setdefault(rel, set()).add(name)
    assert set(found) == PLAN_VERIFY_HOOK, found
    assert all(names == hook for names in found.values()), found
