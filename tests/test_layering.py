"""Audit: which way the imports point.

The library (``smartbft_tpu/``) is what an embedder installs; the
harnesses beside it (``chipbench/``, ``benchmarks/``, ``chip_smoke.py``)
measure it and may import it, never the other way round.  Inside the
library, ``smartbft_tpu/testing/`` is the in-process cluster the tests and
harnesses build on: product code that imports it is a named debt
(ROADMAP.md, D7), and the list below may only shrink.

Imports are read from the AST, so a name in a comment or a docstring does
not count and a relative import is resolved to its absolute module.
"""

import ast
import functools
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
PKG = ROOT / "smartbft_tpu"

#: product modules that still import ``smartbft_tpu.testing`` (debt D7:
#: two embedder Apps).  Remove an entry when its module stops; add none.
TESTING_IMPORT_DEBTS = {"smartbft_tpu/net/launch.py",
                        "smartbft_tpu/net/cluster.py"}


@functools.cache
def imported_modules(path: pathlib.Path) -> frozenset[str]:
    """Every module ``path`` imports, as absolute dotted names.  ``from a
    import b`` yields both ``a`` and ``a.b`` (``b`` may be a submodule);
    a constant handed to ``import_module`` / ``__import__`` counts too."""
    rel = path.relative_to(ROOT).with_suffix("")
    package = list(rel.parts[:-1])
    found: set[str] = set()
    for node in ast.walk(ast.parse(path.read_text(), filename=str(path))):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            found.add(module)
            found.update(f"{module}.{alias.name}" for alias in node.names)
        elif (isinstance(node, ast.Call) and node.args
              and isinstance(node.args[0], ast.Constant)
              and isinstance(node.args[0].value, str)
              and getattr(node.func, "attr", getattr(node.func, "id", ""))
              in ("import_module", "__import__")):
            found.add(node.args[0].value)
    return frozenset(found)


def importers_of(top: str, files) -> list[str]:
    return sorted(
        str(path.relative_to(ROOT)) for path in files
        if any(m == top or m.startswith(top + ".")
               for m in imported_modules(path))
    )


@pytest.mark.parametrize("harness",
                         ["bench", "benchmarks", "chipbench", "chip_smoke"])
def test_library_never_imports_a_harness(harness):
    offenders = importers_of(harness, sorted(PKG.rglob("*.py")))
    assert not offenders, (
        f"library modules import the harness {harness!r}: {offenders}")


def test_testing_is_imported_only_by_named_debts():
    product = [p for p in sorted(PKG.rglob("*.py"))
               if PKG / "testing" not in p.parents]
    importers = set(importers_of("smartbft_tpu.testing", product))
    assert importers <= TESTING_IMPORT_DEBTS, (
        "product modules outside the D7 list import smartbft_tpu.testing: "
        f"{sorted(importers - TESTING_IMPORT_DEBTS)}")
    assert importers == TESTING_IMPORT_DEBTS, (
        "a D7 debt was paid: take it off TESTING_IMPORT_DEBTS (and "
        f"ROADMAP.md): {sorted(TESTING_IMPORT_DEBTS - importers)}")


def test_benchmarks_package_is_what_the_chip_paths_import():
    """``benchmarks/`` holds what ``chip_smoke.py`` and
    ``chipbench/deployments/sharded.py`` import and nothing else, and
    importing ``benchmarks.throughput`` (it happens inside the measured
    process of three cells) touches neither the environment nor the
    platform JAX will pick."""
    assert sorted(p.name for p in (ROOT / "benchmarks").glob("*.py")) == \
        ["mesh.py", "throughput.py"]
    probe = (
        "import json, os, sys\n"
        "import jax  # sets its own TPU_* / TF_* names when first imported\n"
        "before = dict(os.environ)\n"
        "from benchmarks.throughput import auto_pad_sizes\n"
        "from smartbft_tpu.crypto import ladder\n"
        "print(json.dumps({\n"
        "    'same_env': dict(os.environ) == before,\n"
        "    'is_ladder': auto_pad_sizes is ladder.auto_pad_sizes,\n"
        "    'platforms': jax.config.jax_platforms,\n"
        "    'cache_dir': jax.config.jax_compilation_cache_dir,\n"
        "}))\n"
    )
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
                        "XLA_FLAGS")}
    out = subprocess.run([sys.executable, "-c", probe], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    assert seen == {"same_env": True, "is_ladder": True,
                    "platforms": None, "cache_dir": None}, seen
