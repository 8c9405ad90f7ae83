"""Audit: the environment names the tree reads.

An environment name is an option nobody lists: it is set outside the
program, it reaches every process, and no configuration file shows it.
The tree reads seven, each in one module, each in the README's table.
A new one fails here first; the way to add a setting is a
``Configuration`` field or an argument (ROADMAP.md, queue 3: the middle
four are debt D1 and leave with it).
"""

import ast
import functools
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent

KNOBS = (
    "SMARTBFT_PALLAS",
    "SMARTBFT_PALLAS_WINDOW",
    "SMARTBFT_PALLAS_CHAIN",
    "SMARTBFT_BN_CHAIN",
    "SMARTBFT_BN_UNROLL",
    "SMARTBFT_NO_NATIVE",
    "SMARTBFT_DETERMINISTIC_SIGN",
)


@functools.cache
def sources() -> tuple[pathlib.Path, ...]:
    """Every ``*.py`` of the tree outside ``tests/`` directories, hidden
    directories and what a chip run leaves behind."""
    out = []
    for path in sorted(ROOT.rglob("*.py")):
        parts = path.relative_to(ROOT).parts[:-1]
        if any(p == "tests" or p == "chiprun_out" or p.startswith(".")
               for p in parts):
            continue
        out.append(path)
    return tuple(out)


@functools.cache
def environment_reads(path: pathlib.Path) -> frozenset[str]:
    """The string constants ``path`` looks up in the process environment:
    ``<x>.environ.get(N)`` / ``.pop`` / ``.setdefault``, ``<x>.environ[N]``,
    ``N in <x>.environ``, ``<x>.getenv(N)``."""
    tree = ast.parse(path.read_text(), filename=str(path))
    reads: set[str] = set()

    def const(node):
        return node.value if isinstance(node, ast.Constant) \
            and isinstance(node.value, str) else None

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args:
            where = ast.unparse(node.func)
            if re.search(r"\benviron\.(get|pop|setdefault)$|\bgetenv$", where):
                reads.add(const(node.args[0]))
        elif isinstance(node, ast.Subscript):
            if ast.unparse(node.value).endswith("environ"):
                reads.add(const(node.slice))
        elif isinstance(node, ast.Compare) and len(node.comparators) == 1 \
                and ast.unparse(node.comparators[0]).endswith("environ"):
            reads.add(const(node.left))
    reads.discard(None)
    return frozenset(reads)


def test_no_undeclared_environment_knob():
    named = set()
    for path in sources():
        named.update(re.findall(r"SMARTBFT_[A-Z0-9_]+", path.read_text()))
    assert named == set(KNOBS), (
        f"undeclared: {sorted(named - set(KNOBS))}; "
        f"declared and gone: {sorted(set(KNOBS) - named)}")


@pytest.mark.parametrize("name", KNOBS)
def test_knob_is_read_in_one_place_and_documented(name):
    readers = [str(p.relative_to(ROOT)) for p in sources()
               if name in environment_reads(p)]
    assert len(readers) == 1, f"{name} is read by {readers}"
    readme = (ROOT / "README.md").read_text()
    rows = [line for line in readme.splitlines()
            if line.startswith("|") and f"`{name}`" in line.split("|")[1]]
    assert len(rows) == 1, (
        f"{name} needs one row in README.md's table of environment names, "
        f"found {len(rows)}")
    assert readers[0] in rows[0], (
        f"README.md's row for {name} does not name its reader {readers[0]}")
