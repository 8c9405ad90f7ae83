"""The README names only files that are in the tree.

``README.md`` describes the system as it is, so a file it names exists.
``PERF.md`` and ``ROADMAP.md`` are records: they rightly name files that
went, and are not held to this.
"""

import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parent.parent

#: inline code spans, and the lines of fenced blocks
CODE_SPAN = re.compile(r"`([^`\n]+)`")
#: a word of one that is a path ending in .py / .json / .md, with an
#: optional ``:line`` or ``:name`` after it
NAMED_FILE = re.compile(r"([A-Za-z0-9_./-]+\.(?:py|json|md))(?::[A-Za-z0-9_.,-]+)?")


def named_files(text: str) -> set[str]:
    code, fenced = [], False
    for line in text.splitlines():
        if line.lstrip().startswith("```"):
            fenced = not fenced
        elif fenced:
            code.append(line)
        else:
            code.extend(CODE_SPAN.findall(line))
    found = set()
    for span in code:
        for word in span.split():
            m = NAMED_FILE.fullmatch(word.strip("()[],;'\""))
            if m and not any(c in word for c in "<*") and ".." not in word:
                found.add(m.group(1))
    return found


def tree_file_names() -> set[str]:
    return {p.name for p in ROOT.rglob("*")
            if p.is_file() and not any(part.startswith(".")
                                       for part in p.relative_to(ROOT).parts)}


def test_readme_names_only_files_that_exist():
    names = tree_file_names()
    missing = []
    for token in sorted(named_files((ROOT / "README.md").read_text())):
        if any((base / token).is_file()
               for base in (ROOT, ROOT / "smartbft_tpu", ROOT / "chipbench")):
            continue
        if "/" not in token and token in names:
            continue
        missing.append(token)
    assert not missing, f"README.md names files that are not there: {missing}"
