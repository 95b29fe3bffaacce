"""The documents a user is sent to name only what the tree holds.

One case per document: every repository path it names exists, every
``make <target>`` it names is a target of the Makefile, and every
``DSLABS_*`` variable it names is read by some ``*.py`` of the tree.
The records of the past (CHANGES.md, ROADMAP.md, PERF.md, SURVEY.md,
PAPER*.md, SNIPPETS.md) are not cases: they say what WAS.  Where a case
fails, the document is stale — repair it, not the lists below.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md", "Makefile", ".claude/skills/verify/SKILL.md"]
             + sorted("docs/" + f for f in os.listdir(
                 os.path.join(ROOT, "docs")) if f.endswith(".md")))

# A path: word characters, dots, dashes and slashes up to one of the
# suffixes a repository file of interest has.  Templates (`<run-dir>/…`,
# `*.flight.jsonl`, `$(PY)`), absolute paths and URLs name no file of
# the repository.
_PATH = re.compile(r"(?<![\w./<>*${}~:-])([\w.-]+(?:/[\w.-]+)*"
                   r"\.(?:py|jsonl|json|md))(?![\w/*])")
_MAKE = re.compile(r"`make ([a-z][a-z0-9-]*)")
_ENV = re.compile(r"DSLABS_[A-Z0-9_]+")
_SKIP_DIRS = {"__pycache__", "chiprun_out"}


def _tree():
    """Every file below the root, dot-directories (.git, caches, an
    unpacked parent) left out — the checkout may not be a git one."""
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d not in _SKIP_DIRS]
        for f in files:
            yield os.path.join(base, f)


@pytest.fixture(scope="module")
def tree():
    files = list(_tree())
    source = {}
    for path in files:
        if path.endswith(".py") and path != os.path.abspath(__file__):
            with open(path, encoding="utf-8") as f:
                source[path] = f.read()
    with open(os.path.join(ROOT, "Makefile"), encoding="utf-8") as f:
        targets = set()
        for m in re.finditer(r"^([a-z][a-z0-9 -]*):", f.read(), re.M):
            targets.update(m.group(1).split())
    return {
        "basenames": {os.path.basename(p) for p in files},
        "env": set(_ENV.findall("\n".join(source.values()))),
        # What the program itself writes at run time (flight.jsonl,
        # STATUS.json, …): a bare *.json / *.jsonl name is one of those
        # when the package's own source spells it.
        "written": "\n".join(
            s for p, s in source.items()
            if p.startswith(os.path.join(ROOT, "dslabs_tpu"))),
        "targets": targets,
    }


def _missing_paths(doc: str, text: str, tree: dict) -> list:
    bases = (ROOT, os.path.join(ROOT, "dslabs_tpu"),
             os.path.dirname(os.path.join(ROOT, doc)))
    missing = []
    for name in sorted(set(_PATH.findall(text))):
        if any(os.path.exists(os.path.join(b, name)) for b in bases):
            continue
        if "/" not in name and (
                name in tree["basenames"]
                or (name.endswith((".json", ".jsonl"))
                    and name in tree["written"])):
            continue
        missing.append(name)
    return missing


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_what_the_tree_holds(doc, tree):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    stale = {
        "paths": _missing_paths(doc, text, tree),
        "make targets": sorted(
            set(_MAKE.findall(text)) - tree["targets"]),
        # `DSLABS_SPILL_*` names a family: some member must be read.
        "environment variables": sorted(
            n for n in set(_ENV.findall(text)) - tree["env"]
            if not (n.endswith("_")
                    and any(e.startswith(n) for e in tree["env"]))),
    }
    assert not any(stale.values()), f"{doc} names what the tree lacks: " \
        + "; ".join(f"{k}: {v}" for k, v in stale.items() if v)
