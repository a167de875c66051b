"""Every file a document names is in the tree.

A document rots one path at a time: a script is deleted, a test file is
split, and the README still tells the reader to run it.  For README.md,
the docs/ pages, the CI workflow and the verify skill, this checks two
kinds of reference:

- a path that starts with one of the code directories and ends in a file
  extension (``tools/runcap.py``, ``geomx_tpu/train/step.py:552``);
- a script a command line runs from the root (``python chip_smoke.py``).

Bare file names, globs, name prefixes (``tests/test_tpu_compile_``) and
the reference implementation's own tree (``src/...``, ``3rdparty/...``,
``docs/source/...``) are outside both patterns.  What a build leaves
behind (``native/libgeops.so``) counts as present when ``.gitignore``
lists it: the document names a file the tree makes, not one it lost.
"""

import fnmatch
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CODE_DIRS = ("geomx_tpu", "tools", "tests", "benchmark", "examples",
             "scripts", "native", "docker")
DOCUMENTS = (["README.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md")))
             + [".github/workflows/tier1.yml",
                ".claude/skills/verify/SKILL.md"])

_PATH = re.compile(r"(?<![\w/.-])(?:%s)/[\w./-]*\.[A-Za-z0-9]+\b"
                   % "|".join(CODE_DIRS))
_ROOT_SCRIPT = re.compile(r"\bpython3?\s+([\w-]+\.py)\b")


def _ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        return [ln.strip().lstrip("/") for ln in f
                if ln.strip() and not ln.startswith("#")]


def _present(path, ignored):
    return (os.path.exists(os.path.join(REPO, path))
            or any(fnmatch.fnmatch(path, pat)
                   or fnmatch.fnmatch(os.path.basename(path), pat)
                   for pat in ignored))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_named_file_exists(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    named = set(_PATH.findall(text)) | set(_ROOT_SCRIPT.findall(text))
    ignored = _ignored()
    missing = sorted(p for p in named if not _present(p, ignored))
    assert not missing, f"{document} names files not in the tree: {missing}"
