#
# The repo's account of itself stays true: a document names no file that is
# not there, and every SRML_* name the package reads is documented.  The
# count of those names is a ratchet: a `simplicity` PR lowers it, nothing
# raises it.
#

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = sorted(
    ["README.md", ".claude/skills/verify/SKILL.md"]
    + [os.path.relpath(p, ROOT) for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))]
)
# what a backticked token must look like to be read as a path of this repo:
# `a/b.py`, `X.md`, `X.json`, `ci/test.sh`, with an optional `:line` behind
_PATH = re.compile(r"^([A-Za-z0-9_.-]+/)*[A-Za-z0-9_.-]+\.(py|md|json|jsonl|sh|toml)$")
# where a document's relative path may start
_BASES = ("", "spark_rapids_ml_tpu", "docs", "tests", "chipbench")
MAX_SRML_NAMES = 54


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def _basenames():
    out = set()
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in (".git", "build", "chiprun_out", "__pycache__")]
        out.update(files)
    return out


def _named_paths(text):
    for token in re.findall(r"`([^`\n]+)`", text):
        token = re.sub(r":[0-9][0-9,:-]*$", "", token.strip())
        if _PATH.match(token):
            yield token


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_that_exist(doc):
    basenames = _basenames()
    missing = []
    for token in sorted(set(_named_paths(_read(doc)))):
        if "/" in token:
            found = any(os.path.exists(os.path.join(ROOT, b, token)) for b in _BASES)
        else:
            found = token in basenames
        if not found:
            missing.append(token)
    assert not missing, f"{doc} names files that do not exist: {missing}"


def _srml_names():
    names = set()
    for path in glob.glob(os.path.join(ROOT, "spark_rapids_ml_tpu", "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            names.update(re.findall(r"SRML_[A-Z0-9_]+", f.read()))
    return names


def test_every_setting_the_package_reads_is_documented():
    documented = "\n".join(_read(d) for d in DOCS if d != ".claude/skills/verify/SKILL.md")
    # a name that ends in "_" is a family written with a star in a comment
    undocumented = sorted(
        n for n in _srml_names()
        if not n.endswith("_") and not re.search(rf"\b{n}\b", documented)
    )
    assert not undocumented, f"no README or docs/*.md names: {undocumented}"


def test_settings_count_does_not_rise():
    names = _srml_names()
    assert len(names) <= MAX_SRML_NAMES, (len(names), sorted(names))
