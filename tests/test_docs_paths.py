"""Every repo path a document names exists.

One case a document (``README.md`` and each ``doc/*.md``), so a failure
names the document. A path is a word of a backticked span, or a
markdown link target, whose first segment is a tracked top-level
directory, or a bare file name by extension (``.py`` / ``.json`` /
``.md``: a root file, a sibling of the document, or the base name of a
file somewhere in the tree, as in "``quota.py``" under a heading that
names its package). ``:line`` suffixes, anchors and punctuation are
stripped; a glob must match something; ``file.py::test_name`` must name
a function of that file.
"""

import fnmatch
import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOP_DIRS = ("cxxnet_tpu", "tests", "tools", "benchmarks", "doc",
            "example", "wrapper", "src")
ROOT_FILE = re.compile(r"^[A-Za-z_][\w.\-*]*\.(py|json|md)$")
DOCS = ["README.md"] + sorted(
    os.path.join("doc", f) for f in os.listdir(os.path.join(ROOT, "doc"))
    if f.endswith(".md"))

_SPAN = re.compile(r"`([^`\n]+)`|\]\(([^)\s]+)\)")


def named_paths(text):
    """Repo-relative paths named in ``text``, as written but for the
    suffixes a reader ignores. A backticked command is read word by
    word, so ``python tools/x.py --flag`` names ``tools/x.py``."""
    out = []
    for tick, link in _SPAN.findall(text):
        if link and "://" in link:
            continue
        for word in (tick or link).split():
            word, _, func = word.split("#")[0].partition("::")
            word = re.sub(r"(:\d+(-\d+)?(,\d+(-\d+)?)*)+$", "", word)
            word = word.strip("\"'.,;:()")
            if not word or re.search(r"[<>=|${}\[\]]", word):
                continue
            if "/" in word:
                if word.split("/")[0] in TOP_DIRS:
                    out.append((word, func))
            elif ROOT_FILE.match(word):
                out.append((word, func))
    return out


@functools.lru_cache(maxsize=None)
def _base_names():
    names = set(os.listdir(ROOT))
    for top in TOP_DIRS:
        for _, _, files in os.walk(os.path.join(ROOT, top)):
            names.update(files)
    return names


def missing_paths(doc):
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    base = os.path.dirname(doc)
    missing = []
    for p, func in named_paths(text):
        # from the root, or relative to the document (a link)
        found = [f for q in (os.path.join(ROOT, p),
                             os.path.join(ROOT, base, p))
                 for f in glob.glob(q)]
        if not found and "/" not in p:
            found = fnmatch.filter(_base_names(), p)
            func = ""
        if not found:
            missing.append(p)
        elif func:
            with open(found[0], encoding="utf-8") as f:
                if not re.search(r"def %s\b" % re.escape(
                        func.split("[")[0]), f.read()):
                    missing.append("%s::%s" % (p, func))
    return sorted(set(missing))


def test_named_paths_reads_what_a_reader_reads():
    text = ("see `cxxnet_tpu/nnet/trainer.py:886-890`, `gone.py`, "
            "[the guide](doc/io.md#keys), `tests/test_fleet*.py`, "
            "`eta = 0.1`, `0010.model.npz`, [x](https://a.b/c.md), "
            "`tests/test_io.py::test_a` and `python tools/x.py --flag`")
    assert named_paths(text) == [
        ("cxxnet_tpu/nnet/trainer.py", ""), ("gone.py", ""),
        ("doc/io.md", ""), ("tests/test_fleet*.py", ""),
        ("tests/test_io.py", "test_a"), ("tools/x.py", "")]


@pytest.mark.parametrize("doc", DOCS)
def test_every_named_path_exists(doc):
    assert missing_paths(doc) == []
