"""The account names what exists: the README, each guide under ``docs/``,
the Makefile, the CI file and the verify skill may name a module to run
(``python -m tpu_ddp.<module>``), a ``make`` target and a path of this
repository only where the tree holds it. Looked up as paths and text:
nothing of the program is imported. A reader's own files (``winner.json``,
``/tmp/run_dir``) and what ``.gitignore`` lists as made at run time are not
its business."""

import fnmatch
import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACKED_DIRS = ("tpu_ddp", "tests", "chipbench", "benchmarks", "docs")

ACCOUNTS = (
    ["README.md"]
    + sorted(os.path.relpath(p, REPO)
             for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
    + ["Makefile", ".github/workflows/ci.yml",
       ".claude/skills/verify/SKILL.md"])

_MODULE = re.compile(r"-m\s+(tpu_ddp(?:\.\w+)+)")
# a target in backticks, or a line that is the command and nothing else
_MAKE = re.compile(
    r"`make\s+([a-z][\w-]*)"
    r"|^\s*(?:run:\s*|\$\s*)?make\s+([a-z][\w-]*)\s*(?:#.*)?$", re.M)
_PATH = re.compile(
    r"(?<![\w/.-])((?:%s)/[\w./-]*)" % "|".join(TRACKED_DIRS))
_TOP_PY = re.compile(r"(?<![\w/.-])([A-Za-z_]\w*\.py)\b")


@functools.lru_cache(maxsize=None)
def _read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def _make_targets():
    return frozenset(
        re.findall(r"^([A-Za-z][\w-]*):", _read("Makefile"), re.M))


def _made_at_run_time(path):
    """Whether ``.gitignore`` lists ``path``: a build or a cache, there
    only after the program ran."""
    parts = path.split("/")
    for line in _read(".gitignore").splitlines():
        entry = line.strip().rstrip("/")
        if not entry or entry.startswith("#"):
            continue
        if "/" in entry:
            if path == entry or path.startswith(entry + "/"):
                return True
        elif any(fnmatch.fnmatch(part, entry) for part in parts):
            return True
    return False


@functools.lru_cache(maxsize=None)
def _basenames():
    """Every ``*.py`` file name at the root and under the tracked
    directories."""
    names = {f for f in os.listdir(REPO) if f.endswith(".py")}
    for top in TRACKED_DIRS:
        for _, _, files in os.walk(os.path.join(REPO, top)):
            names.update(f for f in files if f.endswith(".py"))
    return frozenset(names)


def missing(text):
    """What ``text`` names that the tree does not hold, as sorted
    ``kind: name`` strings."""
    out = set()
    for module in _MODULE.findall(text):
        stem = os.path.join(REPO, *module.split("."))
        if not (os.path.isfile(stem + ".py")
                or os.path.isfile(os.path.join(stem, "__main__.py"))):
            out.add(f"module: {module}")
    for quoted, alone in _MAKE.findall(text):
        if (quoted or alone) not in _make_targets():
            out.add(f"make target: {quoted or alone}")
    for m in _PATH.finditer(text):
        path = m.group(1)
        if text[m.end():m.end() + 1] in ("*", "<", "{", "[", "$"):
            path = os.path.dirname(path)  # a pattern: its directory
        path = path.rstrip("./")
        if not (os.path.exists(os.path.join(REPO, path))
                or _made_at_run_time(path)):
            out.add(f"path: {path}")
    for name in _TOP_PY.findall(text):
        # a bare name may be a module its guide has already placed
        # (``store.py``): it is missing when no file anywhere has it
        if name not in _basenames():
            out.add(f"file: {name}")
    return sorted(out)


@pytest.mark.parametrize("account", ACCOUNTS)
def test_account_names_what_exists(account):
    assert missing(_read(account)) == []
