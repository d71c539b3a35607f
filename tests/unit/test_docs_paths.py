"""Every file a guide names exists.

A guide that sends its reader to a tool that was deleted describes a
system that is gone.  For each tracked guide: every back-ticked
repo-relative path with a file suffix, and every ``python <path>``
command, names a file in the tree.
"""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GUIDES = ["README.md", ".claude/skills/verify/SKILL.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "docs", "tutorials", "*.md")))

SUFFIXES = ("py", "md", "json", "jsonl", "sh", "txt", "cc", "cpp", "h",
            "toml", "cfg")
# `a/b.py`, `a/b.py:12`, `a/b.py::test_x` — one token between back-ticks
_TICKED = re.compile(r"`([A-Za-z0-9_.\-/]+\.(?:%s))(?:::?[^`\s]*)?`"
                     % "|".join(SUFFIXES))
_PYTHON = re.compile(r"\bpython3?\s+(?:-u\s+)?([A-Za-z0-9_.\-/]+\.py)\b")
# where a guide may root a path it abbreviates
_ROOTS = ("", "deepspeed_tpu", "deepspeed_tpu/runtime", "docs/tutorials")


def _exists(path):
    return any(os.path.exists(os.path.join(REPO, root, path))
               for root in _ROOTS)


def _named_paths(text):
    """The paths a guide names.  A bare file name in back-ticks
    (`ds_config.json`) is the reader's own file, not the repo's: only
    paths with a directory, or names at the repo's root in capitals
    (`PERF.md`), are held to exist.  Commands are always held."""
    for m in _PYTHON.finditer(text):
        yield m.group(1)
    for m in _TICKED.finditer(text):
        path = m.group(1)
        if path.startswith(("/", "~")):
            continue
        if "/" in path or path.split(".")[0].isupper():
            yield path


def missing_paths(text, written_by_reader=()):
    return sorted({path for path in _named_paths(text)
                   if path not in written_by_reader and not _exists(path)})


# scripts and files a guide tells its reader to write, by guide
_READERS_OWN = {
    ".claude/skills/verify/SKILL.md": ("user_flow.py",),
    "docs/tutorials/static_analysis.md": (
        "tools/graftlint/rules/my_rule.py",),
}


@pytest.mark.parametrize("guide", GUIDES)
def test_guide_names_only_files_that_exist(guide):
    assert len(GUIDES) == 12, GUIDES
    with open(os.path.join(REPO, guide)) as f:
        text = f.read()
    gone = missing_paths(text, _READERS_OWN.get(guide, ()))
    assert not gone, f"{guide} names files that do not exist: {gone}"
