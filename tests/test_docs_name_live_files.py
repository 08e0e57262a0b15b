"""The documents that send a reader to a file name only files that
exist. Every name ending in .py, .sh, .toml, .json or .md written in
backticks or in a fenced block in README.md, ARCHITECTURE.md,
PARITY.md and the verify skill is a file of this tree (by full path,
by path suffix or by base name) or one of the reference's own sources.
CHANGES.md, ROADMAP.md and PERF.md are history, queues and the
builders' record, and may name what is gone."""

import os
import pathlib
import re

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SKILL = ".claude/skills/verify/SKILL.md"
# The reference's own files (SURVEY.md section 1), with or without `src/`.
REFERENCE_FILES = {
    "dispatcher.py", "node.py", "node_state.py", "dag_util.py", "test.py",
    "local_infer.py",
}
_SPAN = re.compile(r"`([^`]+)`")
# A word that is a file's name, with a `:line`, a `:from-to` or a
# `::name` after it left off.
_NAME = re.compile(r"([^\s:]+\.(?:py|sh|toml|json|md))(?::[\w:,\-\[\]]*)?")


def _tree_files() -> set[str]:
    """Every file under the repository's root as a /-joined relative
    path. No call to git: the tests also run from an unpacked archive."""
    out = {SKILL}  # under a dot directory, which the walk leaves out
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [
            d for d in dirs if d != "chiprun_out" and not d.startswith(".")
        ]
        rel = pathlib.Path(root).relative_to(REPO)
        out.update((rel / n).as_posix() for n in names)
    return out


def _names(text: str) -> set[str]:
    """The file names among the words of every backticked span and
    every fenced block (either may hold a whole command). Names
    holding `<`, `*`, `{` or `$` are patterns, not files, and an
    absolute path lies outside the repository."""
    parts = text.split("```")
    spans = parts[1::2] + [
        span for prose in parts[0::2] for span in _SPAN.findall(prose)
    ]
    return {
        m.group(1)
        for span in spans
        for word in span.split()
        if (m := _NAME.fullmatch(word))
        and not set(m.group(1)) & set("<*{$")
        and not m.group(1).startswith("/")
    }


@pytest.mark.parametrize(
    "document", ["README.md", "ARCHITECTURE.md", "PARITY.md", SKILL]
)
def test_document_names_live_files(document):
    names = _names((REPO / document).read_text(encoding="utf-8"))
    assert names, f"{document}: the rule found no file name to check"
    files = _tree_files()
    bases = {f.rsplit("/", 1)[-1] for f in files}
    dangling = sorted(
        n
        for n in names
        if n.removeprefix("src/") not in REFERENCE_FILES
        and n not in files
        and not any(f.endswith("/" + n) for f in files)
        and n.rsplit("/", 1)[-1] not in bases
    )
    assert not dangling, f"{document} names files not in the tree: {dangling}"
