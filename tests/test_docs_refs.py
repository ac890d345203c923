"""The documents name only what the tree has.

One case a document (`README.md` and `docs/*.md`): every back-ticked
token that looks like a repository path (`*.py`, `*.md`, `*.json`,
`*.toml`, or a directory written with its `/`; with or without
`::name` or `:line`) resolves to a file or directory of this tree, a
`path::name` to a name that file defines, a `path:line` to a line it
has. A path may be written from the root, from `delta_tpu/` or from
`tests/`, as the documents do (`ops/replay.py`, `test_merge.py`).

`ROADMAP.md`, `CHANGES.md` and `PERF.md` are history and are not
checked. Three kinds of token are no claim about this tree and are
named below, each with its reason: paths of the upstream project that
`docs/parity.md` and the README map against, what a run writes, and a
format spelt out with a made-up name.
"""

import ast
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.join("docs", f) for f in os.listdir(os.path.join(ROOT, "docs"))
    if f.endswith(".md"))

# directories and files of the upstream repository (vkorukanti/delta),
# named where a document maps this tree against it
UPSTREAM = {"kernel/", "spark/", "storage-s3-dynamodb/", "PROTOCOL.md"}
# the Delta log's own layout on a table's storage, and what a run of
# `delta-lint --changed` leaves behind (`.gitignore` lists it)
WRITTEN_AT_RUN_TIME = {"_delta_log/", "_sidecars/", "_commits/", "N.json",
                       ".delta-lint-cache.json"}
# the analyzer's node-id format, `<relpath>::<qualname>`, spelt out
PLACEHOLDERS = {"module.py"}
NOT_OF_THIS_TREE = UPSTREAM | WRITTEN_AT_RUN_TIME | PLACEHOLDERS

_TOKEN = re.compile(
    r"^(?P<path>[\w.-]+(?:/[\w.-]+)*(?:\.py|\.md|\.json|\.toml|/))"
    r"(?:::(?P<name>[A-Za-z_][\w.]*))?(?::(?P<line>\d+)(?:-\d+)?)?$")
_SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", ".hypothesis",
              ".jax_cache", ".chip_smoke_work", "chiprun_out",
              "_archive_check"}


@pytest.fixture(scope="module")
def tree():
    """(files, directories) of the checkout, as paths from its root."""
    files, dirs = set(), set()
    for base, subdirs, names in os.walk(ROOT):
        subdirs[:] = [d for d in subdirs if d not in _SKIP_DIRS]
        rel = os.path.relpath(base, ROOT)
        rel = "" if rel == "." else rel + "/"
        dirs.update(rel + d + "/" for d in subdirs)
        files.update(rel + n for n in names)
    return files, dirs


def _resolve(path, files, dirs):
    """The tree's entry the document means: the path as written, or
    under `delta_tpu/` or `tests/`, or else the tail of some deeper
    path (`passes/locks.py`, `transfer_budget.json`)."""
    pool = dirs if path.endswith("/") else files
    for prefix in ("", "delta_tpu/", "tests/"):
        if prefix + path in pool:
            return prefix + path
    return min((p for p in pool if p.endswith("/" + path)), default=None)


def _defined(source):
    names = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            names.update(t.id for t in targets if isinstance(t, ast.Name))
    return names


def _tokens(text):
    text = re.sub(r"^```.*?^```", "", text, flags=re.S | re.M)
    for tok in re.findall(r"`([^`\n]+)`", text):
        m = _TOKEN.match(tok.strip())
        if m:
            yield tok, m


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc, tree):
    files, dirs = tree
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        text = f.read()
    checked, stale = 0, []
    for tok, m in _tokens(text):
        path = m["path"]
        if path in NOT_OF_THIS_TREE:
            continue
        checked += 1
        found = _resolve(path, files, dirs)
        if found is None:
            stale.append(f"`{tok}`: no such path")
            continue
        if not (m["name"] or m["line"]) or found.endswith("/"):
            continue
        with open(os.path.join(ROOT, found), encoding="utf-8") as f:
            source = f.read()
        if m["line"] and int(m["line"]) > source.count("\n") + 1:
            stale.append(f"`{tok}`: {found} has no line {m['line']}")
        if m["name"] and found.endswith(".py"):
            # `Class.method` and `module.attr` name their last part
            if m["name"].split(".")[-1] not in _defined(source):
                stale.append(f"`{tok}`: {found} defines no {m['name']}")
    assert checked, f"{doc}: the pattern found no path to check"
    assert not stale, f"{doc} names what the tree does not have:\n  " \
        + "\n  ".join(sorted(set(stale)))
