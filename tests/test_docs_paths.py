"""The documents name files and modules that exist.

One case a document.  Every relative path it names in backticks or on a
command line that ends in `.py`, `.json`, `.sh` or `.md` must be a file of
the tree: a name matches when it is the whole of, or the tail (from a
directory boundary) of, a file's path from the repo root, so `serve.py`
and `ops/xent.py` resolve as `scripts/lint.py` does.  Not checked:
absolute paths, names with `<`, `*` or `{` (patterns, placeholders, and
the upstream project's files, which the documents write as
`<reference>/...`), and names under a directory that holds a run's
output (`build/`, `chiprun_out/`).  Every `automodule` target must
resolve.

`ROADMAP.md`, `CHANGES.md`, `PERF.md`, `VERDICT.md` and `SURVEY.md` are
history: they may name what is gone, and are not cases.
"""
import functools
import glob
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = (["README.md", "MIGRATION.md", "examples/resnet/README.md",
         ".claude/skills/verify/SKILL.md"]
        + sorted(os.path.relpath(p, REPO) for p in
                 glob.glob(os.path.join(REPO, "docs", "source", "*.rst"))))
# what a run leaves behind: never a file of the tree, and a name under
# one is an output's
SKIP_DIRS = {".git", "__pycache__", ".pytest_cache", ".hypothesis",
             ".jax_cache", "chiprun_out", ".chip_archive", "build", "dist"}
RUNNERS = {"python", "python3", "pytest", "bash", "sh", "spark-submit"}
NAME = re.compile(r"[\w.+-]+(?:/[\w.+-]+)*\.(?:py|json|sh|md)")


@functools.lru_cache(maxsize=None)
def _tree_files():
    out = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        out.extend("/" + os.path.relpath(os.path.join(root, f), REPO)
                   .replace(os.sep, "/") for f in files)
    return out


def _command_words(line):
    """The words of `line` if it runs a program, else nothing."""
    words = line.strip().lstrip("$ ").split()
    while words and re.fullmatch(r"\w+=\S*", words[0]):
        words.pop(0)                        # VAR=value prefixes
    return words[1:] if words and words[0] in RUNNERS else []


def named_paths(text):
    """Relative `.py`/`.json`/`.sh`/`.md` names in backticks and on
    command lines."""
    words = []
    for span in re.findall(r"`+([^`\n]+(?:\n[^`\n]+)?)`+", text):
        words.extend(span.split())
    for line in text.splitlines():
        words.extend(_command_words(line))
    names = set()
    for word in words:
        if any(c in word for c in "<*{") or "://" in word:
            continue
        word = re.sub(r":\d+(-\d+)?$", "", word.strip("\"'(),;:"))
        if (not word.startswith(("/", "~")) and NAME.fullmatch(word)
                and word.split("/")[0] not in SKIP_DIRS):
            names.add(word[2:] if word.startswith("./") else word)
    return sorted(names)


def automodules(text):
    return re.findall(r"^\.\. automodule::\s*(\S+)", text, flags=re.M)


def _resolves(module):
    try:
        return importlib.util.find_spec(module) is not None
    except ModuleNotFoundError:             # a parent package is missing
        return False


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_what_exists(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    files = _tree_files()
    missing = [n for n in named_paths(text)
               if not any(f.endswith("/" + n) for f in files)]
    assert not missing, f"{doc} names files not in the tree: {missing}"
    unresolved = [m for m in automodules(text) if not _resolves(m)]
    assert not unresolved, f"{doc} documents modules not found: {unresolved}"


def test_the_extraction_sees_what_it_should():
    text = ("run `python old/gone.py --x` or ``pkg/mod.py:12``;\n"
            "   $ JAX_PLATFORMS=cpu python3 scripts/tool.py --flag\n"
            "not `/tmp/scratch.py`, `<cell>.json`, `tests/test_*.py`,\n"
            "`out.jsonl`, `build/report.json` or plain words like notes.md\n"
            ".. automodule:: a.b\n")
    assert named_paths(text) == ["old/gone.py", "pkg/mod.py",
                                 "scripts/tool.py"]
    assert automodules(text) == ["a.b"]
