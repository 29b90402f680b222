"""The documents name files that exist: every repo-relative path quoted
in a code span of README.md, docs/*.md and benchmark/README.md — and
every `python <file>` / `scripts/<file>` command in their code blocks —
resolves in the tree. Globs, placeholders (`<name>`), absolute paths
(the `/root/reference/...` citations, URL paths of the status port) and
what a run leaves behind (`.jax_cache/`, `chiprun_out/`) are skipped."""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCS = sorted([ROOT / "README.md", ROOT / "benchmark" / "README.md",
               *(ROOT / "docs").glob("*.md")])

_EXTS = (".py", ".sh", ".md", ".json", ".jsonl", ".cc", ".pb", ".toml")
# a doc may name a file relative to the repo, to the package, to the
# benchmark or to its own directory; a bare file name (`wire.py` after
# its directory was named) has to exist somewhere in the tree
_BASES = ("", "tidb_tpu", "benchmark", "docs", "tests")
_GENERATED = ("chiprun_out/", "_build/")
_TOKEN = re.compile(r"[A-Za-z0-9_.\-/]+")
_COMMAND = re.compile(r"(?:python3?\s+|(?=scripts/))([A-Za-z0-9_.\-/]+)")
_BASENAMES = {p.name for d in ("tidb_tpu", "tests", "benchmark", "scripts",
                                "docs") for p in (ROOT / d).rglob("*.*")
              } | {p.name for p in ROOT.glob("*.*")}


def _candidates(text: str):
    """(line number, path) of every token of a code span that looks
    like a file or a directory of the repo, and of the file every
    command of a code block runs."""
    fenced = False
    for lineno, line in enumerate(text.splitlines(), 1):
        if line.lstrip().startswith("```"):
            fenced = not fenced
            continue
        block = fenced or line.startswith("    ")
        for span in [line] if block else re.findall(r"`([^`]+)`", line):
            if not block and any(c in span for c in "*<>{}$"):
                continue
            for tok in (_COMMAND if block else _TOKEN).findall(span):
                tok = tok.rstrip(".")
                if tok.startswith(("/", ".", "-")) or "//" in tok or \
                        any(g in tok for g in _GENERATED):
                    continue
                if tok.endswith(_EXTS) or \
                        (tok.endswith("/") and "/" in tok[:-1]):
                    yield lineno, tok


@pytest.mark.parametrize("doc", DOCS, ids=lambda p: str(
    p.relative_to(ROOT)))
def test_quoted_paths_exist(doc):
    bases = [ROOT / b for b in _BASES] + [doc.parent]
    missing = [f"{doc.relative_to(ROOT)}:{lineno}: {tok}"
               for lineno, tok in _candidates(doc.read_text())
               if not (tok in _BASENAMES if "/" not in tok else
                       any((b / tok).exists() for b in bases))]
    assert not missing, "\n".join(missing)


def test_the_walk_sees_paths():
    """Vacuity guard: the extraction finds what it is for."""
    found = {tok for _n, tok in _candidates(
        "see `store/copr.py:101` and `benchmark/run.py`\n\n"
        "    python chip_smoke.py --sf 0.01 > out.json\n"
        "    scripts/lint.sh --json\n"
        "skip `configs/<name>.json`, `/root/reference/x.go`, "
        "`tests/*.py` and `device/dispatch`\n")}
    assert found == {"store/copr.py", "benchmark/run.py", "chip_smoke.py",
                     "scripts/lint.sh"}
