"""The package imports nothing from outside itself: no module under
tidb_tpu/ imports the repo's tests, its benchmark or a repo-root
script. The arrows point one way — tests, benchmark/ and chip_smoke.py
import the program."""

import ast
import pathlib

ROOT = pathlib.Path(__file__).resolve().parent.parent
OUTSIDE = {"tests", "benchmark", "bench", "chip_smoke"}


def _imported_top_levels(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield node.lineno, a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                and node.module:
            yield node.lineno, node.module.split(".")[0]


def test_package_imports_nothing_outside_itself():
    files = sorted((ROOT / "tidb_tpu").rglob("*.py"))
    assert len(files) > 100, "the walk found no package"
    bad = [f"{f.relative_to(ROOT)}:{lineno} imports {top}"
           for f in files
           for lineno, top in _imported_top_levels(
               ast.parse(f.read_text(), filename=str(f)))
           if top in OUTSIDE]
    assert not bad, "\n".join(bad)
