"""The project lint suite, as a single parametrized pytest shim.

Replaces the four standalone AST-walking test files (test_lint_wire.py,
test_lint_sync.py, test_lint_metrics.py, test_lint_memtrack.py), each
of which re-parsed the whole ~100-module package with its own ad-hoc
suppression convention. The engine (tidb_tpu/lint) parses the package
ONCE into a shared forest; every registered rule — the four ported
invariants, the twelve project-specific additions, the three
whole-program flow rules (tidb_tpu/lint/flow), and the three
device-plane dataflow rules (tidb_tpu/lint/flow/device) — runs over
it, and each gets its own test id here so a regression names the rule
that caught it.

The single-parse guarantee is pinned by PARSE COUNTS, not wall time:
the engine counts every `ast.parse` it performs
(tidb_tpu.lint.engine.parse_count), and the assertions below hold
whatever the CI load — the old wall-time pin flaked whenever the tight
tier-1 budget ran this file under concurrent CPU pressure.

The same rule set backs `python -m tidb_tpu.lint` (CI / pre-commit,
scripts/lint.sh); test_cli_* pins that front end's exit-code contract
and the `--json` schema.
"""

import json
import os
import subprocess
import sys

import pytest

from tidb_tpu.lint import REGISTRY, run
from tidb_tpu.lint.engine import (BAD_RULE, REPO, UNUSED_RULE,
                                  parse_count)

RULE_NAMES = list(REGISTRY)


@pytest.fixture(scope="module")
def report():
    """One engine run — one parse of the package — shared by every
    per-rule assertion below. The process-wide parse counter is
    bracketed around the run so the instrumentation tests can account
    for every single ast.parse it triggered."""
    before = parse_count()
    rep = run()
    rep.parse_calls_run = parse_count() - before
    return rep


def test_catalog_is_complete():
    """4 ported + 11 project-specific + 3 whole-program flow rules
    + 3 device-plane dataflow rules."""
    assert len(RULE_NAMES) == 21, RULE_NAMES
    for ported in ("wire-discipline", "hot-path-sync", "metric-names",
                   "memtrack-alloc"):
        assert ported in RULE_NAMES
    for new in ("lock-discipline", "sysvar-registry",
                "errcode-discipline", "device-sync", "dtype-discipline",
                "bare-except", "device-cache", "decode-discipline",
                "failpoint-discipline", "trace-names",
                "metric-cardinality"):
        assert new in RULE_NAMES
    for flow in ("lock-order", "guarded-by", "paired-resource"):
        assert flow in RULE_NAMES
    for dev in ("donation-safety", "cache-key", "retrace-hazard"):
        assert dev in RULE_NAMES


@pytest.mark.parametrize("rule", RULE_NAMES)
def test_rule_clean(report, rule):
    """The repo is clean under this rule (includes the rule's vacuity
    guard: its fixture still fires and it examined real in-tree
    sites)."""
    bad = [f for f in report.findings if f.rule == rule]
    assert not bad, "\n".join(str(f) for f in bad)


def test_suppression_hygiene(report):
    """No stale (unused) exempt tags, no reasonless or unknown-rule
    tags anywhere in the package."""
    bad = [f for f in report.findings
           if f.rule in (UNUSED_RULE, BAD_RULE)]
    assert not bad, "\n".join(str(f) for f in bad)


def test_no_unattributed_findings(report):
    known = set(RULE_NAMES) | {UNUSED_RULE, BAD_RULE}
    assert not [f for f in report.findings if f.rule not in known]


def test_single_parse_instrumentation(report):
    """The whole point of the shared forest: parse once per module,
    and every rule — the flow rules' call graph and lock registry
    included — walks that one parse. Asserted on the engine's
    `ast.parse` counter (load-independent), not wall time:

    * Forest.load parsed exactly one AST per package module;
    * the only parses beyond the load are the vacuity guard's fixture
      forests (a known, enumerable set) — the rule walks themselves
      added ZERO.
    """
    assert report.files >= 90          # it really saw the package
    assert report.parse_calls == report.files
    fixture_parses = sum(1 + len(cls.fixture_support)
                         for cls in REGISTRY.values())
    assert report.parse_calls_run == report.files + fixture_parses, (
        f"{report.parse_calls_run - report.files - fixture_parses} "
        f"unexpected ast.parse call(s) during the rule walks — a rule "
        f"is re-parsing instead of using the forest")


# -- CLI front end (CI / pre-commit contract) -------------------------------

def test_cli_json_smoke():
    """One real `python -m tidb_tpu.lint --json` subprocess (the
    scripts/lint.sh invocation): exit 0 on the clean tree and the
    stable machine-readable schema — file/line/rule/message findings,
    rule list, and the parse-count instrumentation that replaces
    wall-time pins."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "tidb_tpu.lint", "--json"],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["version"] == 1
    assert doc["clean"] is True
    assert doc["findings"] == []
    assert doc["files"] >= 90
    assert doc["rules"] == RULE_NAMES
    timing = doc["timing"]
    assert set(timing) == {"parse_ms", "total_ms", "parse_calls",
                           "rule_ms"}
    assert timing["parse_calls"] == doc["files"]    # single parse
    assert set(timing["rule_ms"]) == set(RULE_NAMES)


def test_cli_exit_codes_in_process(capsys):
    """Exit-code contract without paying three jax-importing
    subprocess spawns: 0 clean / 2 usage (1-on-findings is covered by
    main() returning bool(report.findings) over the clean repo run)."""
    from tidb_tpu.lint.__main__ import main
    assert main(["--rule", "no-such-rule"]) == 2
    assert "unknown rule" in capsys.readouterr().err
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in RULE_NAMES:
        assert name in out


def test_findings_report_is_not_clean(tmp_path):
    """The 1-exit half of the contract, in process: a tree with a real
    lock-order cycle produces a non-clean report (main() exits
    bool(findings)); the JSON rows carry file/line/rule/message."""
    pkg = tmp_path / "tidb_tpu"
    pkg.mkdir()
    (pkg / "__init__.py").write_text(
        "import threading\n"
        "_a = threading.Lock()\n"
        "_b = threading.Lock()\n"
        "def f():\n"
        "    with _a:\n"
        "        with _b:\n"
        "            pass\n"
        "def g():\n"
        "    with _b:\n"
        "        with _a:\n"
        "            pass\n")
    from tidb_tpu.lint import engine
    rep = engine.run(rules=["lock-order"], root=str(tmp_path),
                     with_selfcheck=False, with_vacuity=False)
    assert not rep.clean
    hit = [f for f in rep.findings
           if f.rule == "lock-order" and "cycle" in f.message]
    assert hit, rep.findings
    row = {"file": hit[0].file, "line": hit[0].line,
           "rule": hit[0].rule, "message": hit[0].message}
    assert json.loads(json.dumps(row)) == row