"""tblint test suite: golden fixture findings, per-rule fire + suppression
proofs, a clean run over the real tree, and the CLI contract.

The fixture tree under tests/fixtures/tblint/ mirrors the package layout
(an ops/ dir, a sim/ dir) because tblint scopes rules by path components;
expected.json pins every (file, line, rule) triple.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "tblint")

from tools import tblint  # noqa: E402  (conftest puts REPO on sys.path)
from tools.tblint.core import (  # noqa: E402
    check_suppressions, iter_files, iter_rules,
)

# Every registered rule must be exercised by the fixtures.
ALL_RULE_IDS = {
    "traced-branch", "concretize", "host-sync", "nondet", "u128-limb",
    "wide-literal", "layout-drift", "swallow", "unrolled-loop",
    # tbsan semantic suite (PR 12):
    "donation", "size-class", "lane-race", "shard-rep",
    # authenticated-wire suite (PR 16):
    "ingress-auth",
}


def _fixture_findings():
    """(relpath, line, rule) triples from a run over the fixture tree."""
    out = set()
    for f in tblint.run([FIXTURES]):
        rel = f.path.split("fixtures/tblint/", 1)[1]
        out.add((rel, f.line, f.rule))
    return out


def _expected():
    with open(os.path.join(FIXTURES, "expected.json")) as fh:
        data = json.load(fh)
    return {(e["path"], e["line"], e["rule"]) for e in data["findings"]}


def test_registry_has_all_rules():
    assert {r.id for r in iter_rules()} == ALL_RULE_IDS
    for rule in iter_rules():
        assert rule.summary and rule.rationale, rule.id


def test_golden_findings_exact():
    got, want = _fixture_findings(), _expected()
    assert got == want, (
        f"missing: {sorted(want - got)}\nunexpected: {sorted(got - want)}"
    )


def test_every_rule_fires_on_fixtures():
    fired = {rule for _, _, rule in _expected()}
    assert fired == ALL_RULE_IDS, ALL_RULE_IDS - fired


def test_every_rule_has_a_suppression_case():
    """Each rule appears in at least one `tblint: ignore[...]` fixture
    comment, and no finding survives on any suppressed line."""
    suppressed_rules = set()
    suppressed_lines = set()  # (relpath, line)
    for dirpath, _dirs, files in os.walk(FIXTURES):
        for name in files:
            if not name.endswith((".py", ".h")):
                continue
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, FIXTURES).replace(os.sep, "/")
            with open(path) as fh:
                for i, line in enumerate(fh, 1):
                    if "tblint: ignore[" in line:
                        inside = line.split("tblint: ignore[", 1)[1]
                        inside = inside.split("]", 1)[0]
                        for rule in inside.split(","):
                            suppressed_rules.add(rule.strip())
                        suppressed_lines.add((rel, i))
    assert suppressed_rules == ALL_RULE_IDS, (
        ALL_RULE_IDS - suppressed_rules
    )
    hits = {(p, ln) for p, ln, _ in _fixture_findings()}
    leaked = hits & suppressed_lines
    assert not leaked, f"suppression did not silence: {sorted(leaked)}"


def test_real_tree_is_clean():
    """The package, tools and tests must stay lint-clean AND
    free of stale suppressions — the same gate tools/ci.py's lint tier
    enforces (tests/fixtures holds the deliberate violations and is
    excluded)."""
    files = iter_files(
        [
            os.path.join(REPO, "tigerbeetle_tpu"),
            os.path.join(REPO, "tools"),
            os.path.join(REPO, "tests"),
        ],
        exclude=[os.path.join(REPO, "tests", "fixtures")],
    )
    findings = check_suppressions(files)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_check_suppressions_flags_stale():
    """The stale fixture's do-nothing suppression is flagged ONLY in
    --check-suppressions mode; used suppressions and bare/placeholder
    doc examples are not."""
    normal = {(f.path, f.rule) for f in tblint.run([FIXTURES])}
    assert not any(r == "stale-suppression" for _, r in normal)
    stale = [
        f for f in check_suppressions([FIXTURES])
        if f.rule == "stale-suppression"
    ]
    assert [
        (f.path.split("fixtures/tblint/", 1)[1], f.line) for f in stale
    ] == [("stale_case.py", 4)], [f.render() for f in stale]


def test_cli_exit_codes_and_json():
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", REPO)
    dirty = subprocess.run(
        [sys.executable, "-m", "tools.tblint", "--json",
         "tests/fixtures/tblint"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    assert dirty.returncode == 1, dirty.stderr
    payload = json.loads(dirty.stdout)
    assert len(payload["findings"]) == len(_expected())
    assert payload["files_scanned"] > 0
    clean = subprocess.run(
        [sys.executable, "-m", "tools.tblint", "tools/tblint"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr


def test_list_rules():
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.tblint", "--list-rules"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    assert proc.returncode == 0
    for rule_id in ALL_RULE_IDS:
        assert rule_id in proc.stdout, rule_id


def test_single_rule_filter():
    findings = tblint.run(
        [FIXTURES],
        rules=[r for r in iter_rules() if r.id == "swallow"],
    )
    assert findings and all(f.rule == "swallow" for f in findings)
