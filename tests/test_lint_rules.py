"""Per-rule fixture tests for yamt-lint (analysis/).

Every rule is proven twice: a bad fixture that MUST flag (and flag only that
rule) and a clean fixture that MUST stay silent — so a rule that silently
stops firing (or starts over-firing) breaks the gate, not just the linter's
usefulness. Plus framework coverage: suppression comments, reporters, CLI
exit codes, syntax-error handling.
"""

import json
import pathlib

import pytest

from yet_another_mobilenet_series_tpu import analysis
from yet_another_mobilenet_series_tpu.analysis import cli

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "lint"
RULE_IDS = [f"YAMT{i:03d}" for i in range(1, 26)]


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_bad_fixture_flags(rule_id):
    findings = analysis.run_lint([FIXTURES / rule_id.lower() / "bad"])
    assert findings, f"{rule_id}: bad fixture produced no findings"
    assert {f.rule for f in findings} == {rule_id}, (
        f"{rule_id}: bad fixture flagged other rules too: "
        + "\n".join(f.format() for f in findings)
    )


@pytest.mark.parametrize("rule_id", RULE_IDS)
def test_clean_fixture_silent(rule_id):
    findings = analysis.run_lint([FIXTURES / rule_id.lower() / "clean"])
    assert findings == [], (
        f"{rule_id}: clean fixture must not flag:\n" + "\n".join(f.format() for f in findings)
    )


# -- suppressions -----------------------------------------------------------


def test_line_suppression(tmp_path):
    (tmp_path / "m.py").write_text("from jax.experimental import maps  # yamt-lint: disable=YAMT006\n")
    assert analysis.run_lint([tmp_path]) == []


def test_line_suppression_is_rule_scoped(tmp_path):
    # suppressing a DIFFERENT rule must not silence this one
    (tmp_path / "m.py").write_text("from jax.experimental import maps  # yamt-lint: disable=YAMT001\n")
    assert [f.rule for f in analysis.run_lint([tmp_path])] == ["YAMT006"]


def test_file_suppression(tmp_path):
    (tmp_path / "m.py").write_text(
        "# yamt-lint: disable-file=YAMT006\n"
        "from jax.experimental import maps\n"
        "import jax._src.core\n"
    )
    assert analysis.run_lint([tmp_path]) == []


def test_disable_all(tmp_path):
    (tmp_path / "m.py").write_text("from jax.experimental import maps  # yamt-lint: disable=all\n")
    assert analysis.run_lint([tmp_path]) == []


def test_suppression_in_docstring_is_not_a_suppression(tmp_path):
    # suppression syntax QUOTED in a docstring (e.g. core.py's own usage
    # examples) must not register: only real COMMENT tokens count
    (tmp_path / "m.py").write_text(
        '"""Example:  # yamt-lint: disable-file=YAMT006\n'
        'and inline:  # yamt-lint: disable=YAMT006\n'
        '"""\n'
        "from jax.experimental import maps\n"
    )
    assert [f.rule for f in analysis.run_lint([tmp_path])] == ["YAMT006"]


# -- stale-suppression audit ------------------------------------------------


def test_stale_suppression_flagged(tmp_path):
    (tmp_path / "m.py").write_text(
        "import jax  # yamt-lint: disable=YAMT006 — stale: plain jax import is fine\n"
    )
    findings = analysis.check_suppressions([tmp_path])
    assert [(f.rule, f.line) for f in findings] == [("YAMT900", 1)]


def test_live_suppression_not_flagged(tmp_path):
    (tmp_path / "m.py").write_text("from jax.experimental import maps  # yamt-lint: disable=YAMT006\n")
    assert analysis.check_suppressions([tmp_path]) == []
    assert analysis.run_lint([tmp_path]) == []


def test_stale_file_suppression_flagged(tmp_path):
    (tmp_path / "m.py").write_text(
        "# yamt-lint: disable-file=YAMT006\n"
        "import jax\n"
    )
    findings = analysis.check_suppressions([tmp_path])
    assert [(f.rule, f.line) for f in findings] == [("YAMT900", 1)]
    assert "file-wide" in findings[0].message


def test_suppression_audit_respects_select(tmp_path):
    # rules outside the selection are not re-run, so their suppressions are
    # left alone rather than declared stale
    (tmp_path / "m.py").write_text(
        "import jax  # yamt-lint: disable=YAMT006\n"
    )
    assert analysis.check_suppressions([tmp_path], select={"YAMT002"}) == []
    assert analysis.check_suppressions([tmp_path], select={"YAMT006"}) != []


def test_cli_check_suppressions(capsys):
    rc = cli.main([str(FIXTURES / "yamt006" / "clean"), "--check-suppressions"])
    capsys.readouterr()
    assert rc == 0


# -- framework --------------------------------------------------------------


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    (tmp_path / "m.py").write_text("def broken(:\n")
    findings = analysis.run_lint([tmp_path])
    assert [f.rule for f in findings] == ["YAMT000"]


def test_select_restricts_rules():
    bad = FIXTURES / "yamt001" / "bad"
    assert analysis.run_lint([bad], select={"YAMT006"}) == []
    assert {f.rule for f in analysis.run_lint([bad], select={"YAMT001"})} == {"YAMT001"}


def test_registry_has_all_rules():
    ids = [r.id for r in analysis.load_rules()]
    assert ids == sorted(ids)
    for rid in RULE_IDS:
        assert rid in ids


def test_reporters():
    findings = analysis.run_lint([FIXTURES / "yamt006" / "bad"])
    text = analysis.render_text(findings)
    assert "YAMT006" in text and text.endswith(f"{len(findings)} findings")
    doc = json.loads(analysis.render_json(findings))
    assert doc["count"] == len(doc["findings"]) == len(findings)
    assert {"path", "line", "col", "rule", "message"} <= set(doc["findings"][0])


def test_github_reporter():
    findings = analysis.run_lint([FIXTURES / "yamt006" / "bad"])
    gh = analysis.render_github(findings)
    first = findings[0]
    lines = gh.splitlines()
    assert lines[0].startswith(
        f"::error file={first.path},line={first.line},col={first.col + 1},title={first.rule}::"
    )
    assert sum(ln.startswith("::error ") for ln in lines) == len(findings)
    assert analysis.render_github([]) == "clean: no findings"


def test_cli_github_format(capsys):
    rc = cli.main([str(FIXTURES / "yamt006" / "bad"), "--format", "github"])
    out = capsys.readouterr().out
    assert rc == 1 and out.startswith("::error file=")


# -- CLI --------------------------------------------------------------------


def test_cli_exit_codes_and_json(capsys):
    rc = cli.main([str(FIXTURES / "yamt006" / "bad"), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["count"] >= 1

    rc = cli.main([str(FIXTURES / "yamt006" / "clean"), "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["count"] == 0


def test_cli_select_filters(capsys):
    rc = cli.main([str(FIXTURES / "yamt001" / "bad"), "--select", "YAMT006"])
    capsys.readouterr()
    assert rc == 0


def test_cli_list_rules(capsys):
    rc = cli.main(["--list-rules"])
    out = capsys.readouterr().out
    assert rc == 0
    for rid in RULE_IDS:
        assert rid in out
