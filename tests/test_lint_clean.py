"""Tier-1 gate: the package must lint clean under its own analyzer.

This is the enforcement half of the yamt-lint tentpole: every invariant the
rules encode (no host effects under trace — now followed through resolved
calls, PRNG discipline including cross-call key flow, real mesh axes,
TRAIN_STATE_FIELDS/TrainState agreement, apps/*.yml vs config.py schema,
version-resilient jax imports, donation discipline through attribute calls,
recompilation hazards at static positions — docs/LINT.md) is checked on
every PR by this pure-AST test. A finding here is a real hazard or an
undocumented suppression — fix the code, don't widen the gate.

The perf guard pins the gate's reason to exist: with the full
interprocedural layer (symbol table + call graph + summary fixpoint) a
whole-package run must stay effectively free, or people stop running it.
"""

import pathlib
import subprocess
import sys

from yet_another_mobilenet_series_tpu.analysis import check_suppressions, load_rules, run_lint

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "yet_another_mobilenet_series_tpu"
SCRIPTS = pathlib.Path(__file__).resolve().parent.parent / "scripts"

# the curated scripts/ subset: PRNG discipline and version-fragile imports
# apply to standalone benches exactly as to package code; the
# package-convention rules (logging sinks, config drift, donation idioms)
# deliberately do not
SCRIPT_RULES = {"YAMT002", "YAMT006"}


def test_package_lints_clean():
    findings = run_lint([PACKAGE])
    assert findings == [], (
        "the package must lint clean (see docs/LINT.md):\n"
        + "\n".join(f.format() for f in findings)
    )


def test_new_interprocedural_rules_are_registered():
    ids = {r.id for r in load_rules()}
    assert {"YAMT009", "YAMT010", "YAMT019", "YAMT020", "YAMT021",
            "YAMT022", "YAMT023", "YAMT024", "YAMT025"} <= ids


def test_no_stale_suppressions():
    # every suppression in the package must still be earning its keep: the
    # audit re-runs the rules raw and flags comments whose rule no longer
    # fires at their site (scripts/lint.sh --check-suppressions in CI)
    findings = check_suppressions([PACKAGE])
    assert findings == [], (
        "stale suppression comments (delete them):\n"
        + "\n".join(f.format() for f in findings)
    )


def test_scripts_lint_clean_under_curated_subset():
    findings = run_lint([SCRIPTS], select=SCRIPT_RULES)
    assert findings == [], (
        "scripts/ must lint clean under the curated subset (see docs/LINT.md):\n"
        + "\n".join(f.format() for f in findings)
    )


def test_whole_package_lint_stays_fast():
    # un-cached end-to-end runs, interprocedural layer included (measured
    # ~3.3-4.5s on the 1-core box with the full 25-rule set, so the 5s bar
    # trips on a complexity regression, not machine noise). Timed in a
    # FRESH subprocess: 500-odd tests into a tier-1 session, pytest's
    # warning capture and stray daemon threads were measured inflating the
    # same run past 6s — that noise belongs to the suite, not the linter,
    # and it's the linter this bar gates. The child times only run_lint
    # (imports excluded; analysis/ is pure-stdlib, ~0.3s to load) and
    # reports the MIN of three runs: this box's scheduler was measured
    # stretching identical runs ±40%, and the minimum estimates the true
    # compute cost — a complexity regression raises every sample, noise
    # only some (each run rebuilds its Project, so nothing is amortized).
    # The clock is the child's CPU time, not the wall: the linter is one
    # thread of pure Python, and under the driver's six xdist workers on
    # eight cores the wall of the SAME work read 5.70s best-of-3 (7.7-9.0s
    # beside 14 busy loops) where its CPU time read 3.97s, 3.2s idle (PR 29):
    # time spent waiting for a core is the suite's, not the linter's.
    code = (
        "import pathlib, time\n"
        "from yet_another_mobilenet_series_tpu.analysis import run_lint\n"
        f"pkg = pathlib.Path({str(PACKAGE)!r})\n"
        "best = min(\n"
        "    (lambda t0: (run_lint([pkg]), time.process_time() - t0)[1])(time.process_time())\n"
        "    for _ in range(3)\n"
        ")\n"
        "print(best)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    elapsed = float(out.stdout.strip().splitlines()[-1])
    assert elapsed < 5.0, f"run_lint over the package took {elapsed:.2f}s of CPU best-of-3 (bar: 5s)"


def test_apps_ymls_are_covered():
    # guard against the gate silently losing its yml coverage: the collector
    # must actually pick up the experiment files next to the code
    from yet_another_mobilenet_series_tpu.analysis.core import collect_paths

    py, yml = collect_paths([PACKAGE])
    assert any(p.endswith("config.py") for p in py)
    assert sum(p.endswith((".yml", ".yaml")) for p in yml) >= 10
