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


# the linter's CPU time over the CPU time of parsing and walking the same
# files five times; see test_whole_package_lint_stays_fast
LINT_COST_BAR = 3.5


def test_whole_package_lint_stays_fast():
    """An un-cached whole-package run, interprocedural layer included, must
    stay effectively free, or people stop running it. The bar is RELATIVE: a
    bar in seconds (5 s of CPU until PR 30) holds on one machine only, and
    the driver's read more than that for work the builder's did in 3.97.

    Measured in a FRESH subprocess (pytest's warning capture and stray daemon
    threads of a 700-test session are the suite's cost, not the linter's),
    on the child's CPU clock (the linter is one thread of pure Python; time
    spent waiting for a core under six xdist workers is the suite's too), and
    against a yardstick timed beside it in the same child: `ast.parse` +
    `ast.walk` over the very files the linter reads, five times, which is
    the same kind of work (pure Python over ASTs and dicts, bound by the
    same caches) and grows with the package as the linter's irreducible part
    does. Each is the MIN of three interleaved samples: a slower or busier
    core stretches both, a complexity regression in a rule or in the
    summary fixpoint raises every sample of the linter's alone, and the
    package growing by a third moves neither side of the ratio. Each
    run_lint rebuilds its Project, so nothing is amortized. On the builder's
    machine the ratio read 1.98-2.07 alone and 2.03-2.48 beside five busy
    xdist workers at PR 30, 2.34-2.61 beside five at PR 31 (CHANGES.md has
    the readings): the bar trips at 1.5x the usual reading, 1.3x the worst."""
    code = (
        "import ast, pathlib, time\n"
        "from yet_another_mobilenet_series_tpu.analysis import run_lint\n"
        "from yet_another_mobilenet_series_tpu.analysis.core import collect_paths\n"
        f"pkg = pathlib.Path({str(PACKAGE)!r})\n"
        "sources = [pathlib.Path(p).read_text() for p in collect_paths([pkg])[0]]\n"
        "def cpu(fn):\n"
        "    t0 = time.process_time()\n"
        "    fn()\n"
        "    return time.process_time() - t0\n"
        "def yardstick():\n"
        "    for _ in range(5):\n"
        "        for src in sources:\n"
        "            for _ in ast.walk(ast.parse(src)):\n"
        "                pass\n"
        "runs = [(cpu(lambda: run_lint([pkg])), cpu(yardstick)) for _ in range(3)]\n"
        "print(min(r[0] for r in runs), min(r[1] for r in runs))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr
    lint, walk = (float(v) for v in out.stdout.strip().splitlines()[-1].split())
    print(f"run_lint {lint:.2f}s CPU, yardstick {walk:.2f}s CPU, ratio {lint / walk:.3f}")
    assert lint / walk < LINT_COST_BAR, (
        f"run_lint over the package took {lint:.2f}s of CPU best-of-3, {lint / walk:.2f}x the "
        f"{walk:.2f}s that parsing and walking the same files five times took beside it (bar: {LINT_COST_BAR}x)")


def test_apps_ymls_are_covered():
    # guard against the gate silently losing its yml coverage: the collector
    # must actually pick up the experiment files next to the code
    from yet_another_mobilenet_series_tpu.analysis.core import collect_paths

    py, yml = collect_paths([PACKAGE])
    assert any(p.endswith("config.py") for p in py)
    assert sum(p.endswith((".yml", ".yaml")) for p in yml) >= 10
