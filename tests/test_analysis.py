"""mce_lint test suite: fixture corpus + suppression mechanics + CLI.

Every bad fixture under tests/analysis_fixtures/ carries `# EXPECT-Rn`
sentinels on the exact lines the rule must flag; the parametrized test
asserts the analyzer reports precisely those (rule, line) pairs — no
misses, no extras. Good twins (the patterns the repo actually ships)
must pass clean. A final test runs the strict analyzer over the real
`src/repro` tree, which is the same gate CI enforces.
"""
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from repro.analysis.cli import analyze, main
from repro.analysis.findings import Suppressions
from repro.analysis.modindex import PackageIndex

HERE = os.path.dirname(__file__)
FIXTURES = os.path.join(HERE, "analysis_fixtures")
SRC = os.path.join(HERE, "..", "src", "repro")

_EXPECT_RE = re.compile(r"#\s*EXPECT-(R\d)\b")


def _expected(fixture_dir):
    """All (rule, path, line) sentinels in a fixture package."""
    out = set()
    for dirpath, _dirs, files in os.walk(fixture_dir):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                for i, line in enumerate(f, start=1):
                    for m in _EXPECT_RE.finditer(line):
                        out.add((m.group(1), path, i))
    return out


BAD = ["bad_r1", "bad_r2", "bad_r3", "bad_r3_cast", "bad_r4", "bad_r5"]
GOOD = ["good_r1", "good_r2", "good_r3", "good_r3_cast", "good_r4", "good_r5"]


@pytest.mark.parametrize("fixture", BAD)
def test_bad_fixture_flagged_at_the_right_lines(fixture):
    root = os.path.join(FIXTURES, fixture)
    active, suppressed, _s1, n = analyze(root)
    assert n > 0
    assert not suppressed
    got = {(f.rule, f.path, f.line) for f in active}
    want = _expected(root)
    assert want, f"{fixture} has no EXPECT sentinels"
    missing = want - got
    extra = got - want
    assert not missing, f"expected findings not raised: {sorted(missing)}"
    assert not extra, f"unexpected findings: {sorted(extra)}"


def test_bad_r2_is_the_pr1_kernel_flagged_at_its_accumulation_site():
    """The reproduced PR-1 vmap-accumulator kernel must be flagged on the
    `best_ref[...] = jnp.maximum(best_ref[...], score)` accumulation line
    itself (and its program_id-gated init)."""
    root = os.path.join(FIXTURES, "bad_r2")
    active, *_ = analyze(root)
    path = os.path.join(root, "kernel.py")
    with open(path) as f:
        lines = f.read().splitlines()
    acc_line = next(i for i, l in enumerate(lines, start=1)
                    if "jnp.maximum(best_ref" in l)
    hits = {f.line: f.message for f in active if f.rule == "R2"}
    assert acc_line in hits
    assert "vmap" in hits[acc_line]


@pytest.mark.parametrize("fixture", GOOD)
def test_good_twin_passes_clean(fixture):
    root = os.path.join(FIXTURES, fixture)
    active, suppressed, s1, n = analyze(root)
    assert n > 0
    assert active == [], [f.format() for f in active]
    assert s1 == []


def test_every_rule_family_fires_in_the_corpus():
    got = set()
    for fixture in BAD:
        active, *_ = analyze(os.path.join(FIXTURES, fixture))
        got |= {f.rule for f in active}
    assert got == {"R1", "R2", "R3", "R4", "R5"}


# ---------------------------------------------------------------------------
# suppression mechanics
# ---------------------------------------------------------------------------

def test_suppression_inline_and_next_line(tmp_path):
    pkg = tmp_path / "suppkg"
    pkg.mkdir()
    (pkg / "steps.py").write_text(textwrap.dedent("""\
        import jax
        import jax.numpy as jnp


        @jax.jit
        def step(x):
            a = int(jnp.sum(x))  # mce-lint: disable=R4 -- test: inline form
            # mce-lint: disable=R4 -- test: next-line form
            b = int(jnp.sum(x))
            c = int(jnp.sum(x))
            return a + b + c
        """))
    active, suppressed, s1, _ = analyze(str(pkg))
    assert len(suppressed) == 2
    assert [f.line for f in active] == [10]        # the unsuppressed int()
    assert s1 == []


def test_suppression_file_level_and_s1(tmp_path):
    pkg = tmp_path / "suppkg"
    pkg.mkdir()
    (pkg / "steps.py").write_text(textwrap.dedent("""\
        # mce-lint: disable-file=R4
        import jax
        import jax.numpy as jnp


        @jax.jit
        def step(x):
            return int(jnp.sum(x))
        """))
    active, suppressed, s1, _ = analyze(str(pkg))
    assert active == [] and len(suppressed) == 1
    # no justification on the disable-file comment -> S1 under --strict
    assert len(s1) == 1 and s1[0].rule == "S1" and s1[0].line == 1


def test_suppression_requires_matching_rule(tmp_path):
    pkg = tmp_path / "suppkg"
    pkg.mkdir()
    (pkg / "steps.py").write_text(textwrap.dedent("""\
        import jax
        import jax.numpy as jnp


        @jax.jit
        def step(x):
            return int(jnp.sum(x))  # mce-lint: disable=R2 -- wrong rule
        """))
    active, suppressed, _s1, _ = analyze(str(pkg))
    assert len(active) == 1 and active[0].rule == "R4"
    assert suppressed == []


def test_suppression_parser_grammar():
    table = Suppressions(
        "x = 1  # mce-lint: disable=R1,R4 -- two rules, one comment\n")
    sup = table.match("R4", 1)
    assert sup is not None and sup.rules == ("R1", "R4")
    assert sup.justification == "two rules, one comment"
    assert table.match("R2", 1) is None


# ---------------------------------------------------------------------------
# the real tree + CLI
# ---------------------------------------------------------------------------

def test_src_repro_is_lint_clean_in_strict_mode():
    """The same gate CI enforces: zero active findings, every suppression
    justified. The suppressed count is >0 — the analyzer did find the
    real grid-gated kernel epilogues and they are documented, not ignored."""
    active, suppressed, s1, n = analyze(SRC)
    assert n >= 90                                  # the whole package
    assert active == [], "\n".join(f.format() for f in active)
    assert s1 == [], "\n".join(f.format() for f in s1)
    assert len(suppressed) >= 3                     # real R2 findings exist


def test_cli_exit_codes_and_report(tmp_path):
    report = tmp_path / "lint_report.json"
    rc = main([os.path.join(FIXTURES, "bad_r2"), "--report", str(report),
               "--format", "json"])
    assert rc == 1
    data = json.loads(report.read_text())
    assert data["counts"]["active"] == 2
    assert {f["rule"] for f in data["findings"]} == {"R2"}

    rc = main([os.path.join(FIXTURES, "good_r2"), "--strict"])
    assert rc == 0

    rc = main([os.path.join(FIXTURES, "does_not_exist")])
    assert rc == 2


def test_cli_rules_subset():
    rc = main([os.path.join(FIXTURES, "bad_r3"), "--rules", "R2"])
    assert rc == 0                                  # R3 findings filtered out
    rc = main([os.path.join(FIXTURES, "bad_r3"), "--rules", "R3"])
    assert rc == 1


def test_module_entry_point_runs_without_jax_imported():
    """`python -m repro.analysis` must work in a jax-less environment:
    the CI lint job runs it bare. Guard: the analysis package never
    imports jax (directly or transitively)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    code = ("import sys; import repro.analysis; "
            "sys.exit(1 if any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules) else 0)")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_package_index_resolves_reexports():
    index = PackageIndex.build(SRC)
    resolved = index.resolve_symbol("repro.core.engine.run_root")
    assert resolved is not None
    mod, node = resolved
    assert mod.name == "repro.core.engine.loop"
    assert getattr(node, "name", None) == "run_root"
