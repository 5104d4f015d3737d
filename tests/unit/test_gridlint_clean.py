"""Tier-1 gate: the full gridlint suite over ``pygrid_tpu/`` is clean.

This is the mechanical enforcement the checkers exist for: any
non-baselined finding (or a stale baseline entry — allowances must
ratchet DOWN as code heals) fails the build. The suite must stay cheap
enough that nobody is tempted to skip it: what keeps it so is that the
whole-program graph is built once, and that count is asserted (a
wall-clock budget was, until PR 34: it failed under load, not under
faults).
"""

from __future__ import annotations

from pathlib import Path

from pygrid_tpu.analysis import run_checks

REPO_ROOT = Path(__file__).resolve().parents[2]


def test_gridlint_suite_is_clean_and_fast():
    from pygrid_tpu.analysis.checkers import ALL_CHECKERS
    from pygrid_tpu.analysis.graph import ProgramGraph

    # the default suite must include the protocol family — a clean run
    # that silently dropped GL7 would prove nothing about the wire
    assert any(c.name == "GL7" for c in ALL_CHECKERS)

    builds_before = ProgramGraph.builds
    result = run_checks([str(REPO_ROOT / "pygrid_tpu")])

    assert result.parse_errors == [], result.parse_errors
    assert result.failures == [], "\n".join(
        f.render() for f in result.failures
    )
    # stale allowances mask future regressions — shrink baseline.json
    assert result.stale_baseline == [], "\n".join(result.stale_baseline)
    assert result.files_checked > 100  # the walk actually saw the tree
    # the whole-program pass (symbol table + call graph + domains) must
    # be built ONCE and shared by every checker — per-checker rebuilds
    # are what would blow the wall-clock budget as checkers multiply.
    # That count IS the speed check: a stopwatch here (10 s around ~8.3)
    # failed under the suite's six workers with no fault in the code
    assert ProgramGraph.builds - builds_before == 1


def test_gridlint_cli_entrypoint_is_clean():
    """`python -m pygrid_tpu.analysis pygrid_tpu/` exits 0 on the final
    tree — the same invocation scripts/gridlint.sh ships."""
    from pygrid_tpu.analysis.cli import main

    assert (
        main([str(REPO_ROOT / "pygrid_tpu"), "--strict-baseline", "-q"]) == 0
    )
