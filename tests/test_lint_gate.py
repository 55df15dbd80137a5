"""The tier-1 lint gate: `python -m fedtpu.cli lint fedtpu/ tests/`.

One in-process invocation of the real CLI entry point over the whole
repo, so a new lint finding (or an unjustified suppression regression)
fails the ordinary test suite without any extra CI infrastructure.
Marker-free by design — this rides in the default `-m 'not slow'` flow.

The linter is pure AST (no jax, no backend). The per-file rules cost
well under a second over the whole tree; the interprocedural pass
(FTP011/FTP012/FTP013 over the module call graphs) is budgeted below so
it can never silently blow tier-1 up.
"""

import os
import time

from fedtpu.cli import main as cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Whole-repo wall-time ceiling for one full lint pass (every rule,
# including the interprocedural concurrency/determinism pass). CI CPUs
# are slow; the pass takes ~2 s on a laptop — 30 s is the point where
# something superlinear has crept into the call-graph flow.
ANALYSIS_BUDGET_S = 30.0


def test_repo_lint_gate_is_clean(capsys):
    t0 = time.perf_counter()
    rc = cli_main(["lint",
                   os.path.join(REPO, "fedtpu"),
                   os.path.join(REPO, "tests")])
    elapsed = time.perf_counter() - t0
    out = capsys.readouterr().out
    assert rc == 0, f"fedtpu lint found regressions:\n{out}"
    # The gate really walked the tree (guards against a silently-empty
    # path list reporting a vacuous pass).
    assert "0 findings" in out
    files = int(out.rsplit(",", 1)[1].split()[0])
    assert files > 50, f"lint gate only saw {files} files"
    assert elapsed < ANALYSIS_BUDGET_S, (
        f"whole-repo analysis took {elapsed:.1f}s — the interprocedural "
        f"pass must stay under {ANALYSIS_BUDGET_S:.0f}s on CPU")


def test_concurrency_determinism_pass_gates_repo_wide(capsys):
    """The interprocedural rules alone, explicitly selected: the repo is
    clean under FTP011/FTP012/FTP013 (only justified noqa survive), and
    the selection really ran the checkers over the package."""
    rc = cli_main(["lint", "--select", "FTP011,FTP012,FTP013",
                   "--show-suppressed",
                   os.path.join(REPO, "fedtpu"),
                   os.path.join(REPO, "tests")])
    out = capsys.readouterr().out
    assert rc == 0, f"concurrency/determinism regressions:\n{out}"
    assert "0 findings" in out
    # The known justified suppression (cohort restore writes _state
    # before any prefetch is in flight) is visible — proof the pass
    # actually analyzed the threaded subsystems rather than no-opping.
    assert "scheduler.py" in out and "[suppressed]" in out


def test_suppressions_carry_justifications():
    """Every `# fedtpu: noqa[...]` in the repo must say WHY: bare
    suppressions (nothing after the closing bracket) are banned."""
    import re

    pat = re.compile(r"#\s*fedtpu:\s*noqa\[[A-Z0-9,\s]+\](.*)")
    offenders = []
    for base in ("fedtpu", "tests"):
        for dirpath, _, files in os.walk(os.path.join(REPO, base)):
            if "__pycache__" in dirpath:
                continue
            for fn in sorted(files):
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                for i, line in enumerate(open(path, encoding="utf-8"), 1):
                    m = pat.search(line)
                    if m and not m.group(1).strip():
                        offenders.append(f"{os.path.relpath(path, REPO)}:{i}")
    assert not offenders, (
        f"noqa without an inline justification: {offenders}")
