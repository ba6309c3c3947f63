"""The geomesa-lint suite is a tier-1 invariant (docs/analysis.md).

Three layers:

- **the tree is clean**: every shipped rule over geomesa_tpu/ +
  scripts/ + docs/*.md yields zero findings WITHOUT baseline help, and
  the checked-in baseline is empty (violations get fixed, not
  suppressed) — this is what makes the analyzer a ratchet;
- **the rules have teeth**: per-rule known-bad/known-good fixtures
  (tests/fixtures/analysis/) replay the defects that motivated each
  family — the PR 5 fused E-bucket grouping-key bug, the pre-PR 3
  unlocked MetricsRegistry mutation, an annotated scheduler queue
  mutated outside its condition — and each must be caught;
- **the gate convention**: scripts/check.py exits 0 clean, 1 findings,
  2 unusable input.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import pytest

from geomesa_tpu import analysis
from geomesa_tpu.analysis.core import (
    Project,
    default_baseline_path,
    load_baseline,
    run_rules,
)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
FIXDIR = "tests/fixtures/analysis"


def _render(findings):
    return "\n".join(f.render() for f in findings)


# scope-sensitive fixtures are staged under SYNTHETIC in-scope paths
# (Project.add_file with text=) so the shipped rule scopes stay
# production-only — the kernel rules scan geomesa_tpu/scan|curve/, the
# lock-inference rule scans serving/cache/ingest/metrics
_SYNTHETIC_PATHS = {
    "kernel_bad.py": "geomesa_tpu/scan/_fixture_kernel_bad.py",
    "locks_bad_registry.py": "geomesa_tpu/serving/_fixture_locks_bad_registry.py",
    # the unregistered-lock direction needs an ENFORCED scope (the
    # concurrent tiers require a LOCKS registry entry)
    "race_bad_unregistered.py": "geomesa_tpu/streaming/_fixture_race_unregistered.py",
}


def _fixture_path(name: str) -> str:
    return _SYNTHETIC_PATHS.get(name, f"{FIXDIR}/{name}")


@pytest.fixture(scope="module")
def fixture_result():
    """One analysis run over the repo PLUS every rule fixture."""
    project = Project.load(ROOT)
    for fn in sorted(os.listdir(os.path.join(ROOT, FIXDIR))):
        if fn.endswith(".py"):
            src = open(os.path.join(ROOT, FIXDIR, fn)).read()
            project.add_file(_fixture_path(fn), text=src)
    return run_rules(project, analysis.ALL_RULES, baseline=set())


def _at(result, path, rule=None):
    return [
        f for f in result.findings
        if f.path == _fixture_path(path)
        and (rule is None or f.rule_id == rule)
    ]


# -- layer 1: the tree is clean ------------------------------------------


def test_repo_is_lint_clean_and_fast():
    t0 = time.process_time()
    result = analysis.run(ROOT, baseline=set())  # no suppression help
    dt = time.process_time() - t0
    assert result.clean, f"new lint findings:\n{_render(result.findings)}"
    # acceptance bound: a full-repo run fits CI comfortably. Held on the
    # process's CPU clock (the analysis runs on one thread), and on the
    # better of two runs: beside five other xdist workers the wall clock,
    # and a first run's CPU time, say how busy the host is, not how slow
    # the analysis (7 s alone, over 10 s of wall beside them)
    if dt >= 10.0:
        t0 = time.process_time()
        analysis.run(ROOT, baseline=set())
        dt = min(dt, time.process_time() - t0)
    assert dt < 10.0, f"analysis took {dt:.1f}s of CPU (budget 10s)"


def test_checked_in_baseline_is_empty():
    keys = load_baseline(default_baseline_path(ROOT))
    assert keys == set(), (
        "the shipped suppression baseline must stay empty — fix "
        f"violations instead of suppressing: {sorted(keys)}"
    )


def test_rule_ids_unique_and_well_formed():
    ids = [r.id for r in analysis.ALL_RULES]
    assert len(ids) == len(set(ids)), ids
    for r in analysis.ALL_RULES:
        assert r.id and r.id == r.id.lower() and " " not in r.id, r.id
        assert r.description, r.id
        assert r.fix_hint, r.id


# -- layer 2: the rules have teeth (fixtures) ----------------------------


def test_pr5_e_bucket_grouping_key_bug_is_caught(fixture_result):
    bad = _at(fixture_result, "fused_bad_pr5.py", "fused-key-dimension")
    assert len(bad) == 1, _render(bad)
    assert "fused_e_bucket" in bad[0].message
    assert "scan_submit_many" in bad[0].message
    # the hardened key is silent
    assert _at(fixture_result, "fused_good.py") == []


def test_fold_side_bucket_ladder_is_caught(fixture_result):
    """Round 11: the rule pattern widened to fold_<dim>_bucket — a
    future fold-operand ladder omitted from the grouping key is the
    same defect class as the PR 5 E-bucket bug."""
    bad = _at(fixture_result, "fold_bad_ladder.py", "fused-key-dimension")
    assert len(bad) == 1, _render(bad)
    assert "fold_s_bucket" in bad[0].message


def test_unlocked_metrics_registry_mutation_is_caught(fixture_result):
    bad = _at(fixture_result, "locks_bad_registry.py", "lock-guarded-mutation")
    assert len(bad) == 1, _render(bad)
    assert "counters" in bad[0].message
    assert "counter()" in bad[0].message
    assert "inferred" in bad[0].message  # inference mode, no annotation


def test_inherited_lock_annotation_still_enforced(fixture_result):
    """A guarded-by annotation is enforced even when the lock lives in
    a base class (no lock assignment visible in the annotated class)."""
    bad = _at(
        fixture_result, "locks_bad_inherited.py", "lock-guarded-mutation"
    )
    assert len(bad) == 1, _render(bad)
    assert "_items" in bad[0].message and "add" in bad[0].message
    # and no bad-annotation noise for the undetectable inherited lock
    assert all("annotation" not in f.symbol for f in bad)


def test_scheduler_guarded_by_mutation_is_caught(fixture_result):
    bad = _at(
        fixture_result, "locks_bad_scheduler.py", "lock-guarded-mutation"
    )
    assert len(bad) == 1, _render(bad)
    assert "_queue" in bad[0].message and "submit" in bad[0].message
    assert "guarded-by" in bad[0].message  # explicit-annotation mode
    # the disciplined twin (with *_locked and holds-lock escapes) passes
    assert _at(fixture_result, "locks_good.py") == []


def test_lock_order_cycle_is_caught(fixture_result):
    """geomesa-race: the A->B / B->A inversion is a cycle finding plus
    a rank violation on the inverted edge."""
    bad = _at(fixture_result, "race_bad_order.py", "lock-order-cycle")
    cycles = [f for f in bad if f.symbol.startswith("cycle:")]
    ranks = [f for f in bad if f.symbol.startswith("rank:")]
    assert len(cycles) == 1, _render(bad)
    assert "RaceyLedger._hot_lock" in cycles[0].message
    assert "RaceyLedger._audit_lock" in cycles[0].message
    assert "deadlock" in cycles[0].message
    assert len(ranks) == 1, _render(bad)
    assert "rank 19" in ranks[0].message and "rank 11" in ranks[0].message


def test_unregistered_concurrent_tier_lock_is_caught(fixture_result):
    """A lock constructed in an enforced scope (the concurrent tiers)
    without a LOCKS registry entry has no declared rank — the finding
    class the production registry in analysis/lockmodel.py closed."""
    bad = _at(
        fixture_result, "race_bad_unregistered.py", "lock-order-cycle"
    )
    assert len(bad) == 1, _render(bad)
    assert "UnrankedBuffer._buf_lock" in bad[0].message
    assert "no LOCKS registry entry" in bad[0].message


def test_pr9_checkpoint_cover_race_is_caught(fixture_result):
    """The PR 9 checkpoint-cover-before-drain race replays as a
    must-fail fixture (the E-bucket convention): the stale pending-set
    write-back is a check-then-act finding."""
    bad = _at(
        fixture_result, "race_bad_pr9_checkpoint.py",
        "atomicity-check-then-act",
    )
    assert len(bad) == 1, _render(bad)
    assert "_pending" in bad[0].message
    assert "checkpoint" in bad[0].message
    assert "without re-reading" in bad[0].message


def test_pr11_take_staged_race_is_caught(fixture_result):
    """The PR 11 _take_staged write-back race replays the same way:
    filtered-snapshot write-back without re-reading the staged list."""
    bad = _at(
        fixture_result, "race_bad_pr11_takestaged.py",
        "atomicity-check-then-act",
    )
    assert len(bad) == 1, _render(bad)
    assert "_staged" in bad[0].message and "take" in bad[0].message


def test_blocking_under_hot_lock_is_caught(fixture_result):
    """fsync + Future.result under an inline-annotated hot lock are the
    PR 8 reader-stall class (and the WAL _rotate fix this PR shipped)."""
    bad = _at(fixture_result, "race_bad_blocking.py", "blocking-under-lock")
    assert len(bad) == 2, _render(bad)
    kinds = {f.message.split(" call ")[0] for f in bad}
    assert kinds == {"fsync", "Future.result"}, kinds
    for f in bad:
        assert "HotTier._lock" in f.message


def test_guarded_escape_is_caught(fixture_result):
    """A guarded container returned bare / stored into an unguarded
    attribute is the adopted-row-dict aliasing class; copies and
    swap-and-drain stay legal."""
    bad = _at(fixture_result, "race_bad_escape.py", "guarded-escape")
    assert len(bad) == 2, _render(bad)
    symbols = {f.symbol for f in bad}
    assert symbols == {
        "LeakyCache.rows._rows:return", "LeakyCache.publish._rows:store",
    }, symbols


def test_race_good_twin_is_silent(fixture_result):
    """The disciplined twin exercises every rule's good path: rank-
    increasing order, one-hold check-then-act, blocking outside the
    lock, copy/swap escapes — zero geomesa-race findings."""
    for rule in ("lock-order-cycle", "atomicity-check-then-act",
                 "blocking-under-lock", "guarded-escape"):
        assert _at(fixture_result, "race_good.py", rule) == [], rule


def test_lock_registry_hygiene():
    """LOCKS registry invariants: Class.attr names, unique strictly
    ordered ranks... (rank ties would make the order a partial one),
    every entry discovered in the tree with a matching witness name,
    and every declared edge rank-increasing."""
    from geomesa_tpu.analysis.core import Project
    from geomesa_tpu.analysis.lockmodel import (
        DECLARED_EDGES, LOCKS, LockModel,
    )

    assert len(LOCKS) >= 12
    ranks = [d.rank for d in LOCKS.values()]
    assert len(ranks) == len(set(ranks)), "ranks must be unique"
    for name, d in LOCKS.items():
        assert name == d.name and "." in name, name
        assert d.doc, name
    model = LockModel.of(Project.load(ROOT))
    for name in LOCKS:
        assert name in model.sites, f"{name} has no construction site"
        assert model.sites[name].witness_name == name, name
    for a, b, why in DECLARED_EDGES:
        assert a in LOCKS and b in LOCKS, (a, b)
        assert LOCKS[a].rank < LOCKS[b].rank, (a, b)
        assert why, (a, b)


def test_static_model_edges_are_rank_consistent():
    """The production acquisition graph (AST-derived + declared) is
    acyclic and every ranked edge strictly increases — the invariant
    the lock-order-cycle rule enforces at zero findings."""
    from geomesa_tpu.analysis.core import Project
    from geomesa_tpu.analysis.lockmodel import LockModel

    model = LockModel.of(Project.load(ROOT))
    assert model.cycles() == []
    # the model must actually SEE the load-bearing nesting, not be
    # vacuously clean
    edges = model.predicted_edges()
    assert ("WriteAheadLog._sync_lock", "WriteAheadLog._lock") in edges
    assert (
        "StreamingFeatureCache._lock", "GenerationTracker._lock"
    ) in edges
    assert ("ResultCache._lock", "GenerationTracker._lock") in edges
    for a, b in edges:
        ra, rb = model.rank_of(a), model.rank_of(b)
        if ra is not None and rb is not None:
            assert ra < rb, (a, b)


def test_undeclared_knob_literal_is_caught(fixture_result):
    bad = _at(fixture_result, "knob_bad.py", "knob-undeclared")
    assert len(bad) == 1, _render(bad)
    assert "geomesa.scan.rangs.target" in bad[0].message  # the typo
    # the correctly spelled neighbor resolved against conf.py


def test_metric_convention_and_type_conflict_are_caught(fixture_result):
    conv = _at(fixture_result, "metric_bad.py", "metric-convention")
    assert len(conv) == 1 and "geomesa.Fixture-Area.hits" in conv[0].message
    dup = _at(fixture_result, "metric_bad.py", "metric-type-conflict")
    assert len(dup) == 1 and "geomesa.fixture.depth" in dup[0].message
    assert "counter" in dup[0].message and "gauge" in dup[0].message


def test_unregistered_histogram_is_caught(fixture_result):
    """ISSUE 13 must-fail: the observe()/histogram_quantile() instrument
    methods are registry extraction sites, so a histogram outside the
    naming registry fails metric-convention (both the write AND read
    sites), and a histogram/counter name collision fails
    metric-type-conflict."""
    conv = _at(fixture_result, "hist_bad.py", "metric-convention")
    assert len(conv) == 2, _render(conv)  # observe + histogram_quantile
    assert all("geomesa.Fixture-Hist.latency" in f.message for f in conv)
    dup = _at(fixture_result, "hist_bad.py", "metric-type-conflict")
    assert len(dup) == 1 and "geomesa.fixture.wait" in dup[0].message
    assert "histogram" in dup[0].message and "counter" in dup[0].message


def test_kernel_purity_hazards_are_caught(fixture_result):
    coerce = _at(fixture_result, "kernel_bad.py", "kernel-traced-coercion")
    # float(x) only: neither int(n_pad) (tuple static form) nor the
    # scalar-string static_argnames twin may be flagged
    assert len(coerce) == 1, _render(coerce)
    assert "float()" in coerce[0].message and "'x'" in coerce[0].message
    assert "bad_kernel" in coerce[0].message
    shape = _at(fixture_result, "kernel_bad.py", "kernel-dynamic-shape")
    assert len(shape) == 1 and "nonzero" in shape[0].message
    # baseline keys stay line-free (the suppression-stability contract)
    for f in coerce + shape:
        assert str(f.line) not in f.key, f.key


def test_unregistered_fault_point_is_caught(fixture_result):
    """A typo'd fault-point literal (the vacuous-crash-test failure
    mode) is flagged; registered points and non-literal names pass."""
    bad = _at(fixture_result, "fault_bad.py", "fault-point-unknown")
    assert len(bad) == 1, _render(bad)
    assert "streem.wal.append" in bad[0].message
    assert _at(fixture_result, "fault_good.py") == []


def test_fault_point_registry_matches_kinds():
    """Registry hygiene: FAULT_POINTS names are dotted, lowercase, and
    every one resolves to a real code site in the clean-tree run (the
    unreached/unexercised directions of the rule)."""
    from geomesa_tpu.analysis.registries import FAULT_POINTS

    assert len(FAULT_POINTS) >= 25
    for name, doc in FAULT_POINTS.items():
        assert "." in name and name == name.lower() and " " not in name, name
        assert doc, name


def test_fstring_family_reported_once(fixture_result):
    """An f-string fragment is scanned exactly once: the JoinedStr
    branch owns it, the plain-Constant walk must skip it (the
    duplicate-findings regression)."""
    bad = _at(fixture_result, "knob_fstring.py", "knob-undeclared")
    assert len(bad) == 1, _render(bad)
    assert "geomesa.bogus" in bad[0].message


def test_warmup_ladder_gap_is_caught(fixture_result):
    bad = _at(fixture_result, "warmup_bad.py", "warmup-coverage")
    assert len(bad) == 1, _render(bad)  # R missing, E covered
    assert "FUSED_R_BUCKETS" in bad[0].message


# -- suppression machinery ------------------------------------------------


def test_baseline_and_inline_suppression(tmp_path):
    project = Project.load(ROOT)
    project.add_file(f"{FIXDIR}/knob_bad.py")
    rules = [r for r in analysis.ALL_RULES if r.id == "knob-undeclared"]
    result = run_rules(project, rules, baseline=set())
    bad = [f for f in result.findings if f.path.endswith("knob_bad.py")]
    assert len(bad) == 1
    # baselining the key suppresses it (and survives line drift: the key
    # carries the offending symbol, not the line number)
    assert str(bad[0].line) not in bad[0].key
    baselined = run_rules(project, rules, baseline={bad[0].key})
    assert not [
        f for f in baselined.findings if f.path.endswith("knob_bad.py")
    ]
    assert [
        f for f in baselined.suppressed if f.path.endswith("knob_bad.py")
    ]
    # inline `# lint: ignore[rule-id]` on the flagged line also works
    src = open(os.path.join(ROOT, FIXDIR, "knob_bad.py")).read()
    lines = src.splitlines()
    lines[bad[0].line - 1] += "  # lint: ignore[knob-undeclared]"
    alt = tmp_path / "knob_bad_suppressed.py"
    alt.write_text("\n".join(lines) + "\n")
    p2 = Project(str(tmp_path))
    p2.add_file("knob_bad_suppressed.py")
    r2 = run_rules(p2, rules, baseline=set())
    assert not r2.findings and r2.suppressed


@pytest.fixture(scope="module")
def repo_project():
    return Project.load(ROOT)


@pytest.mark.parametrize(
    "layer", ["scan", "curve", "native", "index", "filter", "utils", "stats", "obs"]
)
def test_scan_layer_imports_nothing_above_it(repo_project, layer):
    """The kernels, the curves, the native tier, the indexes, the
    filters, the utilities, the sketches and the instruments are the
    bottom of the program: none of their modules imports an entry
    point (datastore, cli) or the serving, planning, pod, streaming,
    sql, process, tiles or cache tier, at module level or inside a
    function."""
    import ast

    above = {
        "serving", "planning", "pod", "streaming", "datastore", "sql",
        "process", "cli", "tiles", "cache",
    }
    found = []
    for sf in repo_project.python_files(under=f"geomesa_tpu/{layer}/"):
        pkg = sf.relpath.split("/")[:-1]  # the module's package
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                stem = pkg[: len(pkg) - node.level + 1] if node.level else []
                mod = ".".join(stem + ([node.module] if node.module else []))
                # ``from geomesa_tpu import serving`` names it too
                mods = [mod] + [f"{mod}.{a.name}" for a in node.names]
            else:
                continue
            found += [
                f"{sf.relpath}:{node.lineno}: {mod}" for mod in mods
                if mod.startswith("geomesa_tpu.")
                and mod.split(".")[1] in above
            ]
    assert not found, "\n".join(found)


# -- layer 3: the gate's exit-code convention ------------------------


class TestCheckGateExitCodes:
    """scripts/check.py exits 0 clean, 1 findings, 2 unusable input."""

    def _run(self, *args):
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "check.py"), *args],
            capture_output=True, text=True, timeout=120,
        )
        return proc

    def _mini_repo(self, tmp_path, body):
        root = tmp_path / "repo"
        (root / "geomesa_tpu").mkdir(parents=True)
        (root / "geomesa_tpu" / "mod.py").write_text(body)
        return str(root)

    def test_clean_tree_exits_zero(self, tmp_path):
        root = self._mini_repo(tmp_path, '"""A module."""\n\nX = 1\n')
        proc = self._run("--root", root, "--json")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        import json

        payload = json.loads(proc.stdout)
        assert payload["clean"] is True and payload["findings"] == []

    def test_findings_exit_one(self, tmp_path):
        root = self._mini_repo(
            tmp_path,
            '"""A module citing geomesa.not.a.knob anywhere."""\n',
        )
        proc = self._run("--root", root, "--json")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        import json

        payload = json.loads(proc.stdout)
        assert payload["findings"], payload
        assert payload["findings"][0]["rule"] == "knob-undeclared"

    def test_unusable_input_exits_two(self, tmp_path):
        assert self._run("--rules", "no-such-rule").returncode == 2
        assert self._run(
            "--root", str(tmp_path / "missing")
        ).returncode == 2
        assert self._run(
            "--baseline", str(tmp_path / "missing.txt")
        ).returncode == 2

    def test_write_baseline_bootstraps_then_suppresses(self, tmp_path):
        """The adopt-time workflow: --write-baseline CREATES a fresh
        baseline file, and a rerun against it exits 0."""
        root = self._mini_repo(
            tmp_path, '"""Cites geomesa.not.a.knob here."""\n'
        )
        bl = tmp_path / "bl" / "lint-baseline.txt"
        proc = self._run(
            "--root", root, "--write-baseline", "--baseline", str(bl)
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert bl.exists() and "knob-undeclared" in bl.read_text()
        rerun = self._run("--root", root, "--baseline", str(bl))
        assert rerun.returncode == 0, rerun.stdout + rerun.stderr
        # idempotent: a second write appends nothing (no duplicate keys)
        n_lines = len(bl.read_text().splitlines())
        again = self._run(
            "--root", root, "--write-baseline", "--baseline", str(bl)
        )
        assert again.returncode == 0
        assert len(bl.read_text().splitlines()) == n_lines

    def test_profile_table_and_json_schema_version(self, tmp_path):
        """--profile prints a per-rule wall-time table; --json carries
        the stable schema_version (the CI pinning contract)."""
        import json

        root = self._mini_repo(tmp_path, '"""A module."""\n\nX = 1\n')
        proc = self._run("--root", root, "--profile")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "knob-undeclared" in proc.stdout and " ms " in proc.stdout
        jproc = self._run("--root", root, "--profile", "--json")
        payload = json.loads(jproc.stdout)
        assert payload["schema_version"] == 1
        assert isinstance(payload["profile"], list) and payload["profile"]
        row = payload["profile"][0]
        assert set(row) == {"rule", "seconds", "raised"}
        plain = json.loads(self._run("--root", root, "--json").stdout)
        assert plain["schema_version"] == 1
        assert plain["changed_only"] is False

    def test_changed_scope(self, tmp_path):
        """--changed reports only findings in files the git work tree
        touched (rules still see the whole repo); a git-less root is
        unusable input (exit 2)."""
        import subprocess

        root = self._mini_repo(
            tmp_path, '"""Cites geomesa.not.a.knob here."""\n'
        )
        assert self._run("--root", root, "--changed").returncode == 2
        subprocess.run(["git", "init", "-q"], cwd=root, check=True)
        # untracked bad file: in scope -> finding survives the filter
        proc = self._run("--root", root, "--changed")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "knob-undeclared" in proc.stdout
        # committed clean tree: nothing changed -> findings filter away
        subprocess.run(["git", "add", "-A"], cwd=root, check=True)
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t",
             "commit", "-qm", "x"], cwd=root, check=True,
        )
        proc = self._run("--root", root, "--changed")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "(changed files only)" in proc.stdout

    def test_parse_error_is_baselinable(self, tmp_path):
        """Adopt-time convergence on trees carrying broken files: the
        parse-error finding goes through the baseline like any other."""
        root = self._mini_repo(tmp_path, "def broken(:\n")
        assert self._run("--root", root).returncode == 1
        bl = tmp_path / "bl.txt"
        assert self._run(
            "--root", root, "--write-baseline", "--baseline", str(bl)
        ).returncode == 0
        assert "parse-error" in bl.read_text()
        rerun = self._run("--root", root, "--baseline", str(bl))
        assert rerun.returncode == 0, rerun.stdout + rerun.stderr
