"""jaxlint: fixture-driven rule tests + the tier-1 regression gate.

The gate (test_tree_is_clean) runs the full pass over ``deepspeed_tpu/``
and fails on any non-baselined finding — the linter IS a permanent
regression gate, not an advisory tool.  Pure-stdlib: no jax import
needed, so these tests run even where jax is broken.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "jaxlint_fixtures")
sys.path.insert(0, REPO)

from tools.jaxlint import lint_paths, load_baseline          # noqa: E402
from tools.jaxlint.core import (default_baseline_path,       # noqa: E402
                                lint_file, lint_source, write_baseline)


def _rules(path):
    return sorted({f.rule for f in lint_file(path)})


def _fixture(name):
    return os.path.join(FIXTURES, name)


# ---------------------------------------------------------------------------
# per-rule positive/negative fixtures
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rule,bad,good", [
    ("JL001", "jl001_bad.py", "jl001_good.py"),
    ("JL002", "jl002_bad.py", "jl002_good.py"),
    ("JL003", "jl003_bad.py", "jl003_good.py"),
    ("JL004", "jl004_bad.py", "jl004_good.py"),
    ("JL005", "jl005_bad.py", "jl005_good.py"),
    ("JL006", "jl006_bad.py", "jl006_good.py"),
    ("JL007", "jl007_bad.py", "jl007_good.py"),
    ("JL008", "jl008_bad.py", "jl008_good.py"),
    ("JL009", "jl009_bad.py", "jl009_good.py"),
    ("JL010", "jl010_bad.py", "jl010_good.py"),
    ("JL101", os.path.join("jl101", "config_bad.py"),
     os.path.join("jl101", "config_good.py")),
])
def test_rule_fires_on_bad_and_not_on_good(rule, bad, good):
    assert rule in _rules(_fixture(bad)), \
        f"{rule} must fire on {bad}"
    assert rule not in _rules(_fixture(good)), \
        f"{rule} must stay silent on {good}"


def test_jl001_flags_every_sync_shape():
    lines = {f.line for f in lint_file(_fixture("jl001_bad.py"))
             if f.rule == "JL001"}
    # np.asarray, .item via helper, float via wrap-assign, self-method
    assert len(lines) == 4, lines


def test_jl002_alias_and_argname_forms():
    msgs = [f.message for f in lint_file(_fixture("jl002_bad.py"))
            if f.rule == "JL002"]
    assert len(msgs) == 3
    assert any("self.state" in m for m in msgs)   # attribute alias caught


def test_jl003_sibling_pinning_heuristic():
    findings = [f for f in lint_file(_fixture("jl003_bad.py"))
                if f.rule == "JL003"]
    assert len(findings) == 2
    assert any("in_shardings" in f.message for f in findings)
    assert any("sibling" in f.message for f in findings)


def test_jl004_all_side_effect_shapes():
    cats = [f.message for f in lint_file(_fixture("jl004_bad.py"))
            if f.rule == "JL004"]
    assert len(cats) == 4
    joined = "\n".join(cats)
    for needle in ("assignment to 'self.last_state'", "'print'",
                   "'.append'", "'global'"):
        assert needle in joined, (needle, joined)


def test_jl006_both_delta_shapes_and_sync_kinds():
    """Direct-call delta AND two-stored-reads delta fire; every sync
    shape in the good fixture (block_until_ready, np.asarray
    materialization, no-device-work) stays silent (covered by the
    parametrized good-file check; here: exactly the two bad lines)."""
    findings = [f for f in lint_file(_fixture("jl006_bad.py"))
                if f.rule == "JL006"]
    assert len(findings) == 2, [(f.line, f.message) for f in findings]
    msgs = "\n".join(f.message for f in findings)
    assert "ENQUEUE latency" in msgs
    assert "'compiled'" in msgs      # known-jitted callable detected
    assert "'step_fn'" in msgs       # compiled-step naming heuristic


def test_jl006_ignores_traced_bodies():
    """Clocks inside jit-traced code are JL005's finding, not JL006's."""
    src = (
        "import jax, time\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    t0 = time.time()\n"
        "    y = jax.numpy.sin(x)\n"
        "    return y, time.time() - t0\n")
    rules = {f.rule for f in lint_source(src, path="t.py")}
    assert "JL006" not in rules
    assert "JL005" in rules


def test_jl101_finding_kinds():
    msgs = "\n".join(f.message for f in
                     lint_file(_fixture(os.path.join("jl101",
                                                     "config_bad.py")))
                     if f.rule == "JL101")
    assert "unknown config key constant C.MISSING_KEY" in msgs
    assert "'raw_key' bypasses constants.py" in msgs
    assert "defaultless read of C.STEPS" in msgs
    assert "cross-wired" in msgs


# ---------------------------------------------------------------------------
# suppression + baseline machinery
# ---------------------------------------------------------------------------

def test_suppression_same_line_and_line_above():
    src = (
        "import jax, numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.asarray(x)  # jaxlint: disable=JL001\n"
        "@jax.jit\n"
        "def g(x):\n"
        "    # jaxlint: disable\n"
        "    return np.asarray(x)\n"
        "@jax.jit\n"
        "def h(x):\n"
        "    return np.asarray(x)  # jaxlint: disable=JL999\n"
    )
    findings = lint_source(src, path="t.py")
    # only h's survives: its comment disables a different rule
    assert [(f.rule, f.line) for f in findings] == [("JL001", 11)]


def test_baseline_roundtrip(tmp_path):
    src = "import jax, numpy as np\n@jax.jit\ndef f(x):\n    return np.asarray(x)\n"
    bad = tmp_path / "mod.py"
    bad.write_text(src)
    findings = lint_file(str(bad))
    assert findings
    bl_path = tmp_path / "baseline.json"
    write_baseline(findings, str(bl_path))
    baseline = load_baseline(str(bl_path))
    assert all(f.key() in baseline for f in findings)
    # baseline keys are line-number independent: shifting the file down
    # must not un-baseline the finding
    bad.write_text("# a new comment line\n" + src)
    shifted = lint_file(str(bad))
    assert shifted and all(f.key() in baseline for f in shifted)


def test_syntax_error_is_a_finding():
    findings = lint_source("def broken(:\n", path="b.py")
    assert [f.rule for f in findings] == ["JL000"]


def test_decorator_jit_call_registers_once():
    """@jax.jit(...) must not be double-registered by the plain-call walk
    (duplicate findings + a phantom non-decorator site that defeats
    JL003's sibling heuristic)."""
    src = ("import jax\n"
           "@jax.jit(in_shardings=(None,))\n"
           "def f(x):\n"
           "    return x\n")
    findings = lint_source(src, path="t.py")
    assert [(f.rule, f.line) for f in findings] == [("JL003", 2)]


def test_write_baseline_preserves_justifications(tmp_path):
    src = "import jax, numpy as np\n@jax.jit\ndef f(x):\n    return np.asarray(x)\n"
    bad = tmp_path / "mod.py"
    bad.write_text(src)
    findings = lint_file(str(bad))
    bl = tmp_path / "baseline.json"
    write_baseline(findings, str(bl))
    data = json.loads(bl.read_text())
    data["findings"][0]["why"] = "accepted: legacy module"
    bl.write_text(json.dumps(data))
    write_baseline(findings, str(bl))          # regenerate
    again = json.loads(bl.read_text())
    assert again["findings"][0]["why"] == "accepted: legacy module"


def test_nonexistent_path_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        lint_paths([str(tmp_path / "no_such_dir")])
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", "deepspeed_tpuu"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "no such file" in proc.stderr


# ---------------------------------------------------------------------------
# the tier-1 gate + CLI contract
# ---------------------------------------------------------------------------

def test_tree_is_clean():
    """The permanent regression gate: zero non-baselined findings over
    the whole package.  Fix new findings (or suppress inline with a
    justification; baseline only with a 'why' — docs/jaxlint.md)."""
    findings = lint_paths([os.path.join(REPO, "deepspeed_tpu")])
    baseline = load_baseline()
    rel = []
    for f in findings:
        key = f.key().replace(REPO + os.sep, "")
        if key not in baseline and f.key() not in baseline:
            rel.append(f.render())
    assert not rel, "new jaxlint findings:\n" + "\n".join(rel)


def test_baseline_entries_are_justified():
    """Every baselined finding must carry a non-empty 'why'."""
    path = default_baseline_path()
    with open(path) as fh:
        data = json.load(fh)
    for entry in data.get("findings", []):
        assert isinstance(entry, dict) and entry.get("why"), \
            f"baseline entry without justification: {entry}"


def test_cli_runs_clean_from_repo_root():
    """``python -m tools.jaxlint deepspeed_tpu/ --format=github`` is the
    CI entry point and must exit 0 on the current tree with no deps
    beyond the stdlib."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", "deepspeed_tpu",
         "--format=github"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_reports_findings_in_github_format(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text(
        "import jax, numpy as np\n@jax.jit\ndef f(x):\n"
        "    return np.asarray(x)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", str(bad),
         "--format=github", "--no-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1
    assert "::error file=" in proc.stdout
    assert "JL001" in proc.stdout


def test_jl007_exemption_is_runtime_stages_only():
    """The JL007 exemption matches the FULL package path suffix
    deepspeed_tpu/runtime/stages.py — a future serving/stages.py, a
    nested .../runtime/stages.py, or any other stages.py basename does
    NOT inherit the right to construct raw daemon threads."""
    src = ("import threading\n"
           "threading.Thread(target=print, daemon=True).start()\n")
    exempt = os.path.join("deepspeed_tpu", "runtime", "stages.py")
    assert not [f for f in lint_source(src, path=exempt)
                if f.rule == "JL007"]
    for path in (os.path.join("deepspeed_tpu", "serving", "stages.py"),
                 "stages.py",
                 os.path.join("deepspeed_tpu", "runtime", "other.py"),
                 os.path.join("deepspeed_tpu", "serving", "runtime",
                              "stages.py")):
        assert [f for f in lint_source(src, path=path)
                if f.rule == "JL007"], path


def test_cli_list_rules_covers_all_ids():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", "--list-rules"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    for rule_id in ("JL001", "JL002", "JL003", "JL004", "JL005", "JL006",
                    "JL007", "JL101"):
        assert rule_id in proc.stdout


def test_disk_offload_is_clean_with_empty_baseline():
    """The disk offload tier (runtime/disk_offload.py) is JL001-JL007
    clean WITHOUT any baseline entries — its bitwise-vs-host contract
    depends on the stage runtime's thread discipline (JL007) and on
    never timing a dispatch as a transfer (JL006), so no finding there
    may ever be baselined (the serving-subsystem rule, applied to the
    new module)."""
    findings = lint_paths([os.path.join(REPO, "deepspeed_tpu", "runtime",
                                        "disk_offload.py")])
    assert not findings, "\n".join(f.render() for f in findings)
    baseline = load_baseline()
    prefix = os.path.join("deepspeed_tpu", "runtime", "disk_offload.py")
    assert not [k for k in baseline if prefix in k]


def test_serving_subsystem_is_clean_with_empty_baseline():
    """The serving engine (deepspeed_tpu/inference/) is JL001-JL007
    clean WITHOUT any baseline entries — the one-compiled-decode-
    program contract (docs/serving.md) depends on staying JL005/JL006
    clean by construction, so no finding there may ever be baselined."""
    findings = lint_paths([os.path.join(REPO, "deepspeed_tpu",
                                        "inference")])
    assert not findings, "\n".join(f.render() for f in findings)
    baseline = load_baseline()
    inference_prefix = os.path.join("deepspeed_tpu", "inference")
    assert not [k for k in baseline if inference_prefix in k]


def test_kv_tier_is_clean_with_empty_baseline():
    """The KV tiering plane (inference/kv_tier.py) is JL001-JL007
    clean WITHOUT any baseline entries — its bitwise-resume contract
    (docs/serving.md "KV tiering") depends on the page export/import
    seams staying on the stage runtime's thread plane (JL007) and on
    the serving subsystem's JL005/JL006 discipline, so no finding
    there may ever be baselined."""
    findings = lint_paths([os.path.join(REPO, "deepspeed_tpu",
                                        "inference", "kv_tier.py")])
    assert not findings, "\n".join(f.render() for f in findings)
    baseline = load_baseline()
    prefix = os.path.join("deepspeed_tpu", "inference", "kv_tier.py")
    assert not [k for k in baseline if prefix in k]


def test_adapter_plane_is_clean_with_empty_baseline():
    """The multi-tenant adapter plane (inference/adapters.py) is
    JL001-JL007 clean WITHOUT any baseline entries — its zero-recompile
    contract (traced adapter-table indirection, docs/serving.md
    "multi-tenant serving") depends on the same JL005/JL006 discipline
    as the rest of the serving subsystem, and its host->HBM fetch must
    stay on the stage runtime's thread plane (JL007), so no finding
    there may ever be baselined."""
    findings = lint_paths([os.path.join(REPO, "deepspeed_tpu",
                                        "inference", "adapters.py")])
    assert not findings, "\n".join(f.render() for f in findings)
    baseline = load_baseline()
    prefix = os.path.join("deepspeed_tpu", "inference", "adapters.py")
    assert not [k for k in baseline if prefix in k]


# ---------------------------------------------------------------------------
# v2: interprocedural rules + the cross-artifact contract registry
# ---------------------------------------------------------------------------

CONTRACTS = os.path.join(FIXTURES, "contracts")


def test_jl008_flags_both_per_file_shapes():
    """Blocking put outside the worker closure AND the Thread
    assignment alias, in one fixture."""
    findings = [f for f in lint_file(_fixture("jl008_bad.py"))
                if f.rule == "JL008"]
    assert len(findings) == 2, [(f.line, f.message) for f in findings]
    msgs = "\n".join(f.message for f in findings)
    assert "blocking Channel.put" in msgs
    assert "assignment alias" in msgs


def test_jl009_names_the_reader_method():
    [f] = [f for f in lint_file(_fixture("jl009_bad.py"))
           if f.rule == "JL009"]
    assert "self.params" in f.message
    assert "snapshot()" in f.message


def test_jl010_anchors_at_the_dead_rebinding():
    [f] = [f for f in lint_file(_fixture("jl010_bad.py"))
           if f.rule == "JL010"]
    assert "scaled_loss" in f.message
    assert "scale = scale + 0.01" in f.line_text.strip()


def test_contracts_good_project_is_clean():
    """The good mini-project satisfies every cross-artifact contract:
    full v2 lint (per-file + project rules) reports nothing."""
    findings = lint_paths([os.path.join(CONTRACTS, "good")])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_contracts_bad_project_catches_every_violation_class():
    findings = lint_paths([os.path.join(CONTRACTS, "bad")])
    msgs = [f"{f.rule} {f.message}" for f in findings]
    expected = [
        ("JL008", "Stage('mystery') is not in the stage registry"),
        ("JL102", "metric 'fixture_orphan_total' is emitted without HELP"),
        ("JL102", "'fixture_orphan_total' is emitted here but consumed"),
        ("JL102", "sync scalar 'fixture_dead_s' is emitted here but"),
        ("JL102", "'fixture_ghost_s' is read here but no engine"),
        ("JL102", "documented metric 'fixture_phantom_total' does not"),
        ("JL103", "`loader`:`vanished` does not exist in code"),
        ("JL103", "('writer', 'flush') is live here but missing"),
        ("JL103", "fence token 'ghost' is not a StageGraph.register"),
        ("JL104", "'ORPHAN_DEFAULT' has no matching key constant"),
        ("JL104", "'TIMEOUT_DEFAULT' is never referenced outside"),
        ("JL104", "config key constant 'DEAD_KEY'"),
    ]
    for rule, needle in expected:
        assert any(m.startswith(rule) and needle in m for m in msgs), \
            f"missing: {rule} ...{needle}...\ngot:\n" + "\n".join(msgs)
    assert len(findings) == len(expected), "\n".join(msgs)


def test_contract_findings_are_suppressible_inline(tmp_path):
    """Inline '# jaxlint: disable=JL10x' works for project-level
    findings exactly like per-file ones (same definition)."""
    import shutil
    proj = tmp_path / "proj"
    shutil.copytree(os.path.join(CONTRACTS, "bad"), proj)
    tel = proj / "deepspeed_tpu" / "telemetry.py"
    src = tel.read_text()
    src = src.replace(
        '        self.ticks = reg.counter("fixture_orphan_total")',
        '        # jaxlint: disable=JL102\n'
        '        self.ticks = reg.counter("fixture_orphan_total")')
    tel.write_text(src)
    findings = lint_paths([str(proj)])
    assert not [f for f in findings
                if "fixture_orphan_total" in f.message], \
        "\n".join(f.render() for f in findings)


def _good_project(tmp_path):
    import shutil
    proj = tmp_path / "proj"
    shutil.copytree(os.path.join(CONTRACTS, "good"), proj)
    return proj


def test_benchmark_dir_is_not_package_code(tmp_path):
    """Emissions are collected from ``deepspeed_tpu/`` alone: a
    benchmark that counts something of its own (no HELP, no consumer)
    defines no contract and gives no finding."""
    proj = _good_project(tmp_path)
    (proj / "benchmark").mkdir()
    (proj / "benchmark" / "x.py").write_text(
        "def run(reg, scalars):\n"
        "    reg.counter('bench_only_total').inc()\n"
        "    scalars['bench_only_s'] = 0.0\n")
    findings = lint_paths([str(proj)])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_gitignored_copy_of_the_tree_is_not_the_project(tmp_path):
    """A whole copy of the project unpacked under a directory the
    root's ``.gitignore`` names (the verify skill's ``bench_trace/``)
    neither doubles the emissions nor joins the consumer corpus."""
    import shutil
    from tools.jaxlint.registry import ProjectRegistry
    proj = _good_project(tmp_path)
    before = ProjectRegistry.build(str(proj)).dump()
    shutil.copytree(os.path.join(CONTRACTS, "good"), proj / "bench_trace")
    (proj / ".gitignore").write_text("# run outputs\nbench_trace/\n*.log\n")
    reg = ProjectRegistry.build(str(proj))
    assert reg.dump() == before
    assert not [rp for rp in reg.sources if rp.startswith("bench_trace")]
    from tools.jaxlint.contracts import run_project_rules
    findings = run_project_rules(reg)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_registry_dump_matches_golden():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", "--registry-dump",
         os.path.join(CONTRACTS, "good")],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    dump = json.loads(proc.stdout)
    assert dump.pop("root").endswith(os.path.join("contracts", "good"))
    with open(os.path.join(CONTRACTS, "good_registry.json")) as f:
        golden = json.load(f)
    assert dump == golden


def test_registry_dump_without_root_is_usage_error(tmp_path):
    (tmp_path / "m.py").write_text("x = 1\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", "--registry-dump",
         str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "no project root" in proc.stderr


def test_missing_baseline_is_typed_error(tmp_path):
    from tools.jaxlint.core import BaselineError
    missing = tmp_path / "nope.json"
    with pytest.raises(BaselineError) as ei:
        load_baseline(str(missing))
    assert str(missing) in str(ei.value)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint",
         os.path.join("deepspeed_tpu", "telemetry"),
         "--baseline", str(missing)],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert str(missing) in proc.stderr


def test_corrupt_baseline_is_typed_error(tmp_path):
    from tools.jaxlint.core import BaselineError
    bad = tmp_path / "corrupt.json"
    bad.write_text("{not json")
    with pytest.raises(BaselineError) as ei:
        load_baseline(str(bad))
    assert str(bad) in str(ei.value)
    bad.write_text(json.dumps({"findings": "wrong-shape"}))
    with pytest.raises(BaselineError):
        load_baseline(str(bad))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint",
         os.path.join("deepspeed_tpu", "telemetry"),
         "--baseline", str(bad)],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert str(bad) in proc.stderr


def test_github_format_paths_are_root_relative_regardless_of_cwd(tmp_path):
    """CI annotations must name repo-relative files no matter where the
    runner invoked the linter from."""
    bad_proj = os.path.join(CONTRACTS, "bad")
    env = dict(os.environ, PYTHONPATH=REPO)
    runs = []
    for cwd in (REPO, str(tmp_path)):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.jaxlint", bad_proj,
             "--format=github", "--no-baseline"],
            cwd=cwd, capture_output=True, text=True, timeout=300, env=env)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        runs.append(sorted(l for l in proc.stdout.splitlines()
                           if l.startswith("::error")))
    assert runs[0] == runs[1]
    assert any("file=deepspeed_tpu/worker.py" in l for l in runs[0]), runs[0]


def test_inference_telemetry_tools_clean_under_full_v2_rules():
    """The v2 gate: the serving plane, the telemetry plane and the
    tools themselves are clean under the FULL rule set (JL001-JL010 +
    JL101-JL104) with the baseline EMPTY."""
    findings = lint_paths([
        os.path.join(REPO, "deepspeed_tpu", "inference"),
        os.path.join(REPO, "deepspeed_tpu", "telemetry"),
        os.path.join(REPO, "tools")])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_baseline_is_empty():
    """v2 acceptance: all real drift is FIXED, not baselined.  The only
    accepted exceptions are inline suppressions with justification
    comments at the site."""
    assert load_baseline() == {}


def _lint_cpu_seconds(*args, timeout):
    """Run the linter in a child and return the CPU seconds it burned
    (user + system).  The budgets are on the linter's own work: wall
    time would also count every other test worker sharing these cores."""
    import resource

    def children_cpu():
        r = resource.getrusage(resource.RUSAGE_CHILDREN)
        return r.ru_utime + r.ru_stime

    c0 = children_cpu()
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", *args,
         "deepspeed_tpu", "tools"],
        cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return children_cpu() - c0


def test_contracts_only_preflight_budget():
    cpu = _lint_cpu_seconds("--contracts-only", timeout=60)
    assert cpu < 10.0, f"--contracts-only burned {cpu:.1f}s (budget: 10s)"


def test_full_tree_run_budget():
    cpu = _lint_cpu_seconds(timeout=120)
    assert cpu < 30.0, f"full tree-wide run burned {cpu:.1f}s (budget: 30s)"


# ---------------------------------------------------------------------------
# pins for the drift the v2 contract passes surfaced (fixed in-tree)
# ---------------------------------------------------------------------------

def test_jl008_suppressions_carry_justifications():
    """The two deliberate blocking puts (serve admission, disk-tier
    bounded-RAM streaming) are suppressed INLINE with a reason — not
    baselined, not silently exempted."""
    for rel in (os.path.join("deepspeed_tpu", "inference", "engine.py"),
                os.path.join("deepspeed_tpu", "runtime",
                             "disk_offload.py")):
        with open(os.path.join(REPO, rel)) as f:
            src = f.read()
        assert "# jaxlint: disable=JL008" in src, rel
        before = src.split("# jaxlint: disable=JL008")[0]
        assert "backpressure" in before.rsplit("\n\n", 1)[-1].lower() \
            or "backpressure" in "\n".join(
                before.splitlines()[-8:]).lower(), \
            f"{rel}: JL008 suppression without a justification comment"


def test_jl006_dispatch_delta_is_inline_suppressed_not_baselined():
    with open(os.path.join(REPO, "deepspeed_tpu", "runtime",
                           "engine.py")) as f:
        src = f.read()
    assert "# jaxlint: disable=JL006" in src
    assert "dispatch-only delta by design" in src


def test_real_tree_registry_pins_the_fixed_drift():
    """docs fence tokens name real StageGraph entries, the serving
    prefix-miss counter is documented, and the offload attribution
    scalars have summarize consumers."""
    from tools.jaxlint.registry import ProjectRegistry
    reg = ProjectRegistry.build(REPO)
    drain_names = {n for entries in reg.drain_orders.values()
                   for n, _l in entries}
    for tok, _f, _l in reg.docs_drain:
        assert tok in drain_names, \
            f"docs drain fence token {tok!r} not registered"
    assert "serve_prefix_misses_total" in {n for n, _f, _l
                                           in reg.docs_metrics}
    for name in ("offload_h2d_s", "offload_cpu_adam_s"):
        assert name in reg.scalars, name
        assert name in reg.scalar_reads, \
            f"{name} emitted but summarize never reads it"
