"""Test harness configuration.

Mirrors the reference's multi-process-without-a-cluster strategy
(reference: tests/unit/common.py:14-100 forks NCCL workers on localhost):
on TPU-less CI we instead expose an 8-device virtual CPU mesh via
``--xla_force_host_platform_device_count`` so every sharding/collective
path (ZeRO, pipeline ppermute, tensor-parallel psum) executes for real,
single-process SPMD, no cluster needed.
"""
import os

# The suite runs on the CPU backend.  Exported, not only configured:
# children that tests start inherit the environment, not jax.config.
os.environ["JAX_PLATFORMS"] = "cpu"

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Checkpoint fsync off for the suite: unit tests simulate process death
# (which the page cache survives), and this image's 9p filesystem makes
# each fsync cost ~50ms/file — ~1.3s per tiny save.  The production
# default stays ON; tests/test_resilience.py pins that default.
os.environ.setdefault("DS_CKPT_FSYNC", "0")
# Same rule for the disk offload tier's per-leaf state files: its
# tmp+rename + CRC plane is what the tests exercise; the ~50ms/fsync 9p
# cost is not.  Production default stays ON;
# tests/test_disk_offload.py::test_fsync_on_by_default pins it.
os.environ.setdefault("DS_DISK_FSYNC", "0")

# A run compiles a program once: its workers (pytest-xdist's, which
# inherit this process's environment) and the children tests start share
# ONE persistent compilation cache, made fresh for the run under the run's
# temporary directory and removed at its end (``pytest_sessionfinish``),
# so no run ever reads what another left.  The two thresholds are JAX's
# own: keep every program, however quick to compile or small.
# ``tests/chip.py::topo`` switches the cache off for the described-device
# compiles.
_RUN_OWNS_CACHE = "PYTEST_XDIST_WORKER" not in os.environ
if _RUN_OWNS_CACHE:
    import tempfile
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="ds_tpu_tests_jax_cache_")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"

import jax  # noqa: E402

assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}")

# ---------------------------------------------------------------------------
# Test-tier guard.  pytest.ini defines two tiers (core = `-m "not slow"`,
# full = everything); this guard keeps the core tier honest by failing any
# test that builds a compile-bound mesh without carrying the ``slow`` marker,
# and (opt-in, for CI) any unmarked test whose call phase overruns a wall
# budget.  Mirrors the reference's CI split into per-PR unit jobs vs nightly
# model tests (reference: azure-pipelines.yml runs tests/unit per PR and
# gates tests/model behind a nightly trigger).
# ---------------------------------------------------------------------------
import pytest  # noqa: E402

HEAVY_PIPE = 4  # pp>=4 programs compile multi-stage scans: always slow-tier

_current_item = None
_duration_offenders = []


def heavy_mesh_violation(mesh_shape, has_slow_marker):
    """Tier policy, pure so tests can exercise it: building a mesh with a
    ``pipe`` axis >= HEAVY_PIPE means compiling a multi-stage pipeline scan
    (the dominant compile cost in this suite — see pytest.ini's slow-tier
    description); such a test must be in the slow tier."""
    pipe = int(mesh_shape.get("pipe", 1))
    if pipe >= HEAVY_PIPE and not has_slow_marker:
        return (f"this test builds a pipe={pipe} mesh but is not marked "
                "@pytest.mark.slow; pp>=4 programs are compile-bound and "
                "belong in the slow tier (see pytest.ini / tests/README.md)")
    return None


def duration_violation(duration_s, has_slow_marker, budget_s):
    """Opt-in (TIER_GUARD=1) wall-clock policy: an unmarked test whose call
    phase overruns the budget must move to the slow tier."""
    if not has_slow_marker and duration_s > budget_s:
        return (f"call phase took {duration_s:.1f}s > TIER_GUARD_SECONDS="
                f"{budget_s:.0f}s without @pytest.mark.slow")
    return None


@pytest.fixture(autouse=True)
def _tier_guard_track_item(request):
    global _current_item
    _current_item = request.node
    yield
    _current_item = None


# What every test of a family file builds identically, the parameters of
# its tiny config, is drawn once a module: a draw is ~2 s, and compiles
# anew at every call (``init`` jits closures of its own).
_drawn = {}


def drawn_once(model_cls, cfg, seed):
    """``model_cls(cfg).init(PRNGKey(seed))``, drawn the first time a test
    of the running module asks (``attn_impl`` draws nothing).  The arrays
    are shared, the containers the caller's own to change."""
    import dataclasses
    key = (model_cls.__name__,
           repr(dataclasses.replace(cfg, attn_impl="dense")), seed)
    if key not in _drawn:
        _drawn[key] = model_cls(cfg).init(jax.random.PRNGKey(seed))
    return jax.tree.map(lambda a: a, _drawn[key])


@pytest.fixture(scope="module", autouse=True)
def _drawn_for_a_module():
    """Drops what ``drawn_once`` holds at the module's end."""
    yield
    _drawn.clear()


# Mesh construction goes through __new__ (cached), not __init__.
_orig_mesh_new = jax.sharding.Mesh.__new__


def _guarded_mesh_new(cls, *args, **kwargs):
    mesh = _orig_mesh_new(cls, *args, **kwargs)
    item = _current_item
    if item is None:
        return mesh
    try:
        shape = dict(mesh.shape)
    except Exception:
        return mesh
    msg = heavy_mesh_violation(
        shape, item.get_closest_marker("slow") is not None)
    if msg:
        pytest.fail(msg, pytrace=False)
    return mesh


jax.sharding.Mesh.__new__ = _guarded_mesh_new


def pytest_sessionstart(session):
    """jaxlint --contracts-only pre-flight: the cross-artifact contract
    rules (stages, metrics, fault points, config keys — JL102-JL104)
    run in seconds and catch docs/code drift before the suite spends
    minutes compiling.  DS_SKIP_LINT_PREFLIGHT=1 skips it (while
    iterating on a fix the gate itself is pinning)."""
    if os.environ.get("DS_SKIP_LINT_PREFLIGHT") == "1":
        return
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "tools.jaxlint", "--contracts-only",
         "deepspeed_tpu", "tools"],
        cwd=repo, capture_output=True, text=True, timeout=60)
    if proc.returncode != 0:
        pytest.exit("jaxlint --contracts-only pre-flight failed "
                    "(DS_SKIP_LINT_PREFLIGHT=1 to bypass):\n"
                    + proc.stdout + proc.stderr, returncode=1)


def pytest_runtest_logreport(report):
    if os.environ.get("TIER_GUARD") != "1":
        return
    if report.when != "call":
        return
    budget = float(os.environ.get("TIER_GUARD_SECONDS", "60"))
    msg = duration_violation(
        report.duration, "slow" in report.keywords, budget)
    if msg:
        _duration_offenders.append(f"{report.nodeid}: {msg}")


def pytest_sessionfinish(session, exitstatus):
    if _RUN_OWNS_CACHE:
        import shutil
        shutil.rmtree(os.environ["JAX_COMPILATION_CACHE_DIR"],
                      ignore_errors=True)
    if _duration_offenders:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        lines = ["tier guard: unmarked tests overran the core-tier budget "
                 "(mark them @pytest.mark.slow):"] + _duration_offenders
        for line in lines:
            if tr is not None:
                tr.write_line(line, red=True)
            else:
                print(line)
        if session.exitstatus == 0:
            session.exitstatus = 1


# The described chip of ``tests/test_chip_*.py`` (``tests/chip.py``).
from chip import one_chip, topo  # noqa: E402,F401
