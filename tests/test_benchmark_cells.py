"""The benchmark that gates every PR (``BENCHMARK.json`` + ``benchmark/``),
guarded from the CPU: every cell rehearses at toy widths with its checks
true, the trace reduction reproduces its recorded fixtures, the declaration
names files that exist, and ``run.py`` never measures a CPU.  The cases are
read from ``BENCHMARK.json``, so a new cell is guarded without an edit here.

A rehearsal proves paths, checks and counts; it yields no time, rate or
share (``run.py --rehearse`` prints counts only)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def _run_py(*args, timeout=600):
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=ROOT,
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def _rehearse(cell):
    """The rehearsal's last line.  A rehearsal is an open loop against the
    clock with ONE second of lead-in: beside five busy workers the
    program a chat cell compiles on its first copied page
    (``serve_copy_page``) lands after the window has opened, and
    ``no_compile_in_window`` alone reads false (PERF.md section 7).  That
    one check is the host's, so such a rehearsal is run again, twice at
    most; any other failure stands."""
    for _ in range(3):
        out = _run_py("--rehearse", "--workload", cell)
        lines = out.stdout.strip().splitlines()
        line = json.loads(lines[-1]) if lines and lines[-1].startswith("{") \
            else None
        late = line is not None and [k for k, v in line["checks"].items()
                                     if not v] == ["no_compile_in_window"]
        if not late:
            break
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    return line


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell):
    line = _rehearse(cell)
    assert line["rehearsal"] and line["platform"] == "cpu"
    assert line["checks"] and all(line["checks"].values()), line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["counts"]["compiles_in_window"] == 0
    if cell.startswith("olmoe"):
        # one record of expert counters beside every tick
        assert line["counts"]["moe_experts_hit_pct"] \
            == line["counts"]["tick_ms"] > 0


def test_trace_selfcheck():
    out = _run_py("--selfcheck", timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    fixtures = [f for f in os.listdir(os.path.join(ROOT, "benchmark",
                                                   "fixtures"))
                if f.endswith(".events.json")]
    assert fixtures
    for f in fixtures:
        assert f"selfcheck {f}: ok" in out.stdout


def test_refuses_to_measure_the_cpu():
    """No TPU: no result line, a non-zero exit, and the reason."""
    out = _run_py("--workload", CELLS[0], "--seconds", "1", timeout=300)
    assert out.returncode == 1, out.stdout
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert "refusing to run" in out.stderr


def _files_exist():
    configs = {c["name"]: c["file"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in configs, w
        assert os.path.isfile(os.path.join(ROOT, configs[w["config"]])), w
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json")), w
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".json")), m["name"]


def _few_cells_take_four_chips():
    four = [w["name"] for w in BENCH["workloads"] if w["chips"] == 4]
    assert {w["chips"] for w in BENCH["workloads"]} <= {1, 4}
    assert len(four) <= len(CELLS) // 4, four


def _metrics_name_existing_cells():
    for group in ("end_to_end", "per_layer"):
        for m in BENCH[group]:
            unknown = set(m.get("workloads", [])) - set(CELLS)
            assert not unknown, (m["name"], unknown)
    judged = {c for m in BENCH["end_to_end"] for c in m.get("workloads", [])}
    assert judged == set(CELLS)


@pytest.mark.parametrize("rule", [_files_exist, _few_cells_take_four_chips,
                                  _metrics_name_existing_cells],
                         ids=lambda f: f.__name__.strip("_"))
def test_benchmark_json_is_consistent(rule):
    """Each rule is one a driver's verdict (``benchmark_moved``,
    ``config_not_added``) would otherwise be the first to check: the files
    ``run.py`` reads for a cell exist; at most a quarter of the cells,
    rounded down, ask for four chips; every metric's ``workloads`` names
    cells that exist, and every cell has a judged metric of its own."""
    rule()
