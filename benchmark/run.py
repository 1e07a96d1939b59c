"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children.  Refuses to run without a TPU holding the chips
the cell asks for.  The last line of stdout is the result (see README.md);
everything else worth reading is on earlier lines or under
``benchmark_out/<cell>/`` (ignored by git).

    python benchmark/run.py --selfcheck              # the trace reduction, no chip, no JAX
    python benchmark/run.py --rehearse --workload X  # toy widths on the CPU, counts only
"""
from __future__ import annotations

import time

CLOCK0 = time.perf_counter()      # before the heavy imports: set-up starts here

import argparse
import dataclasses
import glob
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from lib import yardstick
from lib.yardstick import say


class CompileLog:
    """Every XLA backend compilation of the process (jax.monitoring)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.seconds = []
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds.append(duration)

    def count(self) -> int:
        return len(self.seconds)


@dataclasses.dataclass
class Context:
    workload: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    rehearse: bool
    cfg_file: dict
    mix: dict
    family: object
    compiles: CompileLog
    out_dir: str


def _job(mix: dict):
    kind = mix["job"]
    if "job_module" in mix:         # a later PR's own job: "lib.<file>:run"
        mod, fn = mix["job_module"].split(":")
        return getattr(importlib.import_module(mod), fn)
    if kind == "serve_open_loop":
        from lib import serve_job
        return serve_job.run
    if kind == "train":
        from lib import train_job
        return train_job.run
    raise NotImplementedError(
        f"no job builder for traffic kind {kind!r}: add "
        "benchmark/lib/<file>.py with run(ctx) and name it in the mix as "
        "\"job_module\": \"lib.<file>:run\" (known: serve_open_loop, train)")


def selfcheck() -> int:
    """Reduce the recorded event list and compare with the values written
    beside it.  Pure Python."""
    from lib.trace import Reduction
    failures = 0
    for path in sorted(glob.glob(os.path.join(HERE, "fixtures",
                                              "*.events.json"))):
        bad = 0
        with open(path) as f:
            planes = json.load(f)
        with open(path.replace(".events.json", ".expected.json")) as f:
            want = json.load(f)
        red = Reduction(planes)
        got = red.summary()
        got["shares"] = {"|".join(q): red.share(*q)
                         for q in want.get("share_queries", [])}
        for key in ("window_s", "busy_s", "idle_share_pct"):
            if abs(got[key] - want[key]) > 1e-9 * max(1.0, abs(want[key])):
                print(f"{os.path.basename(path)}: {key} {got[key]!r} != "
                      f"{want[key]!r}")
                bad += 1
        for key in ("device_ops", "idle_gaps"):
            if [k for k, _ in got[key]] != [k for k, _ in want[key]] or any(
                    abs(a[1] - b[1]) > 1e-12 for a, b in
                    zip(got[key], want[key])):
                print(f"{os.path.basename(path)}: {key} differ:\n  "
                      f"{got[key]}\n  {want[key]}")
                bad += 1
        for k, v in want.get("shares", {}).items():
            if abs(got["shares"][k] - v) > 1e-9:
                print(f"{os.path.basename(path)}: share {k} "
                      f"{got['shares'][k]!r} != {v!r}")
                bad += 1
        print(f"selfcheck {os.path.basename(path)}: "
              f"{'ok' if not bad else 'FAILED'}")
        failures += bad
    return 1 if failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--selfcheck", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.selfcheck:
        return selfcheck()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; known: {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        cfg_file = json.load(f)
    mix = yardstick.load_json("traffic", cell["traffic"] + ".json")
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])

    if args.rehearse:
        mix = {**mix, **mix["rehearse"]}
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell["chips"] > 1:
            os.environ.setdefault(
                "XLA_FLAGS", "--xla_force_host_platform_device_count="
                + str(cell["chips"]))
        seconds = min(seconds, float(mix.get("seconds", 3)))
    import jax
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse and (device["platform"] != "tpu"
                              or len(devices) < cell["chips"]):
        print(f"refusing to run: {args.workload} needs {cell['chips']} TPU "
              f"chip(s), JAX reports {device}", file=sys.stderr)
        return 1
    if not args.rehearse:
        cache_dir = enable_compile_cache()
        # every program, however small or quick to compile, is kept: a
        # second run in this checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        say(f"device {device}; compile cache at {cache_dir}")

    from lib import families
    out_dir = os.path.join(ROOT, "benchmark_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(
        workload=args.workload, seed=args.seed, seconds=seconds,
        trace=bool(args.trace), chips=cell["chips"], rehearse=args.rehearse,
        cfg_file=cfg_file, mix=mix,
        family=families.build(cfg_file, args.rehearse),
        compiles=CompileLog(), out_dir=out_dir)
    res = _job(mix)(ctx)
    series = res["series"]
    series["setup_s"] = res["window_start"] - CLOCK0
    say(f"checks: {res['checks']}; compilations in the whole process "
        f"{ctx.compiles.count()} ({sum(ctx.compiles.seconds):.1f} s)")

    if args.rehearse:
        counts = {k: (len(v) if isinstance(v, list) else v)
                  for k, v in series.items()
                  if isinstance(v, (list, int)) and not isinstance(v, bool)}
        print(json.dumps({"rehearsal": True, "platform": device["platform"],
                          "checks": res["checks"],
                          "attempted": res["attempted"],
                          "failed": res["failed"], "counts": counts}))
        return 0 if all(res["checks"].values()) else 1

    reduction = None
    if args.trace:
        from lib.trace import Reduction, events_from_xplane
        paths = sorted(glob.glob(os.path.join(
            res["trace_dir"], "plugins", "profile", "*", "*.xplane.pb")))
        planes = events_from_xplane(paths[-1])
        with open(os.path.join(out_dir, f"events-{args.seed}.json"), "w") as f:
            json.dump(planes, f)
        reduction = Reduction(planes)
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
    group = "per_layer" if args.trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in bench[group]}
    metrics = {}
    for name in yardstick.metric_names(bench, group, args.workload):
        value = yardstick.read_metric(name, series, reduction)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    line = {"correct": bool(res["checks"]) and all(res["checks"].values()),
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    if reduction is not None:
        summary = reduction.summary()
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
