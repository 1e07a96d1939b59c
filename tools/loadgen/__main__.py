"""``python -m tools.loadgen <scenario>`` — run one bench scenario.

Scenarios are workload configs over the one replay harness; each
writes its ``BENCH_*.json`` next to ``--out-dir`` and prints the
record.  ``goodput`` is the workload plane's own headline (uniform vs
burst arrival at the same mean rate + the chaos leg); the other five
are the legacy ``bench_serve.py`` legs.

``python -m tools.loadgen convert <src> <dst>`` is the trace
converter: public Azure/Mooncake trace rows → the replayable
``load_trace`` JSONL shape (tools/loadgen/convert.py).
"""
import argparse
import json
import sys


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "convert":
        from tools.loadgen.convert import main as convert_main
        return convert_main(sys.argv[2:])
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    from tools.loadgen.scenarios import SCENARIOS
    enable_compile_cache()
    ap = argparse.ArgumentParser(
        prog="python -m tools.loadgen",
        description="replay one bench scenario over the workload plane")
    ap.add_argument("scenario", choices=sorted(SCENARIOS),
                    help="which scenario to run")
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_*.json lands (default: cwd)")
    ap.add_argument("--seed", type=int, default=None,
                    help="goodput: workload seed")
    ap.add_argument("--requests", type=int, default=None,
                    help="goodput: requests per leg")
    ap.add_argument("--trace", default=None,
                    help="goodput: replay this JSONL trace as the "
                         "burst leg (load_trace format)")
    ap.add_argument("--no-chaos", action="store_true",
                    help="goodput: skip the fleet chaos leg")
    args = ap.parse_args()
    kwargs = {"out_dir": args.out_dir}
    if args.scenario == "goodput":
        if args.seed is not None:
            kwargs["seed"] = args.seed
        if args.requests is not None:
            kwargs["n_requests"] = args.requests
        if args.trace is not None:
            kwargs["trace_path"] = args.trace
        if args.no_chaos:
            kwargs["chaos"] = False
    rec = SCENARIOS[args.scenario](**kwargs)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
