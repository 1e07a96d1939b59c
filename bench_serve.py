#!/usr/bin/env python
"""Serving bench entry point — a thin shim over the workload plane.

The five A/B legs (serve / paged / spec / quant / fleet) now live as
scenario configs over the ONE open-loop replay harness in
``tools/loadgen/`` (docs/serving.md "workload plane"); this file keeps
the historical CLI and the ``run_*_ab`` import surface stable.  Each
leg still writes its committed ``BENCH_*.json`` headline:

    BENCH_serve.json        serve_continuous_batching_speedup
    BENCH_serve_paged.json  serve_paged_admitted_ratio
    BENCH_serve_spec.json   serve_spec_wall_per_token_ratio
    BENCH_serve_quant.json  serve_quant_admitted_ratio
    BENCH_fleet.json        fleet_scaling_tokens_ratio

The workload plane's own goodput headline
(``BENCH_loadgen_goodput.json``) runs via
``python -m tools.loadgen goodput``.
"""
import json
import os
import sys

# this file is loaded both as a script and via spec_from_file_location
# (the bench tests) — anchor the repo root so ``tools.loadgen``
# resolves regardless of the caller's cwd
_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

from tools.loadgen.scenarios import (  # noqa: E402  (path anchor above)
    run_ab, run_fleet_ab, run_goodput, run_paged_ab, run_quant_ab,
    run_spec_ab)

__all__ = ["run_ab", "run_paged_ab", "run_spec_ab", "run_quant_ab",
           "run_fleet_ab", "run_goodput"]


def _mode_kwargs(args, **attr_to_kw):
    """Per-mode default sentinels: every mode flag defaults to None at
    the parser, and ONLY explicitly-given values are forwarded, so
    each ``run_*_ab`` keeps its own mode defaults (the paged/spec/
    quant A/Bs want different slot counts, delays and budgets than the
    plain one).  One copy of the forwarding — the third mode no longer
    clones the other two's kwargs blocks."""
    kw = {}
    for attr, name in attr_to_kw.items():
        v = getattr(args, attr)
        if v is not None:
            kw[name] = v
    return kw


def main():
    import argparse
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--slots", type=int, default=None,
                        help="slot pool size (default 8); with --paged "
                             "this is the KV-byte budget in legacy-slot "
                             "strides (default 4 there)")
    parser.add_argument("--requests", type=int, default=None,
                        help="workload size (default 16; 24 with "
                             "--paged)")
    parser.add_argument("--prompt", type=int, default=None,
                        help="prompt length (unpaged and --spec A/Bs, "
                             "default 8 — the paged/quant legs drive a "
                             "fixed short/long mix)")
    parser.add_argument("--gen", type=int, default=None,
                        help="tokens per request (default 16; with "
                             "--spec, 4*(k+1)+1 — block-aligned for "
                             "the given --k)")
    parser.add_argument("--delay", type=float, default=None,
                        help="injected device time (s): per TICK for "
                             "the unpaged A/B (default 0.02), per "
                             "prefill PAGE for the --paged prefix leg "
                             "(default 0.03)")
    parser.add_argument("--paged", choices=("on", "off", "ab"),
                        default=None,
                        help="run the paged-KV A/B instead "
                             "(BENCH_serve_paged.json); 'ab' = both "
                             "arms (on/off are accepted for symmetry "
                             "with the other benches and also run the "
                             "full A/B — both arms are needed for the "
                             "ratio)")
    parser.add_argument("--spec", choices=("on", "off", "ab"),
                        default=None,
                        help="run the speculative-decoding A/B instead "
                             "(BENCH_serve_spec.json); both arms always "
                             "run — the headline is the spec/non-spec "
                             "wall-per-token ratio")
    parser.add_argument("--quant", choices=("on", "off", "ab"),
                        default=None,
                        help="run the quantized-serving A/B instead "
                             "(BENCH_serve_quant.json): admitted "
                             "concurrency at a fixed KV-byte budget, "
                             "int8 vs fp pages, plus the int8-weights "
                             "params-HBM leg; both arms always run — "
                             "the headline is a ratio")
    parser.add_argument("--k", type=int, default=4,
                        help="draft tokens per tick for --spec "
                             "(default 4)")
    parser.add_argument("--fleet", choices=("on", "off", "ab"),
                        default=None,
                        help="run the serving-fleet A/B instead "
                             "(BENCH_fleet.json): aggregate tokens/s "
                             "at 1 vs 2 replicas under identical "
                             "injected per-tick device time, plus the "
                             "replica-kill + autoscale-up trace; both "
                             "arms always run — the headline is the "
                             "2/1 tokens-per-second ratio")
    args = parser.parse_args()
    # one shared dispatch harness: every mode forwards ONLY the flags
    # the user gave (None sentinels), so each run_*_ab keeps its own
    # per-mode defaults — no more per-mode kwargs blocks to clone
    if args.fleet is not None:
        rec = run_fleet_ab(**_mode_kwargs(
            args, requests="n_requests", gen="gen_tokens",
            delay="tick_delay_s"))
    elif args.spec is not None:
        rec = run_spec_ab(**{"k": args.k}, **_mode_kwargs(
            args, delay="pass_delay_s", slots="slots",
            requests="n_requests", gen="gen_tokens",
            prompt="prompt_len"))
    elif args.quant is not None:
        rec = run_quant_ab(**_mode_kwargs(
            args, slots="kv_budget_slots", requests="n_requests"))
    elif args.paged is not None:
        rec = run_paged_ab(**_mode_kwargs(
            args, delay="tick_delay_s", slots="kv_budget_slots",
            requests="n_requests"))
    else:
        rec = run_ab(**_mode_kwargs(
            args, slots="slots", requests="n_requests",
            prompt="prompt_len", gen="gen_tokens",
            delay="tick_delay_s"))
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
