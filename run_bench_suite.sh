#!/bin/bash
# Bench suite: every root bench script in turn, each its own process (the
# chip belongs to one process at a time), raw output to BENCH_<name>_raw.json.
# The serving and A/B legs are scheduling and overlap proofs that run on any
# backend; say JAX_PLATFORMS=cpu to hold them to the CPU.
#
# --gate: opt-in regression tripwire (tools/benchgate) — after each leg
# whose bench wrote a fresh BENCH_<name>.json, compare its headline
# metric against the committed predecessor and ABORT the suite nonzero
# on a >20% regression.  Off by default: hardware-window runs must
# finish and report even when slower.
cd "$(dirname "$0")"
GATE=0
ARGS=()
for a in "$@"; do
  if [ "$a" = "--gate" ]; then GATE=1; else ARGS+=("$a"); fi
done
set -- "${ARGS[@]}"
echo "=== suite start $(date -u +%H:%M:%S) gate=$GATE ===" >> bench_suite.log
# jaxlint contract pre-flight (<10s, stdlib only): abort before burning
# a hardware window when the stage/metric/config contract registries
# drifted — a bench emitting metrics nothing summarizes (or gating on
# an unpinned headline) produces an unusable artifact
echo "=== jaxlint contracts pre-flight $(date -u +%H:%M:%S) ===" >> bench_suite.log
if ! python -m tools.jaxlint --contracts-only deepspeed_tpu tools \
    >> bench_suite.log 2>&1; then
  echo "=== jaxlint contract pre-flight FAILED — aborting suite ===" \
    | tee -a bench_suite.log >&2
  exit 1
fi
gate() {
  name=$1
  if [ "$GATE" = "1" ] && [ -f "BENCH_${name}.json" ]; then
    echo "=== $name benchgate ===" >> bench_suite.log
    python -m tools.benchgate "BENCH_${name}.json" \
      >> bench_suite.log 2>&1
    rc=$?
    # only exit 1 is a REGRESSION; 0 covers pass/skip/first-run and
    # 2 (unreadable artifact) is logged but must not wedge a
    # hardware-window suite
    if [ "$rc" = "1" ]; then
      echo "=== $name benchgate REGRESSED — aborting suite ===" \
        | tee -a bench_suite.log >&2
      exit 1
    elif [ "$rc" != "0" ]; then
      echo "=== $name benchgate rc=$rc (artifact unreadable; " \
           "continuing) ===" >> bench_suite.log
    fi
  fi
}
run() {
  name=$1; shift
  echo "=== $name start $(date -u +%H:%M:%S) ===" >> bench_suite.log
  "$@" > "BENCH_${name}_raw.json" 2>> bench_suite.log
  echo "=== $name done rc=$? $(date -u +%H:%M:%S) ===" >> bench_suite.log
  gate "$name"
}
# --serve: just the serving A/Bs (the continuous-batching and paged-KV
# claims are scheduling claims proven with injected device time; run them
# under JAX_PLATFORMS=cpu)
if [ "$1" = "--serve" ]; then
  run serve python bench_serve.py
  run serve_paged python bench_serve.py --paged ab
  run serve_spec python bench_serve.py --spec ab
  run serve_quant python bench_serve.py --quant ab
  run fleet python bench_serve.py --fleet ab
  run fleet_disagg python -m tools.loadgen fleet_disagg
  run loadgen_goodput python -m tools.loadgen goodput
  run serve_lora python -m tools.loadgen lora
  run kv_tier python -m tools.loadgen kv_tier
  exit 0
fi
# --loadgen: just the workload plane's goodput/chaos headline (pure
# CPU — uniform vs burst arrival over the one replay harness)
if [ "$1" = "--loadgen" ]; then
  run loadgen_goodput python -m tools.loadgen goodput
  exit 0
fi
# --trace-replay: smoke the public-trace path end to end — BOTH
# committed fixtures (Azure CSV + Mooncake JSONL) through the
# tools.loadgen converter, load_trace, and the trace arrival path,
# scored by the goodput plane.  No new committed artifact: converted
# traces land in a temp dir; the assertions are zero lost requests
# (every submitted request completes error-free and is scored) and a
# present goodput section per leg.  The fixtures are rows-of-a-real-
# trace samples, not load: offsets are time-compressed 10x for the
# replay (same trace SHAPE through the same ArrivalSpec path) and no
# burst-gap phenomenon is asserted — that is the synthetic goodput
# leg's job.
if [ "$1" = "--trace-replay" ]; then
  echo "=== trace-replay smoke start $(date -u +%H:%M:%S) ===" >> bench_suite.log
  TMP=$(mktemp -d)
  trap 'rm -rf "$TMP"' EXIT
  for SRC in tests/data/azure_llm_sample.csv tests/data/mooncake_sample.jsonl; do
    BASE=$(basename "$SRC")
    DST="$TMP/${BASE%.*}.jsonl"
    echo "=== trace-replay convert $BASE ===" >> bench_suite.log
    if ! python -m tools.loadgen convert "$SRC" "$DST" >> bench_suite.log 2>&1; then
      echo "=== trace-replay convert $BASE FAILED ===" | tee -a bench_suite.log >&2
      exit 1
    fi
    echo "=== trace-replay replay $BASE ===" >> bench_suite.log
    if ! python - "$DST" <<'PY' >> bench_suite.log 2>&1; then
import sys
from tools.loadgen.harness import replay_engine
from tools.loadgen.scenarios import _init_model
from tools.loadgen.workload import ArrivalSpec, LengthSpec, Workload, \
    load_trace

arrival, records = load_trace(sys.argv[1])
assert records, "converted trace is empty"
# 10x time compression: same shape, smoke-suite wall clock
arrival = ArrivalSpec(kind="trace",
                      trace=tuple(t * 0.1 for t in arrival.trace))
wl = Workload(len(records), arrival=arrival,
              prompt_len=LengthSpec(value=6),
              gen_tokens=LengthSpec(value=8))
model, params = _init_model()
run = replay_engine(
    model, params,
    {"slots": 4, "max_seq_len": 64, "prefill_len": 8,
     "queue_capacity": 256, "flush_interval_ticks": 10},
    wl.build(seed=0), telemetry=True,
    warmup=(wl.build(seed=0)[0].prompt, 2),
    slo=(0.5, 0.25), tag="trace_replay")
# zero lost: every trace row became a completed, error-free request
assert len(run.requests) == len(records), \
    (len(run.requests), len(records))
assert all(len(r.tokens) > 0 for r in run.requests)
# the goodput section is present and scored over every request
assert run.goodput is not None and run.goodput["goodput"] is not None
assert run.goodput["requests"] == len(records), run.goodput
assert run.report.get("serve_goodput") is not None
print(f"trace-replay OK: {len(records)} requests, "
      f"goodput {run.goodput['goodput']:.2f}")
PY
      echo "=== trace-replay $BASE FAILED ===" | tee -a bench_suite.log >&2
      exit 1
    fi
  done
  echo "=== trace-replay smoke done $(date -u +%H:%M:%S) ===" >> bench_suite.log
  exit 0
fi
# capacity runs LAST: its probes are subprocesses killed on timeout,
# and a client killed mid-step may leave the device unusable for
# whatever runs after it
run r03 python bench.py
run prefetch python bench.py --prefetch=ab
run ckpt python bench.py --ckpt=ab
# offload-tier A/B: ZeRO-Infinity disk tier vs host RAM — bitwise-loss
# check plus the disk leg's state-I/O overlap ratio under injected
# per-leaf disk latency (pure CPU-provable; docs/stages.md disk tier)
run offload_disk python bench.py --offload-tier=ab
# stage chaos: sticky injected faults at every async stage boundary;
# training must complete degraded, bitwise-equal to the serial legs
run stage_chaos python bench.py --stage-chaos
# elastic smoke is pure-CPU subprocess supervision: kill one local
# worker mid-run, assert resume at reduced
# width with trajectory continuity + sample-exactness
run elastic python bench.py --elastic-smoke
# serving A/B: continuous batching vs sequential decode (pure CPU,
# injected per-tick device time — see docs/serving.md)
run serve python bench_serve.py
# paged-KV A/B: admitted slots at fixed KV bytes + prefix-reuse
# prefill compute (pure CPU scheduling claims — see docs/serving.md)
run serve_paged python bench_serve.py --paged ab
# speculative-decoding A/B: draft-verify vs one-token-per-tick under
# injected per-PASS device time; wall/token tracks 1/mean-accepted-
# length (pure CPU scheduling claim — see docs/serving.md)
run serve_spec python bench_serve.py --spec ab
# quantized-serving A/B: admitted concurrency at a fixed KV-byte
# budget (int8 vs fp pages) + int8-weights params-HBM leg (pure CPU
# capacity claims from the cache/param byte planes — docs/serving.md)
run serve_quant python bench_serve.py --quant ab
# serving-fleet A/B: router + replicated engine subprocesses — aggregate
# tokens/s scales with replicas under identical injected per-tick device
# time, plus the replica-kill + autoscale-up SLO-recovery trace (pure
# CPU subprocess supervision — see docs/serving.md "serving fleet")
run fleet python bench_serve.py --fleet ab
# disaggregated-fleet A/B: prefill/decode role split + chunked prefill
# vs a homogeneous fleet on the same mixed long-prompt/short-decode
# trace — the decode-cadence tail (TPOT p99) stays flat under prefill
# interference (pure CPU, injected per-chunk device time —
# docs/serving.md "disaggregated fleet")
run fleet_disagg python -m tools.loadgen fleet_disagg
# workload-plane goodput A/B: the SAME payload under uniform vs
# heavy-tailed burst arrival at the same mean rate — throughput stays
# flat, goodput (both-phase SLO attainment) collapses; plus the fleet
# chaos leg (replica kill + autoscale mid-burst, zero lost requests
# asserted from the ledger) — docs/serving.md "workload plane"
run loadgen_goodput python -m tools.loadgen goodput
# multi-tenant LoRA serving A/B: admitted tenants per HBM byte vs one
# merged model copy per tenant, on the SAME compiled decode program
# (zero recompiles over a Zipf tenant mix), plus the cold-adapter-
# fault TTFT tail under eviction pressure (pure CPU capacity +
# scheduling claims — docs/serving.md "multi-tenant serving")
run serve_lora python -m tools.loadgen lora
# KV-tiering A/B: conversation sessions resumed from the host/disk
# tier vs HBM-only at the SAME fixed page budget — turn-2 prefix
# hits survive parking bitwise, zero corrupt resumes (pure CPU
# capacity claim — docs/serving.md "KV tiering")
run kv_tier python -m tools.loadgen kv_tier
run bert python bench_bert.py
run sparse python bench_sparse.py
run flash python bench_flash.py
run moe python bench_moe.py
run capacity python bench_capacity.py
echo "=== cpu_adam start $(date -u +%H:%M:%S) ===" >> bench_suite.log
python bench_cpu_adam.py > BENCH_cpu_adam.txt 2>> bench_suite.log
echo "=== suite done $(date -u +%H:%M:%S) ===" >> bench_suite.log
