"""Peak trainable parameters on ONE chip with ZeRO-Offload — the second
BASELINE metric (BASELINE.md:26; the reference's headline is 13B on a
32 GB V100 with CPU offload vs 1.4B plain DP, features.md:115 there).

Binary-searches GPT-2 depth (d_model fixed at 1600) for the largest model
that completes one full training step, twice: with the XLA host-offload
tier (fp32 master + moments in pinned host memory) and without offload
(fp32 state in HBM).  Reports both and the ratio — the "10x larger models"
claim is the ratio.  Writes BENCH_capacity.json.

Each probe runs in a fresh subprocess: an OOM'd XLA client can leave HBM
fragmented, and a clean exit releases everything deterministically.
"""
import json
import os
import subprocess
import sys

PROBE = """
import sys
import numpy as np
import jax
sys.path.insert(0, {repo!r})
import os
from deepspeed_tpu.utils.compile_cache import enable_compile_cache
enable_compile_cache()
from deepspeed_tpu.config import DeepSpeedConfig
from deepspeed_tpu.models import GPT2Config, GPT2Model
from deepspeed_tpu.parallel import build_mesh
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

n_layer, offload = int(sys.argv[1]), bool(int(sys.argv[2]))
chunks = int(os.environ.get("CAPACITY_GRAD_CHUNKS", "0"))
stream = os.environ.get("CAPACITY_PARAM_STREAM", "0") == "1"
if len(sys.argv) > 3 and sys.argv[3] == "smoke":  # CPU plumbing check
    jax.config.update("jax_platforms", "cpu")
    cfg_model = GPT2Config(d_model=64, n_layer=n_layer, n_head=4,
                           vocab_size=256, n_positions=64, remat=None,
                           scan_layers=True, stream_scan=stream)
else:
    cfg_model = GPT2Config(d_model=1600, n_layer=n_layer, n_head=25,
                           vocab_size=50257, n_positions=1024,
                           remat="block", scan_layers=True,
                           stream_scan=stream)
zero = {{"stage": 2, "cpu_offload": True, "offload_impl": "xla"}} if offload \
    else {{"stage": 0}}
if offload and chunks > 1:
    zero["offload_grad_chunks"] = chunks
if offload and stream:
    zero["param_streaming"] = True
# split update by default for offload probes: the fused update program
# materializes the whole fp32 state as HBM temporaries
# (docs/chip_notes.md), which would cap the measured offload capacity at
# roughly the no-offload level.  CAPACITY_SPLIT_UPDATE=0 measures the
# fused structure deliberately.
if offload and os.environ.get("CAPACITY_SPLIT_UPDATE", "1") == "1":
    zero["offload_split_update"] = True
ds_cfg = DeepSpeedConfig({{
    "train_micro_batch_size_per_gpu": 1,
    "gradient_accumulation_steps": 1,
    "steps_per_print": 10 ** 9,
    "bf16": {{"enabled": True}},
    "optimizer": {{"type": "Adam", "params": {{"lr": 1e-4}}}},
    "zero_optimization": zero,
}}, world_size=1)
engine = DeepSpeedEngine(GPT2Model(cfg_model), ds_cfg,
                         mesh=build_mesh(devices=jax.devices()[:1]))
tokens = np.zeros((1, min(cfg_model.n_positions, 1024) + 1), dtype=np.int32)
loss = float(np.asarray(engine.train_batch(tokens)))
assert np.isfinite(loss), loss
print("PROBE_OK", cfg_model.num_params)
"""


def _split_update_env() -> str:
    """One resolution of the split-update knob, recorded in the artifact:
    a fused-structure run's capacity number must be distinguishable from
    the (default) split-update run's."""
    return os.environ.get("CAPACITY_SPLIT_UPDATE", "1")


def _probe(n_layer: int, offload: bool, timeout: int,
           smoke: bool = False, chunks: int = 0,
           stream: bool = False) -> int:
    """Return param count if one step trains at this depth, else 0."""
    argv = [sys.executable, "-u", "-c",
            PROBE.format(repo=os.path.dirname(os.path.abspath(__file__))),
            str(n_layer), str(int(offload))]
    if smoke:
        argv.append("smoke")
    env = dict(os.environ)
    env["CAPACITY_GRAD_CHUNKS"] = str(chunks)
    env["CAPACITY_PARAM_STREAM"] = "1" if stream else "0"
    env["CAPACITY_SPLIT_UPDATE"] = _split_update_env()
    try:
        proc = subprocess.run(argv, capture_output=True, text=True,
                              timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        # a probe that hangs near the OOM boundary counts as a failed
        # size — the bisection must continue, not abort
        print(f"  probe n_layer={n_layer} offload={offload} timed out "
              f"after {timeout}s", file=sys.stderr)
        return 0
    for line in proc.stdout.splitlines():
        if line.startswith("PROBE_OK"):
            return int(line.split()[1])
    print(f"  probe n_layer={n_layer} offload={offload} failed "
          f"(rc={proc.returncode}): {proc.stderr.strip()[-300:]}",
          file=sys.stderr)
    return 0


D_MODEL = 1600
PER_LAYER = 12 * D_MODEL * D_MODEL + 13 * D_MODEL  # GPT-2 block params
EMB = (50257 + 1024) * D_MODEL


def _hbm_bytes(timeout: int) -> int:
    """bytes_limit of the real chip, read in a child: this parent never
    starts a backend, so every probe child gets the chip to itself."""
    code = ("import jax; d = jax.local_devices()[0]; "
            "print('HBM', d.memory_stats().get('bytes_limit', 0))")
    try:
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=timeout)
        for line in p.stdout.splitlines():
            if line.startswith("HBM"):
                v = int(line.split()[1])
                if v > 0:
                    return v
    except subprocess.TimeoutExpired:
        pass
    return 16 << 30  # v5e default


def _predict_layers(offload: bool, hbm: int, chunks: int = 0,
                    stream: bool = False) -> int:
    """Analytic seed for the search: device bytes/param at micro=1 ga=1.

    no-offload stage 0: fp32 master+mu+nu (12) + bf16 params (2) + fp32
    grads (4) = 18 B/param.  offload xla tier (piece-wise staging, bf16
    init above the fp32 limit, scanless ga=1 grads): bf16 params (2) +
    bf16 grads (2) + one staging piece ~= 4.5 B/param.  param_streaming
    removes the resident bf16 params (device holds ~ one layer), leaving
    the grad term — 2/K with K grad chunks — plus slack for the in-
    flight slices.  ~1.5 GB margin for activations (seq 1024, micro 1,
    block remat + fp32 logits), workspace, and fragmentation."""
    margin = int(1.5 * (1 << 30))
    if not offload:
        per_param = 18.0
    elif stream:
        per_param = (2.0 / chunks if chunks > 1 else 2.0) + 0.6
    elif chunks > 1:
        # chunked: bf16 params (2) + largest grad group (~2/K) + slack
        per_param = 2.0 + 2.0 / chunks + 0.6
    else:
        per_param = 4.5
    budget = max(hbm - margin, 1 << 30)
    return max(1, int((budget / per_param - EMB) / PER_LAYER))


def _search_seeded(offload: bool, seed_layers: int, timeout: int,
                   max_probes: int = 6, chunks: int = 0,
                   stream: bool = False):
    """Largest working n_layer with a bounded probe budget: start at the
    analytic prediction, climb geometrically while passing (the model
    may be conservative), fall back geometrically while failing, then
    one refinement bisect in the final bracket.  Each probe is a fresh
    subprocess (OOM leaves fragmented HBM; exit releases it)."""
    probes = 0

    def probe(n):
        nonlocal probes
        probes += 1
        return _probe(n, offload, timeout, chunks=chunks, stream=stream)

    n = max(1, seed_layers)
    params = probe(n)
    if params:
        best, best_params = n, params
        hi_fail = None
        while probes < max_probes:
            nxt = max(best + 1, int(best * 1.3))
            p = probe(nxt)
            if p:
                best, best_params = nxt, p
            else:
                hi_fail = nxt
                break
    else:
        # prediction too optimistic: halve until something trains (no
        # give-up floor — a failing size only tightens the bracket), then
        # refine upward like the climb branch
        hi_fail, best, best_params = n, 0, 0
        while probes < max_probes and hi_fail > 1:
            n = max(1, hi_fail // 2)
            params = probe(n)
            if params:
                best, best_params = n, params
                break
            hi_fail = n
        if not best_params:
            return 0, 0
    # refinement bisect in the final (best, hi_fail) bracket
    while hi_fail is not None and probes < max_probes:
        mid = (best + hi_fail) // 2
        if mid <= best:
            break
        p = probe(mid)
        if p:
            best, best_params = mid, p
        else:
            hi_fail = mid
    return best, best_params


def main():
    timeout = int(os.environ.get("CAPACITY_PROBE_TIMEOUT", "1200"))
    if os.environ.get("CAPACITY_SMOKE"):
        # validate the subprocess plumbing on CPU (no OOM boundary there)
        ok = _probe(2, False, timeout, smoke=True)
        ok_off = _probe(2, True, timeout, smoke=True)
        ok_stream = _probe(2, True, timeout, smoke=True, chunks=2,
                           stream=True)
        print(json.dumps({"metric": "capacity_smoke", "value": 1.0,
                          "unit": "ok",
                          "vs_baseline": float(bool(ok and ok_off
                                                    and ok_stream))}))
        return
    hbm = _hbm_bytes(timeout=min(timeout, 300))
    chunks = int(os.environ.get("CAPACITY_CHUNKS", "4"))
    p_plain = _predict_layers(False, hbm)
    p_off = _predict_layers(True, hbm)
    p_ck = _predict_layers(True, hbm, chunks)
    p_st = _predict_layers(True, hbm, chunks, stream=True)
    max_probes = int(os.environ.get("CAPACITY_MAX_PROBES", "6"))
    print(f"  hbm={hbm / (1 << 30):.1f} GiB predict: plain={p_plain} "
          f"offload={p_off} chunked(k={chunks})={p_ck} "
          f"stream+chunked={p_st} layers",
          file=sys.stderr)
    plain_layers, plain_params = _search_seeded(False, p_plain, timeout,
                                                max_probes)
    off_layers, off_params = _search_seeded(True, p_off, timeout,
                                            max_probes)
    ck_layers, ck_params = (0, 0)
    if chunks > 1:
        ck_layers, ck_params = _search_seeded(
            True, max(p_ck, off_layers), timeout, max_probes,
            chunks=chunks)
    # param streaming (ZeRO-Infinity-style): host-resident stacked
    # compute params break the 2 B/param device floor entirely —
    # the mode that reaches past the reference's 10x claim
    st_layers, st_params = _search_seeded(
        True, max(p_st, ck_layers, off_layers), timeout, max_probes,
        chunks=chunks, stream=True)
    best_params = max(off_params, ck_params, st_params)
    ratio = best_params / plain_params if plain_params else 0.0
    out = {
        "metric": "offload_peak_trainable_params_per_chip",
        "value": round(best_params / 1e9, 3),
        "unit": "B params",
        "no_offload_params_b": round(plain_params / 1e9, 3),
        "offload_params_b": round(off_params / 1e9, 3),
        "offload_chunked_params_b": round(ck_params / 1e9, 3),
        "offload_stream_params_b": round(st_params / 1e9, 3),
        "grad_chunks": chunks,
        "split_update": _split_update_env() == "1",
        "offload_layers": off_layers,
        "offload_chunked_layers": ck_layers,
        "offload_stream_layers": st_layers,
        "no_offload_layers": plain_layers,
        "capacity_ratio": round(ratio, 2),
        # reference: 10x larger models via offload (BASELINE.md:16)
        "vs_baseline": round(ratio / 10.0, 4),
    }
    print(json.dumps(out))
    with open("BENCH_capacity.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
