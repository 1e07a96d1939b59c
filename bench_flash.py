"""Flash-attention kernel tuning sweep on the real chip.

Sweeps (block_q, block_k) for the Pallas flash kernel at GPT-2-sized
shapes and long sequences, against the XLA dense baseline.  Prints one
JSON line per configuration and a final summary line with the best
blocks per sequence length — feed the winner back into the kernel
defaults (ops/pallas/flash_attention.py:394-395).
"""
import json
import sys

import numpy as np


def _time(fn, iters):
    from bench import calibrated_time
    return calibrated_time(fn, iters)


def main():
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, ".")
    from deepspeed_tpu.ops.attention import causal_attention
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = jax.devices()[0].platform != "cpu"
    iters = None  # calibrated_time owns the platform default + window
    B, H, D = (4, 12, 64) if on_tpu else (1, 2, 32)
    seqs = [1024, 4096, 8192] if on_tpu else [128]
    blocks = ([256, 512, 1024] if on_tpu else [64])

    best = {}
    for T in seqs:
        # generate ON DEVICE: a host rng + upload is 50+ MB of H2D per
        # tensor for nothing
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, H, T, D),
                                     jnp.bfloat16) for i in range(3))
        dense_fn = jax.jit(lambda q, k, v: causal_attention(q, k, v))
        try:
            t_dense = _time(lambda: dense_fn(q, k, v), iters)
        except Exception:
            t_dense = float("inf")  # dense OOMs at long seq — that's the point
        rows = []
        for bq in blocks:
            for bk in blocks:
                f = jax.jit(lambda q, k, v, bq=bq, bk=bk: flash_attention(
                    q, k, v, causal=True, block_q=bq, block_k=bk))
                try:
                    t = _time(lambda: f(q, k, v), iters)
                except Exception as e:
                    print(f"  seq{T} bq{bq} bk{bk}: FAIL {e}",
                          file=sys.stderr)
                    continue
                tok_s = B * T / t
                rows.append((t, bq, bk))
                speedup = (round(t_dense / t, 3)
                           if np.isfinite(t_dense) else None)
                print(json.dumps({
                    "metric": f"flash_seq{T}_bq{bq}_bk{bk}",
                    "value": round(tok_s, 1), "unit": "tokens/s",
                    "vs_baseline": speedup if speedup is not None else 0.0,
                    "dense_baseline": "oom" if speedup is None else "ok"}))
        if rows:
            t, bq, bk = min(rows)
            best[T] = {"block_q": bq, "block_k": bk,
                       "speedup_vs_dense": (round(t_dense / t, 3)
                                            if np.isfinite(t_dense)
                                            else None)}
    print(json.dumps({"metric": "flash_best_blocks", "value": 1.0,
                      "unit": "summary", "best": best, "vs_baseline": 1.0}))
    if on_tpu:
        with open("BENCH_flash.json", "w") as f:
            json.dump(best, f, indent=1)


if __name__ == "__main__":
    main()
