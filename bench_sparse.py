"""Block-sparse attention benchmark: Pallas sparse kernel vs flash vs
dense at long sequence lengths on one real TPU chip.

Writes BENCH_sparse.json — the artifact backing the sparse-attention perf
claim (reference claims 6.3x vs dense, BASELINE.md:20); prints one JSON
line per (layout, seq) with tokens/s and speedups.
"""
import json
import sys

import numpy as np


def _bench(fn, *args, iters=None):
    """Calibrated timing (a 10-iteration window once produced flat
    ~0.03 ms times across seq lengths — pure noise floor); shared
    helper lives in bench.py."""
    from bench import calibrated_time
    return calibrated_time(lambda: fn(*args), iters)


def main():
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, ".")
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = jax.devices()[0].platform != "cpu"
    from deepspeed_tpu.ops.pallas.block_sparse_attention import (
        block_sparse_attention)
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.sparse_attention import (
        BigBirdSparsityConfig, BSLongformerSparsityConfig)

    B, H, D = (1, 8, 64) if on_tpu else (1, 2, 64)
    block = 64
    seqs = [4096, 8192, 16384] if on_tpu else [256]
    layouts = [
        ("bigbird", lambda: BigBirdSparsityConfig(
            num_heads=H, block=block, num_random_blocks=1,
            num_sliding_window_blocks=3, num_global_blocks=1)),
        ("longformer", lambda: BSLongformerSparsityConfig(
            num_heads=H, block=block, num_sliding_window_blocks=3)),
    ]

    results = []
    for name, mk in layouts:
        cfg = mk()
        for T in seqs:
            layout = np.asarray(cfg.make_layout(T))
            density = float(layout.sum()) / layout.size
            # on-device generation: no bulk H2D
            q, k, v = (jax.random.normal(
                jax.random.PRNGKey(i), (B, H, T, D), jnp.bfloat16)
                for i in range(3))

            sparse_fn = jax.jit(lambda q, k, v, lay=layout: (
                block_sparse_attention(q, k, v, lay, block)))
            flash_fn = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=False))
            t_sparse = _bench(sparse_fn, q, k, v)
            t_flash = _bench(flash_fn, q, k, v)
            # fwd+bwd (the training shape of the claim): grad of a scalar
            # reduction through each kernel
            sparse_g = jax.jit(jax.grad(lambda q, k, v, lay=layout: (
                block_sparse_attention(q, k, v, lay, block)
                .astype(jnp.float32).sum()), argnums=(0, 1, 2)))
            flash_g = jax.jit(jax.grad(lambda q, k, v: (
                flash_attention(q, k, v, causal=False)
                .astype(jnp.float32).sum()), argnums=(0, 1, 2)))
            t_sparse_bwd = _bench(sparse_g, q, k, v)
            t_flash_bwd = _bench(flash_g, q, k, v)
            t_dense = None
            if T <= 8192:  # dense scores get big fast

                def dense(q, k, v):
                    s = jnp.einsum(
                        "bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(D)
                    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
                    return jnp.einsum("bhqk,bhkd->bhqd", p, v)

                try:
                    t_dense = _bench(jax.jit(dense), q, k, v)
                except Exception:
                    t_dense = None
            rec = {
                "layout": name, "seq": T, "density": round(density, 4),
                "sparse_ms": round(t_sparse * 1e3, 3),
                "flash_ms": round(t_flash * 1e3, 3),
                "dense_ms": (round(t_dense * 1e3, 3)
                             if t_dense else None),
                "speedup_vs_flash": round(t_flash / t_sparse, 2),
                "speedup_vs_dense": (round(t_dense / t_sparse, 2)
                                     if t_dense else None),
                "sparse_fwdbwd_ms": round(t_sparse_bwd * 1e3, 3),
                "flash_fwdbwd_ms": round(t_flash_bwd * 1e3, 3),
                "speedup_vs_flash_fwdbwd": round(
                    t_flash_bwd / t_sparse_bwd, 2),
            }
            results.append(rec)
            print(json.dumps(rec))

    if on_tpu:  # never clobber the TPU-measured artifact with CPU smoke
        with open("BENCH_sparse.json", "w") as f:
            json.dump({"device": str(jax.devices()[0]),
                       "shape": {"B": B, "H": H, "D": D, "block": block},
                       "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
