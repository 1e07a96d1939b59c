"""MoE routing/dispatch overhead benchmark (round-2 verdict weak #6).

Question: how much of an MoE layer's step time is routing + dispatch +
combine rather than expert FFN math, as E and tokens-per-group grow —
and does the scatter dispatch (``MoEConfig.dispatch_impl='scatter'``)
beat the one-hot einsum?

Analysis the numbers check: with capacity C = k·cf·S/E the one-hot
dispatch einsum ("gsec,gsd->egcd") does G·S·(E·C)·d ≈ G·S²·cf·k·d MACs —
*independent of E* at fixed group size, but quadratic in S; the expert
FFN does G·S·k·cf·2·d·f MACs (linear in S).  So dispatch overhead is a
function of S/(2f), not of E.  The scatter path moves O(S·d) per group
instead.  Emits one JSON line per measurement; writes BENCH_moe.json on
TPU (never clobbered by CPU smoke runs).
"""
import json
import sys
def _bench(fn, *args, iters=None):
    """Calibrated timing (bench.py helper): a 10-iteration window
    measures dispatch jitter at these kernel sizes, not the kernels —
    it once showed fwd+bwd 'faster' than fwd and flat ~0.04 ms rows."""
    from bench import calibrated_time
    return calibrated_time(lambda: fn(*args), iters)


def main():
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, ".")
    from deepspeed_tpu.moe import MoEConfig, init_moe_params, moe_ffn
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    on_tpu = jax.devices()[0].platform != "cpu"

    if on_tpu:
        d, G = 1024, 4
        experts = [8, 32, 64]
        seqs = [1024, 4096, 8192]
    else:
        d, G = 64, 2
        experts = [4, 8]
        seqs = [128]
    f = 4 * d
    results = []
    for E in experts:
        for S in seqs:
            # on-device generation: no bulk H2D
            x = jax.random.normal(jax.random.PRNGKey(2), (G, S, d),
                                  jnp.bfloat16)
            key = jax.random.PRNGKey(0)
            rec = {"E": E, "S": S, "G": G, "d": d}
            params = None
            for impl in ("einsum", "scatter"):
                cfg = MoEConfig(n_experts=E, d_model=d, d_ff=f, top_k=2,
                                capacity_factor=1.25, dispatch_impl=impl)
                if params is None:
                    params = init_moe_params(jax.random.PRNGKey(1), cfg)

                def step(p, xin, c=cfg):
                    y, aux = moe_ffn(c, p, xin, key, train=True)
                    return jnp.sum(y.astype(jnp.float32) ** 2) + aux

                fwd = jax.jit(lambda p, xin, c=cfg: moe_ffn(
                    c, p, xin, key, train=True)[0])
                bwd = jax.jit(jax.grad(step))
                rec[f"{impl}_fwd_ms"] = round(_bench(fwd, params, x) * 1e3, 3)
                rec[f"{impl}_fwdbwd_ms"] = round(
                    _bench(bwd, params, x) * 1e3, 3)

            # FFN-equivalent floor: the same expert math with dispatch
            # replaced by a reshape — tokens pre-packed into E·C slots.
            C = cfg.capacity(S, True)
            packed = jax.random.normal(jax.random.PRNGKey(3), (E, G, C, d),
                                       jnp.bfloat16)

            def ffn_only(p, ein):
                dt = ein.dtype
                h = jnp.einsum("egcd,edf->egcf", ein, p["wi"].astype(dt))
                h = jax.nn.gelu(h + p["bi"].astype(dt)[:, None, None, :],
                                approximate=True)
                eo = jnp.einsum("egcf,efd->egcd", h, p["wo"].astype(dt))
                return jnp.sum(eo.astype(jnp.float32) ** 2)

            rec["ffn_only_fwdbwd_ms"] = round(
                _bench(jax.jit(jax.grad(ffn_only)), params, packed) * 1e3, 3)
            for impl in ("einsum", "scatter"):
                t = rec[f"{impl}_fwdbwd_ms"]
                rec[f"{impl}_overhead_frac"] = round(
                    max(0.0, t - rec["ffn_only_fwdbwd_ms"]) / t, 3)
            rec["scatter_speedup_fwdbwd"] = round(
                rec["einsum_fwdbwd_ms"] / rec["scatter_fwdbwd_ms"], 2)
            results.append(rec)
            print(json.dumps(rec), flush=True)

    if on_tpu:
        with open("BENCH_moe.json", "w") as fh:
            json.dump({"device": str(jax.devices()[0]),
                       "top_k": 2, "capacity_factor": 1.25,
                       "results": results}, fh, indent=1)


if __name__ == "__main__":
    main()
