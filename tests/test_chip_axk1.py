"""A.X-K1 at its cell's sizes, compiled for a described v5e
(``tests/chip.py``; ``benchmark/configs/a.x-k1.json``: 6 layers of latent
attention, 12 of 192 experts held at hidden 7,168, 192 slots, ONE pool of
12,289 pages of 64 rows 640 wide): the latent decode kernel, the expert
kernels walked in blocks, and both serve programs.
"""
import jax.numpy as jnp
import pytest

from chip import (ReadsItsMatricesWhereTheyLie, ServedFamily, _compile,
                  _is_one_kernel, _sds, gated_experts_alone)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.context_attention import \
    LATENT_CONTEXT_ATTN_KERNEL
from deepspeed_tpu.ops.pallas.decode_attention import (
    LATENT_DECODE_ATTN_KERNEL, PAGED_KV_VMEM_BUDGET, latent_decode_attention,
    latent_pages_per_block)
from deepspeed_tpu.ops.pallas.flash_attention import FLASH_FWD_KERNEL


class TestAxK1(ServedFamily, ReadsItsMatricesWhereTheyLie):
    """The ONE pool (6.04 GB) passes through aliased, None where a second
    would be; the arguments are the weights and that pool and no second
    array of latents.  (``reduced_why`` states the prefill's temporaries
    as the XLA loop's, 1.091 GB: a ``benchmark`` PR's to edit, ``PERF.md``
    section 7.)  The absorbed matrices ``k_b_w`` / ``v_b_w`` are among the
    leaves the tick reads where they lie."""
    config = "a.x-k1"
    family = "a.x-k1"
    kernels = {
        "serve_decode": {dropless.MOE_GATE_UP_KERNEL: 5,
                         dropless.MOE_DOWN_KERNEL: 5,
                         LATENT_DECODE_ATTN_KERNEL: 6},
        "serve_prefill": {dropless.MOE_GATE_UP_KERNEL: 5,
                          dropless.MOE_DOWN_KERNEL: 5, FLASH_FWD_KERNEL: 6,
                          LATENT_CONTEXT_ATTN_KERNEL: 6}}
    temporaries = {"serve_decode": 0.06e9, "serve_prefill": 1.1e9}
    fits = 15.6e9
    unscoped = {"serve_decode": 0.7, "serve_prefill": 14.2}
    matrices = {"leaves": 3 + 6 * 9 + 4 + 5 * 5 + 3}

    def test_latent_decode_kernel_reads_the_one_pool_where_it_lies(
            self, one_chip):
        """64 heads' [q_lat ; q_rope] against rows 640 wide, values their
        first 512 lanes: 32 pages of 64 a block inside the module's VMEM
        budget, the pool left in HBM, no layer sliced out of it."""
        assert LATENT_DECODE_ATTN_KERNEL == "ds_latent_decode_attn"
        spec = self.spec()
        assert (spec.layers, spec.page_len, spec.head_dim) == (6, 64, 640)
        ppb = latent_pages_per_block(64, 640, 2, spec.max_pages)
        assert ppb == 32
        assert 2 * ppb * 64 * 640 * 2 <= PAGED_KV_VMEM_BUDGET
        compiled = _compile(
            lambda q, pool, t, n: latent_decode_attention(
                q, pool, t, n, 512, sm_scale=0.13, interpret=False),
            one_chip, _sds((spec.slots, 64, 640)),
            _sds((6 * spec.pages, 64, 640)),
            _sds((spec.slots, spec.max_pages), jnp.int32),
            _sds((spec.slots,), jnp.int32))
        _is_one_kernel(compiled, LATENT_DECODE_ATTN_KERNEL, 1 << 20)

    @pytest.mark.parametrize("tokens", [192, 2048],
                             ids=["decode_tick", "prefill_rung"])
    def test_moe_kernels_walk_an_expert_in_blocks_at_hidden_7168(
            self, tokens, one_chip):
        """12 held of 192 experts of 7,168 x 2,048, top-8: both
        up-projections whole would be 112 MiB in flight of a core's 128;
        the kernel walks them in two blocks of 1,024 columns (72 MiB with
        the rows), the down-projection whole (72 MiB).  The compile is the
        proof that the chip allows both."""
        d, f, held = 7168, 2048, 12
        weights = [_sds((5 * held, d, f))] * 2
        assert dropless.weight_blocks(weights, f) == 2
        assert dropless._vmem_limit(weights, 2) == 72 << 20
        gated_experts_alone(one_chip, tokens, d, f, 192, 5 * held, held)
