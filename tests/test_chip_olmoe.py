"""OLMoE at its published widths, compiled for a described v5e
(``tests/chip.py``; ``benchmark/configs/olmoe-1b-7b.json``, cut as
``chip.CUT`` says): the expert layer's two kernels, the paged decode
kernel at head size 128, and both serve programs, two layers deep.
"""
import jax.numpy as jnp
import pytest

from chip import (ServedFamily, _compile, _is_one_kernel, _sds,
                  gated_experts_alone)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.decode_attention import (
    PAGED_DECODE_ATTN_KERNEL, PAGED_KV_VMEM_BUDGET, decode_attention_paged,
    paged_decode_arm, paged_page_vmem_bytes, paged_pages_per_block)
from deepspeed_tpu.ops.pallas.flash_attention import FLASH_FWD_KERNEL


class TestOlmoe(ServedFamily):
    """The pools pass through the layer scan without a copy of either: a
    tick's temporaries are under a quarter of one pool's 67 MB."""
    config = "olmoe-1b-7b"
    kernels = {
        "serve_decode": {dropless.MOE_GATE_UP_KERNEL: 2,
                         dropless.MOE_DOWN_KERNEL: 2,
                         PAGED_DECODE_ATTN_KERNEL: 2},
        "serve_prefill": {dropless.MOE_GATE_UP_KERNEL: 2,
                          dropless.MOE_DOWN_KERNEL: 2, FLASH_FWD_KERNEL: 2}}
    temporaries = {"serve_decode": 2 * 513 * 16 * 16 * 128 * 2 // 4}
    says_arguments = ()
    unscoped = {"serve_decode": 13.8, "serve_prefill": 3.0}

    def test_paged_decode_kernel_at_head_128_keeps_its_name(self, one_chip):
        """The direct arm at the cell's own shapes (64 slots, 128 table
        entries, 12 layers' pages in one row of 12 x 3,457, a page as it
        rests ``[16, 16, 128]``): chosen from the pool's shape, its double
        buffer inside the module's VMEM budget, the pools left in HBM (no
        temporary of any size that a copy of a pool would be), and still
        the one trace row ``paged_decode_share.*`` reads."""
        spec = self.spec()
        heads, page_len, dh = spec.heads, spec.page_len, spec.head_dim
        shape = (heads, page_len, dh, 2)
        assert shape == (16, 16, 128, 2)
        assert paged_decode_arm(*shape) == "direct"
        ppb = paged_pages_per_block(*shape, spec.max_pages)
        assert ppb == 16
        # K and V, two halves each, and nothing packed
        assert paged_page_vmem_bytes(*shape) == 4 * page_len * heads * dh * 2
        assert ppb * paged_page_vmem_bytes(*shape) <= PAGED_KV_VMEM_BUDGET
        flat = _sds((12 * 3457, page_len, heads, dh))
        compiled = _compile(
            lambda q, k, v, t, n: decode_attention_paged(
                q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), t, n,
                interpret=False),
            one_chip, _sds((spec.slots, heads, dh)), flat, flat,
            _sds((spec.slots, spec.max_pages), jnp.int32),
            _sds((spec.slots,), jnp.int32))
        _is_one_kernel(compiled, PAGED_DECODE_ATTN_KERNEL, 1 << 20)

    @pytest.mark.parametrize("tokens", [64, 1024], ids=["decode_tick",
                                                        "prefill_bucket"])
    def test_moe_kernels_carry_their_names(self, tokens, one_chip):
        """64 experts of 2048 x 1024, top-8: rows of 16 at a decode tick,
        of 128 at a prefill; an expert's matrices are one block each
        (16 MiB in flight: the kernels raise Mosaic's VMEM limit, and the
        compile is the proof that the chip allows it)."""
        assert dropless.MOE_GATE_UP_KERNEL == "ds_moe_gate_up"
        assert dropless.MOE_DOWN_KERNEL == "ds_moe_down"
        gated_experts_alone(one_chip, tokens, 2048, 1024, 64, 2 * 64)
