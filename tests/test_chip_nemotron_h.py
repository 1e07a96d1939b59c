"""Nemotron-3 Super at its published widths and its cell's shapes, compiled
for a described v5e (``tests/chip.py``;
``benchmark/configs/nemotron-3-super-120b-a12b.json``: MEMEM*EMEME, 128 of
512 experts held, 192 slots of recurrent state, 24,577 pages of 2 key
heads): the Mamba-2 decode update, the relu2 expert kernels, the paged
decode kernel at 32 query heads on 2 key heads, and both serve programs.
"""
import jax.numpy as jnp
import pytest

from chip import ServedFamily, _compile, _is_one_kernel, _kernel_names, _sds
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.decode_attention import (
    PAGED_DECODE_ATTN_KERNEL, PAGED_KV_VMEM_BUDGET, decode_attention_paged,
    paged_decode_arm, paged_pages_per_block)
from deepspeed_tpu.ops.pallas.flash_attention import FLASH_FWD_KERNEL
from deepspeed_tpu.ops.pallas.ssm import SSM_DECODE_KERNEL, ssm_decode

#: the 4 GB of ``ds_ssm_decode``'s state: 5 layers x 192 slots of
#: [128, 64, 128] float32
SSM_STATE = 5 * 192 * 128 * 64 * 128 * 4


class TestNemotronH(ServedFamily):
    """The recurrent state (and the pools) pass through aliased, in a
    decode tick (``ds_ssm_decode`` rewrites it where it lies) and in a
    prefill (one slot's rows are written); the temporaries are far smaller
    than the state: no copy of it."""
    config = "nemotron-3-super-120b-a12b"
    kernels = {
        "serve_decode": {dropless.MOE_UP_RELU2_KERNEL: 5,
                         dropless.MOE_DOWN_KERNEL: 5, SSM_DECODE_KERNEL: 5,
                         PAGED_DECODE_ATTN_KERNEL: 1},
        "serve_prefill": {dropless.MOE_UP_RELU2_KERNEL: 5,
                          dropless.MOE_DOWN_KERNEL: 5, FLASH_FWD_KERNEL: 1}}
    temporaries = {"serve_decode": SSM_STATE // 8,
                   "serve_prefill": SSM_STATE // 8}
    fits = 15.0e9
    says_arguments = ()
    unscoped = {"serve_decode": 1.4, "serve_prefill": 5.7}

    def test_ssm_decode_kernel_keeps_its_name_and_the_state_in_place(
            self, one_chip):
        assert SSM_DECODE_KERNEL == "ds_ssm_decode"
        s, h, p, n, g = self.spec().slots, 128, 64, 128, 8
        f32 = jnp.float32
        compiled = _compile(
            lambda st, d, x, b, c, act, base: ssm_decode(
                st, d, x, b, c, act, base=base, interpret=False),
            one_chip, _sds((5 * s, h, p, n), f32), _sds((s, h), f32),
            _sds((s, h, p), f32), _sds((s, g, n), f32), _sds((s, g, n), f32),
            _sds((s,), jnp.bool_), _sds((), jnp.int32), donate=(0,))
        _is_one_kernel(compiled, SSM_DECODE_KERNEL, 4 << 20)
        assert compiled.memory_analysis().alias_size_in_bytes >= SSM_STATE

    def test_paged_decode_kernel_at_32_on_2_heads_keeps_its_name(
            self, one_chip):
        """Grouped keys at the cell's shapes: the direct body, a page at
        rest ``[2, 16, 128]`` the operand, blocks of 128 pages inside the
        module's VMEM budget, the pools left in HBM."""
        spec = self.spec()
        shape = (spec.heads, spec.page_len, spec.head_dim, 2)
        assert shape == (2, 16, 128, 2)
        assert paged_decode_arm(*shape, q_heads=32) == "direct"
        ppb = paged_pages_per_block(*shape, spec.max_pages, q_heads=32)
        assert ppb == 128
        assert ppb * 4 * 2 * 16 * 128 * 2 <= PAGED_KV_VMEM_BUDGET
        pool = _sds((spec.pages,) + shape[:3])
        compiled = _compile(
            lambda q, k, v, t, n: decode_attention_paged(q, k, v, t, n,
                                                         interpret=False),
            one_chip, _sds((spec.slots, 32, 128)), pool, pool,
            _sds((spec.slots, spec.max_pages), jnp.int32),
            _sds((spec.slots,), jnp.int32))
        _is_one_kernel(compiled, PAGED_DECODE_ATTN_KERNEL, 1 << 20)

    @pytest.mark.parametrize("tokens", [192, 1024],
                             ids=["decode_tick", "prefill_bucket"])
    def test_moe_relu2_kernels_carry_their_names(self, tokens, one_chip):
        """128 held experts of 1024 x 2688 (two matrices, no gate), top-22
        of 512 handed in: rows of 16 at a decode tick and of 64 at a
        prefill, the tile count sound if every assignment lands on the
        held."""
        assert dropless.MOE_UP_RELU2_KERNEL == "ds_moe_up_relu2"
        lat, f, held, k = 1024, 2688, 128, 22
        compiled = _compile(
            lambda x, r, w, e, u, d: dropless.dropless_moe(
                x, r, None, u, d, k, expert_offset=jnp.int32(held),
                routing=(w, e), experts_held=(0, held), act="relu2",
                interpret=False)[0],
            one_chip, _sds((tokens, lat)), _sds((4096, 512)),
            _sds((tokens, k), jnp.float32), _sds((tokens, k), jnp.int32),
            _sds((2 * held, lat, f)), _sds((2 * held, f, lat)))
        assert sorted(_kernel_names(compiled)) == [
            dropless.MOE_DOWN_KERNEL, dropless.MOE_UP_RELU2_KERNEL]
