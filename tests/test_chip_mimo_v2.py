"""MiMo-V2.5 at its published widths and its cell's shapes, compiled for a
described v5e (``tests/chip.py``; ``benchmark/configs/mimo-v2.5.json``:
layer 0 + one period of five window layers and a full one, 16 of 256
experts held, 192 slots of window rings, 13,825 pages of 4 key heads, keys
192 wide kept 256 wide over values of 128): the window decode kernel, the
paged kernel at two widths, and both serve programs.
"""
import jax.numpy as jnp

from chip import (ReadsItsMatricesWhereTheyLie,
                  RestsItsQueryProjectionsOutputMajor, ServedFamily, _compile,
                  _is_one_kernel, _sds, window_decode)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.decode_attention import (
    PAGED_DECODE_ATTN_KERNEL, PAGED_KV_VMEM_BUDGET, WINDOW_DECODE_ATTN_KERNEL,
    decode_attention_paged, paged_decode_arm, paged_pages_per_block)
from deepspeed_tpu.ops.pallas.flash_attention import FLASH_FWD_KERNEL
from deepspeed_tpu.utils.hlo import parameter_rewrites


class TestMimoV2(ServedFamily, ReadsItsMatricesWhereTheyLie,
                 RestsItsQueryProjectionsOutputMajor):
    """The pool (5.4 GB) and the window rings (0.75 GB) pass through
    aliased; a decode tick's temporaries stay under 0.2 GB."""
    config = "mimo-v2.5"
    kernels = {
        "serve_decode": {dropless.MOE_GATE_UP_KERNEL: 6,
                         dropless.MOE_DOWN_KERNEL: 6,
                         WINDOW_DECODE_ATTN_KERNEL: 5,
                         PAGED_DECODE_ATTN_KERNEL: 2},
        "serve_prefill": {dropless.MOE_GATE_UP_KERNEL: 6,
                          dropless.MOE_DOWN_KERNEL: 6, FLASH_FWD_KERNEL: 7}}
    temporaries = {"serve_decode": 0.2e9, "serve_prefill": 1.6e9}
    fits = 15.0e9
    says_arguments = ()
    unscoped = {"serve_decode": 38.7, "serve_prefill": 4.7}
    matrices = {"leaves": 3 + 2 * 5 + 5 * 6 + 4 + 6 * 3 + 3, "relaid": True}
    relaid = ("q_w", 7)

    def test_the_tick_at_rest_copies_a_window_layers_keys_and_nothing_to_hbm(
            self, one_chip):
        """Stacked ``[layers, d, n]`` and sliced by a static index, every
        ``q_w`` (100.7 MB) was written to HBM transposed and every window
        layer's ``k_w`` / ``v_w`` to fast memory before a ``copy`` brought
        it to the matmul: 0.83 GB a tick moved once more than needed,
        0.112 GB of temporaries.  PR 39 left one ``copy`` a ``q_w`` (1.19
        ms of a tick on the chip); with the ``q_w`` resting output-major
        (PR 55) what is copied is a window layer's ``k_w`` (12.6 MB) and
        one ``v_w`` into fast memory; the temporaries are 0.015 GB."""
        compiled = self.program(one_chip, "serve_decode", relaid=True)
        moved = [r for r in parameter_rewrites(
            compiled.as_text(), self.matrices["leaves"])
            if r.bytes >= 1 << 20]
        assert len({r.parameter for r in moved}) == len(moved) <= 7, moved
        assert max(r.bytes for r in moved) < 16 << 20, moved
        assert compiled.memory_analysis().temp_size_in_bytes < 0.03e9

    def test_prefill_rung_holds_half_the_temporaries_of_stacked_leaves(
            self, one_chip):
        """The 2,048 rung of the same walk: with stacked leaves one fusion
        wrote all five window layers' ``q_w`` transposed at once (0.966 GB
        of temporaries); with a leaf a layer 0.492 GB."""
        compiled = self.program(one_chip, "serve_prefill", 2048)
        assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9

    def test_window_decode_kernel_reads_the_rings_where_they_lie(
            self, one_chip):
        assert WINDOW_DECODE_ATTN_KERNEL == "ds_window_decode_attn"
        # no layer's slots are sliced out of the rings: the base is traced
        _is_one_kernel(window_decode(one_chip, self.spec().slots),
                       WINDOW_DECODE_ATTN_KERNEL, 4 << 20)

    def test_paged_decode_kernel_at_two_widths_keeps_its_name(self,
                                                              one_chip):
        """64 query heads on 4 key heads, keys 256 wide at rest over
        values of 128: the grouped direct body, 16 pages of 64 a block
        inside the module's VMEM budget, the pools left in HBM."""
        spec = self.spec()
        shape = (spec.heads, spec.page_len, spec.head_dim, 2)
        assert shape + (spec.value_dim,) == (4, 64, 256, 2, 128)
        assert paged_decode_arm(*shape, q_heads=64) == "direct"
        ppb = paged_pages_per_block(*shape, spec.max_pages, q_heads=64,
                                    v_head_dim=128)
        assert ppb == 16
        assert ppb * 2 * 4 * 64 * (256 + 128) * 2 <= PAGED_KV_VMEM_BUDGET
        compiled = _compile(
            lambda q, k, v, t, n: decode_attention_paged(
                q, k, v, t, n, sm_scale=192 ** -0.5, interpret=False),
            one_chip, _sds((spec.slots, 64, 256)),
            _sds((spec.pages, 4, 64, 256)), _sds((spec.pages, 4, 64, 128)),
            _sds((spec.slots, spec.max_pages), jnp.int32),
            _sds((spec.slots,), jnp.int32))
        _is_one_kernel(compiled, PAGED_DECODE_ATTN_KERNEL, 1 << 20)
