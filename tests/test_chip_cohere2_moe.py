"""Command A+ at its cell's sizes, compiled for a described v5e
(``tests/chip.py``; ``benchmark/configs/command-a-plus-05-2026.json``: one
period of three window layers and a full one, 8 of 128 experts held at
4,096 x 4,096, 96 slots of rings of 4,096 keys, 12,289 pages of 8 key
heads): the ring walked in blocks, the chunk's attention over keys ahead
of it, the expert kernels in blocks, and both serve programs.
"""
import jax.numpy as jnp
import pytest

from chip import (ReadsItsMatricesWhereTheyLie,
                  RestsItsQueryProjectionsOutputMajor, ServedFamily, _compile,
                  _is_one_kernel, _kernel_names, _sds, gated_experts_alone)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.decode_attention import (
    PAGED_DECODE_ATTN_KERNEL, WINDOW_DECODE_ATTN_KERNEL,
    window_decode_attention)
from deepspeed_tpu.ops.pallas.flash_attention import (FLASH_FWD_CTX_KERNEL,
                                                      FLASH_FWD_KERNEL,
                                                      flash_attention_fwd)
from deepspeed_tpu.utils.hlo import parameter_rewrites

WINDOW = 4096


class TestCohere2Moe(ServedFamily, ReadsItsMatricesWhereTheyLie,
                     RestsItsQueryProjectionsOutputMajor):
    """The pool (3.22 GB) and the rings (4.83 GB) pass through aliased and
    no program copies a layer of them (a static slice of a ring leaf did:
    0.77 GB a window layer); a chunk holds both forms of its attention
    (from nothing; over the ring and the pages the chunk before left)."""
    config = "command-a-plus-05-2026"
    family = "command-a-plus"
    kernels = {
        "serve_decode": {dropless.MOE_GATE_UP_KERNEL: 4,
                         dropless.MOE_DOWN_KERNEL: 4,
                         WINDOW_DECODE_ATTN_KERNEL: 3,
                         PAGED_DECODE_ATTN_KERNEL: 1},
        "serve_prefill": {dropless.MOE_GATE_UP_KERNEL: 4,
                          dropless.MOE_DOWN_KERNEL: 4, FLASH_FWD_KERNEL: 4,
                          FLASH_FWD_CTX_KERNEL: 4}}
    temporaries = {"serve_decode": 0.2e9, "serve_prefill": 1.2e9}
    fits = 15.6e9
    unscoped = {"serve_decode": 32.4, "serve_prefill": 11.2}
    matrices = {"leaves": 2 + 4 * 9 + 3, "relaid": True}
    relaid = ("q_w", 4)

    def test_the_tick_at_rest_copies_no_weight(self, one_chip):
        """The experts alone stacked, the query projections resting
        output-major as the engine holds them (PR 55): nothing in the
        tick's entry computation writes a megabyte of a weight again, to
        HBM or to fast memory."""
        compiled = self.program(one_chip, "serve_decode", relaid=True)
        assert [r for r in parameter_rewrites(
            compiled.as_text(), self.matrices["leaves"])
            if r.bytes >= 1 << 20] == []

    def test_window_decode_walks_rings_of_4096_keys_where_they_lie(
            self, one_chip):
        """128 query heads on 8 key heads over rings of 4,096 keys: 512
        rows of every key head a grid step (4 MiB of keys and values in
        flight), no layer's slots sliced out of the 3.2 GB of rings."""
        s = self.spec().slots
        compiled = _compile(
            lambda q, k, v, n, base: window_decode_attention(
                q, k, v, n, None, base=base, interpret=False),
            one_chip, _sds((s, 128, 128)), _sds((3 * s, 8, WINDOW, 128)),
            _sds((3 * s, 8, WINDOW, 128)), _sds((s,), jnp.int32),
            _sds((), jnp.int32))
        _is_one_kernel(compiled, WINDOW_DECODE_ATTN_KERNEL, 8 << 20)

    @pytest.mark.parametrize("ctx", ["ring", "pages"])
    def test_flash_forward_over_context_keys_compiles_at_128_on_8(
            self, ctx, one_chip):
        """A chunk of 4,096 queries on 128 heads over ``[context ; chunk]``
        keys on 8: a window layer's ring of 4,096 ahead, the full layer's
        20,480 gathered positions; the live count is traced."""
        spec = self.spec()
        window = WINDOW if ctx == "ring" else None
        ctx = window or spec.max_pages * spec.page_len
        compiled = _compile(
            lambda q, k, v, n: flash_attention_fwd(
                q, k, v, window=window, ctx_live=n, interpret=False),
            one_chip, _sds((1, 128, 4096, 128)),
            _sds((1, 8, ctx + 4096, 128)), _sds((1, 8, ctx + 4096, 128)),
            _sds((), jnp.int32))
        assert _kernel_names(compiled) == [FLASH_FWD_CTX_KERNEL]

    @pytest.mark.parametrize("tokens", [96, 2048],
                             ids=["decode_tick", "prefill_rung"])
    def test_moe_kernels_walk_an_expert_in_blocks_at_4096_by_4096(
            self, tokens, one_chip):
        """8 held of 128 experts of 4,096 x 4,096, top-8: both
        up-projections whole would be 128 MiB in flight, the whole VMEM;
        the kernel walks them in two blocks of 2,048 columns (64 MiB + 16
        for the rows), the down-projection whole (64 MiB + 16).  The
        compile is the proof that the chip allows both."""
        d = f = 4096
        held = 8
        weights = [_sds((4 * held, d, f))] * 2
        assert dropless.weight_blocks(weights, f) == 2
        assert dropless._vmem_limit(weights, 2) == 80 << 20
        assert dropless.weight_blocks(weights[:1], d) == 1
        assert dropless._vmem_limit(weights[:1], 1) == 80 << 20
        gated_experts_alone(one_chip, tokens, d, f, 128, 4 * held, held)
