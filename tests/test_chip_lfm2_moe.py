"""LFM2-24B-A2B at its cell's sizes, compiled for a described v5e
(``tests/chip.py``; ``benchmark/configs/lfm2-24b-a2b.json``: 8 conv layers
+ 2 full ones at hidden 2,048, all 64 experts of 8 expert layers, 512 slots
of convolution rows [2 x 2,048] side by side, 11,265 pages of 64 keys on 8
key heads of 64 resting as 4 paired heads of 128): the grouped decode body
at a head of 64 and at 128 paired, and both serve programs.
"""
import jax.numpy as jnp
import pytest

from chip import (ReadsItsMatricesWhereTheyLie, ServedFamily, _compile,
                  _is_one_kernel, _sds)
from deepspeed_tpu.moe.dropless import MOE_DOWN_KERNEL, MOE_GATE_UP_KERNEL
from deepspeed_tpu.ops.pallas.decode_attention import (
    PAGED_DECODE_ATTN_KERNEL, decode_attention_paged)
from deepspeed_tpu.ops.pallas.flash_attention import (FLASH_FWD_CTX_KERNEL,
                                                      FLASH_FWD_KERNEL)


class TestLfm2Moe(ServedFamily, ReadsItsMatricesWhereTheyLie):
    """A tick reads a full layer's pages as they rest, ``[4, 64, 128]``
    (two key heads of 64 a row: no transpose of a pool, every copy whole
    lane tiles); a rung holds the flash forward at the model's own 64 for a
    first chunk and ``ds_flash_fwd_ctx`` for a later one, a layer each; all
    64 experts of a layer under the two expert kernels, a layer each; both
    pools (2.95 GB) and the convolution rows (0.034 GB) pass through
    aliased; 80 % of the chip is arguments and they fit under the issue's
    15.6 GB.  The query projections rest output-major
    (``query_projections``); the tied head reads ``wte`` where it lies."""
    config = "lfm2-24b-a2b"
    at_rest = True
    kernels = {
        "serve_decode": {PAGED_DECODE_ATTN_KERNEL: 2, MOE_GATE_UP_KERNEL: 8,
                         MOE_DOWN_KERNEL: 8},
        "serve_prefill": {FLASH_FWD_KERNEL: 2, FLASH_FWD_CTX_KERNEL: 2,
                          MOE_GATE_UP_KERNEL: 8, MOE_DOWN_KERNEL: 8}}
    fits = 15.6e9
    arguments_share = 0.79
    says_temporaries = {"serve_decode": "temporaries %.3f GB (decode",
                        "serve_prefill": "%.3f GB (the 1,024 rung"}
    unscoped = {"serve_decode": 0.9, "serve_prefill": 2.5}
    # wte and norm_f; 8 conv layers of 4 leaves, 2 full of 7, 2 dense of 4,
    # 8 expert layers' router, bias and norm + the three stacked matrices.
    # ``share`` 0.5: the head's logits [512, 65,536] are a quarter of the
    # embedding's bytes, a dense layer's [512, 11,776] a quarter of W_3's,
    # and are activations
    matrices = {"leaves": 2 + 8 * 4 + 2 * 7 + 2 * 4 + 8 * 3 + 3,
                "share": 0.5, "relaid": True}

    def test_the_tick_writes_the_convolutions_rows_once(self, one_chip):
        """The kept rows are written once, stacked (``walked.shift_tail``),
        and rest in whole tiles (``walked.shift_tail_lanes``: a leaf [8,
        512, 2, 2,048] was copied into tiles of 2 sublanes, a twelfth of the
        tick's estimated cycles)."""
        text = self.program(one_chip, "serve_decode").as_text()
        assert "%st__conv__" in text
        again = [line for line in text.splitlines()
                 if ".remat = " in line and "%st__conv__" in line
                 and "scatter" in line]
        assert not again, again
        assert "bf16[8,512,4096]{2,1,0:T(8,128)(2,1)}" in text

    def test_the_tied_head_reads_the_embedding_where_it_lies(self, one_chip):
        """``x @ wte.T`` is one dot over the last axes of both: no copy or
        transpose of the [65,536, 2,048] table in either program."""
        for program in ("serve_decode", "serve_prefill"):
            text = self.program(one_chip, program).as_text()
            moved = [line for line in text.splitlines()
                     if "bf16[2048,65536]" in line.split(" = ")[-1][:40]]
            assert not moved, moved[:3]

    @pytest.mark.parametrize("heads,width,lowers", [(8, 64, False),
                                                    (4, 128, True)])
    def test_grouped_keys_of_64_lower_only_paired(self, heads, width, lowers,
                                                  one_chip):
        """32 query heads on 8 key heads of 64: the grouped body copies a
        page ``[H, page_len, Dh]`` by hand, and Mosaic slices no HBM array
        along a last dimension under 128; the same bytes as 4 heads of 128
        lower, the pools left where they lie."""
        s, pages = self.spec().slots, 2 * self.spec().pages

        def attend():
            return _compile(
                lambda q, k, v, t, n: decode_attention_paged(
                    q, k, v, t, n, sm_scale=0.125, interpret=False),
                one_chip, _sds((s, 32, width)),
                _sds((pages, heads, 64, width)),
                _sds((pages, heads, 64, width)), _sds((s, 64), jnp.int32),
                _sds((s,), jnp.int32))

        if lowers:
            _is_one_kernel(attend(), PAGED_DECODE_ATTN_KERNEL, 1 << 20)
        else:
            with pytest.raises(Exception, match="aligned to tiling"):
                attend()
