"""Olmo Hybrid at its cell's sizes, compiled for a described v5e
(``tests/chip.py``; ``benchmark/configs/olmo-hybrid-7b.json``: 6
linear_attention layers + 2 full ones at hidden 3,840, 256 slots of
delta-rule state 30 x 96 x 192 at rest [96, 5,760], 3,073 pages of 64 keys
on 30 key heads): the scalar-decay decode update and both serve programs.
"""
import jax.numpy as jnp

from chip import (ReadsItsMatricesWhereTheyLie, ServedFamily, _compile,
                  _is_one_kernel, _sds)
from deepspeed_tpu.ops.pallas.decode_attention import PAGED_DECODE_ATTN_KERNEL
from deepspeed_tpu.ops.pallas.flash_attention import (FLASH_FWD_CTX_KERNEL,
                                                      FLASH_FWD_KERNEL)
from deepspeed_tpu.ops.pallas.kda import GDN_DECODE_KERNEL, gdn_decode


class TestOlmoHybrid(ServedFamily, ReadsItsMatricesWhereTheyLie):
    """A tick reads a full layer's pages as they rest, ``[30, 64, 128]``
    (no transpose of a pool); a rung holds the flash forward for a first
    chunk and ``ds_flash_fwd_ctx`` for a later one, a layer each; both
    pools (6.04 GB) and the state (3.50 GB) pass through aliased (a chunk
    gathers its request's pages out of the flat pool: a layer sliced out
    first was 1.5 GB); over 80 % of the chip is arguments and they fit
    under the issue's 15.6 GB: no page had to go.  No leaf asks for
    another form at rest (``query_projections`` names none)."""
    config = "olmo-hybrid-7b"
    kernels = {
        "serve_decode": {GDN_DECODE_KERNEL: 6, PAGED_DECODE_ATTN_KERNEL: 2},
        "serve_prefill": {FLASH_FWD_KERNEL: 2, FLASH_FWD_CTX_KERNEL: 2}}
    fits = 15.6e9
    arguments_share = 0.8
    says_temporaries = {"serve_decode": "temporaries %.3f GB (decode",
                        "serve_prefill": "%.3f GB (the 1,024 rung"}
    unscoped = {"serve_decode": 2.1, "serve_prefill": 1.7}
    matrices = {"leaves": 3 + 6 * 10 + 2 * 7 + 8 * 4, "share": 0.5}

    def test_the_tick_writes_the_convolutions_tails_once(self, one_chip):
        """The tails are written once, stacked (``walked.shift_tail``)."""
        text = self.program(one_chip, "serve_decode").as_text()
        assert "%st__gdn_conv__" in text
        again = [line for line in text.splitlines()
                 if ".remat = " in line and "%st__gdn_conv__" in line
                 and "scatter" in line]
        assert not again, again

    def test_gdn_decode_kernel_keeps_its_name_and_the_state_in_place(
            self, one_chip):
        """256 slots x 6 layers of [96, 5,760] float32 aliased through:
        the state rests with NO padding (45 lane tiles a row, 12 sublane
        tiles: the compiler's own count of the leaf is the published 30 x
        96 x 192 x 4 B a slot and layer), a grid step's blocks and the
        body inside the kernel's VMEM limit, nothing of the state's size a
        temporary."""
        assert GDN_DECODE_KERNEL == "ds_gdn_decode"
        s, h, dk, dv = self.spec().slots, 30, 96, 192
        f32 = jnp.float32
        compiled = _compile(
            lambda st, a, k, v, q, b, act, base: gdn_decode(
                st, a, k, v, q, b, act, base=base, interpret=False),
            one_chip, _sds((6 * s, dk, h * dv), f32), _sds((s, h), f32),
            _sds((s, h, dk), f32), _sds((s, h, dv), f32),
            _sds((s, h, dk), f32), _sds((s, h), f32), _sds((s,), jnp.bool_),
            _sds((), jnp.int32), donate=(0,))
        _is_one_kernel(compiled, GDN_DECODE_KERNEL, 4 << 20)
        assert compiled.memory_analysis().alias_size_in_bytes \
            == 6 * s * 2211840
        assert f"f32[{6 * s},{dk},{h * dv}]{{2,1,0:T(8,128)}}" \
            in compiled.as_text()
