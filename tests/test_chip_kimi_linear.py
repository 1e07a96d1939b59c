"""Kimi Linear at its cell's sizes, compiled for a described v5e
(``tests/chip.py``; ``benchmark/configs/kimi-linear-48b-a3b.json``: 6 KDA
layers + 2 latent ones, 32 of 256 experts held at hidden 2,304, 256 slots
of delta-rule state, 24,577 pages of 64 rows 640 wide over the latent
layers alone): the KDA decode update, and both serve programs.
"""
import jax.numpy as jnp

from chip import (ReadsItsMatricesWhereTheyLie, ServedFamily, _compile,
                  _is_one_kernel, _sds)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.context_attention import \
    LATENT_CONTEXT_ATTN_KERNEL
from deepspeed_tpu.ops.pallas.decode_attention import \
    LATENT_DECODE_ATTN_KERNEL
from deepspeed_tpu.ops.pallas.kda import KDA_DECODE_KERNEL, kda_decode


class TestKimiLinear(ServedFamily, ReadsItsMatricesWhereTheyLie):
    """A tick runs ``ds_kda_decode`` once a KDA layer and the latent
    kernel once a latent layer; the one pool (4.03 GB) and the state
    (3.33 GB) pass through aliased; over 60 % of the chip is arguments.
    The tick's temporaries are the convolutions' tails stacked once
    (0.113 GB) + 9 MB."""
    config = "kimi-linear-48b-a3b"
    family = "kimi-linear"
    kernels = {
        "serve_decode": {dropless.MOE_GATE_UP_KERNEL: 7,
                         dropless.MOE_DOWN_KERNEL: 7, KDA_DECODE_KERNEL: 6,
                         LATENT_DECODE_ATTN_KERNEL: 2},
        "serve_prefill": {dropless.MOE_GATE_UP_KERNEL: 7,
                          dropless.MOE_DOWN_KERNEL: 7,
                          LATENT_CONTEXT_ATTN_KERNEL: 2}}
    temporaries = {"serve_decode": 0.15e9, "serve_prefill": 2.0e9}
    fits = 14.5e9
    arguments_share = 0.6
    says_arguments = ("serve_decode",)
    unscoped = {"serve_decode": 6.4, "serve_prefill": 7.8}
    matrices = {"leaves": 3 + 6 * 12 + 2 * 7 + 4 + 7 * 6 + 3, "share": 0.5}

    def test_the_tick_writes_the_convolutions_tails_once(self, one_chip):
        """The tails are read from the leaf as it came and written once,
        into a buffer of their own: no UPDATE of the donated leaf is an
        instruction the compiler runs a second time (``.remat``; a layer's
        ``.at[i].set`` was, in place, and its second run read the first
        one's rows: wrong from the second tick)."""
        text = self.program(one_chip, "serve_decode").as_text()
        assert "%st__kda_conv__" in text
        again = [line for line in text.splitlines()
                 if ".remat = " in line and "%st__kda_conv__" in line
                 and "scatter" in line]
        assert not again, again

    def test_kda_decode_kernel_keeps_its_name_and_the_state_in_place(
            self, one_chip):
        """256 slots x 6 layers of [32, 128, 128] float32 (3.2 GB) aliased
        through; a grid step's blocks and the body inside the kernel's
        VMEM limit; nothing of the state's size a temporary."""
        assert KDA_DECODE_KERNEL == "ds_kda_decode"
        s, h, d = self.spec().slots, 32, 128
        f32 = jnp.float32
        compiled = _compile(
            lambda st, a, k, v, q, b, act, base: kda_decode(
                st, a, k, v, q, b, act, base=base, interpret=False),
            one_chip, _sds((6 * s, h, d, d), f32), _sds((s, h, d), f32),
            _sds((s, h, d), f32), _sds((s, h, d), f32), _sds((s, h, d), f32),
            _sds((s, h), f32), _sds((s,), jnp.bool_), _sds((), jnp.int32),
            donate=(0,))
        _is_one_kernel(compiled, KDA_DECODE_KERNEL, 4 << 20)
        assert compiled.memory_analysis().alias_size_in_bytes \
            >= 6 * s * h * d * d * 4
