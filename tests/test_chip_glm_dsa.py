"""GLM-5.2 at its cell's sizes, compiled for a described v5e
(``tests/chip.py``; ``benchmark/configs/glm-5.2.json``: 7 layers of latent
attention of which 2 score, 16 of 256 experts held at hidden 6,144, 32
slots, 7,169 pages of 64 rows 640 wide + 2 layers of indexer keys 128 wide
under the same page ids): the indexer's kernel over a whole context, the
sparse kernel over a whole context under the picks' mask, the context
kernel at two rungs, and both serve programs.
"""
import jax
import jax.numpy as jnp
import pytest

from chip import (RestsItsQueryProjectionsOutputMajor, ServedFamily, _compile,
                  _is_one_kernel, _sds)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import context_attention as ca
from deepspeed_tpu.ops.pallas.decode_attention import (
    INDEX_SCORE_KERNEL, SPARSE_LATENT_DECODE_ATTN_KERNEL, index_score,
    latent_pages_per_block, sparse_latent_decode_attention)
from deepspeed_tpu.utils.hlo import parameter_rewrites


class TestGlmDsa(ServedFamily, RestsItsQueryProjectionsOutputMajor):
    """The two paged arrays (4.346 GB) pass through aliased, None where a
    second pool would be.  (``reduced_why`` states the tick's temporaries
    as PR 49's gathering tick had them, 0.123 GB, and the prefill's as the
    XLA loop's, 0.543: a ``benchmark`` PR's to edit, ``PERF.md`` section
    7.)  The prefill's are the expert layer's (a rung's rows gathered for
    8 experts each, 0.2 GB twice) and the picks' mask."""
    config = "glm-5.2"
    kernels = {
        "serve_decode": {dropless.MOE_GATE_UP_KERNEL: 6,
                         dropless.MOE_DOWN_KERNEL: 6, INDEX_SCORE_KERNEL: 2,
                         SPARSE_LATENT_DECODE_ATTN_KERNEL: 7},
        "serve_prefill": {dropless.MOE_GATE_UP_KERNEL: 6,
                          dropless.MOE_DOWN_KERNEL: 6,
                          ca.LATENT_CONTEXT_ATTN_KERNEL: 7}}
    temporaries = {"serve_decode": 0.03e9, "serve_prefill": 0.56e9}
    fits = 16.0e9
    weights = 10.996e9
    unscoped = {"serve_decode": 27.1, "serve_prefill": 13.8}
    relaid = ("q_b_w", 7)

    def test_the_tick_writes_no_layer_of_either_array_again(self, one_chip):
        """A layer of the indexer's keys is 0.117 GB and one of rows 0.587
        (the tick's temporaries, under 0.03 GB, can be a copy of neither);
        in the entry computation (the arrays are its parameters after the
        weights and the tokens) no fusion or copy reads one and writes a
        layer's bytes."""
        compiled = self.program(one_chip, "serve_decode")
        spec = self.spec()
        n = len(jax.tree.leaves(compiled.in_avals[0][0]))
        layer = spec.index_page_bytes // spec.index_layers * spec.pages
        assert layer == 7169 * 64 * 128 * 2
        assert [r for r in parameter_rewrites(compiled.as_text(), n + 3, 0.0)
                if r.parameter in (n + 1, n + 2) and r.bytes >= layer] == []

    def test_index_score_kernel_streams_a_whole_context_of_keys(self,
                                                                one_chip):
        """32 indexer heads against keys 128 wide: 128 pages of 64 a
        block (8,192 keys) inside the module's VMEM budget, three blocks
        to the longest context, the keys left in HBM, float32 scores
        out."""
        assert INDEX_SCORE_KERNEL == "ds_index_score"
        spec = self.spec()
        assert (spec.page_len, spec.index_dim, spec.index_layers) \
            == (64, 128, 2)
        ppb = latent_pages_per_block(64, 128, 2, spec.max_pages)
        assert ppb == 128 and spec.max_pages % ppb == 0
        compiled = _compile(
            lambda q, w, pool, t, n: index_score(q, w, pool, t, n,
                                                 interpret=False),
            one_chip, _sds((spec.slots, 32, 128)),
            _sds((spec.slots, 32), jnp.float32),
            _sds((2 * spec.pages, 64, 128)),
            _sds((spec.slots, spec.max_pages), jnp.int32),
            _sds((spec.slots,), jnp.int32))
        _is_one_kernel(compiled, INDEX_SCORE_KERNEL, 1 << 20)

    def test_sparse_kernel_reads_a_context_under_the_picks_mask(self,
                                                                one_chip):
        """64 heads' [q_lat ; q_rope] against a slot's whole context: the
        latent kernel under the sparse kernel's name walks the pages where
        they lie, 32 pages of 64 rows a grid step, with that block's lanes
        of the mask as one more operand; the pool stays in HBM and the
        temporaries are the mask's ``int32[32 * 12, 1, 2048]`` (3.1 MB)."""
        assert SPARSE_LATENT_DECODE_ATTN_KERNEL \
            == "ds_sparse_latent_decode_attn"
        spec = self.spec()
        s, cap = spec.slots, spec.max_pages * spec.page_len
        assert latent_pages_per_block(64, 640, 2, spec.max_pages) == 32
        compiled = _compile(
            lambda q, pool, t, n, allowed: sparse_latent_decode_attention(
                q, pool, t, n, allowed, 512, sm_scale=0.0625,
                interpret=False),
            one_chip, _sds((s, 64, 640)), _sds((7 * spec.pages, 64, 640)),
            _sds((s, spec.max_pages), jnp.int32), _sds((s,), jnp.int32),
            _sds((s, cap), jnp.bool_))
        _is_one_kernel(compiled, SPARSE_LATENT_DECODE_ATTN_KERNEL,
                       2 * s * cap * 4)

    @pytest.mark.parametrize("rung", [2048, 1024])
    def test_context_kernel_walks_a_chunks_context_where_it_lies(
            self, rung, one_chip):
        """A rung's queries (64 heads, 256 lanes as the keys are laid out)
        against a request's 384 pages of 64 rows 640 wide under the picks'
        mask ``[rung, 24,576]``: 16 pages a grid step and as many heads as
        ``CONTEXT_VMEM_BUDGET`` allows, the step's VMEM and the body's
        allowance inside what a core has beside the compiler's own 24 MiB;
        the pool stays in HBM and the one temporary of size is the mask as
        the kernel reads it (int8)."""
        assert ca.LATENT_CONTEXT_ATTN_KERNEL == "ds_latent_context_attn"
        spec = self.spec()
        cap = spec.max_pages * spec.page_len
        shape = (rung, 256, 256, 640, 512, 1024, 2, True)
        heads = ca.context_heads_per_step(64, ca.CONTEXT_VMEM_BUDGET, *shape)
        assert heads == {2048: 4, 1024: 8}[rung]
        assert ca.context_vmem_bytes(heads, *shape) + ca._BODY_VMEM \
            <= (128 - 24) << 20
        compiled = _compile(
            lambda q, k_w, v_w, pool, ids, pos, n, allowed:
            ca.latent_context_attention(q, k_w, v_w, pool, ids, pos, n,
                                        sm_scale=0.0625, allowed=allowed,
                                        interpret=False),
            one_chip, _sds((64, rung, 256)), _sds((64, 640, 256)),
            _sds((64, 512, 256)), _sds((7 * spec.pages, 64, 640)),
            _sds((spec.max_pages,), jnp.int32), _sds((rung,), jnp.int32),
            _sds((), jnp.int32), _sds((rung, cap), jnp.bool_))
        _is_one_kernel(compiled, ca.LATENT_CONTEXT_ATTN_KERNEL,
                       2 * rung * cap)
