"""dots3-note-prev at its cell's sizes, compiled for a described v5e
(``tests/chip.py``; ``benchmark/configs/dots3-note-prev.json``: a dense
full layer and two periods of three sliding layers and a full one, 16 of
256 experts held, 128 slots of six rings of 576 x 1,152, 12,289 pages of
latent rows 640 wide with the indexer's keys beside them): the ring
kernel, the decode tick and the prefill rungs.
"""
import jax
import jax.numpy as jnp

from chip import ServedFamily, _compile, _is_one_kernel, _sds, served_model
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas.context_attention import \
    LATENT_CONTEXT_ATTN_KERNEL
from deepspeed_tpu.ops.pallas.decode_attention import (
    INDEX_SCORE_KERNEL, PAGED_KV_VMEM_BUDGET,
    SPARSE_LATENT_DECODE_ATTN_KERNEL, WINDOW_LATENT_DECODE_ATTN_KERNEL,
    ring_granule, window_latent_decode_attention)
from deepspeed_tpu.ops.pallas.flash_attention import (FLASH_FWD_CTX_KERNEL,
                                                      FLASH_FWD_KERNEL)
from deepspeed_tpu.utils.hlo import parameter_rewrites


class TestDots3Note(ServedFamily):
    """The rings (1.019 GB), the pool and the indexer's keys (3.624 GB)
    pass through aliased, None where a second pool would be; its programs
    are compiled with the query projections at rest as the engine holds
    them."""
    config = "dots3-note-prev"
    at_rest = True
    kernels = {
        "serve_decode": {dropless.MOE_GATE_UP_KERNEL: 8,
                         dropless.MOE_DOWN_KERNEL: 8, INDEX_SCORE_KERNEL: 3,
                         SPARSE_LATENT_DECODE_ATTN_KERNEL: 3,
                         WINDOW_LATENT_DECODE_ATTN_KERNEL: 6},
        "serve_prefill": {dropless.MOE_GATE_UP_KERNEL: 8,
                          dropless.MOE_DOWN_KERNEL: 8,
                          LATENT_CONTEXT_ATTN_KERNEL: 3, FLASH_FWD_KERNEL: 6,
                          FLASH_FWD_CTX_KERNEL: 6}}
    temporaries = {"serve_decode": 0.06e9, "serve_prefill": 0.5e9}
    fits = 16.0e9
    weights = 9.207e9
    unscoped = {"serve_decode": 3.4, "serve_prefill": 23.5}

    def test_the_tick_copies_no_weight_and_no_cache(self, one_chip):
        """A layer's rings are 0.170 GB, a layer of rows 1.007 (the tick's
        temporaries, under 0.06 GB, can be a copy of neither); no weight
        is laid out again, and no fusion or copy of the entry computation
        reads a cache (its parameters after the weights and the tokens)
        and writes a layer of keys' bytes."""
        compiled = self.program(one_chip, "serve_decode")
        spec = self.spec()
        n = len(jax.tree.leaves(compiled.in_avals[0][0]))
        text = compiled.as_text()
        assert parameter_rewrites(text, n, 0.5) == []
        layer = spec.index_page_bytes // spec.index_layers * spec.pages
        assert layer == 12289 * 64 * 128 * 2
        assert [r for r in parameter_rewrites(text, n + 4, 0.0)
                if r.parameter > n and r.bytes >= layer] == []

    def test_window_latent_kernel_reads_a_slots_ring_in_one_grid_step(
            self, one_chip):
        """64 heads' [q_lat ; q_rope] against each slot's ring of 576 rows
        of 1,152 lanes, six layers' rings stacked: the latent kernel's
        body under a name of its own, all nine granules of 64 rows one
        grid step (2.65 MB the double buffer, inside the module's VMEM
        budget), the rings left in HBM."""
        assert WINDOW_LATENT_DECODE_ATTN_KERNEL \
            == "ds_window_latent_decode_attn"
        cfg, s = served_model(self.config)[0].config, self.spec().slots
        rows, width = cfg.ring_rows, cfg.window.row
        assert (rows, width, ring_granule(rows)) == (576, 1152, 64)
        assert 2 * rows * width * 2 <= PAGED_KV_VMEM_BUDGET
        compiled = _compile(
            lambda q, rings, n: window_latent_decode_attention(
                q, rings, n, cfg.window.kv_rank, base=2 * s,
                sm_scale=0.0625, interpret=False),
            one_chip, _sds((s, 64, width)), _sds((6 * s, rows, width)),
            _sds((s,), jnp.int32))
        _is_one_kernel(compiled, WINDOW_LATENT_DECODE_ATTN_KERNEL, 1 << 20)
