"""The main path's kernels and serve programs, compiled for a TPU v5e
that is described and not attached — at GPT-2 124M and GPT-2 XL widths.
A whole program is compiled once and kept (``functools.cache`` on its
builder): the tests that read it, and the ceiling on what no scope of the
layer map owns at the end of the file, share one compile.

Nothing runs: this guards against what interpret mode cannot see (tile
alignment, scalar-prefetch and VMEM budgets, unsupported lowerings) at no
chip time.  A compile that passes is not a chip run.

All of these live in this ONE file, and the topology is described only
inside the module-scoped fixture below: the TPU library is loaded by the
first test that runs, in the one xdist worker that owns this file, and
never while a module is imported.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.ops.pallas.decode_attention import (
    PAGED_DECODE_ATTN_KERNEL, PAGED_KV_VMEM_BUDGET, decode_attention,
    decode_attention_multi, decode_attention_paged,
    decode_attention_paged_multi, paged_decode_arm, paged_page_vmem_bytes,
    paged_pages_per_block)
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.runtime import interpret_scope
from deepspeed_tpu.utils.hlo import (UNSCOPED, less_metadata, rng_fusions,
                                     scope_cycles, scopes)

KERNEL = "tpu_custom_call"
BF16 = jnp.bfloat16
SLOTS, SEQ, DH = 8, 1024, 64
GPT2_124M = GPT2Config(d_model=768, n_layer=12, n_head=12, vocab_size=50257,
                       n_positions=1024, attn_impl="flash")


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host.  The persistent compilation cache is off
    meanwhile: a program compiled for a described device is written to it
    but can never be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; the program must hold a
    Mosaic kernel."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    compiled = jax.jit(fn).lower(*args).compile()
    assert KERNEL in compiled.as_text()
    return compiled


def _program_args(shapes, one_chip, model=None):
    """``shapes`` (a step's operands, the params first) on the described
    chip; with ``model``, the params as ``ServeEngine`` holds them
    (``inference/engine.py::params_at_rest``: each leaf in the form the
    model declares for it at rest)."""
    if model is not None:
        params = shapes[0]
        held = jax.tree.map(
            lambda a, form: a if form is None else jax.eval_shape(form.of, a),
            params, model.serving_layouts(params))
        shapes = (held,) + tuple(shapes[1:])
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), shapes)


def _sds(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


#: A program's temporaries in GB as the compiler counts them since PR 58,
#: where a configuration's ``reduced_why`` still states the count from
#: before it (the routing plan's and the router's own arrays moved each:
#: A.X-K1's tick 0.045 -> 0.043, Command A+'s 0.149 -> 0.142 and its
#: chunk 1.055 -> 1.053, Kimi Linear's tick 0.122 -> 0.136 and its rung
#: 1.096 -> 0.965).
#: The text is a ``benchmark`` PR's to edit (``PERF.md`` section 7); that
#: PR puts ``"... %.3f GB" % ... in reduced_why`` back in place of this
#: table.  Until then the text is held as it stands and the count here.
TEMPORARIES_GB = {
    ("a.x-k1", "serve_decode"): ("temporaries 0.045 GB (decode)", "0.043"),
    ("command-a-plus", "serve_decode"): ("temporaries 0.149 GB (decode)",
                                         "0.142"),
    ("command-a-plus", "serve_prefill"): ("1.055 GB (a chunk of 4,096",
                                          "1.053"),
    ("kimi-linear", "serve_decode"): ("temporaries 0.122 GB (decode",
                                      "0.136"),
    ("kimi-linear", "serve_prefill"): ("1.096 GB (prefill", "0.965"),
}


def _holds_its_temporaries(mem, file, family, program):
    said, compiled = TEMPORARIES_GB[family, program]
    assert said in file["reduced_why"]
    assert "%.3f" % (mem.temp_size_in_bytes / 1e9) == compiled


# ---------------------------------------------------------------------------
# flash attention, forward and backward, at the train smoke's shape
# ---------------------------------------------------------------------------

def _flash_fwd(one_chip, rows=16):
    qkv = [_sds((rows, 12, SEQ, DH))] * 3
    return _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                    interpret=False),
                    one_chip, *qkv)


def _flash_bwd(one_chip, rows=16, wrap=lambda f: f):
    """``wrap=jax.checkpoint`` differentiates as the models do; the
    default is the bare ``jax.grad``."""
    qkv = [_sds((rows, 12, SEQ, DH))] * 3

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    return _compile(jax.grad(wrap(loss), argnums=(0, 1, 2)), one_chip,
                    *qkv)


def test_flash_forward_compiles(one_chip):
    _flash_fwd(one_chip)


def test_flash_backward_compiles(one_chip):
    _flash_bwd(one_chip)


def _flash_grad_on_four_chips(topo, attend, *, dp=1, sp=1, tp=1):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel import build_mesh
    mesh = build_mesh(dp=dp, sp=sp, tp=tp, devices=topo.devices)

    def loss(q, k, v):
        return attend(q, k, v).astype(jnp.float32).sum()

    with jax.set_mesh(mesh):
        _compile(jax.grad(loss, argnums=(0, 1, 2)),
                 NamedSharding(mesh, P("data", "model")),
                 *[_sds((16, 12, SEQ, DH))] * 3)


@pytest.mark.parametrize("dp,tp", [(4, 1), (2, 2)], ids=["dp4", "dp2xtp2"])
def test_flash_backward_compiles_on_four_chips(topo, dp, tp):
    """Rows over four chips, as under dp=4 ZeRO or dp x tp: GSPMD refuses
    to partition a bare Mosaic call, so the models call the kernel
    through ``sharded_flash_attention``, inside a shard_map
    (chip_smoke.py --chips 4 runs the dp4 path)."""
    from deepspeed_tpu.parallel.attention import sharded_flash_attention
    _flash_grad_on_four_chips(
        topo, lambda q, k, v: sharded_flash_attention(
            q, k, v, causal=True, interpret=False), dp=dp, tp=tp)


def test_flash_nested_in_a_partial_shard_map_is_still_refused(topo):
    """The known limit, pinned: Ulysses is manual over 'seq' only, and
    with 'data' larger than one its flash call is refused on real chips,
    bare or under a second shard_map over the remaining axes
    (parallel/mesh.py ``_kernel_mesh``).  Sequence parallelism beside
    data parallelism on chips needs the enclosing shard_map to be manual
    over the whole mesh; when that lands this test turns into a compile."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.parallel import ulysses_attention
    seq = P(None, None, "seq", None)

    def attend(q, k, v):
        with interpret_scope(False):
            return jax.shard_map(
                lambda a, b, c: ulysses_attention(a, b, c, causal=True),
                in_specs=(seq, seq, seq), out_specs=seq,
                axis_names={"seq"}, check_vma=False)(q, k, v)

    with pytest.raises(NotImplementedError, match="Mosaic kernels cannot"):
        _flash_grad_on_four_chips(topo, attend, dp=2, sp=2)


# ---------------------------------------------------------------------------
# the four decode arms at 124M (12) and XL (25) head counts
# ---------------------------------------------------------------------------

def _paged_shapes(heads, page_len, quant):
    pages, max_pages = 1 + SLOTS * (SEQ // page_len), SEQ // page_len
    pool = _sds((pages, heads, page_len, DH), jnp.int8 if quant else BF16)
    scale = _sds((pages, heads, page_len), jnp.float32)
    table = _sds((SLOTS, max_pages), jnp.int32)
    return pool, scale, table


def _slot(heads, one_chip):
    cache = _sds((SLOTS, heads, SEQ, DH))
    return _compile(
        lambda q, k, v, n: decode_attention(q, k, v, n, interpret=False),
        one_chip, _sds((SLOTS, heads, DH)), cache, cache,
        _sds((SLOTS,), jnp.int32))


def _multi(heads, one_chip, w=5):
    cache = _sds((SLOTS, heads, SEQ, DH))
    return _compile(
        lambda q, k, v, n: decode_attention_multi(q, k, v, n,
                                                  interpret=False),
        one_chip, _sds((SLOTS, heads, w, DH)), cache, cache,
        _sds((SLOTS, w), jnp.int32))


def _paged(heads, one_chip, page_len, quant=False):
    pool, scale, table = _paged_shapes(heads, page_len, quant)

    def fn(q, k, v, t, n, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return decode_attention_paged(q, k, v, t, n, interpret=False, **kw)

    return _compile(
        fn, one_chip, _sds((SLOTS, heads, DH)), pool, pool, table,
        _sds((SLOTS,), jnp.int32), *([scale, scale] if quant else []))


def _paged_multi(heads, one_chip, page_len, quant=False, w=5):
    pool, scale, table = _paged_shapes(heads, page_len, quant)

    def fn(q, k, v, t, n, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return decode_attention_paged_multi(q, k, v, t, n, interpret=False,
                                            **kw)

    return _compile(
        fn, one_chip, _sds((SLOTS, heads, w, DH)), pool, pool, table,
        _sds((SLOTS, w), jnp.int32), *([scale, scale] if quant else []))


ARMS = {
    "slot": _slot,
    "multi": _multi,
    "paged16": lambda h, c: _paged(h, c, 16),
    "paged64": lambda h, c: _paged(h, c, 64),
    "paged128": lambda h, c: _paged(h, c, 128),
    "paged_multi16": lambda h, c: _paged_multi(h, c, 16),
    "int8_paged16": lambda h, c: _paged(h, c, 16, quant=True),
    "int8_paged128": lambda h, c: _paged(h, c, 128, quant=True),
    "int8_paged_multi64": lambda h, c: _paged_multi(h, c, 64, quant=True),
}


@pytest.mark.parametrize("heads", [12, 25], ids=["gpt2_124m", "gpt2_xl"])
@pytest.mark.parametrize("arm", sorted(ARMS))
def test_decode_arm_compiles(arm, heads, one_chip):
    ARMS[arm](heads, one_chip)


#: what a Mosaic kernel may use of a v5e's VMEM by default
V5E_SCOPED_VMEM = 16 * 1024 * 1024


@pytest.mark.parametrize("heads", [12, 25], ids=["gpt2_124m", "gpt2_xl"])
@pytest.mark.parametrize("page_len", [16, 64, 128])
def test_paged_block_kernel_fits_and_keeps_its_name(page_len, heads,
                                                    one_chip):
    """The fp paged arm's block of pages: chosen from the shapes, its
    pages within the module's VMEM budget (and the budget well within
    what the chip allows: the compile is the proof), one Mosaic call,
    and still the trace row ``paged_decode_share.*`` reads."""
    ppb = paged_pages_per_block(heads, page_len, DH, 2, SEQ // page_len)
    assert ppb == {(12, 16): 16, (12, 64): 4, (12, 128): 2,
                   (25, 16): 8, (25, 64): 2, (25, 128): 1}[heads, page_len]
    assert (ppb * paged_page_vmem_bytes(heads, page_len, DH, 2)
            <= PAGED_KV_VMEM_BUDGET <= V5E_SCOPED_VMEM // 2)
    names = _kernel_names(_paged(heads, one_chip, page_len))
    assert [n.split(".")[0] for n in names] == [PAGED_DECODE_ATTN_KERNEL]


# ---------------------------------------------------------------------------
# the serve programs' model entry points at 124M widths, chip_smoke's sizes
# ---------------------------------------------------------------------------

PAGE_LEN = 16
MAX_PAGES = SEQ // PAGE_LEN


def _serve_shapes():
    """(model, bf16 param shapes, one layer-stacked K or V pool)."""
    model = GPT2Model(GPT2_124M)
    params = jax.tree.map(lambda s: _sds(s.shape),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = _sds((GPT2_124M.n_layer, 1 + SLOTS * MAX_PAGES,
                 GPT2_124M.n_head, PAGE_LEN, DH))
    return model, params, pool


@functools.cache
def _gpt2_prefill_program(one_chip, bucket=128):
    model, params, pool = _serve_shapes()
    i32 = _sds((), jnp.int32)
    with interpret_scope(False):
        return _compile(model.prefill_paged, one_chip, params,
                        _sds((1, bucket), jnp.int32), i32, i32,
                        _sds((MAX_PAGES,), jnp.int32), pool, pool)


@functools.cache
def _gpt2_decode_program(one_chip):
    model, params, pool = _serve_shapes()
    with interpret_scope(False):
        return _compile(
            lambda *a: model.decode_step_paged(*a, impl="pallas"),
            one_chip, params, _sds((SLOTS,), jnp.int32), pool, pool,
            _sds((SLOTS, MAX_PAGES), jnp.int32),
            _sds((SLOTS,), jnp.int32), _sds((SLOTS,), jnp.bool_))


@pytest.mark.parametrize("bucket", [128, 1024])
def test_prefill_paged_compiles(bucket, one_chip):
    _gpt2_prefill_program(one_chip, bucket)


def test_decode_step_paged_compiles(one_chip):
    _gpt2_decode_program(one_chip)


# ---------------------------------------------------------------------------
# kernel names: every Mosaic call is ``ds_<kernel>.<n>`` in the compiled
# program, whatever JAX construct wraps it — the device trace's row names
# and the benchmark's per-kernel shares (benchmark/metrics/*_share.*.json)
# rest on it
# ---------------------------------------------------------------------------

def _kernel_names(compiled):
    """Instruction names of the program's Mosaic custom calls."""
    import re
    return re.findall(
        r'^\s*(?:ROOT )?%?(\S+) = [^\n]*custom_call_target="' + KERNEL + '"',
        compiled.as_text(), flags=re.M)


def _sparse(one_chip, grad):
    import numpy as np
    from deepspeed_tpu.ops.pallas.block_sparse_attention import \
        block_sparse_attention
    block, nb, heads = 128, 4, 4
    layout = np.tril(np.ones((heads, nb, nb), np.int32))

    def fwd(q, k, v):
        return block_sparse_attention(q, k, v, layout, block,
                                      interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    qkv = [_sds((2, heads, block * nb, DH))] * 3
    return _compile(
        jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2)) if grad else fwd,
        one_chip, *qkv)


def _flash_bwd_remat(one_chip):
    """Differentiated as the models do it, under ``jax.checkpoint``: a
    transformation names what it traces DIRECTLY after itself
    (``jvp(ds_flash_fwd)``), and any closed jaxpr between the two
    (remat, a layer scan, shard_map, cond) keeps the kernel's own name
    innermost."""
    return _flash_bwd(one_chip, 4, wrap=jax.checkpoint)


def _arm(arm):
    return lambda one_chip: ARMS[arm](25, one_chip)


KERNEL_CASES = {
    # constant name -> (its module under ops/pallas, program builder)
    "DECODE_ATTN_KERNEL": ("decode_attention", _arm("slot")),
    "PAGED_DECODE_ATTN_KERNEL": ("decode_attention", _arm("paged16")),
    "PAGED_DECODE_ATTN_INT8_KERNEL":
        ("decode_attention", _arm("int8_paged16")),
    "WINDOW_DECODE_ATTN_KERNEL":
        ("decode_attention", lambda c: _window_decode(c)),
    "DECODE_ATTN_MULTI_KERNEL": ("decode_attention", _arm("multi")),
    "PAGED_DECODE_ATTN_MULTI_KERNEL":
        ("decode_attention", _arm("paged_multi16")),
    "PAGED_DECODE_ATTN_MULTI_INT8_KERNEL":
        ("decode_attention", _arm("int8_paged_multi64")),
    "FLASH_FWD_KERNEL": ("flash_attention", lambda c: _flash_fwd(c, 4)),
    "FLASH_BWD_DQ_KERNEL": ("flash_attention", _flash_bwd_remat),
    "FLASH_BWD_DKV_KERNEL": ("flash_attention", _flash_bwd_remat),
    "SPARSE_FWD_KERNEL":
        ("block_sparse_attention", lambda c: _sparse(c, grad=False)),
    "SPARSE_BWD_DQ_KERNEL":
        ("block_sparse_attention", lambda c: _sparse(c, grad=True)),
    "SPARSE_BWD_DKV_KERNEL":
        ("block_sparse_attention", lambda c: _sparse(c, grad=True)),
}


@pytest.mark.parametrize("constant", sorted(KERNEL_CASES))
def test_kernel_carries_its_name(constant, one_chip):
    """The compiled program holds a Mosaic call whose instruction name
    starts with the kernel's module-level constant, and the constant
    carries the common ``ds_`` prefix."""
    import importlib
    module, build = KERNEL_CASES[constant]
    name = getattr(importlib.import_module(
        "deepspeed_tpu.ops.pallas." + module), constant)
    assert name.startswith("ds_")
    names = _kernel_names(build(one_chip))
    assert names, "no Mosaic call found in the program text"
    # exact name up to the compiler's ``.<n>`` suffix: the int8 and
    # multi arms extend the base names, so a prefix test would let the
    # wrong body pass
    assert any(n.split(".")[0] == name for n in names), names


def test_bare_grad_wraps_the_kernel_name(one_chip):
    """The known limit, pinned: with no closed jaxpr between ``jax.grad``
    and the kernel, the instruction is named after the transformation
    and the benchmark's ``unnamed_kernel_share.*`` counts it.  Every
    model path has a layer scan, remat or shard_map in between."""
    names = _kernel_names(_flash_bwd(one_chip, 4))
    assert sorted(n.split(".")[0] for n in names) == [
        "jvp_ds_flash_fwd_", "transpose_jvp_ds_flash_bwd_dkv__",
        "transpose_jvp_ds_flash_bwd_dq__"], names


@functools.cache
def _gpt2_train_program(one_chip):
    """Forward + backward of GPT-2 124M's widths through the flash
    kernel, two layers deep under remat and the layer scan (the scan
    body compiles once, so depth adds nothing to check)."""
    import dataclasses
    model = GPT2Model(dataclasses.replace(GPT2_124M, n_layer=2))
    params = jax.tree.map(lambda s: _sds(s.shape),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))

    def step(params, tokens, rng):
        return jax.value_and_grad(model.loss_fn)(params, tokens, rng)

    with interpret_scope(False):
        return _compile(step, one_chip, params,
                        _sds((4, SEQ + 1), jnp.int32),
                        _sds((2,), jnp.uint32))


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill",
                                     "train"])
def test_gpt2_programs_hold_no_unnamed_kernel(program, one_chip):
    """Inside scan, remat, cond and custom_vjp alike, no Mosaic call of
    the GPT-2 programs is named after a JAX construct (``closed_call``,
    ``checkpoint``, ``branch_1_fun``, ...): each starts with ``ds_``."""
    build = {"serve_decode": _gpt2_decode_program,
             "serve_prefill": _gpt2_prefill_program,
             "train": _gpt2_train_program}[program]
    names = _kernel_names(build(one_chip))
    assert names
    assert all(n.startswith("ds_") for n in names), names


# ---------------------------------------------------------------------------
# OLMoE at its published widths (benchmark/configs/olmoe-1b-7b.json): the
# expert layer's two kernels, the paged decode kernel at head size 128, and
# both serve programs, two layers deep (the scan body compiles once)
# ---------------------------------------------------------------------------

OLMOE_SLOTS, OLMOE_HEADS, OLMOE_DH, OLMOE_MAX_PAGES = 64, 16, 128, 128


def _olmoe_shapes(pages=1 + 4 * OLMOE_MAX_PAGES):
    from deepspeed_tpu.models.olmoe import OlmoeConfig, OlmoeModel
    cfg = OlmoeConfig(num_hidden_layers=2, param_dtype="bfloat16")
    model = OlmoeModel(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = _sds((2, pages, OLMOE_HEADS, PAGE_LEN, OLMOE_DH))
    return model, params, pool


@functools.cache
def _olmoe_decode_program(one_chip):
    model, params, pool = _olmoe_shapes()
    s = OLMOE_SLOTS
    with interpret_scope(False):
        return _compile(
            lambda *a: model.decode_step_paged(*a, impl="pallas", aux=True),
            one_chip, params, _sds((s,), jnp.int32), pool, pool,
            _sds((s, OLMOE_MAX_PAGES), jnp.int32), _sds((s,), jnp.int32),
            _sds((s,), jnp.bool_))


@functools.cache
def _olmoe_prefill_program(one_chip, bucket=1024):
    model, params, pool = _olmoe_shapes()
    i32 = _sds((), jnp.int32)
    with interpret_scope(False):
        return _compile(
            lambda *a: model.prefill_paged(*a, aux=True), one_chip, params,
            _sds((1, bucket), jnp.int32), i32, i32,
            _sds((OLMOE_MAX_PAGES,), jnp.int32), pool, pool)


def test_paged_decode_kernel_at_head_128_keeps_its_name(one_chip):
    """The direct arm at the cell's own shapes (benchmark/configs/
    olmoe-1b-7b.json: 64 slots, 128 table entries, 12 layers' pages in
    one row of 12 x 3,457, a page as it rests ``[16, 16, 128]``): chosen
    from the pool's shape, its double buffer inside the module's VMEM
    budget, the pools left in HBM (no temporary of any size that a copy
    of a pool would be), and still the one trace row
    ``paged_decode_share.*`` reads."""
    shape = (OLMOE_HEADS, PAGE_LEN, OLMOE_DH, 2)
    assert paged_decode_arm(*shape) == "direct"
    ppb = paged_pages_per_block(*shape, OLMOE_MAX_PAGES)
    assert ppb == 16
    # K and V, two halves each, and nothing packed
    assert paged_page_vmem_bytes(*shape) == 4 * PAGE_LEN * OLMOE_HEADS \
        * OLMOE_DH * 2
    assert ppb * paged_page_vmem_bytes(*shape) <= PAGED_KV_VMEM_BUDGET
    flat = _sds((12 * 3457, PAGE_LEN, OLMOE_HEADS, OLMOE_DH))
    compiled = _compile(
        lambda q, k, v, t, n: decode_attention_paged(
            q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), t, n,
            interpret=False),
        one_chip, _sds((OLMOE_SLOTS, OLMOE_HEADS, OLMOE_DH)), flat, flat,
        _sds((OLMOE_SLOTS, OLMOE_MAX_PAGES), jnp.int32),
        _sds((OLMOE_SLOTS,), jnp.int32))
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [PAGED_DECODE_ATTN_KERNEL]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("tokens", [64, 1024], ids=["decode_tick",
                                                    "prefill_bucket"])
def test_moe_kernels_carry_their_names(tokens, one_chip):
    """64 experts of 2048 x 1024, top-8: rows of 16 at a decode tick, of
    128 at a prefill; an expert's matrices are one block each (16 MiB in
    flight: the kernels raise Mosaic's VMEM limit, and the compile is the
    proof that the chip allows it)."""
    from deepspeed_tpu.moe import dropless
    assert dropless.MOE_GATE_UP_KERNEL == "ds_moe_gate_up"
    assert dropless.MOE_DOWN_KERNEL == "ds_moe_down"
    d, f, e = 2048, 1024, 64
    compiled = _compile(
        lambda x, r, g, u, w: dropless.dropless_moe(
            x, r, g, u, w, 8, expert_offset=jnp.int32(e),
            interpret=False)[0],
        one_chip, _sds((tokens, d)), _sds((d, e)), _sds((2 * e, d, f)),
        _sds((2 * e, d, f)), _sds((2 * e, f, d)))
    names = sorted(n.split(".")[0] for n in _kernel_names(compiled))
    assert names == [dropless.MOE_DOWN_KERNEL, dropless.MOE_GATE_UP_KERNEL]


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_olmoe_programs_hold_their_named_kernels(program, one_chip):
    """Every Mosaic call of OLMoE's two serve programs starts ``ds_``
    (what ``unnamed_kernel_share.saturated`` reads as 0), and the pools
    pass through the layer scan without a copy of either: the program's
    temporaries are far smaller than one pool."""
    build, attn = {"serve_decode": (_olmoe_decode_program,
                                    PAGED_DECODE_ATTN_KERNEL),
                   "serve_prefill": (_olmoe_prefill_program,
                                     "ds_flash_fwd")}[program]
    compiled = build(one_chip)
    names = {n.split(".")[0] for n in _kernel_names(compiled)}
    assert names == {"ds_moe_gate_up", "ds_moe_down", attn}, names
    pool_bytes = 2 * (1 + 4 * OLMOE_MAX_PAGES) * OLMOE_HEADS * PAGE_LEN \
        * OLMOE_DH * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    limit = pool_bytes // 4 if program == "serve_decode" else None
    assert limit is None or temp < limit, (temp, pool_bytes)


# ---------------------------------------------------------------------------
# Nemotron-3 Super at its published widths and the cell's own shapes
# (benchmark/configs/nemotron-3-super-120b-a12b.json: MEMEM*EMEME, 128 of
# 512 experts held, 192 slots of recurrent state, 24,577 pages of 2 key
# heads): the Mamba-2 decode update, the relu2 expert kernels, the paged
# decode kernel at 32 query heads on 2 key heads, and both serve programs
# ---------------------------------------------------------------------------

NEMO_SLOTS, NEMO_PAGES, NEMO_MAX_PAGES = 192, 24577, 320


def _nemotron():
    from deepspeed_tpu.models.nemotron_h import (NemotronHConfig,
                                                 NemotronHModel)
    model = NemotronHModel(NemotronHConfig(
        vocab_size=32768, experts_held=(0, 128), param_dtype="bfloat16"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = _sds((1, NEMO_PAGES, 2, PAGE_LEN, 128))
    return model, params, pool, model.serving_state(NEMO_SLOTS)


@functools.lru_cache(maxsize=None)
@functools.cache
def _nemotron_program(program, one_chip, bucket=1024):
    """The model's paged step as the engine calls it: pools and state
    donated; a prefill at ``bucket`` tokens."""
    model, params, pool, state = _nemotron()
    i32, s = _sds((), jnp.int32), NEMO_SLOTS
    if program == "serve_decode":
        def fn(p, t, k, v, tab, ln, act, st):
            return model.decode_step_paged(p, t, k, v, tab, ln, act,
                                           state=st, impl="pallas", aux=True)
        shapes = (params, _sds((s,), jnp.int32), pool, pool,
                  _sds((s, NEMO_MAX_PAGES), jnp.int32), _sds((s,), jnp.int32),
                  _sds((s,), jnp.bool_), state)
        donate = (2, 3, 7)
    else:
        def fn(p, t, n, row, k, v, st, slot):
            return model.prefill_paged(p, t, n, jnp.int32(0), row, k, v,
                                       state=st, slot=slot, aux=True)
        shapes = (params, _sds((1, bucket), jnp.int32), i32,
                  _sds((NEMO_MAX_PAGES,), jnp.int32), pool, pool, state, i32)
        donate = (4, 5, 6)
    args = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), shapes)
    with interpret_scope(False):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_nemotron_programs_hold_their_kernels_and_no_copy_of_the_state(
        program, one_chip):
    """Every Mosaic call of both serve programs starts ``ds_``; the 4 GB
    of recurrent state (and the pools) pass through aliased to the
    outputs, and the program's temporaries are far smaller than the
    state: no copy of it, in a decode tick (``ds_ssm_decode`` rewrites it
    where it lies) or in a prefill (one slot's rows are written)."""
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.pallas.ssm import SSM_DECODE_KERNEL
    compiled = _nemotron_program(program, one_chip)
    names = {n.split(".")[0] for n in _kernel_names(compiled)}
    experts = {dropless.MOE_UP_RELU2_KERNEL, dropless.MOE_DOWN_KERNEL}
    assert names == experts | (
        {SSM_DECODE_KERNEL, PAGED_DECODE_ATTN_KERNEL}
        if program == "serve_decode" else {"ds_flash_fwd"}), names
    mem = compiled.memory_analysis()
    state_bytes = 5 * NEMO_SLOTS * 128 * 64 * 128 * 4
    pools = 2 * NEMO_PAGES * 2 * PAGE_LEN * 128 * 2
    assert mem.alias_size_in_bytes >= state_bytes + pools
    assert mem.temp_size_in_bytes < state_bytes // 8, mem.temp_size_in_bytes
    # everything the chip must hold at once fits its 16.91e9 bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9


def test_ssm_decode_kernel_keeps_its_name_and_the_state_in_place(one_chip):
    from deepspeed_tpu.ops.pallas.ssm import SSM_DECODE_KERNEL, ssm_decode
    assert SSM_DECODE_KERNEL == "ds_ssm_decode"
    s, h, p, n, g = NEMO_SLOTS, 128, 64, 128, 8
    f32 = jnp.float32
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (_sds((5 * s, h, p, n), f32), _sds((s, h), f32),
         _sds((s, h, p), f32), _sds((s, g, n), f32), _sds((s, g, n), f32),
         _sds((s,), jnp.bool_), _sds((), jnp.int32)))
    compiled = jax.jit(
        lambda st, d, x, b, c, act, base: ssm_decode(
            st, d, x, b, c, act, base=base, interpret=False),
        donate_argnums=(0,)).lower(*args).compile()
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [SSM_DECODE_KERNEL]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 5 * s * h * p * n * 4
    assert mem.temp_size_in_bytes < 4 << 20


def test_paged_decode_kernel_at_32_on_2_heads_keeps_its_name(one_chip):
    """Grouped keys at the cell's shapes: the direct body, a page at rest
    ``[2, 16, 128]`` the operand, blocks of 128 pages inside the module's
    VMEM budget, the pools left in HBM."""
    shape = (2, PAGE_LEN, 128, 2)
    assert paged_decode_arm(*shape, q_heads=32) == "direct"
    ppb = paged_pages_per_block(*shape, NEMO_MAX_PAGES, q_heads=32)
    assert ppb == 128
    assert ppb * 4 * 2 * PAGE_LEN * 128 * 2 <= PAGED_KV_VMEM_BUDGET
    pool = _sds((NEMO_PAGES, 2, PAGE_LEN, 128))
    compiled = _compile(
        lambda q, k, v, t, n: decode_attention_paged(q, k, v, t, n,
                                                     interpret=False),
        one_chip, _sds((NEMO_SLOTS, 32, 128)), pool, pool,
        _sds((NEMO_SLOTS, NEMO_MAX_PAGES), jnp.int32),
        _sds((NEMO_SLOTS,), jnp.int32))
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [PAGED_DECODE_ATTN_KERNEL]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("tokens", [NEMO_SLOTS, 1024],
                         ids=["decode_tick", "prefill_bucket"])
def test_moe_relu2_kernels_carry_their_names(tokens, one_chip):
    """128 held experts of 1024 x 2688 (two matrices, no gate), top-22 of
    512 handed in: rows of 16 at a decode tick and of 64 at a prefill,
    the tile count sound if every assignment lands on the held."""
    from deepspeed_tpu.moe import dropless
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
    names = sorted(n.split(".")[0] for n in _kernel_names(compiled))
    assert names == [dropless.MOE_DOWN_KERNEL, dropless.MOE_UP_RELU2_KERNEL]


# ---------------------------------------------------------------------------
# MiMo-V2.5 at its published widths and the cell's own shapes
# (benchmark/configs/mimo-v2.5.json: layer 0 + one period of five window
# layers and a full one, 16 of 256 experts held, 192 slots of window rings,
# 13,825 pages of 4 key heads, keys 192 wide kept 256 wide over values of
# 128): the window decode kernel, the paged kernel at two widths, and both
# serve programs
# ---------------------------------------------------------------------------

MIMO_SLOTS, MIMO_PAGE_LEN, MIMO_PAGES, MIMO_MAX_PAGES = 192, 64, 13825, 128


def _window_decode(one_chip, slots=MIMO_SLOTS):
    from deepspeed_tpu.ops.pallas.decode_attention import \
        window_decode_attention
    return _compile(
        lambda q, k, v, n, b, base: window_decode_attention(
            q, k, v, n, b, base=base, sm_scale=192 ** -0.5,
            interpret=False),
        one_chip, _sds((slots, 64, 256)), _sds((5 * slots, 8, 128, 256)),
        _sds((5 * slots, 8, 128, 128)), _sds((slots,), jnp.int32),
        _sds((64,)), _sds((), jnp.int32))


def test_window_decode_kernel_reads_the_rings_where_they_lie(one_chip):
    from deepspeed_tpu.ops.pallas.decode_attention import \
        WINDOW_DECODE_ATTN_KERNEL
    assert WINDOW_DECODE_ATTN_KERNEL == "ds_window_decode_attn"
    compiled = _window_decode(one_chip)
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [WINDOW_DECODE_ATTN_KERNEL]
    # no layer's slots are sliced out of the rings: the base is traced
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


def test_paged_decode_kernel_at_two_widths_keeps_its_name(one_chip):
    """64 query heads on 4 key heads, keys 256 wide at rest over values
    of 128: the grouped direct body, 16 pages of 64 a block inside the
    module's VMEM budget, the pools left in HBM."""
    shape = (4, MIMO_PAGE_LEN, 256, 2)
    assert paged_decode_arm(*shape, q_heads=64) == "direct"
    ppb = paged_pages_per_block(*shape, MIMO_MAX_PAGES, q_heads=64,
                                v_head_dim=128)
    assert ppb == 16
    assert ppb * 2 * 4 * MIMO_PAGE_LEN * (256 + 128) * 2 \
        <= PAGED_KV_VMEM_BUDGET
    compiled = _compile(
        lambda q, k, v, t, n: decode_attention_paged(
            q, k, v, t, n, sm_scale=192 ** -0.5, interpret=False),
        one_chip, _sds((MIMO_SLOTS, 64, 256)),
        _sds((MIMO_PAGES, 4, MIMO_PAGE_LEN, 256)),
        _sds((MIMO_PAGES, 4, MIMO_PAGE_LEN, 128)),
        _sds((MIMO_SLOTS, MIMO_MAX_PAGES), jnp.int32),
        _sds((MIMO_SLOTS,), jnp.int32))
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [PAGED_DECODE_ATTN_KERNEL]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@functools.lru_cache(maxsize=None)
@functools.cache
def _mimo_program(program, one_chip, bucket=4096, relaid=False):
    """The model's paged step as the engine calls it: pools and window
    state donated; a prefill at ``bucket`` tokens."""
    from deepspeed_tpu.models.mimo_v2 import MimoV2Config, MimoV2Model
    model = MimoV2Model(MimoV2Config(
        vocab_size=19072, num_hidden_layers=7,
        hybrid_layer_pattern=(0, 1, 1, 1, 1, 1, 0),
        moe_layer_freq=(0, 1, 1, 1, 1, 1, 1), experts_held=(0, 16),
        param_dtype="bfloat16"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    k_pool = _sds((2, MIMO_PAGES, 4, MIMO_PAGE_LEN, 256))
    v_pool = _sds((2, MIMO_PAGES, 4, MIMO_PAGE_LEN, 128))
    state = model.serving_state(MIMO_SLOTS)
    i32, s = _sds((), jnp.int32), MIMO_SLOTS
    if program == "serve_decode":
        def fn(p, t, k, v, tab, ln, act, st):
            return model.decode_step_paged(p, t, k, v, tab, ln, act,
                                           state=st, impl="pallas", aux=True)
        shapes = (params, _sds((s,), jnp.int32), k_pool, v_pool,
                  _sds((s, MIMO_MAX_PAGES), jnp.int32), _sds((s,), jnp.int32),
                  _sds((s,), jnp.bool_), state)
        donate = (2, 3, 7)
    else:
        def fn(p, t, n, row, k, v, st, slot):
            return model.prefill_paged(p, t, n, jnp.int32(0), row, k, v,
                                       state=st, slot=slot, aux=True)
        shapes = (params, _sds((1, bucket), jnp.int32), i32,
                  _sds((MIMO_MAX_PAGES,), jnp.int32), k_pool, v_pool, state,
                  i32)
        donate = (4, 5, 6)
    args = _program_args(shapes, one_chip, model if relaid else None)
    with interpret_scope(False):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_mimo_programs_hold_their_kernels_and_no_copy_of_a_cache(
        program, one_chip):
    """Every Mosaic call of both serve programs starts ``ds_``; the pool
    (5.4 GB) and the window rings (0.75 GB) pass through aliased to the
    outputs; a decode tick's temporaries stay under 0.2 GB: no program
    copies a pool or a state leaf; all the chip must hold at once fits
    its 16.91e9 bytes."""
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.pallas.decode_attention import \
        WINDOW_DECODE_ATTN_KERNEL
    compiled = _mimo_program(program, one_chip)
    names = {n.split(".")[0] for n in _kernel_names(compiled)}
    experts = {dropless.MOE_GATE_UP_KERNEL, dropless.MOE_DOWN_KERNEL}
    assert names == experts | (
        {WINDOW_DECODE_ATTN_KERNEL, PAGED_DECODE_ATTN_KERNEL}
        if program == "serve_decode" else {"ds_flash_fwd"}), names
    mem = compiled.memory_analysis()
    pools = 2 * MIMO_PAGES * 4 * MIMO_PAGE_LEN * (256 + 128) * 2
    rings = 5 * MIMO_SLOTS * 8 * 128 * (256 + 128) * 2
    assert mem.alias_size_in_bytes >= pools + rings
    limit = 0.2e9 if program == "serve_decode" else 1.6e9
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9


def test_mimo_decode_tick_reads_each_layers_matrices_where_they_lie(
        one_chip):
    """``MimoV2Model``'s layers are walked in Python and each reads
    leaves of its own (``params["window"]["q_w"][i]``).  Stacked
    ``[layers, d, n]`` and sliced by a static index, every ``q_w``
    (100.7 MB) was written to HBM transposed by a
    ``slice_bitcast_fusion`` and every window layer's ``k_w`` / ``v_w``
    to fast memory, before a ``copy`` brought it to the matmul: 0.83 GB
    a tick moved once more than the model needs, 0.112 GB of
    temporaries (PR 39).  Since then no fusion of the entry computation
    writes a weight again; PR 39 left one ``copy`` a ``q_w`` (100.7 MB
    each, 1.19 ms of a tick on the chip), and with the ``q_w`` resting
    output-major as the engine holds them (PR 55) those are gone too:
    what is copied is a window layer's ``k_w`` (12.6 MB) and one ``v_w``
    into fast memory, nothing into HBM; the tick's temporaries are
    0.015 GB.  A leaf stacked again trips this."""
    from deepspeed_tpu.utils.hlo import parameter_rewrites
    compiled = _mimo_program("serve_decode", one_chip, relaid=True)
    weights = len(jax.tree.leaves(compiled.in_avals[0][0]))
    assert weights == 3 + 2 * 5 + 5 * 6 + 4 + 6 * 3 + 3
    moved = [r for r in parameter_rewrites(compiled.as_text(), weights)
             if r.bytes >= 1 << 20]
    assert [r for r in moved if r.op != "copy" or r.hbm_bytes] == [], moved
    assert len({r.parameter for r in moved}) == len(moved) <= 7, moved
    assert max(r.bytes for r in moved) < 16 << 20, moved
    assert compiled.memory_analysis().temp_size_in_bytes < 0.03e9


def test_mimo_prefill_rung_holds_half_the_temporaries_of_stacked_leaves(
        one_chip):
    """The 2,048 rung of the same walk: with stacked leaves one fusion
    wrote all five window layers' ``q_w`` transposed at once (0.966 GB
    of temporaries); with a leaf a layer 0.492 GB."""
    compiled = _mimo_program("serve_prefill", one_chip, 2048)
    assert compiled.memory_analysis().temp_size_in_bytes < 0.6e9


def _family_program(family):
    if family == "olmoe":
        return lambda program, one_chip, bucket=1024: \
            _olmoe_prefill_program(one_chip, bucket)
    return {"mimo": _mimo_program, "nemotron": _nemotron_program,
            "axk1": _axk1_program, "cmda": _cmda_program,
            "kimi": _kimi_program, "glm": _glm_program,
            "dots": _dots_program,
            "olmo_hybrid": _olmo_hybrid_program}[family]


@pytest.mark.parametrize("family,bucket", [
    ("mimo", 2048), ("nemotron", 512),
    # the rung under the half, built from 1,024 tokens up (PR 53)
    ("mimo", 1024), ("axk1", 1024), ("cmda", 1024), ("kimi", 1024),
    # the quarter of a ladder of two rungs, whatever its length (PR 61):
    # a new length for a scan chunk of 64 ...
    ("olmo_hybrid", 256),
    # ... and of 128, for an indexer that picks 2,048 rows and for a ring
    # of 513.  Slow: each compiles two more programs at published widths
    # (~35-45 s a case) in the one file that sets the suite's wall time
    # and must stay on one worker (ROADMAP D10); run by hand with
    # `-m slow -k lower_rung` after touching a prefill or the ladder
    pytest.param("nemotron", 256, marks=pytest.mark.slow),
    pytest.param("olmoe", 256, marks=pytest.mark.slow),
    pytest.param("glm", 512, marks=pytest.mark.slow),
    pytest.param("dots", 512, marks=pytest.mark.slow)])
def test_the_lower_rung_of_the_prefill_ladder_compiles_under_the_top_rungs_peak(
        family, bucket, one_chip):
    """``ServeEngine`` builds ``serve_prefill`` below
    ``serving.prefill_len`` too (``inference/engine.py::prefill_ladder``:
    4,096 -> 1,024 and 2,048; 2,048 -> 512 and 1,024; 1,024 -> 256 and
    512).  A shorter rung holds the same kernels, passes the same caches
    through aliased, and needs fewer temporaries than the rung above it:
    what the chip must hold at once is still set by ``prefill_len``."""
    from deepspeed_tpu.inference.engine import prefill_ladder
    build = _family_program(family)
    top = build("serve_prefill", one_chip)
    top_len = top.in_avals[0][1].shape[1]
    assert bucket in prefill_ladder(top_len)[:-1]
    above = build("serve_prefill", one_chip, 2 * bucket)
    rung = build("serve_prefill", one_chip, bucket)
    assert rung.in_avals[0][1].shape == (1, bucket)
    assert {n.split(".")[0] for n in _kernel_names(rung)} \
        == {n.split(".")[0] for n in _kernel_names(top)}
    mem, top_mem = rung.memory_analysis(), top.memory_analysis()
    assert mem.alias_size_in_bytes == top_mem.alias_size_in_bytes
    assert mem.argument_size_in_bytes <= top_mem.argument_size_in_bytes
    above_temp = above.memory_analysis().temp_size_in_bytes
    print(f"{family} serve_prefill temporaries: {mem.temp_size_in_bytes} B "
          f"at {bucket} tokens, {above_temp} B at {2 * bucket}, "
          f"{top_mem.temp_size_in_bytes} B at {top_len}")
    assert mem.temp_size_in_bytes < above_temp <= top_mem.temp_size_in_bytes


# ---------------------------------------------------------------------------
# A.X-K1 at its cell's sizes (benchmark/configs/a.x-k1.json: 6 layers of
# latent attention, 12 of 192 experts held at hidden 7,168, 192 slots, ONE
# pool of 12,289 pages of 64 rows 640 wide): the latent decode kernel, the
# expert kernels walked in blocks, and both serve programs
# ---------------------------------------------------------------------------

AXK1_SLOTS, AXK1_PAGE_LEN, AXK1_PAGES, AXK1_MAX_PAGES = 192, 64, 12289, 192


def _axk1_model():
    import json
    from deepspeed_tpu.models.axk1 import AxK1Config, AxK1Model
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "a.x-k1.json")) as f:
        file = json.load(f)
    serving = file["serving"]
    assert (serving["slots"], serving["page_len"], serving["pages"],
            -(-serving["max_seq_len"] // serving["page_len"])) == (
        AXK1_SLOTS, AXK1_PAGE_LEN, AXK1_PAGES, AXK1_MAX_PAGES)
    fields = {f.name for f in dataclasses.fields(AxK1Config)}
    keys = {k: v for k, v in file.items() if k in fields}
    keys["n_routed_experts"] = file["published"]["n_routed_experts"]
    return AxK1Model(AxK1Config(**keys, param_dtype=file["dtype"])), file


def test_latent_decode_kernel_reads_the_one_pool_where_it_lies(one_chip):
    """64 heads' [q_lat ; q_rope] against rows 640 wide, values their
    first 512 lanes: 32 pages of 64 a block inside the module's VMEM
    budget, the pool left in HBM, no layer sliced out of it."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        LATENT_DECODE_ATTN_KERNEL, latent_decode_attention,
        latent_pages_per_block)
    assert LATENT_DECODE_ATTN_KERNEL == "ds_latent_decode_attn"
    ppb = latent_pages_per_block(AXK1_PAGE_LEN, 640, 2, AXK1_MAX_PAGES)
    assert ppb == 32
    assert 2 * ppb * AXK1_PAGE_LEN * 640 * 2 <= PAGED_KV_VMEM_BUDGET
    compiled = _compile(
        lambda q, pool, t, n: latent_decode_attention(
            q, pool, t, n, 512, sm_scale=0.13, interpret=False),
        one_chip, _sds((AXK1_SLOTS, 64, 640)),
        _sds((6 * AXK1_PAGES, AXK1_PAGE_LEN, 640)),
        _sds((AXK1_SLOTS, AXK1_MAX_PAGES), jnp.int32),
        _sds((AXK1_SLOTS,), jnp.int32))
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [LATENT_DECODE_ATTN_KERNEL]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("tokens", [AXK1_SLOTS, 2048],
                         ids=["decode_tick", "prefill_rung"])
def test_moe_kernels_walk_an_expert_in_blocks_at_hidden_7168(tokens,
                                                             one_chip):
    """12 held of 192 experts of 7,168 x 2,048, top-8: both
    up-projections whole would be 112 MiB in flight of a core's 128; the
    kernel walks them in two blocks of 1,024 columns (72 MiB with the
    rows), the down-projection whole (72 MiB).  The compile is the proof
    that the chip allows both."""
    from deepspeed_tpu.moe import dropless
    d, f, held = 7168, 2048, 12
    weights = [_sds((5 * held, d, f))] * 2
    assert dropless.weight_blocks(weights, f) == 2
    assert dropless._vmem_limit(weights, 2) == 72 << 20
    compiled = _compile(
        lambda x, r, g, u, w: dropless.dropless_moe(
            x, r, g, u, w, 8, expert_offset=jnp.int32(held),
            experts_held=(0, held), interpret=False)[0],
        one_chip, _sds((tokens, d)), _sds((d, 192)), *weights,
        _sds((5 * held, f, d)))
    names = sorted(n.split(".")[0] for n in _kernel_names(compiled))
    assert names == [dropless.MOE_DOWN_KERNEL, dropless.MOE_GATE_UP_KERNEL]


@functools.lru_cache(maxsize=None)
@functools.cache
def _axk1_program(program, one_chip, bucket=4096, relaid=False):
    """The model's paged step as the engine calls it: the one pool
    donated, None where a second would be; a prefill at ``bucket`` tokens
    with its prefix length TRACED, so both forms of its attention (from
    nothing; its context read back from the pages) are in the program."""
    model, _ = _axk1_model()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = _sds((6, AXK1_PAGES, 1, AXK1_PAGE_LEN, 640))
    i32, s = _sds((), jnp.int32), AXK1_SLOTS
    if program == "serve_decode":
        def fn(p, t, k, tab, ln, act):
            return model.decode_step_paged(p, t, k, None, tab, ln, act,
                                           impl="pallas", aux=True)
        shapes = (params, _sds((s,), jnp.int32), pool,
                  _sds((s, AXK1_MAX_PAGES), jnp.int32),
                  _sds((s,), jnp.int32), _sds((s,), jnp.bool_))
        donate = (2,)
    else:
        def fn(p, t, n, pre, row, k):
            return model.prefill_paged(p, t, n, pre, row, k, None, aux=True)
        shapes = (params, _sds((1, bucket), jnp.int32), i32, i32,
                  _sds((AXK1_MAX_PAGES,), jnp.int32), pool)
        donate = (5,)
    args = _program_args(shapes, one_chip, model if relaid else None)
    with interpret_scope(False):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_axk1_programs_hold_their_kernels_and_one_pool(program, one_chip):
    """Every Mosaic call of both serve programs starts ``ds_``; the ONE
    pool (6.04 GB) passes through aliased to the output and nothing of
    its size is a temporary; the arguments are the weights and that pool
    and no second array of latents; the compiler's own counts are the
    ones the configuration's ``reduced_why`` states (but the prefill's
    temporaries, which it states as the XLA loop's, 1.091 GB: a
    ``benchmark`` PR's to edit, ``PERF.md`` section 7); all the chip must
    hold at once fits its 16.91e9 bytes."""
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.pallas.context_attention import \
        LATENT_CONTEXT_ATTN_KERNEL
    from deepspeed_tpu.ops.pallas.decode_attention import \
        LATENT_DECODE_ATTN_KERNEL
    compiled = _axk1_program(program, one_chip)
    names = {n.split(".")[0] for n in _kernel_names(compiled)}
    experts = {dropless.MOE_GATE_UP_KERNEL, dropless.MOE_DOWN_KERNEL}
    assert names == experts | ({LATENT_DECODE_ATTN_KERNEL}
                               if program == "serve_decode"
                               else {"ds_flash_fwd",
                                     LATENT_CONTEXT_ATTN_KERNEL}), names
    mem = compiled.memory_analysis()
    pool = 6 * AXK1_PAGES * AXK1_PAGE_LEN * 640 * 2
    assert mem.alias_size_in_bytes >= pool
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(compiled.in_avals[0][0]))
    assert abs(mem.argument_size_in_bytes - weights - pool) < 1 << 20
    limit = 0.06e9 if program == "serve_decode" else 1.1e9
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.6e9
    _, file = _axk1_model()
    assert "arguments %.3f GB" % (mem.argument_size_in_bytes / 1e9) \
        in file["reduced_why"]
    if program == "serve_decode":
        _holds_its_temporaries(mem, file, "a.x-k1", program)


def test_axk1_decode_tick_reads_each_layers_matrices_where_they_lie(
        one_chip):
    """A leaf a layer (``models/mimo_v2.py``'s rule): no fusion of the
    tick's entry computation writes a weight again, the absorbed
    matrices ``k_b_w`` / ``v_b_w`` [heads, ., .] included, and what is
    copied is a matrix's one read into the layout its dot takes."""
    from deepspeed_tpu.utils.hlo import parameter_rewrites
    compiled = _axk1_program("serve_decode", one_chip)
    weights = len(jax.tree.leaves(compiled.in_avals[0][0]))
    assert weights == 3 + 6 * 9 + 4 + 5 * 5 + 3
    moved = [r for r in parameter_rewrites(compiled.as_text(), weights)
             if r.bytes >= 1 << 20]
    assert [r for r in moved if r.op != "copy" or r.hbm_bytes] == [], moved


def test_axk1_lower_prefill_rung_compiles_under_the_top_rungs_peak(one_chip):
    from deepspeed_tpu.inference.engine import prefill_ladder
    assert prefill_ladder(4096) == (1024, 2048, 4096)
    top = _axk1_program("serve_prefill", one_chip).memory_analysis()
    rung = _axk1_program("serve_prefill", one_chip, 2048).memory_analysis()
    assert rung.alias_size_in_bytes == top.alias_size_in_bytes
    assert rung.temp_size_in_bytes < top.temp_size_in_bytes


# ---------------------------------------------------------------------------
# GLM-5.2 at its cell's sizes (benchmark/configs/glm-5.2.json: 7 layers of
# latent attention of which 2 score, 16 of 256 experts held at hidden 6,144,
# 32 slots, 6,145 pages of 64 rows 640 wide + 2 layers of indexer keys 128
# wide under the same page ids): the indexer's kernel over a whole context,
# the sparse kernel over a whole context under the picks' mask, and both
# serve programs
# ---------------------------------------------------------------------------

GLM_SLOTS, GLM_PAGE_LEN, GLM_PAGES, GLM_MAX_PAGES = 32, 64, 7169, 384


def _glm_model():
    import json
    from deepspeed_tpu.models.glm_dsa import GlmDsaConfig, GlmDsaModel
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "glm-5.2.json")) as f:
        file = json.load(f)
    serving = file["serving"]
    assert (serving["slots"], serving["page_len"], serving["pages"],
            -(-serving["max_seq_len"] // serving["page_len"])) == (
        GLM_SLOTS, GLM_PAGE_LEN, GLM_PAGES, GLM_MAX_PAGES)
    fields = {f.name for f in dataclasses.fields(GlmDsaConfig)}
    keys = {k: v for k, v in file.items() if k in fields}
    keys["n_routed_experts"] = file["published"]["n_routed_experts"]
    keys["experts_held"] = tuple(file["experts_held"])
    return GlmDsaModel(GlmDsaConfig(**keys, param_dtype=file["dtype"])), file


def test_index_score_kernel_streams_a_whole_context_of_keys(one_chip):
    """32 indexer heads against keys 128 wide: 128 pages of 64 a block
    (8,192 keys) inside the module's VMEM budget, three blocks to the
    longest context, the keys left in HBM, float32 scores out."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        INDEX_SCORE_KERNEL, index_score, latent_pages_per_block)
    assert INDEX_SCORE_KERNEL == "ds_index_score"
    ppb = latent_pages_per_block(GLM_PAGE_LEN, 128, 2, GLM_MAX_PAGES)
    assert ppb == 128 and GLM_MAX_PAGES % ppb == 0
    compiled = _compile(
        lambda q, w, pool, t, n: index_score(q, w, pool, t, n,
                                             interpret=False),
        one_chip, _sds((GLM_SLOTS, 32, 128)),
        _sds((GLM_SLOTS, 32), jnp.float32),
        _sds((2 * GLM_PAGES, GLM_PAGE_LEN, 128)),
        _sds((GLM_SLOTS, GLM_MAX_PAGES), jnp.int32),
        _sds((GLM_SLOTS,), jnp.int32))
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [INDEX_SCORE_KERNEL]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_sparse_kernel_reads_a_context_under_the_picks_mask(one_chip):
    """64 heads' [q_lat ; q_rope] against a slot's whole context at the
    cell's shapes: the latent kernel under the sparse kernel's name walks
    the pages where they lie, 32 pages of 64 rows (2,048 positions) a grid
    step, with that block's lanes of the mask as one more operand; the
    pool stays in HBM and the temporaries are the mask's ``int32[32 * 12,
    1, 2048]`` (3.1 MB), not rows."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        SPARSE_LATENT_DECODE_ATTN_KERNEL, latent_pages_per_block,
        sparse_latent_decode_attention)
    assert SPARSE_LATENT_DECODE_ATTN_KERNEL == "ds_sparse_latent_decode_attn"
    assert latent_pages_per_block(GLM_PAGE_LEN, 640, 2, GLM_MAX_PAGES) == 32
    cap = GLM_MAX_PAGES * GLM_PAGE_LEN
    compiled = _compile(
        lambda q, pool, t, n, allowed: sparse_latent_decode_attention(
            q, pool, t, n, allowed, 512, sm_scale=0.0625, interpret=False),
        one_chip, _sds((GLM_SLOTS, 64, 640)),
        _sds((7 * GLM_PAGES, GLM_PAGE_LEN, 640)),
        _sds((GLM_SLOTS, GLM_MAX_PAGES), jnp.int32),
        _sds((GLM_SLOTS,), jnp.int32), _sds((GLM_SLOTS, cap), jnp.bool_))
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] \
        == [SPARSE_LATENT_DECODE_ATTN_KERNEL]
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * GLM_SLOTS * cap * 4


@pytest.mark.parametrize("rung", [2048, 1024])
def test_context_kernel_walks_a_chunks_context_where_it_lies(rung, one_chip):
    """A prefill rung's queries (64 heads of 192 + 64 as the keys are laid
    out, 256 lanes) against a request's 384 pages of 64 rows 640 wide
    under the picks' mask ``[rung, 24,576]``: 16 pages (1,024 keys) a grid
    step and as many heads as ``CONTEXT_VMEM_BUDGET`` allows, the step's
    VMEM and the body's allowance inside what a core has beside the
    compiler's own 24 MiB; the pool stays in HBM and the one temporary of
    size is the mask as the kernel reads it (int8)."""
    from deepspeed_tpu.ops.pallas import context_attention as ca
    assert ca.LATENT_CONTEXT_ATTN_KERNEL == "ds_latent_context_attn"
    cap = GLM_MAX_PAGES * GLM_PAGE_LEN
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
        _sds((64, 512, 256)), _sds((7 * GLM_PAGES, GLM_PAGE_LEN, 640)),
        _sds((GLM_MAX_PAGES,), jnp.int32), _sds((rung,), jnp.int32),
        _sds((), jnp.int32), _sds((rung, cap), jnp.bool_))
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [ca.LATENT_CONTEXT_ATTN_KERNEL]
    print("context kernel temporaries", rung,
          compiled.memory_analysis().temp_size_in_bytes)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * rung * cap


@functools.lru_cache(maxsize=None)
@functools.cache
def _glm_program(program, one_chip, bucket=2048, relaid=False):
    """The model's paged step as the engine calls it: both arrays donated,
    None where a second pool would be; a prefill at ``bucket`` tokens with
    its prefix length TRACED (a whole prompt and a chunk are one
    program)."""
    model, _ = _glm_model()
    cfg = model.config
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = _sds((7, GLM_PAGES, 1, GLM_PAGE_LEN, cfg.latent_width))
    keys = _sds((2, GLM_PAGES, 1, GLM_PAGE_LEN, cfg.d_index))
    i32, s = _sds((), jnp.int32), GLM_SLOTS
    if program == "serve_decode":
        def fn(p, t, k, ik, tab, ln, act):
            return model.decode_step_paged(p, t, k, None, tab, ln, act,
                                           impl="pallas", aux=True,
                                           index_pool=ik)
        shapes = (params, _sds((s,), jnp.int32), pool, keys,
                  _sds((s, GLM_MAX_PAGES), jnp.int32),
                  _sds((s,), jnp.int32), _sds((s,), jnp.bool_))
        donate = (2, 3)
    else:
        def fn(p, t, n, pre, row, k, ik):
            return model.prefill_paged(p, t, n, pre, row, k, None, aux=True,
                                       index_pool=ik)
        shapes = (params, _sds((1, bucket), jnp.int32), i32, i32,
                  _sds((GLM_MAX_PAGES,), jnp.int32), pool, keys)
        donate = (5, 6)
    args = _program_args(shapes, one_chip, model if relaid else None)
    with interpret_scope(False):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_glm_programs_hold_their_kernels_and_both_arrays(program, one_chip):
    """Every Mosaic call of both serve programs starts ``ds_``; the two
    paged arrays (4.346 GB) pass through aliased to the outputs and nothing
    of their size is a temporary; the arguments are the weights and those
    arrays; the compiler's own counts are the ones the configuration's
    ``reduced_why`` states (but the temporaries: it states the tick's as
    the gathering tick of PR 49 had them, 0.123 GB, and the prefill's as
    the XLA loop's, 0.543: a ``benchmark`` PR's to edit, ``PERF.md``
    section 7); all the chip must hold at once fits its 16.91e9 bytes.  The
    prefill's are the expert layer's (a rung's rows gathered for 8 experts
    each, 0.2 GB twice) and the picks' mask, not the attention's."""
    from deepspeed_tpu.utils.hlo import parameter_rewrites
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.pallas.context_attention import \
        LATENT_CONTEXT_ATTN_KERNEL
    from deepspeed_tpu.ops.pallas.decode_attention import (
        INDEX_SCORE_KERNEL, SPARSE_LATENT_DECODE_ATTN_KERNEL)
    compiled = _glm_program(program, one_chip)
    names = {n.split(".")[0] for n in _kernel_names(compiled)}
    experts = {dropless.MOE_GATE_UP_KERNEL, dropless.MOE_DOWN_KERNEL}
    assert names == experts | ({INDEX_SCORE_KERNEL,
                                SPARSE_LATENT_DECODE_ATTN_KERNEL}
                               if program == "serve_decode"
                               else {LATENT_CONTEXT_ATTN_KERNEL}), names
    mem = compiled.memory_analysis()
    arrays = GLM_PAGES * GLM_PAGE_LEN * (7 * 640 + 2 * 128) * 2
    assert mem.alias_size_in_bytes >= arrays
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(compiled.in_avals[0][0]))
    assert abs(weights - 10.996e9) < 1e6
    assert abs(mem.argument_size_in_bytes - weights - arrays) < 1 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    _, file = _glm_model()
    assert "arguments %.3f GB" % (mem.argument_size_in_bytes / 1e9) \
        in file["reduced_why"]
    if program == "serve_prefill":
        assert mem.temp_size_in_bytes < 0.56e9, mem.temp_size_in_bytes
        return
    assert mem.temp_size_in_bytes < 0.03e9, mem.temp_size_in_bytes
    # a layer of the indexer's keys is 0.117 GB and one of rows 0.587: no
    # temporary can be a copy of either; and in the entry computation (the
    # arrays are its parameters after the weights and the tokens) no
    # fusion or copy reads one and writes a layer's bytes
    n = len(jax.tree.leaves(compiled.in_avals[0][0]))
    layer = GLM_PAGES * GLM_PAGE_LEN * 128 * 2
    assert mem.temp_size_in_bytes < layer / 2
    assert [r for r in parameter_rewrites(compiled.as_text(), n + 3, 0.0)
            if r.parameter in (n + 1, n + 2) and r.bytes >= layer] == []


def test_glm_lower_prefill_rung_compiles_under_the_top_rungs_peak(one_chip):
    from deepspeed_tpu.inference.engine import prefill_ladder
    assert prefill_ladder(2048) == (512, 1024, 2048)
    top = _glm_program("serve_prefill", one_chip).memory_analysis()
    rung = _glm_program("serve_prefill", one_chip, 1024).memory_analysis()
    assert rung.alias_size_in_bytes == top.alias_size_in_bytes
    assert rung.temp_size_in_bytes < top.temp_size_in_bytes


# ---------------------------------------------------------------------------
# Command A+ at its cell's sizes (benchmark/configs/command-a-plus-05-2026
# .json: one period of three window layers and a full one, 8 of 128 experts
# held at 4,096 x 4,096, 96 slots of rings of 4,096 keys, 12,289 pages of 8
# key heads): the ring walked in blocks, the chunk's attention over keys
# ahead of it, the expert kernels in blocks, and both serve programs
# ---------------------------------------------------------------------------

CMDA_SLOTS, CMDA_PAGE_LEN, CMDA_MAX_PAGES, CMDA_WINDOW = 96, 64, 320, 4096


@functools.lru_cache(maxsize=None)
def _cmda_model():
    import json
    from deepspeed_tpu.models.cohere2_moe import (Cohere2MoeConfig,
                                                  Cohere2MoeModel)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmark",
                           "configs", "command-a-plus-05-2026.json")) as f:
        file = json.load(f)
    fields = {f.name for f in dataclasses.fields(Cohere2MoeConfig)}
    m = {k: v for k, v in file.items() if k in fields}
    m["num_experts"] = file["published"]["num_experts"]
    m["experts_held"] = tuple(file["experts_held"])
    return Cohere2MoeModel(Cohere2MoeConfig(
        **m, param_dtype=file["dtype"])), file


def test_window_decode_walks_rings_of_4096_keys_where_they_lie(one_chip):
    """128 query heads on 8 key heads over rings of 4,096 keys: 512 rows
    of every key head a grid step (4 MiB of keys and values in flight), no
    layer's slots sliced out of the 3.2 GB of rings."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        WINDOW_DECODE_ATTN_KERNEL, window_decode_attention)
    s = CMDA_SLOTS
    compiled = _compile(
        lambda q, k, v, n, base: window_decode_attention(
            q, k, v, n, None, base=base, interpret=False),
        one_chip, _sds((s, 128, 128)), _sds((3 * s, 8, CMDA_WINDOW, 128)),
        _sds((3 * s, 8, CMDA_WINDOW, 128)), _sds((s,), jnp.int32),
        _sds((), jnp.int32))
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [WINDOW_DECODE_ATTN_KERNEL]
    assert compiled.memory_analysis().temp_size_in_bytes < 8 << 20


@pytest.mark.parametrize("ctx,window", [(CMDA_WINDOW, CMDA_WINDOW),
                                        (CMDA_MAX_PAGES * CMDA_PAGE_LEN,
                                         None)], ids=["ring", "pages"])
def test_flash_forward_over_context_keys_compiles_at_128_on_8(ctx, window,
                                                              one_chip):
    """A chunk of 4,096 queries on 128 heads over ``[context ; chunk]``
    keys on 8: a window layer's ring of 4,096 ahead, the full layer's
    20,480 gathered positions; the live count is traced."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        FLASH_FWD_CTX_KERNEL, flash_attention_fwd)
    compiled = _compile(
        lambda q, k, v, n: flash_attention_fwd(
            q, k, v, window=window, ctx_live=n, interpret=False),
        one_chip, _sds((1, 128, 4096, 128)), _sds((1, 8, ctx + 4096, 128)),
        _sds((1, 8, ctx + 4096, 128)), _sds((), jnp.int32))
    assert [n.split(".")[0] for n in _kernel_names(compiled)] \
        == [FLASH_FWD_CTX_KERNEL]


@pytest.mark.parametrize("tokens", [CMDA_SLOTS, 2048],
                         ids=["decode_tick", "prefill_rung"])
def test_moe_kernels_walk_an_expert_in_blocks_at_4096_by_4096(tokens,
                                                              one_chip):
    """8 held of 128 experts of 4,096 x 4,096, top-8: both up-projections
    whole would be 128 MiB in flight, the whole VMEM; the kernel walks
    them in two blocks of 2,048 columns (64 MiB + 16 for the rows), the
    down-projection whole (64 MiB + 16).  The compile is the proof that
    the chip allows both."""
    from deepspeed_tpu.moe import dropless
    d = f = 4096
    held = 8
    weights = [_sds((4 * held, d, f))] * 2
    assert dropless.weight_blocks(weights, f) == 2
    assert dropless._vmem_limit(weights, 2) == 80 << 20
    assert dropless.weight_blocks(weights[:1], d) == 1
    assert dropless._vmem_limit(weights[:1], 1) == 80 << 20
    compiled = _compile(
        lambda x, r, g, u, w: dropless.dropless_moe(
            x, r, g, u, w, 8, expert_offset=jnp.int32(held),
            experts_held=(0, held), interpret=False)[0],
        one_chip, _sds((tokens, d)), _sds((d, 128)), *weights,
        _sds((4 * held, f, d)))
    names = sorted(n.split(".")[0] for n in _kernel_names(compiled))
    assert names == [dropless.MOE_DOWN_KERNEL, dropless.MOE_GATE_UP_KERNEL]


@functools.lru_cache(maxsize=None)
@functools.cache
def _cmda_program(program, one_chip, bucket=4096, relaid=False):
    """The model's paged step as the engine calls it: pool and rings
    donated; a prefill (or a chunk of one) at ``bucket`` tokens with its
    prefix length TRACED, so both forms of its attention (from nothing;
    over the ring and the pages the chunk before left) are in it."""
    model, file = _cmda_model()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = _sds((1, file["serving"]["pages"], 8, CMDA_PAGE_LEN, 128))
    state = model.serving_state(CMDA_SLOTS)
    i32, s = _sds((), jnp.int32), CMDA_SLOTS
    if program == "serve_decode":
        def fn(p, t, k, v, tab, ln, act, st):
            return model.decode_step_paged(p, t, k, v, tab, ln, act,
                                           state=st, impl="pallas", aux=True)
        shapes = (params, _sds((s,), jnp.int32), pool, pool,
                  _sds((s, CMDA_MAX_PAGES), jnp.int32), _sds((s,), jnp.int32),
                  _sds((s,), jnp.bool_), state)
        donate = (2, 3, 7)
    else:
        def fn(p, t, n, pre, row, k, v, st, slot):
            return model.prefill_paged(p, t, n, pre, row, k, v, state=st,
                                       slot=slot, aux=True)
        shapes = (params, _sds((1, bucket), jnp.int32), i32, i32,
                  _sds((CMDA_MAX_PAGES,), jnp.int32), pool, pool, state, i32)
        donate = (5, 6, 7)
    args = _program_args(shapes, one_chip, model if relaid else None)
    with interpret_scope(False):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_command_a_plus_programs_hold_their_kernels_and_no_copy_of_a_cache(
        program, one_chip):
    """Every Mosaic call of both serve programs starts ``ds_``; the pool
    (3.22 GB) and the rings (4.83 GB) pass through aliased to the
    outputs and no program copies a layer of them (a static slice of a
    ring leaf did: 0.77 GB a window layer); the compiler's own counts are
    the ones the configuration's ``reduced_why`` states; all the chip
    must hold at once fits its 16.91e9 bytes."""
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.pallas.decode_attention import \
        WINDOW_DECODE_ATTN_KERNEL
    compiled = _cmda_program(program, one_chip)
    _, file = _cmda_model()
    names = {n.split(".")[0] for n in _kernel_names(compiled)}
    experts = {dropless.MOE_GATE_UP_KERNEL, dropless.MOE_DOWN_KERNEL}
    assert names == experts | (
        {WINDOW_DECODE_ATTN_KERNEL, PAGED_DECODE_ATTN_KERNEL}
        if program == "serve_decode"
        else {"ds_flash_fwd", "ds_flash_fwd_ctx"}), names
    mem = compiled.memory_analysis()
    pools = 2 * file["serving"]["pages"] * 8 * CMDA_PAGE_LEN * 128 * 2
    rings = 2 * 3 * CMDA_SLOTS * 8 * CMDA_WINDOW * 128 * 2
    assert mem.alias_size_in_bytes >= pools + rings
    limit = 0.2e9 if program == "serve_decode" else 1.2e9
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.6e9
    assert "arguments %.3f GB" % (mem.argument_size_in_bytes / 1e9) \
        in file["reduced_why"]
    _holds_its_temporaries(mem, file, "command-a-plus", program)


def test_command_a_plus_tick_reads_each_layers_matrices_where_they_lie(
        one_chip):
    """A leaf a layer (``models/mimo_v2.py``'s rule), the experts alone
    stacked, the query projections resting output-major as the engine
    holds them (PR 55): nothing in the tick's entry computation writes a
    megabyte of a weight again, to HBM or to fast memory."""
    from deepspeed_tpu.utils.hlo import parameter_rewrites
    compiled = _cmda_program("serve_decode", one_chip, relaid=True)
    weights = len(jax.tree.leaves(compiled.in_avals[0][0]))
    assert weights == 2 + 4 * 9 + 3
    moved = [r for r in parameter_rewrites(compiled.as_text(), weights)
             if r.bytes >= 1 << 20]
    assert moved == [], moved


def test_command_a_plus_lower_prefill_rung_compiles_under_the_top_rungs_peak(
        one_chip):
    top = _cmda_program("serve_prefill", one_chip).memory_analysis()
    rung = _cmda_program("serve_prefill", one_chip, 2048).memory_analysis()
    assert rung.alias_size_in_bytes == top.alias_size_in_bytes
    assert rung.temp_size_in_bytes < top.temp_size_in_bytes


@pytest.mark.parametrize("model", ["gpt2", "bert"])
def test_training_flash_calls_are_what_they_were(model):
    """A window, a sink, grouped keys and a second width were added to
    ``ds_flash_fwd`` for the serving prefill.  A training step's three
    flash calls take the operands they took before (6, 9 and 9: no sink
    tile), over the whole causal or bidirectional grid (no band), with
    blocks as wide as the keys, and their kernels are bound with none of
    the new switches."""
    def loss(q, k, v, mask=None):
        return flash_attention(q, k, v, causal=model == "gpt2",
                               key_mask=mask, interpret=False
                               ).astype(jnp.float32).sum()

    shape = (2, 12, SEQ, DH) if model == "gpt2" else (2, 16, 512, DH)
    qkv = [_sds(shape)] * 3
    mask = () if model == "gpt2" else (_sds(shape[::2], jnp.bool_),)
    jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2))
                           )(*qkv, *mask)
    calls = {}

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.setdefault(eqn.params["name"], eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(calls) == ["ds_flash_bwd_dkv", "ds_flash_bwd_dq",
                             "ds_flash_fwd"]
    bh, blocks = shape[0] * shape[1], shape[2] // 512
    for name, operands in (("ds_flash_fwd", 6), ("ds_flash_bwd_dq", 9),
                           ("ds_flash_bwd_dkv", 9)):
        eqn = calls[name]
        assert len(eqn.invars) == operands, (name, len(eqn.invars))
        assert eqn.params["grid_mapping"].grid == (bh, blocks, blocks)
    fwd = calls["ds_flash_fwd"]
    assert [tuple(v.aval.shape) for v in fwd.outvars][0] == (bh,) + shape[2:]
    text = str(fwd.params["jaxpr"])
    assert "window" not in text and "sink" not in text


# ---------------------------------------------------------------------------
# BERT-large: hidden dropout's masks in the layer stack (PR 43).  Threefry
# fused into the output projections cost more than the matmuls; the masks
# now come from a counter hash (ops/dropout.py).
# ---------------------------------------------------------------------------
BERT_STACK_ROWS = 8     # x 512: the cell's layers, a quarter of its batch
_FUSION = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) fusion\(.*"
                     r'op_name="([^"]*)".*"estimated_cycles":"(\d+)"')


def _bert_layer_stack(one_chip, hidden_dropout: float) -> str:
    """The loss-and-gradient program of two scanned BERT-large layers
    under ``remat='block'`` (no embedding, no head), as the chip's
    compiler leaves it."""
    from deepspeed_tpu.models.bert import BertConfig, BertModel
    cfg = BertConfig(vocab_size=30522, hidden_size=1024, num_hidden_layers=2,
                     num_attention_heads=16, intermediate_size=4096,
                     max_position_embeddings=512,
                     hidden_dropout_prob=hidden_dropout)
    model = BertModel(cfg)
    layers = jax.eval_shape(model.init, jax.random.PRNGKey(0))["layers"]

    def loss(layers, x, key):
        def body(h, xs):
            lp, i = xs
            return model.layer(lp, h, None, jax.random.fold_in(key, i),
                               True), None

        y, _ = jax.lax.scan(jax.checkpoint(body), x,
                            (layers, jnp.arange(cfg.num_hidden_layers)))
        return jnp.sum(y.astype(jnp.float32) ** 2)

    shapes = (jax.tree.map(lambda s: _sds(s.shape), layers),
              _sds((BERT_STACK_ROWS, 512, 1024)), _sds((2,), jnp.uint32))
    with interpret_scope(False):
        return _compile(jax.value_and_grad(loss, argnums=(0, 1)), one_chip,
                        *shapes).as_text()


def _ffn_cycles(text: str) -> dict:
    """``estimated_cycles`` of the FFN's forward matmul fusions, by
    (in | out, first | recomputed)."""
    found = {}
    for line in text.splitlines():
        m = _FUSION.match(line)
        if not m or not m.group(2).endswith("layer/mlp/dot_general") \
                or "transpose(jvp())/while/body/closed_call/checkpoint/layer" \
                in m.group(2):
            continue
        side = "in" if f"[{BERT_STACK_ROWS},512,4096]" in m.group(1) else "out"
        run = "recomputed" if "rematted_computation" in m.group(2) else "first"
        assert (side, run) not in found, line[:200]
        found[side, run] = int(m.group(3))
    return found


@pytest.fixture(scope="module")
def bert_stacks(one_chip):
    return {rate: _bert_layer_stack(one_chip, rate) for rate in (0.1, 0.0)}


def test_rng_fusions_reads_a_threefry_draw_in_the_chips_text(one_chip):
    def drawn(x, key):
        keep = jax.random.bernoulli(key, 0.9, x.shape)
        return jnp.where(keep, x / 0.9, 0.0).astype(x.dtype)

    args = (jax.ShapeDtypeStruct((BERT_STACK_ROWS, 512, 1024), BF16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip))
    found = rng_fusions(jax.jit(drawn).lower(*args).compile().as_text())
    assert [(f.elements, f.times) for f in found] == [
        (BERT_STACK_ROWS * 512 * 1024, 1)]
    assert found[0].cycles > 100_000      # 153,776 when this was written


def test_bert_layer_stack_draws_no_random_bits_an_element(bert_stacks):
    """The parent's stack held nine such fusions at this shape (threefry
    in the attention-output and FFN-out matmuls, first and recomputed, and
    in five fusions of the backward); the whole step's text held five."""
    assert rng_fusions(bert_stacks[0.1]) == []
    assert rng_fusions(bert_stacks[0.0]) == []


@pytest.mark.parametrize("run", ["first", "recomputed"])
def test_hashed_dropout_costs_the_ffn_out_matmul_next_to_nothing(bert_stacks,
                                                                 run):
    """The compiler's own estimate of the FFN-out fusion (matmul, bias,
    dropout, residual, LayerNorm's sums): with the hash it is within a
    tenth of what it is with no dropout at all (411,960 against 399,028
    first, 410,197 against 394,932 recomputed, when this was written; the
    parent's threefry made it 538,709), and under 1.6 x the FFN-in
    fusion's, which has the same FLOPs and an ``erf`` epilogue (1.49 and
    1.47; with no dropout 1.29 and 1.42, so the 1.3 x the issue asked
    for is not the draw's to give)."""
    hashed, none = (_ffn_cycles(bert_stacks[r]) for r in (0.1, 0.0))
    assert sorted(hashed) == sorted(none) == [
        ("in", "first"), ("in", "recomputed"),
        ("out", "first"), ("out", "recomputed")]
    assert hashed["out", run] <= 1.1 * none["out", run], (hashed, none)
    assert hashed["out", run] <= 1.6 * hashed["in", run], hashed


# ---------------------------------------------------------------------------
# What remat="block" keeps (PR 46): with the flash kernel's output and
# log-sum-exp saved across the block's boundary the recomputed forward's
# kernel has no consumer, and the chip's program runs it once a layer.
# ---------------------------------------------------------------------------
def _train_step_text(model, batch, one_chip, saved: bool) -> str:
    """The loss-and-gradient program of a whole model at bf16 weights, as
    the chip's compiler leaves it; ``saved``: traced with a budget that is
    all room (the flash results kept), else with none (the bare
    checkpoint)."""
    from deepspeed_tpu.runtime.activation_checkpointing.block_remat import (
        RematBudget, remat_budget_scope)
    params = jax.tree.map(lambda s: _sds(s.shape),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    budget = RematBudget(bytes_limit=10 ** 12, resident_bytes=0) if saved \
        else None

    def step(params, batch, rng):
        return jax.value_and_grad(model.loss_fn)(params, batch, rng)

    with interpret_scope(False), remat_budget_scope(budget):
        return _compile(step, one_chip, params, batch,
                        _sds((2,), jnp.uint32)).as_text()


@functools.cache
def _bert_large_step(one_chip, saved):
    from deepspeed_tpu.models.bert import BERT_LARGE, BertModel
    batch = {"input_ids": _sds((BERT_STACK_ROWS, 512), jnp.int32),
             "masked_lm_labels": _sds((BERT_STACK_ROWS, 512), jnp.int32),
             "next_sentence_label": _sds((BERT_STACK_ROWS,), jnp.int32)}
    return _train_step_text(BertModel(BERT_LARGE), batch, one_chip, saved)


@pytest.fixture(scope="module")
def bert_large_step(one_chip):
    return _bert_large_step(one_chip, saved=True)


def test_the_step_runs_the_flash_forward_once_a_layer_when_its_output_is_kept(
        bert_large_step):
    """``utils/hlo.py::kernel_calls`` on the chip's text, loops counted:
    BERT-large's step holds 24 ``ds_flash_fwd`` calls (48 under the bare
    checkpoint, the parent's: the test below; GPT-2's, on four chips:
    the last test), the backward kernels run once a layer, and no fusion
    draws random bits an element."""
    from deepspeed_tpu.utils.hlo import kernel_calls
    assert kernel_calls(bert_large_step) == {
        "ds_flash_fwd": 24, "ds_flash_bwd_dq": 24, "ds_flash_bwd_dkv": 24}
    assert rng_fusions(bert_large_step) == []


def test_the_step_holds_the_logits_of_one_block_of_labelled_rows(
        bert_large_step):
    """PR 47, on the chip's text: no array of every row's 30,522 logits
    (the parent's text names ``[rows, 512, 30522]`` in float32 and bf16), and
    the decoder's three matmuls lie in a ``while`` whose trip count the
    text does not state: the label count decides how often they run."""
    from deepspeed_tpu.ops.mlm_head import HEAD_BLOCK_ROWS
    from deepspeed_tpu.utils.hlo import _arrays, matmuls
    held = {dims for _, dims in _arrays(bert_large_step)}
    assert not {(BERT_STACK_ROWS, 512, 30522),
                (BERT_STACK_ROWS * 512, 30522)} & held
    decoder = [m for m in matmuls(bert_large_step)
               if any(30522 in dims for _, dims in m.shapes)]
    assert sorted(dims for m in decoder for _, dims in m.shapes) == [
        (HEAD_BLOCK_ROWS, 30522), (30522, 1024)], decoder
    assert all(m.at_run_time for m in decoder)
    walked = [m for m in matmuls(bert_large_step) if m.at_run_time]
    assert (HEAD_BLOCK_ROWS, 1024) in {
        dims for m in walked for _, dims in m.shapes}       # dlogits @ E


def test_the_bare_checkpoint_runs_the_flash_forward_twice_a_layer(
        bert_stacks):
    """What the parent's step did, and what a step still does where no
    budget is handed over: the two scanned layers of ``bert_stacks`` run
    ``ds_flash_fwd`` four times, the backward kernels twice."""
    from deepspeed_tpu.utils.hlo import kernel_calls
    assert kernel_calls(bert_stacks[0.1]) == {
        "ds_flash_fwd": 4, "ds_flash_bwd_dq": 2, "ds_flash_bwd_dkv": 2}


def test_saved_flash_results_stay_with_their_rows_on_four_chips(topo):
    """dp=4, the flash call inside ``sharded_flash_attention``'s manual
    region: the kept output leaves it as the batch is sharded (a device's
    stack holds its own 4 of 16 rows x 12 heads, no more), the forward
    kernel runs once a layer, and the program's collectives are the bare
    checkpoint's, instruction for instruction."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.runtime.activation_checkpointing.block_remat import (
        RematBudget, remat_budget_scope)
    from deepspeed_tpu.utils.hlo import collectives, kernel_calls
    mesh = build_mesh(dp=4, devices=topo.devices)
    layers = 2
    model = GPT2Model(dataclasses.replace(GPT2_124M, n_layer=layers))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, BF16,
                                       sharding=NamedSharding(mesh, P())),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((16, SEQ + 1), jnp.int32,
                                  sharding=NamedSharding(mesh, P("data")))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))

    def text(budget):
        with jax.set_mesh(mesh), interpret_scope(False), \
                remat_budget_scope(budget):
            return jax.jit(jax.value_and_grad(model.loss_fn)).lower(
                params, tokens, key).compile().as_text()

    bare = text(None)
    assert kernel_calls(bare)["ds_flash_fwd"] == 2 * layers
    assert f"bf16[{layers},48,{SEQ},{DH}]" not in bare
    kept = text(RematBudget(bytes_limit=10 ** 12, resident_bytes=0))
    assert kernel_calls(kept) == {"ds_flash_fwd": layers,
                                  "ds_flash_bwd_dq": layers,
                                  "ds_flash_bwd_dkv": layers}
    assert f"bf16[{layers},48,{SEQ},{DH}]" in kept      # 4 rows x 12 heads
    assert f"bf16[{layers},192,{SEQ},{DH}]" not in kept
    assert sorted((c.op, c.shapes, c.times) for c in collectives(kept)) == \
        sorted((c.op, c.shapes, c.times) for c in collectives(bare))


# ---------------------------------------------------------------------------
# Kimi Linear at its cell's sizes (benchmark/configs/kimi-linear-48b-a3b
# .json: 6 KDA layers + 2 latent ones, 32 of 256 experts held at hidden
# 2,304, 256 slots of delta-rule state, 24,577 pages of 64 rows 640 wide
# over the latent layers alone): the KDA decode update, and both serve
# programs, the prefill with its prefix length traced (a chunk)
# ---------------------------------------------------------------------------

KIMI_SLOTS, KIMI_PAGE_LEN, KIMI_PAGES, KIMI_MAX_PAGES = 256, 64, 24577, 448


def _kimi_model():
    import json
    from deepspeed_tpu.models.kimi_linear import (KimiLinearConfig,
                                                  KimiLinearModel)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        file = json.load(f)
    serving = file["serving"]
    assert (serving["slots"], serving["page_len"], serving["pages"],
            -(-serving["max_seq_len"] // serving["page_len"])) == (
        KIMI_SLOTS, KIMI_PAGE_LEN, KIMI_PAGES, KIMI_MAX_PAGES)
    fields = {f.name for f in dataclasses.fields(KimiLinearConfig)}
    keys = {k: v for k, v in file.items() if k in fields}
    keys["num_experts"] = file["published"]["num_experts"]
    keys["experts_held"] = tuple(keys["experts_held"])
    return KimiLinearModel(KimiLinearConfig(
        **keys, param_dtype=file["dtype"])), file


def test_kda_decode_kernel_keeps_its_name_and_the_state_in_place(one_chip):
    """256 slots x 6 layers of [32, 128, 128] float32 (3.2 GB) aliased
    through; a grid step's blocks and the body inside the kernel's VMEM
    limit; nothing of the state's size a temporary."""
    from deepspeed_tpu.ops.pallas.kda import KDA_DECODE_KERNEL, kda_decode
    assert KDA_DECODE_KERNEL == "ds_kda_decode"
    s, h, d = KIMI_SLOTS, 32, 128
    f32 = jnp.float32
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (_sds((6 * s, h, d, d), f32), _sds((s, h, d), f32),
         _sds((s, h, d), f32), _sds((s, h, d), f32), _sds((s, h, d), f32),
         _sds((s, h), f32), _sds((s,), jnp.bool_), _sds((), jnp.int32)))
    compiled = jax.jit(
        lambda st, a, k, v, q, b, act, base: kda_decode(
            st, a, k, v, q, b, act, base=base, interpret=False),
        donate_argnums=(0,)).lower(*args).compile()
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [KDA_DECODE_KERNEL]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 6 * s * h * d * d * 4
    assert mem.temp_size_in_bytes < 4 << 20


@functools.lru_cache(maxsize=None)
@functools.cache
def _kimi_program(program, one_chip, bucket=4096, relaid=False):
    """The model's paged step as the engine calls it: the one pool and the
    state donated, None where a second pool would be; a prefill at
    ``bucket`` tokens with its prefix length TRACED (a chunk)."""
    model, _ = _kimi_model()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = _sds((2, KIMI_PAGES, 1, KIMI_PAGE_LEN, 640))
    state = model.serving_state(KIMI_SLOTS)
    i32, s = _sds((), jnp.int32), KIMI_SLOTS
    if program == "serve_decode":
        def fn(p, t, k, tab, ln, act, st):
            return model.decode_step_paged(p, t, k, None, tab, ln, act,
                                           state=st, impl="pallas", aux=True)
        shapes = (params, _sds((s,), jnp.int32), pool,
                  _sds((s, KIMI_MAX_PAGES), jnp.int32),
                  _sds((s,), jnp.int32), _sds((s,), jnp.bool_), state)
        donate = (2, 6)
    else:
        def fn(p, t, n, pre, row, k, st, slot):
            return model.prefill_paged(p, t, n, pre, row, k, None, state=st,
                                       slot=slot, aux=True)
        shapes = (params, _sds((1, bucket), jnp.int32), i32, i32,
                  _sds((KIMI_MAX_PAGES,), jnp.int32), pool, state, i32)
        donate = (5, 6)
    args = _program_args(shapes, one_chip, model if relaid else None)
    with interpret_scope(False):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_kimi_programs_hold_their_kernels_and_no_copy_of_the_state(
        program, one_chip):
    """Every Mosaic call of both serve programs starts ``ds_``; a tick
    runs ``ds_kda_decode`` once a KDA layer and the latent kernel once a
    latent layer; the one pool (4.03 GB) and the state (3.33 GB) pass
    through aliased to the outputs and nothing of their size is a
    temporary; the arguments are the weights, the pool and the state; the
    compiler's own counts are the ones the configuration's ``reduced_why``
    states; all the chip must hold at once fits its 16.91e9 bytes, over
    60 % of them arguments."""
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.pallas.context_attention import \
        LATENT_CONTEXT_ATTN_KERNEL
    from deepspeed_tpu.ops.pallas.decode_attention import \
        LATENT_DECODE_ATTN_KERNEL
    from deepspeed_tpu.ops.pallas.kda import KDA_DECODE_KERNEL
    from deepspeed_tpu.utils.hlo import kernel_calls
    compiled = _kimi_program(program, one_chip)
    calls = kernel_calls(compiled.as_text())
    experts = {dropless.MOE_GATE_UP_KERNEL: 7, dropless.MOE_DOWN_KERNEL: 7}
    assert calls == {**experts, **(
        {KDA_DECODE_KERNEL: 6, LATENT_DECODE_ATTN_KERNEL: 2}
        if program == "serve_decode"
        else {LATENT_CONTEXT_ATTN_KERNEL: 2})}, calls
    mem = compiled.memory_analysis()
    pool = 2 * KIMI_PAGES * KIMI_PAGE_LEN * 640 * 2
    state = 6 * KIMI_SLOTS * (32 * 128 * 128 * 4 + 3 * 12288 * 2)
    assert mem.alias_size_in_bytes >= pool + state
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(compiled.in_avals[0][0]))
    assert abs(mem.argument_size_in_bytes - weights - pool - state) < 1 << 20
    assert mem.argument_size_in_bytes > 0.6 * 16.91e9
    # the tick's: the convolutions' tails stacked once (0.113 GB) + 9 MB
    limit = 0.15e9 if program == "serve_decode" else 2.0e9
    assert mem.temp_size_in_bytes < limit, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
    if program == "serve_decode":
        # the convolutions' tails are read from the leaf as it came and
        # written once, into a buffer of their own: no UPDATE of the
        # donated leaf is an instruction the compiler runs a second time
        # (``.remat``; a layer's ``.at[i].set`` was, in place, and its
        # second run read the first one's rows: wrong from the second tick)
        again = [line for line in compiled.as_text().splitlines()
                 if ".remat = " in line and "%st__kda_conv__" in line
                 and "scatter" in line]
        assert not again, again
    _, file = _kimi_model()
    if program == "serve_decode":
        assert "arguments %.3f GB" % (mem.argument_size_in_bytes / 1e9) \
            in file["reduced_why"]
    _holds_its_temporaries(mem, file, "kimi-linear", program)


def test_kimi_decode_tick_reads_each_layers_matrices_where_they_lie(
        one_chip):
    """A leaf a layer (``models/mimo_v2.py``'s rule): no fusion of the
    tick's entry computation writes a weight again, and what is copied is
    a matrix's one read into the layout its dot takes.  (``share`` 0.5:
    at 256 slots the [256, 12288] float32 + bfloat16 results of ``h
    W_qkv`` fused with its convolution are a third of that weight's
    bytes, and are activations.)"""
    from deepspeed_tpu.utils.hlo import parameter_rewrites
    compiled = _kimi_program("serve_decode", one_chip)
    weights = len(jax.tree.leaves(compiled.in_avals[0][0]))
    assert weights == 3 + 6 * 12 + 2 * 7 + 4 + 7 * 6 + 3
    moved = [r for r in parameter_rewrites(compiled.as_text(), weights,
                                           share=0.5)
             if r.bytes >= 1 << 20]
    assert [r for r in moved if r.op != "copy" or r.hbm_bytes] == [], moved


def test_kimi_lower_prefill_rung_compiles_under_the_top_rungs_peak(one_chip):
    from deepspeed_tpu.inference.engine import prefill_ladder
    assert prefill_ladder(4096) == (1024, 2048, 4096)
    top = _kimi_program("serve_prefill", one_chip).memory_analysis()
    rung = _kimi_program("serve_prefill", one_chip, 2048).memory_analysis()
    assert rung.alias_size_in_bytes == top.alias_size_in_bytes
    assert rung.temp_size_in_bytes < top.temp_size_in_bytes


# ---------------------------------------------------------------------------
# dots3-note-prev at its cell's sizes (benchmark/configs/dots3-note-prev.json:
# a dense full layer and two periods of three sliding layers and a full one,
# 16 of 256 experts held, 128 slots of six rings of 576 x 1,152, 12,289 pages
# of latent rows 640 wide with the indexer's keys beside them): the ring
# kernel, the decode tick and ONE prefill rung
# ---------------------------------------------------------------------------

DOTS_SLOTS, DOTS_PAGE_LEN, DOTS_PAGES, DOTS_MAX_PAGES = 128, 64, 12289, 384


@functools.cache
def _dots_model():
    import json
    from deepspeed_tpu.models.dots3_note import (Dots3NoteConfig,
                                                 Dots3NoteModel)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "dots3-note-prev.json")) as f:
        file = json.load(f)
    serving = file["serving"]
    assert (serving["slots"], serving["page_len"], serving["pages"],
            -(-serving["max_seq_len"] // serving["page_len"])) == (
        DOTS_SLOTS, DOTS_PAGE_LEN, DOTS_PAGES, DOTS_MAX_PAGES)
    fields = {f.name for f in dataclasses.fields(Dots3NoteConfig)}
    keys = {k: v for k, v in file.items() if k in fields}
    keys["n_routed_experts"] = file["published"]["n_routed_experts"]
    keys["experts_held"] = tuple(file["experts_held"])
    return Dots3NoteModel(Dots3NoteConfig(**keys,
                                          param_dtype=file["dtype"])), file


def test_window_latent_kernel_reads_a_slots_ring_in_one_grid_step(one_chip):
    """64 heads' [q_lat ; q_rope] against each slot's ring of 576 rows of
    1,152 lanes, six layers' rings stacked: the latent kernel's body under
    a name of its own, all nine granules of 64 rows one grid step (2.65 MB
    the double buffer, inside the module's VMEM budget), the rings left in
    HBM."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        WINDOW_LATENT_DECODE_ATTN_KERNEL, ring_granule,
        window_latent_decode_attention)
    assert WINDOW_LATENT_DECODE_ATTN_KERNEL == "ds_window_latent_decode_attn"
    cfg = _dots_model()[0].config
    rows, width = cfg.ring_rows, cfg.window.row
    assert (rows, width, ring_granule(rows)) == (576, 1152, 64)
    assert 2 * rows * width * 2 <= PAGED_KV_VMEM_BUDGET
    compiled = _compile(
        lambda q, rings, n: window_latent_decode_attention(
            q, rings, n, cfg.window.kv_rank, base=2 * DOTS_SLOTS,
            sm_scale=0.0625, interpret=False),
        one_chip, _sds((DOTS_SLOTS, 64, width)),
        _sds((6 * DOTS_SLOTS, rows, width)), _sds((DOTS_SLOTS,), jnp.int32))
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] \
        == [WINDOW_LATENT_DECODE_ATTN_KERNEL]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@functools.cache
def _dots_program(program, one_chip, bucket=2048):
    """The model's paged step as the engine calls it: the pool, the
    indexer's keys and the rings donated, None where a second pool would
    be, the parameters as the engine holds them at rest; a prefill at
    ``bucket`` tokens with its prefix length and slot TRACED."""
    model, _ = _dots_model()
    cfg = model.config
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = _sds((3, DOTS_PAGES, 1, DOTS_PAGE_LEN, cfg.d_head))
    keys = _sds((3, DOTS_PAGES, 1, DOTS_PAGE_LEN, cfg.d_index))
    state = model.serving_state(DOTS_SLOTS)
    i32, s = _sds((), jnp.int32), DOTS_SLOTS
    if program == "serve_decode":
        def fn(p, t, k, ik, st, tab, ln, act):
            return model.decode_step_paged(p, t, k, None, tab, ln, act,
                                           state=st, impl="pallas", aux=True,
                                           index_pool=ik)
        shapes = (params, _sds((s,), jnp.int32), pool, keys, state,
                  _sds((s, DOTS_MAX_PAGES), jnp.int32),
                  _sds((s,), jnp.int32), _sds((s,), jnp.bool_))
        donate = (2, 3, 4)
    else:
        def fn(p, t, n, pre, row, k, ik, st, slot):
            return model.prefill_paged(p, t, n, pre, row, k, None, state=st,
                                       slot=slot, aux=True, index_pool=ik)
        shapes = (params, _sds((1, bucket), jnp.int32), i32, i32,
                  _sds((DOTS_MAX_PAGES,), jnp.int32), pool, keys, state, i32)
        donate = (5, 6, 7)
    args = _program_args(shapes, one_chip, model)
    with interpret_scope(False):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_dots3_programs_hold_their_kernels_and_all_three_caches(program,
                                                                one_chip):
    """Every Mosaic call of the tick and of the 2,048 rung starts ``ds_``;
    the rings (1.019 GB), the pool and the indexer's keys (3.624 GB) pass
    through aliased to the outputs and nothing of a layer's size is a
    temporary; the arguments are the weights and those caches, in the
    compiler's own count as the configuration's ``reduced_why`` states it;
    all the chip must hold at once fits its 16.91e9 bytes; the tick, its
    query projections at rest as the engine holds them, copies no weight
    and no cache."""
    from deepspeed_tpu.utils.hlo import parameter_rewrites
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.pallas.context_attention import \
        LATENT_CONTEXT_ATTN_KERNEL
    from deepspeed_tpu.ops.pallas.decode_attention import (
        INDEX_SCORE_KERNEL, SPARSE_LATENT_DECODE_ATTN_KERNEL,
        WINDOW_LATENT_DECODE_ATTN_KERNEL)
    from deepspeed_tpu.ops.pallas.flash_attention import (
        FLASH_FWD_CTX_KERNEL, FLASH_FWD_KERNEL)
    compiled = _dots_program(program, one_chip)
    names = {n.split(".")[0] for n in _kernel_names(compiled)}
    experts = {dropless.MOE_GATE_UP_KERNEL, dropless.MOE_DOWN_KERNEL}
    assert names == experts | (
        {INDEX_SCORE_KERNEL, SPARSE_LATENT_DECODE_ATTN_KERNEL,
         WINDOW_LATENT_DECODE_ATTN_KERNEL} if program == "serve_decode"
        else {LATENT_CONTEXT_ATTN_KERNEL, FLASH_FWD_KERNEL,
              FLASH_FWD_CTX_KERNEL}), names
    mem = compiled.memory_analysis()
    rings = 6 * DOTS_SLOTS * 576 * 1152 * 2
    arrays = DOTS_PAGES * DOTS_PAGE_LEN * 3 * (640 + 128) * 2
    assert mem.alias_size_in_bytes >= rings + arrays
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(compiled.in_avals[0][0]))
    assert abs(weights - 9.207e9) < 1e6
    assert abs(mem.argument_size_in_bytes - weights - rings - arrays) \
        < 1 << 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16.0e9
    _, file = _dots_model()
    assert "arguments %.3f GB" % (mem.argument_size_in_bytes / 1e9) \
        in file["reduced_why"]
    if program == "serve_prefill":
        assert mem.temp_size_in_bytes < 0.5e9, mem.temp_size_in_bytes
        return
    # a layer's rings are 0.170 GB, a layer of rows 1.007: no temporary can
    # be a copy of either, and no weight is laid out again
    assert mem.temp_size_in_bytes < 0.06e9, mem.temp_size_in_bytes
    n = len(jax.tree.leaves(compiled.in_avals[0][0]))
    text = compiled.as_text()
    assert parameter_rewrites(text, n, 0.5) == []
    layer = DOTS_PAGES * DOTS_PAGE_LEN * 128 * 2
    assert [r for r in parameter_rewrites(text, n + 4, 0.0)
            if r.parameter > n and r.bytes >= layer] == []


# ---------------------------------------------------------------------------
# the form a query projection rests in (PR 55): ``ServeEngine`` holds the
# leaves a family declares (``WalkedModel.serving_layouts``) output-major,
# made once, and ``walked.project_heads`` contracts the last axes of both
# ---------------------------------------------------------------------------

#: family -> (its builder, the query projection's leaf name, how many the
#: cell's tick reads)
RELAID_TICKS = {"cmda": (_cmda_program, "q_w", 4),
                "glm": (_glm_program, "q_b_w", 7),
                "mimo": (_mimo_program, "q_w", 7)}


@pytest.mark.parametrize("family", sorted(RELAID_TICKS))
def test_the_tick_copies_every_query_weight_from_the_default_layout_and_none_at_rest(
        family, one_chip):
    """From the default layout the compiler copies every query
    projection whole before the matmul that reads it, once a layer, every
    tick (Command A+: ``copy(bitcast(param))``, 134 MB each written to HBM
    transposed; GLM-5.2 and MiMo-V2.5: into fast memory); held as the
    engine holds them (``inference/engine.py::params_at_rest``: ``w.T``
    under ``walked.OutputMajor``) the tick copies none, and writes no
    megabyte of any weight to HBM."""
    from deepspeed_tpu.utils.hlo import parameter_rewrites
    build, leaf, count = RELAID_TICKS[family]

    def moved(compiled):
        flat = jax.tree_util.tree_flatten_with_path(compiled.in_avals[0][0])
        names = [jax.tree_util.keystr(path) for path, _ in flat[0]]
        found = [(names[r.parameter], r) for r in parameter_rewrites(
            compiled.as_text(), len(names)) if r.bytes >= 1 << 20]
        return ([n for n, r in found if f"['{leaf}']" in n],
                [n for n, r in found if r.hbm_bytes])

    default = build("serve_decode", one_chip)
    queries, _ = moved(default)
    assert len(queries) == len(set(queries)) == count, queries
    relaid = build("serve_decode", one_chip, relaid=True)
    assert moved(relaid) == ([], [])
    # the same leaves, the declared ones transposed
    turned = [a.shape == d.shape[::-1] != d.shape for a, d in zip(
        *(jax.tree.leaves(c.in_avals[0][0]) for c in (relaid, default)))]
    assert turned.count(True) == count


# ---------------------------------------------------------------------------
# Olmo Hybrid at its cell's sizes (benchmark/configs/olmo-hybrid-7b.json: 6
# linear_attention layers + 2 full ones at hidden 3,840, 256 slots of
# delta-rule state 30 x 96 x 192 at rest [96, 5,760], 3,073 pages of 64 keys
# on 30 key heads): the scalar-decay decode update and both serve programs,
# the decode tick and ONE prefill rung
# ---------------------------------------------------------------------------

OLMO_SLOTS, OLMO_PAGE_LEN, OLMO_PAGES, OLMO_MAX_PAGES = 256, 64, 3073, 48


@functools.cache
def _olmo_hybrid_model():
    import json
    from deepspeed_tpu.models.olmo_hybrid import (OlmoHybridConfig,
                                                  OlmoHybridModel)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        file = json.load(f)
    serving = file["serving"]
    assert (serving["slots"], serving["page_len"], serving["pages"],
            -(-serving["max_seq_len"] // serving["page_len"])) == (
        OLMO_SLOTS, OLMO_PAGE_LEN, OLMO_PAGES, OLMO_MAX_PAGES)
    fields = {f.name for f in dataclasses.fields(OlmoHybridConfig)}
    keys = {k: v for k, v in file.items() if k in fields}
    return OlmoHybridModel(OlmoHybridConfig(
        **keys, param_dtype=file["dtype"])), file


def test_gdn_decode_kernel_keeps_its_name_and_the_state_in_place(one_chip):
    """256 slots x 6 layers of [96, 5,760] float32 aliased through: the
    state rests with NO padding (45 lane tiles a row, 12 sublane tiles:
    the compiler's own count of the leaf is the published 30 x 96 x 192 x
    4 B a slot and layer), a grid step's blocks and the body inside the
    kernel's VMEM limit, nothing of the state's size a temporary."""
    from deepspeed_tpu.ops.pallas.kda import GDN_DECODE_KERNEL, gdn_decode
    assert GDN_DECODE_KERNEL == "ds_gdn_decode"
    s, h, dk, dv = OLMO_SLOTS, 30, 96, 192
    f32 = jnp.float32
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (_sds((6 * s, dk, h * dv), f32), _sds((s, h), f32),
         _sds((s, h, dk), f32), _sds((s, h, dv), f32), _sds((s, h, dk), f32),
         _sds((s, h), f32), _sds((s,), jnp.bool_), _sds((), jnp.int32)))
    compiled = jax.jit(
        lambda st, a, k, v, q, b, act, base: gdn_decode(
            st, a, k, v, q, b, act, base=base, interpret=False),
        donate_argnums=(0,)).lower(*args).compile()
    names = _kernel_names(compiled)
    assert [n.split(".")[0] for n in names] == [GDN_DECODE_KERNEL]
    mem = compiled.memory_analysis()
    state = 6 * s * 2211840
    assert mem.alias_size_in_bytes == state
    assert f"f32[{6 * s},{dk},{h * dv}]{{2,1,0:T(8,128)}}" \
        in compiled.as_text()
    assert mem.temp_size_in_bytes < 4 << 20


@functools.cache
def _olmo_hybrid_program(program, one_chip, bucket=1024):
    """The model's paged step as the engine calls it: both pools and the
    state donated; a prefill at ``bucket`` tokens with its prefix length
    TRACED (a chunk)."""
    model, _ = _olmo_hybrid_model()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = _sds((2, OLMO_PAGES, 30, OLMO_PAGE_LEN, 128))
    state = model.serving_state(OLMO_SLOTS)
    i32, s = _sds((), jnp.int32), OLMO_SLOTS
    if program == "serve_decode":
        def fn(p, t, k, v, tab, ln, act, st):
            return model.decode_step_paged(p, t, k, v, tab, ln, act,
                                           state=st, impl="pallas", aux=True)
        shapes = (params, _sds((s,), jnp.int32), pool, pool,
                  _sds((s, OLMO_MAX_PAGES), jnp.int32),
                  _sds((s,), jnp.int32), _sds((s,), jnp.bool_), state)
        donate = (2, 3, 7)
    else:
        def fn(p, t, n, pre, row, k, v, st, slot):
            return model.prefill_paged(p, t, n, pre, row, k, v, state=st,
                                       slot=slot, aux=True)
        shapes = (params, _sds((1, bucket), jnp.int32), i32, i32,
                  _sds((OLMO_MAX_PAGES,), jnp.int32), pool, pool, state, i32)
        donate = (5, 6, 7)
    args = _program_args(shapes, one_chip, model)
    with interpret_scope(False):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill"])
def test_olmo_hybrid_programs_hold_their_kernels_and_no_copy_of_the_state(
        program, one_chip):
    """A tick runs ``ds_gdn_decode`` once a linear layer and
    ``ds_paged_decode_attn`` once a full layer (its pages read as they
    rest, ``[30, 64, 128]``: no transpose of a pool); a rung holds the
    flash forward for a first chunk and ``ds_flash_fwd_ctx`` for a later
    one, a layer each; both pools (6.04 GB) and the state (3.50 GB) pass
    through aliased to the outputs and nothing of their size is a
    temporary (a chunk gathers its request's pages out of the flat pool: a
    layer sliced out first was 1.5 GB); the arguments are the weights, the
    pools and the state; the compiler's own counts are the ones the
    configuration's ``reduced_why`` states, and fit the chip with the
    issue's 15.6 GB to spare for nothing: no page had to go."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        FLASH_FWD_CTX_KERNEL, FLASH_FWD_KERNEL)
    from deepspeed_tpu.ops.pallas.kda import GDN_DECODE_KERNEL
    from deepspeed_tpu.utils.hlo import kernel_calls
    compiled = _olmo_hybrid_program(program, one_chip)
    calls = kernel_calls(compiled.as_text())
    assert calls == ({GDN_DECODE_KERNEL: 6, PAGED_DECODE_ATTN_KERNEL: 2}
                     if program == "serve_decode"
                     else {FLASH_FWD_KERNEL: 2, FLASH_FWD_CTX_KERNEL: 2}), calls
    mem = compiled.memory_analysis()
    pools = 2 * 2 * OLMO_PAGES * 30 * OLMO_PAGE_LEN * 128 * 2
    state = 6 * OLMO_SLOTS * (2211840 + 3 * 11520 * 2)
    assert mem.alias_size_in_bytes >= pools + state
    weights = sum(a.size * a.dtype.itemsize
                  for a in jax.tree.leaves(compiled.in_avals[0][0]))
    assert abs(mem.argument_size_in_bytes - weights - pools - state) < 1 << 20
    assert mem.argument_size_in_bytes > 0.8 * 16.91e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.6e9
    _, file = _olmo_hybrid_model()
    said = {"serve_decode": "temporaries %.3f GB (decode",
            "serve_prefill": "%.3f GB (the 1,024 rung"}[program]
    assert "arguments %.3f GB" % (mem.argument_size_in_bytes / 1e9) \
        in file["reduced_why"]
    assert said % (mem.temp_size_in_bytes / 1e9) in file["reduced_why"]
    if program == "serve_decode":
        # the tails are written once, stacked (``walked.shift_tail``)
        again = [line for line in compiled.as_text().splitlines()
                 if ".remat = " in line and "%st__gdn_conv__" in line
                 and "scatter" in line]
        assert not again, again


def test_olmo_hybrid_decode_tick_reads_each_layers_matrices_where_they_lie(
        one_chip):
    """A leaf a layer: no fusion or copy of the tick's entry computation
    writes a weight again (``share`` 0.5: at 256 slots the float32 +
    bfloat16 results of ``x W_qkv`` fused with its convolution are a fifth
    of that weight's bytes, and are activations), so no leaf of this model
    asks for another form at rest (``query_projections`` names none)."""
    from deepspeed_tpu.utils.hlo import parameter_rewrites
    compiled = _olmo_hybrid_program("serve_decode", one_chip)
    weights = len(jax.tree.leaves(compiled.in_avals[0][0]))
    assert weights == 3 + 6 * 10 + 2 * 7 + 8 * 4
    moved = [r for r in parameter_rewrites(compiled.as_text(), weights,
                                           share=0.5)
             if r.bytes >= 1 << 20]
    assert [r for r in moved if r.op != "copy" or r.hbm_bytes] == [], moved


# ---------------------------------------------------------------------------
# whose the programs' instructions are (PR 54): ``utils/hlo.py::scope_cycles``
# over the texts the tests above already hold.  Cycles are the compiler's
# guess, of the instructions it guesses for (fusions and copies), in programs
# that donate nothing: no time, and no share of the chip's (PERF.md section 5
# has those, from traces).  What the ceiling guards is the layer map itself: a
# refactor that drops a ``jax.named_scope``, or moves work outside one, shows
# here with no chip.
# ---------------------------------------------------------------------------

#: program -> (its text, percent of its estimated cycles under
#: ``(unscoped)`` + ``mixed:`` as read when PR 54 wrote this).  Where a
#: reading is over 10 the cycles are copies the compiler put in and gave no
#: ``op_name`` (a pool the test does not donate, a weight laid out again:
#: ``scopes()`` lists them by instruction); no ``named_scope`` reaches those.
SCOPED_PROGRAMS = {
    "gpt2.serve_decode": (lambda c: _gpt2_decode_program(c).as_text(), 0.0),
    "gpt2.serve_prefill": (lambda c: _gpt2_prefill_program(c).as_text(), 0.2),
    "gpt2.train": (lambda c: _gpt2_train_program(c).as_text(), 0.2),
    "bert.train": (lambda c: _bert_large_step(c, saved=True), 0.1),
    # 57.1 before PR 58: the same unscoped copies (of the pool the test
    # does not donate) over fewer cycles under ``layer/moe``
    "olmoe.serve_decode": (lambda c: _olmoe_decode_program(c).as_text(), 59.3),
    "olmoe.serve_prefill": (lambda c: _olmoe_prefill_program(c).as_text(),
                            9.9),
    "nemotron.serve_decode": (
        lambda c: _nemotron_program("serve_decode", c).as_text(), 1.4),
    "mimo.serve_decode": (
        lambda c: _mimo_program("serve_decode", c).as_text(), 38.7),
    "axk1.serve_decode": (
        lambda c: _axk1_program("serve_decode", c).as_text(), 0.7),
    "axk1.serve_prefill": (
        lambda c: _axk1_program("serve_prefill", c).as_text(), 14.2),
    "cmda.serve_decode": (
        lambda c: _cmda_program("serve_decode", c).as_text(), 32.4),
    "glm.serve_decode": (
        lambda c: _glm_program("serve_decode", c).as_text(), 27.1),
    "kimi.serve_decode": (
        lambda c: _kimi_program("serve_decode", c).as_text(), 6.4),
    "olmo_hybrid.serve_decode": (
        lambda c: _olmo_hybrid_program("serve_decode", c).as_text(), 2.1),
}


@pytest.mark.parametrize("program", sorted(SCOPED_PROGRAMS))
def test_the_layer_map_owns_the_programs_estimated_cycles(program, one_chip):
    """At most what it read when written + 2 points of a program's
    estimated cycles belong to no scope of the layer map (or to two)."""
    build, read = SCOPED_PROGRAMS[program]
    text = build(one_chip)
    cycles = scope_cycles(text)
    total = sum(cycles.values())
    assert total > 0
    loose = sum(n for scope, n in cycles.items()
                if scope == UNSCOPED or scope.startswith("mixed:"))
    largest = sorted((s for s in scopes(text) if s.cycles and (
        not s.scope or s.scope.startswith("mixed:"))),
        key=lambda s: -s.cycles * s.times)[:6]
    print(f"{program}: {100.0 * loose / total:.1f} % of {total} estimated "
          f"cycles unscoped or mixed; largest: " + ", ".join(
              f"{s.instruction} [{s.op_name}] x{s.times}" for s in largest))
    assert 100.0 * loose / total <= read + 2.0, largest


@pytest.mark.parametrize("program", ["gpt2.serve_decode", "gpt2.train"])
def test_a_named_scope_changes_names_and_never_instructions(program, one_chip,
                                                           monkeypatch):
    """The program compiled with every ``with jax.named_scope(...)`` of
    the model code a no-op (the ``layer`` around the layer scan and the
    loss under ``lm_head`` of PR 54 among them) is the same program less
    its metadata: the scan's slices of the pool, 95 % of the decode
    tick's estimated cycles, have an owner and not one instruction
    moved."""
    import contextlib
    build = {"gpt2.serve_decode": _gpt2_decode_program,
             "gpt2.train": _gpt2_train_program}[program]
    scoped = build(one_chip).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = build.__wrapped__(one_chip).as_text()
    assert less_metadata(bare) == less_metadata(scoped)
    assert scope_cycles(bare).get(UNSCOPED, 0) \
        > 10 * scope_cycles(scoped).get(UNSCOPED, 0)
