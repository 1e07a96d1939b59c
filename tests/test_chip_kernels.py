"""The main path's kernels alone, and GPT-2's programs at 124M and XL
widths, compiled for a described v5e (``tests/chip.py``).  A served
family's own kernels are in its file, ``tests/test_chip_<family>.py``; the
training texts in ``tests/test_chip_training.py``.
"""
import contextlib
import dataclasses
import functools
import importlib
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import (BF16, _compile, _kernel_names, _sds, _unscoped_percent,
                  gpt2_124m, window_decode)
from deepspeed_tpu.models.gpt2 import GPT2Model
from deepspeed_tpu.ops.pallas.decode_attention import (
    PAGED_DECODE_ATTN_KERNEL, PAGED_KV_VMEM_BUDGET, decode_attention,
    decode_attention_multi, decode_attention_paged,
    decode_attention_paged_multi, paged_page_vmem_bytes,
    paged_pages_per_block)
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.runtime import interpret_scope
from deepspeed_tpu.utils.hlo import UNSCOPED, less_metadata, scope_cycles

SLOTS, SEQ, DH = 8, 1024, 64
GPT2_124M = gpt2_124m()


def test_a_second_process_describes_the_chip_while_this_one_holds_it(topo):
    """The files of this suite run on any workers at once, and whoever
    calls them need not have said so: a child with
    ``ALLOW_MULTIPLE_LIBTPU_LOAD`` unset describes the host while this
    process holds the TPU library (``chip.described_host`` says it for
    itself); without that the child loses ``/tmp/libtpu_lockfile``."""
    env = {k: v for k, v in os.environ.items()
           if k != "ALLOW_MULTIPLE_LIBTPU_LOAD"}
    child = subprocess.run(
        [sys.executable, "-c", "import chip; "
         "print(len(chip.described_host().devices))"],
        cwd=os.path.dirname(os.path.abspath(__file__)), env=env,
        capture_output=True, text=True, timeout=300)
    assert child.returncode == 0, child.stderr[-2000:]
    assert child.stdout.split()[-1] == str(len(topo.devices)) == "4"


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


# ---------------------------------------------------------------------------
# the four decode arms at 124M (12) and XL (25) head counts
# ---------------------------------------------------------------------------

def _slot(heads, one_chip, w=None):
    """The slot cache's arm; ``w``: its multi-query form at that many
    queries a slot."""
    cache, wide = _sds((SLOTS, heads, SEQ, DH)), () if w is None else (w,)
    attend = decode_attention if w is None else decode_attention_multi
    return _compile(
        lambda q, k, v, n: attend(q, k, v, n, interpret=False),
        one_chip, _sds((SLOTS, heads, *wide, DH)), cache, cache,
        _sds((SLOTS, *wide), jnp.int32))


def _paged(heads, one_chip, page_len, quant=False, w=None):
    """The page pool's arm, fp or int8 with its scales; ``w`` as above."""
    pages, max_pages = 1 + SLOTS * (SEQ // page_len), SEQ // page_len
    pool = _sds((pages, heads, page_len, DH), jnp.int8 if quant else BF16)
    scales = [_sds((pages, heads, page_len), jnp.float32)] * 2 * quant
    wide = () if w is None else (w,)
    attend = (decode_attention_paged if w is None
              else decode_attention_paged_multi)
    return _compile(
        lambda q, k, v, t, n, *scales: attend(
            q, k, v, t, n, interpret=False,
            **dict(zip(("k_scale", "v_scale"), scales))),
        one_chip, _sds((SLOTS, heads, *wide, DH)), pool, pool,
        _sds((SLOTS, max_pages), jnp.int32), _sds((SLOTS, *wide), jnp.int32),
        *scales)


ARMS = {
    "slot": _slot,
    "multi": lambda h, c: _slot(h, c, w=5),
    "paged16": lambda h, c: _paged(h, c, 16),
    "paged64": lambda h, c: _paged(h, c, 64),
    "paged128": lambda h, c: _paged(h, c, 128),
    "paged_multi16": lambda h, c: _paged(h, c, 16, w=5),
    "int8_paged16": lambda h, c: _paged(h, c, 16, quant=True),
    "int8_paged128": lambda h, c: _paged(h, c, 128, quant=True),
    "int8_paged_multi64": lambda h, c: _paged(h, c, 64, quant=True, w=5),
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
    assert _kernel_names(_paged(heads, one_chip, page_len)) \
        == [PAGED_DECODE_ATTN_KERNEL]


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

def _sparse(one_chip, grad):
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
        ("decode_attention", lambda c: window_decode(c, 192)),
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
    module, build = KERNEL_CASES[constant]
    name = getattr(importlib.import_module(
        "deepspeed_tpu.ops.pallas." + module), constant)
    assert name.startswith("ds_")
    names = _kernel_names(build(one_chip))
    assert name in names, names


def test_bare_grad_wraps_the_kernel_name(one_chip):
    """The known limit, pinned: with no closed jaxpr between ``jax.grad``
    and the kernel, the instruction is named after the transformation
    and the benchmark's ``unnamed_kernel_share.*`` counts it.  Every
    model path has a layer scan, remat or shard_map in between."""
    assert sorted(_kernel_names(_flash_bwd(one_chip, 4))) == [
        "jvp_ds_flash_fwd_", "transpose_jvp_ds_flash_bwd_dkv__",
        "transpose_jvp_ds_flash_bwd_dq__"]


@functools.cache
def _gpt2_train_program(one_chip):
    """Forward + backward of GPT-2 124M's widths through the flash
    kernel, two layers deep under remat and the layer scan (the scan
    body compiles once, so depth adds nothing to check)."""
    model = GPT2Model(dataclasses.replace(GPT2_124M, n_layer=2))
    params = jax.tree.map(lambda s: _sds(s.shape),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))

    def step(params, tokens, rng):
        return jax.value_and_grad(model.loss_fn)(params, tokens, rng)

    with interpret_scope(False):
        return _compile(step, one_chip, params,
                        _sds((4, SEQ + 1), jnp.int32),
                        _sds((2,), jnp.uint32))


@pytest.mark.parametrize("program", ["serve_decode", "serve_prefill", "train"])
def test_gpt2_programs_hold_no_unnamed_kernel(program, one_chip):
    """Inside scan, remat, cond and custom_vjp alike, no Mosaic call of
    the GPT-2 programs is named after a JAX construct (``closed_call``,
    ``checkpoint``, ``branch_1_fun``, ...): each starts with ``ds_``."""
    names = _kernel_names(GPT2_PROGRAMS[program][0](one_chip))
    assert names
    assert all(n.startswith("ds_") for n in names), names




# ---------------------------------------------------------------------------
# whose the programs' instructions are (PR 54)
# ---------------------------------------------------------------------------

#: program -> (its builder, percent of its estimated cycles under
#: ``(unscoped)`` + ``mixed:`` as read when PR 54 wrote this)
GPT2_PROGRAMS = {"serve_decode": (_gpt2_decode_program, 0.0),
                 "serve_prefill": (_gpt2_prefill_program, 0.2),
                 "train": (_gpt2_train_program, 0.2)}


@pytest.mark.parametrize("program", sorted(GPT2_PROGRAMS))
def test_the_layer_map_owns_the_programs_estimated_cycles(program, one_chip):
    """At most what it read when written + 2 points of a program's
    estimated cycles belong to no scope of the layer map (or to two)."""
    build, read = GPT2_PROGRAMS[program]
    assert _unscoped_percent(build(one_chip).as_text(),
                             "gpt2." + program) <= read + 2.0


@pytest.mark.parametrize("program", ["serve_decode", "train"])
def test_a_named_scope_changes_names_and_never_instructions(program, one_chip,
                                                           monkeypatch):
    """The program compiled with every ``with jax.named_scope(...)`` of
    the model code a no-op (the ``layer`` around the layer scan and the
    loss under ``lm_head`` of PR 54 among them) is the same program less
    its metadata: the scan's slices of the pool, 95 % of the decode
    tick's estimated cycles, have an owner and not one instruction
    moved."""
    build, _ = GPT2_PROGRAMS[program]
    scoped = build(one_chip).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = build.__wrapped__(one_chip).as_text()
    assert less_metadata(bare) == less_metadata(scoped)
    assert scope_cycles(bare).get(UNSCOPED, 0) \
        > 10 * scope_cycles(scoped).get(UNSCOPED, 0)
