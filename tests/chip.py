"""What the described-chip files (``tests/test_chip_*.py``) share: kernels
and whole programs compiled for a TPU v5e that is described and not
attached.  Nothing runs: this guards against what interpret mode cannot see
(tile alignment, scalar-prefetch and VMEM budgets, unsupported lowerings, a
cache copied, a weight laid out again) at no chip time.  A compile that
passes is not a chip run.

A whole program is compiled once a process and kept (``functools.cache``
on its builder), so every reader of a family's programs lives in that
family's file, ``tests/test_chip_<family>.py``, and the files run on any
workers at once.  The topology is described only inside the ``topo``
fixture (``tests/conftest.py`` re-exports it), never at import.  A served
family is a ``Test<Family>(ServedFamily)``: its configuration's name, what
each program holds as data, its own kernels' tests as methods beside them.
"""
import dataclasses
import functools
import importlib.util
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNEL = "tpu_custom_call"
BF16 = jnp.bfloat16
PROGRAMS = ("serve_decode", "serve_prefill")
#: what a v5e's compiler has for a program's arguments and temporaries
CHIP_BYTES = 16.91e9


def described_host():
    """A v5e 2x2 host, described.  Several processes may describe one at
    once (the workers of a run, a child of a test): each says so to the
    TPU library itself, which otherwise gives ``/tmp/libtpu_lockfile`` to
    the first and refuses the rest."""
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def topo():
    """``described_host()``; skipped only where there is no TPU library to
    describe one, so a lock lost to another process fails.  The persistent
    compilation cache is off meanwhile: a program compiled for a described
    device is written to it but can never be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no libtpu: no v5e:2x2 topology can be described here")
    topo = described_host()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


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


def _compile(fn, one_chip, *shapes, donate=()):
    """Compile ``fn`` for the described chip; the program must hold a
    Mosaic kernel."""
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        *_program_args(shapes, one_chip)).compile()
    assert KERNEL in compiled.as_text()
    return compiled


def _kernel_names(compiled):
    """Names of the program's Mosaic custom calls, less the compiler's
    ``.<n>``: the int8 and multi arms extend the base names, so a prefix
    test would let the wrong body pass."""
    return [n.split(".")[0] for n in re.findall(
        r'^\s*(?:ROOT )?%?(\S+) = [^\n]*custom_call_target="' + KERNEL + '"',
        compiled.as_text(), flags=re.M)]


def _is_one_kernel(compiled, name, temporaries):
    """A kernel alone: one Mosaic call under ``name``, its operands left
    where they lie (temporaries under ``temporaries`` bytes)."""
    assert _kernel_names(compiled) == [name]
    assert compiled.memory_analysis().temp_size_in_bytes < temporaries


def _unscoped_percent(text, what):
    """Percent of a program's estimated cycles that no scope of the layer
    map owns (or two do).  Cycles are the compiler's guess, of the fusions
    and copies it guesses for: no time, and no share of the chip's
    (PERF.md section 5 has those, from traces)."""
    from deepspeed_tpu.utils.hlo import UNSCOPED, scope_cycles, scopes
    cycles = scope_cycles(text)
    total = sum(cycles.values())
    assert total > 0
    loose = sum(n for scope, n in cycles.items()
                if scope == UNSCOPED or scope.startswith("mixed:"))
    largest = sorted((s for s in scopes(text) if s.cycles and (
        not s.scope or s.scope.startswith("mixed:"))),
        key=lambda s: -s.cycles * s.times)[:6]
    print(f"{what}: {100.0 * loose / total:.1f} % of {total} estimated "
          f"cycles unscoped or mixed; largest: " + ", ".join(
              f"{s.instruction} [{s.op_name}] x{s.times}" for s in largest))
    return 100.0 * loose / total


def gpt2_124m():
    from deepspeed_tpu.models.gpt2 import GPT2Config
    return GPT2Config(d_model=768, n_layer=12, n_head=12, vocab_size=50257,
                      n_positions=1024, attn_impl="flash")


def window_decode(one_chip, slots):
    """``ds_window_decode_attn`` over MiMo-V2.5's five layers of rings."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        window_decode_attention
    return _compile(
        lambda q, k, v, n, b, base: window_decode_attention(
            q, k, v, n, b, base=base, sm_scale=192 ** -0.5,
            interpret=False),
        one_chip, _sds((slots, 64, 256)), _sds((5 * slots, 8, 128, 256)),
        _sds((5 * slots, 8, 128, 128)), _sds((slots,), jnp.int32),
        _sds((64,)), _sds((), jnp.int32))


def gated_experts_alone(one_chip, tokens, d, f, routed, stack, held=None):
    """``dropless_moe``'s two gated kernels alone: ``tokens`` rows, top-8
    of ``routed`` experts of ``d`` x ``f``, a flat stack of ``stack`` of
    which a layer is this chip's ``held`` (all of them where None)."""
    from deepspeed_tpu.moe import dropless
    kw = {} if held is None else {"experts_held": (0, held)}
    compiled = _compile(
        lambda x, r, g, u, w: dropless.dropless_moe(
            x, r, g, u, w, 8, expert_offset=jnp.int32(held or routed),
            interpret=False, **kw)[0],
        one_chip, _sds((tokens, d)), _sds((d, routed)), _sds((stack, d, f)),
        _sds((stack, d, f)), _sds((stack, f, d)))
    assert sorted(_kernel_names(compiled)) == [
        dropless.MOE_DOWN_KERNEL, dropless.MOE_GATE_UP_KERNEL]


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
# a served family's programs, from its configuration's file
# ---------------------------------------------------------------------------

#: OLMoE's programs stay two layers deep over 513 pages, not the file's 12
#: over 3,457: the scan body compiles once, and the limits and the unscoped
#: share asserted of them were read at this pool's size.
CUT = {"olmoe-1b-7b": ({"num_hidden_layers": 2}, {"pages": 1 + 4 * 128})}


@functools.cache
def served_model(config_name, cut=True):
    """(the model ``benchmark/run.py`` builds from
    ``benchmark/configs/<config_name>.json``, the file), cut as ``CUT``
    says."""
    if os.path.join(ROOT, "benchmark") not in sys.path:
        sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from lib import families
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config_name + ".json")) as f:
        file = json.load(f)
    model = families.build(file, rehearse=False).model
    if cut and config_name in CUT:
        sizes, serving = CUT[config_name]
        model = type(model)(dataclasses.replace(model.config, **sizes))
        file["serving"].update(serving)
    return model, file


@functools.cache
def served_cache(config_name, cut=True):
    """(spec, cache, state) of the configuration's cell, abstract: the
    cache ``ServeEngine`` makes for this model under the file's ``serving``
    block, and the request state by slot (None: the model keeps none)."""
    from deepspeed_tpu.inference.kv_cache import (PagedKVCacheSpec,
                                                  init_paged_cache)
    model, file = served_model(config_name, cut)
    serving = file["serving"]
    spec = PagedKVCacheSpec.for_model(
        model.config, slots=serving["slots"], pages=serving["pages"],
        page_len=serving["page_len"], max_seq_len=serving["max_seq_len"],
        dtype=jnp.dtype(file["dtype"]))
    state = (dict(model.serving_state(spec.slots))
             if hasattr(model, "serving_state") else {})
    return (spec, jax.eval_shape(lambda: init_paged_cache(spec)),
            state or None)


def _nbytes(tree):
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


@functools.cache
def served_program(config_name, program, one_chip, bucket=None,
                   relaid=False):
    """The model's paged step as the engine calls it, compiled once a
    process: pools, indexer keys and request state donated, None where a
    family has no second pool, no indexer or no state; a prefill at
    ``bucket`` tokens with its prefix length and slot TRACED, so a whole
    prompt and a chunk are one program; ``relaid``: the params as the
    engine holds them at rest."""
    from deepspeed_tpu.ops.pallas.runtime import interpret_scope
    model, _ = served_model(config_name)
    spec, cache, state = served_cache(config_name)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    held = (cache["k"], cache.get("v"), cache.get("index_k"), state)
    i32, s = _sds((), jnp.int32), spec.slots

    def kept(ik, st, **slot):
        return {**({} if ik is None else {"index_pool": ik}),
                **({} if st is None else {"state": st, **slot})}

    # the argument NAMES are in the text (``%st__kda_conv__``) and the
    # params are parameters 0.. (``utils/hlo.py::parameter_rewrites``)
    if program == "serve_decode":
        def fn(p, t, k, v, ik, st, tab, ln, act):
            return model.decode_step_paged(p, t, k, v, tab, ln, act,
                                           impl="pallas", aux=True,
                                           **kept(ik, st))
        shapes = (params, _sds((s,), jnp.int32), *held,
                  _sds((s, spec.max_pages), jnp.int32), cache["lengths"],
                  _sds((s,), jnp.bool_))
        first = 2
    else:
        def fn(p, t, n, pre, row, k, v, ik, st, slot):
            return model.prefill_paged(p, t, n, pre, row, k, v, aux=True,
                                       **kept(ik, st, slot=slot))
        shapes = (params, _sds((1, bucket), jnp.int32), i32, i32,
                  _sds((spec.max_pages,), jnp.int32), *held,
                  None if state is None else i32)
        first = 5
    donate = tuple(first + i for i, a in enumerate(held) if a is not None)
    args = _program_args(shapes, one_chip, model if relaid else None)
    with interpret_scope(False):
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


class ServedFamily:
    """What every served family's compiled programs are held to.  A
    family states, as data:"""
    #: ``benchmark/configs/<config>.json``
    config: str
    #: its name in ``TEMPORARIES_GB``
    family = None
    #: its programs are compiled with the params at rest (``relaid``)
    at_rest = False
    #: program -> {Mosaic kernel: calls, loops counted}
    kernels: dict
    #: program -> what its temporaries stay under, bytes
    temporaries: dict = {}
    #: arguments + temporaries stay under this (the chip has CHIP_BYTES)
    fits = None
    #: the arguments are at least this share of CHIP_BYTES
    arguments_share = 0.0
    #: the weights' bytes, within a megabyte
    weights = None
    #: programs whose arguments the file's ``reduced_why`` counts as the
    #: compiler does, and program -> how it says their temporaries
    says_arguments = PROGRAMS
    says_temporaries: dict = {}
    #: program -> percent of its estimated cycles unscoped, as read
    unscoped: dict

    @classmethod
    def program(cls, one_chip, program, bucket=None, relaid=None):
        """``served_program`` of this family, the top rung by default."""
        if program == "serve_prefill" and bucket is None:
            bucket = served_model(cls.config)[1]["serving"]["prefill_len"]
        return served_program(cls.config, program, one_chip, bucket,
                              cls.at_rest if relaid is None else relaid)

    @classmethod
    def spec(cls):
        return served_cache(cls.config)[0]

    @pytest.mark.parametrize("program", PROGRAMS)
    def test_programs_hold_their_kernels_and_caches(self, program, one_chip):
        """Every Mosaic call of both serve programs is one the family
        names (so starts ``ds_``: what ``unnamed_kernel_share.*`` reads as
        0), as often as it names it; the pools, the indexer's keys and the
        request state pass through aliased to the outputs and no program
        copies a cache; the arguments are the weights and those caches;
        the compiler's own counts are the ones the configuration's
        ``reduced_why`` states; all the chip must hold at once fits it."""
        from deepspeed_tpu.utils.hlo import kernel_calls
        compiled = self.program(one_chip, program)
        calls = kernel_calls(compiled.as_text())
        assert calls == self.kernels[program], calls
        spec, _, state = served_cache(self.config)
        caches = spec.bytes + _nbytes(state)
        weights = _nbytes(compiled.in_avals[0][0])
        mem = compiled.memory_analysis()
        print(f"{self.config} {program}: arguments "
              f"{mem.argument_size_in_bytes} B, temporaries "
              f"{mem.temp_size_in_bytes} B, weights {weights} B")
        assert mem.alias_size_in_bytes >= caches
        assert abs(mem.argument_size_in_bytes - weights - caches) < 1 << 20
        assert self.weights is None or abs(weights - self.weights) < 1e6
        assert mem.argument_size_in_bytes >= self.arguments_share * CHIP_BYTES
        limit = self.temporaries.get(program)
        assert limit is None or mem.temp_size_in_bytes < limit, \
            mem.temp_size_in_bytes
        assert self.fits is None or (
            mem.argument_size_in_bytes + mem.temp_size_in_bytes < self.fits)
        file = served_model(self.config)[1]
        why = file["reduced_why"]
        if program in self.says_arguments:
            assert "arguments %.3f GB" % (mem.argument_size_in_bytes / 1e9) \
                in why
        if program in self.says_temporaries:
            assert self.says_temporaries[program] % (
                mem.temp_size_in_bytes / 1e9) in why
        if (self.family, program) in TEMPORARIES_GB:
            _holds_its_temporaries(mem, file, self.family, program)

    @pytest.mark.parametrize("rung", ["half", "quarter"])
    def test_a_lower_rung_compiles_under_the_rung_above_it(self, rung,
                                                           one_chip):
        """``ServeEngine`` builds ``serve_prefill`` below
        ``serving.prefill_len`` too (``inference/engine.py::prefill_ladder``:
        4,096 -> 1,024 and 2,048; 2,048 -> 512 and 1,024; 1,024 -> 256 and
        512).  A shorter rung holds the same kernels, passes the same caches
        through aliased, and needs fewer temporaries than the rung above it:
        what the chip must hold at once is still set by ``prefill_len``."""
        from deepspeed_tpu.inference.engine import prefill_ladder
        top = self.program(one_chip, "serve_prefill")
        top_len = top.in_avals[0][1].shape[1]
        bucket = top_len // {"half": 2, "quarter": 4}[rung]
        assert bucket in prefill_ladder(top_len)[:-1]
        above = self.program(one_chip, "serve_prefill", 2 * bucket)
        lower = self.program(one_chip, "serve_prefill", bucket)
        assert lower.in_avals[0][1].shape == (1, bucket)
        assert set(_kernel_names(lower)) == set(_kernel_names(top))
        mem, top_mem = lower.memory_analysis(), top.memory_analysis()
        assert mem.alias_size_in_bytes == top_mem.alias_size_in_bytes
        assert mem.argument_size_in_bytes <= top_mem.argument_size_in_bytes
        above_temp = above.memory_analysis().temp_size_in_bytes
        temps = (mem.temp_size_in_bytes, above_temp,
                 top_mem.temp_size_in_bytes)
        print(f"{self.config} serve_prefill temporaries at {bucket}, "
              f"{2 * bucket} and {top_len} tokens: {temps}")
        assert temps[0] < temps[1] <= temps[2]

    @pytest.mark.parametrize("program", PROGRAMS)
    def test_the_layer_map_owns_the_programs_estimated_cycles(
            self, program, one_chip):
        """At most what it read when written + 2 points of a program's
        estimated cycles belong to no scope of the layer map (or to two).
        Over 10 the cycles are copies the compiler put in and gave no
        ``op_name`` (a weight laid out again: ``scopes()`` lists them by
        instruction); no ``named_scope`` reaches those.  The ceiling guards
        the layer map: a refactor that drops a ``jax.named_scope``, or
        moves work outside one, shows here with no chip."""
        text = self.program(one_chip, program).as_text()
        assert _unscoped_percent(text, f"{self.config} {program}") \
            <= self.unscoped[program] + 2.0


class ReadsItsMatricesWhereTheyLie:
    """For a family that keeps a leaf a layer (``models/mimo_v2.py``'s
    rule); it states ``matrices``: ``leaves`` (how many the tick reads),
    ``share`` (``parameter_rewrites``' own where not said; 0.5 where the
    results of a wide matmul fused with its convolution are a third of the
    weight's bytes, and are activations) and ``relaid`` (as the engine
    holds them)."""
    matrices: dict

    def test_the_tick_reads_each_layers_matrices_where_they_lie(
            self, one_chip):
        """No fusion of the tick's entry computation writes a megabyte of
        a weight again, stacked matrices ``[heads, ., .]`` included; what
        is copied is a matrix's one read into the layout its dot takes,
        into fast memory and not into HBM.  A leaf stacked again trips
        this."""
        from deepspeed_tpu.utils.hlo import parameter_rewrites
        compiled = self.program(one_chip, "serve_decode",
                                relaid=self.matrices.get("relaid"))
        weights = len(jax.tree.leaves(compiled.in_avals[0][0]))
        assert weights == self.matrices["leaves"]
        moved = [r for r in parameter_rewrites(
            compiled.as_text(), weights, self.matrices.get("share", 0.125))
            if r.bytes >= 1 << 20]
        assert [r for r in moved if r.op != "copy" or r.hbm_bytes] == [], moved


class RestsItsQueryProjectionsOutputMajor:
    """For a family whose ``query_projections`` names a leaf (PR 55):
    ``ServeEngine`` holds those output-major, made once, and
    ``walked.project_heads`` contracts the last axes of both.  It states
    ``relaid``: (the leaf's name, how many the cell's tick reads)."""
    relaid: tuple

    def test_the_tick_copies_every_query_weight_from_the_default_layout_and_none_at_rest(
            self, one_chip):
        """From the default layout the compiler copies every query
        projection whole before the matmul that reads it, once a layer,
        every tick (Command A+: ``copy(bitcast(param))``, 134 MB each
        written to HBM transposed; GLM-5.2 and MiMo-V2.5: into fast
        memory); held as the engine holds them (``params_at_rest``: ``w.T``
        under ``walked.OutputMajor``) the tick copies none, and writes no
        megabyte of any weight to HBM."""
        from deepspeed_tpu.utils.hlo import parameter_rewrites
        leaf, count = self.relaid

        def moved(compiled):
            flat = jax.tree_util.tree_flatten_with_path(
                compiled.in_avals[0][0])
            names = [jax.tree_util.keystr(path) for path, _ in flat[0]]
            found = [(names[r.parameter], r) for r in parameter_rewrites(
                compiled.as_text(), len(names)) if r.bytes >= 1 << 20]
            return ([n for n, r in found if f"['{leaf}']" in n],
                    [n for n, r in found if r.hbm_bytes])

        default = self.program(one_chip, "serve_decode", relaid=False)
        queries, _ = moved(default)
        assert len(queries) == len(set(queries)) == count, queries
        relaid = self.program(one_chip, "serve_decode", relaid=True)
        assert moved(relaid) == ([], [])
        # the same leaves, the declared ones transposed
        turned = [a.shape == d.shape[::-1] != d.shape for a, d in zip(
            *(jax.tree.leaves(c.in_avals[0][0]) for c in (relaid, default)))]
        assert turned.count(True) == count
