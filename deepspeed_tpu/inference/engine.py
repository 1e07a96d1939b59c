"""ServeEngine — the KV-cached decode engine (docs/serving.md).

The serving half of the north star: requests stream through a bounded
queue into a FIXED pool of decode slots, and two compiled programs
serve every mix —

  ``prefill``      one request's prompt (right-padded to the smallest
                   rung of the prefill ladder that holds it:
                   ``prefill_ladder(serving.prefill_len)``) → its K/V
                   rows written into the assigned slot + the first
                   greedy token.
  ``decode_step``  ONE masked tick for ALL slots at once: each active
                   slot's last token in, its next greedy token out, its
                   K/V appended in place.  Free/finished slots ride
                   along masked.  Static shapes by construction: the
                   request mix NEVER changes a program shape, so
                   ``recompiles_total{program=decode_step}`` stays 0
                   (asserted by tests/test_inference.py).

Admission/eviction are the continuous-batching moves (Orca, PAPERS.md):
a finished slot is refilled on the very next tick instead of waiting
for the batch to drain.  The KV cache rides the layouts of
``kv_cache.py`` — TP-sharded heads, DP-sharded slots/pages — via the
ordinary mesh plumbing.

Paged mode (``serving.page_len > 0`` — PagedAttention + RadixAttention,
PAPERS.md): KV storage becomes a flat pool of fixed-size pages and each
slot gets a host-owned int32 page table passed as a TRACED operand, so
a short request holds ``ceil(len/page_len)`` pages instead of a full
``max_seq_len`` stride — the pool, not the slot count, caps how many
users fit a chip (tests/test_paged_kv.py counts the multiple).  The
scheduler grows a refcounted page allocator (free-list alloc on
admission/append, free on eviction; ``kv_capacity`` finishes become
pool-exhaustion-aware and admission backpressures when even prefix-
cache eviction can't free enough pages) and, on top, PREFIX CACHING:
prompt prefixes hash to refcounted read-only shared pages, a divergent
append copy-on-writes the last partial page, and the prefill program
computes only the uncached delta — N requests sharing a system prompt
store and prefill it once.

Speculative decoding (``serving.speculate_k > 0`` — Leviathan et al.
2023, Chen et al. 2023, PAPERS.md): a small DRAFT model (the
``serving.draft`` config block; its own fixed-stride slot KV cache)
proposes k tokens per tick in one compiled propose program, and the
target scores all k+1 positions per slot in ONE widened
``verify_step`` program — the pass that used to buy one token now buys
``accepted + 1`` of them: fewer target passes than tokens
(tests/test_spec_decode.py holds the count; no cell times it yet).
Greedy acceptance emits exactly the non-speculative stream (the parity
bar); ``serving.temperature > 0`` switches to rejection-sampling
acceptance that recovers the target distribution
(inference/speculative.py).  Rollback: unpaged masks lengths back;
paged frees the pages only rejected speculation touched.  Accepted-
length variance makes per-slot progress uneven — exactly what the
masked slot machinery absorbs.

Quantized serving (``serving.quantization``, docs/serving.md): two
independently togglable int8 arms, both STATIC for the engine's life.
``weights='int8'`` quantizes the GPT-2 matmul weights per output
channel at build (LLM.int8, PAPERS.md) and fuses dequant into the
serving matmuls — the fp master never reaches the device, params HBM
~ halves (the ``serve_param_bytes`` plane measures it).
``kv='int8'`` stores the paged pool as int8 rows + per-row fp32 scale
sidecars, quantized on write inside the compiled programs and
dequantized fused in the decode kernels — ~2x more pages in the same
KV bytes (tests/test_quant_serve.py holds the admitted-requests
multiple), composing multiplicatively with paging and making the
speculative draft plane nearly free.  Default off = every program
bitwise-unchanged.

A tick sent ahead (docs/serving.md "A tick"): while every slot is taken
the engine sends decode tick n+1, fed tick n's output tokens as they lie
on the device, BEFORE it pulls and books tick n's, so the device has a
program queued while the host counts; with a free slot the order is the
old one (``ServeEngine._run_ahead`` is the rule, ``ahead_stats`` the
count).  An admission that fills the last free slot of such an engine
sends the next tick BEHIND its prefill, the request's first token taken
from the prefill's output on the device, before the host waits for that
token (``_send_behind_prefill``): the device goes from the prefill to the
tick without the host between them.  All orders run the one compiled
``serve_decode``.

Fault plane: the request queue is a stages.py :class:`Channel` and all
serving work runs under one :class:`Stage` record ("serve", points
``admit``/``step``), so poison/drain semantics, graceful degradation
(budget-exhausted → chaos-free direct serving) and the unified
``DS_STAGE_FAULT``/``DS_STAGE_DELAY_S`` spec apply unchanged — the
bench's A/B leg injects its synthetic per-tick device time through
exactly that knob.  In spec mode one delay unit buys one TARGET pass
(a whole verify block), not one token — docs/stages.md.
"""
from __future__ import annotations

import contextlib
import json
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor, wait
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config.config import (DeepSpeedConfig, DeepSpeedServingConfig,
                             DeepSpeedStagesConfig,
                             DeepSpeedTelemetryConfig)
from ..config import constants as C
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, build_mesh
from ..runtime.engine_stages import wire_serve_stage_plane
from ..runtime.stages import Channel, Stage, injected_delay
from ..telemetry import tracing
from ..telemetry.device_queue import DeviceQueueBook, Phase
from ..utils.logging import logger
from .kv_cache import (KVCacheSpec, PagedKVCacheSpec, cache_shardings,
                       init_cache, init_paged_cache,
                       paged_cache_shardings, shard_cache,
                       validate_cache_mesh, validate_paged_cache_mesh)
from .scheduler import PagePool, PrefixCache, Request, SlotScheduler
from .speculative import select_next_token, speculative_accept


#: in a tick's host ``tokens`` operand: this slot's input is the output of
#: the tick in flight and lies on the device (``serve_feed_tokens``)
_FROM_DEVICE = -1
#: this slot's input is the first token of the prefill just sent, which
#: the host has not seen yet (``_send_behind_prefill``)
_FROM_PREFILL = -2


#: below the half of ``prefill_len`` the prefill ladder builds ONE further
#: rung whatever its length (the quarter: the ladder's third rung), and
#: past it only rungs of at least this many tokens.  A rung costs the host
#: a fixed second or two of lowering and loading at set-up whatever its
#: length, and saves device time in proportion to its length: at 1,024
#: tokens 38-53 ms a call on the families read so far (``PERF.md`` section
#: 6, PRs 37 and 53).  PR 37 read a 256 rung under 512 at ~5 ms a call and
#: +2.5 % on one cell and cut it for set-up alone: the first prefill call
#: then waited for the WHOLE ladder, and a short set-up read +9 % of a
#: 10 % bound.  Since PR 53 a call waits for the rung it runs, and since
#: PR 61 a rung under this floor is built last and never waited for
#: (``late_rungs``, ``ServeEngine._prefill_operand``), so it costs a
#: caller nothing but the thread's own work: the quarter stands
LADDER_FLOOR = 1024


def prefill_ladder(prefill_len: int) -> tuple:
    """The lengths the prefill program is built at, ascending:
    ``prefill_len``; where that is a whole multiple of 512, its half; and
    below the half each further half that is a whole multiple of 256 and
    is the ladder's third rung or at least ``LADDER_FLOOR`` tokens long
    (docs/serving.md "The prefill ladder"): 1,024 -> (256, 512, 1,024),
    2,048 -> (512, 1,024, 2,048), 4,096 -> (1,024, 2,048, 4,096), 512 ->
    (256, 512).  256 tokens is where a bf16 matmul stops being bound by
    reading its weights, so a shorter program would save nothing, and it
    is a multiple of every unit a prefill program has (page, window, scan
    chunk, flash block).  Under 512 the ladder is ``(prefill_len,)``."""
    ladder = [int(prefill_len)]
    while ladder[0] % 512 == 0 and (
            len(ladder) <= 2 or ladder[0] // 2 >= LADDER_FLOOR):
        ladder.insert(0, ladder[0] // 2)
    return tuple(ladder)


def late_rungs(ladder: tuple) -> tuple:
    """The rungs of ``ladder`` that are built after every other and that
    no call waits for while a longer one is built: those below the half
    that are shorter than ``LADDER_FLOOR`` (a quarter of 256, 512 or 768
    tokens; none in a ladder whose ``prefill_len`` is 4,096 or more)."""
    return tuple(r for r in ladder[:-2] if r < LADDER_FLOOR)


class _Tick(NamedTuple):
    """A decode tick between its dispatch and its retirement."""
    active_map: Dict[int, Request]  #: slot -> the request whose row it runs
    next_tok: Any                   #: [slots] int32 on the device
    aux: tuple                      #: a ``serving_aux`` model's counters, or ()
    rec: dict                       #: its record in the device's queue book


class _ServeConfigView:
    """The three config blocks serving needs, from a dict / json path /
    full DeepSpeedConfig — without dragging in the training-only batch
    triangle."""

    def __init__(self, src):
        if isinstance(src, DeepSpeedConfig):
            self.serving = src.serving_config
            self.telemetry = src.telemetry_config
            self.stages = src.stages_config
            return
        if isinstance(src, str):
            with open(src) as f:
                src = json.load(f)
        pd = dict(src or {})
        self.serving = DeepSpeedServingConfig(pd)
        self.telemetry = DeepSpeedTelemetryConfig(pd)
        self.stages = DeepSpeedStagesConfig(pd)


def _auto_decode_impl(mcfg) -> str:
    """``serving.decode_impl: auto``: the decode arm that matches the
    model's own attention (the protocol's ``config.attn_impl``)."""
    impl = getattr(mcfg, "attn_impl", None)
    if impl == "flash":
        return "pallas"
    if impl == "dense":
        return "dense"
    raise NotImplementedError(
        f"attn_impl={impl!r} has no serving decode path; serve with "
        "'flash' or 'dense' (sequence-parallel attention shards the time "
        "axis the decode cache does not have)")


def _refuse_unsupported(model, serving) -> None:
    """A model that lacks a serving arm says so in
    ``serving_unsupported``; a configuration that asks for one is
    refused here, not deep in a trace."""
    lacks = set(getattr(model, "serving_unsupported", ()))
    stateful = hasattr(model, "serving_state")
    if stateful:
        lacks |= set(_STATE_UNSUPPORTED)
    quant = serving.quantization
    parks = serving.kv_tier[C.SERVING_KV_TIER_IDLE_PARK_TICKS] > 0
    arms = {    # arm: (the configuration asks for it, as the message says it)
        "slot_cache": (serving.page_len == 0,
                       "serving.page_len: 0 (set page_len > 0)"),
        "speculate_k": (serving.speculate_k > 0, "serving.speculate_k > 0"),
        "quantization": ("int8" in (quant["weights"], quant["kv"]),
                         "serving.quantization int8"),
        "lora": (int(serving.lora["rank"]) > 0, "serving.lora.rank > 0"),
        "prefix_cache": (bool(serving.prefix_cache) and serving.page_len > 0,
                         "serving.prefix_cache: true (set it false)"),
        "prefill_chunk_len": (serving.prefill_chunk_len > 0,
                              "serving.prefill_chunk_len > 0"),
        "kv_tier": (parks, "serving.kv_tier.idle_park_ticks > 0"),
    }
    bad = [how for arm, (asked, how) in arms.items()
           if asked and arm in lacks]
    if bad:
        raise ValueError(
            f"{type(model).__name__} cannot be served with "
            + "; ".join(bad) + ": its serving steps have no such arm"
            + (" (it keeps request state by slot, 'serving_state': a page "
               "of keys without the state at its boundary is no prefix, "
               "and a parked session or a drafted token needs a snapshot "
               "of it; chunks of a prompt need none, and a stateful "
               "model may prefill in chunks if its prefill_paged reads "
               "what the chunk before left in the slot, state= at slot=)"
               if stateful else ""))


#: what the engine cannot do yet for a model that keeps request state by
#: slot (``serving_state``), whatever the model's own steps have: each
#: needs the state saved at a boundary, which nothing does.  Not a chunked
#: prefill: the chunks of ONE request run in order into ONE slot, and the
#: slot's state after a chunk is what the next one needs; a model whose
#: prefill takes no prefix says so itself (``serving_unsupported``)
_STATE_UNSUPPORTED = ("prefix_cache", "kv_tier", "slot_cache",
                      "speculate_k")


def params_at_rest(model, params, shardings):
    """``params`` as an engine holds them on ``shardings`` (a tree like
    ``params``): each leaf the caller's own, placed, or, where the model
    declares a form at rest for it (``serving_layouts(params)``, a tree of
    None or a form with a traceable ``.of(leaf)``: ``models/walked.py``), a
    new leaf in that form.  A model without the method declares nothing.
    Returns (the tree, how many leaves were made anew, their bytes)."""
    declared = getattr(model, "serving_layouts", None)
    if declared is None:
        return jax.tree.map(jax.device_put, params, shardings), 0, 0
    anew = []

    def hold(x, sharding, form):
        if form is None:
            return jax.device_put(x, sharding)
        anew.append(x.nbytes)
        return jax.jit(form.of, out_shardings=sharding)(x)

    held = jax.tree.map(hold, params, shardings, declared(params))
    return held, len(anew), sum(anew)


def _percentile(sorted_vals: List[float], q: float) -> Optional[float]:
    from ..telemetry.cli import _percentile as p
    return p(sorted_vals, q)


class _SetupPhase:
    """An open phase of an engine's set-up (``ServeEngine._setup``)."""

    __slots__ = ("_eng", "_label", "_t0", "_span")

    def __init__(self, eng: "ServeEngine", phase: str, args: dict):
        self._eng = eng
        self._label = ":".join([phase] + [str(v) for v in args.values()])
        self._t0 = time.perf_counter()
        self._span = tracing.span(eng._tracer, "serve/setup_" + phase,
                                  cat="serve", **args)

    def end(self, **found) -> None:
        """``found``: what the phase counted, onto its span."""
        self._span.end(**found)
        eng, dt = self._eng, time.perf_counter() - self._t0
        eng.setup_log.append((self._label, self._t0, dt))
        if eng.telemetry is not None:
            eng._setup_gauge.set(dt, phase=self._label)

    def __enter__(self) -> "_SetupPhase":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


#: what ``ServeEngine._first_call`` hands out after a program's first call
_NO_PHASE = contextlib.nullcontext()


class ServeEngine:
    """Continuous-batching greedy decode over a decoder-only model.

    **The serving protocol** (what the engine, and the benchmark's probe,
    read of ``model``; ``GPT2Model`` and ``OlmoeModel`` are its two
    implementations; ``NemotronHModel`` is a third, with request state,
    ``MimoV2Model`` a fourth, whose state is a second kind of key
    cache, ``AxK1Model`` a fifth, whose pool is one array of latent
    rows, and ``GlmDsaModel`` a sixth, with an indexer's keys paged
    beside them):

    * ``model.config`` with ``n_layer``, ``n_head``, ``d_head`` (the KV
      pool's shape; ``n_layer`` counts the layers that keep every key,
      and a config with ``n_kv_head`` has that many key heads in the
      pool, under ``n_head`` query heads; ``d_head`` is the keys' width
      at rest, and a config with ``d_head_v`` has values that wide
      beside them; a config that also declares ``values_in_keys`` keeps
      ONE pool whose rows' first ``d_head_v`` lanes are the values:
      no ``"v"`` array exists, and its paged steps are handed None for
      ``v_pool`` and hand None back; a config with ``n_index_layer``
      and ``d_index`` also keeps an indexer key a token on that many
      layers in a SECOND paged array under the same page ids,
      ``cache["index_k"]``: its paged steps take it as ``index_pool=``
      and return it after the pools), ``n_positions`` (the longest
      sequence) and
      ``attn_impl`` (``'flash'`` | ``'dense'``: which decode arm
      ``serving.decode_impl: auto`` takes); ``d_model`` with LoRA;
    * ``model.init(rng)`` -> a params dict whose ``"wte"`` leaf has the
      dtype the KV cache is kept in; ``model.param_partition_specs(
      params)`` -> specs or None (replicated);
    * paged (``serving.page_len > 0``): ``prefill_paged(params, tokens
      [1, Tq], delta_len, prefix_len, page_row, k_pool, v_pool)`` ->
      ``(logits [1, Tq, V], k_pool, v_pool)`` and ``decode_step_paged(
      params, tokens [S], k_pool, v_pool, page_table, lengths, active,
      impl=)`` -> ``(logits [S, V], k_pool, v_pool, new_lengths)``, the
      pools ``[L, pages, H, page_len, Dh]``, donated and returned, every
      other operand traced (``models/gpt2.py`` has the full contracts);
    * slot cache (``page_len: 0``): ``prefill(params, tokens)`` ->
      ``(logits, k, v)`` and ``decode_step(params, tokens, k, v, lengths,
      active, impl=)``;
    * optional arms, each asked for by configuration: ``verify_step`` /
      ``verify_step_paged`` (``speculate_k``), the ``k_scale`` /
      ``v_scale`` operands and an int8 weight tree (``quantization``),
      the ``lora`` / ``adapter_slots`` operands (``lora``).

    * request state by slot (optional): a model with
      ``serving_state(slots)`` -> ``{name: ShapeDtypeStruct}``, each
      leaf ``[..., slots, ...]`` with the slot on axis 1 and of a fixed
      size (``NemotronHModel``: the Mamba-2 recurrent state and conv
      window of each mixer layer; ``MimoV2Model``: each sliding-window
      layer's ring of its last keys and values).  The engine allocates
      it once, beside the pools, under ``cache["state"]``; both paged
      steps take it as
      ``state=`` and return it after the pools (``prefill_paged(...,
      state=, slot=)`` -> ``(logits, k_pool, v_pool, state)``,
      ``decode_step_paged(..., state=)`` -> ``(logits, k_pool, v_pool,
      state, new_lengths)``; a model that also keeps ``index_pool``
      returns the state after it), donated with the rest of the cache.  A
      prefill OVERWRITES the state of the slot it is told (the slot the
      request is admitted to); a decode tick leaves an inactive slot's
      state alone (a free slot's, and one still prefilling in chunks); no
      program clears state.  It is never paged, shared or migrated:
      ``prefix_cache``, ``kv_tier``, the slot cache, ``speculate_k``,
      ``export_pages`` and ``adopt_request`` are refused for such a
      model.  Chunked prefill (``prefill_chunk_len``) is the model's to
      take or refuse: the chunks of a request run in order into its slot
      (``_prefill_chunk_tick``), each handed ``prefix_len`` (the tokens
      the chunks before it covered), ``state=`` and ``slot=``, and a
      model that takes them reads from the slot's state and the
      request's pages what the chunk before left there.

    A model names the arms it lacks in ``serving_unsupported`` (of
    ``'slot_cache'``, ``'speculate_k'``, ``'quantization'``, ``'lora'``,
    ``'prefix_cache'``, ``'prefill_chunk_len'``, ``'kv_tier'``) and the
    engine refuses such a configuration here, at construction, with
    ``ValueError``.
    A model that names counters in ``serving_aux`` (OLMoE:
    ``moe_experts_hit``, ``moe_rows``, ``moe_load_imbalance``) takes
    ``aux=True`` in its paged steps and returns one more output, a dict
    of those scalars for the call; the engine keeps them per call in
    ``aux_log``, the one log of every program the host sent and waited
    for (``_file``).
    """

    def __init__(self, model, config=None, mesh=None, params=None,
                 seed: int = 0, draft_params=None):
        cfg = _ServeConfigView(config)
        # -- telemetry first: construction is spans and gauges too --------
        self.telemetry = None
        #: the engine's own set-up by phase, (phase, started, seconds)
        #: each, from any thread (gauge ``serve_setup_seconds{phase=}``):
        #: ``params``, ``cache``, ``copy_page``, ``draft``, ``feed``, then
        #: for each rung of the prefill ladder ``lower:<rung>`` and
        #: ``compile:<rung>``
        #: (its executable's load or compile) on the ladder's thread,
        #: ``rungs_wait:<rung>`` (a prefill call waiting for that thread
        #: to reach its rung) and ``first_call:<program>[:<rung>]`` (a program's
        #: first call until it returns: trace, compile or load, enqueue)
        self.setup_log: List[tuple] = []
        self._called: set = set()
        mirror = {}
        if cfg.telemetry.enabled:
            import os
            from ..telemetry.hub import TelemetryHub
            out = cfg.telemetry.output_path or os.path.join(
                os.getcwd(), "telemetry")
            self.telemetry = TelemetryHub(
                out, trace=cfg.telemetry.trace,
                compile_events=cfg.telemetry.compile_events,
                memory=cfg.telemetry.memory,
                storm_threshold=cfg.telemetry.recompile_storm_threshold)
            reg = self.telemetry.registry
            self._setup_gauge = reg.gauge(
                "serve_setup_seconds",
                "the engine's own set-up by phase: params, cache, feed, "
                "lower:<rung> and compile:<rung> of the prefill ladder, "
                "rungs_wait:<rung>, first_call:<program>[:<rung>]")
            device_ctr = reg.counter(
                "serve_device_seconds_total",
                "the device's time by program and by rung (prefill) or "
                "arm (decode), from the host's waits: ready - max(sent, "
                "the ready before); waits that returned at once left out")
            dry_ctr = reg.counter(
                "serve_queue_dry_seconds_total",
                "seconds the device had nothing queued (a lower bound "
                "on its idleness), by the engine span open at the middle "
                "of the interval, outside_step for the caller's time")
            # the counters alone are captured, not the engine
            mirror = {
                "on_device": lambda prog, bucket, s: device_ctr.inc(
                    s, program=prog, bucket=bucket),
                "on_dry": lambda phase, s: dry_ctr.inc(s, phase=phase)}
        #: every program the host sends and waits for, in device order
        #: (telemetry/device_queue.py): ``device_seconds`` and
        #: ``queue_dry_seconds`` below are its sums, ``aux_log`` its
        #: records
        # the clock is looked up at each read, so that a test can hand this
        # module a clock of its own
        self.book = DeviceQueueBook(clock=lambda: time.perf_counter(),
                                    **mirror)
        #: (program, rung or arm) -> seconds of the device
        #: (``serve_device_seconds_total{program=,bucket=}``)
        self.device_seconds = self.book.device_seconds
        #: phase -> seconds the device's queue was dry
        #: (``serve_queue_dry_seconds_total{phase=}``)
        self.queue_dry_seconds = self.book.dry_seconds
        try:
            self._construct(model, cfg, mesh, params, seed, draft_params)
        except BaseException:
            # a refused configuration leaves no hub open behind it
            if self.telemetry is not None:
                self.telemetry.close()
            raise

    def _construct(self, model, cfg, mesh, params, seed, draft_params):
        self.model = model
        self.serving_config = cfg.serving
        mcfg = model.config
        if mesh is None:
            # serving default: one replica on one device; pass a
            # (data, model) mesh for DP/TP serving
            mesh = build_mesh(pp=1, dp=1, tp=1,
                              devices=jax.devices()[:1])
        self.mesh = mesh

        self.max_seq_len = (cfg.serving.max_seq_len
                            or int(mcfg.n_positions))
        self.prefill_len = cfg.serving.prefill_len or self.max_seq_len
        if self.max_seq_len > mcfg.n_positions:
            raise ValueError(
                f"serving.max_seq_len={self.max_seq_len} exceeds the "
                f"model's n_positions={mcfg.n_positions}")
        if self.prefill_len > self.max_seq_len:
            raise ValueError(
                f"serving.prefill_len={self.prefill_len} exceeds "
                f"max_seq_len={self.max_seq_len}")
        self.slots = cfg.serving.slots
        self.eos_id_default = (None if cfg.serving.eos_id < 0
                               else cfg.serving.eos_id)
        _refuse_unsupported(model, cfg.serving)
        if cfg.serving.decode_impl == "auto":
            self.decode_impl = _auto_decode_impl(mcfg)
        else:
            self.decode_impl = cfg.serving.decode_impl
        #: draft-verify speculation (0 = off — the parity reference arm)
        self.spec_k = cfg.serving.speculate_k
        #: STATIC sampling temperature: it selects the compiled
        #: emission/acceptance arm for the engine's lifetime, so
        #: changing it can never recompile mid-serve
        self.temperature = cfg.serving.temperature
        #: quantized serving plane (docs/serving.md "quantized
        #: serving"): both arms are STATIC — they select compiled
        #: program shapes/dtypes for the engine's lifetime
        self.quant_weights = (
            cfg.serving.quantization["weights"] == "int8")
        self.quant_kv = cfg.serving.quantization["kv"] == "int8"
        self._rng_base = (jax.random.PRNGKey(seed ^ 0x5eed)
                          if self.temperature > 0 else None)
        self._rng_n = 0
        self._spec_proposed_n = 0
        self._spec_accepted_n = 0
        self._spec_passes = 0

        # -- params + cache, sharded over the mesh -----------------------
        phase = self._setup("params")
        if params is None:
            params = model.init(jax.random.PRNGKey(seed))
        pspecs = model.param_partition_specs(params)
        if pspecs is None:
            pspecs = jax.tree.map(lambda _: P(), params)
        if self.quant_weights:
            # one-shot post-load quantization (LLM.int8, PAPERS.md):
            # the fp master tree stays on the host — only int8 weights
            # + fp32 scale rows are placed on the mesh, so params HBM
            # ~ halves vs fp16 (collect_memory_stats / the
            # serve_param_bytes gauge are the measurement plane)
            from .quantize import (quantize_gpt2_params,
                                   quantized_partition_specs)
            params = quantize_gpt2_params(params)
            pspecs = quantized_partition_specs(pspecs)
        self._param_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda s: isinstance(s, P))
        #: a leaf the model declares a form at rest for
        #: (``serving_layouts``) is made in it, once: new bytes beside the
        #: caller's, whose tree is left as it came; the others are the
        #: caller's own buffers, as ever.  How many those are and their
        #: bytes: gauges ``serve_params_relaid_leaves`` /
        #: ``serve_params_relaid_bytes``, args of the
        #: ``serve/setup_params`` span
        self.params, self.params_relaid_leaves, self.params_relaid_bytes \
            = params_at_rest(model, params, self._param_shardings)
        phase.end(relaid_leaves=self.params_relaid_leaves,
                  relaid_bytes=self.params_relaid_bytes)
        if self.params_relaid_leaves:
            logger.info(
                "serve params: %d leaves (%.3f GB) made anew in the form %s "
                "declares for them at rest, beside the caller's",
                self.params_relaid_leaves, self.params_relaid_bytes / 1e9,
                type(model).__name__)
        wte = params["wte"] if isinstance(params, dict) else None
        kv_dtype = wte.dtype if wte is not None else jnp.float32
        self.page_len = cfg.serving.page_len
        self.paged = self.page_len > 0
        #: the one log of the programs the host sent and waited for, of
        #: every model: (host time at retirement, 'prefill' | 'decode' |
        #: 'propose' | 'verify', vals) per call, newest last; bounded.
        #: ``vals`` is the call's record in the device's queue book
        #: (``program``, ``bucket``, ``sent_t``, ``ready_t``, ``at_once``,
        #: ``ahead``, ``run_s``, ``dry_s``, ``dry_phase``:
        #: telemetry/device_queue.py) and, for a ``serving_aux`` model
        #: (class docstring), the call's counters by name beside them
        self._aux_keys = tuple(getattr(model, "serving_aux", ()))
        self._aux = self.paged and bool(self._aux_keys)
        self.aux_log: deque = deque(maxlen=65536)
        #: the lengths the prefill program is built at, ascending
        #: (``prefill_ladder``): a call runs the smallest that holds its
        #: tokens and is built, the longest is ``prefill_len``
        self.prefill_buckets = prefill_ladder(self.prefill_len)
        #: rung -> prefill calls that ran it (counter
        #: ``serve_prefills_total{bucket=}``)
        self.prefill_calls = {r: 0 for r in self.prefill_buckets}
        #: what the rungs cost: a prompt (or delta, or chunk) of n tokens
        #: in a rung of r pays for ``r - n`` more; summed, beside the
        #: tokens that were wanted (counter
        #: ``serve_prefill_pad_tokens_total``)
        self.prefill_pad_tokens = 0
        self.prefill_tokens = 0
        #: prefill calls that ran a longer rung because the smallest that
        #: holds their tokens was not built yet (counter
        #: ``serve_prefill_rung_pending_total``): how long a late rung
        #: took to arrive, in calls
        self.prefill_rung_pending = 0
        #: rung -> prefill calls that were one chunk of a longer prompt
        #: (counter ``serve_prefill_chunks_total{bucket=}``)
        self.prefill_chunk_calls = {r: 0 for r in self.prefill_buckets}
        #: rung -> its executable on its way (``_build_prefill_rung``),
        #: in the order the ladder's thread builds them; {} for a ladder
        #: of one rung, which is the jitted program
        self._prefill_build: Dict[int, Future] = {}
        #: request state by slot (class docstring): name -> shape and
        #: dtype, {} for a model that keeps none
        self._state_spec = (dict(model.serving_state(self.slots))
                            if self.paged and hasattr(model, "serving_state")
                            else {})
        #: chunked prefill (Sarathi-Serve, PAPERS.md; docs/serving.md
        #: "disaggregated fleet"): > 0 = prompts with a longer uncached
        #: delta admit immediately and prefill one chunk per step(),
        #: co-scheduled with decode ticks (config requires paged)
        self.prefill_chunk_len = (cfg.serving.prefill_chunk_len
                                  if self.paged else 0)
        #: the longest prompt ``submit`` takes: what one prefill program
        #: holds; or, where every chunk fits one (and no draft mirrors the
        #: whole prompt), whatever leaves room for a token: a prompt longer
        #: than any prefill program is prefilled in chunks
        self.max_prompt_len = (
            self.max_seq_len - 1
            if 0 < self.prefill_chunk_len <= self.prefill_len
            and not self.spec_k else self.prefill_len)
        if self.quant_kv and not self.paged:
            raise ValueError(
                "serving.quantization.kv='int8' requires a paged cache "
                "(serving.page_len > 0); the slot layout keeps the "
                "master dtype")
        #: the body of the fp single-query Pallas arm that the pool's
        #: shape chooses ('direct': the fetched page is the matmul
        #: operand; 'packed'); None where another arm decodes
        self.paged_decode_arm = None
        phase = self._setup("cache")
        if self.paged:
            self.max_pages = -(-self.max_seq_len // self.page_len)
            pages = cfg.serving.pages
            if pages == 0:
                # capacity-neutral auto-size: every slot can still reach
                # max_seq_len, plus the scratch page, rounded up to the
                # data width so the pool DP-shards evenly
                pages = 1 + self.slots * self.max_pages
                dp = mesh.shape.get(DATA_AXIS, 1)
                pages += (-pages) % dp
            self.cache_spec = PagedKVCacheSpec.for_model(
                mcfg, slots=self.slots, pages=pages, page_len=self.page_len,
                max_seq_len=self.max_seq_len, dtype=kv_dtype,
                quant=self.quant_kv)
            kv_heads = self.cache_spec.heads
            v_dim = self.cache_spec.v_head_dim
            one_pool = self.cache_spec.values_in_keys
            index_layers = self.cache_spec.index_layers
            validate_paged_cache_mesh(mesh, self.cache_spec)
            self._cache_shardings = paged_cache_shardings(
                mesh, quant=self.quant_kv, values_in_keys=one_pool,
                indexed=bool(index_layers))
            self.cache = shard_cache(init_paged_cache(self.cache_spec),
                                     mesh, self._cache_shardings)
            if self._state_spec:
                # request state by slot (class docstring): made where it
                # lives, replicated like the lengths
                rep = NamedSharding(mesh, P())
                self._cache_shardings["state"] = {
                    k: rep for k in self._state_spec}
                self.cache["state"] = {
                    k: jax.device_put(jnp.zeros(v.shape, v.dtype), rep)
                    for k, v in self._state_spec.items()}
            self.pool = PagePool(pages)
            self.prefix = (PrefixCache(self.page_len, self.pool)
                           if cfg.serving.prefix_cache else None)
            #: host-owned page tables, one row per slot; dead entries
            #: hold the scratch page (a valid index, masked data)
            self._table = np.zeros((self.slots, self.max_pages),
                                   np.int32)
            #: pages a grid step of the decode kernel attends (the fp
            #: single-query Pallas arm takes a block of several; every
            #: other arm steps page by page)
            self._pages_per_block = 1
            if self.decode_impl == "pallas" and one_pool:
                from ..ops.pallas.decode_attention import (
                    latent_pages_per_block)
                self._pages_per_block = latent_pages_per_block(
                    self.page_len, mcfg.d_head,
                    jnp.dtype(kv_dtype).itemsize, self.max_pages)
            elif (self.decode_impl == "pallas" and not self.quant_kv
                    and not self.spec_k):
                from ..ops.pallas.decode_attention import (
                    paged_decode_arm, paged_pages_per_block)
                shape = (kv_heads, self.page_len, mcfg.d_head,
                         jnp.dtype(kv_dtype).itemsize)
                grouped = ({"q_heads": mcfg.n_head}
                           if kv_heads != mcfg.n_head else {})
                # a model whose pages rest [H, page_len, Dh] at a group
                # of one (``walked.PagePool`` on ungrouped keys)
                if getattr(model, "pool_head_major", False):
                    grouped["head_major"] = True
                self._pages_per_block = paged_pages_per_block(
                    *shape, self.max_pages, v_head_dim=v_dim, **grouped)
                self.paged_decode_arm = paged_decode_arm(*shape, **grouped)
        else:
            self.pool = None
            self.prefix = None
            self.cache_spec = KVCacheSpec(
                layers=mcfg.n_layer, slots=self.slots, heads=mcfg.n_head,
                max_len=self.max_seq_len, head_dim=mcfg.d_head,
                dtype=kv_dtype)
            validate_cache_mesh(mesh, self.cache_spec)
            self._cache_shardings = cache_shardings(mesh)
            self.cache = shard_cache(init_cache(self.cache_spec), mesh,
                                     self._cache_shardings)
        phase.end()

        # -- multi-tenant LoRA adapter plane (serving.lora, docs/
        # serving.md "multi-tenant serving"; S-LoRA / Punica,
        # PAPERS.md): per-tenant low-rank adapters live in a host
        # registry; hbm_adapter_slots+1 device slots (0 = the reserved
        # zero adapter) hold the hot ones, refcounted + LRU-evicted
        # exactly like KV pages; the compiled programs gather each
        # slot's adapter by a TRACED int32 table, so tenant mixes ride
        # the same tick.  rank=0 (default): no pools, no extra
        # operands — every program bitwise-unchanged.
        lcfg = cfg.serving.lora
        self.lora_rank = int(lcfg["rank"])
        self.lora = self.lora_rank > 0
        self.lora_scale = (float(lcfg["alpha"]) / self.lora_rank
                           if self.lora else 1.0)
        self.adapters = None
        self.adapter_bytes = 0
        self._adapter_table = None
        self._adapter_hits_seen = 0
        self._adapter_faults_seen = 0
        if self.lora:
            from .adapters import (AdapterPool, AdapterRegistry,
                                   adapter_param_shapes)
            self.lora_targets = tuple(lcfg["targets"])
            n_aslots = int(lcfg["hbm_adapter_slots"])
            self._lora_shapes = adapter_param_shapes(
                mcfg.n_layer, mcfg.d_model, self.lora_rank,
                self.lora_targets)
            # TP layout mirrors the base matmuls' Megatron split
            # (models/gpt2.py param_partition_specs): column-parallel
            # targets shard B's output features, row-parallel targets
            # shard A's input features; the rank dim is tiny and stays
            # replicated.  Pool axes: A [L, N, d_in, r], B [L, N, r,
            # *out] with N = hbm_adapter_slots + 1.
            mx = MODEL_AXIS
            lora_specs = {
                "qkv_w": (P(), P(None, None, None, None, mx)),
                "out_w": (P(None, None, mx, None), P()),
                "fc_w": (P(), P(None, None, None, mx)),
                "proj_w": (P(None, None, mx, None), P()),
            }
            self._lora_shardings = {
                t: tuple(NamedSharding(mesh, s) for s in lora_specs[t])
                for t in self.lora_targets}
            pools = {}
            for t in self.lora_targets:
                a_shape, b_shape = self._lora_shapes[t]
                pa = jnp.zeros((a_shape[0], n_aslots + 1) + a_shape[1:],
                               kv_dtype)
                pb = jnp.zeros((b_shape[0], n_aslots + 1) + b_shape[1:],
                               kv_dtype)
                sa, sb = self._lora_shardings[t]
                pools[t] = (jax.device_put(pa, sa),
                            jax.device_put(pb, sb))
            self._lora_pools = pools
            self.adapter_bytes = sum(int(a.nbytes) + int(b.nbytes)
                                     for a, b in pools.values())

            # slot-traced donated upload: N uploads, one compiled
            # program (the _copy_fn discipline applied to weights)
            def serve_adapter_upload(pools, slot, new):
                out = {}
                for t in sorted(pools):
                    ap, bp = pools[t]
                    an, bn = new[t]
                    out[t] = (ap.at[:, slot].set(an.astype(ap.dtype)),
                              bp.at[:, slot].set(bn.astype(bp.dtype)))
                return out

            self._adapter_upload_fn = jax.jit(
                serve_adapter_upload, donate_argnums=(0,),
                out_shardings=self._lora_shardings)
            self.adapter_registry = AdapterRegistry(
                int(lcfg["max_adapters"]), self._lora_shapes)
            self.adapter_stage = Stage(
                "adapter_fetch",
                max_failures=cfg.stages.max_stage_failures,
                fallback="synchronous host->HBM adapter copy "
                         "(injection plane bypassed)")
            self.adapters = AdapterPool(
                n_aslots, self.adapter_registry, self._upload_adapter,
                stage=self.adapter_stage)
            #: host-owned per-slot adapter table — one more TRACED
            #: decode/verify operand (dead slots hold 0: the zero
            #: adapter's delta is mathematically zero)
            self._adapter_table = np.zeros((self.slots,), np.int32)

        # -- pallas interpret + ambient mesh scope (the engine idiom) ----
        from ..ops.pallas.runtime import (interpret_scope,
                                          mesh_wants_interpret)
        self._pallas_interpret = mesh_wants_interpret(mesh)

        def _step_scope():
            stack = contextlib.ExitStack()
            stack.enter_context(interpret_scope(self._pallas_interpret))
            stack.enter_context(jax.set_mesh(self.mesh))
            return stack

        self._pallas_scope = _step_scope

        # -- compiled programs -------------------------------------------
        rep = NamedSharding(mesh, P())
        self._copy_fn = None
        self._page_out_fn = None
        self._page_in_fn = None
        self._set_len_fn = None
        # the one shared next-token rule (inference/speculative.py):
        # greedy at temperature 0 — bitwise the argmax these programs
        # used to inline — sampling otherwise.  Programs take a
        # trailing *rng operand only when the static temperature
        # demands one, so the 0-temperature programs are unchanged.
        temp = self.temperature

        if self.paged:
            quant_kv = self.quant_kv

            def cache_scales(cache):
                """The scale-sidecar kwargs of the model's paged entry
                points — empty on the fp pool, so those traces stay
                byte-identical to the pre-quant programs."""
                if not quant_kv:
                    return {}
                return {"k_scale": cache["k_scale"],
                        "v_scale": cache["v_scale"]}

            # multi-tenant lora threads (pools, slot-table) as two
            # extra TRACED operands ahead of the rng tail; lora off
            # leaves both signatures and traces byte-identical
            lora_on = self.lora
            lora_scale = self.lora_scale
            # a model with per-call counters returns them as one more
            # output of both programs; without, nothing changes
            aux_kw = {"aux": True} if self._aux else {}

            aux_keys = self._aux_keys
            stateful = bool(self._state_spec)

            def pack_aux(counters):
                """One float32 vector for the host to fetch, in the
                order the model names them (counts exact to 2**24)."""
                return jnp.stack([counters[k].astype(jnp.float32)
                                  for k in aux_keys])

            indexed = bool(self.cache_spec.index_layers)
            # a model that keeps request state AND an indexer's keys hands
            # the keys back after the pools and the state after the keys
            state_at = 3 + indexed

            def index_kw(cache):
                """The indexer keys' array for a model that keeps one
                (``PagedKVCacheSpec.index_layers``); it comes back after
                the pools."""
                return {"index_pool": cache["index_k"]} if indexed else {}

            def pools(k, v, lengths, out):
                """The cache a paged step hands back (``out``: all it
                returned); a one-pool model
                (``PagedKVCacheSpec.values_in_keys``) is handed None for
                the values and hands None back."""
                newc = {"k": k, "lengths": lengths}
                if not one_pool:
                    newc["v"] = v
                if indexed:
                    newc["index_k"] = out[3]
                return newc

            def split_lora(extra):
                """(lora kwargs, rng tail) of a program's *extra."""
                if not lora_on:
                    return {}, extra
                return ({"lora": extra[0], "adapter_slots": extra[1],
                         "lora_scale": lora_scale}, extra[2:])

            # delta-aware prefill over the page pool: page_row,
            # prefix_len and delta_len are TRACED, so one program
            # serves full prefills AND prefix-hit deltas
            def serve_prefill(params, cache, tokens, delta_len,
                              prefix_len, page_row, slot, *extra):
                lkw, rng = split_lora(extra)
                skw = ({"state": cache["state"], "slot": slot}
                       if stateful else {})
                out = self.model.prefill_paged(
                    params, tokens, delta_len, prefix_len, page_row,
                    cache["k"], cache.get("v"), **lkw, **aux_kw, **skw,
                    **cache_scales(cache), **index_kw(cache))
                logits, kp, vp = out[0], out[1], out[2]
                total = jnp.reshape(prefix_len + delta_len,
                                    (1,)).astype(jnp.int32)
                lengths = jax.lax.dynamic_update_slice(
                    cache["lengths"], total, (slot,))
                last = jax.lax.dynamic_index_in_dim(
                    logits, delta_len - 1, axis=1, keepdims=False)[0]
                first_tok = select_next_token(last, temp,
                                              rng[0] if rng else None)
                newc = pools(kp, vp, lengths, out)
                if quant_kv:
                    newc["k_scale"], newc["v_scale"] = out[3], out[4]
                if stateful:
                    newc["state"] = out[state_at]
                if aux_kw:
                    return newc, first_tok, pack_aux(out[-1])
                return newc, first_tok

            def serve_decode(params, cache, tokens, active, page_table,
                             *extra):
                lkw, rng = split_lora(extra)
                out = self.model.decode_step_paged(
                    params, tokens, cache["k"], cache.get("v"), page_table,
                    cache["lengths"], active, impl=self.decode_impl,
                    **lkw, **aux_kw, **cache_scales(cache),
                    **index_kw(cache),
                    **({"state": cache["state"]} if stateful else {}))
                stats = ()
                if aux_kw:
                    out, stats = out[:-1], (pack_aux(out[-1]),)
                logits, k, v, new_len = out[0], out[1], out[2], out[-1]
                next_tok = select_next_token(logits, temp,
                                             rng[0] if rng else None)
                newc = pools(k, v, new_len, out)
                if quant_kv:
                    newc["k_scale"], newc["v_scale"] = out[3], out[4]
                if stateful:
                    newc["state"] = out[state_at]
                return (newc, next_tok) + stats

            # copy-on-write: duplicate one page (src/dst traced — zero
            # recompiles no matter which pages diverge).  Every pool-
            # shaped leaf is copied — on the quantized cache that
            # includes the scale sidecars, or the COW'd page would
            # dequantize with the wrong scales.
            pool_names = self.cache_spec.pool_names

            def serve_copy_page(cache, src, dst):
                out = dict(cache)
                for key in pool_names:
                    a = cache[key]
                    pg = jax.lax.dynamic_slice_in_dim(a, src, 1, axis=1)
                    out[key] = jax.lax.dynamic_update_slice_in_dim(
                        a, pg, dst, axis=1)
                return out

            self._copy_fn = jax.jit(
                serve_copy_page, donate_argnums=(0,),
                out_shardings=self._cache_shardings)
            if self.prefix is not None:
                # only a prefix hit that ends inside a page copies one, and
                # no warm-up is sure to bring one: compile it where nothing
                # is being timed (the scratch page onto itself)
                with self._setup("copy_page"), self._pallas_scope():
                    self.cache = self._copy_fn(self.cache, np.int32(0),
                                               np.int32(0))

            # KV-page export/import (disaggregated fleet, docs/
            # serving.md): one page's pool rows out to the host / back
            # in, every pool-shaped leaf in _copy_fn's fixed order —
            # on the quantized cache that includes the scale sidecars,
            # or an imported page would dequantize with the wrong
            # scales.  The page index is TRACED like _copy_fn's
            # src/dst, so any page migrates on one compiled pair.
            def serve_page_out(cache, page):
                out = []
                for key in pool_names:
                    out.append(jax.lax.dynamic_slice_in_dim(
                        cache[key], page, 1, axis=1))
                return tuple(out)

            def serve_page_in(cache, page, *leaves):
                out = dict(cache)
                i = 0
                for key in pool_names:
                    out[key] = jax.lax.dynamic_update_slice_in_dim(
                        cache[key], leaves[i], page, axis=1)
                    i += 1
                return out

            # adoption rebuilds a migrated slot's cache length without
            # a prefill pass (slot + length traced)
            def serve_set_len(cache, slot, length):
                out = dict(cache)
                out["lengths"] = jax.lax.dynamic_update_slice(
                    cache["lengths"],
                    jnp.reshape(length, (1,)).astype(jnp.int32),
                    (slot,))
                return out

            # exported page slices are host-bound bytes: replicated
            # output (identity on one device) so every host sees the
            # full page, like the other pinned siblings
            self._page_out_fn = jax.jit(serve_page_out,
                                        out_shardings=rep)
            self._page_in_fn = jax.jit(
                serve_page_in, donate_argnums=(0,),
                out_shardings=self._cache_shardings)
            self._set_len_fn = jax.jit(
                serve_set_len, donate_argnums=(0,),
                out_shardings=self._cache_shardings)
        else:
            def serve_prefill(params, cache, tokens, length, slot, *rng):
                logits, ks, vs = self.model.prefill(params, tokens)
                new_k = ks[:, 0][:, None].astype(cache["k"].dtype)
                new_v = vs[:, 0][:, None].astype(cache["v"].dtype)
                start = (0, slot, 0, 0, 0)
                k_cache = jax.lax.dynamic_update_slice(cache["k"],
                                                       new_k, start)
                v_cache = jax.lax.dynamic_update_slice(cache["v"],
                                                       new_v, start)
                lengths = jax.lax.dynamic_update_slice(
                    cache["lengths"], length[None].astype(jnp.int32),
                    (slot,))
                last = jax.lax.dynamic_index_in_dim(
                    logits, length - 1, axis=1, keepdims=False)[0]
                first_tok = select_next_token(last, temp,
                                              rng[0] if rng else None)
                return ({"k": k_cache, "v": v_cache, "lengths": lengths},
                        first_tok)

            def serve_decode(params, cache, tokens, active, *rng):
                logits, k, v, new_len = self.model.decode_step(
                    params, tokens, cache["k"], cache["v"],
                    cache["lengths"], active, impl=self.decode_impl)
                next_tok = select_next_token(logits, temp,
                                             rng[0] if rng else None)
                return ({"k": k, "v": v, "lengths": new_len}, next_tok)

        outs = (self._cache_shardings, rep) + ((rep,) if self._aux else ())
        self._prefill_fn = jax.jit(
            serve_prefill, donate_argnums=(1,), out_shardings=outs)
        self._decode_fn = jax.jit(
            serve_decode, donate_argnums=(1,), out_shardings=outs)

        # a tick's tokens reach serve_decode as ONE kind of operand, a
        # replicated device array, whether the host built them
        # (device_put) or the tick in flight did (its next_tok, with the
        # slots only the host knows merged in here): a host array and a
        # committed one key two executables of the same program
        def serve_feed_tokens(prev, first, tokens):
            return jnp.where(tokens == _FROM_DEVICE, prev,
                             jnp.where(tokens == _FROM_PREFILL, first,
                                       tokens))

        self._rep = rep
        self._feed_fn = jax.jit(serve_feed_tokens, out_shardings=rep)
        if self.spec_k:
            with self._setup("draft"):
                self._build_spec_plane(cfg, mcfg, kv_dtype, draft_params,
                                       seed, rep)

        # -- fault plane: queue as a Channel, work under one Stage -------
        self.queue = Channel(capacity=cfg.serving.queue_capacity)
        self.scheduler = SlotScheduler(self.slots)
        self.stage = Stage(
            "serve", max_failures=cfg.stages.max_stage_failures,
            fallback="chaos-free direct serving (injection plane "
                     "bypassed)")
        # flight recorder: every stage event samples the request-queue
        # depth (and, paged, the pool's free pages; speculating, the
        # live accept ratio), so a dump shows the backlog + headroom +
        # speculation-health trajectory before a failure
        if self.paged or self.spec_k:
            self.stage.depth_fn = self._stage_depth
        else:
            self.stage.depth_fn = self.queue.qsize
        self.stage.on_degrade = lambda st: self.dump_flight_record(
            reason=f"stage {st.name!r} degraded to {st.fallback}")

        # -- KV tiering (docs/serving.md "KV tiering"): park idle
        # sessions' prefix-cache pages on host/disk and stream them
        # back on resume.  Off by default (idle_park_ticks=0) — the
        # engine is bitwise what it was without it.
        self.kv_tier = None
        kvt = cfg.serving.kv_tier
        if self.paged and self.prefix is not None \
                and kvt[C.SERVING_KV_TIER_IDLE_PARK_TICKS] > 0:
            from ..runtime.disk_offload import disk_fsync_enabled
            from .kv_tier import KVTier
            self.kv_tier = KVTier(
                page_len=self.page_len, pool=self.pool,
                prefix=self.prefix,
                exporter=self._export_page_bytes,
                importer=self._import_page_bytes,
                idle_park_ticks=kvt[C.SERVING_KV_TIER_IDLE_PARK_TICKS],
                host_budget_pages=kvt[
                    C.SERVING_KV_TIER_HOST_BUDGET_PAGES],
                disk_dir=kvt[C.SERVING_KV_TIER_DISK_DIR] or None,
                fsync=disk_fsync_enabled(kvt[C.SERVING_KV_TIER_FSYNC]),
                max_failures=cfg.stages.max_stage_failures)
        wire_serve_stage_plane(self)

        # -- the tick sent ahead (_run_ahead is the rule) ------------------
        #: the decode tick on the device's queue that the host has not
        #: pulled yet, sent ahead of the retirement of the one before it
        self._inflight: Optional[_Tick] = None
        #: the tick sent behind the prefill of the admission that filled
        #: the slots, until ``_decode_tick`` of the same step takes it
        #: for the one in flight (``_send_behind_prefill``)
        self._behind: Optional[_Tick] = None
        #: that prefill's first token on the device while that tick is
        #: being sent; a zero stands in on every other tick
        self._first_dev = self._no_first = None
        #: decode ticks by the order they were sent in, and the rows run
        #: for a request that had ended (serve_ticks_total{arm=},
        #: serve_ahead_wasted_rows_total with telemetry on)
        self.ahead_stats = {"ahead": 0, "behind": 0, "sync": 0,
                            "wasted_rows": 0}
        #: speculation, the KV tier's park_tick and the slot cache keep
        #: the synchronous order (a chunked prefill in flight too: that
        #: one is state, read in _run_ahead)
        self._ahead_ok = (self.paged and not self.spec_k
                          and self.kv_tier is None)
        if self._ahead_ok:
            # warm-ups never fill the slots, so compile the feed where
            # nothing is being timed
            zeros = np.zeros((self.slots,), np.int32)
            self._first_dev = self._no_first = jax.device_put(
                np.int32(0), rep)
            with self._setup("feed"), self._pallas_scope():
                self._feed_fn(jax.device_put(zeros, rep), self._no_first,
                              zeros)

        # -- memory planes (docs/serving.md "quantized serving"): the
        # device bytes the params and KV cache claim, from the param
        # tree + cache spec — the ONE accounting the serve_*_bytes
        # gauges, the summarize "serving memory" row and the bench's
        # fixed-KV-byte budgets read (no more hand-recomputed
        # bytes-per-element claims in bench legs)
        from .quantize import param_nbytes
        self.param_bytes = param_nbytes(self.params)
        self.kv_bytes = self.cache_spec.bytes
        #: bytes of request state by kind, a stateful model's only
        self.state_bytes = {
            k: int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
            for k, v in self._state_spec.items()}
        if self.paged and self.cache_spec.values_in_keys:
            index_bytes = (self.cache_spec.pages
                           * self.cache_spec.index_page_bytes)
            self.state_bytes["latent"] = self.kv_bytes - index_bytes
            if index_bytes:
                self.state_bytes["index_k"] = index_bytes
        elif self.state_bytes:
            self.state_bytes["kv"] = self.kv_bytes
        if self.spec_k:
            self.param_bytes += param_nbytes(self.draft_params)
            self.kv_bytes += self.draft_cache_spec.bytes

        # -- telemetry: the hub was made first, the metrics of what has
        # been built since are made here ----------------------------------
        if self.telemetry is not None:
            for fn in self.programs():
                self.telemetry.track_program(fn.__name__, fn)
            book = self.book
            self.telemetry.watch_stalls(lambda: book.phase)
            reg = self.telemetry.registry
            self._tokens_total = reg.counter(
                "serve_tokens_total", "generated tokens")
            self._requests_total = reg.counter(
                "serve_requests_total", "finished requests")
            self._requests_failed = reg.counter(
                "serve_requests_failed_total",
                "requests finished with an error")
            self._token_seconds = reg.histogram(
                "serve_token_seconds",
                "per-token latency (first token = time to first token)")
            self._ttft_hist = reg.histogram(
                "serve_ttft_seconds",
                "time to first token: submit -> first generated token "
                "(queue wait + prefill)")
            self._queue_wait_hist = reg.histogram(
                "serve_queue_wait_seconds",
                "submit -> slot admission wait (the Orca iteration-"
                "level scheduling number)")
            self._active_gauge = reg.gauge(
                "serve_active_slots", "slots decoding this tick")
            self._ticks_ctr = reg.counter(
                "serve_ticks_total",
                "decode ticks sent, by arm: ahead (before the tick in "
                "flight was retired: every slot was taken), behind (ahead "
                "too, behind the prefill that took the last slot) or sync")
            self._wasted_rows_ctr = reg.counter(
                "serve_ahead_wasted_rows_total",
                "rows a tick sent ahead ran for a request that the tick "
                "before it had ended (an eos, found at retirement)")
            self._param_bytes_gauge = reg.gauge(
                "serve_param_bytes",
                "device bytes of the serving params (target + draft; "
                "int8 weights + scales under quantization)")
            self._param_bytes_gauge.set(self.param_bytes)
            reg.gauge(
                "serve_params_relaid_bytes",
                "device bytes of the param leaves made anew in the form "
                "their model declares for them at rest (serving_layouts): "
                "held beside the caller's own").set(self.params_relaid_bytes)
            reg.gauge(
                "serve_params_relaid_leaves",
                "how many leaves those are").set(self.params_relaid_leaves)
            self._kv_bytes_gauge = reg.gauge(
                "serve_kv_bytes",
                "device bytes of the KV cache from its spec (both "
                "layouts; incl. quant scale sidecars + draft cache)")
            self._kv_bytes_gauge.set(self.kv_bytes)
            self._prefill_pad_ctr = reg.counter(
                "serve_prefill_pad_tokens_total",
                "tokens the prefill calls ran that were padding: the "
                "rung a call took less its prompt (delta, chunk)")
            self._prefills_ctr = reg.counter(
                "serve_prefills_total",
                "prefill calls by the rung of the prefill ladder "
                "(tokens of the program) they ran")
            self._prefill_pending_ctr = reg.counter(
                "serve_prefill_rung_pending_total",
                "prefill calls that ran a longer rung because the "
                "smallest that holds their tokens was not built yet")
            self._prefill_chunks_ctr = reg.counter(
                "serve_prefill_chunks_total",
                "of those, the calls that were one chunk of a prompt "
                "longer than serving.prefill_chunk_len, by rung")
            layers = getattr(self.model, "serving_cache_layers", None)
            if layers is not None:
                layer_gauge = reg.gauge(
                    "serve_cache_layers",
                    "layers by the kind of cache they keep: full (every "
                    "key, in the page pool), window (the last keys, by "
                    "slot), latent (one row a token in the page pool), "
                    "window_latent (the last latent rows, by slot) or "
                    "index (an indexer key a token beside the rows)")
                for kind, n in layers().items():
                    layer_gauge.set(n, kind=kind)
            if self.paged:
                self._pages_total_gauge = reg.gauge(
                    "serve_pages_total",
                    "allocatable KV pages (excludes the scratch page)")
                self._pages_total_gauge.set(self.cache_spec.pages - 1)
                self._free_pages_gauge = reg.gauge(
                    "serve_free_pages", "unallocated KV pages")
                self._free_pages_gauge.set(self.pool.free_count)
                self._prefix_hits = reg.counter(
                    "serve_prefix_hits_total",
                    "admissions that reused cached prefix pages")
                self._prefix_misses = reg.counter(
                    "serve_prefix_misses_total",
                    "admissions that found no cached prefix")
                if self.paged_decode_arm is not None:
                    arm_gauge = reg.gauge(
                        "paged_decode_arm",
                        "which body of ds_paged_decode_attn the pool's "
                        "shape chose, 1 on the engaged arm: direct (a "
                        "page at rest is the matmul operand) or packed")
                    for arm in ("direct", "packed"):
                        arm_gauge.set(int(arm == self.paged_decode_arm),
                                      arm=arm)
            if self.state_bytes:
                state_gauge = reg.gauge(
                    "serve_state_bytes",
                    "device bytes a stateful model's requests hold by "
                    "kind: each serving_state leaf (ssm, conv; window_k, "
                    "window_v; kda, kda_conv; gdn, gdn_conv; window_latent) "
                    "and the page "
                    "pool (kv; latent "
                    "where it is "
                    "one pool of latent rows, index_k the indexer keys "
                    "paged beside them)")
                for kind, nbytes in self.state_bytes.items():
                    state_gauge.set(nbytes, kind=kind)
            if self._aux:
                self._moe_hit_gauge = reg.gauge(
                    "serve_moe_experts_hit",
                    "experts with at least one token in the last decode "
                    "tick, summed over layers (of num_experts x layers)")
                self._moe_imbalance_gauge = reg.gauge(
                    "serve_moe_load_imbalance",
                    "tokens of the busiest expert over the mean tokens "
                    "an expert, largest over layers, last decode tick")
            if self.spec_k:
                self._spec_proposed = reg.counter(
                    "serve_spec_proposed_total",
                    "draft tokens proposed to the verify program")
                self._spec_accepted_ctr = reg.counter(
                    "serve_spec_accepted_total",
                    "accepted draft tokens actually emitted")
                self._spec_len_hist = reg.histogram(
                    "serve_spec_accepted_len",
                    "tokens emitted per verify pass (accepted draft "
                    "prefix + the bonus token)")
            if self.lora:
                self._adapters_resident_gauge = reg.gauge(
                    "serve_adapters_resident",
                    "tenant adapters resident in HBM pool slots "
                    "(pinned + cold-evictable; excludes the reserved "
                    "zero adapter)")
                self._adapter_hits_ctr = reg.counter(
                    "serve_adapter_hits_total",
                    "admissions whose adapter was already HBM-resident")
                self._adapter_faults_ctr = reg.counter(
                    "serve_adapter_faults_total",
                    "cold-adapter admissions that fetched host->HBM "
                    "(the adapter_fetch stage point)")

            if self.kv_tier is not None:
                self._kv_parked_gauge = reg.gauge(
                    "serve_kv_parked_sessions",
                    "idle sessions parked off HBM in the host/disk KV "
                    "tier (parked digest-chain tails)")
                self._kv_spill_ctr = reg.counter(
                    "serve_kv_spill_bytes_total",
                    "KV page bytes exported HBM -> host/disk by the "
                    "kv_spill stage")
                self._kv_fetch_ctr = reg.counter(
                    "serve_kv_fetch_bytes_total",
                    "parked KV page bytes streamed back on session "
                    "resume by the kv_fetch stage")
                self._kv_spill_seen = 0
                self._kv_fetch_seen = 0

            def _stage_counter(name, help, n):
                reg.counter(name, help).inc(n)

            self.stage.counter_fn = _stage_counter
            if self.lora:
                self.adapter_stage.counter_fn = _stage_counter
            if self.kv_tier is not None:
                self.kv_tier.spill_stage.counter_fn = _stage_counter
                self.kv_tier.fetch_stage.counter_fn = _stage_counter

        #: perf_counter epoch for the completion records' ``arrival_s``
        #: stamps — submit times made record-relative, so open-loop
        #: queueing is reconstructible from events.jsonl alone
        self._epoch_t = time.perf_counter()
        self._rid = 0
        self._ticks = 0
        self._closed = False
        #: requests popped from the queue but not yet admitted — the
        #: page-pool backpressure parking spot (head goes first, so
        #: admission order is preserved under exhaustion)
        self._pending: deque = deque()
        self._latencies: deque = deque(maxlen=8192)
        #: decode-phase (post-first-token) latencies only — the TPOT
        #: plane the per-role autoscaler reads off the heartbeat gauge
        self._tpot_lat: deque = deque(maxlen=2048)
        self._flush_every = cfg.serving.flush_interval_ticks
        self._last_flush_t = time.perf_counter()
        self._last_flush_tokens = 0
        self._tokens_seen = 0
        if len(self.prefill_buckets) > 1:
            # beside whatever the caller does between building an engine
            # and its first prompt (probing, warming a tick), one rung
            # after the other on ONE thread, the shortest first and the
            # late ones (the quarter) last: a prefill call waits only
            # where no rung that holds its tokens is built
            # (_prefill_operand), close() for all of them
            pool = ThreadPoolExecutor(
                1, thread_name_prefix="serve_prefill_rungs")
            operands = self._prefill_rung_operands()
            late = late_rungs(self.prefill_buckets)
            self._prefill_build = {
                r: pool.submit(self._build_prefill_rung, r, *operands)
                for r in sorted(self.prefill_buckets,
                                key=lambda r: (r in late, r))}
            pool.shutdown(wait=False)

    # -- speculative decoding: the draft plane --------------------------
    def _build_spec_plane(self, cfg, mcfg, kv_dtype, draft_params,
                          seed: int, rep) -> None:
        """Build the draft model + its slot KV cache + the three
        compiled spec programs (docs/serving.md "speculative
        decoding"): ``draft_prefill`` (mirror the prompt into the
        draft cache at admission), ``draft_propose`` (k+1 chained
        draft decode steps in ONE program — the extra step writes the
        last proposal's K/V so the draft cache stays aligned with the
        target on full acceptance), and ``verify_step`` (the widened
        target pass + acceptance, zero recompiles across any accepted-
        length mix).

        The draft always runs the fixed-stride SLOT cache, paged
        target or not: at draft scale a full stride is a rounding
        error next to the target pool, and it keeps the rollback a
        pure lengths mask."""
        from ..models.gpt2 import GPT2Config, GPT2Model
        from ..config import constants as C
        if not isinstance(self.model, GPT2Model):
            from .quantize import NotGPT2ParamsError
            raise NotGPT2ParamsError(
                "serving.speculate_k: the draft plane pairs a GPT-2 draft "
                "with a GPT2Model target (shared vocabulary and verify "
                f"steps); the target is {type(self.model).__name__}")
        d = cfg.serving.draft
        draft_cfg = GPT2Config(
            vocab_size=mcfg.vocab_size, n_positions=mcfg.n_positions,
            d_model=d[C.SERVING_DRAFT_D_MODEL],
            n_layer=d[C.SERVING_DRAFT_N_LAYER],
            n_head=d[C.SERVING_DRAFT_N_HEAD],
            remat=None,
            attn_impl=d[C.SERVING_DRAFT_ATTN_IMPL] or mcfg.attn_impl)
        self.draft_config = draft_cfg
        self.draft_model = GPT2Model(draft_cfg)
        self._draft_impl = ("dense" if self.decode_impl == "dense"
                            else _auto_decode_impl(draft_cfg))
        if draft_params is None:
            draft_params = self.draft_model.init(
                jax.random.PRNGKey(seed + 1))
        dspecs = self.draft_model.param_partition_specs(draft_params)
        if dspecs is None:
            dspecs = jax.tree.map(lambda _: P(), draft_params)
        if self.quant_weights:
            # the draft rides the weights arm too (ISSUE: a quantized
            # draft is nearly free); its slot KV cache keeps the
            # master dtype — at draft scale the stride is a rounding
            # error and the rollback stays a pure lengths mask
            from .quantize import (quantize_gpt2_params,
                                   quantized_partition_specs)
            draft_params = quantize_gpt2_params(draft_params)
            dspecs = quantized_partition_specs(dspecs)
        dshard = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), dspecs,
            is_leaf=lambda s: isinstance(s, P))
        self.draft_params = jax.tree.map(jax.device_put, draft_params,
                                         dshard)
        dspec = KVCacheSpec(
            layers=draft_cfg.n_layer, slots=self.slots,
            heads=draft_cfg.n_head, max_len=self.max_seq_len,
            head_dim=draft_cfg.d_head, dtype=kv_dtype)
        validate_cache_mesh(self.mesh, dspec)
        self.draft_cache_spec = dspec
        self._draft_shardings = cache_shardings(self.mesh)
        self._draft_cache = shard_cache(init_cache(dspec), self.mesh,
                                        self._draft_shardings)

        temp = self.temperature
        k_spec = self.spec_k
        W = k_spec + 1

        def serve_draft_prefill(dparams, dcache, tokens, length, slot):
            _, ks, vs = self.draft_model.prefill(dparams, tokens)
            new_k = ks[:, 0][:, None].astype(dcache["k"].dtype)
            new_v = vs[:, 0][:, None].astype(dcache["v"].dtype)
            start = (0, slot, 0, 0, 0)
            k_cache = jax.lax.dynamic_update_slice(dcache["k"], new_k,
                                                   start)
            v_cache = jax.lax.dynamic_update_slice(dcache["v"], new_v,
                                                   start)
            lengths = jax.lax.dynamic_update_slice(
                dcache["lengths"], length[None].astype(jnp.int32),
                (slot,))
            return {"k": k_cache, "v": v_cache, "lengths": lengths}

        def serve_draft_propose(dparams, dcache, cur, active, *rng):
            def body(carry, i):
                cache, tok = carry
                logits, kk, vv, nl = self.draft_model.decode_step(
                    dparams, tok, cache["k"], cache["v"],
                    cache["lengths"], active, impl=self._draft_impl)
                lg = logits.astype(jnp.float32)
                if temp > 0:
                    sk = jax.random.fold_in(rng[0], i)
                    nxt = select_next_token(lg, temp, sk)
                    out = (nxt, jax.nn.softmax(lg / temp, axis=-1))
                else:
                    nxt = select_next_token(lg)
                    out = nxt
                return ({"k": kk, "v": vv, "lengths": nl}, nxt), out
            (dcache, _), ys = jax.lax.scan(
                body, (dcache, cur.astype(jnp.int32)),
                jnp.arange(W, dtype=jnp.int32))
            if temp > 0:
                return (dcache, ys[0][:k_spec].T,
                        jnp.transpose(ys[1][:k_spec], (1, 0, 2)))
            return dcache, ys[:k_spec].T

        def verify_core(params, cache, cur, proposals, active,
                        page_table, qprobs, key, lora=None,
                        adapter_slots=None):
            tokens_w = jnp.concatenate(
                [cur[:, None].astype(jnp.int32),
                 proposals.astype(jnp.int32)], axis=1)
            newc = {}
            if self.paged:
                scales = ({"k_scale": cache["k_scale"],
                           "v_scale": cache["v_scale"]}
                          if self.quant_kv else {})
                lkw = ({"lora": lora, "adapter_slots": adapter_slots,
                        "lora_scale": self.lora_scale}
                       if lora is not None else {})
                out = self.model.verify_step_paged(
                    params, tokens_w, cache["k"], cache["v"],
                    page_table, cache["lengths"], active,
                    impl=self.decode_impl, **lkw, **scales)
                logits, kc, vc = out[0], out[1], out[2]
                if self.quant_kv:
                    newc["k_scale"], newc["v_scale"] = out[3], out[4]
            else:
                logits, kc, vc = self.model.verify_step(
                    params, tokens_w, cache["k"], cache["v"],
                    cache["lengths"], active, impl=self.decode_impl)
            out_tok, accepted = speculative_accept(
                logits.astype(jnp.float32), proposals, qprobs, temp,
                key)
            adv = jnp.where(active, accepted + 1, 0).astype(jnp.int32)
            new_len = jnp.minimum(cache["lengths"] + adv,
                                  jnp.int32(self.max_seq_len))
            newc.update({"k": kc, "v": vc, "lengths": new_len})
            return newc, out_tok, accepted

        if self.paged:
            lora_on = self.lora

            def serve_verify(params, cache, cur, proposals, active,
                             page_table, *s):
                lora, aslots = None, None
                if lora_on:
                    lora, aslots = s[0], s[1]
                    s = s[2:]
                return verify_core(params, cache, cur, proposals,
                                   active, page_table,
                                   s[0] if s else None,
                                   s[1] if s else None,
                                   lora=lora, adapter_slots=aslots)
        else:
            def serve_verify(params, cache, cur, proposals, active, *s):
                return verify_core(params, cache, cur, proposals,
                                   active, None, s[0] if s else None,
                                   s[1] if s else None)

        self._draft_prefill_fn = jax.jit(
            serve_draft_prefill, donate_argnums=(1,),
            out_shardings=self._draft_shardings)
        prop_out = ((self._draft_shardings, rep, rep) if temp > 0
                    else (self._draft_shardings, rep))
        self._propose_fn = jax.jit(
            serve_draft_propose, donate_argnums=(1,),
            out_shardings=prop_out)
        self._verify_fn = jax.jit(
            serve_verify, donate_argnums=(1,),
            out_shardings=(self._cache_shardings, rep, rep))

    def _maybe_key(self):
        """One fresh PRNG key per sampling program call — an empty
        tuple at temperature 0, where no program takes one."""
        if self._rng_base is None:
            return ()
        self._rng_n += 1
        return (jax.random.fold_in(self._rng_base, self._rng_n),)

    # -- adapter plane (multi-tenant LoRA) ------------------------------
    def _upload_adapter(self, slot: int, weights) -> None:
        """Host->HBM copy of one adapter into pool slot `slot`.

        Runs through the jitted donated upload program so the pool
        arrays keep their shardings and the copy is a slot-traced
        `at[:, slot].set` — no recompile per (slot, tenant) pair.
        """
        new = {t: (jnp.asarray(weights[t][0]), jnp.asarray(weights[t][1]))
               for t in self.lora_targets}
        self._lora_pools = self._adapter_upload_fn(
            self._lora_pools, np.int32(slot), new)

    def register_adapter(self, adapter_id: int, weights=None):
        """Register a tenant adapter (host-side).  `weights=None`
        synthesizes deterministic factors from the adapter id, so every
        replica in a fleet derives identical weights for the same
        tenant without shipping bytes."""
        if not self.lora:
            raise ValueError("serving.lora.rank is 0 — adapters disabled")
        if weights is None:
            return self.adapter_registry.get(adapter_id)
        return self.adapter_registry.register(adapter_id, weights)

    def hot_adapters(self):
        """Adapter ids currently resident in HBM slots (for heartbeat
        affinity gauges)."""
        return self.adapters.hot_ids() if self.lora else []

    def _spec_ratio(self) -> float:
        """The live draft-acceptance ratio — ONE formula shared by the
        depth dict, the flight-record extras and the flush scalar."""
        return round(
            self._spec_accepted_n / max(self._spec_proposed_n, 1), 4)

    def _stage_depth(self):
        d: Dict[str, Any] = {"depth": self.queue.qsize()}
        if self.paged:
            d["free_pages"] = self.pool.free_count
        if self.spec_k:
            d["spec_accept_ratio"] = self._spec_ratio()
        return d

    # -- telemetry helpers ----------------------------------------------
    def programs(self) -> list:
        """Every jitted program of this engine.  ``fn.__name__`` is the
        program's one stable name: the module on the profiler's ``XLA
        Modules`` line is ``jit_<name>`` and ``recompiles_total``
        carries ``program=<name>``."""
        fns = [self._decode_fn, self._prefill_fn, self._feed_fn]
        if self.paged:
            fns += [self._copy_fn, self._page_out_fn, self._page_in_fn,
                    self._set_len_fn]
        if self.spec_k:
            fns += [self._verify_fn, self._propose_fn,
                    self._draft_prefill_fn]
        if self.lora:
            fns.append(self._adapter_upload_fn)
        return fns

    def _span(self, name: str, **args):
        """A serving span: a profiler annotation always, a trace.json
        event with telemetry on (docs/observability.md), and a phase of
        the host's time in the device's queue book (the name less its
        ``serve/``).  The serving loop's thread only."""
        return Phase(self.book, name[6:],
                     tracing.span(self._tracer, name, cat="serve", **args))

    def _setup(self, phase: str, **args) -> "_SetupPhase":
        """A phase of the engine's own set-up, from any thread: a
        ``serve/setup_<phase>`` span (its args: ``rung=``, ``program=``)
        whose seconds go to ``setup_log`` and the
        ``serve_setup_seconds{phase=}`` gauge.  ``with``, or ``.end()``."""
        return _SetupPhase(self, phase, args)

    def _first_call(self, program: str, bucket: str = ""):
        """Around a program's call: its first (trace, compile or the
        cached executable's load, enqueue) is set-up and stamped as such;
        every later one passes through."""
        key = (program, bucket)
        if key in self._called:
            return _NO_PHASE
        self._called.add(key)
        args = {"bucket": bucket} if bucket else {}
        return self._setup("first_call", program=program, **args)

    def _ready_now(self, arr) -> bool:
        """Whether a wait for ``arr`` would return at once."""
        return arr.is_ready()

    @contextlib.contextmanager
    def _wait(self, rec: dict, out):
        """Around the host's wait (``jax.block_until_ready``) for ``out``,
        the output of the program ``rec`` records: the stamp that the wait
        returned.  The wait itself stays in the caller's frame, which is
        what the benchmark's ``breakdown.idle_gaps`` names a gap by.
        Whatever was sent before ``rec`` and not waited for yet is waited
        for first (``_await_ahead``); a record already stamped keeps its
        stamp."""
        if "ready_t" in rec:
            yield
            return
        self._await_ahead(rec)
        at_once = self._ready_now(out)
        try:
            yield
        finally:
            self.book.ready(rec, at_once)

    def _await_ahead(self, rec: dict) -> None:
        """Wait (``block_until_ready`` only: no pull, no retirement) for
        every program sent before ``rec``'s whose end the host has not
        seen, oldest first: a decode tick in flight, retired where it
        always was.  The device runs them in that order, so the wait for
        ``rec`` implied these."""
        for head, out in self.book.ahead_of(rec):
            with self._wait(head, out):
                jax.block_until_ready(out)

    def _file(self, kind: str, rec: dict, aux=()) -> None:
        """File a retired program's record in ``aux_log`` (one entry a
        call, stamped here, at its retirement), with the counters of a
        ``serving_aux`` model's call, its third output, beside the
        queue's; with telemetry on, the expert layer's two go to their
        gauges."""
        if aux:
            rec.update(zip(self._aux_keys, np.asarray(aux[0]).tolist()))
        self.aux_log.append((time.perf_counter(), kind, rec))
        if self.telemetry is not None and kind == "decode" \
                and "moe_experts_hit" in rec:
            self._moe_hit_gauge.set(rec["moe_experts_hit"])
            self._moe_imbalance_gauge.set(rec["moe_load_imbalance"])

    @property
    def _tracer(self):
        tel = self.telemetry
        return tel.tracer if tel is not None else None

    # -- per-request causal trace + completion record ---------------------
    def _begin_request_trace(self, req: Request) -> None:
        tr = self._tracer
        if tr is None:
            return
        from ..telemetry.tracing import TraceContext
        req.ctx = TraceContext.new()
        # root covers submit -> finish; queue_wait ends at admission.
        # ASYNC (b/e) events, not complete slices: concurrent requests
        # overlap without nesting, which the X per-thread call-stack
        # model mis-renders — async pairs match by (cat, id, name)
        req.span = tr.async_begin("serve/request", req.ctx.trace_id,
                                  cat="serve", rid=req.rid)
        req.queue_span = tr.async_begin("serve/queue_wait",
                                        req.ctx.trace_id, cat="serve",
                                        rid=req.rid)

    def _end_request_trace(self, req: Request, reason=None,
                           error=None) -> None:
        """Close the request's spans and terminate its flow — inside a
        ``serve/finish`` (or ``serve/error``) span so the arrowhead
        binds somewhere visible.  A failing request's trace ends with an
        error span, never a leaked open flow."""
        tr = self._tracer
        args = {}
        if reason is not None:
            args["reason"] = reason
        if error is not None:
            args["error"] = repr(error)
        if req.queue_span is not None:  # never admitted: close it now
            req.queue_span.end(**args)
            req.queue_span = None
        if tr is not None and req.ctx is not None:
            name = "serve/error" if error is not None else "serve/finish"
            with tr.span(name, cat="serve", rid=req.rid, **args):
                if req.admit_t:
                    # the flow starts at admission — a queued request
                    # failed before any flow existed to terminate
                    tr.flow_end("serve/request", req.ctx, cat="serve",
                                rid=req.rid)
            req.ctx = None
        if req.span is not None:
            req.span.end(**args)
            req.span = None

    def _write_request_record(self, req: Request) -> None:
        """One structured completion record per request in events.jsonl
        (``kind: serve_request``) — the offline source for the summarize
        queue/prefill/decode split and the diagnose post-mortem."""
        if self.telemetry is None:
            return
        decode = [float(t) for t in req.token_times[1:]]
        rec = {
            "rid": req.rid,
            "prompt_len": len(req.prompt),
            # submit time relative to the engine's epoch: the open-loop
            # arrival schedule, reconstructible offline (goodput.py);
            # readers tolerate its absence in pre-PR-17 artifacts
            "arrival_s": round(req.submit_t - self._epoch_t, 6),
            "tokens": len(req.tokens),
            "finish_reason": req.finish_reason,
            "error": repr(req.error) if req.error is not None else None,
            "total_s": time.perf_counter() - req.submit_t,
            "queue_wait_s": (req.admit_t - req.submit_t
                             if req.admit_t else None),
            "ttft_s": (float(req.token_times[0])
                       if req.token_times else None),
            # the prefill program's own interval, and the rest of
            # admission -> first token (Request.prefill_wait_s)
            "prefill_s": req.prefill_s if req.prefill_s else None,
            "prefill_wait_s": (req.prefill_wait_s if req.prefill_s
                               else None),
            "decode_tokens": len(decode),
            "decode_s_sum": sum(decode),
            # bounded: a million-token request must not write a
            # million-float record (decode_tokens keeps the true count)
            "token_times_s": [round(t, 6) for t in decode[:512]],
        }
        if req.ctx is not None:
            rec["trace_id"] = req.ctx.trace_id
        self.telemetry.jsonl.write_event("serve_request", rec)

    def dump_flight_record(self, reason: str = "manual",
                           error=None):
        """Serve-side flight recorder: dump the ``serve`` stage's event
        ring (admissions, ticks, queue depths, failures) as
        ``flightrec_<tick>.json``.  Fired on poison and degradation;
        callable on demand.  Never raises."""
        if self.telemetry is None:
            return None
        try:
            # a dump mutates nothing: it says that a tick is in flight
            # and leaves retiring it to the engine
            extra = {"active_slots": len(self.scheduler.active),
                     "queued": self.queue.qsize(),
                     "tick_in_flight": self._inflight is not None}
            if self.paged:
                extra["free_pages"] = self.pool.free_count
                extra["pending"] = len(self._pending)
            if self.spec_k:
                extra["spec_accept_ratio"] = self._spec_ratio()
            return self.telemetry.dump_flight_record(
                {"serve": self.stage}, self._ticks, reason, error=error,
                extra=extra)
        except Exception:
            logger.exception("serve flight-record dump failed "
                             "(reason=%r)", reason)
            return None

    def _count_token(self, latency_s: float):
        self._tokens_seen += 1
        self._latencies.append(latency_s)
        if self.telemetry is not None:
            self._tokens_total.inc()
            self._token_seconds.observe(latency_s)

    def _flush(self):
        """Materialize serving scalars as a telemetry sync event (the
        summarize CLI's 'serving' row reads exactly these)."""
        if self.telemetry is None:
            return
        now = time.perf_counter()
        dt = max(now - self._last_flush_t, 1e-9)
        toks = self._tokens_seen - self._last_flush_tokens
        lat = sorted(self._latencies)
        scalars = {"serve_tokens_per_s": toks / dt,
                   # static for the engine's life, but flushed as
                   # scalars so the offline summarize "serving memory"
                   # row needs only events.jsonl
                   "serve_param_bytes": float(self.param_bytes),
                   "serve_kv_bytes": float(self.kv_bytes)}
        p50 = _percentile(lat, 0.50)
        p99 = _percentile(lat, 0.99)
        if p50 is not None:
            scalars["serve_token_p50_s"] = p50
            scalars["serve_token_p99_s"] = p99
        tpot = self.tpot_p99()
        if tpot is not None:
            scalars["serve_tpot_p99_s"] = tpot
        if self.paged:
            usable = self.cache_spec.pages - 1
            scalars["serve_free_pages"] = float(self.pool.free_count)
            scalars["serve_page_utilization"] = (
                self.pool.used_count / usable if usable else 0.0)
            if self.prefix is not None:
                tot = self.prefix.hits + self.prefix.misses
                if tot:
                    scalars["serve_prefix_hit_ratio"] = \
                        self.prefix.hits / tot
                scalars["serve_prefix_hit_tokens"] = \
                    float(self.prefix.hit_tokens)
                scalars["serve_page_cow_total"] = float(self.prefix.cow)
        if self.spec_k and self._spec_passes:
            # cumulative over the run (like the prefix scalars): the
            # LAST flush is the run's answer — mean accepted length is
            # tokens-per-target-pass, the 1/MAL speedup denominator
            scalars["serve_spec_accept_ratio"] = self._spec_ratio()
            scalars["serve_spec_mean_accepted_len"] = (
                (self._spec_accepted_n + self._spec_passes)
                / self._spec_passes)
        if self.lora:
            pool = self.adapters
            scalars["serve_adapters_resident"] = float(pool.resident())
            scalars["serve_adapter_bytes"] = float(self.adapter_bytes)
            scalars["serve_adapter_hits_total"] = float(pool.hits)
            scalars["serve_adapter_faults_total"] = float(pool.faults)
            scalars["serve_adapter_evictions_total"] = \
                float(pool.evictions)
            self._adapters_resident_gauge.set(pool.resident())
            # counters advance by the pool's deltas since last flush —
            # cumulative scalars above stay the summarize source
            self._adapter_hits_ctr.inc(
                pool.hits - self._adapter_hits_seen)
            self._adapter_faults_ctr.inc(
                pool.faults - self._adapter_faults_seen)
            self._adapter_hits_seen = pool.hits
            self._adapter_faults_seen = pool.faults
        if self.kv_tier is not None:
            tier = self.kv_tier
            scalars["serve_kv_parked_sessions"] = \
                float(tier.parked_sessions)
            scalars["serve_kv_spill_bytes_total"] = \
                float(tier.spill_bytes)
            scalars["serve_kv_fetch_bytes_total"] = \
                float(tier.fetch_bytes)
            p99r = tier.resume_p99_s()
            if p99r is not None:
                scalars["serve_kv_resume_p99_s"] = p99r
            self._kv_parked_gauge.set(tier.parked_sessions)
            # same delta discipline as the adapter pool above: the
            # cumulative scalars stay the summarize source
            self._kv_spill_ctr.inc(
                tier.spill_bytes - self._kv_spill_seen)
            self._kv_fetch_ctr.inc(
                tier.fetch_bytes - self._kv_fetch_seen)
            self._kv_spill_seen = tier.spill_bytes
            self._kv_fetch_seen = tier.fetch_bytes
        self.telemetry.on_sync(step=self._ticks, scalars=scalars)
        self._last_flush_t = now
        self._last_flush_tokens = self._tokens_seen

    def tpot_p99(self) -> Optional[float]:
        """Decode-phase p99 latency per token (TPOT) over the recent
        window — the gauge a decode-role replica beats for the
        per-role autoscaler (docs/serving.md "disaggregated fleet")."""
        if not self._tpot_lat:
            return None
        return _percentile(sorted(self._tpot_lat), 0.99)

    # -- request intake ---------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               detach_kv: bool = False,
               adapter_id: int = 0) -> Request:
        """Enqueue one generation request (blocks on a full queue — the
        open-loop backpressure point).  Greedy decoding; the first
        generated token comes from the prefill logits.

        ``detach_kv`` (paged only) marks a KV-migration source: when
        the request finishes, its pages stay alive for
        :meth:`export_pages` instead of freeing — the disaggregated
        fleet's prefill leg (``release_detached`` frees them after the
        transfer).

        ``adapter_id`` selects the tenant's LoRA adapter (0 = base
        model).  Admission resolves it to an HBM pool slot, parking on
        pool-dry exactly like a pages-dry admission; requires a
        ``serving.lora`` block with ``rank > 0``."""
        if self._closed:
            raise RuntimeError("ServeEngine is closed")
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.max_prompt_len:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the static "
                f"serving.prefill_len bucket ({self.prefill_len}); "
                "raise the bucket or truncate the prompt"
                if self.max_prompt_len == self.prefill_len else
                f"prompt length {len(prompt)} leaves no room for a token "
                f"under serving.max_seq_len ({self.max_seq_len})")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if self.paged:
            need = -(-len(prompt) // self.page_len)
            usable = self.cache_spec.pages - 1
            if need > usable:
                raise ValueError(
                    f"prompt needs {need} KV pages but the pool only "
                    f"has {usable} allocatable pages "
                    f"(serving.pages={self.cache_spec.pages}, page 0 "
                    "reserved); it could never be admitted")
        if detach_kv and not self.paged:
            raise ValueError(
                "detach_kv (KV-migration handoff) requires the paged "
                "layout (serving.page_len > 0)")
        adapter_id = int(adapter_id)
        if adapter_id < 0:
            raise ValueError("adapter_id must be >= 0 (0 = base model)")
        if adapter_id > 0 and not self.lora:
            raise ValueError(
                f"adapter_id={adapter_id} but multi-tenant LoRA is off "
                "(set serving.lora.rank > 0)")
        self._rid += 1
        req = Request(rid=self._rid, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      eos_id=(self.eos_id_default if eos_id is None
                              else int(eos_id)),
                      submit_t=time.perf_counter())
        req.detach_kv = bool(detach_kv)
        req.adapter_id = adapter_id
        self._begin_request_trace(req)
        # Deliberate submission-side backpressure: submit() runs on the
        # CALLER's thread, and a full queue must block the caller (and a
        # closed one must reject) — that is the admission contract, and
        # the result is checked on the next line.
        # jaxlint: disable=JL008
        if not self.queue.put(req):
            err = self.queue.err
            rej = RuntimeError(
                "serve queue rejected the request (engine closed or "
                f"poisoned){': ' + repr(err) if err else ''}")
            req.error = rej
            self._end_request_trace(req, error=rej)
            raise rej
        return req

    def _pop_request(self) -> Optional[Request]:
        with self.queue.cond:
            if self.queue.items:
                item = self.queue.items.pop(0)
                self.queue.cond.notify_all()
                return item
            if self.queue.err is not None:
                raise self.queue.err
            return None

    # -- admission (prefill) ----------------------------------------------
    def _admit_one(self, req: Request) -> bool:
        """Admit one request (prefill + slot assignment).  Returns False
        when the paged pool can't hold it yet (backpressure — the
        request stays parked); True otherwise."""
        if self.paged:
            return self._admit_one_paged(req)
        return self._admit_one_slot(req)

    def _alloc_pages(self, n: int):
        """``n`` fresh pages, evicting least-recently-hit prefix-cache
        leaves under pressure (the eviction-ordered backpressure valve);
        None when the pool is dry even after eviction."""
        pages = self.pool.alloc(n)
        if pages is None and self.prefix is not None:
            if self.prefix.evict(n):
                pages = self.pool.alloc(n)
        return pages

    def _copy_page(self, src: int, dst: int) -> None:
        with self._span("serve/page_cow", src=src, dst=dst):
            with self._pallas_scope():
                self.cache = self._copy_fn(self.cache, np.int32(src),
                                           np.int32(dst))

    def _charge_prefill_delay(self, computed_tokens: int) -> None:
        """Paged arm of the injected-device-time model: the serve
        stage's ``DS_STAGE_DELAY_S`` unit is ONE PAGE of prefill
        compute.  ``stage.check`` already charged one unit at the admit
        boundary; charge the remaining ``ceil(computed/page_len) - 1``
        here, inside the prefill's own interval (``_await_first``) — so
        a prefix-hit delta pays for its delta pages only
        (tests/test_paged_kv.py,
        ``test_prefix_hit_prefill_pays_delta_chunks_only``)."""
        if self.stage.degraded:
            return
        d = injected_delay(self.stage.name)
        if d <= 0:
            return
        chunks = max(1, -(-computed_tokens // self.page_len))
        if chunks > 1:
            time.sleep(d * (chunks - 1))

    def _draft_prefill(self, req: Request,
                       slot: Optional[int] = None) -> None:
        """Mirror the admitted prompt into the DRAFT's slot cache so
        next tick's proposals start from the same history the target
        holds.  The prefill logits are discarded — the tick's first
        pending token is the TARGET's emission.  ``slot`` overrides
        the next-free-slot peek for requests already admitted (chunked
        prefill's final chunk, KV adoption).  Always the whole
        ``prefill_len``: the draft's program is its own and has no
        ladder."""
        dtokens = np.zeros((1, self.prefill_len), np.int32)
        dtokens[0, :len(req.prompt)] = req.prompt
        with self._span("serve/draft_prefill", rid=req.rid):
            with self._pallas_scope():
                self._draft_cache = self._draft_prefill_fn(
                    self.draft_params, self._draft_cache, dtokens,
                    np.int32(len(req.prompt)),
                    np.int32(self.scheduler.free[0]
                             if slot is None else slot))

    def _prefill_operand(self, wanted) -> np.ndarray:
        """A prefill call's ``tokens`` for the ``wanted`` ones (a prompt,
        a delta, a chunk): right-padded to the smallest rung that holds
        them and is built; where none that holds them is built yet, to
        the one of them the ladder's thread reaches first (never a late
        rung), which ``_run_prefill`` then waits for.  The call and its
        padding are counted for the rung it runs."""
        n = len(wanted)
        holds = [r for r in self.prefill_buckets if r >= n]
        build = self._prefill_build
        rung = next((r for r in holds if r in build and build[r].done()),
                    None)
        if rung is None:
            rung = next((r for r in build if r in holds), holds[0])
        if rung != holds[0]:
            self.prefill_rung_pending += 1
            if self.telemetry is not None:
                self._prefill_pending_ctr.inc()
        tokens = np.zeros((1, rung), np.int32)
        tokens[0, :n] = wanted
        self.prefill_calls[rung] += 1
        self.prefill_tokens += n
        self.prefill_pad_tokens += rung - n
        if self.telemetry is not None:
            self._prefill_pad_ctr.inc(rung - n)
            self._prefills_ctr.inc(bucket=str(rung))
        return tokens

    def _prefill_rung_operands(self) -> tuple:
        """(params and cache, the operands after ``tokens``) of
        ``serve_prefill`` as shapes: what the admission arms hand it
        (``_admit_one_paged`` and ``_prefill_chunk_tick``, or
        ``_admit_one_slot``)."""
        def like(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=x.sharding)

        i32 = jax.ShapeDtypeStruct((), np.int32)
        rest = [i32]                            # delta_len | length
        if self.paged:                          # prefix_len, page_row
            rest += [i32, jax.ShapeDtypeStruct((self.max_pages,), np.int32)]
        rest.append(i32)                        # slot
        if self.paged and self.lora:
            rest += [jax.tree.map(like, self._lora_pools), i32]
        if self._rng_base is not None:
            rest.append(jax.ShapeDtypeStruct(self._rng_base.shape,
                                             self._rng_base.dtype))
        return jax.tree.map(like, (self.params, self.cache)), rest

    def _build_prefill_rung(self, rung: int, held, rest):
        """``serve_prefill`` compiled at ``rung`` tokens, ahead of time.
        Every rung is an executable of the one jitted function, which
        keeps the name and an empty cache of its own.  Runs on the
        ladder's thread from construction on, so that a harness which
        warms an engine on a prompt or two finds the rungs ready, and
        pays for them beside its own set-up, not after it."""
        with self._pallas_scope():
            with self._setup("lower", rung=rung):
                lowered = self._prefill_fn.lower(
                    *held, jax.ShapeDtypeStruct((1, rung), np.int32), *rest)
            with self._setup("compile", rung=rung):
                return lowered.compile()

    def _run_prefill(self, *operands):
        """``serve_prefill`` at the length of its tokens (operand 2): the
        jitted program for a ladder of one rung, as ever; else that rung's
        executable, waited for if the ladder's thread has not reached it
        yet (``_prefill_operand`` chose it: no built rung holds the
        tokens; a rung still on its way does not hold up a call of
        another), so that no length compiles anything in the call.  Takes
        the cache it is handed for the one it returns; gives (first token
        on the device, the call's counters, its record in the device's
        queue book)."""
        rung = operands[2].shape[1]
        fn = self._prefill_fn
        if len(self.prefill_buckets) > 1:
            built = self._prefill_build[rung]
            if not built.done():
                with self._setup("rungs_wait", rung=rung):
                    wait([built])
            fn = built.result()
        with self._first_call("serve_prefill", str(rung)):
            self.cache, first, *aux = fn(*operands)
        return first, aux, self.book.sent("serve_prefill", str(rung), first)

    def _await_first(self, req: Request, rec: dict, first,
                     delay_tokens: int = 0) -> int:
        """A prefill's first token, the wait for it cut in two:
        ``serve/prefill_wait`` for what was on the device's queue ahead of
        the prefill (the decode tick in flight, on a full engine) and
        ``serve/prefill_run`` for the prefill itself, whose interval
        (``run_s`` of its record: from the later of its send and the end
        of that first wait) is added to ``req.prefill_s``.  The injected
        device time of ``delay_tokens`` (``_charge_prefill_delay``) is the
        prefill's own and falls in the second."""
        with self._span("serve/prefill_wait"):
            self._await_ahead(rec)
        with self._span("serve/prefill_run"):
            if delay_tokens:
                self._charge_prefill_delay(delay_tokens)
            with self._wait(rec, first):
                jax.block_until_ready(first)
            first = int(np.asarray(first))
        req.prefill_s += rec["run_s"]
        return first

    def _admit_one_paged(self, req: Request) -> bool:
        total_pages = -(-len(req.prompt) // self.page_len)
        # tenant namespace: adapter A's KV pages must never be matched
        # by adapter B (or the base model) — the LoRA delta makes the
        # caches semantically different even for identical prompts.
        # "" keeps the no-lora digest chain bitwise unchanged.
        ns = f"adapter:{req.adapter_id}" if req.adapter_id else ""
        if self.prefix is not None:
            shared_len, spages, cow = self.prefix.match(req.prompt, ns)
        else:
            shared_len, spages, cow = 0, [], False
        tpages: List[int] = []
        if self.kv_tier is not None and not cow \
                and shared_len % self.page_len == 0 \
                and self.pool.free_count >= total_pages - len(spages):
            # session resume (docs/serving.md "KV tiering"): continue
            # the digest chain into the parked tier — fetched pages
            # extend the prefix-cache match and insert() below
            # re-registers them, so a resume IS a prefix hit.  Gated
            # on enough free pages for the whole admission so consumed
            # one-shot records are not spent on a request that then
            # parks; tier failures never raise out of resume — they
            # fall back to the recompute (delta prefill) path below.
            shared_len, tpages = self.kv_tier.resume(
                req.prompt, ns, shared_len, self._alloc_pages)
        need = total_pages - len(spages) - len(tpages) \
            + (1 if cow else 0)
        fresh = self._alloc_pages(need)
        if fresh is None:
            if self.prefix is not None:
                self.prefix.release(spages)
            for p in tpages:
                self.pool.deref(p)
            return False
        aslot = 0
        if self.lora and req.adapter_id:
            # resolve tenant -> HBM adapter slot AFTER the page alloc so
            # a pages-dry park never holds an adapter pin; pool-dry
            # parks the request exactly like a pages-dry admission
            try:
                got = self.adapters.acquire(req.adapter_id)
            except BaseException:
                for p in list(spages) + tpages + fresh:
                    self.pool.deref(p)
                raise
            if got is None:
                for p in list(spages) + tpages + fresh:
                    self.pool.deref(p)
                return False
            aslot = got
        held = list(spages) + tpages + fresh
        seated = None
        try:
            # queue wait ends HERE, before any device work: the COW
            # copy below (and its first-use compile) is compute and
            # must land in the prefill attribution, not as a spurious
            # queue-wait spike in the PR 9 latency split
            req.admit_t = time.perf_counter()
            if req.queue_span is not None:
                req.queue_span.end()
                req.queue_span = None
            if self.telemetry is not None:
                self._queue_wait_hist.observe(req.admit_t - req.submit_t)
            fi = 0
            if cow:
                # divergent append into a shared partial page: copy it
                # into a fresh page BEFORE the delta prefill writes its
                # remaining rows (the COW of docs/serving.md)
                self._copy_page(spages[-1], fresh[0])
                self.pool.deref(spages[-1])
                held.remove(spages[-1])
                row = spages[:-1] + fresh[:1]
                fi = 1
            else:
                row = list(spages) + tpages
            row.extend(fresh[fi:])
            delta = req.prompt[shared_len:]
            if self.prefill_chunk_len \
                    and len(delta) > self.prefill_chunk_len:
                # chunked prefill (Sarathi-Serve, PAPERS.md): admit
                # the slot NOW with zero device work — step() feeds
                # the delta one chunk per tick under the
                # prefill_chunk stage point, so in-flight decodes
                # never stall behind this prompt.  prefix.insert is
                # deferred to the final chunk: a mid-prefill page
                # must never be matched by a concurrent sharer.
                now = time.perf_counter()
                slot = self.scheduler.admit(req, now=now)
                if self.prefix is not None:
                    self.prefix.note_admission(shared_len)
                    if cow:
                        self.prefix.cow += 1
                    if self.telemetry is not None:
                        (self._prefix_hits if shared_len
                         else self._prefix_misses).inc()
                req.pages = row
                req.shared_len = shared_len
                req.computed_len = len(delta)
                req.kv_len = shared_len
                req.prefilling = True
                req.chunk_pos = 0
                self._table[slot, :] = 0
                self._table[slot, :len(row)] = row
                if self.lora:
                    req.adapter_slot = aslot
                    self._adapter_table[slot] = aslot
                return True
            tokens = self._prefill_operand(delta)
            row_np = np.zeros((self.max_pages,), np.int32)
            row_np[:len(row)] = row
            with self._span("serve/prefill", rid=req.rid,
                            prompt_len=len(req.prompt)):
                tr = self._tracer
                if tr is not None and req.ctx is not None:
                    tr.flow_start("serve/request", req.ctx, cat="serve",
                                  rid=req.rid)
                with self._pallas_scope():
                    first, aux, rec = self._run_prefill(
                        self.params, self.cache, tokens,
                        np.int32(len(delta)), np.int32(shared_len),
                        row_np, np.int32(self.scheduler.free[0]),
                        *((self._lora_pools, np.int32(aslot))
                          if self.lora else ()),
                        *self._maybe_key())
                # where this admission fills the slots the next tick
                # goes behind the prefill now, before the host waits
                seated = self._send_behind_prefill(req, row, aslot, first)
                # behind the tick in flight, if one is: first the wait
                # for that tick, then the prefill's own
                first = self._await_first(req, rec, first, len(delta))
                self._file("prefill", rec, aux)
            if self.spec_k:
                # the draft mirrors the FULL prompt (it has no prefix
                # cache — draft prefill is cheap by construction)
                self._draft_prefill(req)
        except BaseException:
            if seated is not None:
                # the tick sent behind runs its row for nobody
                held += req.pages[len(row):]
                self.scheduler.release(seated, "error")
                self._table[seated, :] = 0
                if self.lora:
                    self._adapter_table[seated] = 0
            # roll back every page this admission still holds a ref on
            for p in held:
                self.pool.deref(p)
            if aslot:
                self.adapters.release(req.adapter_id)
            raise
        now = time.perf_counter()
        req.prefill_wait_s = now - req.admit_t - req.prefill_s
        if seated is None:
            slot = self._seat(req, row, aslot, now)
        else:
            slot, req.last_t = seated, now
        if self.prefix is not None:
            # stats count SUCCESSFUL admissions only — neither a
            # parked request re-matching every tick nor a failed
            # prefill may inflate the hit ratio; the COW count's one
            # source of truth is prefix.cow (the flush scalar)
            self.prefix.note_admission(shared_len)
            if cow:
                self.prefix.cow += 1
            if self.telemetry is not None:
                (self._prefix_hits if shared_len
                 else self._prefix_misses).inc()
        req.shared_len = shared_len
        req.computed_len = len(delta)
        if self.prefix is not None:
            # register the freshly computed pages for future sharers
            # (full pages of prompt[:-1] + the partial tail)
            self.prefix.insert(req.prompt, row, ns)
        req.tokens.append(first)
        req.token_times.append(now - req.submit_t)
        req.last_token = first
        self._count_token(now - req.submit_t)
        if self.telemetry is not None:
            self._ttft_hist.observe(now - req.submit_t)
        reason = self.scheduler.finish_reason(req, first,
                                              self.max_seq_len)
        if reason is not None:
            self._finish(slot, reason)
        return True

    def _seat(self, req: Request, row: List[int], aslot: int,
              now: float) -> int:
        """The host's record of a prefilled request in its slot, as far
        as a decode tick reads it.  Returns the slot."""
        slot = self.scheduler.admit(req, now=now)
        req.pages = row
        req.kv_len = len(req.prompt)
        self._table[slot, :] = 0
        self._table[slot, :len(row)] = row
        if self.lora:
            req.adapter_slot = aslot
            self._adapter_table[slot] = aslot
        return slot

    def _send_behind_prefill(self, req: Request, row: List[int],
                             aslot: int, first) -> Optional[int]:
        """Between a prefill's dispatch and the wait for its token: if
        it fills the last free slot of an engine with a tick in flight,
        seat the request and send the next tick behind the prefill,
        its first input the prefill's output where it lies
        (``_FROM_PREFILL``), so the device goes from one to the other
        while the host waits and books.  Returns the slot, or None
        where the old order holds: a free slot left, no tick in flight
        or one sent behind a prefill already in this step (whose request
        ended at its first token), a request that its first token may
        end by count, or a pool so low that the tick could find it dry.
        An ``eos`` as first token is found after the wait, one wasted
        row late, as a tick sent ahead finds one."""
        if not (self._inflight is not None and self._behind is None
                and self._run_ahead(seats=1)
                and req.max_new_tokens > 1
                and len(req.prompt) + 1 < self.max_seq_len
                and self.pool.free_count >= self.slots):
            return None
        slot = self._seat(req, list(row), aslot, time.perf_counter())
        req.last_token = _FROM_PREFILL
        self._first_dev = first
        try:
            self._behind = self._send_tick(*self._decode_prepare(),
                                           arm="behind")
        finally:
            self._first_dev = self._no_first
        return slot

    def _admit_one_slot(self, req: Request) -> bool:
        tokens = self._prefill_operand(req.prompt)
        length = np.int32(len(req.prompt))
        req.admit_t = time.perf_counter()
        if req.queue_span is not None:
            # the queue_wait child span ends the moment a slot is ours
            req.queue_span.end()
            req.queue_span = None
        if self.telemetry is not None:
            self._queue_wait_hist.observe(req.admit_t - req.submit_t)
        with self._span("serve/prefill", rid=req.rid,
                        prompt_len=len(req.prompt)):
            tr = self._tracer
            if tr is not None and req.ctx is not None:
                # flow tail binds to this prefill span; each decode tick
                # the request rides emits a flow step
                tr.flow_start("serve/request", req.ctx, cat="serve",
                              rid=req.rid)
            with self._pallas_scope():
                first, aux, rec = self._run_prefill(
                    self.params, self.cache, tokens, length,
                    np.int32(self.scheduler.free[0]),
                    *self._maybe_key())
            first = self._await_first(req, rec, first)
            self._file("prefill", rec, aux)
        if self.spec_k:
            self._draft_prefill(req)
        now = time.perf_counter()
        req.prefill_wait_s = now - req.admit_t - req.prefill_s
        slot = self.scheduler.admit(req, now=now)
        req.kv_len = len(req.prompt)
        req.tokens.append(first)
        req.token_times.append(now - req.submit_t)
        req.last_token = first
        self._count_token(now - req.submit_t)
        if self.telemetry is not None:
            # TTFT = queue wait + prefill (the first token comes out of
            # the prefill logits)
            self._ttft_hist.observe(now - req.submit_t)
        reason = self.scheduler.finish_reason(req, first,
                                              self.max_seq_len)
        if reason is not None:
            self._finish(slot, reason)
        return True

    def _admit(self) -> int:
        """``serve/admit``: fill free slots from the queue (pop, prefix
        lookup, page allocation, prefill).  Returns admissions made."""
        with self._span("serve/admit") as sp:
            admitted = 0
            while self.scheduler.has_free():
                if self._pending:
                    req = self._pending[0]
                else:
                    req = self._pop_request()
                    if req is None:
                        break
                    self._pending.append(req)
                try:
                    ok = self.stage.call(
                        "admit", lambda r=req: self._admit_one(r),
                        path=f"rid={req.rid}")
                    if not ok:
                        # page-pool backpressure: the head request stays
                        # parked until eviction/release frees pages —
                        # admission order is preserved, the pool (not the
                        # slot count) is the binding constraint now
                        break
                    self._pending.popleft()
                    admitted += 1
                except BaseException as e:
                    self._pending.popleft()
                    self._fail_request(req, e)
                    if not isinstance(e, Exception):
                        # KeyboardInterrupt / SystemExit are not a
                        # per-request failure: the cache may have been
                        # donated into the interrupted call, so poison and
                        # propagate instead of serving on
                        self._poison(e)
                        raise
                    # one bad request must not take the pool down: record
                    # its error and keep serving (Orca-style isolation) —
                    # unless the cache was donated into the failing call, in
                    # which case the engine is broken and must poison
                    logger.error("serve: admission of rid=%d failed: %r",
                                 req.rid, e)
                    if self._cache_broken():
                        self._poison(e)
                        raise
            sp.note(admitted=admitted)
            return admitted

    def _cache_broken(self) -> bool:
        """True when a failing call consumed a donated KV cache —
        target or draft: either loss means the engine cannot keep
        serving and must poison instead of isolating the request."""
        def dead(cache):
            k = cache.get("k")
            return not isinstance(k, jnp.ndarray) or \
                getattr(k, "is_deleted", lambda: False)()
        if dead(self.cache):
            return True
        return bool(self.spec_k) and dead(self._draft_cache)

    def _release_pages(self, req: Request) -> None:
        if req.pages:
            for p in req.pages:
                self.pool.deref(p)
        req.pages = None

    def _finish(self, slot: int, reason: str) -> None:
        req = self.scheduler.release(slot, reason)
        if self.paged:
            # eviction = page frees + a zeroed table row (scratch): the
            # freed pages are immediately admissible capacity — except
            # a KV-migration source (detach_kv), whose pages stay held
            # for export_pages; release_detached frees them after the
            # transfer
            self._table[slot, :] = 0
            if not req.detach_kv:
                self._release_pages(req)
        if self.lora and req.adapter_id:
            # unpin the tenant's adapter (refcount 0 keeps it RESIDENT
            # and evictable — the next request is a free hit) and point
            # the dead slot at the reserved zero adapter
            self.adapters.release(req.adapter_id)
            self._adapter_table[slot] = 0
            req.adapter_slot = 0
        # record + trace close BEFORE done.set(): a waiter released by
        # result() must find the completed artifacts already written
        self._write_request_record(req)
        self._end_request_trace(req, reason=reason)
        req.done.set()
        if self.telemetry is not None:
            self._requests_total.inc()

    # -- chunked prefill --------------------------------------------------
    def _prefill_chunk_tick(self) -> int:
        """One chunk of the OLDEST mid-prefill slot (Sarathi-Serve's
        co-scheduling policy, FIFO over prefilling slots): the same
        delta-aware compiled prefill program with ``prefix_len``
        advanced to the chunk boundary — the rung that holds a chunk,
        traced prefix/delta lengths and page row, so N chunks cost
        zero recompiles.  Intermediate chunk logits are discarded; the
        FINAL chunk's next-token is the request's first token (TTFT
        stamps here).  Returns tokens produced (0 until the final
        chunk)."""
        req = None
        for r in self.scheduler.active.values():
            if r.prefilling:
                req = r
                break
        if req is None:
            return 0
        slot = req.slot
        delta = req.prompt[req.shared_len:]
        pos = req.chunk_pos
        chunk = delta[pos:pos + self.prefill_chunk_len]
        final = pos + len(chunk) >= len(delta)
        tokens = self._prefill_operand(chunk)
        with self._span("serve/prefill_chunk", rid=req.rid, pos=pos,
                        chunk=len(chunk)):
            tr = self._tracer
            if final and tr is not None and req.ctx is not None:
                tr.flow_start("serve/request", req.ctx, cat="serve",
                              rid=req.rid)
            with self._pallas_scope():
                first, aux, rec = self._run_prefill(
                    self.params, self.cache, tokens,
                    np.int32(len(chunk)),
                    np.int32(req.shared_len + pos),
                    self._table[slot], np.int32(slot),
                    *((self._lora_pools, np.int32(req.adapter_slot))
                      if self.lora else ()),
                    *self._maybe_key())
            first = self._await_first(req, rec, first)
            rec.update(chunk_pos=pos, final_chunk=final)
            self._file("prefill", rec, aux)
        self.prefill_chunk_calls[tokens.shape[1]] += 1
        if self.telemetry is not None:
            self._prefill_chunks_ctr.inc(bucket=str(tokens.shape[1]))
        req.chunk_pos = pos + len(chunk)
        req.kv_len = req.shared_len + req.chunk_pos
        if not final:
            return 0
        now = time.perf_counter()
        req.prefilling = False
        req.prefill_wait_s = now - req.admit_t - req.prefill_s
        req.kv_len = len(req.prompt)
        if self.prefix is not None:
            # the pages are fully written now — register them for
            # future sharers (deferred from admission), under the same
            # tenant namespace the admission matched with
            self.prefix.insert(
                req.prompt, req.pages,
                f"adapter:{req.adapter_id}" if req.adapter_id else "")
        if self.spec_k:
            self._draft_prefill(req, slot=slot)
        req.tokens.append(first)
        req.token_times.append(now - req.submit_t)
        req.last_token = first
        req.last_t = now
        self._count_token(now - req.submit_t)
        if self.telemetry is not None:
            self._ttft_hist.observe(now - req.submit_t)
        reason = self.scheduler.finish_reason(req, first,
                                              self.max_seq_len)
        if reason is not None:
            self._finish(slot, reason)
        return 1

    # -- the decode tick --------------------------------------------------
    # Each phase of a tick is one method that opens its span at its
    # top, so the span and the Python frame coincide: a profiler
    # session shows the same phase under either name.  Counts ride the
    # spans as args at the boundary where they are known; nothing here
    # is per request per tick with telemetry off.
    def _decode_prepare(self, rows: int = 1):
        """``serve/decode_prep``: the slots that decode this tick, their
        page-boundary allocations for ``rows`` more KV rows, and the
        tick's host operands.  Returns (active_map, tokens, active);
        an empty map means nothing to run.

        With a tick in flight this one is sent ahead of its retirement,
        and everything it needs is arithmetic on counts the host holds:
        a request with a row in flight stands one token on, is left out
        if that token ends it by count (``length``, ``kv_capacity``),
        and takes its input from the device (``_FROM_DEVICE``); an
        ``eos`` is found at retirement, one wasted row late."""
        with self._span("serve/decode_prep") as sp:
            flying = (self._inflight.active_map
                      if self._inflight is not None else {})
            # mid-prefill slots ride masked: they have no last token to
            # feed and their KV is a partial prefix (chunked prefill)
            active_map = {s: r for s, r in self.scheduler.active.items()
                          if not r.prefilling}
            tokens = np.zeros((self.slots,), np.int32)
            for slot, req in list(active_map.items()):
                on = int(flying.get(slot) is req)
                if on and (len(req.tokens) + 1 >= req.max_new_tokens
                           or req.kv_len + 1 >= self.max_seq_len):
                    del active_map[slot]
                    continue
                tokens[slot] = _FROM_DEVICE if on else req.last_token
                if not self.paged:
                    continue
                # page-boundary appends allocate BEFORE the tick (a
                # speculative block: all its pages up front); a dry
                # pool (even after prefix-cache eviction) finishes the
                # request with the pool-exhaustion-aware kv_capacity
                # reason instead of letting the program write into the
                # void
                kv_len = req.kv_len + on
                need = (kv_len // self.page_len + 1 if rows == 1
                        else -(-min(kv_len + rows, self.max_seq_len)
                               // self.page_len))
                extra = need - len(req.pages)
                if extra > 0:
                    pg = self._alloc_pages(extra)
                    if pg is None:
                        # with a row in flight its token is booked
                        # first: it sits this tick out, and the next
                        # prepare decides as this one would have, after
                        # the retirement has freed what it frees
                        if not on:
                            self._finish(slot, "kv_capacity")
                        del active_map[slot]
                        tokens[slot] = 0
                        continue
                    for p in pg:
                        self._table[slot, len(req.pages)] = p
                        req.pages.append(p)
            active = np.zeros((self.slots,), bool)
            live_pages = live_blocks = 0
            for slot, req in active_map.items():
                active[slot] = True
                if self.paged:
                    live_pages += len(req.pages)
                    live_blocks += -(-len(req.pages)
                                     // self._pages_per_block)
            notes = {"active": len(active_map)}
            if self.paged:
                # the reach of the kernel's grid: blocks of pages that
                # hold live keys / blocks it steps through, a head group
                grid = self.slots * -(-self.max_pages
                                      // self._pages_per_block)
                notes.update(live_pages=live_pages,
                             page_blocks=f"{live_blocks}/{grid}")
            sp.note(**notes)
            return active_map, tokens, active

    def _flow_step_tick(self, active_map) -> None:
        """Per-tick decode attribution: each active request's flow
        steps through the enclosing span (host appends only; telemetry
        on only — the one per-request-per-tick cost)."""
        tr = self._tracer
        if tr is None:
            return
        for req in active_map.values():
            if req.ctx is not None:
                tr.flow_step("serve/request", req.ctx, cat="serve",
                             rid=req.rid, tick=self._ticks)

    def _decode_dispatch(self, tokens, active):
        """``serve/decode_dispatch``: the decode program's call, until
        it returns (enqueue; the device runs on).  Returns (next_tok,
        the call's counters), both on the device."""
        with self._span("serve/decode_dispatch"):
            with self._pallas_scope():
                if self._inflight is not None:
                    tokens = self._feed_fn(self._inflight.next_tok,
                                           self._first_dev, tokens)
                else:
                    tokens = jax.device_put(tokens, self._rep)
                aux = ()
                with self._first_call("serve_decode"):
                    if self.paged:
                        # the tables are copied: the host writes them
                        # again (a finish, an admission) while this call
                        # may not have read them yet
                        self.cache, next_tok, *aux = self._decode_fn(
                            self.params, self.cache, tokens, active,
                            self._table.copy(),
                            *((self._lora_pools,
                               self._adapter_table.copy())
                              if self.lora else ()),
                            *self._maybe_key())
                    else:
                        self.cache, next_tok = self._decode_fn(
                            self.params, self.cache, tokens, active,
                            *self._maybe_key())
                for a in aux:
                    # on its way while the host waits for the tokens
                    a.copy_to_host_async()
            return next_tok, tuple(aux)

    def _pull_tokens(self, rec: dict, *arrays):
        """``serve/token_pull``: the host waiting for the device, for
        the outputs of the program ``rec`` records.  The per-token
        latency point: the pull IS the device sync (transfer-real,
        JL006-clean)."""
        with self._span("serve/token_pull"):
            with self._wait(rec, arrays[0]):
                ready = [jax.block_until_ready(a) for a in arrays]
            return [np.asarray(a) for a in ready]

    def _emit_tokens(self, active_map, next_host) -> int:
        """``serve/emit``: per-request bookkeeping of one decoded token
        each, finish reasons.  A request that the tick before ended
        after this one was sent (an ``eos``) ran a wasted row: counted,
        its output dropped."""
        with self._span("serve/emit") as sp:
            now = time.perf_counter()
            produced = wasted = 0
            for slot, req in active_map.items():
                if req.finish_reason is not None:
                    wasted += 1
                    continue
                tok = int(next_host[slot])
                req.kv_len += 1
                req.tokens.append(tok)
                req.token_times.append(now - req.last_t)
                self._count_token(now - req.last_t)
                self._tpot_lat.append(now - req.last_t)
                req.last_t = now
                req.last_token = tok
                produced += 1
                reason = self.scheduler.finish_reason(req, tok,
                                                      self.max_seq_len)
                if reason is not None:
                    self._finish(slot, reason)
            if wasted:
                self.ahead_stats["wasted_rows"] += wasted
                if self.telemetry is not None:
                    self._wasted_rows_ctr.inc(wasted)
            sp.note(produced=produced)
            return produced

    def _run_ahead(self, seats: int = 0) -> bool:
        """Whether the next decode tick is sent before the one in flight
        is retired (``seats``: requests about to be seated, counted as
        if they were).  THE rule, in this one place, from state the engine
        holds and no option: only while every slot is taken.  Then no
        arrival could be seated before a finish, so nobody waits longer
        for a first token because a tick was sent early, and throughput
        is what a loaded engine's operator pays for; with a free slot an
        arrival during this tick is admitted before the next, as it
        always was.  Speculation, the KV tier and the slot cache
        (``_ahead_ok``) and a chunked prefill in flight stay
        synchronous."""
        return (self._ahead_ok
                and len(self.scheduler.active) + seats == self.slots
                and not (self.prefill_chunk_len and any(
                    r.prefilling for r in self.scheduler.active.values())))

    def _send_tick(self, active_map, tokens, active,
                   arm: Optional[str] = None) -> Optional[_Tick]:
        """One prepared decode tick onto the device's queue (behind the
        tick in flight, if one is); None where nothing is to run."""
        if not active_map:
            return None
        arm = arm or ("sync" if self._inflight is None else "ahead")
        self.ahead_stats[arm] += 1
        if self.telemetry is not None:
            self._ticks_ctr.inc(arm=arm)
        self._flow_step_tick(active_map)
        next_tok, aux = self._decode_dispatch(tokens, active)
        return _Tick(active_map, next_tok, aux,
                     self.book.sent("serve_decode", arm, next_tok))

    def _settle(self) -> int:
        """Retire the tick in flight, if one is: whatever reads the
        cache or a request's tokens from outside a tick calls this
        first.  Returns tokens produced."""
        tick, self._inflight = self._inflight, None
        if tick is None:
            return 0
        (next_host,) = self._pull_tokens(tick.rec, tick.next_tok)
        self._file("decode", tick.rec, tick.aux)
        return self._emit_tokens(tick.active_map, next_host)

    def _decode_tick(self) -> int:
        """Retire one decode tick: the one sent ahead by the step
        before, or (the synchronous arm) one sent here from the host's
        tokens.  While ``_run_ahead`` holds, the NEXT tick goes onto the
        device's queue before the host turns to this one's tokens."""
        tick = self._inflight
        if tick is None:
            operands = self._decode_prepare()
            if not operands[0]:
                return 0
        with self._span("serve/decode_step", active=len(
                operands[0] if tick is None else tick.active_map)):
            if tick is None:
                tick = self._inflight = self._send_tick(*operands)
            behind, self._behind = self._behind, None
            self._inflight = behind or (
                self._send_tick(*self._decode_prepare())
                if self._run_ahead() else None)
            # the pull stays inside the decode_step span
            (next_host,) = self._pull_tokens(tick.rec, tick.next_tok)
            self._file("decode", tick.rec, tick.aux)
        return self._emit_tokens(tick.active_map, next_host)

    def _draft_propose(self, active_map, tokens, active):
        """``serve/draft_propose``: k+1 chained draft passes in one
        compiled program.  Returns (proposals, the verify program's
        sampling tail)."""
        with self._span("serve/draft_propose", active=len(active_map),
                        k=self.spec_k):
            with self._pallas_scope(), \
                    self._first_call("serve_draft_propose"):
                out = self._propose_fn(self.draft_params,
                                       self._draft_cache, tokens,
                                       active, *self._maybe_key())
            if self.temperature > 0:
                self._draft_cache, proposals, qprobs = out
                extra = (qprobs,) + self._maybe_key()
            else:
                self._draft_cache, proposals = out
                extra = ()
            rec = self.book.sent("serve_draft_propose", "", proposals)
            # drain the draft INSIDE its span so the window times real
            # draft compute (the verify pull syncs the rest)
            with self._wait(rec, proposals):
                jax.block_until_ready(proposals)
            self._file("propose", rec)
            return proposals, extra

    def _verify_dispatch(self, tokens, proposals, active, extra):
        """``serve/verify_dispatch``: the widened verify program's
        call, until it returns."""
        with self._span("serve/verify_dispatch"):
            with self._pallas_scope(), self._first_call("serve_verify"):
                if self.paged:
                    self.cache, out_tok, accepted = self._verify_fn(
                        self.params, self.cache, tokens, proposals,
                        active, self._table,
                        *((self._lora_pools, self._adapter_table)
                          if self.lora else ()),
                        *extra)
                else:
                    self.cache, out_tok, accepted = self._verify_fn(
                        self.params, self.cache, tokens, proposals,
                        active, *extra)
            return out_tok, accepted

    def _emit_spec_blocks(self, active_map, out_host, acc_host) -> int:
        """``serve/emit`` of a speculative tick: each request advances
        by its accepted prefix plus the bonus token; rejected
        speculation is rolled back (pages freed, draft lengths
        masked)."""
        with self._span("serve/emit") as sp:
            now = time.perf_counter()
            produced = 0
            for slot, req in active_map.items():
                m = int(acc_host[slot])
                emit = [int(t) for t in out_host[slot, :m + 1]]
                finished = False
                first_of_block = True
                used = 0
                for tok in emit:
                    # the block lands at one wall moment: the first token
                    # carries the pass latency, the rest arrive "free" —
                    # the burst semantics the latency histograms should see
                    req.kv_len += 1
                    req.tokens.append(tok)
                    lat = (now - req.last_t) if first_of_block else 0.0
                    first_of_block = False
                    req.token_times.append(lat)
                    self._count_token(lat)
                    self._tpot_lat.append(lat)
                    produced += 1
                    used += 1
                    reason = self.scheduler.finish_reason(
                        req, tok, self.max_seq_len)
                    if reason is not None:
                        # EOS (or budget/capacity) INSIDE the accepted
                        # block: the tail of the block is discarded, the
                        # slot frees this tick — _finish releases every
                        # page incl. the speculative pre-allocation
                        self._finish(slot, reason)
                        finished = True
                        break
                # accounting counts tokens the pass actually DELIVERED
                # (used - 1 accepted drafts + the first/bonus token), not
                # what verify hypothetically accepted: an EOS/budget/
                # capacity truncation inside the block must not let the
                # mean-accepted-length scalars drift from
                # serve_tokens_total (they share the 1/MAL denominator)
                req.spec_accepted.append(used - 1)
                self._spec_passes += 1
                self._spec_proposed_n += self.spec_k
                self._spec_accepted_n += used - 1
                if self.telemetry is not None:
                    self._spec_proposed.inc(self.spec_k)
                    self._spec_accepted_ctr.inc(used - 1)
                    self._spec_len_hist.observe(used)
                if finished:
                    continue
                req.last_t = now
                req.last_token = emit[-1]
                if self.paged:
                    # rollback: keep the pages covering the verified rows,
                    # free the ones only rejected speculation touched
                    keep = -(-req.kv_len // self.page_len)
                    while len(req.pages) > keep:
                        pg = req.pages.pop()
                        self._table[slot, len(req.pages)] = 0
                        self.pool.deref(pg)
            # draft rollback: one replicated lengths row masks every live
            # slot's draft KV back to its verified length (rejected draft
            # rows become dead tail the kernels never attend)
            dlen = np.zeros((self.slots,), np.int32)
            for slot, req in self.scheduler.active.items():
                dlen[slot] = req.kv_len
            self._draft_cache = dict(self._draft_cache)
            self._draft_cache["lengths"] = jax.device_put(
                jnp.asarray(dlen), self._draft_shardings["lengths"])
            sp.note(produced=produced)
            return produced

    def _spec_tick(self) -> int:
        """One SPECULATIVE serving tick (serving.speculate_k > 0): the
        draft proposes k tokens per active slot (k+1 chained draft
        passes in one compiled program), the target scores all k+1
        positions per slot in ONE widened verify pass, and each
        request advances by its accepted prefix plus the bonus token —
        1 to k+1 tokens for one target pass.  Accepted-length variance
        across slots is absorbed by the same masked machinery as
        admission/eviction; rejection rollback masks lengths back
        (unpaged) or frees the speculated pages (paged)."""
        active_map, tokens, active = self._decode_prepare(
            rows=self.spec_k + 1)
        if not active_map:
            return 0
        proposals, extra = self._draft_propose(active_map, tokens, active)
        with self._span("serve/verify_step", active=len(active_map),
                        k=self.spec_k):
            self._flow_step_tick(active_map)
            out_tok, accepted = self._verify_dispatch(tokens, proposals,
                                                      active, extra)
            rec = self.book.sent("serve_verify", "", out_tok)
            # the per-block latency point, inside the span
            out_host, acc_host = self._pull_tokens(rec, out_tok, accepted)
            self._file("verify", rec)
        return self._emit_spec_blocks(active_map, out_host, acc_host)

    def step(self) -> int:
        """One serving tick: admit into free slots, then one masked
        decode — or, speculating, one draft-propose + widened-verify
        block — over the whole pool.  Returns tokens produced (of the
        tick retired here; while every slot is taken the next one is
        already on the device's queue: ``_decode_tick``)."""
        if self._closed:
            raise RuntimeError("ServeEngine is closed")
        tick_args = {"pages_free": self.pool.free_count} \
            if self.paged else {}
        with self._span("serve/tick", tick=self._ticks,
                        active=len(self.scheduler.active),
                        queued=self.queue.qsize() + len(self._pending),
                        **tick_args) as sp:
            if self.kv_tier is not None:
                # park BEFORE admission so pages freed by parking are
                # immediately allocatable this very tick
                self.kv_tier.park_tick(self._ticks)
            admitted = self._admit()
            try:
                n = 0
                if self.prefill_chunk_len and any(
                        r.prefilling
                        for r in self.scheduler.active.values()):
                    # chunked-prefill co-scheduling: ONE chunk rides
                    # this tick next to the decode pass, and the stage
                    # point charges one injected delay unit per CHUNK
                    # (docs/stages.md) — the bounded-stall guarantee
                    # the disagg bench proves
                    n += self.stage.call("prefill_chunk",
                                         self._prefill_chunk_tick)
                n += self.stage.call(
                    "step",
                    self._spec_tick if self.spec_k
                    else self._decode_tick)
            except BaseException as e:
                self._poison(e)
                raise
            sp.note(produced=n, admitted=admitted)
        if self.telemetry is not None:
            self._active_gauge.set(len(self.scheduler.active))
            if self.paged:
                self._free_pages_gauge.set(self.pool.free_count)
        self._ticks += 1
        if self._ticks % self._flush_every == 0:
            self._flush()
        return n

    def run_until_idle(self, max_ticks: int = 100_000) -> int:
        """Serve until the queue and every slot are empty.  Returns
        total tokens produced."""
        total = 0
        for _ in range(max_ticks):
            if not self.scheduler.active and not self._pending \
                    and self.queue.qsize() == 0:
                # a tick still in flight holds only rows of requests
                # that have ended
                return total + self._settle()
            total += self.step()
        raise RuntimeError(
            f"serve loop still busy after max_ticks={max_ticks} "
            f"({len(self.scheduler.active)} active, "
            f"{len(self._pending)} pending, "
            f"{self.queue.qsize()} queued)")

    # -- KV-page migration (disaggregated fleet) --------------------------
    def _page_leaves(self) -> List[str]:
        """Pool-shaped cache leaves in the fixed wire order (mirrors
        _copy_fn: scales ride along on the quantized pool)."""
        return list(self.cache_spec.pool_names)

    def page_leaf_nbytes(self) -> List[int]:
        """Per-leaf byte lengths inside ONE exported page payload —
        the binary frame header's validation contract (both ends of a
        migration run the same config, so these must agree)."""
        return [int(self.cache[k].nbytes) // int(self.cache[k].shape[1])
                for k in self._page_leaves()]

    def export_pages(self, req: Request) -> List[bytes]:
        """A finished ``detach_kv`` request's KV pages as raw bytes,
        one payload per page: the page's leaf slices concatenated in
        ``_page_leaves`` order.  Whole pages ship (the bounded page
        copy — a partial tail's dead rows are masked by lengths on the
        importing side); the page index is traced, so N exports ride
        one compiled program.  Call :meth:`release_detached` after the
        payloads hit the wire."""
        if not self.paged or not req.pages:
            raise RuntimeError(
                "export_pages needs a paged engine and a finished "
                "detach_kv request still holding its pages")
        self._refuse_migration("export_pages")
        self._settle()
        out = []
        for pid in req.pages:
            with self._span("serve/page_out", rid=req.rid, page=pid):
                with self._pallas_scope():
                    slices = self._page_out_fn(self.cache,
                                               np.int32(pid))
                slices = jax.block_until_ready(slices)
            out.append(b"".join(np.asarray(s).tobytes()
                                for s in slices))
        return out

    def _refuse_migration(self, what: str) -> None:
        if self._state_spec:
            raise NotImplementedError(
                f"{what}: {type(self.model).__name__} keeps request state "
                f"by slot ({', '.join(sorted(self._state_spec))}) that "
                "pages do not carry; migrating a request needs a snapshot "
                "of it")

    def release_detached(self, req: Request) -> None:
        """Drop the pages a ``detach_kv`` finish kept alive — the
        export's payloads are on the wire, the pages are admissible
        capacity again."""
        self._release_pages(req)

    def _export_page_bytes(self, pid: int) -> bytes:
        """ONE pool page as raw host bytes — the KV tier's spill unit
        (``export_pages``'s packing for a single page; the tier CRC-
        stamps the result before the page's pool ref is released)."""
        with self._span("serve/kv_spill", page=pid):
            with self._pallas_scope():
                slices = self._page_out_fn(self.cache, np.int32(pid))
            slices = jax.block_until_ready(slices)
        return b"".join(np.asarray(s).tobytes() for s in slices)

    def _import_page_bytes(self, pid: int, payload: bytes) -> None:
        """Import one parked page payload into pool page ``pid`` — the
        KV tier's fetch unit (``adopt_request``'s unpacking for a
        single page).  A size mismatch is a corrupt record, typed so
        the tier's recompute fallback catches it."""
        from .kv_tier import KVTierCorruptError
        leaves, off = [], 0
        for ref in [self.cache[k] for k in self._page_leaves()]:
            nb = int(ref.nbytes) // int(ref.shape[1])
            shape = ref.shape[:1] + (1,) + ref.shape[2:]
            leaves.append(np.frombuffer(
                payload, dtype=np.dtype(ref.dtype),
                count=nb // ref.dtype.itemsize,
                offset=off).reshape(shape))
            off += nb
        if off != len(payload):
            raise KVTierCorruptError(
                f"parked page payload is {len(payload)} bytes; this "
                f"pool's page is {off}")
        with self._span("serve/kv_fetch", page=pid):
            with self._pallas_scope():
                self.cache = self._page_in_fn(self.cache,
                                              np.int32(pid), *leaves)

    def adopt_request(self, prompt, first_token: int,
                      max_new_tokens: int,
                      eos_id: Optional[int],
                      page_payloads: List[bytes],
                      adapter_id: int = 0) -> Optional[Request]:
        """Adopt a migrated request mid-decode (docs/serving.md
        "disaggregated fleet"): import its exported KV pages into
        freshly allocated local pages (page ids are replica-local —
        the table is rebuilt), restore the slot's cache length, and
        resume decoding from ``first_token`` on the next tick.
        Identical params + imported KV ⇒ the continued stream is
        bitwise the single-replica stream (the parity bar).  Returns
        None when no slot or pages are free yet — the caller parks and
        retries, the same backpressure contract as admission."""
        if not self.paged:
            raise RuntimeError("KV adoption requires the paged layout")
        self._refuse_migration("adopt_request")
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        need = -(-len(prompt) // self.page_len)
        if need != len(page_payloads):
            raise ValueError(
                f"migrated request ships {len(page_payloads)} pages "
                f"but a {len(prompt)}-token prompt needs {need}")
        if not self.scheduler.has_free():
            return None
        pages = self._alloc_pages(need)
        if pages is None:
            return None
        adapter_id = int(adapter_id)
        if adapter_id > 0 and not self.lora:
            raise ValueError(
                f"migrated request carries adapter_id={adapter_id} but "
                "multi-tenant LoRA is off on this replica")
        aslot = 0
        if self.lora and adapter_id:
            # same ordering as admission: adapter pin AFTER page alloc,
            # pool-dry parks (deterministic synthesis means this
            # replica derives the identical weights locally — no
            # adapter bytes ride the migration payload)
            try:
                got = self.adapters.acquire(adapter_id)
            except BaseException:
                for p in pages:
                    self.pool.deref(p)
                raise
            if got is None:
                for p in pages:
                    self.pool.deref(p)
                return None
            aslot = got
        # there is room: from here on the cache is written
        self._settle()
        self._rid += 1
        now = time.perf_counter()
        req = Request(rid=self._rid, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      eos_id=(self.eos_id_default if eos_id is None
                              else int(eos_id)),
                      submit_t=now)
        req.admit_t = now
        req.adapter_id = adapter_id
        try:
            leaf_refs = [self.cache[k] for k in self._page_leaves()]
            for pid, payload in zip(pages, page_payloads):
                leaves, off = [], 0
                for ref in leaf_refs:
                    nb = int(ref.nbytes) // int(ref.shape[1])
                    shape = ref.shape[:1] + (1,) + ref.shape[2:]
                    leaves.append(np.frombuffer(
                        payload, dtype=np.dtype(ref.dtype),
                        count=nb // ref.dtype.itemsize,
                        offset=off).reshape(shape))
                    off += nb
                if off != len(payload):
                    raise ValueError(
                        f"migrated page payload is {len(payload)} "
                        f"bytes; this pool's page is {off} (config "
                        "mismatch between migration endpoints)")
                with self._span("serve/page_in", rid=req.rid,
                                page=pid):
                    with self._pallas_scope():
                        self.cache = self._page_in_fn(
                            self.cache, np.int32(pid), *leaves)
        except BaseException:
            for p in pages:
                self.pool.deref(p)
            if aslot:
                self.adapters.release(adapter_id)
            raise
        slot = self.scheduler.admit(req, now=now)
        req.pages = list(pages)
        req.shared_len = 0
        req.computed_len = len(prompt)
        req.kv_len = len(prompt)
        self._table[slot, :] = 0
        self._table[slot, :len(pages)] = pages
        if self.lora:
            req.adapter_slot = aslot
            self._adapter_table[slot] = aslot
        with self._pallas_scope():
            self.cache = self._set_len_fn(self.cache, np.int32(slot),
                                          np.int32(len(prompt)))
        if self.spec_k:
            # the draft has no imported pages — mirror the prompt into
            # its slot cache the ordinary way (draft prefill is cheap)
            self._draft_prefill(req, slot=slot)
        # the first token was generated (and latency-counted) on the
        # prefill replica; record it here without double-counting
        req.tokens.append(int(first_token))
        req.token_times.append(0.0)
        req.last_token = int(first_token)
        req.last_t = now
        reason = self.scheduler.finish_reason(req, int(first_token),
                                              self.max_seq_len)
        if reason is not None:
            self._finish(slot, reason)
        return req

    # -- failure + shutdown ----------------------------------------------
    def _fail_request(self, req: Request, err: BaseException) -> None:
        """The one per-request failure path: record + trace close
        BEFORE done.set() (a released waiter must find the artifacts
        written), and keep the failed counter consistent with the
        record-derived summarize count."""
        req.error = err
        self._write_request_record(req)
        self._end_request_trace(req, error=err)
        req.done.set()
        if self.telemetry is not None:
            self._requests_failed.inc()

    def _poison(self, err: BaseException) -> None:
        """A failed decode tick is fatal for every in-flight request:
        donation means the cache is gone.  Typed propagation — requests
        and submitters see the ORIGINAL exception.  Every in-flight
        request's trace ends with an error span (no leaked flows), and
        the flight recorder dumps the pool's last moments."""
        self.queue.poison(err)
        self.stage.record_event("poison", error=repr(err))
        # the tick in flight goes with the cache: its requests fail below
        self._inflight = self._behind = None
        self.book.pending.clear()
        for slot in list(self.scheduler.active):
            req = self.scheduler.release(slot, "error")
            if self.paged:
                self._table[slot, :] = 0
                self._release_pages(req)
            if self.lora and req.adapter_id:
                self.adapters.release(req.adapter_id)
                self._adapter_table[slot] = 0
            self._fail_request(req, err)
        # backpressure-parked requests are in flight too — fail them
        # with the same original exception, never strand their waiters
        while self._pending:
            self._fail_request(self._pending.popleft(), err)
        self.dump_flight_record(reason="serve poison", error=err)

    def _close_queue(self):
        err = RuntimeError("ServeEngine closed")
        # the tokens the device has already made are booked first
        self._settle()
        # mark closed and capture the backlog under ONE lock hold: a
        # submit() racing close() either sees put() return False
        # (raises to its caller) or its item lands in `items` here and
        # fails typed — never silently cleared with a hung waiter
        with self.queue.cond:
            self.queue.closed = True
            items = list(self.queue.items)
            self.queue.items.clear()
            self.queue.cond.notify_all()
        items = list(self._pending) + items
        self._pending.clear()
        for req in items:
            self._fail_request(req, err)
        if self.prefix is not None:
            self.prefix.clear()

    def _drain_kv_spill(self):
        """Write every host-resident parked page to the disk tier
        (when one exists) — the spill plane's drain barrier, so parked
        sessions survive the process."""
        if self.kv_tier is not None:
            self.kv_tier.drain()

    def _close_kv_spill(self):
        if self.kv_tier is not None:
            self.kv_tier.close_spill()

    def _close_kv_fetch(self):
        if self.kv_tier is not None:
            self.kv_tier.close()

    def _close_telemetry(self):
        if self.telemetry is not None:
            self._flush()
            self.telemetry.close()

    def close(self):
        """Idempotent: drain order is queue -> kv spill -> kv fetch ->
        telemetry (docs/serving.md); queued never-admitted requests
        fail with a typed error instead of hanging their waiters."""
        if self._closed:
            return
        self._closed = True
        wait(self._prefill_build.values())
        errors = self._graph.close_all()
        if errors:
            raise errors[0][1]
