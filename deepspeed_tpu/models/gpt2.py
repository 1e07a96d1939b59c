"""GPT-2 model family — the flagship decoder LM, TPU-first.

Fills the role of the Megatron-GPT2 integration models the reference trains
in its perf suite (reference: tests/model/Megatron_GPT2/run_perf_test.py:18-60
pins 1.5B/4B/8B/20B configs; DeepSpeedExamples provides the model).  Design
is idiomatic JAX rather than a torch port:

  - parameters for all layers are STACKED on a leading layer axis and the
    blocks run under ``lax.scan`` — one compiled block regardless of depth
    (fast compile, XLA pipelines the layer loop);
  - tensor parallelism is declared, not coded: ``param_partition_specs``
    marks qkv/mlp weights on the ``model`` mesh axis (Megatron column/row
    split — column-parallel matmuls shard the output feature dim, row-
    parallel shard the input dim so XLA inserts exactly one psum per block,
    the same comm pattern Megatron hand-codes);
  - remat: ``jax.checkpoint`` around each block body when
    ``remat='block'`` (the activation-checkpointing feature slot,
    reference deepspeed/runtime/activation_checkpointing/checkpointing.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import causal_attention
from ..ops.dropout import dropout
from ..parallel.mesh import MODEL_AXIS
from ..runtime.activation_checkpointing.block_remat import checkpoint_block
from ..runtime.module import TrainModule, mark_subtrees
from ..runtime.zero import gather_layer


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    d_model: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    embd_dropout: float = 0.0
    remat: Optional[str] = "block"   # None | 'block'
    attn_impl: str = "flash"         # 'flash' (Pallas) | 'dense' |
                                     # 'ring' | 'ulysses' (seq-parallel)
    scan_layers: bool = True         # False: unroll (≈25% faster on TPU —
                                     # XLA optimizes across layer bounds —
                                     # at the cost of depth-linear compile)
    stream_scan: bool = False        # fetch ONE layer's params per scan
                                     # tick with an explicit memory-space
                                     # transfer — pair with the engine's
                                     # zero_optimization.param_streaming
                                     # (host-resident block params) so
                                     # device param bytes ~ one layer

    @property
    def d_head(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def num_params(self) -> int:
        d, L, V, Tmax = self.d_model, self.n_layer, self.vocab_size, self.n_positions
        per_block = (4 * d  # ln scales/biases
                     + d * 3 * d + 3 * d      # qkv
                     + d * d + d              # attn out
                     + d * 4 * d + 4 * d      # fc
                     + 4 * d * d + d)         # proj
        return V * d + Tmax * d + L * per_block + 2 * d


# canned sizes (GPT-2 paper / Megatron perf ladder)
GPT2_SMALL = GPT2Config(d_model=768, n_layer=12, n_head=12)          # 124M
GPT2_MEDIUM = GPT2Config(d_model=1024, n_layer=24, n_head=16)        # 350M
GPT2_LARGE = GPT2Config(d_model=1280, n_layer=36, n_head=20)         # 774M
GPT2_XL = GPT2Config(d_model=1600, n_layer=48, n_head=25)            # 1.5B


class GPT2Model(TrainModule):
    """Causal LM with tied input/output embeddings and next-token loss."""

    def __init__(self, config: GPT2Config):
        self.config = config

    # ---------------- init ----------------
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.config
        d, L = cfg.d_model, cfg.n_layer
        keys = jax.random.split(rng, 8)
        std = 0.02
        resid_std = std / jnp.sqrt(2.0 * L)

        def norm(key, shape, s=std):
            return (jax.random.normal(key, shape, jnp.float32) * s)

        params = {
            "wte": norm(keys[0], (cfg.vocab_size, d)),
            "wpe": norm(keys[1], (cfg.n_positions, d)),
            "ln_f_scale": jnp.ones((d,), jnp.float32),
            "ln_f_bias": jnp.zeros((d,), jnp.float32),
            "blocks": {
                "ln1_scale": jnp.ones((L, d), jnp.float32),
                "ln1_bias": jnp.zeros((L, d), jnp.float32),
                # [L, d, 3, d] (not [L, d, 3d]): the q/k/v boundary lives
                # on its own unsharded dim so the TP 'model' shard on the
                # feature dim never straddles it — the fused-[3d] layout
                # forced GSPMD halo collective-permutes at every q/k/v
                # split (same values: reshape of the fused layout).
                "qkv_w": norm(keys[2], (L, d, 3, d)),
                "qkv_b": jnp.zeros((L, 3, d), jnp.float32),
                "out_w": norm(keys[3], (L, d, d), resid_std),
                "out_b": jnp.zeros((L, d), jnp.float32),
                "ln2_scale": jnp.ones((L, d), jnp.float32),
                "ln2_bias": jnp.zeros((L, d), jnp.float32),
                "fc_w": norm(keys[4], (L, d, 4 * d)),
                "fc_b": jnp.zeros((L, 4 * d), jnp.float32),
                "proj_w": norm(keys[5], (L, 4 * d, d), resid_std),
                "proj_b": jnp.zeros((L, d), jnp.float32),
            },
        }
        return params

    # ---------------- TP declaration ----------------
    def param_partition_specs(self, params) -> Dict[str, Any]:
        """Megatron column/row parallel layout on the ``model`` axis."""
        m = MODEL_AXIS
        return {
            "wte": P(m, None),          # vocab-sharded embedding
            "wpe": P(),                 # small, replicate
            "ln_f_scale": P(),
            "ln_f_bias": P(),
            "blocks": {
                "ln1_scale": P(), "ln1_bias": P(),
                "qkv_w": P(None, None, None, m),  # column parallel (per-
                "qkv_b": P(None, None, m),        # q/k/v feature shards)
                "out_w": P(None, m, None),   # row parallel
                "out_b": P(),
                "ln2_scale": P(), "ln2_bias": P(),
                "fc_w": P(None, None, m),    # column parallel
                "fc_b": P(None, m),
                "proj_w": P(None, m, None),  # row parallel
                "proj_b": P(),
            },
        }

    # ---------------- forward ----------------
    def _block(self, bp, x, rng, train: bool):
        """One transformer block; bp leaves have the layer axis removed."""
        return gpt2_block_forward(self.config, bp, x, rng, train)

    def apply(self, params, tokens: jnp.ndarray, rng,
              train: bool = True) -> jnp.ndarray:
        """tokens [B, T] int32 → logits [B, T, vocab]."""
        cfg = self.config
        B, T = tokens.shape
        if T > cfg.n_positions:
            raise ValueError(
                f"sequence length {T} exceeds n_positions={cfg.n_positions}")
        with jax.named_scope("embed"):
            x = params["wte"][tokens] + params["wpe"][:T][None]
        x = dropout(x, cfg.embd_dropout if train else 0.0,
                    jax.random.fold_in(rng, 997))

        block_params = params["blocks"]

        def body(carry, xs):
            x = carry
            bp, i = xs
            lrng = jax.random.fold_in(rng, i)
            return self._block(bp, x, lrng, train), None

        # whether a block keeps the flash kernel's results besides its
        # input follows the engine's memory budget (block_remat.py)
        remat = checkpoint_block(
            x, trips=cfg.n_layer, heads=cfg.n_head,
            ffn_width=block_params["fc_w"].shape[-1],
            head_width=cfg.vocab_size,
            attn_sites=int(cfg.attn_impl == "flash")
        ) if cfg.remat == "block" else (lambda f: f)

        if cfg.scan_layers and cfg.stream_scan:
            # Param-streaming form: block params stay a scan CONSTANT
            # (host-resident under zero_optimization.param_streaming) and
            # the body fetches layer i's slice with an explicit transfer
            # to device memory.  The fetch sits INSIDE the remat'd body,
            # so the backward pass re-fetches each layer instead of
            # keeping the stack alive — device param bytes ~ one layer in
            # both directions.  The transfer's transpose moves the layer
            # grads back toward the stack's (host) memory space, so the
            # accumulated grad stack does not claim HBM either.
            fetch = _layer_fetcher(
                self.param_partition_specs(params)["blocks"])

            def body_stream(carry, i):
                return body(carry, (fetch(block_params, i), i))

            with jax.named_scope("layer"):
                x, _ = jax.lax.scan(remat(body_stream), x,
                                    jnp.arange(cfg.n_layer))
        elif cfg.scan_layers:
            # the layer's gather sits INSIDE the remat'd body: under ZeRO
            # the backward gathers the layer again instead of keeping 48
            # gathered layers alive
            block_specs = self.param_partition_specs(params)["blocks"]

            def body_gather(carry, xs):
                bp, i = xs
                return body(carry, (gather_layer(bp, block_specs), i))

            layer_idx = jnp.arange(cfg.n_layer)
            with jax.named_scope("layer"):
                x, _ = jax.lax.scan(remat(body_gather), x,
                                    (block_params, layer_idx))
        else:
            body_fn = remat(body)
            for i in range(cfg.n_layer):
                bp = jax.tree.map(lambda a, i=i: a[i], block_params)
                x, _ = body_fn(x, (bp, jnp.asarray(i)))

        logits = _lm_head(params, x)
        return logits

    def loss_fn(self, params, batch, rng, train: bool = True):
        tokens = batch["input_ids"] if isinstance(batch, dict) else batch
        logits = self.apply(params, tokens[:, :-1], rng, train)
        targets = tokens[:, 1:]
        with jax.named_scope("lm_head"):
            logits = logits.astype(jnp.float32)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
            return jnp.mean(nll)

    # ---------------- serving entry points ----------------
    def prefill(self, params, tokens):
        """Inference forward that also returns every layer's K/V (the
        serving cache fill) — see ``gpt2_prefill``."""
        return gpt2_prefill(self.config, params, tokens)

    def decode_step(self, params, tokens, k_cache, v_cache, lengths,
                    active, impl: Optional[str] = None):
        """One masked decode tick over the slot KV cache — see
        ``gpt2_decode_step``."""
        return gpt2_decode_step(self.config, params, tokens, k_cache,
                                v_cache, lengths, active, impl=impl)

    def prefill_paged(self, params, tokens, delta_len, prefix_len,
                      page_row, k_pool, v_pool, k_scale=None,
                      v_scale=None, lora=None, adapter_slots=None,
                      lora_scale: float = 1.0):
        """Delta-aware prefill into a paged KV pool — see
        ``gpt2_prefill_paged``."""
        return gpt2_prefill_paged(self.config, params, tokens,
                                  delta_len, prefix_len, page_row,
                                  k_pool, v_pool, k_scale=k_scale,
                                  v_scale=v_scale, lora=lora,
                                  adapter_slots=adapter_slots,
                                  lora_scale=lora_scale)

    def decode_step_paged(self, params, tokens, k_pool, v_pool,
                          page_table, lengths, active,
                          impl: Optional[str] = None, k_scale=None,
                          v_scale=None, lora=None, adapter_slots=None,
                          lora_scale: float = 1.0):
        """One masked decode tick over the paged KV pool — see
        ``gpt2_decode_step_paged``."""
        return gpt2_decode_step_paged(self.config, params, tokens,
                                      k_pool, v_pool, page_table,
                                      lengths, active, impl=impl,
                                      k_scale=k_scale, v_scale=v_scale,
                                      lora=lora,
                                      adapter_slots=adapter_slots,
                                      lora_scale=lora_scale)

    def verify_step(self, params, tokens, k_cache, v_cache, lengths,
                    active, impl: Optional[str] = None):
        """Score W speculative tokens per slot in one widened decode
        pass — see ``gpt2_verify_step``."""
        return gpt2_verify_step(self.config, params, tokens, k_cache,
                                v_cache, lengths, active, impl=impl)

    def verify_step_paged(self, params, tokens, k_pool, v_pool,
                          page_table, lengths, active,
                          impl: Optional[str] = None, k_scale=None,
                          v_scale=None, lora=None, adapter_slots=None,
                          lora_scale: float = 1.0):
        """The paged twin of ``verify_step`` — see
        ``gpt2_verify_step_paged``."""
        return gpt2_verify_step_paged(self.config, params, tokens,
                                      k_pool, v_pool, page_table,
                                      lengths, active, impl=impl,
                                      k_scale=k_scale, v_scale=v_scale,
                                      lora=lora,
                                      adapter_slots=adapter_slots,
                                      lora_scale=lora_scale)

    # ---------------- stacked-leaf declarations ----------------
    def stacked_param_spec(self, params):
        """The block leaves are stacked ``[L, ...]`` and ``apply`` scans
        over L; embeddings/final LN are not."""
        if not self.config.scan_layers:
            return None
        return mark_subtrees(params, {"blocks"})

    def streaming_param_spec(self, params):
        """The stacked block leaves stream (one layer per scan tick);
        embeddings/final LN stay device-resident.  Requires the scan form
        with explicit per-layer fetch (``stream_scan``) so the engine's
        host placement actually bounds device bytes."""
        if not self.config.stream_scan:
            return None
        return self.stacked_param_spec(params)


_DEVICE_MEMORY_KIND: Optional[str] = None


def _device_memory_kind() -> str:
    """The backend's default (device/HBM) memory kind — the fetch target
    for streamed layer slices.  'device' on TPU and on the CPU test
    backend; resolved once, outside any trace."""
    global _DEVICE_MEMORY_KIND
    if _DEVICE_MEMORY_KIND is None:
        try:
            _DEVICE_MEMORY_KIND = jax.local_devices()[0].default_memory().kind
        except Exception:
            _DEVICE_MEMORY_KIND = "device"
    return _DEVICE_MEMORY_KIND


def stream_fetch(tree, specs_tree, index, rows=None):
    """Fetch the streaming slice of every leaf's leading (layer) axis and
    move it into device memory with the leaf's own TP sharding (leading
    dim dropped for a single squeezed row when ``rows`` is None, kept at
    length ``rows`` otherwise).  Uses the engine's ambient mesh
    (``jax.set_mesh``); with no mesh set (eager unit use) the fetch
    degrades to a plain index.  Shared by the GPT-2 layer scan and the
    MoE group scan."""
    am = jax.sharding.get_abstract_mesh()
    has_mesh = am is not None and bool(dict(getattr(am, "shape", {})))
    kind = _device_memory_kind() if has_mesh else None

    def one(a, spec):
        if rows is None:
            w = jax.lax.dynamic_index_in_dim(a, index, 0, keepdims=False)
            sp = P(*tuple(spec)[1:])
        else:
            w = jax.lax.dynamic_slice_in_dim(a, index, rows, 0)
            sp = P(*((None,) + tuple(spec)[1:]))
        if not has_mesh:
            return w
        return jax.device_put(
            w, jax.sharding.NamedSharding(am, sp, memory_kind=kind))

    return jax.tree.map(one, tree, specs_tree)


def _layer_fetcher(block_specs):
    """Per-layer fetch for GPT-2's streaming scan (see stream_fetch)."""
    def fetch(block_params, i):
        return stream_fetch(block_params, block_specs, i)
    return fetch


@jax.named_scope("layer")
def gpt2_block_forward(cfg: GPT2Config, bp, x, rng, train: bool):
    """One pre-LN transformer block over unstacked per-layer params — the
    single source of the block math, shared by the scan-over-layers model,
    the pipeline flavor (models/gpt2_pipe.py), and the MoE flavor
    (models/gpt2_moe.py, which swaps the FFN sublayer)."""
    r_attn, r3 = jax.random.split(rng)
    drop = cfg.dropout if train else 0.0
    x = gpt2_attn_sublayer(cfg, bp, x, r_attn, train)
    h = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    h = gpt2_ffn(bp, h)
    return x + dropout(h, drop, r3)


def _wscale(y, bp, name: str):
    """Fused weight dequant (serving.quantization.weights='int8',
    docs/serving.md): a quantized tree carries an ``<name>_scale``
    sibling per matmul weight, and because the scale is per OUTPUT
    channel, ``x · (w8 · s) == (x · w8) · s`` — one multiply on the
    matmul output, never a dequantized weight matrix.  Trees without
    scales (every training path, the default serving config) take the
    no-op branch: their trace is byte-identical to the pre-quant
    code."""
    s = bp.get(name + "_scale")
    return y if s is None else y * s.astype(y.dtype)


def _lora_delta(x, bp, name: str):
    """Heterogeneous batched LoRA delta (serving.lora, docs/serving.md
    "multi-tenant serving"): a lora-bound tree carries a
    ``<name>_lora`` sibling of PER-ROW gathered factors
    ``(A [B, d_in, r], B [B, r, *out], alpha/r)`` — each batch row's
    own tenant adapter, gathered by the traced adapter-slot table
    (:func:`_lora_bind`) — and the delta ``(x·A)·B · (alpha/r)`` is
    computed fused next to the base matmul (S-LoRA/Punica, PAPERS.md).
    Trees without lora entries (every training path, the default
    serving config) return None: their trace is byte-identical to the
    pre-lora code, the ``_wscale`` discipline applied to adapters."""
    lo = bp.get(name + "_lora")
    if lo is None:
        return None
    a, b, scale = lo
    u = jnp.einsum("btd,bdr->btr", x, a.astype(x.dtype))
    delta = jnp.einsum("btr,br...->bt...", u, b.astype(x.dtype))
    return delta * jnp.asarray(scale, x.dtype)


def _lora_bind(bp, lora_layer, adapter_slots, scale):
    """Bind one layer's adapter-slot pools into the block-param dict:
    gather every target's per-row factors by the TRACED int32
    ``adapter_slots`` (the PR 11 scalar-prefetch idiom applied to
    weights — slot 0 is the reserved zero adapter, so no-tenant rows
    compute a mathematically-zero delta through the SAME program).
    ``lora_layer`` is ``{target: (A [N, d_in, r], B [N, r, *out])}``;
    returns a shallow copy of ``bp`` with ``<target>_lora`` entries."""
    if lora_layer is None:
        return bp
    bp = dict(bp)
    for t in sorted(lora_layer):
        a, b = lora_layer[t]
        bp[t + "_lora"] = (a[adapter_slots], b[adapter_slots], scale)
    return bp


@jax.named_scope("mlp")
def gpt2_ffn(bp, h):
    """fc → gelu → proj over already-normalized input (dense FFN body,
    shared with the MoE flavor's dense blocks)."""
    y = _wscale(h @ bp["fc_w"].astype(h.dtype), bp, "fc_w") \
        + bp["fc_b"].astype(h.dtype)
    d = _lora_delta(h, bp, "fc_w")
    if d is not None:
        y = y + d
    h = jax.nn.gelu(y, approximate=True)
    z = _wscale(h @ bp["proj_w"].astype(h.dtype), bp, "proj_w") \
        + bp["proj_b"].astype(h.dtype)
    d = _lora_delta(h, bp, "proj_w")
    if d is not None:
        z = z + d
    return z


def gpt2_qkv_heads(cfg: GPT2Config, bp, x):
    """ln1 → fused qkv → per-head split, [B, H, T, Dh] each — the
    attention sublayer's input math, shared by the training sublayer and
    the serving prefill/decode paths (they must stay bit-identical or
    the decode cache silently diverges from the training forward)."""
    B, T, D = x.shape
    H, Dh = cfg.n_head, cfg.d_head
    h = _layer_norm(x, bp["ln1_scale"], bp["ln1_bias"])
    # contraction keeps q/k/v on a dedicated unsharded dim — slicing it is
    # local under TP (see the qkv_w layout note in GPT2Model.init)
    qkv = (_wscale(jnp.einsum("btd,dke->btke", h,
                              bp["qkv_w"].astype(h.dtype)), bp, "qkv_w")
           + bp["qkv_b"].astype(h.dtype))
    d = _lora_delta(h, bp, "qkv_w")                 # [B, T, 3, E]
    if d is not None:
        qkv = qkv + d
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

    def heads(t):
        return t.reshape(B, T, H, Dh).transpose(0, 2, 1, 3)

    return heads(q), heads(k), heads(v)


def gpt2_attn_project(bp, x, attn, drop: float, rng):
    """heads → output projection → residual (the sublayer's tail,
    shared with the serving paths; ``rng`` may be None when drop=0)."""
    B, H, T, Dh = attn.shape
    attn = attn.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)
    y = _wscale(attn @ bp["out_w"].astype(x.dtype), bp, "out_w") \
        + bp["out_b"].astype(x.dtype)
    d = _lora_delta(attn, bp, "out_w")
    if d is not None:
        y = y + d
    return x + dropout(y, drop, rng)


@jax.named_scope("attn")
def gpt2_attn_sublayer(cfg: GPT2Config, bp, x, rng, train: bool):
    """ln1 → attention → residual (the block minus its FFN sublayer)."""
    B, T, D = x.shape
    H, Dh = cfg.n_head, cfg.d_head
    r1, r2 = jax.random.split(rng)
    drop = cfg.dropout if train else 0.0

    q, k, v = gpt2_qkv_heads(cfg, bp, x)

    if cfg.attn_impl == "flash":
        # Pallas flash kernel (prob-dropout fused in-kernel).
        from ..parallel.attention import sharded_flash_attention
        attn = sharded_flash_attention(q, k, v, causal=True,
                                       dropout_rate=drop, dropout_rng=r1)
    elif cfg.attn_impl == "dense":
        attn = causal_attention(q, k, v,
                                dropout_rate=drop, dropout_rng=r1)
    elif cfg.attn_impl in ("ring", "ulysses"):
        # sequence-parallel attention over the mesh's 'seq' axis: manual
        # shard_map on 'seq' only, data/model stay under GSPMD.  Requires
        # the engine to run under jax.set_mesh (it does) so the abstract
        # mesh is visible here.
        from jax.sharding import PartitionSpec as P
        from ..parallel.sequence import (SEQ_AXIS, ring_attention,
                                         ulysses_attention)
        am = jax.sharding.get_abstract_mesh()
        sp = dict(getattr(am, "shape", {})).get(SEQ_AXIS, 1)
        # Direct attribute access on purpose: if jax renames manual_axes
        # this guard must break loudly, not silently disable (a silent ()
        # default would let sp>1 run inside the 1-bit/CSR engines' manual
        # 'data' shard_map — exactly the partitioner crash / divergent-
        # collective deadlock this guard pre-empts).
        manual = set(am.manual_axes) if am is not None else set()
        if sp > 1 and not manual <= {"pipe"}:
            # Nesting under the pipeline's manual 'pipe' axis is
            # supported: the inner shard_map closes over only 'seq' and
            # the pipeline's uniform-stage body keeps the seq collectives
            # identical on every pipe rank (pipe/engine.py:
            # _uniform_stack_info).  Any OTHER manual context (the 1-bit
            # and CSR engines' shard_map over 'data', or 'seq' itself
            # already manual) has had no such hardening — fail with the
            # real story instead of a partitioner crash or a divergent
            # collective deadlock.
            raise NotImplementedError(
                "sequence-parallel attention cannot run inside a manual "
                f"SPMD program over axes {sorted(manual)}; sp composes "
                "with the plain dp/tp/ZeRO engines and (via the uniform-"
                "stage body) the pipeline engine — not the 1-bit or "
                "sparse-gradient engines")
        seed = (jax.random.bits(r1, (), jnp.uint32) if drop > 0.0
                else jnp.zeros((), jnp.uint32))
        if sp > 1:
            impl = (ring_attention if cfg.attn_impl == "ring"
                    else ulysses_attention)
            spec = P(None, None, SEQ_AXIS, None)
            # dropout mask is hashed from GLOBAL positions (the flash
            # kernel's hash), so the seed is a replicated scalar and the
            # realization is identical for any seq-shard count (incl.
            # the sp==1 fallback below)
            # the seq rank rides in as a P(seq)-sharded iota operand:
            # axis_index inside this shard_map would lower to a manual
            # computation over the complement axes, which re-binds 'pipe'
            # when nested inside the pipeline engine's manual region
            fn = jax.shard_map(
                lambda q, k, v, seed, rk: impl(
                    q, k, v, SEQ_AXIS, causal=True, dropout_rate=drop,
                    dropout_seed=seed, rank=rk),
                in_specs=(spec, spec, spec, P(), P(SEQ_AXIS)),
                out_specs=spec,
                axis_names={SEQ_AXIS}, check_vma=False)
            attn = fn(q, k, v, seed, jnp.arange(sp, dtype=jnp.int32))
        else:  # mesh has no seq shards: dense attention, same hash mask
            keep = None
            if drop > 0.0:
                from ..ops.pallas.flash_attention import dense_keep_mask
                keep = dense_keep_mask(B, H, T, T, seed, drop)
            attn = causal_attention(q, k, v,
                                    dropout_rate=drop, dropout_keep=keep)
    else:
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r}: expected 'flash', 'dense', "
            "'ring', or 'ulysses'")
    return gpt2_attn_project(bp, x, attn, drop, r2)


# ---------------------------------------------------------------------------
# serving paths: prefill + step-decode over a slot KV cache
# (deepspeed_tpu/inference/ — docs/serving.md).  These REUSE the block
# helpers above (gpt2_qkv_heads / gpt2_attn_project / gpt2_ffn /
# _layer_norm) so a step-decoded token's logits match the training
# forward's logits at the same position: the prefill==decode parity
# tests (tests/test_inference.py) pin fp32 bitwise on the dense path.
# ---------------------------------------------------------------------------


def _decode_attn_impl(cfg: GPT2Config) -> str:
    """Map the training attention impl onto the decode kernel arm."""
    if cfg.attn_impl == "flash":
        return "pallas"
    if cfg.attn_impl == "dense":
        return "dense"
    raise NotImplementedError(
        f"attn_impl={cfg.attn_impl!r} has no serving decode path; serve "
        "with 'flash' or 'dense' (sequence-parallel attention shards the "
        "time axis the decode cache does not have)")


@jax.named_scope("layer")
def gpt2_block_prefill(cfg: GPT2Config, bp, x):
    """One block at inference (train=False — every dropout is a no-op),
    additionally returning the per-head K/V for the serving cache."""
    with jax.named_scope("attn"):
        q, k, v = gpt2_qkv_heads(cfg, bp, x)
        if cfg.attn_impl == "flash":
            from ..parallel.attention import sharded_flash_attention
            attn = sharded_flash_attention(q, k, v, causal=True)
        elif cfg.attn_impl == "dense":
            attn = causal_attention(q, k, v)
        else:
            _decode_attn_impl(cfg)  # raises with the real story
        x = gpt2_attn_project(bp, x, attn, 0.0, None)
    h = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    return x + gpt2_ffn(bp, h), (k, v)


def _cache_write(cache, new, pos, active):
    """Masked in-place write of one token's K (or V) rows into the slot
    cache: ``cache[s, :, pos[s]] = new[s]`` where ``active[s]``; inactive
    slots write their OLD value back (a pure no-op), so one static-shape
    program serves any admission/eviction mix.  cache [S, H, T, Dh],
    new [S, H, Dh], pos [S] int32 (clipped), active [S] bool."""
    S, H, T, Dh = cache.shape
    s_idx = jnp.arange(S)
    pos = jnp.clip(pos, 0, T - 1)
    old = cache[s_idx, :, pos]                          # [S, H, Dh]
    blended = jnp.where(active[:, None, None], new.astype(cache.dtype),
                        old)
    return cache.at[s_idx, :, pos].set(blended)


@jax.named_scope("layer")
def gpt2_block_decode(cfg: GPT2Config, bp, x, k_cache, v_cache,
                      positions, att_len, active, impl: str):
    """One block for a single decode tick: x [S, 1, D] (one new token
    per slot); writes the token's K/V at ``positions`` (masked by
    ``active``) then attends over ``att_len`` live keys per slot."""
    with jax.named_scope("attn"):
        q, k, v = gpt2_qkv_heads(cfg, bp, x)                # [S, H, 1, Dh]
        k_cache = _cache_write(k_cache, k[:, :, 0], positions, active)
        v_cache = _cache_write(v_cache, v[:, :, 0], positions, active)
        from ..ops.pallas.decode_attention import decode_attention
        attn = decode_attention(q[:, :, 0], k_cache, v_cache, att_len,
                                impl=impl)                  # [S, H, Dh]
        x = gpt2_attn_project(bp, x, attn[:, :, None, :], 0.0, None)
    h = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    return x + gpt2_ffn(bp, h), k_cache, v_cache


def gpt2_prefill(cfg: GPT2Config, params, tokens):
    """tokens [B, T] int32 → (logits [B, T, V], k, v [L, B, H, T, Dh]).

    The inference forward (train=False numerics of ``GPT2Model.apply``)
    that also materializes every layer's K/V for the serving cache.
    Causal masking means positions beyond a prompt's live length only
    contaminate THEIR OWN rows — the cache masks them by length."""
    B, T = tokens.shape
    if T > cfg.n_positions:
        raise ValueError(
            f"sequence length {T} exceeds n_positions={cfg.n_positions}")
    with jax.named_scope("embed"):
        x = params["wte"][tokens] + params["wpe"][:T][None]
    block_params = params["blocks"]
    if cfg.scan_layers:
        def body(x, bp):
            return gpt2_block_prefill(cfg, bp, x)
        with jax.named_scope("layer"):
            x, (ks, vs) = jax.lax.scan(body, x, block_params)
    else:
        ks_l, vs_l = [], []
        for i in range(cfg.n_layer):
            bp = jax.tree.map(lambda a, i=i: a[i], block_params)
            x, (kk, vv) = gpt2_block_prefill(cfg, bp, x)
            ks_l.append(kk)
            vs_l.append(vv)
        ks, vs = jnp.stack(ks_l), jnp.stack(vs_l)
    logits = _lm_head(params, x)
    return logits, ks, vs


def gpt2_decode_step(cfg: GPT2Config, params, tokens, k_cache, v_cache,
                     lengths, active, impl: Optional[str] = None):
    """One decode tick for every slot at once (static shapes — the ONE
    compiled decode program of docs/serving.md).

    tokens [S] int32 — each slot's last emitted/prompt token;
    k_cache/v_cache [L, S, H, T, Dh]; lengths [S] int32 — live KV length
    BEFORE this token; active [S] bool — slots actually decoding this
    tick (free/finished slots compute masked no-ops).

    Returns (logits [S, V], k_cache, v_cache, new_lengths): logits for
    the NEXT token of each active slot; inactive slots' logits are
    garbage-but-finite and must be ignored by the caller."""
    if impl is None:
        impl = _decode_attn_impl(cfg)
    T = k_cache.shape[3]
    lengths = lengths.astype(jnp.int32)
    positions = jnp.clip(lengths, 0, min(T, cfg.n_positions) - 1)
    with jax.named_scope("embed"):
        x = (params["wte"][tokens][:, None, :]
             + params["wpe"][positions][:, None, :])
    # live keys this tick INCLUDE the token being decoded; free slots
    # attend nothing (exact-zero attention rows)
    att_len = jnp.where(active, lengths + 1, 0).astype(jnp.int32)
    block_params = params["blocks"]
    if cfg.scan_layers:
        def body(x, xs):
            bp, kc, vc = xs
            x, kc, vc = gpt2_block_decode(cfg, bp, x, kc, vc, positions,
                                          att_len, active, impl)
            return x, (kc, vc)
        with jax.named_scope("layer"):
            x, (k_cache, v_cache) = jax.lax.scan(
                body, x, (block_params, k_cache, v_cache))
    else:
        kc_l, vc_l = [], []
        for i in range(cfg.n_layer):
            bp = jax.tree.map(lambda a, i=i: a[i], block_params)
            x, kc, vc = gpt2_block_decode(cfg, bp, x, k_cache[i],
                                          v_cache[i], positions,
                                          att_len, active, impl)
            kc_l.append(kc)
            vc_l.append(vc)
        k_cache, v_cache = jnp.stack(kc_l), jnp.stack(vc_l)
    logits = _lm_head(params, x)[:, 0]
    new_lengths = lengths + active.astype(jnp.int32)
    return logits, k_cache, v_cache, new_lengths


# ---------------------------------------------------------------------------
# speculative verify path (serving.speculate_k > 0, docs/serving.md):
# ONE widened decode pass scores W = k+1 new tokens per slot — the
# slot's pending token plus its k draft proposals — writing all W K/V
# rows (masked) and attending each query over its own causal window.
# Same block helpers, same masked-no-op contract as gpt2_decode_step;
# acceptance/rollback are the engine's (inference/speculative.py).
# ---------------------------------------------------------------------------


def _verify_rows(lengths, active, W: int, cap: int):
    """The per-row geometry every verify arm shares: absolute positions
    (clipped), write validity, and per-query attention lengths.

    Row ``i`` of slot ``s`` sits at absolute position ``lengths[s]+i``
    and attends ``lengths[s]+i+1`` keys.  Rows beyond ``cap`` (the
    cache stride / table capacity) are masked — their K/V write is a
    no-op and their output row is exact-zero garbage the engine's
    acceptance truncation discards (a kv_capacity finish is at most W
    tokens away)."""
    base = lengths.astype(jnp.int32)
    offs = jnp.arange(W, dtype=jnp.int32)[None, :]
    abs_pos = base[:, None] + offs                      # [S, W]
    row_valid = active[:, None] & (abs_pos < cap)
    positions = jnp.clip(abs_pos, 0, cap - 1)
    row_lens = jnp.where(row_valid, abs_pos + 1, 0).astype(jnp.int32)
    return positions, row_valid, row_lens


@jax.named_scope("layer")
def gpt2_block_verify(cfg: GPT2Config, bp, x, k_cache, v_cache,
                      positions, row_valid, row_lens, impl: str):
    """One block of the verify pass: x [S, W, D] (W new tokens per
    slot); writes all W K/V rows (masked per row) then runs the
    multi-query decode attention."""
    with jax.named_scope("attn"):
        q, k, v = gpt2_qkv_heads(cfg, bp, x)                # [S, H, W, Dh]
        W = x.shape[1]
        for i in range(W):                                  # static, W <= 9
            k_cache = _cache_write(k_cache, k[:, :, i], positions[:, i],
                                   row_valid[:, i])
            v_cache = _cache_write(v_cache, v[:, :, i], positions[:, i],
                                   row_valid[:, i])
        from ..ops.pallas.decode_attention import decode_attention_multi
        attn = decode_attention_multi(q, k_cache, v_cache, row_lens,
                                      impl=impl)            # [S, H, W, Dh]
        x = gpt2_attn_project(bp, x, attn, 0.0, None)
    h = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    return x + gpt2_ffn(bp, h), k_cache, v_cache


def gpt2_verify_step(cfg: GPT2Config, params, tokens, k_cache, v_cache,
                     lengths, active, impl: Optional[str] = None):
    """One speculative verify pass for every slot at once (static
    shapes — W = k+1 is baked into the program, everything else is
    traced, so the one-compiled-verify-program contract holds across
    arbitrary accepted-length mixes).

    tokens [S, W] int32 — per slot: its pending last token followed by
    its k draft proposals; k_cache/v_cache [L, S, H, T, Dh]; lengths
    [S] int32 — live KV length BEFORE this pass; active [S] bool.

    Returns ``(logits [S, W, V], k_cache, v_cache)``: ``logits[s, i]``
    scores the token AFTER ``tokens[s, i]`` (absolute position
    ``lengths[s] + i``).  Lengths are NOT advanced — how far the cache
    really moved is the acceptance decision, made by the caller
    (inference/speculative.py); un-accepted rows simply stay masked
    beyond the advanced length (the unpaged rollback is free)."""
    if impl is None:
        impl = _decode_attn_impl(cfg)
    S, W = tokens.shape
    T = k_cache.shape[3]
    cap = min(T, cfg.n_positions)
    positions, row_valid, row_lens = _verify_rows(lengths, active, W,
                                                  cap)
    with jax.named_scope("embed"):
        x = params["wte"][tokens] + params["wpe"][positions]    # [S, W, D]
    block_params = params["blocks"]
    if cfg.scan_layers:
        def body(x, xs):
            bp, kc, vc = xs
            x, kc, vc = gpt2_block_verify(cfg, bp, x, kc, vc, positions,
                                          row_valid, row_lens, impl)
            return x, (kc, vc)
        with jax.named_scope("layer"):
            x, (k_cache, v_cache) = jax.lax.scan(
                body, x, (block_params, k_cache, v_cache))
    else:
        kc_l, vc_l = [], []
        for i in range(cfg.n_layer):
            bp = jax.tree.map(lambda a, i=i: a[i], block_params)
            x, kc, vc = gpt2_block_verify(cfg, bp, x, k_cache[i],
                                          v_cache[i], positions,
                                          row_valid, row_lens, impl)
            kc_l.append(kc)
            vc_l.append(vc)
        k_cache, v_cache = jnp.stack(kc_l), jnp.stack(vc_l)
    logits = _lm_head(params, x)                # [S, W, V]
    return logits, k_cache, v_cache


@jax.named_scope("layer")
def gpt2_block_verify_paged(cfg: GPT2Config, bp, x, k_pool, v_pool,
                            page_table, positions, row_valid, row_lens,
                            impl: str, k_scale=None, v_scale=None):
    """One block of the PAGED verify pass: W masked page-routed writes
    (invalid rows to the scratch page) then the paged multi-query
    attention — quantizing each row on write and running the fused-
    dequant multi arm when the pool is int8."""
    with jax.named_scope("attn"):
        q, k, v = gpt2_qkv_heads(cfg, bp, x)                # [S, H, W, Dh]
        W = x.shape[1]
        page_len = k_pool.shape[2]
        s_idx = jnp.arange(page_table.shape[0])
        for i in range(W):                                  # static, W <= 9
            pos = positions[:, i]
            page_ids = jnp.where(row_valid[:, i],
                                 page_table[s_idx, pos // page_len], 0)
            offs = pos % page_len
            k_pool, k_scale = _paged_write(k_pool, k_scale, k[:, :, i],
                                           page_ids, offs, row_valid[:, i])
            v_pool, v_scale = _paged_write(v_pool, v_scale, v[:, :, i],
                                           page_ids, offs, row_valid[:, i])
        from ..ops.pallas.decode_attention import decode_attention_paged_multi
        attn = decode_attention_paged_multi(q, k_pool, v_pool, page_table,
                                            row_lens, impl=impl,
                                            k_scale=k_scale,
                                            v_scale=v_scale)
        x = gpt2_attn_project(bp, x, attn, 0.0, None)
    h = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    return (x + gpt2_ffn(bp, h), k_pool, v_pool, k_scale, v_scale)


def gpt2_verify_step_paged(cfg: GPT2Config, params, tokens, k_pool,
                           v_pool, page_table, lengths, active,
                           impl: Optional[str] = None,
                           k_scale=None, v_scale=None,
                           lora=None, adapter_slots=None,
                           lora_scale: float = 1.0):
    """The paged twin of ``gpt2_verify_step`` — same contract over the
    page pool; the engine must have allocated pages covering all W
    speculative rows before the pass (rollback frees the ones the
    acceptance didn't keep).  With the int8 pool's scale sidecars the
    return grows to (logits, k_pool, v_pool, k_scale, v_scale).
    ``lora``/``adapter_slots``/``lora_scale`` follow
    ``gpt2_decode_step_paged``'s multi-tenant contract."""
    if impl is None:
        impl = _decode_attn_impl(cfg)
    quant = k_scale is not None
    S, W = tokens.shape
    page_len = k_pool.shape[3]
    cap = min(page_table.shape[1] * page_len, cfg.n_positions)
    positions, row_valid, row_lens = _verify_rows(lengths, active, W,
                                                  cap)
    with jax.named_scope("embed"):
        x = params["wte"][tokens] + params["wpe"][positions]
    block_params = params["blocks"]
    if cfg.scan_layers:
        def body(x, xs):
            bp, kc, vc, ks, vs = xs[:5]
            if lora is not None:
                bp = _lora_bind(bp, xs[5], adapter_slots, lora_scale)
            x, kc, vc, ks, vs = gpt2_block_verify_paged(
                cfg, bp, x, kc, vc, page_table, positions, row_valid,
                row_lens, impl, k_scale=ks, v_scale=vs)
            return x, (kc, vc, ks, vs)
        xs = (block_params, k_pool, v_pool, k_scale, v_scale)
        if lora is not None:
            xs = xs + (lora,)
        with jax.named_scope("layer"):
            x, (k_pool, v_pool, k_scale, v_scale) = jax.lax.scan(
                body, x, xs)
    else:
        kc_l, vc_l, ks_l, vs_l = [], [], [], []
        for i in range(cfg.n_layer):
            bp = jax.tree.map(lambda a, i=i: a[i], block_params)
            if lora is not None:
                bp = _lora_bind(
                    bp, jax.tree.map(lambda a, i=i: a[i], lora),
                    adapter_slots, lora_scale)
            x, kc, vc, ks, vs = gpt2_block_verify_paged(
                cfg, bp, x, k_pool[i], v_pool[i], page_table, positions,
                row_valid, row_lens, impl,
                k_scale=None if k_scale is None else k_scale[i],
                v_scale=None if v_scale is None else v_scale[i])
            kc_l.append(kc)
            vc_l.append(vc)
            ks_l.append(ks)
            vs_l.append(vs)
        k_pool, v_pool = jnp.stack(kc_l), jnp.stack(vc_l)
        if quant:
            k_scale, v_scale = jnp.stack(ks_l), jnp.stack(vs_l)
    logits = _lm_head(params, x)
    if quant:
        return logits, k_pool, v_pool, k_scale, v_scale
    return logits, k_pool, v_pool


# ---------------------------------------------------------------------------
# paged serving paths (serving.page_len > 0, docs/serving.md): the same
# block helpers over a flat page pool [P, H, page_len, Dh] addressed
# through per-slot int32 page tables.  Page 0 is the reserved scratch
# page: every MASKED write is routed there, so a scatter conflict can
# only be two no-ops colliding — an active slot's row is never racing
# a masked write.
# ---------------------------------------------------------------------------


def _paged_cache_write(pool, new, page_ids, offs, active):
    """Masked one-row-per-slot write into the page pool:
    ``pool[page_ids[s], :, offs[s]] = new[s]`` where ``active[s]``;
    inactive slots write their OLD value back at the scratch page.
    pool [P, H, page_len, Dh], new [S, H, Dh], page_ids/offs [S] int32
    (already routed to scratch for inactive slots), active [S] bool."""
    old = pool[page_ids, :, offs]                       # [S, H, Dh]
    blended = jnp.where(active[:, None, None], new.astype(pool.dtype),
                        old)
    return pool.at[page_ids, :, offs].set(blended)


def _paged_cache_write_quant(pool, scales, new, page_ids, offs, active):
    """The quantize-on-write twin of :func:`_paged_cache_write`
    (serving.quantization.kv='int8'): each fp row is quantized per
    (row, head) — symmetric absmax int8 + one fp32 scale
    (inference/quantize.py, the ONE quantization rule) — and both the
    int8 row and its scale land under the same mask, so an inactive
    slot's scale write is the same old-value no-op as its data write.
    pool int8 [P, H, page_len, Dh], scales fp32 [P, H, page_len]."""
    from ..inference.quantize import quantize_rows
    q8, s = quantize_rows(new)                          # [S,H,Dh]/[S,H]
    old = pool[page_ids, :, offs]
    old_s = scales[page_ids, :, offs]
    blended = jnp.where(active[:, None, None], q8, old)
    blended_s = jnp.where(active[:, None], s, old_s)
    return (pool.at[page_ids, :, offs].set(blended),
            scales.at[page_ids, :, offs].set(blended_s))


def _paged_write(pool, scales, new, page_ids, offs, active):
    """Dispatch one masked row write to the fp or quantized pool arm —
    ``scales`` None selects the pre-quant write, byte for byte."""
    if scales is None:
        return _paged_cache_write(pool, new, page_ids, offs, active), None
    return _paged_cache_write_quant(pool, scales, new, page_ids, offs,
                                    active)


@jax.named_scope("layer")
def gpt2_block_decode_paged(cfg: GPT2Config, bp, x, k_pool, v_pool,
                            page_table, positions, att_len, active,
                            impl: str, k_scale=None, v_scale=None):
    """One block for a single paged decode tick: x [S, 1, D]; writes
    the token's K/V at ``positions`` into the slot's page (masked by
    ``active``, inactive routed to scratch) then attends over
    ``att_len`` live keys per slot through the page table.  With the
    int8 pool (``k_scale``/``v_scale`` [P, H, page_len]) the write
    quantizes per row and the attention runs the fused-dequant arm."""
    with jax.named_scope("attn"):
        q, k, v = gpt2_qkv_heads(cfg, bp, x)                # [S, H, 1, Dh]
        page_len = k_pool.shape[2]
        s_idx = jnp.arange(page_table.shape[0])
        page_ids = jnp.where(active,
                             page_table[s_idx, positions // page_len], 0)
        offs = positions % page_len
        k_pool, k_scale = _paged_write(k_pool, k_scale, k[:, :, 0],
                                       page_ids, offs, active)
        v_pool, v_scale = _paged_write(v_pool, v_scale, v[:, :, 0],
                                       page_ids, offs, active)
        from ..ops.pallas.decode_attention import decode_attention_paged
        attn = decode_attention_paged(q[:, :, 0], k_pool, v_pool,
                                      page_table, att_len, impl=impl,
                                      k_scale=k_scale, v_scale=v_scale)
        x = gpt2_attn_project(bp, x, attn[:, :, None, :], 0.0, None)
    h = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    return (x + gpt2_ffn(bp, h), k_pool, v_pool, k_scale, v_scale)


def gpt2_decode_step_paged(cfg: GPT2Config, params, tokens, k_pool,
                           v_pool, page_table, lengths, active,
                           impl: Optional[str] = None,
                           k_scale=None, v_scale=None,
                           lora=None, adapter_slots=None,
                           lora_scale: float = 1.0):
    """One decode tick for every slot at once over the paged pool —
    the paged twin of ``gpt2_decode_step`` (same masked-no-op contract,
    same traced-operand zero-recompile contract; the page table is one
    more traced operand).

    tokens [S] int32; k_pool/v_pool [L, P, H, page_len, Dh];
    page_table [S, max_pages] int32 (dead entries = scratch page 0);
    lengths [S] int32 — live KV length BEFORE this token; active [S]
    bool.  Returns (logits [S, V], k_pool, v_pool, new_lengths).

    Quantized pool (serving.quantization.kv='int8'): pass the fp32
    scale sidecars ``k_scale``/``v_scale`` [L, P, H, page_len] — the
    return grows to (logits, k_pool, v_pool, k_scale, v_scale,
    new_lengths); they are one more scan carry, still traced, still
    zero-recompile.

    Multi-tenant LoRA (serving.lora, docs/serving.md): ``lora`` is the
    layer-stacked adapter-slot pools
    ``{target: (A [L, N, d_in, r], B [L, N, r, *out])}`` and
    ``adapter_slots`` [S] int32 maps each slot to its tenant's HBM
    adapter slot (0 = the reserved zero adapter).  Both are TRACED
    operands — tenant mixes change the table contents, never the
    program.  ``lora=None`` (the default) leaves the trace
    byte-identical to the pre-lora code."""
    if impl is None:
        impl = _decode_attn_impl(cfg)
    quant = k_scale is not None
    page_len = k_pool.shape[3]
    cap = page_table.shape[1] * page_len
    lengths = lengths.astype(jnp.int32)
    positions = jnp.clip(lengths, 0, min(cap, cfg.n_positions) - 1)
    with jax.named_scope("embed"):
        x = (params["wte"][tokens][:, None, :]
             + params["wpe"][positions][:, None, :])
    att_len = jnp.where(active, lengths + 1, 0).astype(jnp.int32)
    block_params = params["blocks"]
    if cfg.scan_layers:
        def body(x, xs):
            bp, kc, vc, ks, vs = xs[:5]
            if lora is not None:
                bp = _lora_bind(bp, xs[5], adapter_slots, lora_scale)
            x, kc, vc, ks, vs = gpt2_block_decode_paged(
                cfg, bp, x, kc, vc, page_table, positions, att_len,
                active, impl, k_scale=ks, v_scale=vs)
            return x, (kc, vc, ks, vs)
        xs = (block_params, k_pool, v_pool, k_scale, v_scale)
        if lora is not None:
            xs = xs + (lora,)
        with jax.named_scope("layer"):
            x, (k_pool, v_pool, k_scale, v_scale) = jax.lax.scan(
                body, x, xs)
    else:
        kc_l, vc_l, ks_l, vs_l = [], [], [], []
        for i in range(cfg.n_layer):
            bp = jax.tree.map(lambda a, i=i: a[i], block_params)
            if lora is not None:
                bp = _lora_bind(
                    bp, jax.tree.map(lambda a, i=i: a[i], lora),
                    adapter_slots, lora_scale)
            x, kc, vc, ks, vs = gpt2_block_decode_paged(
                cfg, bp, x, k_pool[i], v_pool[i], page_table,
                positions, att_len, active, impl,
                k_scale=None if k_scale is None else k_scale[i],
                v_scale=None if v_scale is None else v_scale[i])
            kc_l.append(kc)
            vc_l.append(vc)
            ks_l.append(ks)
            vs_l.append(vs)
        k_pool, v_pool = jnp.stack(kc_l), jnp.stack(vc_l)
        if quant:
            k_scale, v_scale = jnp.stack(ks_l), jnp.stack(vs_l)
    logits = _lm_head(params, x)[:, 0]
    new_lengths = lengths + active.astype(jnp.int32)
    if quant:
        return logits, k_pool, v_pool, k_scale, v_scale, new_lengths
    return logits, k_pool, v_pool, new_lengths


@jax.named_scope("layer")
def gpt2_block_prefill_paged(cfg: GPT2Config, bp, x, k_pool, v_pool,
                             page_row, prefix_len, delta_len,
                             k_scale=None, v_scale=None):
    """One block of the delta-aware paged prefill: compute the DELTA
    tokens' K/V (positions ``prefix_len + i``), scatter them into the
    slot's pages, then attend.

    Two attention arms under ``lax.cond`` on the TRACED ``prefix_len``:

    * ``prefix_len == 0`` (no cached prefix) — the model's OWN prefill
      attention (flash or dense, exactly ``gpt2_block_prefill``'s ops),
      so a paged prefill without a prefix hit is BITWISE identical to
      the pre-page prefill: the parity anchor of tests/test_paged_kv.py.
      With the int8 pool the attention still runs over the EXACT fp
      K/V (only the STORED rows are quantized — the standard KV-quant
      discipline: prefill computes full-precision, decode reads back
      dequantized; docs/serving.md tolerance tiers).
    * ``prefix_len > 0`` — dense attention over the pool gathered
      through ``page_row`` (dequantized on the quant arm): delta query
      ``i`` (absolute position ``prefix_len+i``) attends every key at
      absolute position ``<= prefix_len+i`` — the cached prefix plus
      the causal delta.
    """
    with jax.named_scope("attn"):
        q, k, v = gpt2_qkv_heads(cfg, bp, x)                # [1, H, Tq, Dh]
        Tq = x.shape[1]
        page_len = k_pool.shape[2]
        cap = page_row.shape[0] * page_len
        abs_pos = prefix_len + jnp.arange(Tq, dtype=jnp.int32)
        valid = jnp.arange(Tq) < delta_len
        # masked rows route to the scratch page: a clipped dead position
        # must never collide with a live row's (page, off) target
        abs_clip = jnp.clip(abs_pos, 0, cap - 1)
        page_ids = jnp.where(valid, page_row[abs_clip // page_len], 0)
        offs = abs_clip % page_len
        kn = k[0].transpose(1, 0, 2)                        # [Tq, H, Dh]
        vn = v[0].transpose(1, 0, 2)
        k_pool, k_scale = _paged_write(k_pool, k_scale, kn, page_ids, offs,
                                       valid)
        v_pool, v_scale = _paged_write(v_pool, v_scale, vn, page_ids, offs,
                                       valid)

        def _self_arm(_):
            # the pre-page prefill attention, op for op
            if cfg.attn_impl == "flash":
                from ..parallel.attention import sharded_flash_attention
                return sharded_flash_attention(q, k, v, causal=True)
            return causal_attention(q, k, v)

        def _gather_arm(_):
            from ..ops.pallas.decode_attention import (_default_scale,
                                                       dequantize_paged,
                                                       paged_gather)
            if k_scale is not None:
                kg = dequantize_paged(k_pool, k_scale, page_row[None])[0]
                vg = dequantize_paged(v_pool, v_scale, page_row[None])[0]
            else:
                kg = paged_gather(k_pool, page_row[None])[0]  # [H, T', Dh]
                vg = paged_gather(v_pool, page_row[None])[0]
            scale = _default_scale(cfg.d_head)
            s = jnp.einsum("htd,hsd->hts", q[0], kg.astype(q.dtype),
                           preferred_element_type=jnp.float32) * scale
            key_pos = jnp.arange(kg.shape[1], dtype=jnp.int32)
            ok = key_pos[None, :] <= abs_pos[:, None]       # [Tq, T']
            neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)
            s = jnp.where(ok[None], s, neg)
            probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
            return jnp.einsum("hts,hsd->htd", probs,
                              vg.astype(q.dtype))[None]

        attn = jax.lax.cond(prefix_len == 0, _self_arm, _gather_arm,
                            operand=None)
        x = gpt2_attn_project(bp, x, attn, 0.0, None)
    h = _layer_norm(x, bp["ln2_scale"], bp["ln2_bias"])
    return (x + gpt2_ffn(bp, h), k_pool, v_pool, k_scale, v_scale)


def gpt2_prefill_paged(cfg: GPT2Config, params, tokens, delta_len,
                       prefix_len, page_row, k_pool, v_pool,
                       k_scale=None, v_scale=None,
                       lora=None, adapter_slots=None,
                       lora_scale: float = 1.0):
    """Delta-aware prefill into the paged pool (ONE compiled program
    for full prefills AND prefix-hit deltas — ``prefix_len``,
    ``delta_len`` and ``page_row`` are all traced).

    tokens [1, Tq] int32 — the DELTA tokens (prompt minus the cached
    prefix) right-padded to the static prefill bucket; delta_len /
    prefix_len scalars; page_row [max_pages] int32 — the slot's FULL
    table (shared prefix pages + freshly allocated delta pages, dead
    entries = scratch); k_pool/v_pool [L, P, H, page_len, Dh].

    Returns (logits [1, Tq, V], k_pool, v_pool): logits[0, i] scores
    the token after absolute position ``prefix_len + i`` — the first
    generated token reads ``logits[0, delta_len - 1]``.  Padding rows
    produce garbage-but-finite logits and never contaminate live rows
    (their K/V scatter is masked to the scratch page).

    Quantized pool: pass ``k_scale``/``v_scale`` [L, P, H, page_len];
    the return grows to (logits, k_pool, v_pool, k_scale, v_scale).

    Multi-tenant LoRA: ``adapter_slots`` is the requesting tenant's
    HBM adapter slot — a TRACED scalar (or [1]) int32, one slot per
    prefill — gathered from the same layer-stacked ``lora`` pools as
    the decode tick (``gpt2_decode_step_paged``'s contract)."""
    B, Tq = tokens.shape
    if Tq > cfg.n_positions:
        raise ValueError(
            f"sequence length {Tq} exceeds n_positions={cfg.n_positions}")
    quant = k_scale is not None
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    delta_len = jnp.asarray(delta_len, jnp.int32)
    pos = jnp.clip(prefix_len + jnp.arange(Tq, dtype=jnp.int32), 0,
                   cfg.n_positions - 1)
    with jax.named_scope("embed"):
        x = params["wte"][tokens] + params["wpe"][pos][None]
    if lora is not None:
        # one tenant per prefill: a length-1 slot table so the batched
        # per-row gather (`_lora_delta`) is the SAME einsum as decode
        adapter_slots = jnp.atleast_1d(
            jnp.asarray(adapter_slots, jnp.int32))
    block_params = params["blocks"]
    if cfg.scan_layers:
        def body(x, xs):
            bp, kc, vc, ks, vs = xs[:5]
            if lora is not None:
                bp = _lora_bind(bp, xs[5], adapter_slots, lora_scale)
            x, kc, vc, ks, vs = gpt2_block_prefill_paged(
                cfg, bp, x, kc, vc, page_row, prefix_len, delta_len,
                k_scale=ks, v_scale=vs)
            return x, (kc, vc, ks, vs)
        xs = (block_params, k_pool, v_pool, k_scale, v_scale)
        if lora is not None:
            xs = xs + (lora,)
        with jax.named_scope("layer"):
            x, (k_pool, v_pool, k_scale, v_scale) = jax.lax.scan(
                body, x, xs)
    else:
        kc_l, vc_l, ks_l, vs_l = [], [], [], []
        for i in range(cfg.n_layer):
            bp = jax.tree.map(lambda a, i=i: a[i], block_params)
            if lora is not None:
                bp = _lora_bind(
                    bp, jax.tree.map(lambda a, i=i: a[i], lora),
                    adapter_slots, lora_scale)
            x, kc, vc, ks, vs = gpt2_block_prefill_paged(
                cfg, bp, x, k_pool[i], v_pool[i], page_row, prefix_len,
                delta_len,
                k_scale=None if k_scale is None else k_scale[i],
                v_scale=None if v_scale is None else v_scale[i])
            kc_l.append(kc)
            vc_l.append(vc)
            ks_l.append(ks)
            vs_l.append(vs)
        k_pool, v_pool = jnp.stack(kc_l), jnp.stack(vc_l)
        if quant:
            k_scale, v_scale = jnp.stack(ks_l), jnp.stack(vs_l)
    logits = _lm_head(params, x)
    if quant:
        return logits, k_pool, v_pool, k_scale, v_scale
    return logits, k_pool, v_pool


@jax.named_scope("lm_head")
def _lm_head(params, x):
    """Final LayerNorm → logits over the tied embedding, [..., vocab]."""
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    return x @ params["wte"].astype(x.dtype).T


def _layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) +
            bias.astype(jnp.float32)).astype(dt)
