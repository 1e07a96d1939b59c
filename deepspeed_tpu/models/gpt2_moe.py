"""GPT-2 Mixture-of-Experts flavor — expert parallelism over the mesh.

Expert parallelism is absent from the reference snapshot (SURVEY.md §2.4
lists EP/MoE as not present in v0.3.2); this model fills that modern slot
the way DeepSpeed-MoE later does — alternating dense/MoE transformer
blocks, top-1/2 token routing with capacity, experts sharded over the
data-parallel group (ep ⊆ dp) — but as placement on one compiled program
rather than explicit expert process groups: the expert dim of the stacked
MoE weights carries ``P('data', ...)`` (see moe/layer.py) and the
dispatch/combine all_to_alls are inserted by GSPMD.

The per-layer loop is heterogeneous (dense and MoE blocks alternate), so
blocks run unrolled by default; ``scan_groups=True`` instead scans over
homogeneous groups of ``moe_layer_freq`` blocks (freq-1 dense + 1 MoE) —
one compiled group body, compile time O(1) in depth, bit-identical math
and RNG streams to the unrolled path.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..moe.layer import MoEConfig, init_moe_params, moe_ffn, moe_param_specs
from ..ops.dropout import dropout
from ..parallel.mesh import MODEL_AXIS
from ..runtime.activation_checkpointing.block_remat import checkpoint_block
from ..runtime.module import TrainModule, mark_subtrees
from ..runtime.zero import gather_layer
from .gpt2 import (GPT2Config, _layer_norm, gpt2_attn_sublayer,
                   gpt2_ffn)


@dataclasses.dataclass(frozen=True)
class GPT2MoEConfig(GPT2Config):
    n_experts: int = 8
    moe_top_k: int = 1
    moe_layer_freq: int = 2           # every freq-th block is MoE
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    aux_loss_weight: float = 1e-2
    router_z_loss_weight: float = 0.0
    router_jitter: float = 0.0
    moe_dispatch_impl: str = "einsum"  # see MoEConfig.dispatch_impl
    # the dense/MoE block alternation makes the per-LAYER loop
    # heterogeneous, so GPT2Config's scan_layers is not supported; the
    # depth-scalable equivalent is scan_groups: lax.scan over homogeneous
    # groups of moe_layer_freq blocks (freq-1 dense + 1 MoE) — one
    # compiled group body, compile time O(1) in depth
    scan_layers: bool = False
    scan_groups: bool = False
    stream_scan: bool = False        # fetch ONE group's params per scan
                                     # tick (requires scan_groups) — pair
                                     # with zero_optimization.
                                     # param_streaming so device param
                                     # bytes ~ one group

    def __post_init__(self):
        if self.scan_layers:
            raise ValueError(
                "GPT2MoEModel always unrolls its heterogeneous layer "
                "loop; scan_layers=True is not supported")
        if self.stream_scan and not self.scan_groups:
            raise ValueError(
                "stream_scan requires scan_groups=True (the streaming "
                "fetch rides the group scan)")
        if self.moe_layer_freq < 1:
            raise ValueError(
                f"moe_layer_freq must be >= 1, got {self.moe_layer_freq}")
        if not any(self.is_moe_layer(i) for i in range(self.n_layer)):
            raise ValueError(
                f"GPT2MoEConfig with n_layer={self.n_layer}, "
                f"moe_layer_freq={self.moe_layer_freq} yields zero MoE "
                "layers — use GPT2Config/GPT2Model for a dense model")
        if self.scan_groups:
            if self.n_layer % self.moe_layer_freq != 0:
                raise ValueError(
                    f"scan_groups needs n_layer ({self.n_layer}) divisible "
                    f"by moe_layer_freq ({self.moe_layer_freq}) — the scan "
                    "body is one homogeneous group")
            # the scan body hardcodes MoE-last-in-group; bind that to
            # is_moe_layer so an overridden placement cannot silently
            # diverge from the unrolled path
            freq = self.moe_layer_freq
            expect = [g * freq + freq - 1
                      for g in range(self.n_layer // freq)]
            if self.moe_layers != expect:
                raise ValueError(
                    f"scan_groups assumes MoE on the last block of each "
                    f"group (layers {expect}), but is_moe_layer yields "
                    f"{self.moe_layers} — use the unrolled path")
        self.moe_cfg()  # validate the routing knobs at config time

    def moe_cfg(self) -> MoEConfig:
        return MoEConfig(
            n_experts=self.n_experts, d_model=self.d_model,
            d_ff=4 * self.d_model, top_k=self.moe_top_k,
            capacity_factor=self.capacity_factor,
            eval_capacity_factor=self.eval_capacity_factor,
            aux_loss_weight=self.aux_loss_weight,
            z_loss_weight=self.router_z_loss_weight,
            router_jitter=self.router_jitter,
            dispatch_impl=self.moe_dispatch_impl)

    def is_moe_layer(self, i: int) -> bool:
        # MoE on the last block of each freq-group (layer 1, 3, ... for
        # freq=2) — DeepSpeed-MoE's alternating placement.
        return (i % self.moe_layer_freq) == self.moe_layer_freq - 1

    @property
    def moe_layers(self):
        return [i for i in range(self.n_layer) if self.is_moe_layer(i)]

    @property
    def num_params(self) -> int:
        """Accurate MoE count (overrides the dense formula): each MoE
        block swaps the dense FFN for E experts plus the router."""
        d, L, E = self.d_model, self.n_layer, self.n_experts
        n_moe = len(self.moe_layers)
        attn_per_block = (4 * d            # ln1/ln2 scales+biases
                          + d * 3 * d + 3 * d
                          + d * d + d)
        dense_ffn = d * 4 * d + 4 * d + 4 * d * d + d
        moe_ffn_params = d * E + E * (d * 4 * d + 4 * d
                                      + 4 * d * d + d)
        return (self.vocab_size * d + self.n_positions * d + 2 * d
                + L * attn_per_block
                + (L - n_moe) * dense_ffn + n_moe * moe_ffn_params)


class GPT2MoEModel(TrainModule):
    """Causal LM where alternate blocks use a top-k routed expert FFN."""

    def __init__(self, config: GPT2MoEConfig):
        self.config = config

    # ---------------- init ----------------
    def init(self, rng) -> Dict[str, Any]:
        cfg = self.config
        d, L = cfg.d_model, cfg.n_layer
        keys = jax.random.split(rng, 8)
        std = 0.02
        resid_std = std / jnp.sqrt(2.0 * L)

        def norm(key, shape, s=std):
            return jax.random.normal(key, shape, jnp.float32) * s

        # attention sublayer params for ALL blocks, stacked [L, ...]
        attn = {
            "ln1_scale": jnp.ones((L, d), jnp.float32),
            "ln1_bias": jnp.zeros((L, d), jnp.float32),
            "qkv_w": norm(keys[2], (L, d, 3, d)),
            "qkv_b": jnp.zeros((L, 3, d), jnp.float32),
            "out_w": norm(keys[3], (L, d, d), resid_std),
            "out_b": jnp.zeros((L, d), jnp.float32),
            "ln2_scale": jnp.ones((L, d), jnp.float32),
            "ln2_bias": jnp.zeros((L, d), jnp.float32),
        }
        # dense FFN params for the non-MoE blocks, stacked [L_dense, ...]
        Ld = L - len(cfg.moe_layers)
        dense = {
            "fc_w": norm(keys[4], (Ld, d, 4 * d)),
            "fc_b": jnp.zeros((Ld, 4 * d), jnp.float32),
            "proj_w": norm(keys[5], (Ld, 4 * d, d), resid_std),
            "proj_b": jnp.zeros((Ld, d), jnp.float32),
        }
        # MoE params stacked over the MoE layers [L_moe, E, ...]
        # (__post_init__ guarantees at least one MoE layer)
        mcfg = cfg.moe_cfg()
        mkeys = jax.random.split(keys[6], len(cfg.moe_layers))
        moe_leaves = [init_moe_params(k, mcfg, std=std, out_std=resid_std)
                      for k in mkeys]
        moe = jax.tree.map(lambda *ls: jnp.stack(ls), *moe_leaves)
        return {
            "wte": norm(keys[0], (cfg.vocab_size, d)),
            "wpe": norm(keys[1], (cfg.n_positions, d)),
            "ln_f_scale": jnp.ones((d,), jnp.float32),
            "ln_f_bias": jnp.zeros((d,), jnp.float32),
            "attn": attn,
            "dense_ffn": dense,
            "moe": moe,
        }

    # ---------------- EP/TP declaration ----------------
    def param_partition_specs(self, params) -> Dict[str, Any]:
        m = MODEL_AXIS
        return {
            "wte": P(m, None),
            "wpe": P(),
            "ln_f_scale": P(),
            "ln_f_bias": P(),
            "attn": {
                "ln1_scale": P(), "ln1_bias": P(),
                "qkv_w": P(None, None, None, m),
                "qkv_b": P(None, None, m),
                "out_w": P(None, m, None),
                "out_b": P(),
                "ln2_scale": P(), "ln2_bias": P(),
            },
            "dense_ffn": {
                "fc_w": P(None, None, m),
                "fc_b": P(None, m),
                "proj_w": P(None, m, None),
                "proj_b": P(),
            },
            "moe": moe_param_specs(tp_axis=m, stacked=True),
        }

    # ---------------- forward ----------------
    def apply(self, params, tokens: jnp.ndarray, rng, train: bool = True):
        """tokens [B, T] → (logits [B, T, vocab], total weighted aux)."""
        cfg = self.config
        B, T = tokens.shape
        if T > cfg.n_positions:
            raise ValueError(
                f"sequence length {T} exceeds n_positions={cfg.n_positions}")
        x = params["wte"][tokens] + params["wpe"][:T][None]
        x = dropout(x, cfg.embd_dropout if train else 0.0,
                    jax.random.fold_in(rng, 997))

        mcfg = cfg.moe_cfg()
        drop = cfg.dropout if train else 0.0

        def dense_block(x, ap, dp, lrng):
            r_attn, r_ffn = jax.random.split(lrng)
            x = gpt2_attn_sublayer(cfg, ap, x, r_attn, train)
            h = _layer_norm(x, ap["ln2_scale"], ap["ln2_bias"])
            y = gpt2_ffn(dp, h)
            return x + dropout(y, drop, jax.random.fold_in(r_ffn, 1))

        def moe_block(x, ap, mp, lrng):
            r_attn, r_ffn = jax.random.split(lrng)
            x = gpt2_attn_sublayer(cfg, ap, x, r_attn, train)
            h = _layer_norm(x, ap["ln2_scale"], ap["ln2_bias"])
            y, aux = moe_ffn(mcfg, mp, h, r_ffn, train)
            return x + dropout(y, drop, jax.random.fold_in(r_ffn, 1)), aux

        # remat='block': ONE choice for every block of whether it keeps
        # the flash kernel's results besides its input, from the engine's
        # memory budget (block_remat.py): groups of freq flash calls, the
        # expert FFN's working set counted as a dense FFN's
        freq = cfg.moe_layer_freq
        remat = checkpoint_block(
            x, trips=-(-cfg.n_layer // freq), heads=cfg.n_head,
            ffn_width=4 * cfg.d_model, head_width=cfg.vocab_size,
            attn_sites=freq * int(cfg.attn_impl == "flash"), ffn_sites=freq,
        ) if cfg.remat == "block" else (lambda f: f)

        aux0 = jnp.zeros((), jnp.float32)
        if cfg.scan_groups and cfg.stream_scan:
            # Param-streaming form of the group scan: the stacks stay
            # scan CONSTANTS (host-resident under zero_optimization.
            # param_streaming) and the body fetches group g's rows with
            # an explicit transfer to device memory — inside the remat'd
            # body, so the backward re-fetches instead of keeping the
            # stacks alive (see GPT2Model's streaming scan for the
            # dense-model form).
            from .gpt2 import stream_fetch
            freq = cfg.moe_layer_freq
            G = cfg.n_layer // freq
            specs = self.param_partition_specs(params)

            def group_body(carry, g):
                x, aux = carry
                ag = stream_fetch(params["attn"], specs["attn"],
                                  g * freq, rows=freq)
                dg = stream_fetch(params["dense_ffn"], specs["dense_ffn"],
                                  g * (freq - 1), rows=freq - 1)
                mg = stream_fetch(params["moe"], specs["moe"], g)
                for j in range(freq - 1):
                    apj = jax.tree.map(lambda a, j=j: a[j], ag)
                    dpj = jax.tree.map(lambda a, j=j: a[j], dg)
                    x = dense_block(
                        x, apj, dpj, jax.random.fold_in(rng, g * freq + j))
                apm = jax.tree.map(lambda a: a[freq - 1], ag)
                x, a = moe_block(
                    x, apm, mg,
                    jax.random.fold_in(rng, g * freq + freq - 1))
                return (x, aux + a), None

            (x, aux_total), _ = jax.lax.scan(
                remat(group_body), (x, aux0), jnp.arange(G))
        elif cfg.scan_groups:
            # One compiled group body regardless of depth: the layer loop
            # scans over groups of ``freq`` blocks (freq-1 dense + 1 MoE,
            # the fixed pattern is_moe_layer defines), with the stored
            # [L, ...] / [L_dense, ...] stacks reshaped to per-group
            # leading dims.  Same math and RNG streams as the unrolled
            # path (layer i = g*freq + j keys identically); remat='block'
            # checkpoints the whole group.
            freq = cfg.moe_layer_freq
            G = cfg.n_layer // freq

            def regroup(tree_, sub):
                return jax.tree.map(
                    lambda a: a.reshape((G, sub) + a.shape[1:]), tree_)

            attn_g = regroup(params["attn"], freq)
            dense_g = regroup(params["dense_ffn"], freq - 1)
            specs = self.param_partition_specs(params)

            def group_body(carry, xs):
                x, aux = carry
                ag, dg, mg, g = xs
                # under ZeRO the group's rows are gathered here, inside
                # the remat'd body (runtime/zero.py::gather_layer)
                ag = gather_layer(ag, specs["attn"], keep_leading=True)
                dg = gather_layer(dg, specs["dense_ffn"], keep_leading=True)
                mg = gather_layer(mg, specs["moe"])
                for j in range(freq - 1):
                    apj = jax.tree.map(lambda a, j=j: a[j], ag)
                    dpj = jax.tree.map(lambda a, j=j: a[j], dg)
                    x = dense_block(
                        x, apj, dpj, jax.random.fold_in(rng, g * freq + j))
                apm = jax.tree.map(lambda a: a[freq - 1], ag)
                x, a = moe_block(
                    x, apm, mg,
                    jax.random.fold_in(rng, g * freq + freq - 1))
                return (x, aux + a), None

            (x, aux_total), _ = jax.lax.scan(
                remat(group_body), (x, aux0),
                (attn_g, dense_g, params["moe"], jnp.arange(G)))
        else:
            dense_block, moe_block = remat(dense_block), remat(moe_block)
            aux_total = aux0
            d_idx = m_idx = 0
            for i in range(cfg.n_layer):
                lrng = jax.random.fold_in(rng, i)
                ap = jax.tree.map(lambda a, i=i: a[i], params["attn"])
                if cfg.is_moe_layer(i):
                    mp = jax.tree.map(
                        lambda a, j=m_idx: a[j], params["moe"])
                    x, aux = moe_block(x, ap, mp, lrng)
                    aux_total = aux_total + aux
                    m_idx += 1
                else:
                    dp = jax.tree.map(
                        lambda a, j=d_idx: a[j], params["dense_ffn"])
                    x = dense_block(x, ap, dp, lrng)
                    d_idx += 1

        x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
        logits = x @ params["wte"].astype(x.dtype).T
        return logits, aux_total

    def stacked_param_spec(self, params):
        """The attn/dense-FFN/MoE leaves are stacked over layers and the
        group scan takes ``freq`` (``freq - 1``, one) rows of them a
        tick; embeddings/final LN are not."""
        if not self.config.scan_groups:
            return None
        return mark_subtrees(params, {"attn", "dense_ffn", "moe"})

    def streaming_param_spec(self, params):
        """The stacked attn/dense-FFN/MoE leaves stream (one group per
        scan tick); embeddings/final LN stay device-resident.  Requires
        the group-scan form with explicit per-group fetch
        (``stream_scan``)."""
        if not self.config.stream_scan:
            return None
        return self.stacked_param_spec(params)

    def loss_fn(self, params, batch, rng, train: bool = True):
        tokens = batch["input_ids"] if isinstance(batch, dict) else batch
        logits, aux = self.apply(params, tokens[:, :-1], rng, train)
        targets = tokens[:, 1:]
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return jnp.mean(nll) + aux
