"""GPT-2 as a pipeline-parallel module.

The pipeline flavor of the flagship model: per-layer LayerSpecs instead of
the scan-over-layers stack, so stages can own layer ranges (the analogue of
the reference's GPT2 PipelineModule usage; reference pattern:
deepspeed/runtime/pipe/module.py:85 + DeepSpeedExamples Megatron pipe
models).  The embedding is a TiedLayerSpec and the LM head reads the same
``wte`` through the 3-ary loss head — gradient tying falls out of AD
(replacing the tied-weight allreduce, reference pipe/module.py:405-474).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..parallel.mesh import MODEL_AXIS
from ..pipe.module import LayerSpec, TiedLayerSpec, PipelineModule
from ..ops.dropout import dropout
from .gpt2 import GPT2Config, _layer_norm, gpt2_block_forward


class GPT2EmbeddingPipe:
    def __init__(self, cfg: GPT2Config):
        self.cfg = cfg

    def init(self, rng):
        cfg = self.cfg
        k1, k2 = jax.random.split(rng)
        return {
            "wte": jax.random.normal(
                k1, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02,
            "wpe": jax.random.normal(
                k2, (cfg.n_positions, cfg.d_model), jnp.float32) * 0.02,
        }

    def param_partition_specs(self):
        return {"wte": P(MODEL_AXIS, None), "wpe": P()}

    def apply(self, params, tokens, rng, train: bool = True):
        cfg = self.cfg
        T = tokens.shape[1]
        if T > cfg.n_positions:
            raise ValueError(
                f"sequence length {T} exceeds n_positions={cfg.n_positions}")
        # one-hot contraction, not wte[tokens]: the gather's VJP is a
        # scatter-add into the (possibly vocab-sharded) table, which the
        # SPMD partitioner cannot handle inside the pipeline's
        # manual(pipe)/auto(model) nesting — and the one-hot dot runs on
        # the MXU where the scatter serializes.  ~V/d extra FLOPs on a
        # layer that is <<1% of the model's compute.
        wte = params["wte"]
        onehot = jax.nn.one_hot(tokens, wte.shape[0], dtype=wte.dtype)
        x = onehot @ wte + params["wpe"][:T][None]
        return dropout(x, cfg.embd_dropout if train else 0.0, rng)


class GPT2BlockPipe:
    """One transformer block (same math as GPT2Model._block, unstacked)."""

    def __init__(self, cfg: GPT2Config, layer_idx: int):
        self.cfg = cfg
        self.layer_idx = layer_idx

    def init(self, rng):
        import math
        cfg = self.cfg
        d = cfg.d_model
        ks = jax.random.split(rng, 4)
        std = 0.02
        resid_std = std / math.sqrt(2.0 * cfg.n_layer)
        return {
            "ln1_scale": jnp.ones((d,), jnp.float32),
            "ln1_bias": jnp.zeros((d,), jnp.float32),
            "qkv_w": jax.random.normal(
                ks[0], (d, 3, d), jnp.float32) * std,
            "qkv_b": jnp.zeros((3, d), jnp.float32),
            "out_w": jax.random.normal(ks[1], (d, d), jnp.float32) * resid_std,
            "out_b": jnp.zeros((d,), jnp.float32),
            "ln2_scale": jnp.ones((d,), jnp.float32),
            "ln2_bias": jnp.zeros((d,), jnp.float32),
            "fc_w": jax.random.normal(ks[2], (d, 4 * d), jnp.float32) * std,
            "fc_b": jnp.zeros((4 * d,), jnp.float32),
            "proj_w": jax.random.normal(
                ks[3], (4 * d, d), jnp.float32) * resid_std,
            "proj_b": jnp.zeros((d,), jnp.float32),
        }

    def param_partition_specs(self):
        """Megatron column/row layout (same as GPT2Model's stacked specs,
        minus the layer axis)."""
        m = MODEL_AXIS
        return {
            "ln1_scale": P(), "ln1_bias": P(),
            "qkv_w": P(None, None, m), "qkv_b": P(None, m),
            "out_w": P(m, None), "out_b": P(),
            "ln2_scale": P(), "ln2_bias": P(),
            "fc_w": P(None, m), "fc_b": P(m),
            "proj_w": P(m, None), "proj_b": P(),
        }

    def apply(self, bp, x, rng, train: bool = True):
        return gpt2_block_forward(self.cfg, bp, x, rng, train)


class GPT2FinalNormPipe:
    def __init__(self, cfg: GPT2Config):
        self.cfg = cfg

    def init(self, rng):
        d = self.cfg.d_model
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}

    def apply(self, params, x, rng, train: bool = True):
        return _layer_norm(x, params["scale"], params["bias"])


def gpt2_loss_head(params, hidden, labels):
    """Tied LM head + next-token CE; 3-ary so it can read the tied wte
    (labels are the raw token ids; hidden covers positions [0, T-1))."""
    wte = params["tied"]["embed"]["wte"]
    logits = hidden @ wte.astype(hidden.dtype).T
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    # one-hot contraction, not take_along_axis: its VJP is a dense
    # multiply (XLA fuses the one-hot into a masked reduce), whereas the
    # gather's VJP is a scatter-add — which the SPMD partitioner cannot
    # handle inside the pipeline's manual(pipe)/auto(model) nesting (and
    # scatters onto a vocab-sharded logit cotangent are slow on TPU
    # regardless).
    onehot = jax.nn.one_hot(labels, logp.shape[-1], dtype=logp.dtype)
    nll = -jnp.sum(logp * onehot, axis=-1)
    return jnp.mean(nll)


def build_gpt2_pipe(cfg: GPT2Config, num_stages: int,
                    partition_method: str = "type:GPT2BlockPipe",
                    activation_checkpoint_interval: int = 0
                    ) -> PipelineModule:
    layers = [TiedLayerSpec("embed", GPT2EmbeddingPipe, cfg)]
    layers += [LayerSpec(GPT2BlockPipe, cfg, i) for i in range(cfg.n_layer)]
    layers += [LayerSpec(GPT2FinalNormPipe, cfg)]
    return PipelineModule(
        layers, num_stages=num_stages, loss_fn=gpt2_loss_head,
        partition_method=partition_method,
        activation_checkpoint_interval=activation_checkpoint_interval)


def split_gpt2_batch(tokens):
    """tokens [B, T+1] → (inputs [B, T], labels [B, T]) for the pipeline
    (inputs enter stage 0; labels are consumed by the last-stage loss)."""
    return tokens[:, :-1], tokens[:, 1:]
