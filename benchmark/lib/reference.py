"""Plain references: each family's forward pass in straightforward jax.numpy
and float32, no kernel, no cache, no batching tricks.  They read the
program's parameter tree (the names are the program's) and nothing else of
it.  Parameters arrive in the dtype they are served or trained in; each
layer's weights are raised to float32 as the layer is computed, so a
reference for a 1.5 B model needs one layer of float32 weights at a time.
Callers run these under ``jax.default_matmul_precision("highest")``.

Departures from the published descriptions: none in the mathematics.
GPT-2 (Radford et al. 2019): pre-LN blocks, learned positions, tanh-GELU,
LayerNorm eps 1e-5, tied output embedding.  BERT (Devlin et al. 2018):
post-LN blocks, erf-GELU, LayerNorm eps 1e-12, MLM head (dense + GELU + LN
+ tied decoder + bias) and NSP head on the tanh-pooled first token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def _ln(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _attend(q, k, v, n_head: int, causal: bool):
    """q, k, v [B, T, D] -> [B, T, D], softmax(q k^T / sqrt(dh)) v."""
    B, T, D = q.shape
    dh = D // n_head

    def heads(t):
        return t.reshape(B, T, n_head, dh).transpose(0, 2, 1, 3)

    s = jnp.einsum("bhqd,bhkd->bhqk", heads(q), heads(k)) / jnp.sqrt(F32(dh))
    if causal:
        keep = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(keep[None, None], s, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), heads(v))
    return out.transpose(0, 2, 1, 3).reshape(B, T, D)


# -- GPT-2 --------------------------------------------------------------------

def gpt2_logits(params, tokens, n_head: int):
    """tokens [B, T] -> float32 logits [B, T, V]."""
    T = tokens.shape[1]
    wte = params["wte"].astype(F32)
    x = wte[tokens] + params["wpe"].astype(F32)[:T][None]

    def block(x, bp):
        bp = _f32(bp)
        h = _ln(x, bp["ln1_scale"], bp["ln1_bias"], 1e-5)
        qkv = jnp.einsum("btd,dke->btke", h, bp["qkv_w"]) + bp["qkv_b"]
        a = _attend(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], n_head, True)
        x = x + a @ bp["out_w"] + bp["out_b"]
        h = _ln(x, bp["ln2_scale"], bp["ln2_bias"], 1e-5)
        h = jax.nn.gelu(h @ bp["fc_w"] + bp["fc_b"], approximate=True)
        return x + h @ bp["proj_w"] + bp["proj_b"], None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = _ln(x, params["ln_f_scale"].astype(F32),
            params["ln_f_bias"].astype(F32), 1e-5)
    return x @ wte.T


def gpt2_loss(params, tokens, n_head: int):
    """Mean next-token cross-entropy of tokens [B, T + 1]."""
    logp = jax.nn.log_softmax(gpt2_logits(params, tokens[:, :-1], n_head))
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return jnp.mean(nll)


# -- BERT ---------------------------------------------------------------------

def bert_loss_parts(params, batch, n_head: int):
    """(sum of MLM nll over labelled positions, labelled positions,
    sum of NSP nll, rows) for one chunk of rows, no dropout."""
    ids = batch["input_ids"]
    T = ids.shape[1]
    eps = 1e-12
    p = {k: v for k, v in params.items() if k != "layers"}
    p = _f32(p)
    x = (p["word_embeddings"][ids] + p["position_embeddings"][:T][None]
         + p["token_type_embeddings"][jnp.zeros_like(ids)])
    x = _ln(x, p["emb_ln_scale"], p["emb_ln_bias"], eps)

    def block(x, lp):
        lp = _f32(lp)
        qkv = jnp.einsum("btd,dke->btke", x, lp["attn_qkvw"]) \
            + lp["attn_qkvb"]
        a = _attend(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], n_head, False)
        x = _ln(x + a @ lp["attn_ow"] + lp["attn_ob"],
                lp["attn_nw"], lp["attn_nb"], eps)
        h = jax.nn.gelu(x @ lp["inter_w"] + lp["inter_b"], approximate=False)
        x = _ln(x + h @ lp["output_w"] + lp["output_b"],
                lp["norm_w"], lp["norm_b"], eps)
        return x, None

    x, _ = jax.lax.scan(block, x, params["layers"])
    h = jax.nn.gelu(x @ p["mlm_transform_w"] + p["mlm_transform_b"],
                    approximate=False)
    h = _ln(h, p["mlm_ln_scale"], p["mlm_ln_bias"], eps)
    logp = jax.nn.log_softmax(h @ p["word_embeddings"].T + p["mlm_bias"])
    labels = batch["masked_lm_labels"]
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                               -1)[..., 0]
    mask = (labels >= 0).astype(F32)
    pooled = jnp.tanh(x[:, 0] @ p["pooler_w"] + p["pooler_b"])
    nsp = jax.nn.log_softmax(pooled @ p["nsp_w"] + p["nsp_b"])
    nsp_nll = -jnp.take_along_axis(
        nsp, batch["next_sentence_label"][:, None], -1)
    return (jnp.sum(nll * mask), jnp.sum(mask), jnp.sum(nsp_nll),
            F32(ids.shape[0]))
