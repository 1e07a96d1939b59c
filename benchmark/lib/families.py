"""One builder per model family: from a configuration file to the program's
model object, its training batches, its plain-reference loss and its
operation count.  A configuration names its family; a family the harness
does not know fails with the name of the builder to add (a new file beside
this one, registered through ``configs/<name>.json``'s ``family_module``).
"""
from __future__ import annotations

import importlib

import numpy as np

import jax

from . import reference, yardstick
from .traffic import zipf_tokens


def _published(cfg_file: dict, rehearse: bool) -> dict:
    """The configuration's sizes; with --rehearse, its toy overrides."""
    m = {k: v for k, v in cfg_file.items()
         if isinstance(v, (int, float)) and not isinstance(v, bool)}
    if rehearse:
        m.update(cfg_file["rehearse"]["sizes"])
    return m


def _chunked(rows: int, chunk: int):
    chunk = min(chunk, rows)
    if rows % chunk:
        raise ValueError(f"reference chunk {chunk} does not divide {rows}")
    return [(i, i + chunk) for i in range(0, rows, chunk)]


class Gpt2:
    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
        self.m = m = _published(cfg_file, rehearse)
        # only what the source fixes; remat, scan_layers, attn_impl and
        # dropout are the program's defaults, so a changed default shows
        self.model = GPT2Model(GPT2Config(
            vocab_size=m["vocab_size"], n_positions=m["n_positions"],
            d_model=m["n_embd"], n_layer=m["n_layer"], n_head=m["n_head"]))
        self.vocab = m["vocab_size"]
        self._logits_fn = None

    def train_flops_per_token(self, seq: int) -> float:
        return yardstick.gpt2_train_flops_per_token(self.m, seq)

    def make_batch(self, rng, rows: int, seq: int, data: dict):
        return zipf_tokens(rng, self.vocab, (rows, seq + 1),
                           data.get("zipf_exponent", 0.0))

    def reference_loss(self, params, batch, chunk: int) -> float:
        fn = jax.jit(lambda p, t: reference.gpt2_loss(p, t, self.m["n_head"]))
        with jax.default_matmul_precision("highest"):
            parts = [float(fn(params, batch[a:b]))
                     for a, b in _chunked(len(batch), chunk)]
        return float(np.mean(parts))

    def reference_logits(self, params, tokens, pad_to: int):
        """float32 logits [len(tokens), V] of one sequence.  The sequence
        is padded to ``pad_to`` so that every call is one program; causal
        attention keeps the padding out of the rows returned."""
        if self._logits_fn is None:
            self._logits_fn = jax.jit(lambda p, t: reference.gpt2_logits(
                p, t, self.m["n_head"])[0])
        padded = np.zeros((1, pad_to), np.int32)
        padded[0, :len(tokens)] = tokens
        with jax.default_matmul_precision("highest"):
            return self._logits_fn(params, padded)[:len(tokens)]


class Bert:
    MASK_ID = 103       # [MASK] in the bert-large-uncased vocabulary

    def __init__(self, cfg_file: dict, rehearse: bool):
        from deepspeed_tpu.models.bert import BertConfig, BertModel
        self.m = m = _published(cfg_file, rehearse)
        self.model = BertModel(BertConfig(
            vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
            num_hidden_layers=m["num_hidden_layers"],
            num_attention_heads=m["num_attention_heads"],
            intermediate_size=m["intermediate_size"],
            max_position_embeddings=m["max_position_embeddings"],
            type_vocab_size=m["type_vocab_size"],
            hidden_dropout_prob=m["hidden_dropout_prob"],
            attention_probs_dropout_prob=m["attention_probs_dropout_prob"],
            initializer_range=m["initializer_range"]))
        self.vocab = m["vocab_size"]

    def train_flops_per_token(self, seq: int) -> float:
        return yardstick.bert_train_flops_per_token(self.m, seq)

    def make_batch(self, rng, rows: int, seq: int, data: dict):
        """MLM + NSP rows as a pre-training loader yields them: 15 % of the
        positions are labelled and their input replaced by [MASK]."""
        ids = zipf_tokens(rng, self.vocab, (rows, seq),
                          data.get("zipf_exponent", 0.0))
        masked = rng.random((rows, seq)) < float(data["mask_rate"])
        mask_id = min(self.MASK_ID, self.vocab - 1)
        return {
            "input_ids": np.where(masked, mask_id, ids).astype(np.int32),
            "masked_lm_labels": np.where(masked, ids, -100).astype(np.int32),
            "next_sentence_label": rng.integers(0, 2, (rows,),
                                                dtype=np.int32),
        }

    def reference_loss(self, params, batch, chunk: int) -> float:
        heads = self.m["num_attention_heads"]
        fn = jax.jit(lambda p, b: reference.bert_loss_parts(p, b, heads))
        rows = len(batch["input_ids"])
        tot = np.zeros(4)
        with jax.default_matmul_precision("highest"):
            for a, b in _chunked(rows, chunk):
                part = {k: v[a:b] for k, v in batch.items()}
                tot += np.array([float(x) for x in fn(params, part)])
        return float(tot[0] / max(tot[1], 1.0) + tot[2] / tot[3])


FAMILIES = {"gpt2": Gpt2, "bert": Bert}


def build(cfg_file: dict, rehearse: bool):
    family = cfg_file["family"]
    if family in FAMILIES:
        return FAMILIES[family](cfg_file, rehearse)
    module = cfg_file.get("family_module")
    if module is None:
        raise NotImplementedError(
            f"no builder for model family {family!r}: add "
            f"benchmark/lib/<file>.py with a class like families.Gpt2 and "
            f"name it in the configuration as \"family_module\": "
            f"\"lib.<file>:<Class>\" (known: {sorted(FAMILIES)})")
    mod, cls = module.split(":")
    return getattr(importlib.import_module(mod), cls)(cfg_file, rehearse)
