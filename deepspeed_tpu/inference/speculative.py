"""Speculative decoding: token selection + draft-verify acceptance.

The device-side math of the draft-verify loop (docs/serving.md,
"speculative decoding"; Leviathan et al. 2023, Chen et al. 2023,
PAPERS.md).  A small DRAFT model proposes ``k`` tokens per serving
tick; the target model scores all ``k+1`` positions (the slot's
pending token + the proposals) in ONE widened ``verify_step`` program,
and this module decides — inside that same compiled program — how many
proposals survive and which tokens the tick actually emits.

Two acceptance arms, dispatched STATICALLY on the engine's
``serving.temperature`` (a python float — the arm never changes for
the life of a compiled program, so the zero-recompile contract of
docs/serving.md is untouched):

* ``temperature == 0`` — greedy: proposal ``i`` survives iff it equals
  the target's argmax at the previous position; the emitted tokens are
  exactly the target argmaxes over the accepted prefix plus one BONUS
  token (the target's own continuation after the last accepted
  proposal).  The emitted stream is therefore the non-speculative
  greedy stream, token for token — the parity bar of
  tests/test_spec_decode.py.
* ``temperature > 0`` — the rejection-sampling rule of Chen et al.
  2023: accept proposal ``x`` with probability ``min(1, p(x)/q(x))``
  (``p`` target, ``q`` draft), resample the first rejection from the
  residual ``max(p - q, 0)`` (renormalized), and sample the bonus from
  ``p`` when everything was accepted.  The emitted tokens are then
  EXACTLY distributed as ordinary ancestral sampling from the target —
  the distribution-recovery guarantee the unit tests check empirically.

Everything here is shape-static (``k`` is baked into the program) and
pure jnp — callable from inside the engine's jitted verify program and
directly from tests.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@jax.named_scope("sample")
def select_next_token(logits: jnp.ndarray, temperature: float = 0.0,
                      rng=None) -> jnp.ndarray:
    """The one next-token rule every serving program shares (the four
    prefill/decode emission sites of inference/engine.py land here).

    ``temperature`` is a STATIC python float: 0 is greedy — bitwise the
    ``jnp.argmax`` the pre-speculation engine inlined (pinned by
    tests/test_spec_decode.py) — and > 0 samples
    ``softmax(logits / temperature)`` via ``jax.random.categorical``
    (which needs ``rng``).  Works on any ``[..., vocab]`` logits."""
    if temperature and temperature > 0.0:
        if rng is None:
            raise ValueError(
                "select_next_token with temperature > 0 needs an rng key")
        return jax.random.categorical(
            rng, logits.astype(jnp.float32) / temperature,
            axis=-1).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def greedy_accept(target_logits: jnp.ndarray,
                  draft_tokens: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Greedy draft-verify acceptance.

    target_logits [S, W, V] — the verify program's logits; row ``i``
    scores the token AFTER the tick's ``i``-th input token (the pending
    token, then the ``k = W-1`` proposals).  draft_tokens [S, k].

    Returns ``(out_tokens [S, W] int32, accepted [S] int32)``:
    ``accepted[s] = m`` is the length of the longest proposal prefix
    matching the target argmaxes, and ``out_tokens[s, :m+1]`` are the
    tokens the tick emits — the accepted proposals ARE the argmaxes of
    rows ``0..m-1``, and row ``m`` is the bonus token, so the emitted
    block is uniformly ``argmax(target_logits)[:m+1]``.  Entries past
    ``m`` are the target's hypothetical continuation and must be
    ignored by the caller."""
    g = jnp.argmax(target_logits, axis=-1).astype(jnp.int32)  # [S, W]
    k = draft_tokens.shape[1]
    ok = draft_tokens.astype(jnp.int32) == g[:, :k]           # [S, k]
    keep = jnp.cumprod(ok.astype(jnp.int32), axis=1)
    return g, jnp.sum(keep, axis=1).astype(jnp.int32)


def rejection_sample_accept(target_logits: jnp.ndarray,
                            draft_tokens: jnp.ndarray,
                            draft_probs: jnp.ndarray,
                            temperature: float,
                            rng) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Speculative SAMPLING acceptance (Chen et al. 2023, PAPERS.md).

    target_logits [S, W, V]; draft_tokens [S, k]; draft_probs [S, k, V]
    — the full proposal distributions ``q_i`` the draft sampled from
    (the residual needs all of ``q``, not just ``q(x)``).

    Per position ``i``: accept ``x = draft_tokens[:, i]`` with
    probability ``min(1, p_i(x) / q_i(x))`` (realized as
    ``u * q_i(x) <= p_i(x)``, division-free); the first rejection
    resamples from ``normalize(max(p_i - q_i, 0))`` (falling back to
    ``p_i`` when the residual is identically zero, i.e. p == q); full
    acceptance samples the bonus from ``p_k``.  Output tokens are then
    exactly target-distributed — the Leviathan/Chen guarantee.

    Returns ``(out_tokens [S, W] int32, accepted [S] int32)`` with the
    same contract as :func:`greedy_accept`: the tick emits
    ``out_tokens[s, :accepted[s] + 1]``."""
    S, W, V = target_logits.shape
    k = W - 1
    t = float(temperature)
    p = jax.nn.softmax(target_logits.astype(jnp.float32) / t, axis=-1)
    q = draft_probs.astype(jnp.float32)                       # [S, k, V]
    d = draft_tokens.astype(jnp.int32)                        # [S, k]
    s_idx = jnp.arange(S)[:, None]
    i_idx = jnp.arange(k)[None, :]
    p_d = p[:, :k][s_idx, i_idx, d]                           # p_i(d_i)
    q_d = q[s_idx, i_idx, d]
    k_u, k_r = jax.random.split(rng)
    u = jax.random.uniform(k_u, (S, k), jnp.float32)
    ok = u * q_d <= p_d                                       # [S, k]
    keep = jnp.cumprod(ok.astype(jnp.int32), axis=1)
    accepted = jnp.sum(keep, axis=1).astype(jnp.int32)        # [S]
    # the replacement token for every possible stop position at once:
    # positions 0..k-1 resample the residual, position k (full
    # acceptance) samples the bonus from p_k — one categorical per row
    resid = jnp.maximum(p[:, :k] - q, 0.0)
    rsum = jnp.sum(resid, axis=-1, keepdims=True)
    resid = jnp.where(rsum > 0.0, resid / jnp.where(rsum > 0.0, rsum, 1.0),
                      p[:, :k])
    repl_dist = jnp.concatenate([resid, p[:, k:]], axis=1)    # [S, W, V]
    # log of exact zeros -> -inf is the correct "never pick this" mask
    repl = jax.random.categorical(
        k_r, jnp.log(repl_dist), axis=-1).astype(jnp.int32)   # [S, W]
    out = jnp.concatenate([d, repl[:, k:k + 1]], axis=1)      # [S, W]
    out = out.at[jnp.arange(S), accepted].set(
        repl[jnp.arange(S), accepted])
    return out, accepted


def speculative_accept(target_logits: jnp.ndarray,
                       draft_tokens: jnp.ndarray,
                       draft_probs: Optional[jnp.ndarray],
                       temperature: float,
                       rng=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static dispatch between the two acceptance arms — greedy at
    ``temperature == 0`` (``draft_probs``/``rng`` unused), rejection
    sampling otherwise.  ``temperature`` is a python float, so the
    branch is resolved at trace time: one arm per compiled program."""
    if temperature and temperature > 0.0:
        if draft_probs is None or rng is None:
            raise ValueError(
                "speculative_accept with temperature > 0 needs the "
                "draft's proposal distributions and an rng key")
        return rejection_sample_accept(target_logits, draft_tokens,
                                       draft_probs, temperature, rng)
    return greedy_accept(target_logits, draft_tokens)
