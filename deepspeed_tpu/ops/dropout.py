"""Dropout from a counter hash: the keep mask is a pure function of the
site's key and the element's position in the whole array.

``jax.random.bernoulli`` draws threefry2x32 bits an element, about 130
integer operations each; fused into a projection matmul's epilogue they
cost more than the matmul (PERF.md section 6, PR 43).  The mask here is
the scheme the flash kernel's attention dropout already is
(``ops/pallas/flash_attention.py::dropout_keep_mask``): a murmur3
finaliser over a counter, compared with ``round(rate * 2**32)``.  Because
the counter is the element's row-major position in the unsharded array,
the mask is the same on any mesh, a recomputed forward
(``jax.checkpoint``) regenerates the first forward's mask bit for bit,
and nothing is stored.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_GOLDEN = 0x9E3779B9    # 2**32 / phi, odd: position -> a well-spread u32
_SCOPE = "hashed_dropout"   # the select of a site, by name: ``traced_sites``


def fmix32(x):
    """murmur3 finalizer — a cheap, well-mixed u32→u32 bijection (not
    cryptographic; dropout only needs decorrelation)."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def keep_threshold(rate: float):
    """u32 ``t`` with P(hash >= t) = 1 - rate.  round() (not int()
    truncation) so the realized drop probability is unbiased to the
    nearest 2^-32; rates within 2^-32 of 1.0 still saturate at 2^32-1 (a
    keep probability of exactly 0 would need a 33-bit threshold —
    irrelevant at practical dropout rates)."""
    return jnp.uint32(min(round(rate * 2.0 ** 32), 2 ** 32 - 1))


def _key_words(rng):
    """Two u32 scalars that every word of the key has been mixed into
    (threefry keys have two words, other implementations four)."""
    words = jax.random.key_data(rng).astype(jnp.uint32).reshape(-1)
    a, b = jnp.uint32(0x243F6A88), jnp.uint32(0x85A308D3)
    for i in range(words.shape[0]):
        a = fmix32(a ^ words[i])
        b = fmix32((b + words[i]) * jnp.uint32(_GOLDEN))
    return a, b


def keep_mask(rng, rate: float, shape):
    """bool ``shape``: element at row-major position ``n`` of the whole
    array is kept iff ``fmix32((n + a) * odd ^ b) >= round(rate * 2**32)``
    with ``a``, ``b`` mixed from the key's words: one scalar draw a site,
    ~15 integer operations an element.  Positions are counted modulo 2^32
    (an array of more elements repeats its mask 2^32 elements on)."""
    a, b = _key_words(rng)
    n = a
    for axis, stride in enumerate(
            math.prod(shape[i + 1:]) for i in range(len(shape))):
        if shape[axis] > 1:
            n = n + jax.lax.broadcasted_iota(jnp.uint32, shape, axis) \
                * jnp.uint32(stride % 2 ** 32)
    x = fmix32(jnp.broadcast_to(n, shape) * jnp.uint32(_GOLDEN) ^ b)
    return x >= keep_threshold(rate)


def dropout(x, rate: float, rng):
    """Inverted dropout of ``x`` at ``rate`` under the site's key ``rng``.
    ``rate <= 0`` (what every ``train=False`` caller passes) or no key
    returns ``x`` itself, with nothing traced."""
    if rate <= 0.0 or rng is None:
        return x
    keep = keep_mask(rng, rate, x.shape)
    scaled = x.astype(jnp.float32) * (1.0 / (1.0 - rate))
    with jax.named_scope(_SCOPE):
        return jax.lax.select(keep, scaled, jnp.zeros_like(scaled)).astype(
            x.dtype)


def traced_sites(jaxpr) -> int:
    """Dropout sites with a rate above 0 in a traced FORWARD program
    (``jax.make_jaxpr(...)(...).jaxpr``; a gradient's program holds each
    site again in its recomputation and its transpose): a ``lax.scan``
    body's count times the scan's length, every branch of a ``cond``."""
    sites = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "select_n" and str(
                eqn.source_info.name_stack).endswith(_SCOPE):
            sites += 1
        times = eqn.params["length"] if eqn.primitive.name == "scan" else 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            sites += times * traced_sites(inner)
    return sites
