"""Mixture-of-Experts FFN with expert parallelism over the ``data`` axis.

The reference (DeepSpeed v0.3.2) predates DeepSpeed-MoE — SURVEY.md §2.4
records expert parallelism as absent — so, like sequence parallelism
(parallel/sequence.py), this fills the modern feature slot the way the
framework's later versions do, designed TPU-first rather than ported:

  - routing, dispatch, and combine are dense one-hot einsums (the GShard
    formulation): no scatter/gather, no dynamic shapes — every op tiles
    onto the MXU and the dispatch/combine "communication" lowers to XLA
    all_to_alls when the expert dim is sharded;
  - expert parallelism is a *placement decision*, exactly like ZeRO and
    Megatron TP elsewhere in this codebase: expert-stacked weights
    ``[E, d, f]`` declare ``P('data', ...)`` on the expert dim
    (``moe_param_specs``) and GSPMD partitions the expert compute over the
    data-parallel group — the same ep⊆dp mapping DeepSpeed-MoE uses for
    its expert groups;
  - expert weights can ALSO shard their feature dim over ``model``
    (column/row-parallel experts), composing EP × TP in one spec;
  - capacity is static (``ceil(top_k · cf · tokens / E)``): overflow
    tokens are dropped (their combine weight is zero) and flow through
    the residual connection, the standard Switch/GShard contract.

Gating runs in fp32 regardless of compute dtype; the auxiliary
load-balancing loss (Switch: ``E · Σ_e fraction_routed_e · mean_prob_e``)
and the router z-loss are returned for the model to fold into its total
loss.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DATA_AXIS, MODEL_AXIS


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    d_model: int
    d_ff: int
    top_k: int = 1                    # 1 = Switch, 2 = GShard
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    aux_loss_weight: float = 1e-2
    z_loss_weight: float = 0.0
    router_jitter: float = 0.0        # multiplicative input noise, train only
    dispatch_impl: str = "einsum"     # "einsum" (one-hot, MXU) | "scatter"

    def __post_init__(self):
        if self.top_k not in (1, 2):
            # of the capacity dispatch only: moe/dropless.py routes any k
            raise ValueError(
                f"top_k must be 1 or 2 on the capacity paths (einsum | "
                f"scatter), got {self.top_k}; dropless top-k is "
                "moe/dropless.py")
        if self.dispatch_impl not in ("einsum", "scatter"):
            raise ValueError(
                f"dispatch_impl must be 'einsum' or 'scatter', got "
                f"{self.dispatch_impl!r}")
        if self.n_experts < 1:
            raise ValueError(f"n_experts must be >= 1, got {self.n_experts}")
        if self.n_experts < self.top_k:
            # top_k > n_experts would double-assign tokens to expert 0 with
            # half gates (probs2 is all-zero after masking, argmax re-picks
            # 0) — a silent half-weighting, not a meaningful routing.
            raise ValueError(
                f"n_experts ({self.n_experts}) must be >= top_k "
                f"({self.top_k})")

    def capacity(self, tokens_per_group: int, train: bool) -> int:
        cf = self.capacity_factor if train else self.eval_capacity_factor
        c = math.ceil(self.top_k * cf * tokens_per_group / self.n_experts)
        return max(1, min(tokens_per_group, c))


def init_moe_params(rng, cfg: MoEConfig, std: float = 0.02,
                    out_std: Optional[float] = None) -> Dict[str, Any]:
    """Expert-stacked FFN weights + router. ``out_std`` scales the output
    projection (models pass their residual-scaled std)."""
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    k_g, k_i, k_o = jax.random.split(rng, 3)
    return {
        "wg": jax.random.normal(k_g, (d, E), jnp.float32) * std,
        "wi": jax.random.normal(k_i, (E, d, f), jnp.float32) * std,
        "bi": jnp.zeros((E, f), jnp.float32),
        "wo": jax.random.normal(k_o, (E, f, d), jnp.float32)
        * (std if out_std is None else out_std),
        "bo": jnp.zeros((E, d), jnp.float32),
    }


def moe_param_specs(ep_axis: str = DATA_AXIS,
                    tp_axis: Optional[str] = MODEL_AXIS,
                    stacked: bool = False) -> Dict[str, P]:
    """Placement: expert dim over ``ep_axis`` (expert parallelism), hidden
    feature dim over ``tp_axis`` (column/row-parallel experts).  With
    ``stacked`` the specs gain a leading ``None`` for a layer axis."""
    lead = (None,) if stacked else ()
    tp = tp_axis  # None disables the TP split
    return {
        "wg": P(*lead),                        # tiny; replicate
        "wi": P(*lead, ep_axis, None, tp),     # column parallel
        "bi": P(*lead, ep_axis, tp),
        "wo": P(*lead, ep_axis, tp, None),     # row parallel
        "bo": P(*lead, ep_axis, None),
    }


def _constrain(x, spec: P):
    """Sharding constraint that is a no-op when no mesh context is set
    (pure single-device unit tests) — the engine always runs its step
    under ``jax.set_mesh``, where the constraint binds."""
    mesh = jax.sharding.get_abstract_mesh()
    # Direct attribute access on purpose (mirrors gpt2.py's sp guard): if
    # jax renames manual_axes this must break loudly, not silently start
    # constraining inside manual computations.
    if mesh is None or not mesh.shape or mesh.manual_axes:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _route(probs, top_k: int):
    """Routing choices from fp32 router probs [G,S,E]:
    ``[(idx [G,S], gate [G,S], mask [G,S,E]), ...]`` per choice.  The
    SINGLE source of the gate math for both dispatch implementations —
    GShard top-2 renormalizes the two gates against each other."""
    E = probs.shape[-1]
    idx1 = jnp.argmax(probs, axis=-1)
    mask1 = jax.nn.one_hot(idx1, E, dtype=probs.dtype)
    g1 = jnp.sum(probs * mask1, axis=-1)
    if top_k == 1:
        return [(idx1, g1, mask1)]
    probs2 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, E, dtype=probs.dtype)
    g2 = jnp.sum(probs * mask2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    return [(idx1, g1 / denom, mask1), (idx2, g2 / denom, mask2)]


def _choice_positions(mask, base):
    """Per-(token, expert) arrival position [G,S,E] for one choice's
    one-hot mask; ``base`` [G,1,E] queues this choice behind all earlier
    choices' assignments (GShard order).  -1 at non-selected entries.
    The single source of the queueing math for both dispatch impls."""
    return (jnp.cumsum(mask, axis=1) + base) * mask - 1.0


def _einsum_dispatch(choices, capacity: int):
    """(dispatch [G,S,E,C] {0,1}, combine [G,S,E,C]) from the shared
    routing choices — the dense one-hot formulation (every op tiles onto
    the MXU; no scatter)."""
    dispatch = combine = None
    base = jnp.zeros_like(choices[0][2][:, :1, :])
    for _idx, gate, mask in choices:
        pos = _choice_positions(mask, base)
        base = base + jnp.sum(mask, axis=1, keepdims=True)
        keep = (pos >= 0) & (pos < capacity)
        d = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                           dtype=mask.dtype) * (mask * keep)[..., None]
        c = gate[..., None, None] * d
        dispatch = d if dispatch is None else dispatch + d
        combine = c if combine is None else combine + c
    return dispatch, combine


def _token_slots(mask: jnp.ndarray, base: jnp.ndarray) -> jnp.ndarray:
    """Per-token capacity slot from a one-hot choice mask [G,S,E]: the
    token's position in its expert's arrival order (``base`` [G,1,E]
    offsets second choices behind all first choices, GShard order).
    Returns [G,S] fp32; the slot at the selected expert is >= 0, so a
    max over E extracts it."""
    return jnp.max(_choice_positions(mask, base), axis=-1)


def _scatter_moe(cfg: MoEConfig, mp: Dict[str, Any], x: jnp.ndarray,
                 probs: jnp.ndarray, capacity: int, choices) -> jnp.ndarray:
    """Scatter/gather dispatch: O(S·d) data movement per token instead of
    the one-hot einsum's O(S·C·E·d) = O(S²·cf·k·d) MXU work per group.
    The einsum formulation's dispatch cost is independent of E (capacity
    shrinks as 1/E) but quadratic in tokens-per-group — at long S the
    dispatch einsum rivals the expert FFN itself, which is when this path
    should win (by these counts; no cell measures it).  Slots are unique
    by construction (disjoint per-expert ranges; second choices queue
    behind all first choices), so scatter-add never actually collides."""
    G, S, d = x.shape
    E, C = cfg.n_experts, capacity
    dt = x.dtype
    base = jnp.zeros((G, 1, E), probs.dtype)
    slots = []
    for (idx, gate, mask) in choices:
        pos = _token_slots(mask, base)                       # [G,S]
        base = base + jnp.sum(mask, axis=1, keepdims=True)
        keep = pos < C
        slot = idx * C + jnp.minimum(pos, C - 1.0).astype(jnp.int32)
        slots.append((slot, keep, gate))

    group_off = (jnp.arange(G, dtype=jnp.int32) * (E * C))[:, None]
    xf = x.reshape(G * S, d)
    buf = jnp.zeros((G * E * C, d), dt)
    for slot, keep, _gate in slots:
        flat = (slot + group_off).reshape(-1)
        buf = buf.at[flat].add(xf * keep.reshape(-1, 1).astype(dt))

    ein = buf.reshape(G, E, C, d).transpose(1, 0, 2, 3)      # [E,G,C,d]
    ein = _constrain(ein, P(DATA_AXIS, None, None, None))
    h = jnp.einsum("egcd,edf->egcf", ein, mp["wi"].astype(dt))
    h = h + mp["bi"].astype(dt)[:, None, None, :]
    h = jax.nn.gelu(h, approximate=True)
    eo = jnp.einsum("egcf,efd->egcd", h, mp["wo"].astype(dt))
    eo = eo + mp["bo"].astype(dt)[:, None, None, :]
    eo = _constrain(eo, P(DATA_AXIS, None, None, None))
    eo_g = eo.transpose(1, 0, 2, 3).reshape(G, E * C, d)     # [G,E*C,d]
    y = jnp.zeros_like(x)
    for slot, keep, gate in slots:
        picked = jnp.take_along_axis(eo_g, slot[..., None], axis=1)
        y = y + picked * (gate * keep).astype(dt)[..., None]
    return _constrain(y, P(DATA_AXIS, None, None))


def moe_ffn(cfg: MoEConfig, mp: Dict[str, Any], x: jnp.ndarray, rng,
            train: bool = True) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x [G, S, d] → (y [G, S, d], weighted aux-loss scalar fp32).

    Dropped (over-capacity) tokens produce y=0 at their positions; the
    caller's residual connection carries them through unchanged.
    """
    G, S, d = x.shape
    E = cfg.n_experts
    C = cfg.capacity(S, train)
    x_gate = x.astype(jnp.float32)
    if train and cfg.router_jitter > 0.0:
        eps = cfg.router_jitter
        x_gate = x_gate * jax.random.uniform(
            jax.random.fold_in(rng, 11), x_gate.shape, jnp.float32,
            1.0 - eps, 1.0 + eps)
    logits = x_gate @ mp["wg"]                                # [G,S,E] fp32
    probs = jax.nn.softmax(logits, axis=-1)

    choices = _route(probs, cfg.top_k)
    mask1 = choices[0][2]
    if cfg.dispatch_impl == "einsum":
        dispatch, combine = _einsum_dispatch(choices, C)

    # Switch load-balance loss: E · Σ_e (fraction of tokens routed to e) ·
    # (mean router prob of e); 1.0 at perfect balance.  The returned term
    # is already weighted — the caller just adds it to its loss.
    density = jnp.mean(mask1, axis=(0, 1))
    density_proxy = jnp.mean(probs, axis=(0, 1))
    aux = cfg.aux_loss_weight * E * jnp.sum(density * density_proxy)
    if cfg.z_loss_weight > 0.0:
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        aux = aux + cfg.z_loss_weight * jnp.mean(z * z)

    if cfg.dispatch_impl == "scatter":
        return _scatter_moe(cfg, mp, x, probs, C, choices), aux

    dt = x.dtype
    dispatch = dispatch.astype(dt)
    combine = combine.astype(dt)
    # dispatch: tokens → per-expert capacity slots.  With the expert dim
    # sharded over ``data`` and the batch dim likewise, GSPMD lowers the
    # resharding below to an all_to_all over the data axis — the dispatch
    # communication DeepSpeed-MoE issues explicitly.
    ein = jnp.einsum("gsec,gsd->egcd", dispatch, x)
    ein = _constrain(ein, P(DATA_AXIS, None, None, None))
    h = jnp.einsum("egcd,edf->egcf", ein, mp["wi"].astype(dt))
    h = h + mp["bi"].astype(dt)[:, None, None, :]
    h = jax.nn.gelu(h, approximate=True)
    eo = jnp.einsum("egcf,efd->egcd", h, mp["wo"].astype(dt))
    eo = eo + mp["bo"].astype(dt)[:, None, None, :]
    eo = _constrain(eo, P(DATA_AXIS, None, None, None))
    y = jnp.einsum("gsec,egcd->gsd", combine, eo)             # combine a2a
    y = _constrain(y, P(DATA_AXIS, None, None))
    return y, aux
