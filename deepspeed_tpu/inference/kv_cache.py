"""Slot-based and page-pooled KV caches: the device state of serving.

Two layouts share this file (docs/serving.md):

**Slot cache** (the pre-page reference arm) — one fixed stride per
slot:

    k, v     [L, S, H, T, Dh]   layer-major, slot-batched
    lengths  [S] int32          per-slot LIVE length (0 = free slot)

**Paged cache** (``serving.page_len > 0`` — PagedAttention, PAPERS.md)
— a flat pool of fixed-size pages plus host-owned page tables:

    k, v     [L, P, H, page_len, Dh]   layer-major, page-pooled
    lengths  [S] int32                 per-slot LIVE length

(a model whose values are lanes of its key rows keeps the ``k`` array
alone: ``PagedKVCacheSpec.values_in_keys``).  A slot's KV rows live
wherever its int32 page table (a TRACED operand of the decode program,
never part of any compiled shape) points; page 0
is the reserved scratch page masked writes of inactive slots land on,
so scatter conflicts can only happen between no-op writes.  Short
requests hold ceil(len/page_len) pages instead of a full ``max_seq_len``
stride — the pool, not the slot count, caps concurrency.

The shapes never change for the life of the engine — admission writes a
prefilled request's K/V rows in place, decode appends one row per tick,
eviction is host bookkeeping (page frees / masked stale rows).  That
static-shape contract is what lets ONE compiled decode program serve
arbitrary request mixes.

Sharding rides the existing mesh plumbing (parallel/mesh.py): heads on
the ``model`` axis (the same Megatron split the qkv weights declare, so
each TP shard caches exactly the heads it computes), slots — or the
page pool — on the ``data`` axis (replica-parallel serving — the EP/DP
batch dimension).  ``lengths`` is replicated: every shard runs the same
masking.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS, MODEL_AXIS


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    layers: int
    slots: int
    heads: int
    max_len: int
    head_dim: int
    dtype: Any = jnp.float32

    @property
    def bytes(self) -> int:
        per = jnp.dtype(self.dtype).itemsize
        return (2 * self.layers * self.slots * self.heads * self.max_len
                * self.head_dim * per)


def init_cache(spec: KVCacheSpec) -> Dict[str, jnp.ndarray]:
    """Fresh all-free cache pytree (host zeros; shard with
    :func:`shard_cache` before handing it to compiled programs)."""
    shape = (spec.layers, spec.slots, spec.heads, spec.max_len,
             spec.head_dim)
    return {
        "k": jnp.zeros(shape, spec.dtype),
        "v": jnp.zeros(shape, spec.dtype),
        "lengths": jnp.zeros((spec.slots,), jnp.int32),
    }


def cache_partition_specs() -> Dict[str, P]:
    """PartitionSpecs for the cache pytree: slots on ``data``, heads on
    ``model`` (matching the models' Megatron qkv column split)."""
    kv = P(None, DATA_AXIS, MODEL_AXIS, None, None)
    return {"k": kv, "v": kv, "lengths": P()}


def cache_shardings(mesh: Mesh) -> Dict[str, NamedSharding]:
    return {name: NamedSharding(mesh, spec)
            for name, spec in cache_partition_specs().items()}


def _validate_tp_and_axes(mesh: Mesh, heads: int, what: str) -> None:
    """The checks both cache layouts share: TP-divisible heads and a
    strictly (data, model) mesh — fail at build time with the real
    story, not as a GSPMD sharding error mid-serve."""
    tp = mesh.shape.get(MODEL_AXIS, 1)
    if heads % tp != 0:
        raise ValueError(
            f"model heads={heads} must be divisible by the mesh's "
            f"model axis ({tp}) to TP-shard the {what}")
    for axis in ("pipe", "seq"):
        if mesh.shape.get(axis, 1) != 1:
            raise ValueError(
                f"the serving engine does not shard over the {axis!r} "
                f"axis (mesh has {axis}={mesh.shape[axis]}); serve on a "
                "(data, model) mesh")


def validate_cache_mesh(mesh: Mesh, spec: KVCacheSpec) -> None:
    dp = mesh.shape.get(DATA_AXIS, 1)
    if spec.slots % dp != 0:
        raise ValueError(
            f"serving.slots={spec.slots} must be divisible by the mesh's "
            f"data axis ({dp}): slots are the replica-sharded batch "
            "dimension of the decode program")
    _validate_tp_and_axes(mesh, spec.heads, "KV cache")


# ---------------------------------------------------------------------------
# paged layout (serving.page_len > 0)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PagedKVCacheSpec:
    """The flat page pool: ``pages`` fixed-size pages of ``page_len``
    tokens each (page 0 reserved as the scratch page), referenced by
    per-slot page tables the host owns.  ``heads`` counts the KEY heads:
    under grouped keys (a model config with ``n_kv_head``) fewer than the
    query heads that read them.  ``head_dim`` is the KEYS' width at
    rest; ``v_head_dim`` the values' where it differs (a model config
    with ``d_head_v``; None: the same), so the two pools are
    ``[L, pages, H, page_len, head_dim]`` and ``[..., v_head_dim]``.
    ``values_in_keys`` (a model config that declares it, beside
    ``d_head_v``): a layer caches ONE row a token and its values are the
    first ``v_head_dim`` lanes of that row (latent attention:
    ``[c_kv ; k_rope]`` read by every head), so the pool is the ``"k"``
    array alone and no ``"v"`` array exists; every count of bytes below
    follows.
    ``index_layers`` > 0 (a model config with ``n_index_layer`` and
    ``d_index``: learned sparse attention, ``models/glm_dsa.py``): a
    SECOND paged array ``"index_k"`` ``[index_layers, pages, 1, page_len,
    index_dim]`` of one indexer key a token, on the layers that score,
    under the SAME page ids as the rows: a page is allocated, freed,
    copied, exported and imported as one, and the page table is one.
    What a request keeps beside its pages
    (a ``serving_state`` model's recurrent state, by slot) is not in
    this spec: ``ServeEngine`` allocates it under ``cache["state"]``.

    ``quant`` (serving.quantization.kv='int8', docs/serving.md): the
    pool stores int8 rows (``dtype`` must be int8) plus a fp32 scale
    sidecar ``[L, pages, H, page_len]`` — one scale per stored token
    row per head, quantized at write time (inference/quantize.py).
    ``bytes``/``page_bytes`` include the sidecar: they are the ONE
    source of KV-byte truth the bench budgets and the
    ``serve_kv_bytes`` gauge read."""
    layers: int
    slots: int
    heads: int
    pages: int
    page_len: int
    head_dim: int
    #: table width: pages a slot can reference (ceil(max_len/page_len))
    max_pages: int
    dtype: Any = jnp.float32
    #: int8 rows + per-(page, head, row) fp32 scale sidecar
    quant: bool = False
    #: the values' width where it is not the keys'
    v_head_dim: Optional[int] = None
    #: one pool: the values are the rows' first ``v_head_dim`` lanes
    values_in_keys: bool = False
    #: layers that keep an indexer key a token beside the rows, and its width
    index_layers: int = 0
    index_dim: int = 0

    def __post_init__(self):
        if bool(self.index_layers) != bool(self.index_dim) or (
                self.index_layers and self.quant):
            raise ValueError(
                f"index_layers {self.index_layers} and index_dim "
                f"{self.index_dim} go together, and such a cache has no "
                "int8 arm")
        if self.values_in_keys and (self.quant or not self.v_head_dim
                                    or self.v_head_dim > self.head_dim):
            raise ValueError(
                "values_in_keys: the values are the first v_head_dim "
                f"(got {self.v_head_dim}) of the rows' {self.head_dim} "
                "lanes, and such a pool has no int8 arm")

    @classmethod
    def for_model(cls, mcfg, *, slots: int, pages: int, page_len: int,
                  max_seq_len: int, dtype, quant: bool = False
                  ) -> "PagedKVCacheSpec":
        """The cache a model's config asks for (``dtype``: the master
        dtype the rows rest in; int8 with ``quant``)."""
        # a second paged array under the same page ids: an indexer key a
        # token on the layers that score (learned sparse attention); the
        # model's paged steps take and return it as ``index_pool`` after
        # the pools
        index_layers = int(getattr(mcfg, "n_index_layer", 0))
        return cls(
            layers=mcfg.n_layer, slots=slots,
            # grouped keys: the pool holds the KEY heads
            heads=getattr(mcfg, "n_kv_head", mcfg.n_head),
            pages=pages, page_len=page_len, head_dim=mcfg.d_head,
            max_pages=-(-max_seq_len // page_len),
            dtype=jnp.int8 if quant else dtype, quant=quant,
            # two widths: the values' where they are not the keys'
            v_head_dim=getattr(mcfg, "d_head_v", None),
            # one pool: the values are the first d_head_v lanes of the
            # rows (latent attention), and the cache has no "v"
            values_in_keys=bool(getattr(mcfg, "values_in_keys", False)),
            index_layers=index_layers,
            index_dim=mcfg.d_index if index_layers else 0)

    @property
    def value_dim(self) -> int:
        return self.head_dim if self.v_head_dim is None else self.v_head_dim

    @property
    def pool_names(self):
        """The pool-shaped leaves of the cache, in the fixed order every
        page copy, export and import walks them."""
        names = ("k",) if self.values_in_keys else ("k", "v")
        if self.index_layers:
            names += ("index_k",)
        return names + (("k_scale", "v_scale") if self.quant else ())

    @property
    def row_width(self) -> int:
        """Lanes a token keeps a head a layer, over every pool."""
        return self.head_dim + (0 if self.values_in_keys else self.value_dim)

    @property
    def bytes(self) -> int:
        return self.pages * self.page_bytes

    @property
    def page_bytes(self) -> int:
        """HBM of ONE page across layers and both of k/v (incl. the
        quant scale sidecar rows) — the allocation quantum the bench's
        fixed-byte budget divides by."""
        per = jnp.dtype(self.dtype).itemsize
        n = self.layers * self.heads * self.page_len * self.row_width * per
        if self.quant:
            n += 2 * self.layers * self.heads * self.page_len * 4
        return n + self.index_page_bytes

    @property
    def index_page_bytes(self) -> int:
        """Of ``page_bytes``, the indexer keys' part."""
        return (self.index_layers * self.page_len * self.index_dim
                * jnp.dtype(self.dtype).itemsize)


def init_paged_cache(spec: PagedKVCacheSpec) -> Dict[str, jnp.ndarray]:
    """Fresh all-free paged pool (host zeros; shard with
    :func:`shard_cache` before handing it to compiled programs).
    Quantized pools get all-zero scale sidecars: dequant of a never-
    written row is 0 * scale = exact zero, the same dead-data story as
    the fp pool."""
    shape = (spec.layers, spec.pages, spec.heads, spec.page_len)
    cache = {
        "k": jnp.zeros(shape + (spec.head_dim,), spec.dtype),
        "lengths": jnp.zeros((spec.slots,), jnp.int32),
    }
    if not spec.values_in_keys:
        cache["v"] = jnp.zeros(shape + (spec.value_dim,), spec.dtype)
    if spec.index_layers:
        cache["index_k"] = jnp.zeros(
            (spec.index_layers, spec.pages, 1, spec.page_len,
             spec.index_dim), spec.dtype)
    if spec.quant:
        sshape = (spec.layers, spec.pages, spec.heads, spec.page_len)
        cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
        cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
    return cache


def paged_partition_specs(quant: bool = False,
                          values_in_keys: bool = False,
                          indexed: bool = False) -> Dict[str, P]:
    """Pool pages on ``data``, heads on ``model`` — the page pool is
    the DP-sharded storage dimension the way slots were.  The quant
    scale sidecars shard exactly like their pools (minus the row dim's
    trailing head_dim).  A one-pool spec (``values_in_keys``) has no
    ``"v"``; an ``indexed`` one (``index_layers``) has ``"index_k"``,
    whose one head is never split."""
    kv = P(None, DATA_AXIS, MODEL_AXIS, None, None)
    specs = {"k": kv, "v": kv, "lengths": P()}
    if values_in_keys:
        del specs["v"]
    if indexed:
        specs["index_k"] = P(None, DATA_AXIS, None, None, None)
    if quant:
        sc = P(None, DATA_AXIS, MODEL_AXIS, None)
        specs["k_scale"] = sc
        specs["v_scale"] = sc
    return specs


def paged_cache_shardings(mesh: Mesh, quant: bool = False,
                          values_in_keys: bool = False,
                          indexed: bool = False
                          ) -> Dict[str, NamedSharding]:
    return {name: NamedSharding(mesh, spec) for name, spec in
            paged_partition_specs(quant, values_in_keys, indexed).items()}


def validate_paged_cache_mesh(mesh: Mesh,
                              spec: PagedKVCacheSpec) -> None:
    dp = mesh.shape.get(DATA_AXIS, 1)
    if spec.pages % dp != 0:
        raise ValueError(
            f"serving.pages={spec.pages} must be divisible by the "
            f"mesh's data axis ({dp}): the page pool is the DP-sharded "
            "storage dimension of the decode program")
    _validate_tp_and_axes(mesh, spec.heads, "KV page pool")


def shard_cache(cache: Dict[str, jnp.ndarray], mesh: Mesh,
                shardings: Optional[Dict[str, NamedSharding]] = None,
                ) -> Dict[str, jnp.ndarray]:
    """Place a cache pytree (either layout) onto the mesh with ONE
    batched list-form ``jax.device_put`` for all leaves — the PR 3/4
    ``_assemble``/``_shard_batch`` idiom: one dispatch instead of one
    per leaf (the spy test in tests/test_paged_kv.py pins the count)."""
    if shardings is None:
        shardings = cache_shardings(mesh)
    names = sorted(cache)
    placed = jax.device_put([cache[n] for n in names],
                            [shardings[n] for n in names])
    return dict(zip(names, placed))
