"""Model protocol + the param-spec system (single source of truth).

Every family module builds a nested dict of :class:`PSpec` (shape, logical
axes, initializer). From that one structure we derive:

- ``init_params``   — materialized fp32 arrays (seeded, fan-in scaled),
- ``param_axes``    — a same-structure pytree of logical-axis tuples that the
  partition rule engine maps to mesh ``PartitionSpec``s,
- ``abstract_params`` — ``ShapeDtypeStruct`` stand-ins for the dry-run.

Logical axis vocabulary (see ``repro.parallel.partition`` for mesh mapping):
``vocab, embed, embed_in, heads, kv_heads, head_dim, mlp, experts,
expert_mlp, layers, state, conv, dt_rank, ssm_heads, batch, seq, null``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig

Pytree = Any


@dataclass(frozen=True)
class PSpec:
    """Declarative parameter spec."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "fan_in"  # fan_in | zeros | ones | normal | small
    fan_axis: int = -2    # which axis is fan-in for scaled init

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _materialize(spec: PSpec, key: jax.Array, dtype=jnp.float32) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "normal":
        return jax.random.normal(key, spec.shape, dtype) * 0.02
    if spec.init == "small":
        return jax.random.normal(key, spec.shape, dtype) * 1e-4
    # fan_in: normal(0, 1/sqrt(fan_in)) — fan over all axes except the last
    fan = max(1, math.prod(spec.shape[:-1]) if len(spec.shape) > 1 else spec.shape[0])
    # layer-stacked params: exclude the leading "layers" axis from fan
    if spec.axes and spec.axes[0] == "layers" and len(spec.shape) > 2:
        fan = max(1, math.prod(spec.shape[1:-1]))
    return jax.random.normal(key, spec.shape, dtype) * (fan ** -0.5)


def init_from_specs(specs: Pytree, rng: jax.Array, dtype=jnp.float32) -> Pytree:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, PSpec))
    keys = jax.random.split(rng, len(leaves))
    vals = [_materialize(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def axes_from_specs(specs: Pytree) -> Pytree:
    return jax.tree.map(
        lambda s: s.axes, specs, is_leaf=lambda x: isinstance(x, PSpec)
    )


def abstract_from_specs(specs: Pytree, dtype=jnp.float32) -> Pytree:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs,
        is_leaf=lambda x: isinstance(x, PSpec),
    )


# ---------------------------------------------------------------------------
# The model function bundle
# ---------------------------------------------------------------------------


@dataclass
class ModelFns:
    """Pure-function bundle implementing one architecture.

    All functions are jit-compatible; ``params``/``cache`` are pytrees.
    """

    cfg: ModelConfig

    # structure
    param_specs: Pytree                       # nested dict of PSpec
    cache_specs: Callable[..., Pytree]        # (batch, max_seq) -> dict of PSpec

    # training path: batch -> (scalar loss, aux dict)
    loss: Callable[[Pytree, dict], tuple[jax.Array, dict]]

    # serving path
    prefill: Callable[[Pytree, dict], tuple[jax.Array, Pytree]]
    decode_step: Callable[[Pytree, Pytree, dict], tuple[jax.Array, Pytree]]

    # inputs for each shape kind: returns dict of ShapeDtypeStruct
    input_specs: Callable[[ShapeConfig], dict]

    # paged serving path (optional). Families that support the paged KV
    # cache expose:
    # - paged_cache_specs(n_slots, n_pages, page_size) -> dict of PSpec —
    #   sequence-indexed leaves become shared page pools
    #   (n_pages, page_size, ...); O(1) per-slot state (SSM/conv) keeps its
    #   dense (n_slots, ...) layout;
    # - prefill_chunk(params, cache, batch, *, offset) — process one prompt
    #   chunk at absolute position ``offset`` (static), writing K/V pages /
    #   recurrent state in place; batch carries tokens (1, C), valid, slot,
    #   page_table (max_pages,); returns (last-valid-token logits, cache);
    # - decode_paged(params, cache, batch) — one batched token step; batch
    #   carries tokens (B, 1), positions (B,), page_table (B, max_pages).
    # - paged_state — True when the paged cache carries per-slot recurrent
    #   state (SSM conv/ssm leaves) in addition to (or instead of) page
    #   pools. Such state is not page-addressable, so the engine's
    #   copy-on-write prefix sharing falls back to trie bookkeeping only.
    # - pools_carried — True when prefill_chunk and decode_paged carry
    #   every cache leaf whole through the layer scan, updating it in
    #   place. The engine then donates the cache to those steps, so the
    #   output aliases the input and no pool is copied. A scan that takes
    #   the pools as ``xs`` and returns them as ``ys`` leaves this False:
    #   donating it adds pool-sized copies into the aliased outputs.
    paged_cache_specs: Callable[..., Pytree] | None = None
    prefill_chunk: Callable[..., tuple[jax.Array, Pytree]] | None = None
    decode_paged: Callable[
        [Pytree, Pytree, dict], tuple[jax.Array, Pytree]
    ] | None = None
    paged_state: bool = False
    pools_carried: bool = False

    # speculative verification (optional): one causal multi-query pass over
    # a W-token draft window. batch carries tokens (B, W), positions (B,)
    # — the cache position of tokens[:, 0] — and page_table (B, max_pages);
    # returns (logits (B, W, V), cache) with the window's K/V scattered into
    # the pages exactly as W sequential decode_paged steps would have.
    verify_paged: Callable[
        [Pytree, Pytree, dict], tuple[jax.Array, Pytree]
    ] | None = None

    # paged cross-attention region (enc-dec families). The cross K/V —
    # derived once per request from the encoder output — lives in its own
    # refcounted page chain rather than a dense (n_slots, ENC_SEQ) block:
    # - paged_cross_specs(n_pages, page_size) -> dict of PSpec — extra
    #   ``*_pages`` leaves merged into the paged cache, addressed by the
    #   engine's per-slot *cross* page table;
    # - prefill_cross(params, cache, batch) -> cache — run the encoder over
    #   batch["frames"] (1, S_enc, d) and scatter the per-layer cross K/V
    #   into the pages named by batch["cross_page_table"] (max_cross_pages,).
    # With both set, prefill_chunk/decode_paged additionally receive
    # cross_page_table + cross_len in their batch.
    paged_cross_specs: Callable[..., Pytree] | None = None
    prefill_cross: Callable[[Pytree, Pytree, dict], Pytree] | None = None

    # True when prefill_chunk consumes modality embeddings *inline* (VLM):
    # the batch carries an extra ``embeds`` leaf (1, C, feat) and a static
    # ``mm_len`` kwarg — positions below mm_len read projected embeddings,
    # positions at or above it read token embeddings.
    paged_mm_inline: bool = False

    def init(self, rng: jax.Array, dtype=jnp.float32) -> Pytree:
        return init_from_specs(self.param_specs, rng, dtype)

    def param_axes(self) -> Pytree:
        return axes_from_specs(self.param_specs)

    def abstract_params(self, dtype=jnp.float32) -> Pytree:
        return abstract_from_specs(self.param_specs, dtype)

    def init_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Pytree:
        specs = self.cache_specs(batch, max_seq)
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, _cache_dtype(s, dtype)),
            specs,
            is_leaf=lambda x: isinstance(x, PSpec),
        )

    def cache_axes(self, batch: int, max_seq: int) -> Pytree:
        return axes_from_specs(self.cache_specs(batch, max_seq))

    def abstract_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Pytree:
        specs = self.cache_specs(batch, max_seq)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, _cache_dtype(s, dtype)),
            specs,
            is_leaf=lambda x: isinstance(x, PSpec),
        )

    # ---- paged serving -----------------------------------------------------

    @property
    def supports_paged(self) -> bool:
        return (
            self.paged_cache_specs is not None
            and self.prefill_chunk is not None
            and self.decode_paged is not None
        )

    @property
    def supports_prefix_sharing(self) -> bool:
        """True when the whole per-token cache lives in shared page pools,
        so a cached prompt prefix can be installed into another slot's
        page table with zero recompute. Families with ``paged_state=True``
        (SSM/hybrid recurrent state, which is not page-addressable) are
        excluded: for them the engine keeps trie bookkeeping only and
        never skips prefill."""
        return self.supports_paged and not self.paged_state

    @property
    def supports_spec_decode(self) -> bool:
        """True when the family can serve as a speculative-decoding target
        (or draft): it exposes the multi-query ``verify_paged`` pass and
        its cache rolls back by page offset alone. ``paged_state`` families
        (SSM/hybrid) are excluded — recurrent state advances with every
        token and cannot be rewound by resetting a length."""
        return self.verify_paged is not None and self.supports_prefix_sharing

    @property
    def supports_paged_cross(self) -> bool:
        """True when the family pages its cross-attention region (enc-dec):
        the engine then allocates a per-request cross page chain at
        admission and runs :attr:`prefill_cross` to fill it."""
        return (
            self.supports_paged
            and self.paged_cross_specs is not None
            and self.prefill_cross is not None
        )

    def _full_paged_specs(self, n_slots: int, n_pages: int,
                          page_size: int) -> Pytree:
        """Paged cache specs with the cross-attention region merged in."""
        specs = dict(self.paged_cache_specs(n_slots, n_pages, page_size))
        if self.paged_cross_specs is not None:
            specs.update(self.paged_cross_specs(n_pages, page_size))
        return specs

    def init_paged_cache(self, n_slots: int, n_pages: int, page_size: int,
                         dtype=jnp.bfloat16) -> Pytree:
        specs = self._full_paged_specs(n_slots, n_pages, page_size)
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, _cache_dtype(s, dtype)),
            specs,
            is_leaf=lambda x: isinstance(x, PSpec),
        )

    def paged_cache_axes(self, n_slots: int, n_pages: int,
                         page_size: int) -> Pytree:
        return axes_from_specs(self._full_paged_specs(n_slots, n_pages,
                                                      page_size))

    def abstract_paged_cache(self, n_slots: int, n_pages: int, page_size: int,
                             dtype=jnp.bfloat16) -> Pytree:
        specs = self._full_paged_specs(n_slots, n_pages, page_size)
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, _cache_dtype(s, dtype)),
            specs,
            is_leaf=lambda x: isinstance(x, PSpec),
        )


def _cache_dtype(spec: PSpec, dtype):
    # integer bookkeeping entries (positions) are marked with init="zeros"
    # and axes ending in "null_i32"
    if spec.axes and spec.axes[-1] == "null_i32":
        return jnp.int32
    if "state" in (spec.axes or ()):  # SSM states carried in f32
        return jnp.float32
    return dtype


# ---------------------------------------------------------------------------
# Shared input-spec builders
# ---------------------------------------------------------------------------


def lm_train_inputs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    return {
        "tokens": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len), jnp.int32),
    }


def lm_prefill_inputs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    return {
        "tokens": jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len), jnp.int32),
    }


def lm_decode_inputs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    b = shape.global_batch
    return {
        "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32),
        "positions": jax.ShapeDtypeStruct((b,), jnp.int32),
    }


def standard_input_specs(cfg: ModelConfig, shape: ShapeConfig, extra=None) -> dict:
    if shape.kind == "train":
        out = lm_train_inputs(cfg, shape)
    elif shape.kind == "prefill":
        out = lm_prefill_inputs(cfg, shape)
    else:
        out = lm_decode_inputs(cfg, shape)
    if extra:
        out.update(extra(cfg, shape))
    return out


def batch_axes_for(specs: dict) -> dict:
    """Logical axes for input batches (tokens/labels/embeds/positions)."""
    out = {}
    for name, s in specs.items():
        nd = len(s.shape)
        if nd == 1:
            out[name] = ("batch",)
        elif nd == 2:
            out[name] = ("batch", "seq")
        elif nd == 3:
            out[name] = ("batch", "seq", None)
        else:
            out[name] = ("batch",) + (None,) * (nd - 1)
    return out
