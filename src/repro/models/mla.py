"""Multi-head latent attention (DeepSeek-V2/V3, without q-LoRA).

Per token: ``q = x W_q`` gives H heads of ``nope + rope`` dims;
``[c, k_pe] = x W_kv_a`` gives a ``kv_lora_rank``-wide latent ``c``, which
an RMSNorm (``kv_norm``) follows, and one rope head ``k_pe`` that every
head shares; ``[k_nope, v] = c W_kv_b`` gives H heads of ``nope + v``
dims. Scores are ``q . [k_nope, k_pe]`` scaled by ``(nope + rope)**-0.5``.
RoPE acts on the rope dims in the published modeling's layout: the pairs
``(x[2i], x[2i+1])`` rotate by ``pos * theta**(-2i/rope)``, and the result
lists the rotated evens, then the rotated odds.

The cache holds one row per token, ``[c | k_pe]`` (``latent_dim`` wide,
one kv head), not per-head K and V, zero-padded to ``row_dim``, a multiple
of 128 lanes: the TPU lays a 576-wide row out in 640 lanes anyway, and a
kernel's copy of a page must span whole lane tiles. Two forms read it:

- expanded (training, prefill, prefill chunks): ``k_nope`` and ``v`` are
  rebuilt from the context's latent rows and go through flash attention;
- absorbed (decode): ``q_lat = q_nope . W_kv_b,k^T`` turns each head's
  query into a latent-wide one, scored against the cached rows as keys
  and averaged over them as values (the paged decode kernel with K = 1);
  the first ``kv_lora_rank`` dims of the result go out through
  ``W_kv_b,v`` and ``W_o``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.models import layers as ll
from repro.models.model_api import PSpec


def attn_specs(cfg: ModelConfig, layers: int) -> dict:
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dq = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    dkv = cfg.qk_nope_head_dim + cfg.v_head_dim
    lead, lax_ = (layers,), ("layers",)
    return {
        "wq": PSpec(lead + (d, H, dq), lax_ + ("embed_in", "heads", "head_dim")),
        "wkv_a": PSpec(lead + (d, cfg.latent_dim), lax_ + ("embed_in", "head_dim")),
        "kv_norm": PSpec(lead + (r,), lax_ + ("head_dim",), init="ones"),
        "wkv_b": PSpec(lead + (r, H, dkv), lax_ + ("head_dim", "heads", "null")),
        "wo": PSpec(lead + (H, cfg.v_head_dim, d),
                    lax_ + ("heads", "head_dim", "embed_out")),
        "ln": PSpec(lead + (d,), lax_ + ("embed",), init="ones"),
    }


def scale(cfg: ModelConfig) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def row_dim(cfg: ModelConfig) -> int:
    """Width of a cache row: ``latent_dim`` rounded up to 128 lanes."""
    return -(-cfg.latent_dim // 128) * 128


def _pad_row(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    pad = row_dim(cfg) - x.shape[-1]
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def rope_pairs(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """RoPE over interleaved pairs. x (..., S, H, D); positions (S,) or
    (B, S)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _project(p: dict, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """x (B, S, d) -> q_nope (B, S, H, nope), roped q_pe (B, S, H, rope)
    and the cache rows (B, S, row_dim): normed c, roped k_pe, zeros."""
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, ll.cast(p["wq"]))
    q_pe = rope_pairs(q[..., nope:], positions, cfg.rope_theta)
    ckv = jnp.einsum("bsd,dc->bsc", x, ll.cast(p["wkv_a"]))
    c = ops.rmsnorm(ckv[..., :r], p["kv_norm"], cfg.norm_eps)
    k_pe = rope_pairs(ckv[..., None, r:], positions, cfg.rope_theta)[..., 0, :]
    return q[..., :nope], q_pe, _pad_row(jnp.concatenate([c, k_pe], -1), cfg)


def _expanded(p, q_nope, q_pe, rows, cfg: ModelConfig, *, q_offset: int = 0):
    """Causal attention in the expanded form: queries (1 or B, S, H, ·)
    over the cache rows (B, Sk, row_dim) of their context, keys and
    values rebuilt per head. -> (B, S, d)."""
    r, nope, H = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.n_heads
    kv = jnp.einsum("bsc,chk->bshk", rows[..., :r], ll.cast(p["wkv_b"]))
    k_pe = jnp.broadcast_to(rows[..., None, r:cfg.latent_dim],
                            (*rows.shape[:2], H, cfg.qk_rope_head_dim))
    k = jnp.concatenate([kv[..., :nope], k_pe], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    out = ops.attention(q, k, kv[..., nope:], causal=True, q_offset=q_offset)
    return jnp.einsum("bshv,hvd->bsd", out, ll.cast(p["wo"]))


def attn_forward(p: dict, x: jax.Array, cfg: ModelConfig,
                 positions: jax.Array):
    """Full-sequence attention (training, prefill) -> (out, cache rows)."""
    q_nope, q_pe, rows = _project(p, x, cfg, positions)
    return _expanded(p, q_nope, q_pe, rows, cfg), rows


def _absorbed_query(p: dict, q_nope, q_pe, cfg: ModelConfig) -> jax.Array:
    """(B, H, nope), (B, H, rope) -> (B, H, row_dim): each head's query
    against a cache row, ``q_nope`` taken through ``W_kv_b,k``."""
    w_uk = ll.cast(p["wkv_b"])[..., :cfg.qk_nope_head_dim]
    return _pad_row(jnp.concatenate(
        [jnp.einsum("bhn,chn->bhc", q_nope, w_uk), q_pe], -1), cfg)


def _absorbed_out(p: dict, o: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The attention-weighted rows (B, H, row_dim) -> (B, 1, d): their
    latent part through ``W_kv_b,v``, then ``W_o``."""
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    w_uv = ll.cast(p["wkv_b"])[..., nope:]
    v = jnp.einsum("bhc,chv->bhv", o[..., :r], w_uv)
    return jnp.einsum("bhv,hvd->bd", v, ll.cast(p["wo"]))[:, None]


def attn_decode_paged(p: dict, x: jax.Array, cfg: ModelConfig,
                      positions: jax.Array, pool: jax.Array, layer,
                      page_table: jax.Array):
    """One token per lane against layer ``layer`` of the paged latent pool
    (L, n_pages, P, 1, row_dim), absorbed form. The pool is read and
    written where it lies, never sliced per layer: the row goes in at
    ``[layer, page, offset]``, and the kernel reads the pool as ``L *
    n_pages`` pages, this layer's from ``layer * n_pages`` on. -> (out (B,
    1, d), pool)."""
    L, n_pages, P = pool.shape[:3]
    q_nope, q_pe, rows = _project(p, x, cfg, positions[:, None])
    page = jnp.take_along_axis(page_table, (positions // P)[:, None], 1)[:, 0]
    pool = pool.at[layer, page, positions % P].set(
        rows[:, 0, None].astype(pool.dtype))
    q = _absorbed_query(p, q_nope[:, 0], q_pe[:, 0], cfg)
    flat = pool.reshape(L * n_pages, *pool.shape[2:])
    o = ops.paged_decode_attention(q, flat, flat, page_table + layer * n_pages,
                                   positions + 1, scale=scale(cfg))
    return _absorbed_out(p, o, cfg), pool


def attn_decode(p: dict, x: jax.Array, cfg: ModelConfig,
                positions: jax.Array, cache: jax.Array):
    """One token per lane against a dense latent cache (B, S, 1,
    row_dim), absorbed form. -> (out (B, 1, d), cache)."""
    q_nope, q_pe, rows = _project(p, x, cfg, positions[:, None])
    cache = ll.kv_append(cache, rows[:, :, None], positions)
    q = _absorbed_query(p, q_nope[:, 0], q_pe[:, 0], cfg)
    o = ops.decode_attention(q, cache, cache, positions + 1, scale=scale(cfg))
    return _absorbed_out(p, o, cfg), cache


def attn_prefill_chunk(p: dict, x: jax.Array, cfg: ModelConfig, offset: int,
                       pool: jax.Array, layer, page_table: jax.Array):
    """A prompt chunk (1, C, d) at static ``offset``: its rows go into the
    slot's pages of layer ``layer`` of the pool (L, n_pages, P, 1,
    row_dim), then it attends in the expanded form over the rows of ``[0,
    offset + C)`` read back through the page table (as
    :func:`repro.models.layers.attn_prefill_chunk`). -> (out, pool)."""
    C = x.shape[1]
    P = pool.shape[2]
    max_pages = page_table.shape[0]
    pos = offset + jnp.arange(C)
    q_nope, q_pe, rows = _project(p, x, cfg, pos)
    logical = pos // P
    pid = jnp.where(logical < max_pages,
                    page_table[jnp.minimum(logical, max_pages - 1)], 0)
    pool = pool.at[layer, pid, pos % P].set(rows[0][:, None].astype(pool.dtype))
    n_ctx = min((offset + C + P - 1) // P, max_pages)
    ctx = pool[layer, page_table[:n_ctx]].reshape(1, n_ctx * P, row_dim(cfg))
    return _expanded(p, q_nope, q_pe, ctx, cfg, q_offset=offset), pool
