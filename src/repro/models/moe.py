"""Token-choice top-k MoE transformers (granite-moe, deepseek-moe,
Moonlight / DeepSeek-V3 with latent attention).

Dispatch is dropless: every (token, expert) assignment to an expert this
chip holds is computed, whatever the batch or chunk it arrives in. The
assignments are sorted by expert and go through a grouped matmul over the
held experts (``ops.moe_experts``), which reads each hit expert's weights
once and skips the experts no token chose; the results are weighted and
added back to their tokens. Under a mesh the held experts split over
``model`` as their weights do.

The router scores all ``n_experts``; the chip holds experts
``[expert_offset, expert_offset + n_held)`` of them (the guide's share of
an expert-parallel deployment) and computes their part of the layer. The
routing weights are normalized over all k chosen experts, held or not, so
the shares of the layer's chips add up to the whole layer.

Routers: ``softmax`` (top-k of the softmax) and ``sigmoid_bias`` (top-k of
sigmoid scores plus a correction bias that picks but never weighs, then
``routed_scale``: DeepSeek-V3's ``noaux_tc`` with one group). Attention is
GQA or, with ``attn_kind="mla"``, latent attention (:mod:`.mla`), whose
paged cache is one latent pool. DeepSeek-style details: shared experts
(always-on), leading dense layers (``first_k_dense``), fine-grained
experts, router aux load-balance loss. ``moe_impl="ep"`` trains through an
expert-parallel shard_map with per-shard capacity instead.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops
from repro.kernels.moe_gmm import row_tile
from repro.models import layers as ll
from repro.models import mla
from repro.models.model_api import ModelFns, PSpec, standard_input_specs
from repro.models.transformer import apply_remat
from repro.parallel import tracing


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def moe_mlp_specs(cfg: ModelConfig, layers: int) -> dict:
    d, E, f = cfg.d_model, cfg.n_held, cfg.d_expert
    lead, lax_ = (layers,), ("layers",)
    specs = {
        "router": PSpec(lead + (d, cfg.n_experts), lax_ + ("embed", "experts"),
                        init="small"),
        "wg": PSpec(lead + (E, d, f), lax_ + ("experts", "embed_in", "expert_mlp")),
        "wu": PSpec(lead + (E, d, f), lax_ + ("experts", "embed_in", "expert_mlp")),
        "wd": PSpec(lead + (E, f, d), lax_ + ("experts", "expert_mlp", "embed_out")),
        "ln": PSpec(lead + (d,), lax_ + ("embed",), init="ones"),
    }
    if cfg.n_shared_experts:
        w = cfg.n_shared_experts * cfg.d_expert
        specs["shared"] = {
            k: v
            for k, v in ll.mlp_specs(cfg, w, layers=layers).items()
            if k != "ln"
        }
    if cfg.router_kind == "sigmoid_bias":
        specs["router_bias"] = PSpec(lead + (cfg.n_experts,),
                                     lax_ + ("experts",), init="zeros")
    return specs


def _attn_specs(cfg: ModelConfig, layers: int) -> dict:
    if cfg.mla:
        return mla.attn_specs(cfg, layers)
    return ll.attn_specs(cfg, layers=layers)


def build_specs(cfg: ModelConfig) -> dict:
    n_moe = cfg.n_layers - cfg.first_k_dense
    specs = {
        **ll.embed_specs(cfg),
        "moe_layers": {
            "attn": _attn_specs(cfg, n_moe),
            "mlp": moe_mlp_specs(cfg, layers=n_moe),
        },
    }
    if cfg.first_k_dense:
        specs["dense_layers"] = {
            "attn": _attn_specs(cfg, cfg.first_k_dense),
            "mlp": ll.mlp_specs(cfg, cfg.d_ff_dense or cfg.d_ff,
                                layers=cfg.first_k_dense),
        }
    return specs


# ---------------------------------------------------------------------------
# MoE MLP: routing and the dropless grouped matmul over the held experts
# ---------------------------------------------------------------------------


def route(p: dict, xf: jax.Array, cfg: ModelConfig):
    """xf (T, d) -> (weights (T, k) f32, chosen expert ids (T, k), router
    probabilities (T, E) for the aux loss)."""
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    if cfg.router_kind == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        weights, sel = jax.lax.top_k(probs, cfg.moe_top_k)
    else:
        probs = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(
            probs + p["router_bias"].astype(jnp.float32), cfg.moe_top_k)
        weights = jnp.take_along_axis(probs, sel, axis=-1)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights * cfg.routed_scale, sel, probs


def held_experts(p: dict, xf: jax.Array, weights: jax.Array, sel: jax.Array,
                 cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer, (T, d) f32, and the rows each
    held expert took, ``(n_held,)`` int32: each assignment to
    a held expert through that expert's SwiGLU, weighted, summed per token.
    Assignments are sorted by held expert (the others last, in no group,
    where the grouped matmul gives zeros) and padded to the kernel's row
    tile. Where ``p`` has a ``layer``, its expert weights are the whole
    layer stack (:func:`_scan_cached`)."""
    T, d = xf.shape
    k, G = sel.shape[1], cfg.n_held
    local = sel.reshape(-1) - cfg.expert_offset
    group = jnp.where((local >= 0) & (local < G), local, G)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=G + 1)[:G].astype(jnp.int32)
    tok = order // k
    A = T * k
    pad = -A % row_tile(A)
    lhs = jnp.pad(xf[tok].astype(ll.COMPUTE_DTYPE), ((0, pad), (0, 0)))
    out = ops.moe_experts(lhs, ll.cast(p["wg"]), ll.cast(p["wu"]),
                          ll.cast(p["wd"]), sizes, p.get("layer"))[:A]
    w = weights.reshape(-1)[order]
    return jnp.zeros((T, d), jnp.float32).at[tok].add(
        out.astype(jnp.float32) * w[:, None]), sizes


def moe_mlp_forward_ep(p: dict, x: jax.Array, cfg: ModelConfig, mesh):
    """Expert-parallel MoE via shard_map (§Perf beyond-paper optimization).

    The pjit scatter path (below) routes tokens through a *globally*
    expert-sharded (E, cap, d) buffer; because the scatter indices are
    data-dependent, XLA cannot prove locality and materializes the buffer
    with per-layer all-reduces (measured: 8.5 TB/device/step on
    deepseek-moe-16b train_4k). Here routing is explicit:

    - dispatch is LOCAL to each data shard (local top-k, local sort,
      per-shard capacity) — zero communication;
    - expert FFNs run model-sharded (each model rank holds E/16 experts
      and reads only its slice of the local buffer);
    - one all-gather over the model axis returns per-expert outputs
      (E · C_local · d bytes — the algorithmic minimum for this layout);
    - combine is local.
    """
    from jax.sharding import PartitionSpec as P

    if cfg.router_kind != "softmax" or cfg.n_held != cfg.n_experts:
        raise NotImplementedError(
            "the expert-parallel training path has the softmax router over "
            "all experts only")
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    has_model = "model" in mesh.axis_names and E % mesh.shape.get("model", 1) == 0
    batch_spec = (data_axes if len(data_axes) > 1 else data_axes[0]) \
        if data_axes and B % n_data == 0 else None
    expert_spec = "model" if has_model else None

    def body(router, wg, wu, wd, xl):
        Bl, Sl, _ = xl.shape
        Tl = Bl * Sl
        xf = xl.reshape(Tl, d)
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                            router.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        weights, sel = jax.lax.top_k(probs, k)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

        counts = jnp.bincount(sel.reshape(-1), length=E)
        frac = counts.astype(jnp.float32) / (Tl * k)
        aux = E * jnp.sum(probs.mean(0) * frac)
        if data_axes:
            aux = jax.lax.pmean(aux, axis_name=data_axes)

        cap = int(math.ceil(Tl * k * cfg.capacity_factor / E))
        cap = max(8, min(cap, Tl))
        e_flat = sel.reshape(-1)
        order = jnp.argsort(e_flat, stable=True)
        sorted_e = e_flat[order]
        starts = jnp.cumsum(counts) - counts
        rank = jnp.arange(Tl * k) - starts[sorted_e]
        keep = rank < cap
        rank_c = jnp.minimum(rank, cap - 1)
        tok = order // k

        vals = jnp.where(keep[:, None], xf[tok], 0).astype(ll.COMPUTE_DTYPE)
        buf = jnp.zeros((E, cap, d), ll.COMPUTE_DTYPE)
        buf = buf.at[sorted_e, rank_c].add(vals)        # local scatter

        # expert FFN on the local expert slice (wg/wu/wd are (E/16,·,·))
        e_local = wg.shape[0]
        if expert_spec is not None:
            midx = jax.lax.axis_index("model")
            buf_l = jax.lax.dynamic_slice_in_dim(buf, midx * e_local,
                                                 e_local, 0)
        else:
            buf_l = buf
        g = jnp.einsum("ecd,edf->ecf", buf_l, ll.cast(wg))
        u = jnp.einsum("ecd,edf->ecf", buf_l, ll.cast(wu))
        h = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
        h = jnp.einsum("ecf,efd->ecd", h, ll.cast(wd))  # (E/16, cap, d)
        if expert_spec is not None:
            # the one unavoidable collective: per-expert outputs to all
            h = jax.lax.all_gather(h, axis_name="model", axis=0,
                                   tiled=True)          # (E, cap, d)

        out_sorted = h[sorted_e, rank_c]
        w_sorted = weights.reshape(-1)[order]
        contrib = out_sorted * jnp.where(keep, w_sorted, 0.0)[:, None].astype(
            out_sorted.dtype
        )
        y = jnp.zeros((Tl, d), ll.COMPUTE_DTYPE).at[tok].add(contrib)
        return y.reshape(Bl, Sl, d), aux

    shmap = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, None),                       # router replicated
            P(expert_spec, None, None),          # wg (E, d, f)
            P(expert_spec, None, None),          # wu
            P(expert_spec, None, None),          # wd (E, f, d)
            P(batch_spec, None, None),           # x
        ),
        out_specs=(P(batch_spec, None, None), P()),
        check_vma=False,
    )
    y, aux = shmap(p["router"], p["wg"], p["wu"], p["wd"], x)
    if cfg.n_shared_experts:
        y = y + ll.mlp_forward(p["shared"], x.reshape(B * S, d), cfg
                               ).reshape(B, S, d)
    return y, aux


def moe_mlp_forward(p: dict, x: jax.Array, cfg: ModelConfig):
    """x (B, S, d) -> (out (B, S, d), aux load-balance loss, rows per held
    expert (n_held,), None on the expert-parallel path)."""
    if cfg.moe_impl == "ep" and x.shape[1] > 1:
        from repro.parallel.partition import current_mesh

        mesh = current_mesh()
        if mesh is not None:
            return *moe_mlp_forward_ep(p, x, cfg, mesh), None
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.moe_top_k
    T = B * S
    xf = x.reshape(T, d)
    weights, sel, probs = route(p, xf, cfg)
    # aux loss (Switch-style): E * Σ_e mean_prob_e * frac_tokens_e
    counts = jnp.bincount(sel.reshape(-1), length=E)            # (E,)
    frac = counts.astype(jnp.float32) / (T * k)
    aux = E * jnp.sum(probs.mean(0) * frac)
    y, sizes = held_experts(p, xf, weights, sel, cfg)
    if cfg.n_shared_experts:
        y = y + ll.mlp_forward(p["shared"], xf, cfg).astype(jnp.float32)
    return y.astype(ll.COMPUTE_DTYPE).reshape(B, S, d), aux, sizes


# ---------------------------------------------------------------------------
# Blocks / entry points
# ---------------------------------------------------------------------------


def _mlp(lp: dict, x: jax.Array, cfg: ModelConfig, moe: bool):
    """The block's MLP half: (residual out, aux loss, rows per held expert
    or None, as :func:`moe_mlp_forward`; None for a dense layer)."""
    h = ops.rmsnorm(x, lp["mlp"]["ln"], cfg.norm_eps)
    if moe:
        y, aux, sizes = moe_mlp_forward(lp["mlp"], h, cfg)
        return x + y, aux, sizes
    return (x + ll.mlp_forward(lp["mlp"], h, cfg), jnp.zeros((), jnp.float32),
            None)


def _block(lp, x, cfg, positions, moe: bool):
    """A full-sequence block -> (out, cache entries, aux): (k, v) for GQA,
    the latent rows for MLA."""
    h = ops.rmsnorm(x, lp["attn"]["ln"], cfg.norm_eps)
    if cfg.mla:
        a, kv = mla.attn_forward(lp["attn"], h, cfg, positions)
        kv = (kv,)
    else:
        a, kv = ll.attn_forward(lp["attn"], h, cfg, positions)
    out, aux, _ = _mlp(lp, x + a, cfg, moe)
    return out, kv, aux


def _stacks(params, cfg: ModelConfig):
    """(layer-stacked params, is-MoE, first layer index) of the dense
    leading layers, if any, then the MoE layers."""
    out = []
    if cfg.first_k_dense:
        out.append((params["dense_layers"], False, 0))
    out.append((params["moe_layers"], True, cfg.first_k_dense))
    return out


def _cache_keys(cfg: ModelConfig, paged: bool) -> tuple[str, ...]:
    if cfg.mla:
        return ("latent_pages",) if paged else ("latent",)
    return ("k_pages", "v_pages") if paged else ("k", "v")


def _scan_cached(params, cfg: ModelConfig, x, cache: dict, keys, step):
    """Scan the layer stacks with the cache leaves in ``keys`` carried
    whole: ``step(lp, x, pools, layer, moe) -> (x, pools, sizes)`` updates
    them in place, so the dense and MoE stacks never stack a second copy of
    the pools to concatenate. The routed experts' weights stay whole too,
    with the layer's index beside them: the grouped matmul reads its layer
    of the stack in place, where a scanned slice would first be copied out
    for the kernel. Returns (x, the cache with those leaves updated, the
    MoE layers' rows per held expert (n_moe, n_held) or None)."""
    pools = tuple(cache[k] for k in keys)
    sizes = None
    for stack, moe, lo in _stacks(params, cfg):
        n = jax.tree.leaves(stack)[0].shape[0]
        whole = {}
        if moe:
            mlp = dict(stack["mlp"])
            whole = {k: mlp.pop(k) for k in ("wg", "wu", "wd")}
            stack = dict(stack, mlp=mlp)

        def body(carry, xs, moe=moe, lo=lo, whole=whole):
            lp, i = xs
            if moe:
                lp = dict(lp, mlp=dict(lp["mlp"], **whole, layer=i))
            x, pools, rows = step(lp, *carry, lo + i, moe)
            return (x, pools), rows

        (x, pools), rows = jax.lax.scan(body, (x, pools),
                                        (stack, jnp.arange(n)),
                                        unroll=tracing.scan_unroll())
        if moe:
            sizes = rows
    return x, dict(cache, **dict(zip(keys, pools))), sizes


def _sliced(fn, pools, layer):
    """``fn(*leaves) -> (out, *leaves)``, a per-layer cache update, on the
    carried pools: the layer's slices out, then back in."""
    out, *leaves = fn(*(jax.lax.dynamic_index_in_dim(p, layer, 0, False)
                        for p in pools))
    return out, tuple(jax.lax.dynamic_update_index_in_dim(p, v, layer, 0)
                      for p, v in zip(pools, leaves))


def _backbone(params, cfg, x, *, remat=True, collect_kv=False):
    positions = jnp.arange(x.shape[1])
    caches, auxs = [], []
    for stack, moe, _ in _stacks(params, cfg):
        def body(carry, lp, moe=moe):
            out, kv, aux = _block(lp, carry, cfg, positions, moe)
            kv = tuple(t.astype(jnp.bfloat16) for t in kv) if collect_kv else None
            return out, (kv, aux)

        if remat:
            body = apply_remat(body, cfg)
        x, (kv, aux) = jax.lax.scan(body, x, stack,
                                    unroll=tracing.scan_unroll())
        caches.append(kv)
        if moe:
            auxs.append(aux)
    x = ops.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    aux = auxs[0].mean()
    if not collect_kv:
        return x, None, aux
    keys = _cache_keys(cfg, paged=False)
    # concatenate the dense and MoE layers' caches along the layer axis;
    # MLA rows gain the cache's single kv-head axis
    cache = {k: jnp.concatenate([c[i] for c in caches], 0)
             for i, k in enumerate(keys)}
    if cfg.mla:
        cache["latent"] = cache["latent"][:, :, :, None]
    return x, cache, aux


def loss_fn(params, batch, cfg: ModelConfig):
    x = ll.embed_lookup(params, batch["tokens"])
    hidden, _, aux = _backbone(params, cfg, x, remat=True)
    loss, info = ll.lm_loss(params, hidden, batch["labels"], cfg)
    info["router_aux"] = aux
    return loss + cfg.router_aux_coef * aux, info


def prefill_fn(params, batch, cfg: ModelConfig):
    x = ll.embed_lookup(params, batch["tokens"])
    hidden, cache, _ = _backbone(params, cfg, x, remat=False, collect_kv=True)
    logits = ll.logits_last(params, hidden[:, -1], cfg)
    return logits, cache


def decode_fn(params, cache, batch, cfg: ModelConfig):
    positions = batch["positions"]
    x = ll.embed_lookup(params, batch["tokens"])

    def step(lp, x, pools, layer, moe):
        h = ops.rmsnorm(x, lp["attn"]["ln"], cfg.norm_eps)
        attend = mla.attn_decode if cfg.mla else ll.attn_decode
        a, pools = _sliced(
            lambda *lv: attend(lp["attn"], h, cfg, positions, *lv),
            pools, layer)
        return _mlp(lp, x + a, cfg, moe)[0], pools, None

    x, cache, _ = _scan_cached(params, cfg, x, cache,
                               _cache_keys(cfg, paged=False), step)
    x = ops.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return ll.logits_last(params, x[:, 0], cfg), cache


def cache_specs(cfg: ModelConfig, batch: int, max_seq: int) -> dict:
    L, K, dh = cfg.n_layers, cfg.n_kv_heads, cfg.d_head
    axes = ("layers", "batch", "seq_fallback", "kv_heads", "head_dim")
    if cfg.mla:
        return {"latent": PSpec((L, batch, max_seq, 1, mla.row_dim(cfg)),
                                axes, init="zeros")}
    return {
        "k": PSpec((L, batch, max_seq, K, dh), axes, init="zeros"),
        "v": PSpec((L, batch, max_seq, K, dh), axes, init="zeros"),
    }


# ---------------------------------------------------------------------------
# Paged serving path
# ---------------------------------------------------------------------------


# The paged cache's device counters, which the engine adds to its stats
# (``stat_*`` leaves): per kind of call, the calls, the (MoE layer, held
# expert) pairs whose weights the grouped matmul read, and the rows it
# computed; int32, the engine reads their growth modulo 2**32.
COUNTERS = tuple(f"stat_moe_{kind}_{what}" for kind in ("decode", "prefill")
                 for what in ("calls", "hits", "rows"))


def paged_cache_specs(cfg: ModelConfig, n_slots: int, n_pages: int,
                      page_size: int) -> dict:
    """GQA: K and V pools. MLA: one latent pool of (c, k_pe) rows, one kv
    head wide (:func:`.mla.row_dim`). Then the expert counters
    (``COUNTERS``)."""
    L, K, dh = cfg.n_layers, cfg.n_kv_heads, cfg.d_head
    axes = ("layers", "pages", "page", "kv_heads", "head_dim")
    if cfg.mla:
        specs = {"latent_pages": PSpec(
            (L, n_pages, page_size, 1, mla.row_dim(cfg)), axes,
            init="zeros")}
    else:
        specs = {
            "k_pages": PSpec((L, n_pages, page_size, K, dh), axes,
                             init="zeros"),
            "v_pages": PSpec((L, n_pages, page_size, K, dh), axes,
                             init="zeros"),
        }
    return dict(specs, **{k: PSpec((1,), ("null_i32",), init="zeros")
                          for k in COUNTERS})


def _count(cache: dict, kind: str, sizes) -> dict:
    """``cache`` with one call of ``kind`` added to its expert counters,
    from the MoE layers' rows per held expert (none on the expert-parallel
    path: nothing is added)."""
    if sizes is None:
        return cache
    add = {"calls": 1, "hits": jnp.sum(sizes > 0), "rows": jnp.sum(sizes)}
    return dict(cache, **{
        f"stat_moe_{kind}_{k}": cache[f"stat_moe_{kind}_{k}"]
        + jnp.asarray(v, jnp.int32) for k, v in add.items()})


def prefill_chunk_fn(params, cache, batch, cfg: ModelConfig, *, offset: int):
    """Chunked prefill through dense + MoE layers, the chunk's cache rows
    written into pages."""
    table = batch["page_table"]
    x = ll.embed_lookup(params, batch["tokens"])          # (1, C, d)

    def step(lp, x, pools, layer, moe):
        h = ops.rmsnorm(x, lp["attn"]["ln"], cfg.norm_eps)
        if cfg.mla:
            a, lat = mla.attn_prefill_chunk(lp["attn"], h, cfg, offset,
                                            pools[0], layer, table)
            pools = (lat,)
        else:
            a, pools = _sliced(
                lambda *lv: ll.attn_prefill_chunk(lp["attn"], h, cfg, offset,
                                                  *lv, table), pools, layer)
        x, _, rows = _mlp(lp, x + a, cfg, moe)
        return x, pools, rows

    x, cache, sizes = _scan_cached(params, cfg, x, cache,
                                   _cache_keys(cfg, paged=True), step)
    x = ops.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    last = jax.lax.dynamic_slice_in_dim(x, batch["valid"] - 1, 1, axis=1)
    return ll.logits_last(params, last[:, 0], cfg), _count(cache, "prefill",
                                                           sizes)


def decode_paged_fn(params, cache, batch, cfg: ModelConfig):
    positions = batch["positions"]
    table = batch["page_table"]
    x = ll.embed_lookup(params, batch["tokens"])

    def step(lp, x, pools, layer, moe):
        h = ops.rmsnorm(x, lp["attn"]["ln"], cfg.norm_eps)
        if cfg.mla:
            a, lat = mla.attn_decode_paged(lp["attn"], h, cfg, positions,
                                           pools[0], layer, table)
            pools = (lat,)
        else:
            a, pools = _sliced(
                lambda *lv: ll.attn_decode_paged(lp["attn"], h, cfg,
                                                 positions, *lv, table),
                pools, layer)
        x, _, rows = _mlp(lp, x + a, cfg, moe)
        return x, pools, rows

    x, cache, sizes = _scan_cached(params, cfg, x, cache,
                                   _cache_keys(cfg, paged=True), step)
    x = ops.rmsnorm(x, params["final_ln"], cfg.norm_eps)
    return ll.logits_last(params, x[:, 0], cfg), _count(cache, "decode", sizes)


def verify_paged_fn(params, cache, batch, cfg: ModelConfig):
    """Speculative verification through dense + MoE layers: fold the
    W-token draft window into the batch dim and run the ordinary
    ``decode_paged`` path, so every lane's arithmetic is bitwise identical
    to plain decode (the greedy spec-decode exactness guarantee — see
    ``transformer.verify_paged_fn``). MoE routing is per-token (top-k over
    each lane's own hidden state), and dispatch is dropless, so folding
    does not change any lane's experts."""
    tokens = batch["tokens"]                              # (B, W)
    B, W = tokens.shape
    fold = {
        "tokens": tokens.reshape(B * W, 1),
        "positions": (batch["positions"][:, None]
                      + jnp.arange(W)[None, :]).reshape(-1),
        "page_table": jnp.repeat(batch["page_table"], W, axis=0),
    }
    logits, cache = decode_paged_fn(params, cache, fold, cfg)
    return logits.reshape(B, W, -1), cache


def make_model(cfg: ModelConfig) -> ModelFns:
    return ModelFns(
        cfg=cfg,
        param_specs=build_specs(cfg),
        cache_specs=functools.partial(cache_specs, cfg),
        loss=functools.partial(loss_fn, cfg=cfg),
        prefill=functools.partial(prefill_fn, cfg=cfg),
        decode_step=functools.partial(decode_fn, cfg=cfg),
        input_specs=functools.partial(standard_input_specs, cfg),
        paged_cache_specs=functools.partial(paged_cache_specs, cfg),
        prefill_chunk=functools.partial(prefill_chunk_fn, cfg=cfg),
        decode_paged=functools.partial(decode_paged_fn, cfg=cfg),
        # speculative verify is not built for latent attention: without
        # it the engine refuses a draft model for this family
        verify_paged=(None if cfg.mla
                      else functools.partial(verify_paged_fn, cfg=cfg)),
        # pure page-pool cache: eligible for copy-on-write prefix sharing
        paged_state=False,
        # _scan_cached carries every pool whole, for MLA and GQA alike
        pools_carried=True,
    )
