"""Latent attention and the chip-share MoE layer (Moonlight-16B-A3B's
block, ``repro.models.mla`` and ``repro.models.moe``) at the reduced
preset on the CPU: the grouped matmul against its XLA twin, the sigmoid
router, the absorbed decode against the expanded form, expert shares that
add up to the whole layer, dropless dispatch, the config's published
size, and the engine's paged steps taking the family's cache donated."""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, REDUCED, get
from repro.configs import moonlight_16b_a3b as moonlight
from repro.kernels import ops
from repro.models import get_model, mla, moe
from repro.serving.engine import ServeEngine

CFG = REDUCED["moonlight-16b-a3b"]


def _params(cfg, seed=0):
    """The model's init, with a router and correction bias wide enough that
    routing depends on both (the init's router is near zero)."""
    params = get_model(cfg).init(jax.random.key(seed))
    mlp = params["moe_layers"]["mlp"]
    k1, k2 = jax.random.split(jax.random.key(seed + 1))
    mlp["router"] = jax.random.normal(k1, mlp["router"].shape) \
        * cfg.d_model ** -0.5
    mlp["router_bias"] = 0.1 * jax.random.normal(k2, mlp["router_bias"].shape)
    return params


@pytest.mark.parametrize("backend", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("m,k,n,sizes", [
    (64, 64, 96, [10, 0, 30, 9]),          # an empty expert, rows left over
    (256, 256, 384, [0, 0, 100, 0, 140, 3, 0, 0]),
    (32, 64, 96, [0, 0, 0, 0]),            # nothing held is chosen
])
@pytest.mark.parametrize("stacked", [False, True], ids=["one", "stacked"])
def test_grouped_matmul_kernel_matches_its_twin(backend, m, k, n, sizes,
                                                stacked):
    """The expert MLPs through the Pallas kernels (in interpret mode) and
    through ``jax.lax.ragged_dot``, against a per-expert loop on every row
    some expert owns; rows past the last group are zero. ``stacked``: the
    weights are layer 1 of a three-layer stack, read in place."""
    rng = np.random.default_rng(m + k)
    G = len(sizes)
    gs = jnp.asarray(sizes, jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    wg, wu = (jnp.asarray(rng.standard_normal((G, k, n)) * k ** -0.5,
                          jnp.bfloat16) for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((G, n, k)) * n ** -0.5, jnp.bfloat16)
    layer, args = None, (wg, wu, wd)
    if stacked:
        # other layers hold other weights: reading one of them is O(1) off
        layer = jnp.int32(1)
        args = tuple(jnp.stack([w * 3, w, -w]) for w in args)
    with ops.use_backend(backend):
        out = ops.moe_experts(lhs, *args, gs, layer)
    f32 = jnp.float32
    rows = 0
    for g, s in enumerate(sizes):
        x = lhs[rows:rows + s].astype(f32)
        a = x @ wg[g].astype(f32)
        h = (a * jax.nn.sigmoid(a) * (x @ wu[g].astype(f32))
             ).astype(jnp.bfloat16)
        # bf16 rounding of h and out, with f32 accumulation inside
        want = h.astype(f32) @ wd[g].astype(f32)
        np.testing.assert_allclose(np.asarray(out[rows:rows + s], f32),
                                   np.asarray(want), atol=2e-2, rtol=2e-2)
        rows += s
    assert not np.asarray(out[rows:], f32).any()


MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import re
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.configs import REDUCED
from repro.kernels import ops
from repro.models import get_model
from repro.parallel.partition import activation_sharding, tree_shardings

mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
r = np.random.default_rng(0)
G, d, f, M = 8, 64, 96, 64
sizes = jnp.asarray([10, 0, 30, 9, 0, 5, 3, 0], jnp.int32)
lhs = jnp.asarray(r.standard_normal((M, d)), jnp.bfloat16)
ws = [jnp.asarray(r.standard_normal((3, G) + s) * 0.1, jnp.bfloat16)
      for s in ((d, f), (d, f), (f, d))]
cfg = REDUCED["moonlight-16b-a3b"]
model = get_model(cfg)
params = model.init(jax.random.key(0), jnp.bfloat16)
mlp = params["moe_layers"]["mlp"]
mlp["router"] = (jax.random.normal(jax.random.key(1), mlp["router"].shape)
                 * cfg.d_model ** -0.5)
cache = jax.tree.map(
    lambda c: jax.random.normal(jax.random.key(2), c.shape).astype(c.dtype),
    model.init_paged_cache(4, 20, 8, jnp.bfloat16))
batch = {"tokens": jnp.asarray([[5], [7], [11], [13]], jnp.int32),
         "positions": jnp.asarray([3, 9, 17, 30], jnp.int32),
         "page_table": jnp.asarray(np.arange(1, 17).reshape(4, 4), jnp.int32)}
shard = tree_shardings(model.param_axes(), model.abstract_params(jnp.bfloat16),
                       mesh)
# one shard's part of one layer's expert weights, in elements
part = cfg.n_held // 2 * cfg.d_model * cfg.d_expert
for b in ("xla", "pallas_interpret"):
    with ops.use_backend(b):
        want = ops.moe_experts(lhs, *ws, sizes, jnp.int32(1))
        sw = [jax.device_put(w, NamedSharding(mesh, P(None, "model")))
              for w in ws]
        with activation_sharding(mesh):
            got = jax.jit(ops.moe_experts)(lhs, *sw, sizes, jnp.int32(1))
        # each shard owns whole rows, the others' zero: the sum is exact
        assert bool((got == want).all()), b

        ref, _ = jax.jit(model.decode_paged)(params, cache, batch)
        sp = jax.device_put(params, shard)
        assert sp["moe_layers"]["mlp"]["wg"].sharding.spec[1] == "model"
        with activation_sharding(mesh):
            fn = jax.jit(model.decode_paged)
            text = fn.lower(sp, cache, batch).compile().as_text()
            out, _ = fn(sp, cache, batch)
        for shape in re.findall(r"= \w+\[([\d,]*)\]\S* all-gather\(", text):
            n = int(np.prod([int(x) for x in shape.split(",") if x]))
            assert n < part, (b, shape)
        want, out = (np.asarray(a, np.float32) for a in (ref, out))
        err = np.linalg.norm(out - want) / np.linalg.norm(want)
        assert err < 0.05, (b, err)
print("ok")
"""


def test_held_experts_split_over_the_model_axis():
    """Under a (data 2, model 2) mesh the held experts stay split over
    ``model`` as the weights are laid out: the expert op equals its
    one-device result exactly, and the compiled MoE decode step gathers
    nothing as large as one shard's part of a layer's expert weights. Its
    logits agree with the one-device step to bf16 rounding of the
    partitioned attention and matmuls (1.6% relative L2 measured on the
    CPU; a shard's experts dropped or counted twice is O(1))."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], capture_output=True, text=True,
        env=dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu"),
        timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("ok")


def test_sigmoid_router_picks_by_score_plus_bias_and_weighs_by_score():
    cfg = dataclasses.replace(CFG, n_experts=4, moe_top_k=2,
                              routed_scale=2.0)
    # one token whose logits make the scores 0.9, 0.8, 0.6, 0.5; the bias
    # lifts experts 2 and 3 above 0 and 1
    scores = np.array([0.9, 0.8, 0.6, 0.5])
    logits = np.log(scores / (1 - scores))
    p = {"router": jnp.asarray(logits[None], jnp.float32),
         "router_bias": jnp.asarray([0.0, 0.0, 0.5, 0.5])}
    w, sel, probs = moe.route(p, jnp.ones((1, 1)), cfg)
    assert sorted(np.asarray(sel)[0].tolist()) == [2, 3]
    np.testing.assert_allclose(np.asarray(probs)[0], scores, rtol=1e-5)
    # the chosen scores, normalized over the two and scaled
    got = dict(zip(np.asarray(sel)[0].tolist(), np.asarray(w)[0].tolist()))
    assert got[2] == pytest.approx(2.0 * 0.6 / 1.1, rel=1e-5)
    assert got[3] == pytest.approx(2.0 * 0.5 / 1.1, rel=1e-5)


def test_absorbed_decode_equals_the_expanded_form():
    """Latent attention of the last token, decoded through the paged
    latent pool (absorbed), against the same token's row of a full
    expanded forward over the sequence: same layer weights, same inputs."""
    cfg = CFG
    lp = jax.tree.map(lambda a: a[0], _params(cfg)["moe_layers"]["attn"])
    S, P = 40, 16
    x = jax.random.normal(jax.random.key(3), (1, S, cfg.d_model)
                          ).astype(jnp.bfloat16)
    full, rows = mla.attn_forward(lp, x, cfg, jnp.arange(S))
    # the rows sit in layer 1 of a two-layer pool; layer 0 holds others
    pool = jax.random.normal(jax.random.key(4),
                             (2, 8, P, 1, mla.row_dim(cfg))).astype(jnp.bfloat16)
    table = jnp.asarray([[3, 1, 6, 2]], jnp.int32)          # 4 pages
    pos = jnp.arange(S - 1)
    pool = pool.at[1, table[0][pos // P], pos % P].set(
        rows[0, :S - 1, None].astype(jnp.bfloat16))
    out, pool = mla.attn_decode_paged(lp, x[:, S - 1:], cfg,
                                      jnp.asarray([S - 1]), pool, 1, table)
    want = np.asarray(full[0, -1], np.float32)
    got = np.asarray(out[0, 0], np.float32)
    # bf16 operands on both sides, summed in a different order: a few
    # 2^-8 of the row; the wrong scale or rope would be O(1)
    assert np.linalg.norm(got - want) < 0.03 * np.linalg.norm(want)
    # the token's own row was appended where the table points
    np.testing.assert_array_equal(
        np.asarray(pool[1, table[0, 2], (S - 1) % P]),
        np.asarray(rows[0, S - 1, None].astype(jnp.bfloat16)))


def test_expert_shares_add_up_to_the_uncut_layer():
    """EP4: the four chips' routed parts, with the shared experts (which
    every chip computes alike) counted once, give the layer that holds all
    experts."""
    cfg = dataclasses.replace(CFG, n_experts=8, moe_top_k=3)
    p = jax.tree.map(lambda a: a[0], _params(cfg)["moe_layers"]["mlp"])
    x = jax.random.normal(jax.random.key(5), (2, 9, cfg.d_model)
                          ).astype(jnp.bfloat16)
    whole, _, _ = moe.moe_mlp_forward(p, x, cfg)
    shared = ll_shared(p, x, cfg)
    parts = []
    for i in range(4):
        c = moonlight.share(i, cfg, ep=4)
        held = slice(c.expert_offset, c.expert_offset + c.n_held)
        pi = dict(p, **{k: p[k][held] for k in ("wg", "wu", "wd")})
        y, _, _ = moe.moe_mlp_forward(pi, x, c)
        parts.append(np.asarray(y, np.float32) - shared)
    total = sum(parts) + shared
    want = np.asarray(whole, np.float32)
    # each part is rounded to bf16 once: a few 2^-8 of the layer's output
    assert np.abs(total - want).max() < 0.03 * np.abs(want).max()
    assert all(np.abs(q).max() > 0 for q in parts)


def ll_shared(p, x, cfg):
    from repro.models import layers as ll

    return np.asarray(ll.mlp_forward(p["shared"], x, cfg), np.float32)


def _engine(cfg, params, **kw):
    return ServeEngine(get_model(cfg), params, n_slots=4, max_seq=128,
                       page_size=16, **kw)


def _last_prompt_logits(engine, prompt):
    seen = []
    chunk = engine._prefill_chunk

    def w(params, cache, batch, **kw):
        out = chunk(params, cache, batch, **kw)
        seen.append(np.asarray(out[0][0], np.float32))
        return out

    engine._prefill_chunk = w
    engine.submit(prompt, max_new_tokens=1)
    engine.run()
    return seen[-1]


def test_dropless_prompt_logits_do_not_depend_on_the_chunk_size():
    """Every token of a chunk reaches its experts, however many tokens of
    the chunk chose the same one: the logits of a prompt's last token do
    not change with the chunk size (bf16 rounding of attention blocks
    aside)."""
    cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=2)
    params = _params(cfg, seed=1)
    prompt = np.random.default_rng(1).integers(1, cfg.vocab_size, 70).tolist()
    a = _last_prompt_logits(_engine(cfg, params, prefill_chunk=16), prompt)
    b = _last_prompt_logits(_engine(cfg, params, prefill_chunk=64), prompt)
    assert np.linalg.norm(a - b) < 0.02 * np.linalg.norm(a)


def test_decode_lane_does_not_depend_on_its_neighbours():
    """A request decodes the same logits alone and beside three others in
    every decode step (dropless dispatch: no lane's token is ever dropped
    for another's)."""
    cfg = dataclasses.replace(CFG, experts_held=4, expert_offset=0)
    params = _params(cfg, seed=2)
    rng = np.random.default_rng(2)
    prompt = rng.integers(1, cfg.vocab_size, 30).tolist()
    others = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (20, 41, 9)]

    def run(extra):
        eng = _engine(cfg, params, prefill_chunk=32)
        rows = []
        decode = eng._decode_paged
        req = eng.submit(prompt, max_new_tokens=6)
        for p in extra:
            eng.submit(p, max_new_tokens=6)

        def d(params, cache, batch):
            out = decode(params, cache, batch)
            rows.append(np.asarray(out[0][req.slot], np.float32))
            return out

        eng._decode_paged = d
        eng.run()
        return req.generated, rows

    toks_a, rows_a = run([])
    toks_b, rows_b = run(others)
    assert toks_a == toks_b
    for a, b in zip(rows_a, rows_b):
        assert np.linalg.norm(a - b) < 1e-3 * np.linalg.norm(a)


def test_published_size_and_the_one_chip_share():
    cfg = get("moonlight-16b-a3b")
    assert ARCHS["moonlight-16b-a3b"] is cfg
    assert (cfg.n_experts, cfg.moe_top_k, cfg.n_held) == (64, 6, 64)
    assert cfg.latent_dim == 576 and mla.row_dim(cfg) == 640
    whole = cfg.param_counts()["total"]
    assert whole == pytest.approx(15.96e9, rel=2e-3)
    one = moonlight.share(0).param_counts()["total"]
    assert one == pytest.approx(5.16e9, rel=2e-3)
    # the router keeps its 64 outputs in the share
    specs = get_model(moonlight.share(3)).param_specs["moe_layers"]["mlp"]
    assert specs["router"].shape == (26, 2048, 64)
    assert specs["wg"].shape == (26, 16, 2048, 1408)


def test_latent_pool_is_one_leaf_and_speculation_is_refused():
    model = get_model(CFG)
    specs = model.paged_cache_specs(4, 9, 16)
    assert {k for k in specs if k.endswith("_pages")} == {"latent_pages"}
    assert set(specs) - {"latent_pages"} == set(moe.COUNTERS)
    assert specs["latent_pages"].shape == (CFG.n_layers, 9, 16, 1, 128)
    params = _params(CFG)
    with pytest.raises(ValueError, match="verify"):
        ServeEngine(model, params, n_slots=2, max_seq=64, page_size=16,
                    draft=model, draft_params=params)


def test_live_page_steps_counts_the_pages_live_requests_hold():
    cfg = CFG
    eng = _engine(cfg, _params(cfg), prefill_chunk=32)
    held = []
    step = eng._step

    def counted(force):
        n = step(force)
        if n:
            held.append(eng.pool.outstanding)
        return n

    eng._step = counted
    for n in (20, 50):
        eng.submit(list(range(1, n + 1)), max_new_tokens=5)
    eng.run()
    # at least one page per live request in every decode step
    assert eng.stats["decode_steps"] == len(held) > 0
    assert eng.stats["live_page_steps"] >= eng.stats["decode_steps"]
    assert eng.stats["live_page_steps"] <= eng.stats["decode_steps"] * eng.n_pages


@pytest.mark.parametrize("first,held,hit", [
    (0, 4, 2),      # both chosen experts held here
    (4, 4, 0),      # neither: the grouped matmul reads no expert
    (0, 8, 2),      # the whole layer
])
def test_expert_counters_count_what_the_grouped_matmul_read(first, held,
                                                            hit):
    """The paged cache's expert counters, as the engine's stats: every
    decode and prefill chunk dispatched is a call; each MoE layer adds the
    held experts some row chose and the rows sent to them. The router is
    zero and the bias picks experts 0 and 1 for every row, so a call of T
    rows (all lanes in decode, the padded chunk in prefill) adds ``hit``
    experts and ``hit * T`` rows per MoE layer."""
    cfg = dataclasses.replace(CFG, experts_held=held, expert_offset=first)
    params = _params(cfg)
    mlp = params["moe_layers"]["mlp"]
    mlp["router"] = jnp.zeros_like(mlp["router"])
    mlp["router_bias"] = jnp.asarray([[1.0, 1.0] + [0.0] * 6] * 2)
    n_moe = cfg.n_layers - cfg.first_k_dense

    def counted_run(eng, prompts):
        """Run ``prompts``; check what the counters gained against the
        calls dispatched."""
        calls = {"decode": 0, "prefill": 0}
        decode, chunk = eng._decode_paged, eng._prefill_chunk

        def d(*a):
            calls["decode"] += 1
            return decode(*a)

        def c(*a, **kw):
            calls["prefill"] += 1
            return chunk(*a, **kw)

        eng._decode_paged, eng._prefill_chunk = d, c
        before = dict(eng.stats)
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        eng.run()
        for kind, rows in (("decode", 4), ("prefill", 32)):
            n = calls[kind]
            got = {k: eng.stats[f"moe_{kind}_{k}"] - before[f"moe_{kind}_{k}"]
                   for k in ("calls", "hits", "rows")}
            assert n and got == {"calls": n, "hits": n * n_moe * hit,
                                 "rows": n * n_moe * hit * rows}

    eng = _engine(cfg, params, prefill_chunk=32)
    counted_run(eng, [list(range(1, n + 1)) for n in (5, 40, 70)])
    # a restored engine goes on from the snapshot's counts
    clone = _engine(cfg, params, prefill_chunk=32)
    clone.restore(eng.snapshot())
    counted_run(clone, [list(range(3, 40))])


# ---------------------------------------------------------------------------
# Donating the paged cache to the engine's jitted steps
# ---------------------------------------------------------------------------


def _pool_copies(hlo: str, pools) -> int:
    """``copy`` instructions in compiled HLO text whose result has the
    shape and dtype of one of ``pools``."""
    short = {"bfloat16": "bf16", "float32": "f32"}
    shapes = {(short[str(v.dtype)], ",".join(map(str, v.shape)))
              for v in pools}
    found = re.findall(r"=\s*(\w+)\[([\d,]*)\]\S*\s+copy\(", hlo)
    return sum(s in shapes for s in found)


@pytest.mark.parametrize("arch", ["moonlight-16b-a3b", "granite-moe-1b-a400m",
                                  "phi4-mini-3.8b"])
@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_cache_is_donated_where_the_family_carries_its_pools(arch, step):
    """The engine donates the cache to its paged steps only where the
    family's scan carries the pools whole (``ModelFns.pools_carried``):
    the output then aliases every pool, and the program has the fewest
    pool-shaped copies of the two. A dense family's scan takes the pools
    as ``xs`` and returns them as ``ys``: donated, it gains pool-sized
    copies into the aliased outputs, so it is left undonated."""
    cfg = REDUCED[arch]
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    eng = _engine(cfg, params, prefill_chunk=32)
    # one buffer cannot be donated twice: no two cache leaves share one
    assert len({v.unsafe_buffer_pointer() for v in eng.cache.values()}) \
        == len(eng.cache)
    pools = [v for k, v in eng.cache.items() if k.endswith("_pages")]
    width = eng.page_table.shape[1]
    if step == "decode":
        fn, engine_fn, kw = model.decode_paged, eng._decode_paged, {}
        batch = {"tokens": jnp.zeros((4, 1), jnp.int32),
                 "positions": jnp.zeros((4,), jnp.int32),
                 "page_table": jnp.zeros((4, width), jnp.int32)}
    else:
        fn, engine_fn, kw = model.prefill_chunk, eng._prefill_chunk, {
            "offset": 0}
        batch = {"tokens": jnp.zeros((1, 32), jnp.int32),
                 "valid": jnp.asarray(32, jnp.int32),
                 "slot": jnp.asarray(0, jnp.int32),
                 "page_table": jnp.zeros((width,), jnp.int32)}

    def compiled(f):
        return f.lower(params, eng.cache, batch, **kw).compile()

    copies = {d: _pool_copies(compiled(jax.jit(
        fn, donate_argnums=d, static_argnames=tuple(kw))).as_text(), pools)
        for d in ((), (1,))}
    got = compiled(engine_fn)
    alias = got.memory_analysis().alias_size_in_bytes
    assert _pool_copies(got.as_text(), pools) == min(copies.values())
    if model.pools_carried:
        assert alias >= sum(v.nbytes for v in pools)
    else:
        assert alias == 0
        assert copies[(1,)] > copies[()]


def _serve_snapshot_restore(new):
    """Snapshot mid-decode; the engine goes on, and a fresh engine
    restored from the blob serves the same requests to their end."""
    eng = new()
    reqs = [eng.submit(list(range(3 + i, 30 + 9 * i)), max_new_tokens=10)
            for i in range(3)]
    for _ in range(6):
        eng.step()
    assert any(r.generated for r in reqs) and not all(r.done for r in reqs)
    clone = new()
    clone.restore(eng.snapshot())
    eng.run()
    clone.run()
    return ([r.generated for r in reqs]
            + [clone.requests[r.req_id].generated for r in reqs]), {}


def _serve_cow_prefix(new):
    """A repeated page-aligned prompt hits every cached page: the last
    one is copied on write (``_copy_pages``) for the recomputed last
    token, beside a request that shares the first page only."""
    eng = new()
    prompt = list(range(5, 37))
    first = eng.submit(prompt, max_new_tokens=4)
    eng.run()
    again = [eng.submit(prompt, max_new_tokens=6),
             eng.submit(prompt[:16] + [7, 8], max_new_tokens=6)]
    eng.run()
    assert eng.stats["cow_copies"] > 0
    return [first.generated] + [r.generated for r in again], {}


def _spill_remote():
    from repro.core.cloudlet import CloudletRegistry
    from repro.core.reliability import ReliabilityRegistry
    from repro.serving.kvcache import RemotePagePool

    reg = CloudletRegistry()
    reg.create("serve", "moonlight")
    reg.join("serve", "h0")
    rel = ReliabilityRegistry()
    for h in ("h1", "h2"):
        reg.join("serve", h)
        rel.add_host(h)
    return RemotePagePool(reg, "serve", "h0", reliability=rel)


def _serve_spill_recall(new):
    """Under page pressure cold prefix pages are lent to peers, and a
    later hit recalls them (``_install_page``); decode pages stage on a
    peer as they fill (write-behind ``extract_page_payload``)."""
    eng = new(n_slots=1, max_seq=96, n_pages=6, remote_pool=_spill_remote(),
              write_behind=True)
    rng = np.random.default_rng(4)
    prefixes = [rng.integers(1, CFG.vocab_size, 32).tolist()
                for _ in range(2)]
    out = []
    for _ in range(2):
        for pref in prefixes:
            reqs = [eng.submit(pref + rng.integers(1, CFG.vocab_size,
                                                   6).tolist(),
                               max_new_tokens=12) for _ in range(2)]
            eng.run(400)
            out += [r.generated for r in reqs]
    assert eng.stats["pages_spilled"] > 0 and eng.stats["pages_recalled"] > 0
    assert eng.stats["pages_staged"] > 0
    return out, {}


def _serve_preempt_resume(new, remote):
    """A low-priority request is preempted mid-decode by a high-priority
    one and resumes: by recalling its lent chain with a spill tier, by
    re-prefilling its tokens without one."""
    from repro.serving.scheduler import SchedulerConfig

    kw = {"remote_pool": _spill_remote(), "write_behind": True} \
        if remote else {}
    eng = new(n_slots=1, max_seq=96, n_pages=12,
              scheduler=SchedulerConfig(token_budget=64, preempt_margin=2),
              **kw)
    prefix = list(range(11, 43))
    low = eng.submit(prefix + [5, 6, 7], max_new_tokens=20, priority=0)
    for _ in range(18):
        eng.step()
    assert low.slot is not None and len(low.generated) >= 2
    high = eng.submit(prefix + [9, 9], max_new_tokens=4, priority=3)
    eng.run(400)
    assert low.done and high.done and eng.stats["preemptions"] == 1
    if remote:
        assert eng.stats["pages_staged"] > 0
        assert eng.stats["recall_resumes"] == 1
    else:
        assert eng.stats["resume_mismatches"] == 0
    return [low.generated, high.generated], {}


def _serve_counters(new):
    """The expert counters read back after each decode count the same
    calls, hits and rows."""
    eng = new()
    reqs = [eng.submit(list(range(2, 20 + 13 * i)), max_new_tokens=8)
            for i in range(4)]
    for _ in range(12):
        eng.step()
    counts = {k: v for k, v in eng.stats.items() if k.startswith("moe_")}
    assert counts["moe_decode_calls"] >= 5
    eng.run()
    return [r.generated for r in reqs], counts


@pytest.mark.parametrize("scenario", [
    _serve_snapshot_restore,
    _serve_cow_prefix,
    _serve_spill_recall,
    functools.partial(_serve_preempt_resume, remote=True),
    functools.partial(_serve_preempt_resume, remote=False),
    _serve_counters,
], ids=["snapshot-restore", "cow-prefix", "spill-recall",
        "preempt-recall", "preempt-reprefill", "counters"])
def test_donated_steps_serve_what_undonated_steps_serve(scenario):
    """Every engine path that holds the paged cache (snapshot and
    restore, copy on write, spill, recall and write-behind, preemption,
    the counters' readback) serves the same tokens and counts when the
    paged steps donate the cache as when they copy it. Each donated
    decode's input cache is deleted, and no path reads it after."""
    cfg = CFG
    params = _params(cfg, seed=3)
    inputs = {True: [], False: []}

    def factory(donated):
        def new(**kw):
            kw = {"n_slots": 4, "max_seq": 128, "prefill_chunk": 32, **kw}
            eng = ServeEngine(get_model(cfg), params, page_size=16, **kw)
            if not donated:
                eng._decode_paged = jax.jit(eng.model.decode_paged)
                eng._prefill_chunk = jax.jit(eng.model.prefill_chunk,
                                             static_argnames=("offset",))
            decode = eng._decode_paged

            def seen(params, cache, batch):
                inputs[donated].append(cache["latent_pages"])
                return decode(params, cache, batch)

            eng._decode_paged = seen
            return eng
        return new

    got = scenario(factory(True))
    want = scenario(factory(False))
    assert got == want
    assert inputs[True] and all(a.is_deleted() for a in inputs[True])
    assert not any(a.is_deleted() for a in inputs[False])
