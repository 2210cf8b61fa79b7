"""The main path's Pallas kernels compile for a TPU v5e chip.

Each test compiles for one chip of a *described* ``v5e:2x2`` topology (no
TPU attached): the TPU compiler refuses what interpret mode accepts —
blocks not aligned to the (8, 128) tiling, more VMEM than a kernel may
scope, a program that does not fit the chip's 16 GB. Shapes are the
published widths of the models that use each kernel: phi4-mini-3.8b
(attention, RMSNorm and the whole serving step), qwen3-8b (paged decode
attention over long contexts), zamba2-1.2b (SSD),
falcon-mamba-7b (selective scan), and Moonlight-16B-A3B's one-chip EP4
share (the held experts' grouped matmuls, paged decode over the latent
pool, and its whole serving step).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get
from repro.configs.moonlight_16b_a3b import share
from repro.kernels import decode_attention, flash_attention, moe_gmm, ops
from repro.kernels import paged_decode_attention, rmsnorm, selective_scan, ssd
from repro.models import get_model

PHI4 = dict(B=8, H=24, K=8, D=128, d=3072, page=64, max_pages=32)
# the decode batch of the qwen3-8b document cell: 8 slots of 8192 tokens
QWEN3 = dict(B=8, H=32, K=8, D=128, page=64, max_pages=128, pool=321)
# the moonlight-agent-batch cell: 32 slots of 2048 positions, 641 pages of
# 640-wide latent rows (576 and padding to 128 lanes) in each of 27 layers
MOONLIGHT = dict(B=32, H=16, D=640, page=64, max_pages=32, pool=641, L=27)
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One chip's sharding, with JAX's persistent compilation cache off:
    a compile for a described chip is written there but cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_decode_attention(one_chip):
    for shape in (dict(PHI4, pool=PHI4["B"] * PHI4["max_pages"] + 1), QWEN3):
        B, H, K, D, page, mp, n = (shape[k] for k in (
            "B", "H", "K", "D", "page", "max_pages", "pool"))
        pool = _spec(one_chip, (n, page, K, D))
        _compile(
            paged_decode_attention.paged_decode_attention,
            _spec(one_chip, (B, H, D)), pool, pool,
            _spec(one_chip, (B, mp), jnp.int32),
            _spec(one_chip, (B,), jnp.int32),
        )


@pytest.mark.parametrize(
    "sq,sk,q_offset",
    # chunked prefill: a 256-token chunk at offsets 0/256/960 over its
    # page-rounded context, and a 50-token tail chunk
    [(256, 256, 0), (256, 512, 256), (256, 1216, 960), (50, 320, 256)],
)
def test_flash_attention_prefill_chunk(one_chip, sq, sk, q_offset):
    H, K, D = PHI4["H"], PHI4["K"], PHI4["D"]
    kv = _spec(one_chip, (1, sk, K, D))
    _compile(
        lambda q, k, v: flash_attention.flash_attention(
            q, k, v, causal=True, q_offset=q_offset),
        _spec(one_chip, (1, sq, H, D)), kv, kv,
    )


@pytest.mark.parametrize("rows", [(PHI4["B"], 1), (1, 256)],
                         ids=["decode", "prefill"])
def test_rmsnorm(one_chip, rows):
    _compile(
        lambda x, w: rmsnorm.rmsnorm(x, w, 1e-5),
        _spec(one_chip, (*rows, PHI4["d"])),
        _spec(one_chip, (PHI4["d"],)),
    )


def test_decode_attention(one_chip):
    B, H, K, D = (PHI4[k] for k in ("B", "H", "K", "D"))
    kv = _spec(one_chip, (B, 2048, K, D))
    _compile(decode_attention.decode_attention,
             _spec(one_chip, (B, H, D)), kv, kv,
             _spec(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("seq", [256, 50])
def test_ssd(one_chip, seq):
    cfg = get("zamba2-1.2b")
    hs = cfg.d_model * cfg.expand // cfg.ssm_head_dim
    P, N = cfg.ssm_head_dim, cfg.ssm_state
    f32 = jnp.float32
    seq_n = _spec(one_chip, (1, seq, N))
    _compile(
        lambda *a: ssd.ssd(*a, chunk=cfg.ssm_chunk),
        _spec(one_chip, (1, seq, hs, P)), _spec(one_chip, (1, seq, hs)),
        _spec(one_chip, (hs,), f32), seq_n, seq_n,
        _spec(one_chip, (hs,), f32), _spec(one_chip, (1, hs, P, N), f32),
    )


@pytest.mark.parametrize("seq", [256, 50])
def test_selective_scan(one_chip, seq):
    cfg = get("falcon-mamba-7b")
    di, N = cfg.d_model * cfg.expand, cfg.ssm_state
    f32 = jnp.float32
    chans = _spec(one_chip, (1, seq, di))
    seq_n = _spec(one_chip, (1, seq, N))
    _compile(
        lambda *a: selective_scan.selective_scan(*a, chunk=cfg.ssm_chunk),
        chans, chans, _spec(one_chip, (di, N), f32), seq_n, seq_n,
        _spec(one_chip, (di,), f32), _spec(one_chip, (1, di, N), f32),
    )


@pytest.mark.parametrize("step", ["decode_paged", "prefill_chunk"])
def test_phi4_serving_step_fits_one_chip(one_chip, step):
    """The engine's two jitted steps at phi4-mini's published size (bf16
    params, 8 slots x 2048 positions of paged KV) compile with the Pallas
    kernels and fit one chip's HBM with three KV pools live: an engine
    step dispatches a prefill chunk and then a decode step, and neither
    donates its input pool, so the old, the intermediate and the new pool
    coexist (the peak measured on a v5e)."""
    model = get_model(get("phi4-mini-3.8b"))
    B, page, mp = PHI4["B"], PHI4["page"], PHI4["max_pages"]

    def place(tree):
        return jax.tree.map(
            lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    params = place(model.abstract_params(jnp.bfloat16))
    cache = place(model.abstract_paged_cache(B, B * mp + 1, page))
    i32 = jnp.int32
    with ops.use_backend("pallas"):
        if step == "decode_paged":
            batch = {"tokens": _spec(one_chip, (B, 1), i32),
                     "positions": _spec(one_chip, (B,), i32),
                     "page_table": _spec(one_chip, (B, mp), i32)}
            compiled = _compile(model.decode_paged, params, cache, batch)
        else:
            batch = {"tokens": _spec(one_chip, (1, 256), i32),
                     "valid": _spec(one_chip, (), i32),
                     "slot": _spec(one_chip, (), i32),
                     "page_table": _spec(one_chip, (mp,), i32)}
            compiled = _compile(
                lambda p, c, b: model.prefill_chunk(p, c, b, offset=256),
                params, cache, batch)
    mem = compiled.memory_analysis()
    pool = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(cache))
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes + pool)
    assert total < V5E_HBM_BYTES, (mem, pool)


@pytest.mark.parametrize("rows", [32 * 6 + 64, 256 * 6], ids=["decode", "prefill"])
def test_moe_gmm(one_chip, rows):
    """The held experts' SwiGLU and down projection at Moonlight's widths
    (d 2048, expert width 1408, 16 experts held): the routed rows of a
    32-lane decode step and of a 256-token prefill chunk."""
    gs = _spec(one_chip, (16,), jnp.int32)
    compiled = _compile(moe_gmm.moe_gmm_swiglu, _spec(one_chip, (rows, 2048)),
                        _spec(one_chip, (16, 2048, 1408)),
                        _spec(one_chip, (16, 2048, 1408)), gs)
    assert "moe_gmm_swiglu" in compiled.as_text()
    _compile(moe_gmm.moe_gmm, _spec(one_chip, (rows, 1408)),
             _spec(one_chip, (16, 1408, 2048)), gs)


def test_paged_decode_attention_latent_pool(one_chip):
    """Absorbed latent decode: 16 query heads against one kv head of
    640-wide rows, the same pool as keys and values, every layer's pages
    in one flat pool, the softmax scale of the 192-wide heads."""
    B, H, D, page, mp, n, L = (MOONLIGHT[k] for k in (
        "B", "H", "D", "page", "max_pages", "pool", "L"))
    pool = _spec(one_chip, (L * n, page, 1, D))
    _compile(
        lambda q, k, v, t, n: paged_decode_attention.paged_decode_attention(
            q, k, v, t, n, scale=192 ** -0.5),
        _spec(one_chip, (B, H, D)), pool, pool,
        _spec(one_chip, (B, mp), jnp.int32), _spec(one_chip, (B,), jnp.int32),
    )


@pytest.mark.parametrize("step", ["decode_paged", "prefill_chunk"])
def test_moonlight_share_serving_step_fits_one_chip(one_chip, step):
    """The engine's two jitted steps for one chip's EP4 share of
    Moonlight-16B-A3B (bf16 params: 27 layers, 16 of 64 experts, the whole
    vocabulary; the latent pool of the cell) compile with the Pallas kernels
    and fit one chip's HBM beside the pool they were given."""
    model = get_model(share(0))
    B, page, mp, n = (MOONLIGHT[k] for k in ("B", "page", "max_pages", "pool"))

    def place(tree):
        return jax.tree.map(
            lambda s: _spec(one_chip, s.shape, s.dtype), tree)

    params = place(model.abstract_params(jnp.bfloat16))
    cache = place(model.abstract_paged_cache(B, n, page))
    i32 = jnp.int32
    with ops.use_backend("pallas"):
        if step == "decode_paged":
            batch = {"tokens": _spec(one_chip, (B, 1), i32),
                     "positions": _spec(one_chip, (B,), i32),
                     "page_table": _spec(one_chip, (B, mp), i32)}
            compiled = _compile(model.decode_paged, params, cache, batch)
        else:
            batch = {"tokens": _spec(one_chip, (1, 256), i32),
                     "valid": _spec(one_chip, (), i32),
                     "slot": _spec(one_chip, (), i32),
                     "page_table": _spec(one_chip, (mp,), i32)}
            compiled = _compile(
                lambda p, c, b: model.prefill_chunk(p, c, b, offset=768),
                params, cache, batch)
    text = compiled.as_text()
    assert "moe_gmm" in text and "rmsnorm" in text
    assert ("paged_decode_attention" if step == "decode_paged"
            else "flash_attention") in text
    mem = compiled.memory_analysis()
    pool = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(cache))
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes + pool)
    assert total < V5E_HBM_BYTES, (mem, pool)
