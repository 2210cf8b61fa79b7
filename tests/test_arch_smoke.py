"""Per-architecture smoke tests: reduced config, one train step on CPU,
shape + finiteness assertions; plus prefill/decode consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import RunConfig
from repro.configs import ARCHS, REDUCED
from repro.data.synthetic import SyntheticDataset
from repro.models import get_model
from repro.training.state import init_train_state
from repro.training.step import make_train_step

ALL_ARCHS = sorted(ARCHS)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_train_step_finite(arch):
    cfg = REDUCED[arch]
    model = get_model(cfg)
    state = init_train_state(model, seed=0)
    step = jax.jit(make_train_step(model, RunConfig(arch=arch)))
    ds = SyntheticDataset(cfg, seq_len=32, global_batch=2)
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    new_state, metrics = step(state, batch)
    loss = float(np.asarray(metrics["loss"]))
    assert np.isfinite(loss) and loss > 0
    assert int(new_state["data_step"]) == 1
    # params actually moved
    moved = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree.leaves(state["params"]),
            jax.tree.leaves(new_state["params"]),
        )
    )
    assert moved
    # loss decreases over a few steps on the learnable synthetic stream
    s = new_state
    first = loss
    for i in range(1, 6):
        batch = {k: jnp.asarray(v) for k, v in ds.batch(i).items()}
        s, metrics = step(s, batch)
    assert float(np.asarray(metrics["loss"])) < first + 0.5


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_full_config_matches_assignment(arch):
    cfg = ARCHS[arch]
    # spot figures from the assignment table
    figures = {
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200_064),
        "qwen3-8b": (36, 4096, 32, 8, 12_288, 151_936),
        "smollm-360m": (32, 960, 15, 5, 2560, 49_152),
        "minitron-4b": (32, 3072, 24, 8, 9216, 256_000),
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65_024),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14_336, 32_000),
        "granite-moe-1b-a400m": (24, 1024, 16, 8, 512, 49_155),
        "deepseek-moe-16b": (28, 2048, 16, 16, 1408, 102_400),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32_000),
        "whisper-medium": (24, 1024, 16, 16, 4096, 51_865),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11_264, 163_840),
    }
    L, d, h, kv, ff, v = figures[arch]
    assert cfg.n_layers == L and cfg.d_model == d and cfg.vocab_size == v
    if h:
        assert cfg.n_heads == h and cfg.n_kv_heads == kv
    if ff:
        assert cfg.d_ff == ff
    if arch == "granite-moe-1b-a400m":
        assert cfg.n_experts == 32 and cfg.moe_top_k == 8
    if arch == "deepseek-moe-16b":
        assert (cfg.n_experts, cfg.moe_top_k, cfg.n_shared_experts) == (64, 6, 2)
    if arch == "falcon-mamba-7b":
        assert cfg.ssm_state == 16 and cfg.family == "ssm"
    if arch == "zamba2-1.2b":
        assert cfg.ssm_state == 64 and cfg.family == "hybrid"


@pytest.mark.parametrize(
    "arch",
    ["qwen3-8b", "falcon-mamba-7b", "zamba2-1.2b", "deepseek-moe-16b",
     "whisper-medium"],
)
def test_prefill_then_decode_matches_fullseq(arch):
    """Greedy next-token from (prefill + decode_step) must equal the one
    from running the longer sequence through prefill directly."""
    cfg = REDUCED[arch]
    if cfg.uses_moe:
        # capacity dropping makes incremental vs full-seq outputs diverge
        # by construction; raise capacity so no token is dropped
        import dataclasses

        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = get_model(cfg)
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(0)
    S = 16
    toks = rng.integers(1, cfg.vocab_size, (2, S + 1)).astype(np.int32)

    extra = {}
    if cfg.family == "encdec":
        frames = rng.standard_normal((2, 16, cfg.d_model)).astype(np.float32)
        extra["frames"] = jnp.asarray(frames)

    # full prefill over S+1 tokens -> logits for the last position
    logits_full, _ = model.prefill(
        params, {"tokens": jnp.asarray(toks), **extra}
    )
    # prefill S tokens, then decode token S
    logits_p, cache = model.prefill(
        params, {"tokens": jnp.asarray(toks[:, :S]), **extra}
    )
    # grow only the *self-attention* caches so position S is writable;
    # SSM/conv states and cross-attention caches keep their true shapes
    cache = {
        k: _pad_cache_seq(v, S + 8)
        if k in ("k", "v", "att_k", "att_v", "self_k", "self_v") else v
        for k, v in cache.items()
    }
    logits_d, _ = model.decode_step(
        params,
        cache,
        {
            "tokens": jnp.asarray(toks[:, S:S + 1]),
            "positions": jnp.full((2,), S, jnp.int32),
        },
    )
    np.testing.assert_allclose(
        np.asarray(logits_d), np.asarray(logits_full), atol=2e-2, rtol=2e-2
    )


def _pad_cache_seq(c, target):
    """Pad attention caches (layers, B, S, K, D) along S; leave SSM/conv
    states untouched (their dims are not seq-sized)."""
    if c.ndim == 5 and c.shape[2] < target:  # (L, B, S, K, D)
        pad = [(0, 0)] * 5
        pad[2] = (0, target - c.shape[2])
        return jnp.pad(c, pad)
    return c


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_input_specs_cover_all_shapes(arch):
    from repro.config import SHAPES, cell_is_valid

    cfg = ARCHS[arch]
    model = get_model(cfg)
    for shape in SHAPES.values():
        ok, _ = cell_is_valid(cfg, shape)
        if not ok:
            continue
        specs = model.input_specs(shape)
        assert "tokens" in specs
        tokens = specs["tokens"]
        if shape.kind == "decode":
            assert tokens.shape == (shape.global_batch, 1)
            assert "positions" in specs
        else:
            assert tokens.shape[0] == shape.global_batch


def test_param_counts_scale():
    """Analytic parameter counts are in the right ballpark for the
    published sizes (names encode them)."""
    expect = {
        "phi4-mini-3.8b": 3.8e9, "qwen3-8b": 8e9, "smollm-360m": 3.6e8,
        "minitron-4b": 4e9, "falcon-mamba-7b": 7e9,
        "llava-next-mistral-7b": 7e9, "deepseek-moe-16b": 16e9,
        "zamba2-1.2b": 1.2e9,
    }
    for arch, n in expect.items():
        total = ARCHS[arch].param_counts()["total"]
        assert 0.5 * n < total < 1.7 * n, (arch, total, n)
    # MoE: active far below total
    ds = ARCHS["deepseek-moe-16b"].param_counts()
    assert ds["active"] < 0.35 * ds["total"]
