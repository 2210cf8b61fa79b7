"""Train step mechanics: microbatching equivalence, compression, optimizer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimConfig, RunConfig
from repro.configs import REDUCED
from repro.data.synthetic import SyntheticDataset
from repro.models import get_model
from repro.optim.adamw import (
    adamw_init,
    adamw_update,
    global_norm,
    lr_schedule,
)
from repro.training.state import init_train_state
from repro.training.step import make_train_step


@pytest.fixture(scope="module")
def setup():
    cfg = REDUCED["smollm-360m"]
    model = get_model(cfg)
    state = init_train_state(model, seed=0)
    ds = SyntheticDataset(cfg, 32, 4, seed=0)
    batch = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
    return cfg, model, state, batch


def test_microbatching_matches_single_batch(setup):
    cfg, model, state, batch = setup
    s1 = jax.jit(make_train_step(model, RunConfig(arch=cfg.arch_id,
                                                  microbatches=1)))
    s2 = jax.jit(make_train_step(model, RunConfig(arch=cfg.arch_id,
                                                  microbatches=2)))
    out1, m1 = s1(state, batch)
    out2, m2 = s2(state, batch)
    # microbatch-mean loss == full-batch loss (uniform token counts)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), abs=2e-3)
    for a, b in zip(jax.tree.leaves(out1["params"]),
                    jax.tree.leaves(out2["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_int8_compression_close_but_not_identical(setup):
    cfg, model, state, batch = setup
    plain = jax.jit(make_train_step(model, RunConfig(arch=cfg.arch_id)))
    comp = jax.jit(make_train_step(
        model, RunConfig(arch=cfg.arch_id, grad_compression="int8")))
    o1, m1 = plain(state, batch)
    o2, m2 = comp(state, batch)
    assert np.isfinite(float(m2["loss"]))
    # quantization perturbs the update but only slightly
    diffs = [
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree.leaves(o1["params"]),
                        jax.tree.leaves(o2["params"]))
    ]
    assert 0 < max(diffs) < 1e-2


def test_train_step_differentiates_under_the_tpu_default_backend(
        setup, monkeypatch):
    """A TPU defaults the kernel backend to ``pallas``, whose kernels have
    no VJP; the train step must still differentiate, through XLA."""
    from repro.kernels import ops

    cfg, model, state, batch = setup
    run = RunConfig(arch=cfg.arch_id)
    _, want = jax.jit(make_train_step(model, run))(state, batch)
    monkeypatch.setattr(ops, "default_backend", lambda: "pallas")
    assert ops.current_backend() == "pallas"
    _, got = jax.jit(make_train_step(model, run))(state, batch)
    assert np.isfinite(float(got["loss"]))
    assert float(got["loss"]) == float(want["loss"])


def test_grad_clipping_bounds_update(setup):
    cfg, model, state, batch = setup
    step = jax.jit(make_train_step(model, RunConfig(
        arch=cfg.arch_id,
        optim=OptimConfig(grad_clip_norm=1e-6, learning_rate=1.0),
    )))
    out, m = step(state, batch)
    # with a near-zero clip, params barely move despite lr=1
    delta = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
        for a, b in zip(jax.tree.leaves(state["params"]),
                        jax.tree.leaves(out["params"]))
    )
    assert delta < 0.2   # weight decay term only


class TestOptimizer:
    def test_schedule_warmup_and_decay(self):
        cfg = OptimConfig(learning_rate=1e-3, warmup_steps=10,
                          total_steps=100)
        lrs = [float(lr_schedule(cfg, jnp.asarray(s))) for s in
               (0, 5, 10, 50, 100)]
        assert lrs[0] == 0.0
        assert lrs[1] == pytest.approx(5e-4)
        assert lrs[2] == pytest.approx(1e-3)
        assert lrs[3] < lrs[2]
        assert lrs[4] == pytest.approx(1e-4, rel=0.01)  # 0.1 floor

    def test_adamw_moves_toward_gradient(self):
        params = {"w": jnp.ones((4,))}
        opt = adamw_init(params)
        grads = {"w": jnp.asarray([1.0, -1.0, 2.0, 0.0])}
        cfg = OptimConfig(learning_rate=0.1, warmup_steps=0,
                          weight_decay=0.0, schedule="constant")
        new, opt, info = adamw_update(params, grads, opt, cfg)
        w = np.asarray(new["w"])
        assert w[0] < 1.0 and w[1] > 1.0 and w[2] < 1.0
        assert w[3] == pytest.approx(1.0)
        assert int(opt["step"]) == 1
        assert float(info["grad_norm"]) == pytest.approx(np.sqrt(6), rel=1e-5)

    def test_global_norm(self):
        t = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
        assert float(global_norm(t)) == pytest.approx(5.0)
