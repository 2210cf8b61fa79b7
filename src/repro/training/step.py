"""The jitted train step: loss → grads → (compressed) reduce → clip → AdamW.

Supports gradient accumulation (microbatching) via an inner ``lax.scan`` —
also the mechanism straggler mitigation uses to rebalance work away from
suspended hosts (see ``repro.training.straggler``).

The step always traces its ops with the ``xla`` kernel backend: the Pallas
kernels have no VJP, and a TPU defaults to them for serving.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import RunConfig
from repro.kernels import ops
from repro.models.model_api import ModelFns
from repro.optim import adamw_update
from repro.parallel import tracing
from repro.parallel.collectives import compress_grads


def make_train_step(model: ModelFns, run: RunConfig):
    """Returns train_step(state, batch) -> (state, metrics)."""

    def loss_fn(params, batch):
        loss, aux = model.loss(params, batch)
        return loss, aux

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def one_micro(params, batch):
        (loss, aux), grads = grad_fn(params, batch)
        return loss, aux, grads

    def train_step(state, batch):
        with ops.use_backend("xla"):
            return _train_step(state, batch)

    def _train_step(state, batch):
        params = state["params"]
        rng = jax.random.wrap_key_data(state["rng"])
        rng, comp_key = jax.random.split(rng)

        n = run.microbatches
        if n > 1:
            def split(x):
                b = x.shape[0]
                return x.reshape(n, b // n, *x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc_step(carry, mb):
                loss_a, grads_a = carry
                loss, aux, grads = one_micro(params, mb)
                grads_a = jax.tree.map(jnp.add, grads_a, grads)
                return (loss_a + loss, grads_a), aux

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            (loss_sum, grads), auxs = jax.lax.scan(
                acc_step, (jnp.zeros(()), zero_grads), micro,
                unroll=tracing.scan_unroll(),
            )
            loss = loss_sum / n
            grads = jax.tree.map(lambda g: g / n, grads)
            aux = jax.tree.map(lambda a: a[-1], auxs)
        else:
            loss, aux, grads = one_micro(params, batch)

        grads = compress_grads(grads, comp_key, run.grad_compression)
        new_params, new_opt, info = adamw_update(
            params, grads, state["opt"], run.optim
        )
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "rng": jax.random.key_data(rng),
            "data_step": state["data_step"] + 1,
        }
        metrics = {"loss": loss, **info, **aux}
        return new_state, metrics

    return train_step
