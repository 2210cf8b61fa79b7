"""Logical-axis → mesh-axis partition rule engine.

Every parameter/cache tensor carries a tuple of *logical axis names* (see
``repro.models.model_api``). This module maps them onto the production mesh
``(pod, data, model)`` with **divisibility-checked fallbacks**, which is what
lets ten heterogeneous architectures (15-head models, 8-KV-head GQA, 64-expert
MoE, SSM inner dims) share one distribution layer:

- primary tensor-parallel dims (``heads, kv_heads, mlp, experts, vocab,
  inner, ssm_heads, embed_model``) take ``model`` when the dim size divides
  the axis;
- if no primary dim could take ``model``, a *fallback* dim
  (``embed_in → embed_out → seq_fallback``) takes it instead (row-parallel
  weights / sequence-sharded caches);
- ``batch`` takes the combined data axes ``(pod, data)`` when divisible,
  then ``(data,)``, else stays replicated (e.g. the batch-1 500k-decode).

Activations use the same tables through :func:`shard`, a
``with_sharding_constraint`` that is a no-op unless a mesh was installed via
:func:`activation_sharding` — so model code is identical on a laptop CPU and
on 512 chips.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any

# Dims that take the "model" axis directly.
MODEL_PRIMARY = {
    "heads",
    "kv_heads",
    "mlp",
    "expert_mlp",
    "experts",
    "vocab",
    "inner",
    "ssm_heads",
    "embed_model",
    "seq_model",   # sequence parallelism: residual-stream seq dim
}

# Ordered fallback receivers of "model" when no primary dim sharded.
# "pages" lets a paged KV pool shard over physical pages when the kv-head
# count doesn't divide the model axis (pages are independent, page ids are
# global — the gather/prefetch indexes the sharded dim).
MODEL_FALLBACK = ("embed_in", "embed_out", "seq_fallback", "pages")

# Dims that never shard.
NEVER = {
    "layers", "embed", "head_dim", "state", "conv", "dt_rank", "q_per_kv",
    "null", "null_i32", "seq", "page", None,
}

DATA_AXES_PREFERENCE = (("pod", "data"), ("data",))


def axis_size(mesh: Mesh, name) -> int:
    if isinstance(name, tuple):
        return math.prod(mesh.shape[n] for n in name)
    return mesh.shape[name]


def data_axes(mesh: Mesh) -> tuple:
    for cand in DATA_AXES_PREFERENCE:
        if all(a in mesh.axis_names for a in cand):
            return cand
    return ()


def spec_for_axes(
    axes: tuple, shape: tuple[int, ...], mesh: Mesh
) -> P:
    """Resolve one tensor's logical axes to a PartitionSpec."""
    assert len(axes) == len(shape), (axes, shape)
    entries: list = [None] * len(axes)
    model_size = mesh.shape.get("model", 1) if "model" in mesh.axis_names else 1
    model_taken = False

    # pass 1: batch + primary model dims
    for i, (name, dim) in enumerate(zip(axes, shape)):
        if name == "batch":
            for cand in DATA_AXES_PREFERENCE:
                if all(a in mesh.axis_names for a in cand) and dim % axis_size(
                    mesh, cand
                ) == 0 and dim > 0:
                    entries[i] = cand if len(cand) > 1 else cand[0]
                    break
        elif name in MODEL_PRIMARY and not model_taken:
            if "model" in mesh.axis_names and dim % model_size == 0 and dim > 0:
                entries[i] = "model"
                model_taken = True

    # pass 2: model fallback
    if not model_taken and "model" in mesh.axis_names:
        for fb in MODEL_FALLBACK:
            for i, (name, dim) in enumerate(zip(axes, shape)):
                if name == fb and dim % model_size == 0 and dim > 0:
                    entries[i] = "model"
                    model_taken = True
                    break
            if model_taken:
                break

    return P(*entries)


def tree_partition_specs(axes_tree: Pytree, abstract_tree: Pytree, mesh: Mesh) -> Pytree:
    """Map trees of logical-axis tuples + shaped values to PartitionSpecs."""
    return jax.tree.map(
        lambda axes, val: spec_for_axes(tuple(axes), tuple(val.shape), mesh),
        axes_tree,
        abstract_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def tree_shardings(axes_tree: Pytree, abstract_tree: Pytree, mesh: Mesh) -> Pytree:
    specs = tree_partition_specs(axes_tree, abstract_tree, mesh)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


# ---------------------------------------------------------------------------
# Activation sharding constraints
# ---------------------------------------------------------------------------

_ACTIVE_MESH: contextvars.ContextVar[Mesh | None] = contextvars.ContextVar(
    "repro_activation_mesh", default=None
)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh | None):
    """Install a mesh so that :func:`shard` emits sharding constraints."""
    tok = _ACTIVE_MESH.set(mesh)
    try:
        yield
    finally:
        _ACTIVE_MESH.reset(tok)


def current_mesh() -> Mesh | None:
    return _ACTIVE_MESH.get()


def shard(x: jax.Array, *axes) -> jax.Array:
    """Constrain an activation's sharding by logical axis names (no-op on CPU)."""
    mesh = _ACTIVE_MESH.get()
    if mesh is None:
        return x
    spec = spec_for_axes(tuple(axes), tuple(x.shape), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
