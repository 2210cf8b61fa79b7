"""The program's dense decoder (``repro.models.transformer``) built from a
configuration file with published key names, and the weights the
benchmark draws for it.

The weights are the benchmark's own: drawn from the seed on the device in
one jitted call, in bfloat16, laid out as the program's parameter tree.
The program and the reference both read them; neither makes them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# model_type -> what the published config implies and does not state
_QK_NORM = {"phi3": False, "qwen3": True}


def dims(conf: dict) -> dict:
    d = conf["hidden_size"]
    H = conf["num_attention_heads"]
    return {
        "L": conf["num_hidden_layers"], "d": d, "H": H,
        "K": conf["num_key_value_heads"],
        "dh": conf.get("head_dim") or d // H,
        "f": conf["intermediate_size"], "V": conf["vocab_size"],
        "tied": bool(conf["tie_word_embeddings"]),
        "qk_norm": _QK_NORM[conf["model_type"]],
        "eps": float(conf["rms_norm_eps"]),
        "theta": float(conf["rope_theta"]),
    }


def shapes(conf: dict) -> dict:
    """Parameter shapes in the program's tree (layer-stacked leaves)."""
    m = dims(conf)
    L, d, H, K, dh, f, V = (m[k] for k in ("L", "d", "H", "K", "dh", "f", "V"))
    attn = {"wq": (L, d, H, dh), "wk": (L, d, K, dh), "wv": (L, d, K, dh),
            "wo": (L, H, dh, d), "ln": (L, d)}
    if m["qk_norm"]:
        attn["q_norm"] = (L, dh)
        attn["k_norm"] = (L, dh)
    out = {"embedding": (V, d), "final_ln": (d,),
           "layers": {"attn": attn,
                      "mlp": {"wg": (L, d, f), "wu": (L, d, f),
                              "wd": (L, f, d), "ln": (L, d)}}}
    if not m["tied"]:
        out["unembed"] = (d, V)
    return out


def _scale(path: str, shape: tuple, init_range: float):
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("ln", "final_ln", "q_norm", "k_norm"):
        return None                           # 1 + 0.1 N(0, 1)
    if leaf in ("embedding", "unembed"):
        return init_range
    fan = {"wq": shape[1], "wk": shape[1], "wv": shape[1],
           "wo": shape[1] * shape[2], "wg": shape[1], "wu": shape[1],
           "wd": shape[1]}[leaf]
    return fan ** -0.5


def make_params(conf: dict, seed: int):
    """Every weight from ``seed``, on the default device, in bfloat16, in
    one jitted call (seeds beyond 32 bits fold their high word in)."""
    tree = shapes(conf)
    flat, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    init_range = float(conf.get("initializer_range", 0.02))
    plan = [("/".join(str(getattr(k, "key", k)) for k in path), shp)
            for path, shp in flat]

    @jax.jit
    def draw(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        leaves = []
        for i, (path, shp) in enumerate(plan):
            z = jax.random.normal(jax.random.fold_in(key, i), shp,
                                  jnp.float32)
            s = _scale(path, shp, init_range)
            w = 1.0 + 0.1 * z if s is None else z * s
            leaves.append(w.astype(jnp.bfloat16))
        return jax.tree.unflatten(treedef, leaves)

    seed = int(seed)
    return draw(jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def param_count(conf: dict, *, embedding: bool) -> int:
    n = 0
    for path, shp in jax.tree.flatten_with_path(
            shapes(conf), is_leaf=lambda x: isinstance(x, tuple))[0]:
        name = str(getattr(path[0], "key", path[0]))
        if name in ("embedding", "unembed") and not embedding:
            continue
        n += math.prod(shp)
    return n


def program_config(conf: dict):
    from repro.config import ModelConfig

    m = dims(conf)
    return ModelConfig(
        arch_id=conf["name"], family="dense", n_layers=m["L"],
        d_model=m["d"], n_heads=m["H"], n_kv_heads=m["K"], d_head=m["dh"],
        d_ff=m["f"], vocab_size=m["V"], qk_norm=m["qk_norm"],
        rope_theta=m["theta"], tie_embeddings=m["tied"], norm_eps=m["eps"],
        source=conf["source"],
    )


def build_engine(conf: dict, params):
    """The normal serving path: the family's model functions and a paged,
    continuously batching ``ServeEngine`` with its default scheduler."""
    from repro.models import get_model
    from repro.serving.engine import ServeEngine

    model = get_model(program_config(conf))
    e = conf["engine"]
    return ServeEngine(model, params, n_slots=e["n_slots"],
                       max_seq=e["max_seq"], n_pages=e["n_pages"],
                       page_size=e["page_size"],
                       prefill_chunk=e["prefill_chunk"])


def call_flops(conf: dict, call: tuple) -> float:
    """Model FLOPs the algorithm needs for one recorded engine call: two
    per non-embedding weight per token, attention over the valid context
    (QK and PV, four per head dimension per key), and the LM head only
    where its logits are used. Padding and inactive lanes count nothing.

    ``("decode", [ctx per active lane], n)`` or
    ``("prefill", offset, valid, final)``."""
    m = dims(conf)
    dense = 2.0 * param_count(conf, embedding=False)
    att = 4.0 * m["L"] * m["H"] * m["dh"]
    head = 2.0 * m["d"] * m["V"]
    if call[0] == "decode":
        return sum(dense + att * c + head for c in call[1])
    _, off, n, final = call
    keys = n * off + n * (n + 1) / 2
    return n * dense + att * keys + (head if final else 0.0)
