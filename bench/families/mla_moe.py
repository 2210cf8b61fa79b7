"""The program's MoE transformer with latent attention
(``repro.models.moe`` with ``attn_kind="mla"``, DeepSeek-V3's block) built
from a configuration file with published key names, as one chip's share of
an expert-parallel deployment, and the weights the benchmark draws for it.

``n_routed_experts`` in the file is how many experts this chip holds;
``ep.chips`` chips share each expert layer, so the router scores
``n_routed_experts * ep.chips`` experts, and this chip holds those from
``ep.this_chip * n_routed_experts`` on. The weights are the benchmark's own:
drawn from the seed on the device in one jitted call, in bfloat16, laid out
as the program's parameter tree. The program and the reference both read
them; neither makes them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# std of the drawn ``e_score_correction_bias``: DeepSeek-V3's balancing
# rule (arXiv:2412.19437, sections 2.1.2 and 4.2) moves each expert's bias
# by 0.001 per training step toward even loads, and a router of random
# weights favours no expert, so the bias that rule holds is zero within
# about one step
BIAS_STD = 0.001


def dims(conf: dict) -> dict:
    held = conf["n_routed_experts"]
    ep = conf.get("ep", {"chips": 1, "this_chip": 0})
    r, dr = conf["kv_lora_rank"], conf["qk_rope_head_dim"]
    return {
        "L": conf["num_hidden_layers"], "d": conf["hidden_size"],
        "H": conf["num_attention_heads"], "nope": conf["qk_nope_head_dim"],
        "dr": dr, "dv": conf["v_head_dim"], "r": r, "latent": r + dr,
        "f_dense": conf["intermediate_size"], "f": conf["moe_intermediate_size"],
        "n_dense": conf["first_k_dense_replace"],
        "shared": conf["n_shared_experts"] * conf["moe_intermediate_size"],
        "E": held * ep["chips"], "E_held": held,
        "E_first": ep["this_chip"] * held, "k": conf["num_experts_per_tok"],
        "norm_topk": bool(conf["norm_topk_prob"]),
        "routed_scale": float(conf["routed_scaling_factor"]),
        "V": conf["vocab_size"], "eps": float(conf["rms_norm_eps"]),
        "theta": float(conf["rope_theta"]),
    }


def _attn(n: int, m: dict) -> dict:
    d, H, r = m["d"], m["H"], m["r"]
    return {"wq": (n, d, H, m["nope"] + m["dr"]), "wkv_a": (n, d, m["latent"]),
            "kv_norm": (n, r), "wkv_b": (n, r, H, m["nope"] + m["dv"]),
            "wo": (n, H, m["dv"], d), "ln": (n, d)}


def shapes(conf: dict) -> dict:
    """Parameter shapes in the program's tree (layer-stacked leaves)."""
    m = dims(conf)
    d, V, nd = m["d"], m["V"], m["n_dense"]
    nm = m["L"] - nd
    Eh, f, fs = m["E_held"], m["f"], m["shared"]
    out = {
        "embedding": (V, d), "final_ln": (d,), "unembed": (d, V),
        "moe_layers": {
            "attn": _attn(nm, m),
            "mlp": {"router": (nm, d, m["E"]), "router_bias": (nm, m["E"]),
                    "wg": (nm, Eh, d, f), "wu": (nm, Eh, d, f),
                    "wd": (nm, Eh, f, d), "ln": (nm, d),
                    "shared": {"wg": (nm, d, fs), "wu": (nm, d, fs),
                               "wd": (nm, fs, d)}},
        },
    }
    if nd:
        fd = m["f_dense"]
        out["dense_layers"] = {
            "attn": _attn(nd, m),
            "mlp": {"wg": (nd, d, fd), "wu": (nd, d, fd), "wd": (nd, fd, d),
                    "ln": (nd, d)}}
    return out


def _scale(path: str, shape: tuple, init_range: float):
    """The std of a leaf, or None for a norm weight (1 + 0.1 N(0, 1))."""
    leaf = path.rsplit("/", 1)[-1]
    if leaf in ("ln", "final_ln", "kv_norm"):
        return None
    if leaf in ("embedding", "unembed"):
        return init_range
    if leaf == "router_bias":
        return BIAS_STD
    fan = {"wq": shape[1], "wkv_a": shape[1], "wkv_b": shape[1],
           "wo": shape[1] * shape[2], "router": shape[1],
           "wg": shape[-2], "wu": shape[-2], "wd": shape[-2]}[leaf]
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
    return draw(jnp.uint32(seed & 0xFFFFFFFF),
                jnp.uint32((seed >> 32) & 0xFFFFFFFF))


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
    assert m["norm_topk"], "the program normalizes the chosen experts' weights"
    return ModelConfig(
        arch_id=conf["name"], family="moe", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["H"], d_ff=m["f_dense"],
        vocab_size=m["V"], attn_kind="mla", kv_lora_rank=m["r"],
        qk_nope_head_dim=m["nope"], qk_rope_head_dim=m["dr"],
        v_head_dim=m["dv"], rope_theta=m["theta"], tie_embeddings=False,
        n_experts=m["E"], n_shared_experts=conf["n_shared_experts"],
        moe_top_k=m["k"], d_expert=m["f"], first_k_dense=m["n_dense"],
        d_ff_dense=m["f_dense"], router_kind="sigmoid_bias",
        routed_scale=m["routed_scale"],
        experts_held=m["E_held"], expert_offset=m["E_first"],
        norm_eps=m["eps"], source=conf["source"],
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
    """Model FLOPs of this chip's share for one recorded engine call: two
    per weight outside the routed experts (attention projections, router,
    shared experts, the dense layer), the held experts' SwiGLU (6 d f) for
    the assignments they take in expectation (k E_held / E per token of
    every MoE layer), attention at ``H (2 (nope + rope) + 2 v)`` per
    (query, valid key) in the expanded form, and the LM head only where its
    logits are used. Padding and inactive lanes count nothing.

    ``("decode", [ctx per active lane], n)`` or
    ``("prefill", offset, valid, final)``."""
    m = dims(conf)
    held = sum(math.prod(s) for s in shapes(conf)["moe_layers"]["mlp"]
               .values() if isinstance(s, tuple) and len(s) == 4)
    dense = 2.0 * (param_count(conf, embedding=False) - held)
    routed = ((m["L"] - m["n_dense"]) * m["k"] * m["E_held"] / m["E"]
              * 6.0 * m["d"] * m["f"])
    per_token = dense + routed
    att = m["L"] * m["H"] * (2.0 * (m["nope"] + m["dr"]) + 2.0 * m["dv"])
    head = 2.0 * m["d"] * m["V"]
    if call[0] == "decode":
        return sum(per_token + att * c + head for c in call[1])
    _, off, n, final = call
    keys = n * off + n * (n + 1) / 2
    return n * per_token + att * keys + (head if final else 0.0)
