"""moonlight-16b-a3b — DeepSeek-V3 block: latent attention + 64 routed
experts chosen by sigmoid scores with a correction bias. [hf:
moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

27L d_model=2048 16H, MLA without q-LoRA (kv_lora_rank 512, qk 128 nope +
64 rope, v 128), rope_theta 5e4; layer 0 dense (d_ff 11264), then 26
layers of 2 shared + 64 routed experts of width 1408, top-6, noaux_tc
(one group), normalized top-k weights times 2.446; vocab 163840, untied.

``CONFIG`` holds all 64 experts. One chip of the stated EP4 deployment
holds 16 of them per layer: ``share(i)``.
"""

from dataclasses import replace

from repro.config import ModelConfig

CONFIG = ModelConfig(
    arch_id="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11_264,
    vocab_size=163_840,
    attn_kind="mla",
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    n_experts=64,
    n_shared_experts=2,
    moe_top_k=6,
    d_expert=1408,
    first_k_dense=1,
    d_ff_dense=11_264,
    router_kind="sigmoid_bias",
    routed_scale=2.446,
    norm_eps=1e-5,
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
    notes="MLA + 2 shared / 64 routed top-6 sigmoid experts",
)

EP = 4   # chips sharing each expert layer in the stated deployment


def share(i: int, cfg: ModelConfig = CONFIG, ep: int = EP) -> ModelConfig:
    """Chip ``i``'s share of an ``ep``-way expert-parallel deployment."""
    n = cfg.n_experts // ep
    return replace(cfg, experts_held=n, expert_offset=i * n)


def reduced() -> ModelConfig:
    return replace(
        CONFIG,
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=96,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        d_expert=32,
        d_ff_dense=96,
        vocab_size=512,
        n_experts=8,
        moe_top_k=2,
    )
