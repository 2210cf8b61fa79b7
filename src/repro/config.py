"""Configuration system: model configs, input shapes, run configs.

Every assigned architecture is a :class:`ModelConfig` in ``repro.configs``;
the four assigned input shapes are :data:`SHAPES`. ``(arch, shape)`` cells are
enumerated by :func:`iter_cells`, with the assignment's skip rules applied
(``long_500k`` only for sub-quadratic families).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field, replace
from typing import Any, Iterator

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------

FAMILIES = ("dense", "moe", "ssm", "hybrid", "encdec", "vlm")


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters (one instance per assigned arch)."""

    arch_id: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm

    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0  # 0 -> d_model // n_heads

    # Attention details
    attn_kind: str = "gqa"          # gqa | mla (multi-head latent attention)
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    causal: bool = True

    # MLA (DeepSeek-V2/V3 latent attention, no q-LoRA): the cache holds one
    # latent row per token, [c (kv_lora_rank) | k_pe (qk_rope_head_dim)]
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # MLP
    gated_mlp: bool = True  # SwiGLU if True, GELU MLP if False

    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    moe_top_k: int = 0
    d_expert: int = 0
    capacity_factor: float = 1.25
    first_k_dense: int = 0          # leading dense layers (deepseek-moe style)
    d_ff_dense: int = 0             # FFN width of those dense layers
    router_aux_coef: float = 0.01
    # softmax: top-k of softmax(logits); sigmoid_bias: top-k of
    # sigmoid(logits) + a per-expert correction bias, which picks but
    # never weighs (DeepSeek-V3 noaux_tc)
    router_kind: str = "softmax"
    routed_scale: float = 1.0       # routed_scaling_factor
    # the chip's expert share: experts [expert_offset, expert_offset +
    # experts_held) of the n_experts the router scores (0 = all of them)
    experts_held: int = 0
    expert_offset: int = 0

    # SSM (Mamba)
    ssm_state: int = 0
    d_conv: int = 4
    expand: int = 2
    mamba_version: int = 1          # 1 = selective scan, 2 = SSD
    ssm_head_dim: int = 64          # mamba2 head dim
    ssm_chunk: int = 256            # scan/SSD chunk length

    # Hybrid (zamba2-style): shared attention block applied every `attn_every`
    attn_every: int = 0

    # Encoder-decoder (whisper-style)
    n_encoder_layers: int = 0
    learned_positions: bool = False
    max_position: int = 0           # learned position table size (0 -> max shape seq)

    # VLM (llava-style)
    n_image_tokens: int = 0

    # Common
    norm_eps: float = 1e-5
    notes: str = ""
    source: str = ""

    # Performance knobs (hillclimbed in EXPERIMENTS.md §Perf; the defaults
    # are the paper-faithful baseline configuration)
    remat_policy: str = "full"     # full | dots | none
    seq_parallel: bool = False     # sequence-parallel residual stream
    moe_impl: str = "dense"        # dense (pjit scatter) | ep (shard_map)
    ssm_dtype: str = "f32"         # chunked-scan intermediate dtype

    # ---- derived -----------------------------------------------------------
    def __post_init__(self):
        if self.d_head == 0 and self.n_heads > 0:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)
        assert self.family in FAMILIES, self.family
        assert self.attn_kind in ("gqa", "mla"), self.attn_kind
        assert self.router_kind in ("softmax", "sigmoid_bias"), self.router_kind
        assert self.expert_offset + self.n_held <= self.n_experts, (
            f"{self.arch_id}: experts [{self.expert_offset}, "
            f"{self.expert_offset + self.n_held}) of {self.n_experts}")
        if self.n_heads:
            assert self.n_heads % max(self.n_kv_heads, 1) == 0, (
                f"{self.arch_id}: n_heads={self.n_heads} not a multiple of "
                f"n_kv_heads={self.n_kv_heads}"
            )

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def mla(self) -> bool:
        return self.attn_kind == "mla"

    @property
    def latent_dim(self) -> int:
        """Width of an MLA cache row: the latent c and the shared k_pe."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def d_inner(self) -> int:
        """Mamba inner width."""
        return self.expand * self.d_model

    @property
    def dt_rank(self) -> int:
        """Mamba1 delta-projection rank."""
        return max(1, math.ceil(self.d_model / 16))

    @property
    def n_ssm_heads(self) -> int:
        """Mamba2 head count."""
        return self.d_inner // self.ssm_head_dim

    @property
    def uses_attention(self) -> bool:
        return self.family != "ssm"

    @property
    def uses_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def sub_quadratic(self) -> bool:
        """True if the arch can run the 500k-token decode shape."""
        return self.family in ("ssm", "hybrid")

    # ---- parameter count (for MODEL_FLOPS and napkin math) ------------------
    def param_counts(self) -> dict[str, float]:
        """Analytic parameter counts: total and active-per-token."""
        d, L = self.d_model, self.n_layers
        emb = self.vocab_size * d
        unemb = 0 if self.tie_embeddings else self.vocab_size * d
        per_layer_attn = 0.0
        if self.mla:
            H, r = self.n_heads, self.kv_lora_rank
            per_layer_attn = (
                d * H * (self.qk_nope_head_dim + self.qk_rope_head_dim)
                + d * self.latent_dim
                + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                + H * self.v_head_dim * d
            )
        elif self.uses_attention:
            qkv = d * (self.n_heads + 2 * self.n_kv_heads) * self.d_head
            out = self.n_heads * self.d_head * d
            per_layer_attn = qkv + out

        def mlp_params(width: int) -> float:
            return (3 if self.gated_mlp else 2) * d * width

        total = emb + unemb
        active = emb + unemb
        if self.family == "ssm" or self.family == "hybrid":
            di = self.d_inner
            # Mamba block params (in_proj (x,z), conv, ssm params, out_proj)
            if self.mamba_version == 1:
                ssm = (
                    d * 2 * di
                    + di * self.d_conv
                    + di * (self.dt_rank + 2 * self.ssm_state)
                    + self.dt_rank * di
                    + di * self.ssm_state  # A
                    + di  # D
                    + di * d
                )
            else:
                nh = self.n_ssm_heads
                ssm = (
                    d * (2 * di + 2 * self.ssm_state * nh // max(nh, 1) * nh + nh)
                    + di * self.d_conv
                    + di * d
                )
            if self.family == "ssm":
                total += L * ssm
                active += L * ssm
            else:
                # hybrid: mamba blocks every layer + one SHARED attention+MLP
                # block applied every `attn_every` layers (zamba2: weights shared)
                shared = per_layer_attn + mlp_params(self.d_ff)
                total += L * ssm + shared
                n_apps = len(self.hybrid_attention_layers())
                active += L * ssm + n_apps * shared
        elif self.uses_moe:
            dense_layers = self.first_k_dense
            moe_layers = L - dense_layers
            router = self.n_experts * d
            experts_total = self.n_held * mlp_params(self.d_expert)
            experts_active = (self.moe_top_k * self.n_held / self.n_experts
                              * mlp_params(self.d_expert))
            shared = self.n_shared_experts * mlp_params(self.d_expert)
            dense_ff = mlp_params(self.d_ff_dense or self.d_ff)
            total += moe_layers * (per_layer_attn + router + experts_total + shared)
            total += dense_layers * (per_layer_attn + dense_ff)
            active += moe_layers * (per_layer_attn + router + experts_active + shared)
            active += dense_layers * (per_layer_attn + dense_ff)
        elif self.family == "encdec":
            enc = self.n_encoder_layers * (per_layer_attn + mlp_params(self.d_ff))
            dec = L * (2 * per_layer_attn + mlp_params(self.d_ff))  # self+cross attn
            total += enc + dec
            active += enc + dec
        else:  # dense, vlm
            per_layer = per_layer_attn + mlp_params(self.d_ff)
            total += L * per_layer
            active += L * per_layer
        return {"total": float(total), "active": float(active)}

    def hybrid_attention_layers(self) -> list[int]:
        """Layer indices at which the shared attention block is applied."""
        if self.family != "hybrid" or self.attn_every <= 0:
            return []
        return [i for i in range(self.n_layers) if i % self.attn_every == 0]


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def cell_is_valid(cfg: ModelConfig, shape: ShapeConfig) -> tuple[bool, str]:
    """Apply the assignment's skip rules. Returns (valid, reason_if_skipped)."""
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "long_500k needs sub-quadratic attention (skip per assignment)"
    return True, ""


def iter_cells(arch_ids: list[str] | None = None) -> Iterator[tuple[str, str]]:
    """Yield every valid (arch_id, shape_name) cell."""
    from repro.configs import ARCHS

    for arch_id in arch_ids or list(ARCHS):
        cfg = ARCHS[arch_id]
        for shape in SHAPES.values():
            ok, _ = cell_is_valid(cfg, shape)
            if ok:
                yield arch_id, shape.name


# ---------------------------------------------------------------------------
# Run configuration (training/serving hyper-parameters)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    weight_decay: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip_norm: float = 1.0
    z_loss_coef: float = 1e-4
    schedule: str = "cosine"  # cosine | constant


@dataclass(frozen=True)
class RunConfig:
    """Everything the launcher needs for one job."""

    arch: str
    shape: str = "train_4k"
    optim: OptimConfig = field(default_factory=OptimConfig)
    seed: int = 0

    # distribution
    multi_pod: bool = False
    remat: bool = True
    grad_compression: str = "none"  # none | int8
    microbatches: int = 1           # gradient accumulation steps

    # ad hoc cloud runtime (paper constants, §III)
    host_poll_interval_s: float = 60.0       # client polls server every 1 min
    host_failure_timeout_s: float = 120.0    # failed after 2 min of silence
    guest_probe_interval_s: float = 10.0     # VBoxManage-style guest probe
    snapshot_interval_steps: int = 50        # periodic snapshot cadence
    snapshot_target_failure: float = 0.05    # joint failure bound (≤5%)
    max_snapshot_receivers: int = 8

    def shape_config(self) -> ShapeConfig:
        return SHAPES[self.shape]


def asdict(cfg: Any) -> dict:
    return dataclasses.asdict(cfg)


def with_overrides(cfg, **kw):
    return replace(cfg, **kw)
