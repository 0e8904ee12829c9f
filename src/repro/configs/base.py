"""Model/run configuration dataclasses + the architecture registry."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax.numpy as jnp

__all__ = ["ModelConfig", "GNNConfig", "ShapeConfig", "SHAPES", "register",
           "get_config", "list_configs"]


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description.  One instance per assigned arch (exact
    literature values) plus reduced variants for smoke tests."""

    name: str
    family: str               # dense | moe | vlm | audio | ssm | hybrid
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0          # 0 -> d_model // num_heads
    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    # --- attention flavour ---
    qk_norm: bool = False
    rope_theta: float = 1_000_000.0
    sliding_window: int = 0    # 0 = full attention
    # --- ssm / hybrid ---
    ssm_state: int = 0
    rwkv_head_dim: int = 64
    # --- encoder-decoder (audio) ---
    encoder_layers: int = 0
    encoder_seq: int = 0       # fixed encoder length (whisper: 1500 frames)
    # --- frontends ---
    frontend: str = "none"     # none | audio_stub | vision_stub
    num_patches: int = 0       # vision_stub prefix length
    # --- misc ---
    norm: str = "rmsnorm"      # rmsnorm | layernorm
    act: str = "swiglu"        # swiglu | gelu
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # sub-quadratic? (drives the long_500k skip rule)
    subquadratic: bool = False
    # loss / dispatch tuning
    ce_chunk: int = 2048
    capacity_factor: float = 1.25
    # attention schedule: "triangular" (§Perf default: unrolled causal
    # frontier + window span slicing — exact same math, ~2x less score
    # traffic, ~13x for sliding windows) or "full" (all chunk pairs, masked
    # — the pre-optimization baseline, kept for ablation)
    attn_schedule: str = "triangular"
    # TP rule: "kv_aligned" (§Perf default: replicate head-misaligned
    # projections so attention stays local) or "naive" (shard flattened
    # projections blindly — baseline)
    tp_rule: str = "kv_aligned"
    # MoE dispatch: "gather" (1-D int scatters + gathers; §Perf) or
    # "scatter" (naive wide buf.at[].set / out.at[].add — ablation only)
    moe_dispatch: str = "gather"
    # §Perf: force the attention input to be model-replicated (one reshard
    # per layer instead of per-score-tile all-reduces; for head counts that
    # do not divide the model axis)
    replicate_attn_input: bool = False
    notes: str = ""

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def vocab_padded(self, multiple: int = 256) -> int:
        return ((self.vocab_size + multiple - 1) // multiple) * multiple

    @property
    def rwkv_heads(self) -> int:
        return self.d_model // self.rwkv_head_dim


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    """Full-graph GNN description (family ``gnn``): ``num_layers`` graph
    convolutions ``in_dim -> hidden -> ... -> num_classes``, each hidden one
    followed by batch norm, ReLU and dropout, trained by Adam at ``lr``.
    ``precision`` is the dense matmuls' (fp32 throughout)."""

    name: str
    num_layers: int
    in_dim: int
    hidden: int
    num_classes: int
    dropout: float = 0.5
    bn_eps: float = 1e-5
    lr: float = 0.01
    weight_decay: float = 0.0
    precision: str = "highest"
    family: str = "gnn"
    notes: str = ""


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One assigned input-shape cell."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

_REGISTRY: Dict[str, ModelConfig] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        # populate registry lazily
        from . import ALL_ARCHS  # noqa: F401
    return _REGISTRY[name]


def list_configs() -> Tuple[str, ...]:
    from . import ALL_ARCHS  # noqa: F401
    return tuple(sorted(_REGISTRY))


def applicable_shapes(cfg: ModelConfig) -> Tuple[str, ...]:
    """The assignment's skip rules: long_500k only for sub-quadratic archs."""
    names = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.subquadratic:
        names.append("long_500k")
    return tuple(names)
