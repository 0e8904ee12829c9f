"""Architecture registry: one module per assigned architecture, and the
full-graph GNN configurations (``gcn_arxiv``; not among ``ALL_ARCHS``, the
language models the train, serve and dry-run launchers take).

``get_config(name)`` / ``--arch <id>`` resolve through here; each module also
provides ``reduced()`` — the same family at smoke-test scale.
"""
from .base import (SHAPES, GNNConfig, ModelConfig, ShapeConfig,
                   applicable_shapes, get_config, list_configs, register)
from . import (gcn_arxiv, granite_34b, hymba_1_5b, internlm2_20b,
               llama3_2_1b, phi3_vision_4_2b, qwen2_moe_a2_7b, qwen3_32b,
               qwen3_moe_30b, rwkv6_3b, whisper_small)

ALL_ARCHS = (
    "qwen3-moe-30b-a3b", "qwen2-moe-a2.7b", "qwen3-32b", "granite-34b",
    "llama3.2-1b", "internlm2-20b", "phi-3-vision-4.2b", "whisper-small",
    "rwkv6-3b", "hymba-1.5b",
)

REDUCED = {
    "qwen3-moe-30b-a3b": qwen3_moe_30b.reduced,
    "qwen2-moe-a2.7b": qwen2_moe_a2_7b.reduced,
    "qwen3-32b": qwen3_32b.reduced,
    "granite-34b": granite_34b.reduced,
    "llama3.2-1b": llama3_2_1b.reduced,
    "internlm2-20b": internlm2_20b.reduced,
    "phi-3-vision-4.2b": phi3_vision_4_2b.reduced,
    "whisper-small": whisper_small.reduced,
    "rwkv6-3b": rwkv6_3b.reduced,
    "hymba-1.5b": hymba_1_5b.reduced,
}

__all__ = ["ALL_ARCHS", "REDUCED", "SHAPES", "GNNConfig", "ModelConfig",
           "ShapeConfig", "applicable_shapes", "get_config", "list_configs",
           "register"]
