"""Family-dispatching model API.

Every architecture exposes the same four entry points regardless of family:

    init_params(cfg, rng)                     -> params pytree
    train_loss(cfg, params, batch)            -> (loss, aux)
    prefill(cfg, params, batch)               -> (cache, last_logits)
    decode_step(cfg, params, cache, tok, len) -> (cache, logits)
    init_cache(cfg, batch, max_len)           -> cache pytree

``batch`` layouts per family (all arrays sharded by the launch layer):
    dense/moe/ssm/hybrid: {tokens (B,S) i32, labels (B,S) i32}
    vlm:   + {patches (B,P,d) f32}
    audio: {frames (B,S_enc,d) f32, tokens (B,S), labels (B,S)}
    gnn:   the whole graph: {x (N,F) f32, y (N,) i32, train (N,) f32,
           key (2,) u32}; ``train_loss`` also takes the static ``graph``
           (``models.gcn.Graph``), and there is no serving path.
"""
from __future__ import annotations

from ..configs.base import ModelConfig
from . import encdec, gcn, transformer

__all__ = ["init_params", "train_loss", "prefill", "decode_step",
           "init_cache", "num_params"]


def _mod(cfg: ModelConfig):
    if cfg.family == "gnn":
        return gcn
    return encdec if cfg.family == "audio" else transformer


def init_params(cfg: ModelConfig, rng):
    return _mod(cfg).init_params(cfg, rng)


def train_loss(cfg: ModelConfig, params, batch, graph=None):
    if cfg.family == "gnn":
        return gcn.train_loss(cfg, params, batch, graph)
    return _mod(cfg).train_loss(cfg, params, batch)


def prefill(cfg: ModelConfig, params, batch):
    return _mod(cfg).prefill(cfg, params, batch)


def decode_step(cfg: ModelConfig, params, cache, tokens, length):
    return _mod(cfg).decode_step(cfg, params, cache, tokens, length)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    return _mod(cfg).init_cache(cfg, batch, max_len, dtype=dtype)


def num_params(params) -> int:
    return transformer.num_params(params)
