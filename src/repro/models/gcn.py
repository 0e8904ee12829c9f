"""Full-graph GCN training through the LOOPS SpMM (family ``gnn``).

The model is OGB's GCN baseline for ogbn-arxiv (Hu et al., arXiv:2005.00687,
``examples/nodeproppred/arxiv/gnn.py``; GCN: Kipf & Welling,
arXiv:1609.02907).  Each layer is a ``GCNConv``: the linear map first, then
propagation over the normalised adjacency ``Â = D^-1/2 (S + I) D^-1/2``
(``S`` the symmetrised edge pattern), so the SpMM runs at the layer's output
width.  Hidden layers are followed by batch norm in training mode (statistics
over all nodes), ReLU and dropout; the loss is the log-softmax NLL over the
train nodes.

Propagation is :func:`repro.core.spmm.loops_spmm`: the fused CSR/BCSR panel
kernels forward, and through its custom VJP the same kernels on ``Âᵀ``
backward (``dB = Âᵀ·dY``).  :func:`prepare_graph` converts ``Â`` and builds
``Âᵀ`` up front, so neither happens while a step is traced.

One departure from the baseline, which changes no result: the hidden
``GCNConv`` layers carry no bias.  Batch norm subtracts each feature's mean
over the nodes, so a per-feature bias before it cancels exactly; its
gradient is zero and Adam would move it by rounding noise alone.

A step's batch is the graph's node arrays (:func:`make_batch`): features
``x`` (N, in_dim), labels ``y`` (N,), the train mask ``train`` (N,) and the
step's dropout key ``key`` (raw key data).  Hidden layer ``i`` draws its
dropout mask from ``fold_in(key, i)``.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import GNNConfig
from ..core.formats import CSR, LoopsFormat, csr_from_coo
from ..core.spmm import loops_spmm, plan_and_convert
from ..optim.adamw import OptConfig

__all__ = ["Graph", "normalized_adjacency", "prepare_graph", "init_params",
           "forward", "train_loss", "make_batch", "opt_config"]

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Graph:
    """The static operand of a full-graph step: ``Â`` in LOOPS form, its
    ``Âᵀ`` already built under ``transpose_plan`` (``adj.transposed(plan=
    transpose_plan)``; None: the model-only plan), and the kernel backend
    (None: the engine's default)."""

    adj: LoopsFormat
    backend: str | None = None
    transpose_plan: object = None


def normalized_adjacency(rows, cols, num_nodes: int) -> CSR:
    """``Â = D^-1/2 (S + I) D^-1/2`` of the directed edges ``rows -> cols``,
    as the baseline builds it: ``S`` links two nodes that an edge joins in
    either direction, self loops are set to one (a self edge counts once),
    every entry weighs one, and ``D`` holds the row sums."""
    n = int(num_nodes)
    loop = np.arange(n, dtype=np.int64)
    r = np.concatenate([np.asarray(rows, np.int64),
                        np.asarray(cols, np.int64), loop])
    c = np.concatenate([np.asarray(cols, np.int64),
                        np.asarray(rows, np.int64), loop])
    lin = np.unique(r * n + c)
    r, c = lin // n, lin % n
    d = np.bincount(r, minlength=n).astype(np.float64) ** -0.5
    return csr_from_coo(r, c, (d[r] * d[c]).astype(np.float32), (n, n),
                        validate=None)


def prepare_graph(adj: CSR, *, backend: str | None = None,
                  tuner=None) -> Graph:
    """Plan and convert ``adj``, panelize it, and build its transposed
    format with its panels, all now: conversion is paid once, before the
    step is traced.  ``tuner`` (a :class:`repro.tune.Tuner`) plans both
    ``Â`` and ``Âᵀ`` from its measured cache, so every layer shares one
    plan each way; without it the plans come from the perf model."""
    fmt, _ = plan_and_convert(adj, tuner=tuner)
    fmt.csr_panels, fmt.bcsr_panels
    tl = fmt.transposed(tuner=tuner)
    tl.fmt.csr_panels, tl.fmt.bcsr_panels
    return Graph(adj=fmt, backend=backend,
                 transpose_plan=tl.plan if tuner is not None else None)


def init_params(cfg: GNNConfig, rng) -> dict:
    """Glorot-uniform weights, zero output bias, batch-norm scale one and
    shift zero, as the baseline initialises them."""
    dims = ([cfg.in_dim] + [cfg.hidden] * (cfg.num_layers - 1)
            + [cfg.num_classes])
    params = {}
    for i, k in enumerate(jax.random.split(rng, cfg.num_layers)):
        fan_in, fan_out = dims[i], dims[i + 1]
        lim = math.sqrt(6.0 / (fan_in + fan_out))
        layer = {"w": jax.random.uniform(k, (fan_in, fan_out), F32, -lim,
                                         lim)}
        if i < cfg.num_layers - 1:
            layer["bn_scale"] = jnp.ones((fan_out,), F32)
            layer["bn_shift"] = jnp.zeros((fan_out,), F32)
        else:
            layer["b"] = jnp.zeros((fan_out,), F32)
        params[f"conv{i}"] = layer
    return params


def _batch_norm(h, scale, shift, eps: float):
    """Training-mode batch norm over the node axis (biased variance)."""
    mean = jnp.mean(h, axis=0)
    var = jnp.mean(jnp.square(h - mean), axis=0)
    return (h - mean) * jax.lax.rsqrt(var + eps) * scale + shift


def _dropout(key, h, rate: float):
    keep = jax.random.bernoulli(key, 1.0 - rate, h.shape)
    return jnp.where(keep, h / (1.0 - rate), 0.0)


def forward(cfg: GNNConfig, params: dict, x, key, graph: Graph):
    """Logits (N, num_classes) of one training-mode pass."""
    h = x
    last = cfg.num_layers - 1
    for i in range(cfg.num_layers):
        p = params[f"conv{i}"]
        h = jnp.dot(h, p["w"], precision=cfg.precision)
        h = loops_spmm(graph.adj, h, backend=graph.backend,
                       transpose_plan=graph.transpose_plan)
        if i == last:
            return h + p["b"]
        h = _batch_norm(h, p["bn_scale"], p["bn_shift"], cfg.bn_eps)
        h = _dropout(jax.random.fold_in(key, i), jax.nn.relu(h), cfg.dropout)


def train_loss(cfg: GNNConfig, params: dict, batch: dict, graph: Graph):
    """``(loss, aux)``: the mean NLL of the log-softmax over the train
    nodes, for ``dist.step.build_train_step``."""
    key = jax.random.wrap_key_data(batch["key"])
    logits = forward(cfg, params, batch["x"], key, graph)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0]
    mask = batch["train"]
    return jnp.sum(nll * mask) / jnp.sum(mask), {}


def make_batch(x, y, train_mask, key_data) -> dict:
    """The full graph as ``build_train_step``'s batch: one microbatch of
    features, labels, train mask (float) and the step's dropout key data."""
    return {"x": x[None], "y": y[None].astype(jnp.int32),
            "train": train_mask[None].astype(F32), "key": key_data[None]}


def opt_config(cfg: GNNConfig) -> OptConfig:
    """The baseline's optimizer as ``optim.adamw`` states it: Adam (torch's
    betas and eps) at a constant ``cfg.lr``, no warm-up, decay or
    clipping."""
    return OptConfig(lr=cfg.lr, beta1=0.9, beta2=0.999, eps=1e-8,
                     weight_decay=cfg.weight_decay, clip_norm=math.inf,
                     warmup_steps=0, min_lr_frac=1.0)
