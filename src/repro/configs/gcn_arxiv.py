"""OGB's GCN baseline for ogbn-arxiv, trained full-graph.

Sources: Hu et al., "Open Graph Benchmark" (arXiv:2005.00687) and its
``examples/nodeproppred/arxiv/gnn.py``; GCN is Kipf & Welling
(arXiv:1609.02907).  Three ``GCNConv`` layers 128 -> 256 -> 256 -> 40, batch
norm, ReLU and dropout 0.5 after each hidden layer, log-softmax NLL on the
train nodes, Adam at lr 0.01, on the symmetrised graph with self loops and
``D^-1/2 (A+I) D^-1/2`` normalisation (``repro.models.gcn``).

``GRAPH`` holds ogbn-arxiv's published counts: nodes, directed edges before
symmetrising, feature width, classes and the train/valid/test split.
"""
import dataclasses

from .base import GNNConfig, register

GRAPH = {"nodes": 169_343, "edges": 1_166_243, "features": 128,
         "classes": 40, "split": (90_941, 29_799, 48_603)}

CONFIG = register(GNNConfig(
    name="gcn-arxiv", num_layers=3, in_dim=GRAPH["features"], hidden=256,
    num_classes=GRAPH["classes"], dropout=0.5, lr=0.01,
    notes="OGB ogbn-arxiv GCN baseline (arXiv:2005.00687)"))


def reduced() -> GNNConfig:
    """The same model at smoke-test widths."""
    return dataclasses.replace(CONFIG, name="gcn-arxiv-reduced", in_dim=16,
                               hidden=32, num_classes=8)
