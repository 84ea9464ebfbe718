"""Contrib nn layers (ref: python/mxnet/gluon/contrib/nn/basic_layers.py)."""
from .basic_layers import (Concurrent, HybridConcurrent, Identity,
                           RoutedMoE, SparseEmbedding, SwitchMoE,
                           SyncBatchNorm)

__all__ = ["Concurrent", "HybridConcurrent", "Identity", "SparseEmbedding",
           "SyncBatchNorm", "SwitchMoE", "RoutedMoE"]
