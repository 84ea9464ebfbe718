"""Pallas TPU kernels for hot ops (SURVEY §7 stage 8).

The reference's answer to hot-spot ops was hand-written CUDA (cudnn wrappers,
fused rnn_impl.h, attention helpers); here the escape hatch below XLA is
Pallas. Kernels fall back to pure-XLA implementations when shapes or platform
don't fit, so numerics are always available on CPU test runs.
"""
from .flash_attention import flash_attention

__all__ = ["flash_attention"]
