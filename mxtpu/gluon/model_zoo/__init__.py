"""Model zoo (ref: python/mxnet/gluon/model_zoo/__init__.py)."""
from . import vision  # noqa: F401
from . import transformer  # noqa: F401  (TPU-first long-context family)
from . import hybrid_lm  # noqa: F401  (per-layer operators: conv | attention)
from . import latent_moe  # noqa: F401  (latent attention + routed experts)
