"""Model zoo (ref: python/mxnet/gluon/model_zoo/__init__.py)."""
from . import vision  # noqa: F401
from . import transformer  # noqa: F401  (TPU-first long-context family)
from . import latent_moe  # noqa: F401  (latent attention + routed experts)
