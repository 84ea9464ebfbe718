"""mxtpu: a TPU-native deep-learning framework with MXNet's capabilities.

A ground-up re-design of the reference (Apache MXNet ~1.3, /root/reference) for
TPU/XLA: the dependency-scheduling engine becomes PJRT async dispatch, the NNVM
graph executor becomes a jit-compile cache, the CUDA/cuDNN operator library becomes
XLA lowerings + Pallas kernels, and the NCCL/parameter-server KVStore becomes XLA
collectives over the device mesh. See SURVEY.md at the repo root for the layer map.

Use ``import mxtpu as mx`` — the namespace mirrors ``import mxnet as mx``.
"""

import os as _os
import time as _time

# the start of ``mxtpu.import``, the restart's first span: recorded at the
# last line of this file, once the telemetry module is there to take it
_T0_NS = _time.perf_counter_ns()

import jax as _jax  # noqa: E402

# float32 contractions stay honest f32 (without this, JAX's default silently
# downcasts f32 matmuls to one-pass bf16, breaking reference-parity numerics —
# MXNet computes f32 in f32). bfloat16 contractions do NOT inherit this
# global: every op passes an explicit per-operand override
# (mxtpu/ops/precision_util.py) choosing DEFAULT precision plus an f32
# accumulator output — the measured-fastest MXU schedule (PERF.md; the
# earlier claim that HIGHEST-on-bf16 cost 3-6x was retracted there).
_jax.config.update("jax_default_matmul_precision", "float32")

from . import base
from . import context  # module alias (ref: mxnet/context.py)
from .base import Context, MXNetError, cpu, current_context, gpu, num_gpus, tpu
# stdlib-only, imported FIRST among the framework modules: every later
# module (ndarray's d2h counter, the trainer's step phases) may hook it
from . import telemetry
# JAX's compile events reach the ring and the ``compile.*`` counters from
# here on: the programs a restart compiles before its first span (parameter
# load, optimizer state) are part of its account
telemetry.watch_compiles()
from . import perf_model
from . import xprof
from . import autograd
from .layout import layout
from . import random
from . import ndarray
from . import ndarray as nd  # mx.nd alias
from .ndarray import NDArray
from . import ops
from . import initializer
from . import initializer as init  # mx.init alias
from . import lr_scheduler
from . import optimizer
from . import metric
from . import kvstore
from . import kvstore as kv  # mx.kv alias
from . import symbol
from . import symbol as sym  # mx.sym alias
from . import io
from . import recordio
from . import image
from . import profiler

# MXNET_PROFILER_AUTOSTART parity (ref docs/faq/env_var.md:152): profile
# the whole program without code changes; dump lands in profile.json at
# exit. Both the native and the reference env names are honored.
if _os.environ.get("MXTPU_PROFILER_AUTOSTART",
                   _os.environ.get("MXNET_PROFILER_AUTOSTART", "0")) == "1":
    import atexit as _atexit

    profiler.set_config(filename="profile.json")
    profiler.start()
    _atexit.register(lambda: (profiler.stop(), profiler.dump()))
from . import model
from . import callback
from . import monitor
from .monitor import Monitor
from . import module
from . import module as mod  # mx.mod alias
from . import executor  # mx.executor.Executor spelling (ref: executor.py)
from .module import Module
from . import gluon
from . import operator
from . import contrib
from . import rnn
from . import parallel
from . import fleet
from . import serving
from . import rtc
from . import libinfo
from .libinfo import __version__, feature_list
from . import test_utils
from . import name
from . import attribute
from .attribute import AttrScope
from . import registry
from . import engine
from . import util
from . import visualization
from . import visualization as viz  # mx.viz alias
from . import kvstore_server
from . import executor_manager
from . import log
from . import torch_interop
# reference import hook (kvstore_server.py:75): a DMLC_ROLE=server process
# must fail fast with the migration note, not silently join as a worker
kvstore_server._init_kvstore_server_module()
# JAX's own import is inside only where the caller had not imported it yet
telemetry.record_interval("mxtpu.import", _T0_NS, cat="setup")
