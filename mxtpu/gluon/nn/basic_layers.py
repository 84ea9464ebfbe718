"""Basic layers: Sequential, Dense, Dropout, norms, Embedding, Flatten, Lambda
(ref: python/mxnet/gluon/nn/basic_layers.py)."""
from __future__ import annotations

from ...base import MXNetError
from ..block import Block, HybridBlock

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
           "InstanceNorm", "LayerNorm", "RMSNorm", "GatedMLP", "ShortConv", "Embedding", "Flatten", "Lambda",
           "HybridLambda", "HybridConcurrent", "Concurrent", "Identity"]


class Sequential(Block):
    """Stack of Blocks run sequentially (ref: basic_layers.py:Sequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
            if isinstance(x, (tuple, list)) and len(x) == 1:
                x = x[0]
        return x

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())

    def hybridize(self, active=True, **kwargs):
        if self._children and all(isinstance(c, HybridBlock)
                                  for c in self._children.values()):
            import warnings
            warnings.warn("All children of this Sequential layer are "
                          "HybridBlocks. Consider using HybridSequential for "
                          "the best performance.", stacklevel=2)
        super().hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """Hybridizable Sequential (ref: basic_layers.py:HybridSequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def hybrid_forward(self, F, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
            if isinstance(x, (tuple, list)) and len(x) == 1:
                x = x[0]
        return x

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer (ref: basic_layers.py:Dense; op
    src/operator/nn/fully_connected.cc). ``flatten=True`` collapses trailing dims
    like the reference; on TPU the matmul hits the MXU whole."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None, bias_initializer="zeros",
                 in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._flatten = flatten
        self._units = units
        self._in_units = in_units
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), dtype=dtype,
                init=weight_initializer, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), dtype=dtype,
                    init=bias_initializer, allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                self.act = _make_activation(activation)
            else:
                self.act = None

    def infer_shape(self, x, *args):
        if self._flatten:
            in_units = 1
            for s in x.shape[1:]:
                in_units *= s
        else:
            in_units = x.shape[-1]
        self.weight._shape_resolved((self._units, in_units))
        if self.bias is not None:
            self.bias._shape_resolved((self._units,))

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(x, weight, bias, num_hidden=self._units,
                               no_bias=bias is None, flatten=self._flatten)
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return "Dense({layout}, {act})".format(
            act=self.act if self.act else "linear",
            layout="{0} -> {1}".format(shape[1] if shape[1] else None, shape[0]))


def _make_activation(activation):
    from .activations import Activation
    if isinstance(activation, (Block,)):
        return activation
    return Activation(activation)


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        return F.Dropout(x, p=self._rate, axes=self._axes)

    def __repr__(self):
        return "Dropout(p = {}, axes={})".format(self._rate, self._axes)


class BatchNorm(HybridBlock):
    """Batch normalization with moving stats as aux params
    (ref: basic_layers.py:BatchNorm; op src/operator/nn/batch_norm.cc).
    Under a hybrid trace the moving-stat update is collected functionally
    (Parameter._update_aux) and written back after the compiled call."""

    def __init__(self, axis=None, momentum=0.9, epsilon=1e-5, center=True, scale=True,
                 use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        if axis is None:
            # reference default is axis=1 (NCHW); under mx.layout("NHWC")
            # the channel axis moves last (mxtpu/layout.py)
            from ...layout import channel_axis
            axis = channel_axis(None)
        self._kwargs = dict(axis=axis, eps=epsilon, momentum=momentum,
                            fix_gamma=not scale, use_global_stats=use_global_stats)
        self._axis = axis
        self._momentum = momentum
        self._in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)
            self.running_mean = self.params.get(
                "running_mean", grad_req="null", shape=(in_channels,),
                init=running_mean_initializer, allow_deferred_init=True,
                differentiable=False)
            self.running_var = self.params.get(
                "running_var", grad_req="null", shape=(in_channels,),
                init=running_variance_initializer, allow_deferred_init=True,
                differentiable=False)

    def infer_shape(self, x, *args):
        channels = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p._shape_resolved((channels,))

    def cast(self, dtype):
        if str(dtype).startswith("float16") or str(dtype) == "bfloat16":
            dtype = "float32"  # stats in f32 (ref: BatchNorm cast override)
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        from ... import autograd
        out = F.BatchNorm(x, gamma, beta, running_mean, running_var,
                          output_mean_var=autograd.is_training()
                          and not self._kwargs["use_global_stats"],
                          **self._kwargs)
        if isinstance(out, (list, tuple)):
            out, mean, var = out
            m = self._momentum
            self.running_mean._update_aux(running_mean * m + mean * (1 - m))
            self.running_var._update_aux(running_var * m + var * (1 - m))
        return out

    def __repr__(self):
        return "BatchNorm(axis={}, eps={}, momentum={}, in_channels={})".format(
            self._axis, self._kwargs["eps"], self._momentum, self.gamma.shape[0])


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        self._axis = axis
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True)

    def infer_shape(self, x, *args):
        channels = x.shape[self._axis]
        self.gamma._shape_resolved((channels,))
        self.beta._shape_resolved((channels,))

    def hybrid_forward(self, F, x, gamma, beta):
        if self._axis == 1:
            return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)
        x = x.swapaxes(1, self._axis)
        return F.InstanceNorm(x, gamma, beta, eps=self._epsilon).swapaxes(1, self._axis)


class LayerNorm(HybridBlock):
    """Layer normalization (ref: basic_layers.py:LayerNorm; op
    src/operator/nn/layer_norm.cc)."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True)

    def infer_shape(self, x, *args):
        channels = x.shape[self._axis]
        self.gamma._shape_resolved((channels,))
        self.beta._shape_resolved((channels,))

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._epsilon)


class RMSNorm(HybridBlock):
    """Root-mean-square normalization, a scale and no shift (op
    ``RMSNorm``; no reference counterpart). ``zero_centered``: the scale is
    ``1 + gamma`` and ``gamma`` starts at zero."""

    def __init__(self, axis=-1, epsilon=1e-6, gamma_initializer=None,
                 in_channels=0, zero_centered=False, **kwargs):
        super().__init__(**kwargs)
        self._axis = axis
        self._epsilon = epsilon
        self._zero_centered = zero_centered
        if gamma_initializer is None:
            gamma_initializer = "zeros" if zero_centered else "ones"
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma._shape_resolved((x.shape[self._axis],))

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, axis=self._axis, eps=self._epsilon,
                         zero_centered=self._zero_centered)


class GatedMLP(HybridBlock):
    """``down(act(gate(x)) * up(x))`` without biases: the gated
    feed-forward of Shazeer (arXiv:2002.05202); SwiGLU with the default
    ``silu``. Acts on the last axis."""

    def __init__(self, units, hidden_units, activation="silu", **kwargs):
        super().__init__(**kwargs)
        self._activation = activation
        with self.name_scope():
            self.gate = Dense(hidden_units, use_bias=False, flatten=False,
                              prefix="gate_")
            self.up = Dense(hidden_units, use_bias=False, flatten=False,
                            prefix="up_")
            self.down = Dense(units, use_bias=False, flatten=False,
                              prefix="down_")

    def hybrid_forward(self, F, x):
        return self.down(F.Activation(self.gate(x),
                                      act_type=self._activation) * self.up(x))


class ShortConv(HybridBlock):
    """The gated short-convolution operator of LFM2 (Liquid AI,
    ``model_type: lfm2``): ``(B, C, x) = split3(in_proj(u))``; ``y =
    out_proj(C * conv(B * x))`` with ``conv`` a depthwise causal filter of
    ``kernel_size`` taps along the sequence axis (-2). No bias, no
    activation (op ``_contrib_short_conv``; no reference counterpart)."""

    def __init__(self, units, kernel_size=3, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.weight = self.params.get("weight",
                                          shape=(units, kernel_size))
            self.in_proj = Dense(3 * units, use_bias=False, flatten=False,
                                 prefix="in_")
            self.out_proj = Dense(units, use_bias=False, flatten=False,
                                  prefix="out_")

    def hybrid_forward(self, F, x, weight):
        return self.out_proj(F._contrib_short_conv(self.in_proj(x), weight))


class Embedding(HybridBlock):
    """Index → vector lookup (ref: basic_layers.py:Embedding; op
    src/operator/tensor/indexing_op.h). ``sparse_grad`` maps to a row-sparse
    gradient in the reference; on TPU gradients stay dense (scatter-add fuses on
    XLA) and the flag is accepted for API parity."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._sparse_grad = sparse_grad
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim), dtype=dtype,
                init=weight_initializer,
                grad_stype="row_sparse" if sparse_grad else "default")

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim)

    def __repr__(self):
        return "Embedding({} -> {}, {})".format(
            self._input_dim, self._output_dim, self.weight.dtype)


class Flatten(HybridBlock):
    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def hybrid_forward(self, F, x):
        return x.flatten()

    def __repr__(self):
        return "Flatten"


class Lambda(Block):
    """Wrap a function as a Block (ref: basic_layers.py:Lambda)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as nd
            if not hasattr(nd, function):
                raise MXNetError("Function name %s is not found in mx.nd." % function)
            self._func_impl = getattr(nd, function)
            self._func_name = function
        else:
            self._func_impl = function
            self._func_name = getattr(function, "__name__", "custom")

    def forward(self, *args):
        return self._func_impl(*args)

    def __repr__(self):
        return "Lambda({})".format(self._func_name)


class HybridLambda(HybridBlock):
    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as nd
            if not hasattr(nd, function):
                raise MXNetError("Function name %s is not found in mx.nd." % function)
            fn = getattr(nd, function)
            self._func = lambda F, *args: fn(*args)
            self._func_name = function
        else:
            self._func = function
            self._func_name = getattr(function, "__name__", "custom")

    def hybrid_forward(self, F, *args):
        return self._func(F, *args)

    def __repr__(self):
        return "HybridLambda({})".format(self._func_name)


class Concurrent(Sequential):
    """Run children on the same input, concat outputs on ``axis``
    (ref: python/mxnet/gluon/contrib/nn/basic_layers.py:Concurrent)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def forward(self, x):
        from ... import ndarray as nd
        return nd.concat(*[block(x) for block in self._children.values()],
                         dim=self.axis)


class HybridConcurrent(HybridSequential):
    """Hybridizable Concurrent (ref: contrib/nn/basic_layers.py:HybridConcurrent)."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def hybrid_forward(self, F, x):
        return F.concat(*[block(x) for block in self._children.values()],
                        dim=self.axis)


class Identity(HybridBlock):
    """Identity mapping (ref: contrib/nn/basic_layers.py:Identity)."""

    def hybrid_forward(self, F, x):
        return x
