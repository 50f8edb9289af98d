"""Basic gluon layers (parity: python/mxnet/gluon/nn/basic_layers.py).

Deferred shape inference: where the reference infers `in_units`/`in_channels`
through symbolic shape propagation, each layer here implements
``_shape_hint(x, ...)`` setting parameter shapes from the first real input
(invoked by HybridBlock._deferred_infer_and_init on the first forward).
"""
from __future__ import annotations

import numpy as np

from ... import ndarray as nd
from ...base import np_dtype
from ..block import Block, HybridBlock
from ..utils import _indent
from .activations import Activation


class Sequential(Block):
    """Stacks Blocks sequentially (parity: basic_layers.py Sequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._layers = []

    def add(self, *blocks):
        for block in blocks:
            self._layers.append(block)
            self.register_child(block)

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x)
        return x

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            f"  ({key}): {_indent(repr(block), 2)}"
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())

    def hybridize(self, active=True, **kwargs):
        if self._children and all(
                isinstance(c, HybridBlock) for c in self._children.values()):
            import warnings
            warnings.warn(
                f"All children of this Sequential layer '{self.prefix}' are "
                "HybridBlocks. Consider using HybridSequential for the best "
                "performance.", stacklevel=2)
        super().hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """Stacks HybridBlocks sequentially (parity: basic_layers.py)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._layers = []

    def add(self, *blocks):
        for block in blocks:
            self._layers.append(block)
            self.register_child(block)

    def hybrid_forward(self, F, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            f"  ({key}): {_indent(repr(block), 2)}"
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __len__(self):
        return len(self._children)

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer (parity: basic_layers.py Dense; op
    FullyConnected → one MXU matmul)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._flatten = flatten
        with self.name_scope():
            self._units = units
            self._in_units = in_units
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
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def _shape_hint(self, x):
        in_units = int(np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight.shape = (self._units, in_units)

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(x, weight, *([bias] if bias is not None else []),
                               no_bias=bias is None, num_hidden=self._units,
                               flatten=self._flatten)
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return (f"{self.__class__.__name__}({shape[0]} -> "
                f"{shape[1] if len(shape) > 1 and shape[1] else None}, "
                f"{'linear' if self.act is None else self.act})")


class Dropout(HybridBlock):
    """Dropout (parity: basic_layers.py Dropout)."""

    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        if self._rate > 0:
            return F.Dropout(x, p=self._rate, axes=self._axes)
        return F._copy(x)

    def __repr__(self):
        return f"{self.__class__.__name__}(p = {self._rate}, axes={self._axes})"


class BatchNorm(HybridBlock):
    """Batch normalization (parity: basic_layers.py BatchNorm). Moving stats
    are mutated aux state — under hybridize they become extra outputs of the
    compiled step, written back after each call."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"axis": axis, "eps": epsilon, "momentum": momentum,
                        "fix_gamma": not scale,
                        "use_global_stats": use_global_stats}
        self._axis = axis
        if in_channels != 0:
            self.in_channels = in_channels
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

    def _shape_hint(self, x):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (c,)

    def cast(self, dtype):
        if np_dtype(dtype) == np.float16:
            dtype = "float32"
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        return F.BatchNorm(x, gamma, beta, running_mean, running_var,
                           name="fwd", **self._kwargs)

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return (f"{self.__class__.__name__}("
                + ", ".join(f"{k}={v}" for k, v in self._kwargs.items())
                + f", in_channels={in_channels or None})")


class Embedding(HybridBlock):
    """Turns indices into dense vectors (parity: basic_layers.py Embedding;
    op = one gather, which XLA maps to efficient dynamic-slice on TPU)."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._kwargs = {"input_dim": input_dim, "output_dim": output_dim,
                        "dtype": dtype, "sparse_grad": sparse_grad}
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim), dtype=dtype,
                init=weight_initializer, allow_deferred_init=True,
                grad_stype="row_sparse" if sparse_grad else "default")

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, **self._kwargs)

    def __repr__(self):
        return f"{self.__class__.__name__}({self._input_dim} -> {self._output_dim}, {self._kwargs['dtype']})"


class Flatten(HybridBlock):
    """Flattens to (N, -1) (parity: basic_layers.py Flatten)."""

    def hybrid_forward(self, F, x):
        return F.flatten(x)

    def __repr__(self):
        return self.__class__.__name__


class InstanceNorm(HybridBlock):
    """Instance normalization (parity: basic_layers.py InstanceNorm)."""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"eps": epsilon, "axis": axis, "center": center,
                        "scale": scale}
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

    def _shape_hint(self, x):
        c = x.shape[self._axis]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        if self._axis == 1:
            return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)
        x = x.swapaxes(1, self._axis)
        return F.InstanceNorm(x, gamma, beta,
                              eps=self._epsilon).swapaxes(1, self._axis)

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return (f"{self.__class__.__name__}("
                + ", ".join(f"{k}={v}" for k, v in self._kwargs.items())
                + f", in_channels={in_channels})")


class LayerNorm(HybridBlock):
    """Layer normalization (parity: basic_layers.py LayerNorm)."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._kwargs = {"eps": epsilon, "axis": axis, "center": center,
                        "scale": scale}
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

    def _shape_hint(self, x):
        c = x.shape[self._axis]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.LayerNorm(x, gamma, beta, axis=self._axis, eps=self._epsilon)

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return (f"{self.__class__.__name__}("
                + ", ".join(f"{k}={v}" for k, v in self._kwargs.items())
                + f", in_channels={in_channels})")


class RMSNorm(HybridBlock):
    """Root-mean-square normalization over the trailing axis with a
    learned scale (Zhang & Sennrich arXiv:1910.07467; op ``RMSNorm``).
    Called with a second input it is Mamba-2's gated norm:
    ``RMSNorm(x * silu(gate))``.  With ``num_groups`` the mean square is
    taken over each group of ``in_channels / num_groups`` consecutive
    channels."""

    def __init__(self, in_channels, epsilon=1e-5, gamma_initializer="ones",
                 num_groups=1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._epsilon, self._groups = epsilon, num_groups
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,),
                                         init=gamma_initializer)

    def hybrid_forward(self, F, x, gate=None, *, gamma):
        gates = [] if gate is None else [gate]
        return F.RMSNorm(x, gamma, *gates, eps=self._epsilon,
                         num_groups=self._groups)

    def __repr__(self):
        return (f"{self.__class__.__name__}(eps={self._epsilon}, "
                f"in_channels={self.gamma.shape[0]}, "
                f"num_groups={self._groups})")


class GroupNorm(HybridBlock):
    """Group normalization (parity: nn/basic_layers.py GroupNorm)."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._kwargs = {"eps": epsilon, "num_groups": num_groups,
                        "center": center, "scale": scale}
        self._num_groups = num_groups
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

    def _shape_hint(self, x):
        c = x.shape[1]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, x, gamma, beta):
        return F.GroupNorm(x, gamma, beta, num_groups=self._num_groups,
                           eps=self._epsilon)

    def __repr__(self):
        return (f"{self.__class__.__name__}("
                + ", ".join(f"{k}={v}" for k, v in self._kwargs.items()) + ")")


class Lambda(Block):
    """Wraps a function as a Block (parity: basic_layers.py Lambda)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            assert hasattr(nd, function), \
                f"Function name {function} is not found in ndarray."
            self._func_impl = getattr(nd, function)
            self._func_name = function
        elif callable(function):
            self._func_impl = function
            self._func_name = function.__name__
        else:
            raise ValueError(
                f"Unrecognized function in lambda: {function} of type "
                f"{type(function)}")

    def forward(self, *args):
        return self._func_impl(*args)

    def __repr__(self):
        return f"{self.__class__.__name__}({self._func_name})"


class HybridLambda(HybridBlock):
    """Wraps a function as a HybridBlock (parity: basic_layers.py)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            assert hasattr(nd, function), \
                f"Function name {function} is not found in ndarray."
            self._func = lambda F, *args: getattr(F, function)(*args)
            self._func_name = function
        elif callable(function):
            self._func = function
            self._func_name = function.__name__
        else:
            raise ValueError(
                f"Unrecognized function in lambda: {function} of type "
                f"{type(function)}")

    def hybrid_forward(self, F, x, *args):
        return self._func(F, x, *args)

    def __repr__(self):
        return f"{self.__class__.__name__}({self._func_name})"
