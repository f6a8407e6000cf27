"""Basic Gluon layers.

Reference parity: python/mxnet/gluon/nn/basic_layers.py (Sequential, Dense,
Dropout, BatchNorm, LayerNorm, GroupNorm, InstanceNorm, Embedding, Flatten,
Lambda, identity/activation blocks). Ops lower through mx.npx to jnp/lax.
"""
from __future__ import annotations

import numpy as onp

from ... import numpy as _np
from ... import numpy_extension as npx
from ...amp import fp8 as _fp8_scope
from ...base import MXNetError
from ..block import Block, HybridBlock
from ..parameter import Parameter, Constant


class Sequential(Block):
    """Stack of Blocks (reference: basic_layers.py Sequential)."""

    def __init__(self):
        super().__init__()
        self._layers = []

    def add(self, *blocks):
        for block in blocks:
            idx = len(self._layers)
            self._layers.append(block)
            self.register_child(block, str(idx))

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        if isinstance(key, slice):
            net = type(self)()
            net.add(*list(self._children.values())[key])
            return net
        return list(self._children.values())[key]

    def __iter__(self):
        return iter(self._children.values())

    def hybridize(self, active=True, **kwargs):
        super().hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """Traceable Sequential (reference: basic_layers.py HybridSequential)."""

    def __init__(self):
        super().__init__()
        self._layers = []

    def add(self, *blocks):
        for block in blocks:
            idx = len(self._layers)
            self._layers.append(block)
            self.register_child(block, str(idx))

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x, *args)
            args = ()
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        if isinstance(key, slice):
            net = type(self)()
            net.add(*list(self._children.values())[key])
            return net
        return list(self._children.values())[key]

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully connected layer (reference: basic_layers.py Dense over
    src/operator/nn/fully_connected.cc). Weight layout (units, in_units)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0):
        super().__init__()
        self._units = units
        self._flatten = flatten
        self.weight = Parameter("weight", shape=(units, in_units),
                                dtype=dtype, init=weight_initializer,
                                allow_deferred_init=True)
        self.bias = (Parameter("bias", shape=(units,), dtype=dtype,
                               init=bias_initializer,
                               allow_deferred_init=True)
                     if use_bias else None)
        self.act = Activation(activation) if activation else None

    def forward(self, x):
        if not self.weight._shape_known():
            in_units = (int(onp.prod(x.shape[1:])) if self._flatten
                        else x.shape[-1])
            self.weight._finish_deferred_init((self._units, in_units))
        if self.bias is not None and self.bias._data is None:
            self.bias._finish_deferred_init()
        fp8 = _fp8_scope.current()
        if fp8 is not None:
            # fp8 training scope (amp/fp8.py): sites keyed by the
            # structural name collect_params assigned; non-sites (tiny
            # or aux-owned weights) fall through to the fp dense path
            site = getattr(self.weight, "_structure_name", None)
            if site in fp8.scales:
                from ...numpy.multiarray import _wrap
                raw = _fp8_scope.dense_fp8(
                    x._data, self.weight.data()._data,
                    self.bias.data()._data if self.bias is not None
                    else None, site, flatten=self._flatten)
                # the kernel accumulates and returns fp32; under mx.amp
                # the layer returns AMP's type, as the dense path it
                # replaces does (what the next op saves for its backward
                # pass is then as wide as without fp8, not twice)
                from ... import amp as _amp
                if _amp.is_active():
                    raw = raw.astype(_amp.target_dtype())
                out = _wrap(raw)
                return self.act(out) if self.act is not None else out
        out = npx.fully_connected(
            x, self.weight.data(),
            self.bias.data() if self.bias is not None else None,
            num_hidden=self._units,
            no_bias=self.bias is None, flatten=self._flatten)
        return self.act(out) if self.act is not None else out

    def __repr__(self):
        return f"Dense({self._units}, in={self.weight.shape[1]})"


class Activation(HybridBlock):
    def __init__(self, activation):
        super().__init__()
        self._act_type = activation

    def forward(self, x):
        return npx.activation(x, act_type=self._act_type)

    def __repr__(self):
        return f"Activation({self._act_type})"


class Dropout(HybridBlock):
    """Reference: basic_layers.py Dropout over src/operator/nn/dropout.cc."""

    def __init__(self, rate, axes=()):
        super().__init__()
        self._rate = rate
        self._axes = axes

    def forward(self, x):
        return npx.dropout(x, p=self._rate, axes=self._axes)


class BatchNorm(HybridBlock):
    """Reference: basic_layers.py BatchNorm over src/operator/nn/batch_norm.cc.

    gamma/beta trainable (unless scale/center False); moving stats are aux
    parameters mutated in place by npx.batch_norm during training — under
    hybridize this rides the cached-graph mutated-aux channel.
    """

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__()
        self._axis = axis
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        shape = (in_channels,)
        self.gamma = Parameter("gamma", grad_req="write" if scale else "null",
                               shape=shape, init=gamma_initializer,
                               allow_deferred_init=True)
        self.beta = Parameter("beta", grad_req="write" if center else "null",
                              shape=shape, init=beta_initializer,
                              allow_deferred_init=True)
        self.running_mean = Parameter("running_mean", grad_req="null",
                                      shape=shape,
                                      init=running_mean_initializer,
                                      allow_deferred_init=True)
        self.running_var = Parameter("running_var", grad_req="null",
                                     shape=shape,
                                     init=running_variance_initializer,
                                     allow_deferred_init=True)

    def forward(self, x):
        ch = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            if not p._shape_known():
                p._finish_deferred_init((ch,))
            elif p._data is None:
                p._finish_deferred_init()
        return npx.batch_norm(
            x, self.gamma.data(), self.beta.data(), self.running_mean.data(),
            self.running_var.data(), eps=self._epsilon,
            momentum=self._momentum, fix_gamma=not self._scale,
            use_global_stats=self._use_global_stats, axis=self._axis)


class SyncBatchNorm(BatchNorm):
    """Cross-device BatchNorm (reference: contrib SyncBatchNorm). On a
    sharded mesh the batch statistics are computed over the global batch by
    XLA automatically when the array is sharded; identical to BatchNorm."""

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, center=True, scale=True, use_global_stats=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", **kwargs):
        super().__init__(1, momentum, epsilon, center, scale,
                         use_global_stats, beta_initializer, gamma_initializer,
                         running_mean_initializer,
                         running_variance_initializer, in_channels)


class BatchNormReLU(BatchNorm):
    """Fused BatchNorm + ReLU (reference: basic_layers.py:478 BatchNormReLU
    over the batch_norm op's act_type='relu' attr). Here the relu tail is
    applied after npx.batch_norm — XLA fuses it into the single-pass BN
    scale/shift FMA, so it is one kernel on TPU like the cuDNN fused op."""

    def forward(self, x):
        return npx.relu(super().forward(x))


class Concatenate(Sequential):
    """Run children on the SAME input, concat outputs along ``axis``
    (reference: basic_layers.py:1002)."""

    def __init__(self, axis=-1):
        super().__init__()
        self._axis = axis

    def forward(self, x):
        return _np.concatenate([block(x) for block in self._children.values()],
                               axis=self._axis)


class HybridConcatenate(HybridSequential):
    """Traceable Concatenate (reference: basic_layers.py:1034)."""

    def __init__(self, axis=-1):
        super().__init__()
        self._axis = axis

    def forward(self, x):
        return _np.concatenate([block(x) for block in self._children.values()],
                               axis=self._axis)


class LayerNorm(HybridBlock):
    """Reference: basic_layers.py LayerNorm over src/operator/nn/layer_norm.cc."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._axis = axis
        self._epsilon = epsilon
        self.gamma = Parameter("gamma", grad_req="write" if scale else "null",
                               shape=(in_channels,), init=gamma_initializer,
                               allow_deferred_init=True)
        self.beta = Parameter("beta", grad_req="write" if center else "null",
                              shape=(in_channels,), init=beta_initializer,
                              allow_deferred_init=True)

    def forward(self, x):
        ch = x.shape[self._axis]
        for p in (self.gamma, self.beta):
            if not p._shape_known():
                p._finish_deferred_init((ch,))
            elif p._data is None:
                p._finish_deferred_init()
        return npx.layer_norm(x, self.gamma.data(), self.beta.data(),
                              axis=self._axis, eps=self._epsilon)


class RMSNorm(HybridBlock):
    """Root-mean-square norm over the last axis with a learned scale
    (npx.rms_norm): ``x / sqrt(mean(x^2) + eps) * gamma``, float32
    inside."""

    def __init__(self, epsilon=1e-5, gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._epsilon = epsilon
        self.gamma = Parameter("gamma", shape=(in_channels,),
                               init=gamma_initializer,
                               allow_deferred_init=True)

    def forward(self, x):
        if not self.gamma._shape_known():
            self.gamma._finish_deferred_init((x.shape[-1],))
        elif self.gamma._data is None:
            self.gamma._finish_deferred_init()
        return npx.rms_norm(x, self.gamma.data(), eps=self._epsilon)


class GroupNorm(HybridBlock):
    """Reference: basic_layers.py GroupNorm over src/operator/nn/group_norm.cc."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._num_groups = num_groups
        self._epsilon = epsilon
        self.gamma = Parameter("gamma", grad_req="write" if scale else "null",
                               shape=(in_channels,), init=gamma_initializer,
                               allow_deferred_init=True)
        self.beta = Parameter("beta", grad_req="write" if center else "null",
                              shape=(in_channels,), init=beta_initializer,
                              allow_deferred_init=True)

    def forward(self, x):
        ch = x.shape[1]
        for p in (self.gamma, self.beta):
            if not p._shape_known():
                p._finish_deferred_init((ch,))
            elif p._data is None:
                p._finish_deferred_init()
        return npx.group_norm(x, self.gamma.data(), self.beta.data(),
                              num_groups=self._num_groups, eps=self._epsilon)


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0):
        super().__init__()
        self._epsilon = epsilon
        self.gamma = Parameter("gamma", grad_req="write" if scale else "null",
                               shape=(in_channels,), init=gamma_initializer,
                               allow_deferred_init=True)
        self.beta = Parameter("beta", grad_req="write" if center else "null",
                              shape=(in_channels,), init=beta_initializer,
                              allow_deferred_init=True)

    def forward(self, x):
        ch = x.shape[1]
        for p in (self.gamma, self.beta):
            if not p._shape_known():
                p._finish_deferred_init((ch,))
            elif p._data is None:
                p._finish_deferred_init()
        return npx.instance_norm(x, self.gamma.data(), self.beta.data(),
                                 eps=self._epsilon)


class Embedding(HybridBlock):
    """Reference: basic_layers.py Embedding over indexing_op.cc.

    ``sparse_grad=True`` gives the weight a ``RowSparseNDArray`` gradient
    (O(batch) rows; see npx.embedding) feeding lazy_update optimizers and
    kvstore row_sparse push, plus row-sparse access via
    ``weight.row_sparse_data(ids)`` / ``kvstore.row_sparse_pull``."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False):
        super().__init__()
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._sparse_grad = sparse_grad
        self.weight = Parameter(
            "weight", shape=(input_dim, output_dim), dtype=dtype,
            init=weight_initializer,
            grad_stype="row_sparse" if sparse_grad else "default")

    def forward(self, x):
        if self.weight._data is None:
            self.weight._finish_deferred_init()
        return npx.embedding(x, self.weight.data(),
                             input_dim=self._input_dim,
                             output_dim=self._output_dim,
                             sparse_grad=self._sparse_grad)


class Flatten(HybridBlock):
    def __init__(self):
        super().__init__()

    def forward(self, x):
        return x.reshape(x.shape[0], -1)

    def __repr__(self):
        return "Flatten"


class Identity(HybridBlock):
    def forward(self, x):
        return x


class Lambda(Block):
    """Reference: basic_layers.py Lambda (wrap a function as a Block)."""

    def __init__(self, function):
        super().__init__()
        if isinstance(function, str):
            from ... import numpy as _np
            function = getattr(_np, function)
        self._func = function

    def forward(self, *args):
        return self._func(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function):
        super().__init__()
        if isinstance(function, str):
            from ... import numpy as _np
            function = getattr(_np, function)
        self._func = function

    def forward(self, *args):
        return self._func(*args)
