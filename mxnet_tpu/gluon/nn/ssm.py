"""State-space mixers.

Reference parity: none (the reference has no state-space layer).

``Mamba2Mixer`` is the mixer of Mamba-2 / Nemotron-H: a sequence mixer
that carries a state instead of attending over keys.  Its device
functions are in ``ops/ssm.py`` (imported where ``forward`` runs, so a
model without a mixer never loads them); ``docs/STATE_SPACE.md`` has the
equations as built.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ... import initializer as _init
from ... import random as _random
from ...numpy.multiarray import _invoke
from ..block import HybridBlock
from ..parameter import Parameter
from .basic_layers import Dense


class _StepBias(_init.Initializer):
    """``dt_bias``: the inverse softplus of a step size drawn log-uniform
    in ``[lo, hi]`` and held above ``floor`` (Mamba-2's own start)."""

    def __init__(self, lo, hi, floor):
        super().__init__(lo=lo, hi=hi, floor=floor)
        self._lo, self._hi, self._floor = lo, hi, floor

    def init_weight(self, name, arr):
        u = jax.random.uniform(_random._next_key(), arr.shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(
            u * (math.log(self._hi) - math.log(self._lo))
            + math.log(self._lo)), self._floor)
        arr._rebind((dt + jnp.log(-jnp.expm1(-dt))).astype(arr.dtype))


class _LogRate(_init.Initializer):
    """``A_log``: the log of a decay rate drawn uniform in ``[lo, hi]``."""

    def __init__(self, lo=1.0, hi=16.0):
        super().__init__(lo=lo, hi=hi)
        self._lo, self._hi = lo, hi

    def init_weight(self, name, arr):
        arr._rebind(jnp.log(jax.random.uniform(
            _random._next_key(), arr.shape, jnp.float32, self._lo,
            self._hi)).astype(arr.dtype))


class Mamba2Mixer(HybridBlock):
    """Mamba-2's mixer on (batch, seq, units), causal, no biases but the
    convolution's.  With ``H = num_heads`` heads of ``P = head_dim``
    channels (``d_in = H P``), ``G = num_groups`` groups of B / C and a
    state of ``N = state_size``:

    ``[z | xBC | dt] = u W_in`` (widths ``d_in | d_in + 2 G N | H``);
    ``xBC = silu(conv(xBC))``, a causal depthwise convolution of
    ``conv_kernel`` taps with a bias; ``xBC`` splits into ``x (H, P)``,
    ``B (G, N)``, ``C (G, N)``; ``D_t = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)``, one scalar a head; per head
    ``S_t = exp(D_t A) S_{t-1} + D_t x_t (x) B_t``, ``y_t = S_t C_t +
    D x_t`` (head ``h`` reads group ``h // (H / G)``); then
    ``y = RMSNorm_grouped(y * silu(z)) * norm_gamma`` — the gate first,
    the mean square over each of the ``G`` groups of ``d_in / G``
    channels — and ``out = y W_out``.

    ``chunk_size`` is how the scan is blocked (``ops.ssm.ssd_scan``) and
    changes no value.  The log-decays, the state and the norm are
    float32; the projections and the scan's products take AMP's type
    where it is on.  Training path only: no recurrent or convolution
    state is kept between calls.
    """

    def __init__(self, units, num_heads, head_dim, num_groups, state_size,
                 conv_kernel=4, chunk_size=128, epsilon=1e-5,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4):
        super().__init__()
        if num_heads % num_groups:
            raise ValueError(f"{num_heads} heads do not group over "
                             f"{num_groups} B / C groups")
        self._heads, self._dim = num_heads, head_dim
        self._groups, self._state = num_groups, state_size
        self._chunk, self._eps = chunk_size, epsilon
        d_in = num_heads * head_dim
        conv = d_in + 2 * num_groups * state_size
        self.in_proj = Dense(d_in + conv + num_heads, use_bias=False,
                             flatten=False, in_units=units)
        self.conv_weight = Parameter("conv_weight", shape=(conv, conv_kernel),
                                     init=_init.Uniform(conv_kernel ** -0.5))
        self.conv_bias = Parameter("conv_bias", shape=(conv,), init="zeros")
        self.dt_bias = Parameter("dt_bias", shape=(num_heads,),
                                 init=_StepBias(time_step_min, time_step_max,
                                                time_step_floor))
        self.A_log = Parameter("A_log", shape=(num_heads,), init=_LogRate())
        self.D = Parameter("D", shape=(num_heads,), init="ones")
        self.norm_gamma = Parameter("norm_gamma", shape=(d_in,), init="ones")
        self.out_proj = Dense(units, use_bias=False, flatten=False,
                              in_units=d_in)

    def forward(self, x):
        from ...ops import ssm
        small = (self.conv_weight, self.conv_bias, self.dt_bias, self.A_log,
                 self.D, self.norm_gamma)
        for p in small:
            if p._data is None:
                p._finish_deferred_init()
        heads, dim, groups, state = (self._heads, self._dim, self._groups,
                                     self._state)
        chunk, eps = self._chunk, self._eps
        d_in, gn = heads * dim, groups * state

        def core(zxbcdt, conv_w, conv_b, dt_bias, a_log, d_skip, gamma):
            b, s, _ = zxbcdt.shape
            z, xbc, dt = jnp.split(zxbcdt, (d_in, 2 * d_in + 2 * gn), axis=-1)
            xbc = ssm.causal_conv1d(xbc, conv_w, conv_b, "silu")
            xs, b_mat, c_mat = jnp.split(xbc, (d_in, d_in + gn), axis=-1)
            y = ssm.ssd_scan(
                xs.reshape(b, s, heads, dim),
                jax.nn.softplus(dt.astype(jnp.float32)
                                + dt_bias.astype(jnp.float32)),
                -jnp.exp(a_log.astype(jnp.float32)),
                b_mat.reshape(b, s, groups, state),
                c_mat.reshape(b, s, groups, state), d_skip, chunk)
            return ssm.gated_rms_norm(y.reshape(b, s, d_in), z, gamma,
                                      groups, eps)

        with jax.named_scope("mx.ssm"):
            y = _invoke(core, (self.in_proj(x),)
                        + tuple(p.data() for p in small),
                        name="mamba2_mixer")
            return self.out_proj(y)
