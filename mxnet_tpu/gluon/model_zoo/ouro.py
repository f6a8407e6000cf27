"""Looped decoder-only LLM family (the ``ouro`` layout: Ouro-2.6B,
"Scaling Latent Reasoning via Looped Language Models").

A stack of ``num_layers`` pre- and post-norm decoder layers —
``a = h + RMS(Attn(RMS(h)))``, ``h' = a + RMS(FF(RMS(a)))``, grouped-query
attention with rotary positions on every layer (no q/k norm, no gate, no
window), a SwiGLU feed-forward — that a token goes through
``total_ut_steps`` times **on the same weights**.  After each pass the
final norm makes ``z_t``, which is both exit ``t``'s state and what pass
``t + 1`` reads; an exit gate ``g_t = sigmoid(w_g . z_t + b_g)`` gives the
token's exit distribution ``p_1 = g_1``, ``p_t = g_t prod_{j<t}(1 - g_j)``,
``p_T = prod_{j<T}(1 - g_j)``; every exit reads the one untied head.
Embeddings are not scaled.

``OuroForCausalLM.forward`` returns what the loss needs and not logits:
``(exit states (T, b, s, units), the head's matrix (vocab, units), log p
(T, b, s))`` — the first two in AMP's type where it is on, as a ``Dense``
would hand them to its product.  ``looped_lm_loss`` is the pre-training
objective over them, ``mean_tokens[sum_t p_t CE_t - beta H(p)]``; the
``T`` cross-entropies go through ``ops.xent.chunked_lm_xent`` over the
exits stacked to ``(T b s, units)``: the head's matrix is read once a
vocabulary chunk for all exits, no ``(b s, vocab)`` logits are kept, and
``dW`` is one product a chunk.  The mean exit distribution of the last
training call rides the aux channel as ``exit.pdf`` (T,).

A gradient of a layer's leaf is the sum over its ``T`` uses; the
activations are ``T`` times those of a plain stack of the same
parameters, which is what ``layer.hybridize(remat=...)`` on the layers
(``OuroModel.layers``) bounds: each application is then a recomputation
boundary of its own (``gluon/block.py``).  ``RECOMPUTE_NAMES`` are the
names a layer gives the values a policy may want to keep.

Training path only: the served form (a KV cache a loop step and layer,
exit by the cumulative ``p`` against a threshold) is not built.
"""
from __future__ import annotations

import functools

from ... import autograd
from ...numpy.multiarray import _invoke, _wrap
from ..block import HybridBlock
from ..nn import (Dense, Embedding, GatedFFN, GroupedQueryAttention,
                  RMSNorm)
from ..nn.transformer import _amp_operands
from ..parameter import Parameter

__all__ = ["OuroModel", "OuroForCausalLM", "looped_lm_loss",
           "RECOMPUTE_NAMES"]

#: ``jax.ad_checkpoint.checkpoint_name``s a layer gives its values, for a
#: ``hybridize(remat=[...])`` policy: q, k and v as the core reads them;
#: the output projection's result; the feed-forward's two inner products;
#: its down projection's result.  (The core's own output has no name:
#: ``"pallas_call"`` keeps what the flash kernel wrote.)
RECOMPUTE_NAMES = ("attn.qkv", "attn.proj", "ffn.inner", "ffn.down")


def _named(x, name):
    """``x`` under a ``checkpoint_name``: the identity, and a handle for a
    recomputation policy."""
    from jax.ad_checkpoint import checkpoint_name
    return _invoke(functools.partial(checkpoint_name, name=name), (x,),
                   name="checkpoint_name")


class OuroAttention(GroupedQueryAttention):
    """``GroupedQueryAttention`` with rotary positions, no q/k norm and no
    gate, whose values carry ``RECOMPUTE_NAMES``."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rope_theta, epsilon):
        super().__init__(units, num_heads, num_kv_heads, head_dim,
                         rotary=True, rope_theta=rope_theta, epsilon=epsilon,
                         gate=False, qk_norm=False)

    def forward(self, x):
        from ...ops.attention import multi_head_attention
        q, k, v = (_named(t, "attn.qkv") for t in self._qkv(x))
        out = multi_head_attention(q, k, v, self._heads, causal=True,
                                   kv_heads=self._kv_heads)
        return _named(self._output(out, x), "attn.proj")


class OuroFFN(GatedFFN):
    """``GatedFFN`` whose values carry ``RECOMPUTE_NAMES``."""

    def forward(self, x):
        from ... import numpy_extension as npx
        gate = _named(self.gate_proj(x), "ffn.inner")
        up = _named(self.up_proj(x), "ffn.inner")
        return _named(self.down_proj(
            npx.activation(gate, act_type="silu") * up), "ffn.down")


class OuroDecoderLayer(HybridBlock):
    """One layer: four norms, rotary grouped-query attention, SwiGLU."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 hidden_size, rope_theta=1e6, epsilon=1e-6):
        super().__init__()
        self.input_norm = RMSNorm(epsilon, in_channels=units)
        self.attention = OuroAttention(units, num_heads, num_kv_heads,
                                       head_dim, rope_theta, epsilon)
        self.post_attn_norm = RMSNorm(epsilon, in_channels=units)
        self.pre_mlp_norm = RMSNorm(epsilon, in_channels=units)
        self.mlp = OuroFFN(units, hidden_size)
        self.post_mlp_norm = RMSNorm(epsilon, in_channels=units)

    def forward(self, x):
        h = x + self.post_attn_norm(self.attention(self.input_norm(x)))
        return h + self.post_mlp_norm(self.mlp(self.pre_mlp_norm(h)))


class OuroExitGate(HybridBlock):
    """forward(exit states (T, b, s, units) float32) -> log p (T, b, s):
    the exit distribution of every token, float32 throughout (the
    ``units -> 1`` product at the highest precision, as a router's).  The
    last exit takes what the earlier ones left, so the gate's value there
    is not read.  ``pdf`` (T,) holds the last training call's mean
    distribution."""

    def __init__(self, units, total_ut_steps):
        super().__init__()
        self._steps = total_ut_steps
        self.proj = Dense(1, use_bias=True, flatten=False, in_units=units)
        self.pdf = Parameter("pdf", grad_req="null", shape=(total_ut_steps,),
                             dtype="float32", init="zeros")

    def forward(self, states):
        import jax
        import jax.numpy as jnp
        if self.pdf._data is None:
            self.pdf._finish_deferred_init()

        def log_pdf(z, w, b):
            a = jnp.einsum("tbsd,d->tbs", z.astype(jnp.float32),
                           w[0].astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST) \
                + b[0].astype(jnp.float32)
            stay = jnp.cumsum(jax.nn.log_sigmoid(-a), axis=0)    # prod(1-g)
            before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
            return jnp.concatenate(
                [jax.nn.log_sigmoid(a[:-1]) + before[:-1], before[-1:]])

        out = _invoke(log_pdf, (states, self.proj.weight.data(),
                                self.proj.bias.data()), name="exit_gate")
        if autograd.is_training():
            self.pdf.data()._rebind(
                jnp.mean(jnp.exp(jax.lax.stop_gradient(out._data)),
                         axis=(1, 2)))
        return out


class OuroModel(HybridBlock):
    """forward(inputs (b, s) int) -> exit states (T, b, s, units) float32:
    the final norm's output after each of the ``total_ut_steps`` passes
    over the one stack, the passes written out in the trace one after
    the other (a ``lax.scan`` over the pass traced four layers where
    this traces sixteen, and kept 3.6 GB more of residuals on the chip:
    PERF.md section 6, PR 42).  With one pass there is no loop."""

    def __init__(self, vocab_size, units, num_layers, num_heads,
                 num_kv_heads, head_dim, hidden_size, total_ut_steps=4,
                 rope_theta=1e6, epsilon=1e-6):
        super().__init__()
        if total_ut_steps < 1:
            raise ValueError(f"{total_ut_steps} passes over the stack")
        self._steps = int(total_ut_steps)
        self.word_embed = Embedding(vocab_size, units)
        self.layers = []
        for i in range(num_layers):
            cell = OuroDecoderLayer(units, num_heads, num_kv_heads, head_dim,
                                    hidden_size, rope_theta, epsilon)
            setattr(self, f"layer{i}", cell)
            self.layers.append(cell)
        self.final_norm = RMSNorm(epsilon, in_channels=units)

    def one_pass(self, x):
        """The stack once, then the final norm (under ``mx.exit``)."""
        import jax
        for cell in self.layers:
            x = cell(x)
        with jax.named_scope("mx.exit"):
            return self.final_norm(x)

    def forward(self, inputs):
        import jax
        from ... import numpy as np
        x = self.word_embed(inputs)
        if self._steps == 1:
            return np.expand_dims(self.one_pass(x), 0)
        states = []
        with jax.named_scope("mx.loop"):
            for t in range(self._steps):
                with jax.named_scope(f"mx.loop.t{t + 1}"):
                    x = self.one_pass(x)
                states.append(x)
            return np.stack(states)


class OuroForCausalLM(HybridBlock):
    """The exit gate and the untied head over OuroModel.  forward ->
    ``(exit states, the head's matrix, log p)``: see the module's
    docstring and ``looped_lm_loss``."""

    def __init__(self, backbone=None, **kwargs):
        super().__init__()
        self.backbone = backbone if backbone is not None \
            else OuroModel(**kwargs)
        embed = self.backbone.word_embed
        self.exit = OuroExitGate(embed._output_dim, self.backbone._steps)
        self.lm_head = Dense(embed._input_dim, use_bias=False, flatten=False,
                             in_units=embed._output_dim)

    def forward(self, inputs):
        import jax
        states = self.backbone(inputs)
        with jax.named_scope("mx.exit"):
            log_p = self.exit(states)
            h, w = _amp_operands(states._data, self.lm_head.weight.data()._data)
        return _wrap(h), _wrap(w), log_p


def looped_lm_loss(out, labels, beta=0.1, chunk=8192):
    """The looped model's pre-training objective over
    ``OuroForCausalLM``'s output against ``labels`` (b, s) = the inputs
    shifted by one: ``mean_tokens[sum_t p_t CE(z_t W^T, label) - beta
    H(p)]`` with ``H(p) = -sum_t p_t log p_t``; gradients through
    everything.  The products take the operands' type with float32
    accumulation; the losses, ``p`` and ``H`` are float32.  Raw jax values
    (a ``ShardedTrainStep`` loss function); ``chunk`` is the vocabulary
    chunk of ``chunked_lm_xent``."""
    import jax
    import jax.numpy as jnp

    from ...ops.xent import chunked_lm_xent
    states, w_head, log_p = out
    steps, units = states.shape[0], states.shape[-1]
    with jax.named_scope("mx.exit"):
        ce = chunked_lm_xent(
            states.reshape(-1, units), w_head,
            jnp.tile(labels.reshape(-1), steps),
            min(chunk, w_head.shape[0])).reshape(log_p.shape)
        p = jnp.exp(log_p)
        return jnp.mean(jnp.sum(p * (ce + beta * log_p), axis=0))
