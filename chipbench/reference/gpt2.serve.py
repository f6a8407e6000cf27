"""What a served GPT-2 should have said and should have cached: the plain
reference run once over each checked request's prompt with the tokens the
engine served.

``reference/gpt2.py`` (float32, ``highest``, one sequence at a time,
nothing of the program) supplies the arithmetic; one pass over a sequence
gives, at every position,

- ``gap``: how far the served token's logit lies below the reference's
  best (0 where the served token is the reference's own choice);
- every layer's keys and values, to hold the rows the engine's programs
  wrote into its cache against.

**Why the cache, and why a projection.**  The engine hands its caller
token ids alone, and a greedy token says something of the logits only
where two of them nearly tie: at the cells' size a sound bf16 run and
the int8 control read the same widest gap (PERF.md section 2).  The
cache rows are values: every layer's K and V at every position, written
by the prefill programs (the prompt's rows) and by the decode program (a
row a token).  Two numbers a layer are taken from them, over all checked
rows at once:

- ``plain``: the rows' error ``E`` (n x 2e, keys beside values) against
  the reference's, ``|E| / |[K, V]|``.  It holds whatever is wrong with
  a row, linear in the layer's input or not: a stale row, a row written
  to the wrong place, a cache rounded lower.  But it is rounding noise
  of the bf16 activations upstream as much as anything a changed weight
  does (sound 1.1 % at the worst layer, int8 weights 2.6 %: the control
  is not three times the sound run on it).
- ``fitted``: a weight's error is a *linear function of the layer's
  input*, the same at every row, and rounding noise is not.  So ``E`` is
  regressed on the reference's own input of that layer's projections,
  ``H = [ln_1(x), 1]`` (n x (e + 1)), and the number is the fitted
  part's size against the reference's rows, ``|H theta| / |[K, V]|``
  with ``theta`` the ridge solution.  Noise keeps only the share of its
  energy that happens to lie in ``H``'s span (rank over rows); a
  changed weight keeps all of its own (sound 0.33 %, int8 2.3 %).

Sequences are padded to a multiple of 128 tokens (``padded``): under a
causal mask what follows a position cannot reach it, and padded rows are
masked out of every sum.  So a window's requests compile a handful of
programs, and nothing here runs an operation whose shape is a request's
own: on the chip each new shape is a compile, and the first form's
hundred requests took 324 s, all of it that.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as onp

_HERE = os.path.dirname(os.path.abspath(__file__))
#: ridge term of the projection, as a share of the mean diagonal of H'H:
#: it only keeps the solve finite where rows repeat (a greedy run of one
#: token), and moves the fitted part by less than its own size
RIDGE = 1e-6


def _plain():
    """``reference/gpt2.py`` beside this file (the harness loads modules
    by path, so a plain import would not find it)."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_reference_gpt2_plain", os.path.join(_HERE, "gpt2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(cfg):
    """A jitted pass over one padded sequence.

    ``(params, tokens (s,), served (s,), valid (s,), k_rows, v_rows
    (layers, s, e))`` -> ``gap`` a position, and a layer ``(H'H, H'E, sum E^2, sum [K, V]^2)`` over the valid
    rows.  ``served[i]`` is the token the engine put at position
    ``i + 1``; ``k_rows`` / ``v_rows`` are the engine's cache rows of
    the sequence (zeros, with ``valid`` zero, where none are held)."""
    plain = _plain()
    eps = cfg["layer_norm_epsilon"]

    @jax.jit
    def read(params, tokens, served, valid, k_rows, v_rows):
        with jax.default_matmul_precision("highest"):
            s = tokens.shape[0]
            x = params["wte"][tokens] + params["wpe"][:s]
            layers = {n: params[n] for n in plain.LAYER_LEAVES}
            keep = valid[:, None]

            def body(x, args):
                p, k_e, v_e = args
                h = plain._ln(x, p["ln_1.g"], p["ln_1.b"], eps)
                kv = jnp.concatenate(
                    [h @ p["attn.k.w"].T + p["attn.k.b"],
                     h @ p["attn.v.w"].T + p["attn.v.b"]], axis=1) * keep
                err = jnp.concatenate([k_e, v_e], axis=1).astype(
                    jnp.float32) * keep - kv
                h1 = jnp.concatenate([h, jnp.ones((s, 1), h.dtype)],
                                     axis=1) * keep
                return plain._block(x, p, cfg), (
                    h1.T @ h1, h1.T @ err, jnp.sum(err * err),
                    jnp.sum(kv * kv))

            x, sums = jax.lax.scan(body, x, (layers, k_rows, v_rows))
            x = plain._ln(x, params["ln_f.g"], params["ln_f.b"], eps)
            logits = x @ params["wte"].T
        said = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
        return jnp.max(logits, axis=-1) - said, sums

    return read


def padded(n, cfg, pad=128):
    """The size a sequence of ``n`` positions is padded to."""
    return min(-(-n // pad) * pad, cfg["n_positions"])


def compare(params, requests, cfg):
    """``requests``: ``(prompt ids, served ids, rows)`` with ``rows``
    None or the engine's ``(k, v)`` cache rows of the sequence, each
    ``(layers, padded(n), e)`` where ``n = prompt + served - 1`` rows
    are the sequence's own (the last served token is never fed).

    Returns ``(gaps, cache)``: a request's ``gap`` over its served
    tokens, one array a request; and, over all rows
    held, ``{"rows", "fitted": [a layer], "plain": [a layer]}`` — the
    projected and the plain relative error of the cache rows — or None
    where no request held rows."""
    read = reader(cfg)
    layers, e = cfg["n_layer"], cfg["n_embd"]
    out, sums, held, none = [], None, 0, {}
    for prompt, served, rows in requests:
        p, g = len(prompt), len(served)
        n = p + g - 1
        size = padded(n, cfg)
        tokens = onp.zeros((size,), onp.int32)
        tokens[:n] = (list(prompt) + list(served))[:n]
        said = onp.zeros((size,), onp.int32)
        said[p - 1:p - 1 + g] = served
        valid = onp.zeros((size,), onp.float32)
        if rows is None:
            if size not in none:
                none[size] = jnp.zeros((layers, size, e), jnp.bfloat16)
            rows = (none[size], none[size])
        else:
            valid[:n] = 1.0
            held += n
        gap, s = read(params, tokens, said, valid, *rows)
        out.append(gap[p - 1:p - 1 + g])
        if valid[0]:
            sums = s if sums is None else _add(sums, s)
    out = [onp.asarray(a) for a in jax.device_get(out)]   # one wait for all
    if sums is None:
        return out, None
    hh, he, err_sq, ref_sq = (onp.asarray(a, onp.float64)
                              for a in jax.device_get(sums))
    fitted = []
    for layer in range(layers):
        a = hh[layer] + RIDGE * onp.trace(hh[layer]) / (e + 1) \
            * onp.eye(e + 1)
        theta = onp.linalg.solve(a, he[layer])
        # |H theta|^2 = sum(theta . (H'H theta)): two products, where a
        # three-operand einsum walks every triple (3 s a layer)
        fitted.append(float(onp.sqrt(
            onp.sum(theta * (hh[layer] @ theta)) / ref_sq[layer])))
    return out, {"rows": held, "fitted": fitted,
                 "plain": [float(x) for x in onp.sqrt(err_sq / ref_sq)]}


@jax.jit
def _add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)
