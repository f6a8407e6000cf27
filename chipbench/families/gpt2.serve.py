"""The one place the benchmark reaches under the serve engine: the rows
its programs wrote into the KV cache.

``ServeEngine`` hands its caller token ids and nothing the values of
which could be held against a reference (PERF.md section 7).  Its cache
is such a value.  ``cache_rows(eng, slot, size)`` returns a slot's first
``size`` rows of every layer, heads merged back into the projection's
width, as ``(k, v)``, each ``(layers, size, n_embd)``:

- through the engine's own ``eng.cache_rows(slot, size)`` where it has
  one.  It has none today; a program PR that changes how the cache is
  laid out (ROADMAP M2) adds that method with this signature and return,
  and the check follows it with no edit here;
- else from the layout the engine has today: per layer one
  ``(max_slots, max_seq, heads, head)`` K and V array,
  ``ServeEngine._cache``, donated through every compiled call — a
  private name, read in one jitted call a ``size`` (the caller pads to a
  few sizes, so a window's slots compile a handful of programs).  A
  cache held as int8 pairs is refused (no cell serves one)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames="size")
def _rows(cache, slot, size):
    def one(a):
        rows = jax.lax.dynamic_index_in_dim(a, slot, 0, keepdims=False)
        return rows[:size].reshape(size, -1)
    return (jnp.stack([one(k) for k, _ in cache]),
            jnp.stack([one(v) for _, v in cache]))


def cache_rows(eng, slot, size):
    """``(k, v)``, each ``(layers, size, n_embd)`` in the cache's type:
    rows 0 .. ``size`` - 1 of ``slot`` (the engine's own accessor first)."""
    if hasattr(eng, "cache_rows"):
        return eng.cache_rows(int(slot), int(size))
    if any(isinstance(k, (tuple, list)) for k, _ in eng._cache):
        raise RuntimeError("an int8 KV cache has no rows this check "
                           "can read")
    return _rows(eng._cache, jnp.int32(slot), size)
