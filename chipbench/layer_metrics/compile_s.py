"""Seconds inside trace + lower + compile-or-load-from-cache during
set-up (``jax.monitoring`` durations)."""


def read(obs):
    return obs["compile_setup"]["seconds"]
