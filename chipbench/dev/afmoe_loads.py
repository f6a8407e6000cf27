"""Load statistics of the afmoe family's seeded weights by the two
settings of its generator that decide the routing (the post-norms' scale
and the selection bias's spread): the plain reference's forward over one
batch of ``trinity-train-8k`` at the cell's own sizes, and from it, by
expert layer, the most loaded of the published experts over the mean,
the same over the held experts, and the rows held.  Counts, so the CPU
will do (a few minutes a setting); ``families/afmoe.py`` quotes them.

    JAX_PLATFORMS=cpu python chipbench/dev/afmoe_loads.py <seed>...
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

#: (post-norm scale, bias spread); the generator ships the last
SETTINGS = ((1.0, 0.01), (0.1, 0.05), (0.03, 0.05), (0.1, 0.01),
            (0.03, 0.01))

if __name__ == "__main__":
    import jax
    import jax.numpy as jnp
    import numpy as onp

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    _, _, cfg, mix = run.resolve(bench, "trinity-train-8k")
    family = run.load_module("families", cfg["family"])
    reference = run.load_module("reference", cfg["family"])
    driver = run.load_module("drivers", mix["driver"])
    lo, n = cfg["experts_held_from"], cfg["num_experts_held"]
    loads = jax.jit(lambda p, b, t: reference.forward(p, b, t, cfg)[1])
    for seed in map(int, sys.argv[1:]):
        w = family.make_weights(cfg, seed)
        x, _ = next(driver.batches(seed, cfg["vocab_size"], 1,
                                   mix["seq_len"]))
        shipped_g, shipped_b = SETTINGS[-1]
        for g, b in SETTINGS:
            p = {k: v * (g / shipped_g)
                 if k in ("ln_post_attn.g", "ln_post_mlp.g") else v
                 for k, v in w.items() if k != family.BIAS}
            load = onp.asarray(loads(
                p, w[family.BIAS] * (b / shipped_b), jnp.asarray(x[0])))
            held = load[:, lo:lo + n]
            print(json.dumps({
                "seed": seed, "post_norm": g, "bias": b,
                "all_max_over_mean":
                    (load.max(1) / load.mean(1)).round(2).tolist(),
                "held_max_over_mean":
                    (held.max(1) / held.mean(1)).round(2).tolist(),
                "held_rows": held.sum(1).tolist()}), flush=True)
