"""Load statistics of the glm4_moe_lite family's seeded weights: the plain
reference's forward over one batch of
``glm-train-8k`` at the cell's own sizes, and from it, by expert
layer, the most loaded of the published experts over the mean, the same
over the held experts, and the rows held (``rows_bound`` is 8192, twice
the expected 4096).  Counts, so the CPU will do (some minutes a seed);
``PERF.md`` section 6 quotes them.

    JAX_PLATFORMS=cpu python chipbench/dev/glm_loads.py <seed>...
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

sys.path.insert(0, run.ROOT)      # the family locates the program

if __name__ == "__main__":
    import jax
    import jax.numpy as jnp
    import numpy as onp

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    _, _, cfg, mix = run.resolve(bench, "glm-train-8k")
    family = run.load_module("families", cfg["family"])
    reference = run.load_module("reference", cfg["family"])
    driver = run.load_module("drivers", mix["driver"])
    lo, n = cfg["experts_held_from"], cfg["num_experts_held"]
    loads = jax.jit(lambda p, b, t: reference.forward(p, b, t, cfg)[1])
    for seed in map(int, sys.argv[1:]):
        w = dict(family.make_weights(cfg, seed))
        bias = w.pop(family.BIAS)
        x, _ = next(driver.batches(seed, cfg["vocab_size"], 1,
                                   mix["seq_len"]))
        load = onp.asarray(loads(w, bias, jnp.asarray(x[0])))
        held = load[:, lo:lo + n]
        print(json.dumps({
            "seed": seed,
            "all_max_over_mean":
                (load.max(1) / load.mean(1)).round(2).tolist(),
            "held_max_over_mean":
                (held.max(1) / held.mean(1)).round(2).tolist(),
            "held_rows": held.sum(1).tolist()}), flush=True)
