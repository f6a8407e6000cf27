"""Load statistics of the keye family's seeded weights by the two settings
tried for its generator that decide the routing (the scale of the
embedding rows, which ships, and of attention's output projection, which
does not): the plain reference's forward over one batch of
``keye-train-8k`` at the cell's own sizes, and from it, by layer, the
most loaded of the published experts over the mean, the same over the
held experts, and the rows held.  Counts, so the CPU will do (a few
hour a setting there, seconds on a chip); ``families/keye.py`` quotes
them.

    python chipbench/dev/keye_loads.py <seed>...
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import run  # noqa: E402

#: (embedding rows x N(0, 0.02), ``attn.o.w`` x N(0, 0.02) / sqrt(2 x
#: layers)); the generator ships the last
SETTINGS = ((1.0, 1.0), (1.0, 0.1), (8.0, 1.0), ("sqrt(hidden)", 1.0))

if __name__ == "__main__":
    import jax
    import jax.numpy as jnp
    import numpy as onp

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    _, _, cfg, mix = run.resolve(bench, "keye-train-8k")
    family = run.load_module("families", cfg["family"])
    reference = run.load_module("reference", cfg["family"])
    driver = run.load_module("drivers", mix["driver"])
    lo, n = cfg["experts_held_from"], cfg["num_experts_held"]
    loads = jax.jit(lambda p, t: reference.forward(p, t, cfg)[2][0])
    for seed in map(int, sys.argv[1:]):
        w = family.make_weights(cfg, seed)
        x, _ = next(driver.batches(seed, cfg["vocab_size"], 1,
                                   mix["seq_len"]))
        shipped = family.EMBED_SCALE * cfg["hidden_size"] ** 0.5
        for embed, out in SETTINGS:
            scale = shipped if embed == "sqrt(hidden)" else embed
            p = dict(w, wte=w["wte"] * (scale / shipped))
            p["attn.o.w"] = w["attn.o.w"] * out
            load = onp.asarray(loads(p, jnp.asarray(x[0])))
            held = load[:, lo:lo + n]
            print(json.dumps({
                "seed": seed, "embed_scale": embed, "attn_out_scale": out,
                "all_max_over_mean":
                    (load.max(1) / load.mean(1)).round(2).tolist(),
                "held_max_over_mean":
                    (held.max(1) / held.mean(1)).round(2).tolist(),
                "held_rows": held.sum(1).tolist()}), flush=True)
