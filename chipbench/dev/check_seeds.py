"""The check's numbers on many seeds from one process (one start-up and
one compile-cache load instead of one a seed): what a cell's limits are
set from.  Each seed is a whole short run of ``run.py``; only the
``# check`` rows matter, the timings of runs after the first do not.

    python chipbench/dev/check_seeds.py <cell> <seconds> <seed>... [--control]
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import run  # noqa: E402

if __name__ == "__main__":
    extra = [a for a in sys.argv[1:] if a.startswith("--")]
    cell, seconds, *seeds = [a for a in sys.argv[1:] if not a.startswith("--")]
    for seed in seeds:
        run.main(["--workload", cell, "--seed", seed, "--seconds", seconds]
                 + extra)
        # the last seed's step program must leave the chip before the
        # next is loaded: its scratch is reserved at load
        import gc
        import jax
        jax.clear_caches()
        gc.collect()
