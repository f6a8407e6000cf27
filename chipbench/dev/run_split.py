"""One run of a cell as ``run.py`` makes it, with the compile path's own
records printed after the result line (``# compile_report``): what a
plain run's set-up spent tracing, lowering and loading, per program.
For a builder's own chip runs; the driver never calls it.

    python chipbench/dev/run_split.py --workload <cell> --seed <n> ...
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

if __name__ == "__main__":
    run.main()
    from mxnet_tpu import _compile_cache
    report = getattr(_compile_cache, "report", None)
    print("# compile_report " + json.dumps(
        {"t_process": run.T_PROCESS,
         "programs": report() if report else None}), flush=True)
