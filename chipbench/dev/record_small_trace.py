"""Record the small trace the reducer's test reads
(``chipbench/tests/data/small.xplane.pb``): a few matmuls with idle
gaps between them, under chipbench's own spans.  Run on the chip.

    python chipbench/dev/record_small_trace.py <out.xplane.pb>
"""
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(out):
    import jax
    import jax.numpy as jnp
    from common import span

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    d = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    with span("window"):
        for i in range(4):
            with span("small.step"):
                f(x).block_until_ready()
            with span("small.idle"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(d, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    shutil.copy(found[-1], out)
    shutil.rmtree(d)
    print(out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
