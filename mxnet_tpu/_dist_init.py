"""Process-group bring-up at import time.

Reference parity: importing mxnet in a DMLC-launched job connects the
ps-lite van using DMLC_* env vars before any work happens (src/kvstore/
kvstore_dist.h, tools/launch.py tracker). Here the coordination service is
jax.distributed, which must initialize BEFORE the first backend touch —
so mxnet_tpu/__init__ calls this first thing. No-op without launcher env.

The local-launcher mode (MXTPU_DIST_DEVICE=cpu, set by tools/launch.py) is
CPU-only by construction: a chip belongs to one process at a time, so N
worker processes on one host run on the CPU platform with gloo.
"""
from __future__ import annotations

import os


def _env_int(*names):
    for n in names:
        v = os.environ.get(n)
        if v is not None:
            return int(v)
    return None


def ensure_distributed():
    """Initialize jax.distributed from DMLC-style or native env vars."""
    import jax

    coord = (os.environ.get("JAX_COORDINATOR_ADDRESS")
             or os.environ.get("DMLC_PS_ROOT_URI"))
    nproc = _env_int("DMLC_NUM_WORKER", "JAX_NUM_PROCESSES")
    pid = _env_int("DMLC_WORKER_ID", "JAX_PROCESS_ID")
    if not (coord and nproc and nproc > 1):
        return
    from jax._src import distributed
    if distributed.global_state.client is not None:
        return  # already connected
    if pid is None:
        # `process_id=pid or 0` would silently make EVERY worker rank 0 —
        # N processes each claiming rank 0 corrupts the reduce instead of
        # failing the launch.
        from .base import MXNetError
        raise MXNetError(
            f"distributed launch env is incomplete: coordinator={coord!r} "
            f"and num_processes={nproc} are set but this process has no "
            "rank. Set DMLC_WORKER_ID (DMLC-style) or JAX_PROCESS_ID "
            "(native) to this worker's 0-based index — tools/launch.py "
            "does this automatically.")
    if os.environ.get("MXTPU_DIST_DEVICE", "") == "cpu":
        # local-launcher mode (tools/launch.py --launcher local): N
        # processes on one box cannot share a chip, so force the CPU
        # platform and gloo collectives so they can psum.
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    port = os.environ.get("DMLC_PS_ROOT_PORT", "1234")
    addr = coord if ":" in coord else f"{coord}:{port}"
    jax.distributed.initialize(coordinator_address=addr,
                               num_processes=nproc,
                               process_id=pid)
