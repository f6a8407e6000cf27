#!/usr/bin/env python
"""Multi-process training launcher (reference: tools/launch.py, the
dmlc-tracker CLI that spawns scheduler+servers+workers; local mode per
tests/nightly/test_distributed_training-gpu.sh:25-38).

TPU-native design: there are no server/scheduler roles — rendezvous is the
PJRT coordination service hosted by worker 0, so only workers are spawned.
Each worker gets DMLC-style env vars that mxnet_tpu.kvstore.dist reads:

    DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT   coordinator address
    DMLC_NUM_WORKER / DMLC_WORKER_ID       world size / rank
    MXTPU_DIST_DEVICE=cpu                  (local launcher) force the CPU
                                           platform + gloo collectives

CPU-only by construction: the local launcher starts N processes on one
host and a chip belongs to one process at a time, so every worker is
pinned to the CPU platform (MXTPU_DIST_DEVICE=cpu).  One process drives
all the chips of a host; this tool is for exercising the multi-process
collective path, not for reaching an accelerator.

Usage:  python tools/launch.py -n 4 [--launcher local] python3 train.py ...
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main(argv=None):
    p = argparse.ArgumentParser(
        description="launch a multi-process mxnet_tpu job on this host")
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("-s", "--num-servers", type=int, default=0,
                   help="accepted for reference-CLI parity; there are no "
                        "server processes (coordination is PJRT)")
    p.add_argument("--launcher", default="local", choices=["local"],
                   help="only 'local' (N processes on this host); multi-host "
                        "pods use the cluster scheduler's own launcher")
    p.add_argument("--port", type=int, default=None,
                   help="coordinator port (default: pick a free one)")
    p.add_argument("--env", action="append", default=[],
                   help="extra KEY=VALUE for workers (repeatable)")
    p.add_argument("command", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if not args.command:
        p.error("no command given")
    cmd = args.command[1:] if args.command[0] == "--" else args.command

    port = args.port or _free_port()
    procs = []
    for rank in range(args.num_workers):
        env = dict(os.environ)
        env.update({
            "DMLC_ROLE": "worker",
            "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(args.num_workers),
            "DMLC_WORKER_ID": str(rank),
            "MXTPU_DIST_DEVICE": "cpu",
        })
        for kv in args.env:
            k, _, v = kv.partition("=")
            env[k] = v
        procs.append(subprocess.Popen(cmd, env=env))

    def _kill_all(signum=None, frame=None):
        for pr in procs:
            if pr.poll() is None:
                pr.terminate()

    signal.signal(signal.SIGINT, _kill_all)
    signal.signal(signal.SIGTERM, _kill_all)

    rc = 0
    for pr in procs:
        pr.wait()
        if pr.returncode != 0:
            rc = pr.returncode
            _kill_all()  # one failed worker dooms the job; reap the rest
    return rc


if __name__ == "__main__":
    sys.exit(main())
