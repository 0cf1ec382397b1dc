"""Multi-process launcher (reference: apex/parallel/multiproc.py:12-35).

The reference spawns one process per GPU appending --rank/--world-size.
Here the launcher spawns N local processes for ``jax.distributed`` runs
on the CPU backend (local testing of the multi-process path).  Children
call ``apex_tpu.parallel.init_distributed()``, which consumes the
``APEX_TPU_COORDINATOR``/``APEX_TPU_NUM_PROCESSES``/``APEX_TPU_PROCESS_ID``
variables exported here and passes them explicitly to
``jax.distributed.initialize`` (jax reads only the coordinator address from
the environment on its own).

On a TPU host the launcher refuses to start more than one child: a chip
belongs to one process at a time, the children get no per-child chip
assignment, and each would claim every local chip.  One process drives
all local chips through ``Mesh(jax.devices(), ...)`` — run the script
directly (docs/parallel.md, "Multi-host").

``--cluster-kv DIR`` additionally exports ``APEX_TPU_CLUSTER_KV`` so the
children share a file-backed cluster membership store
(``apex_tpu.cluster.kvstore.FileKV`` — what
``apex_tpu.cluster.kvstore.default_kv`` resolves when no
jax.distributed coordinator is up, e.g. N local CPU processes).

Usage:  python -m apex_tpu.parallel.multiproc [--nproc N]
        [--cluster-kv DIR] script.py args...
"""
from __future__ import annotations

import os
import subprocess
import sys


def _probe_local_devices():
    """``(platform, count)`` of the local devices, read in a throwaway
    child so the parent never initializes the backend (a parent that
    holds the chip would make every spawned worker fail at init)."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.local_devices(); "
         "print(d[0].platform, len(d))"],
        capture_output=True, text=True)
    try:
        platform, count = out.stdout.strip().splitlines()[-1].split()
        return platform, int(count)
    except (ValueError, IndexError):
        sys.exit("multiproc: could not list the local devices:\n"
                 + out.stderr[-2000:])


def main():
    argv = list(sys.argv[1:])
    nproc = None
    cluster_kv = None
    while argv and argv[0] in ("--nproc", "--cluster-kv"):
        if argv[0] == "--nproc":
            nproc = int(argv[1])
        else:
            cluster_kv = os.path.abspath(argv[1])
        argv = argv[2:]
    if not argv:
        print(__doc__)
        sys.exit(1)
    platform, n_local = _probe_local_devices()
    if nproc is None:
        nproc = n_local
    if platform == "tpu" and nproc > 1:
        sys.exit(
            f"multiproc: refusing to start {nproc} processes on a TPU "
            f"host ({n_local} local chips): a chip belongs to one process "
            f"and each child would claim all of them.  Run "
            f"`python {argv[0]} ...` directly — one process drives every "
            f"local chip through Mesh(jax.devices(), ...).")

    port = int(os.environ.get("APEX_TPU_COORD_PORT", "12355"))
    coordinator = f"127.0.0.1:{port}"

    procs = []
    for local_rank in range(nproc):
        env = dict(os.environ)
        env["APEX_TPU_COORDINATOR"] = coordinator
        env["APEX_TPU_NUM_PROCESSES"] = str(nproc)
        env["APEX_TPU_PROCESS_ID"] = str(local_rank)
        if cluster_kv is not None:
            env["APEX_TPU_CLUSTER_KV"] = cluster_kv
        cmd = [sys.executable, argv[0], *argv[1:],
               f"--local_rank={local_rank}"]
        procs.append(subprocess.Popen(cmd, env=env))

    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    sys.exit(rc)


if __name__ == "__main__":
    main()
