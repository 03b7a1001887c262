"""Spawn a multi-process edge federation on this host.

Counterpart of the reference's mpirun wrapper
(fedml_experiments/distributed/fedavg/run_fedavg_distributed_pytorch.sh:21-23:
``mpirun -np $PROCESS_NUM -hostfile ./mpi_host_file python3 main_fedavg.py``):
one OS process per rank, rank 0 = server. Each child is

    python -m fedml_tpu.experiments.main_fedavg_edge \
        --rank R --world_size N [--grpc_ipconfig_path ...] <passthrough flags>

so the exact same per-rank entry deploys across machines — run it by hand
(or via your scheduler) on each host with a shared grpc_ipconfig csv
(reference grpc_ipconfig.csv, grpc_comm_manager.py:59-60). This helper just
automates the single-host case. See docs/deploy.md for the runbook.

One process per chip: an accelerator belongs to the first process that
touches JAX, and a second one that wants it hangs or dies. On a single
host only rank 0 (the server — aggregation and evaluation) inherits this
environment and may claim the host's chip; every worker rank is started
with ``JAX_PLATFORMS=cpu``. The launcher itself never initialises a JAX
backend. A rank started by hand on its own machine keeps that machine's
device.

All FedConfig flags pass through to every rank — including the wire
reliability/chaos knobs (--wire_reliable, --chaos_seed, --chaos_drop,
--chaos_dup, --chaos_delay_ms, --chaos_reorder, --chaos_crash_rank,
--chaos_crash_after; docs/deploy.md "Wire reliability"), so a lossy-wire
rehearsal runs with the exact deployment entry points.

Usage:
    python -m fedml_tpu.experiments.launch_edge --world_size 3 \
        --dataset synthetic_1_1 --model lr --comm_round 5 [flags...]
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Optional, Sequence


def main(argv: Optional[Sequence[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--world_size" not in argv:
        print("launch_edge: --world_size N is required", file=sys.stderr)
        return 2
    n = int(argv[argv.index("--world_size") + 1])
    if any(a == "--rank" for a in argv):
        print("launch_edge: do not pass --rank; it is assigned per process",
              file=sys.stderr)
        return 2
    # --result_json names ONE output file: only the server's history goes
    # there, so route the flag to rank 0 alone
    result_json = []
    if "--result_json" in argv:
        i = argv.index("--result_json")
        result_json = argv[i:i + 2]
        del argv[i:i + 2]

    # one process per chip (module docstring): the server keeps the
    # inherited environment, the workers are pinned to the CPU backend
    worker_env = dict(os.environ, JAX_PLATFORMS="cpu")
    print(f"launch_edge: rank 0 (server) keeps this host's JAX device "
          f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '<unset>')}); "
          f"ranks 1..{n - 1} run with JAX_PLATFORMS=cpu", file=sys.stderr)
    procs = []
    try:
        for rank in range(n):
            cmd = [sys.executable, "-m", "fedml_tpu.experiments.main_fedavg_edge",
                   "--rank", str(rank), *argv,
                   *(result_json if rank == 0 else [])]
            # rank 0 (server) inherits stdout so its result JSON reaches the
            # caller; workers log to stderr only
            procs.append(subprocess.Popen(
                cmd,
                stdout=None if rank == 0 else subprocess.DEVNULL,
                env=os.environ.copy() if rank == 0 else worker_env,
            ))
        rcs = [p.wait() for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
    bad = [(r, rc) for r, rc in enumerate(rcs) if rc != 0]
    if bad:
        print(f"launch_edge: ranks failed: {bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
