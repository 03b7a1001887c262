"""Multi-host rehearsal: the cross-process psum path a real pod would use.

``init_multihost`` (parallel/mesh.py) is the counterpart of the reference's
mpirun + hostfile bootstrap (run_fedavg_distributed_pytorch.sh:19-23). A TPU
pod drives it env-first; here the SAME code path is rehearsed as 2 OS
processes × 4 virtual CPU devices forming one 8-device mesh, running the
REAL grouped cross-silo federated rounds with psum aggregation crossing the
process boundary — and the result must match the single-process 8-device
run of the identical config.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = """
import json, sys
pid, port = int(sys.argv[1]), sys.argv[2]
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, %(repo)r)
from fedml_tpu.parallel.mesh import init_multihost
idx = init_multihost(f"127.0.0.1:{port}", num_processes=2, process_id=pid)
assert idx == pid and len(jax.devices()) == 8 and len(jax.local_devices()) == 4
from fedml_tpu.core.config import FedConfig
from fedml_tpu.data import load_dataset
from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
cfg = FedConfig(**%(cfg)r)
ds = load_dataset("synthetic_1_1", num_clients=16, batch_size=5, seed=2)
api = CrossSiloFedAvgAPI(ds, cfg)
hist = api.train()
print("RESULT " + json.dumps({
    "acc": [float(a) for a in hist["Test/Acc"]],
    "loss": [float(l) for l in hist["Test/Loss"]],
    "path": api._path,
}), flush=True)
"""

# 16 clients / 8 devices = 2 per device with ragged (power-law) counts,
# full participation, resident: the stack is sharded over both processes
CFG = dict(model="lr", dataset="synthetic_1_1", client_num_in_total=16,
           client_num_per_round=16, comm_round=3, batch_size=5, lr=0.1,
           epochs=1, frequency_of_the_test=1, seed=2,
           bucket_quantum_batches=1, device_data="on")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _env():
    env = os.environ.copy()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return env


def test_two_process_mesh_matches_single_process():
    port = _free_port()
    script = WORKER % {"repo": REPO, "cfg": CFG}
    env = _env()
    procs = [subprocess.Popen([sys.executable, "-c", script, str(p), str(port)],
                              env=env, cwd=REPO, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for p in (0, 1)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process mesh run timed out")
        if p.returncode != 0:
            pytest.fail(f"worker failed rc={p.returncode}\n{err[-4000:]}")
        outs.append(out)

    results = []
    for out in outs:
        line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")][-1]
        results.append(json.loads(line[len("RESULT "):]))

    # both processes observe the same replicated result
    assert results[0] == results[1]
    assert results[0]["path"] == "mesh_sharded", \
        "rehearsal must exercise the resident-sharded program"

    # and it matches the single-process 8-virtual-device run (conftest env)
    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data import load_dataset

    ds = load_dataset("synthetic_1_1", num_clients=16, batch_size=5, seed=2)
    ref = CrossSiloFedAvgAPI(ds, FedConfig(**CFG)).train()
    np.testing.assert_allclose(results[0]["acc"], ref["Test/Acc"], rtol=0, atol=1e-6)
    np.testing.assert_allclose(results[0]["loss"], ref["Test/Loss"], rtol=1e-5)
