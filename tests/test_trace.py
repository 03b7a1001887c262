"""fedtrace (fedml_tpu/obs): span tracing, registry unification, exporters,
and the trace_report analyzer (ISSUE 4 acceptance surface).

Pinned contracts:
- a traced run is bit-identical to an untraced run (the tracer only reads
  clocks);
- per-rank trace files stitch into ONE causal timeline: every round present
  on every rank, every recv span linked to its send span by message uid —
  over the local AND grpc transports;
- the disabled path allocates nothing (tracing off is free);
- exporter round-trip preserves events; the Chrome export draws flow arrows;
- tools/trace_report.py exits non-zero exactly on structural anomalies.
"""

import gc
import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

import jax

from fedml_tpu import obs
from fedml_tpu.obs import tracer
from fedml_tpu.core.config import FedConfig
from fedml_tpu.data import load_dataset
from fedml_tpu.data.synthetic import make_synthetic_classification
from fedml_tpu.distributed.fedavg_edge import run_fedavg_edge

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _reset_tracing():
    """Tracing state is process-global; never leak it across tests."""
    obs.reset()
    yield
    obs.reset()


def _edge_cfg(**kw):
    base = dict(
        model="lr", dataset="synthetic_1_1", client_num_in_total=4,
        client_num_per_round=4, comm_round=2, batch_size=10, lr=0.1,
        epochs=1, frequency_of_the_test=1, seed=3, device_data="off",
    )
    base.update(kw)
    return FedConfig(**base)


def _edge_ds():
    return load_dataset("synthetic_1_1", num_clients=4, batch_size=10, seed=3)


def _free_base_port(n: int) -> int:
    """A base port with ``n`` consecutive free ports, checked now. Fixed
    ports collide under xdist, and so do ports the OS hands out: they lie
    in the ephemeral range (32768 up), where the next ``bind(0)`` of another
    worker (an MQTT broker's, an outgoing connection's) lands right beside
    them. So: below that range, from a start that differs by process, each
    run of ports verified by binding it."""
    import random
    import socket

    rng = random.Random(os.getpid() * 7919 + time.monotonic_ns())
    for _ in range(256):
        base = rng.randrange(20000, 32000 - n)
        socks = []
        try:
            for port in range(base, base + n):
                sk = socket.socket()
                socks.append(sk)
                sk.bind(("127.0.0.1", port))
            return base
        except OSError:
            continue
        finally:
            for sk in socks:
                sk.close()
    raise RuntimeError(f"no {n} consecutive free ports found")


# -- bit-identity: tracing must not touch the math -------------------------

def test_traced_fedavg_run_bit_identical(tmp_path):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI

    def run(trace_dir):
        obs.reset()
        ds = make_synthetic_classification(
            "tr", (6,), 3, 4, records_per_client=8,
            partition_method="homo", batch_size=4, seed=0)
        cfg = FedConfig(model="lr", client_num_in_total=4,
                        client_num_per_round=4, comm_round=2, batch_size=4,
                        lr=0.1, frequency_of_the_test=1, trace_dir=trace_dir)
        api = FedAvgAPI(ds, cfg)
        hist = api.train()
        return hist, api

    traced_hist, traced_api = run(str(tmp_path / "traces"))
    plain_hist, plain_api = run(None)
    assert traced_hist["Test/Acc"] == plain_hist["Test/Acc"]
    assert traced_hist["Test/Loss"] == plain_hist["Test/Loss"]
    for a, b in zip(jax.tree.leaves(traced_api.variables),
                    jax.tree.leaves(plain_api.variables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the traced run actually produced a trace with its rounds
    path = tmp_path / "traces" / "trace-rank0.jsonl"
    assert path.exists()
    events = [json.loads(l) for l in open(path)]
    rounds = {e["args"]["round"] for e in events
              if e.get("name") == "round" and e.get("ph") == "X"}
    assert rounds == {0, 1}
    phases = {e["name"] for e in events if e.get("cat") == "phase"}
    assert "train" in phases and "eval" in phases


# -- cross-rank stitch: local + grpc ---------------------------------------

def _assert_stitched(trace_dir, n_ranks, n_rounds, allow=()):
    tr = _load_trace_report()
    events = tr.load_trace_dir(str(trace_dir))
    rep = tr.analyze(events, expect_ranks=n_ranks)
    unexpected = [a for a in rep["anomalies"]
                  if not any(a.startswith(p) for p in allow)]
    assert unexpected == []
    assert rep["ranks"] == list(range(n_ranks))
    assert rep["rounds"] == n_rounds
    for entry in rep["timeline"]:
        assert entry["ranks"] == list(range(n_ranks))   # every rank, every round
        assert "critical_path" in entry                  # chain fully linked
        assert entry["critical_path"]["train_ms"] >= 0
    # message-id causality: every recv in the merged trace has its send
    sends = {e["args"]["mid"] for e in events
             if e.get("name") == "send" and e.get("ph") == "X"}
    recvs = {e["args"]["mid"] for e in events
             if e.get("name") == "recv" and e.get("ph") == "X"}
    assert recvs and recvs <= sends
    return rep


def test_cross_rank_stitch_local(tmp_path):
    d = str(tmp_path / "tr")
    run_fedavg_edge(_edge_ds(), _edge_cfg(trace_dir=d), worker_num=2)
    rep = _assert_stitched(d, n_ranks=3, n_rounds=2)
    assert rep["straggler_ranking"]   # workers ranked


def test_cross_rank_stitch_grpc_4_ranks(tmp_path):
    """The acceptance run: a 4-rank grpc fedavg federation with --trace_dir
    set produces per-rank files that merge into one causally-stitched
    timeline — every round on every rank, sends linked to recvs by uid."""
    pytest.importorskip("grpc")
    from fedml_tpu.comm.grpc_backend import GRPCCommManager

    d = str(tmp_path / "tr")
    port = _free_base_port(4)
    run_fedavg_edge(
        _edge_ds(), _edge_cfg(trace_dir=d), worker_num=3,
        comm_factory=lambda r: GRPCCommManager(
            rank=r, size=4, base_port=port, host="127.0.0.1"))
    assert sorted(os.listdir(d)) == [f"trace-rank{r}.jsonl" for r in range(4)]
    _assert_stitched(d, n_ranks=4, n_rounds=2)


def test_retransmits_tagged_with_message_uid(tmp_path):
    """Chaos drops force retransmits; the retransmit instants carry the SAME
    uid as the original send span, so the analyzer collapses the storm onto
    one logical edge and still stitches every round."""
    # A chaos-dropped ACK for a worker's FINAL upload can leave the worker
    # retransmitting into a server whose receive loop already finished its
    # own drain and closed — the storm then exhausts honestly (gave_up=1)
    # without touching any round: the first copy delivered, dedup absorbed
    # the rest. Whether the race fires depends on teardown timing (warm
    # jit caches close the server sooner), so the stitch assertion
    # tolerates exactly that teardown anomaly; what this test pins —
    # retransmit instants uid-tagged onto their logical edge, every round
    # stitched on every rank — stays strict.
    d = str(tmp_path / "tr")
    cfg = _edge_cfg(trace_dir=d, wire_reliable=True, chaos_drop=0.2,
                    chaos_seed=7)
    run_fedavg_edge(_edge_ds(), cfg, worker_num=2)
    rep = _assert_stitched(d, n_ranks=3, n_rounds=2,
                           allow=("wire gave_up",))
    assert rep["wire"]["chaos/dropped"] > 0
    assert rep["wire"]["retransmit_instants"] > 0
    events = _load_trace_report().load_trace_dir(d)
    send_mids = {e["args"]["mid"] for e in events if e.get("name") == "send"}
    retx_mids = {e["args"]["mid"] for e in events
                 if e.get("name") == "retransmit" and "mid" in e.get("args", {})}
    assert retx_mids and retx_mids <= send_mids


# -- exporters -------------------------------------------------------------

GOLDEN_EVENTS = [
    {"ph": "X", "name": "round", "cat": "round", "ts": 1000, "rank": 0,
     "tid": 1, "dur": 500, "sid": 1, "args": {"round": 0, "role": "server"}},
    {"ph": "X", "name": "send", "cat": "comm", "ts": 1010, "rank": 0,
     "tid": 1, "dur": 5, "sid": 2, "psid": 1,
     "args": {"msg_type": "2", "peer": 1, "mid": "abcdef0123456789"}},
    {"ph": "X", "name": "recv", "cat": "comm", "ts": 1100, "rank": 1,
     "tid": 2, "dur": 300, "sid": 1,
     "args": {"msg_type": "2", "peer": 0, "mid": "abcdef0123456789"}},
    {"ph": "i", "name": "retransmit", "cat": "wire", "ts": 1050, "rank": 0,
     "tid": 1, "args": {"peer": 1, "attempt": 1}},
    {"ph": "C", "name": "host_stages", "cat": "counter", "ts": 1400,
     "rank": 0, "tid": 1,
     "args": {"round": 0, "values": {"materialize_ms": 2.5, "wait_ms": 0.5}}},
]


def test_exporter_jsonl_roundtrip(tmp_path):
    from fedml_tpu.obs.export import read_jsonl, write_jsonl

    p = str(tmp_path / "golden.jsonl")
    write_jsonl(p, GOLDEN_EVENTS)
    assert read_jsonl(p) == GOLDEN_EVENTS


def test_exporter_chrome_trace_golden(tmp_path):
    from fedml_tpu.obs.export import read_jsonl, to_chrome_trace, write_chrome_trace

    out = to_chrome_trace(GOLDEN_EVENTS)
    evs = out["traceEvents"]
    # per-rank process metadata
    proc = {e["pid"]: e["args"]["name"] for e in evs
            if e["ph"] == "M" and e["name"] == "process_name"}
    assert proc == {0: "rank 0", 1: "rank 1"}
    # spans keep rank->pid, ts, dur
    span = next(e for e in evs if e["ph"] == "X" and e["name"] == "round")
    assert (span["pid"], span["ts"], span["dur"]) == (0, 1000, 500)
    # counters flatten to numeric args
    ctr = next(e for e in evs if e["ph"] == "C")
    assert ctr["args"] == {"materialize_ms": 2.5, "wait_ms": 0.5}
    # the send/recv pair becomes a flow arrow from rank 0 to rank 1
    fs = next(e for e in evs if e["ph"] == "s")
    ff = next(e for e in evs if e["ph"] == "f")
    assert fs["pid"] == 0 and ff["pid"] == 1 and fs["id"] == ff["id"]
    # file writer emits the same structure
    p = str(tmp_path / "chrome.json")
    write_chrome_trace(p, GOLDEN_EVENTS)
    assert json.load(open(p))["traceEvents"] == evs
    assert read_jsonl  # imported for parity; silence linters


# -- disabled-path overhead ------------------------------------------------

def test_disabled_path_allocates_nothing():
    """tracing off: the hot-path gate returns None from one global read and
    span() on the shared disabled tracer returns a singleton — no per-call
    allocations survive."""
    import tracemalloc

    assert obs.tracer_if_enabled(0) is None
    tr = obs.get_tracer(0)
    assert tr.span("x") is tr.span("y")   # the shared no-op singleton
    gc.collect()
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(2000):
        t = obs.tracer_if_enabled(3)
        if t is not None:                  # never taken: tracing is off
            with t.span("hot"):
                pass
        with tr.span("hot"):
            pass
        tr.instant("i")
        tr.counter("c", 1.0)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    growth = sum(s.size_diff for s in after.compare_to(before, "lineno")
                 if s.size_diff > 0)
    # tracemalloc's own bookkeeping costs a few KiB; 2000 traced spans would
    # cost hundreds of KiB of event dicts
    assert growth < 64_000, f"disabled tracing leaked {growth} bytes"


# -- trace_report anomaly exit codes ---------------------------------------

def _write_trace(tmp_path, name, events):
    d = tmp_path / name
    d.mkdir()
    by_rank = {}
    for e in events:
        by_rank.setdefault(e.get("rank", 0), []).append(e)
    for r, evs in by_rank.items():
        with open(d / f"trace-rank{r}.jsonl", "w") as f:
            for e in evs:
                f.write(json.dumps(e) + "\n")
    return str(d)


def test_trace_report_exit_codes(tmp_path, capsys):
    tr = _load_trace_report()
    clean = _write_trace(tmp_path, "clean", [
        {"ph": "X", "name": "round", "cat": "round", "ts": 10, "rank": 0,
         "dur": 5, "sid": 1, "args": {"round": 0}},
        {"ph": "X", "name": "round", "cat": "round", "ts": 11, "rank": 1,
         "dur": 5, "sid": 1, "args": {"round": 0}},
    ])
    assert tr.main([clean]) == 0

    # empty dir: nothing to analyze
    empty = tmp_path / "empty"
    empty.mkdir()
    assert tr.main([str(empty)]) == 2

    # unclosed span -> anomaly
    unclosed = _write_trace(tmp_path, "unclosed", [
        {"ph": "X", "name": "round", "cat": "round", "ts": 10, "rank": 0,
         "dur": 5, "sid": 1, "args": {"round": 0}},
        {"ph": "O", "name": "round", "cat": "round", "ts": 20, "rank": 0,
         "sid": 2, "args": {"round": 1}},
    ])
    assert tr.main([unclosed]) == 1

    # a round missing on one rank -> anomaly
    missing = _write_trace(tmp_path, "missing", [
        {"ph": "X", "name": "round", "cat": "round", "ts": 10, "rank": 0,
         "dur": 5, "sid": 1, "args": {"round": 0}},
        {"ph": "X", "name": "round", "cat": "round", "ts": 11, "rank": 1,
         "dur": 5, "sid": 1, "args": {"round": 0}},
        {"ph": "X", "name": "round", "cat": "round", "ts": 30, "rank": 0,
         "dur": 5, "sid": 2, "args": {"round": 1}},
    ])
    assert tr.main([missing]) == 1

    # recv with no matching send (span imbalance) -> anomaly
    orphan = _write_trace(tmp_path, "orphan", [
        {"ph": "X", "name": "round", "cat": "round", "ts": 10, "rank": 0,
         "dur": 5, "sid": 1, "args": {"round": 0}},
        {"ph": "X", "name": "recv", "cat": "comm", "ts": 12, "rank": 0,
         "dur": 1, "sid": 2, "args": {"mid": "beef", "peer": 1}},
    ])
    assert tr.main([orphan]) == 1

    # fewer ranks than expected -> anomaly
    assert tr.main([clean, "--expect-ranks", "4"]) == 1
    capsys.readouterr()


def test_trace_report_cli_smoke(tmp_path):
    """The actual CLI entry point (subprocess) agrees with main()."""
    import subprocess

    d = _write_trace(tmp_path, "cli", [
        {"ph": "X", "name": "round", "cat": "round", "ts": 10, "rank": 0,
         "dur": 5, "sid": 1, "args": {"round": 0}},
    ])
    out = str(tmp_path / "perfetto.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         d, "--json", "--perfetto", out],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    rep = json.loads(r.stdout)
    assert rep["rounds"] == 1 and rep["anomalies"] == []
    assert json.load(open(out))["traceEvents"]


# -- registry unification --------------------------------------------------

def test_wire_counters_visible_through_registry():
    """The reliable layer's stats dict IS a registry group now: the same
    counters are readable per-manager (exact legacy surface) and through
    one registry snapshot, without the manager in hand."""
    from fedml_tpu.comm.local import LocalCommunicationManager, LocalRouter
    from fedml_tpu.comm.reliable import ReliableCommManager
    from fedml_tpu.obs import default_registry

    before = default_registry().snapshot("wire").get("sent", 0)
    router = LocalRouter(2)
    rel = ReliableCommManager(
        LocalCommunicationManager(router, 0, wire_roundtrip=True), rank=0)
    from fedml_tpu.comm import Message

    m = Message("data", 0, 1)
    m.add_params("i", 1)
    rel.send_message(m)
    assert rel.stats["sent"] == 1                      # legacy view
    assert default_registry().snapshot("wire")["sent"] >= before + 1
    rel.stop_receive_message()
    # rank 1 has no manager, so the send above retries until it gives up on
    # a background thread; wait that storm out HERE — otherwise the live
    # manager's gave_up counter leaks into later tests' registry snapshots
    import time

    deadline = time.monotonic() + 30
    while getattr(rel, "_outstanding", {}) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not rel._outstanding, "wire drain did not finish in 30 s"
    rel._retx.join(timeout=10)   # the loop thread holds the manager alive
    del rel
    gc.collect()


def test_round_timer_feeds_registry_and_monotonic_wall():
    from fedml_tpu.obs import default_registry
    from fedml_tpu.utils.metrics import RoundTimer

    import time

    t = RoundTimer()
    with t.phase("train"):
        time.sleep(0.002)
    t.tick_round()
    s = t.summary()
    assert "time/train_s" in s and s["time/wall_s"] > 0
    assert s["rounds_per_sec"] > 0
    # the phase sum is the SAME number the registry sees (a view, not a copy)
    assert default_registry().snapshot("time", rank=0)["train"] >= \
        t.sums["train"]


def test_metrics_logger_cap_context_manager_and_registry_source(tmp_path):
    from fedml_tpu.obs import default_registry
    from fedml_tpu.utils.metrics import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    with MetricsLogger(jsonl_path=path, history_cap=3) as ml:
        for i in range(10):
            ml.log({"Test/Acc": i / 10}, i)
        assert len(ml.history) == 3                      # capped like the ring
        assert ml.last("Test/Acc") == 0.9                # newest survives
        g = default_registry().group("smoke_ns", keys=("hits",))
        g["hits"] += 5
        rec = ml.log_registry(namespace="smoke_ns")
        assert rec == {"smoke_ns/hits": 5}
    assert ml._jsonl is None                             # context exit closed it
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 11                              # JSONL keeps everything


def test_stage_rows_recorded_in_registry():
    """The host-path stage rows that feed round_stats are also recorded in
    the registry's row store — same numbers, one unified surface."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.obs import default_registry
    from fedml_tpu.utils.metrics import round_stats

    default_registry().clear_rows("stage")
    ds = make_synthetic_classification(
        "rows", (6,), 3, 4, records_per_client=8,
        partition_method="homo", batch_size=4, seed=0)
    cfg = FedConfig(model="lr", client_num_in_total=4, client_num_per_round=4,
                    comm_round=2, batch_size=4, lr=0.1, device_data="off",
                    frequency_of_the_test=1)
    api = FedAvgAPI(ds, cfg)
    for r in range(2):
        api.run_round(r)
    rows = default_registry().rows("stage")
    assert [r["round"] for r in rows] == [0, 1]
    assert round_stats(rows)["rounds"] == round_stats(api._stage_rows)["rounds"]
    np.testing.assert_allclose(
        round_stats(rows)["materialize_ms"],
        round_stats(api._stage_rows)["materialize_ms"])
    default_registry().clear_rows("stage")


def test_trace_flags_validated():
    with pytest.raises(ValueError):
        FedConfig(trace_buffer_events=0)
    c = FedConfig(trace_dir="/tmp/x", trace_buffer_events=128)
    assert c.trace_dir == "/tmp/x"
    assert c.trace_device_sampler is True
    assert FedConfig(trace_device_sampler=False).trace_device_sampler is False


# -- fedscope: mesh-paradigm spans, compile + device telemetry --------------

def _mesh_cfg(trace_dir=None, **kw):
    base = dict(
        model="lr", client_num_in_total=4, client_num_per_round=4,
        comm_round=4, batch_size=4, lr=0.1, frequency_of_the_test=2,
        seed=0, device_data="on", pack_lanes=2, trace_dir=trace_dir,
    )
    base.update(kw)
    return FedConfig(**base)


def _mesh_run(trace_dir):
    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel.mesh import client_mesh

    obs.reset()
    gc.collect()   # drop dead counter groups other tests' managers left
    ds = make_synthetic_classification(
        "mesh-tr", (6,), 3, 4, records_per_client=8,
        partition_method="homo", batch_size=4, seed=0)
    api = CrossSiloFedAvgAPI(
        ds, _mesh_cfg(trace_dir),
        create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]),
        mesh=client_mesh(2))
    hist = api.train()
    assert api._packed_mesh is not None   # the run exercised the packed path
    return hist, api


def test_traced_mesh_packed_run_bit_identical(tmp_path):
    """The mesh mirror of the sim/edge bit-identity pins: a traced packed
    cross-silo run computes exactly the untraced weights."""
    traced_hist, traced_api = _mesh_run(str(tmp_path / "traces"))
    plain_hist, plain_api = _mesh_run(None)
    assert traced_hist["Test/Acc"] == plain_hist["Test/Acc"]
    assert traced_hist["Test/Loss"] == plain_hist["Test/Loss"]
    for a, b in zip(jax.tree.leaves(traced_api.variables),
                    jax.tree.leaves(plain_api.variables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    path = tmp_path / "traces" / "trace-rank0.jsonl"
    assert path.exists()
    events = [json.loads(l) for l in open(path)]
    # every mesh round is on the one timeline (wrapper spans)...
    rounds = {e["args"]["round"] for e in events
              if e.get("name") == "round" and e.get("ph") == "X"}
    assert rounds == {0, 1, 2, 3}
    # ...each with its own device span: one program a round, real boundaries
    ms = [e for e in events if e.get("name") == "mesh_step"]
    assert sorted(e["args"]["round"] for e in ms) == [0, 1, 2, 3]
    assert all(e["args"]["path"] == "packed_mesh" and e.get("psid")
               for e in ms)
    # compile spans attribute the program builds (shape-keyed)
    # compile spans attribute the program builds (shape-keyed): ONE ring
    # record a build interval (fedml/round/build), none of cat "compile"
    comp = [e for e in events
            if e.get("cat") == "round" and e.get("name") == "build"]
    assert any(e["args"]["phase"] == "first_call" for e in comp)
    assert all("shape_key" in e["args"] and "program" in e["args"]
               for e in comp)
    assert not [e for e in events if e.get("cat") == "compile"]
    builds = [(e["args"]["program"], e["args"]["phase"]) for e in comp]
    assert len(builds) == len(set(builds))


def test_mesh_report_critical_path_compile_and_device_lane(tmp_path):
    """ISSUE 5 acceptance: one traced cross-silo packed run (sim mesh, CPU)
    → trace_report shows per-round critical paths for mesh rounds, compile
    hit/miss accounting, and the --perfetto export carries a device lane."""
    d = str(tmp_path / "tr")
    _mesh_run(d)
    tr = _load_trace_report()
    events = tr.load_trace_dir(d)
    rep = tr.analyze(events)
    assert rep["anomalies"] == []
    assert rep["rounds"] == 4
    for entry in rep["timeline"]:
        cp = entry["critical_path"]
        assert cp["kind"] == "mesh"
        assert cp["device_ms"] > 0 and cp["path"] == "packed_mesh"
        assert entry["device"]["path"] == "packed_mesh"
    # compile accounting: registry counters + spans both present
    comp = rep["compile"]
    assert comp["counters"]["misses"] >= 2       # default + packed round
    assert comp["counters"]["first_call_ms"] > 0
    assert any(k.endswith(":first_call") for k in comp["spans"])
    # device lane: sampler ran at every round boundary (CPU falls back to
    # host RSS, so the lane exists on every backend the tests run on)
    assert rep["device_mem"]["samples"] >= 4
    assert rep["device_mem"]["high_water"]
    # and the Perfetto export routes it to the dedicated devices track
    out = str(tmp_path / "perfetto.json")
    from fedml_tpu.obs.export import DEVICE_LANE_PID, write_chrome_trace

    write_chrome_trace(out, events)
    evs = json.load(open(out))["traceEvents"]
    lane = [e for e in evs if e.get("pid") == DEVICE_LANE_PID]
    assert any(e.get("ph") == "C" for e in lane)
    assert any(e.get("ph") == "M" and e["args"]["name"] == "devices"
               for e in lane)


def test_sharded_mesh_rounds_traced(tmp_path):
    """The non-packed (resident-sharded) mesh path emits per-round
    mesh_step device spans too."""
    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel.mesh import client_mesh

    d = str(tmp_path / "tr")
    ds = make_synthetic_classification(
        "mesh-gr", (6,), 3, 4, records_per_client=8,
        partition_method="homo", batch_size=4, seed=0)
    api = CrossSiloFedAvgAPI(
        ds, _mesh_cfg(d, pack_lanes=0, comm_round=2),
        create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]),
        mesh=client_mesh(2))
    api.train()
    tr = _load_trace_report()
    rep = tr.analyze(tr.load_trace_dir(d))
    assert rep["anomalies"] == []
    for entry in rep["timeline"]:
        assert entry["critical_path"]["kind"] == "mesh"
        assert entry["critical_path"]["path"] == "sharded"


def test_mesh_gossip_rounds_traced(tmp_path):
    """MeshDecentralizedFedAPI rides the traced wrapper too (the last
    paradigm that used to override run_round untraced)."""
    from fedml_tpu.algorithms.decentralized import MeshDecentralizedFedAPI
    from fedml_tpu.parallel.mesh import client_mesh

    d = str(tmp_path / "tr")
    ds = make_synthetic_classification(
        "mesh-go", (6,), 3, 4, records_per_client=8,
        partition_method="homo", batch_size=4, seed=0)
    cfg = FedConfig(model="lr", client_num_in_total=4, client_num_per_round=4,
                    comm_round=2, batch_size=4, lr=0.1,
                    frequency_of_the_test=1, trace_dir=d)
    api = MeshDecentralizedFedAPI(ds, cfg, mesh=client_mesh(4, axis="nodes"))
    api.train()
    tr = _load_trace_report()
    rep = tr.analyze(tr.load_trace_dir(d))
    assert rep["anomalies"] == []
    assert rep["rounds"] == 2
    assert all(e["critical_path"]["path"] == "gossip"
               for e in rep["timeline"])


# -- per-host tracer identity (process_index, rank) -------------------------

def test_per_host_trace_files_merge_into_one_timeline(tmp_path):
    """Two-process layout over the local transport: each simulated HOST
    process (distinct process_index, as parallel/mesh.py sets under
    jax.distributed) runs a 3-rank federation into the SAME trace dir. The
    per-host files must coexist (no clobbering) and merge into one timeline
    with every round on every (process, rank) and no orphan recvs."""
    d = str(tmp_path / "tr")
    for proc in (0, 1):
        obs.reset()
        obs.set_process_index(proc)
        run_fedavg_edge(_edge_ds(), _edge_cfg(trace_dir=d), worker_num=2)
    files = sorted(os.listdir(d))
    assert files == [
        "trace-p1-rank0.jsonl", "trace-p1-rank1.jsonl",
        "trace-p1-rank2.jsonl",
        "trace-rank0.jsonl", "trace-rank1.jsonl", "trace-rank2.jsonl",
    ]
    tr = _load_trace_report()
    events = tr.load_trace_dir(d)
    rep = tr.analyze(events)
    assert rep["anomalies"] == [], rep["anomalies"]
    labels = {f"p{p}/r{r}" for p in (0, 1) for r in (0, 1, 2)}
    assert set(rep["ranks"]) == labels
    for entry in rep["timeline"]:
        assert set(entry["ranks"]) == labels   # every host, every rank
    # no orphan recvs across the merge: every recv's mid has its send
    sends = {e["args"]["mid"] for e in events if e.get("name") == "send"}
    recvs = {e["args"]["mid"] for e in events if e.get("name") == "recv"}
    assert recvs and recvs <= sends


# -- trace_report: registry-only dirs are "nothing to analyze" --------------

def test_trace_report_registry_only_dir_exits_2(tmp_path, capsys):
    """Regression: a trace dir holding only registry snapshots (a run that
    flushed counters but never opened a span) used to report success with
    an empty timeline; it must exit 2 like an empty dir."""
    tr = _load_trace_report()
    d = _write_trace(tmp_path, "registry_only", [
        {"ph": "M", "name": "trace_meta", "rank": 0, "ts": 100,
         "args": {"trace_id": "x"}},
        {"ph": "C", "name": "registry", "cat": "registry", "ts": 101,
         "rank": 0, "args": {"values": {"wire/sent": 3}}},
    ])
    assert tr.main([d]) == 2
    # one real span flips it back to analyzable
    with open(os.path.join(d, "trace-rank0.jsonl"), "a") as f:
        f.write(json.dumps(
            {"ph": "X", "name": "round", "cat": "round", "ts": 110,
             "rank": 0, "dur": 5, "sid": 1, "args": {"round": 0}}) + "\n")
    assert tr.main([d]) == 0
    capsys.readouterr()


# -- fedscope timed_build: counter consistency on failure --------------------

def test_timed_build_raising_builder_records_nothing():
    """Regression (ISSUE 6): a builder that raises must not leave a partial
    misses/build_ms entry — the caller's LRU never stores the step, so a
    retry is a fresh build that must count exactly once."""
    from fedml_tpu.obs import compile_counters, timed_build

    g = compile_counters()
    before = g.as_dict()

    def boom():
        raise RuntimeError("builder exploded")

    with pytest.raises(RuntimeError, match="builder exploded"):
        timed_build("t1_raise_build", ("k",), boom)
    assert g.as_dict() == before, "partial counter entry after failed build"

    # the retry (a working builder) counts exactly one miss
    step = timed_build("t1_raise_build", ("k",), lambda: (lambda x: x + 1))
    assert g.get("misses.t1_raise_build", 0) == \
        before.get("misses.t1_raise_build", 0) + 1
    assert g.get("misses", 0) == before.get("misses", 0) + 1
    assert step(2) == 3


def test_timed_build_raising_first_call_retimed_not_recorded():
    """A first invocation that raises (failed trace/compile) propagates
    with no first_call_ms recorded; the NEXT invocation — where the
    compile genuinely happens — is timed as the first call."""
    from fedml_tpu.obs import compile_counters, timed_build

    g = compile_counters()
    calls = {"n": 0}

    def fn(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("first call dies in trace")
        return x * 2

    step = timed_build("t1_raise_first", ("k",), lambda: fn)
    before_fc = g.get("first_call_ms", 0.0)
    with pytest.raises(ValueError, match="first call dies"):
        step(3)
    assert g.get("first_call_ms", 0.0) == before_fc, \
        "first_call_ms recorded for a raising first call"
    assert step(3) == 6                       # retry succeeds...
    assert g.get("first_call_ms", 0.0) > before_fc   # ...and IS the compile
    assert step(4) == 8                       # steady state: no re-timing
    after = g.get("first_call_ms", 0.0)
    step(5)
    assert g.get("first_call_ms", 0.0) == after


# -- the set-up log: setup_span, the compile listener (ISSUE 35) -------------

def _new_records(last_id):
    return [r for r in obs.setup_log().records() if r.id > last_id]


def _last_id():
    return max((r.id for r in obs.setup_log().records()), default=0)


def test_setup_spans_nest_on_their_own_thread_and_record_with_tracing_off():
    """A set-up span's parent is the set-up span open on the SAME thread
    when it started; another thread's spans start a tree of their own. The
    records exist with every tracer off, on time.perf_counter."""
    import threading

    assert not obs.tracing_enabled()
    last, t_before = _last_id(), time.perf_counter()
    inside, done = threading.Event(), threading.Event()

    def other_thread():
        inside.wait(10)
        with obs.setup_span("t35/other", who="thread"):
            with obs.setup_span("t35/other/child"):
                pass
        done.set()

    th = threading.Thread(target=other_thread)
    th.start()
    with obs.setup_span("t35/outer", api="X") as outer:
        with obs.setup_span("t35/inner") as inner:
            inside.set()
            assert done.wait(10)
            inner.set("bytes", 7)
    th.join(10)
    assert not th.is_alive()
    recs = {r.name: r for r in _new_records(last)}
    assert set(recs) == {"t35/outer", "t35/inner", "t35/other",
                         "t35/other/child"}
    assert recs["t35/outer"].parent is None and outer.rec is recs["t35/outer"]
    assert recs["t35/inner"].parent == recs["t35/outer"].id
    assert recs["t35/inner"].ids == {"bytes": 7}
    assert recs["t35/outer"].ids == {"api": "X"}
    # opened while t35/inner was open on the main thread: not its child
    assert recs["t35/other"].parent is None
    assert recs["t35/other/child"].parent == recs["t35/other"].id
    assert recs["t35/other"].thread != recs["t35/outer"].thread
    o, i = recs["t35/outer"], recs["t35/inner"]
    assert t_before <= o.t0 <= i.t0 <= i.t1 <= o.t1 <= time.perf_counter()
    assert o.seconds == o.t1 - o.t0 and not obs.setup_log().open_stack()


def test_setup_log_is_capped_and_counts_what_falls_off():
    log = tracer.SetupLog(cap=3)
    for k in range(5):
        log.close(log.new(f"r{k}", {}))
    assert len(log) == 3 and log.dropped == 2
    assert [r.name for r in log.records()] == ["r2", "r3", "r4"]
    assert len({r.id for r in log.records()}) == 3
    # the process's own log is bounded the same way
    assert obs.setup_log()._records.maxlen == 4096


def test_setup_span_is_one_ring_record_under_trace_dir(tmp_path):
    """Under --trace_dir a set-up span lands in rank 0's ring ONCE, whatever
    the round sampling says (set-up belongs to no round)."""
    obs.configure(str(tmp_path), sample_rate=0.0)
    with obs.setup_span(tracer.SPAN_SETUP_PLACE) as placing:
        placing.set("bytes", 12)
    events = [e for e in obs.get_tracer(0).drain() if e["ph"] == "X"]
    assert [(e["cat"], e["name"], e["args"]) for e in events] == [
        ("setup", "place_data", {"bytes": 12})]


def test_a_compile_event_lands_under_the_open_span_on_the_logs_clock():
    """The compiler's lower / load events become records of the set-up log:
    child of the set-up span open on the thread, JAX's time.time() interval
    moved onto perf_counter, fun_name kept, the persistent cache's verdict
    since the thread's last load on the load. jaxpr_trace_duration is not
    read (it fires for every inner jit inside the outer one's interval)."""
    from jax import monitoring

    lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    load = "/jax/core/compile/backend_compile_duration"
    last = _last_id()
    with obs.setup_span(tracer.SPAN_BUILD, program="t35", phase="first_call"
                        ) as call:
        now_wall, now_perf = time.time(), time.perf_counter()
        monitoring.record_event_time_span(
            "/jax/core/compile/jaxpr_trace_duration", now_wall - 0.9,
            now_wall - 0.5, fun_name="jit(fed)")
        monitoring.record_event_time_span(lower, now_wall - 0.45,
                                          now_wall - 0.30, fun_name="jit(fed)")
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event_time_span(load, now_wall - 0.25,
                                          now_wall - 0.05, fun_name="jit(fed)")
        monitoring.record_event_time_span(load, now_wall - 0.04,
                                          now_wall - 0.01, fun_name="jit(two)")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    monitoring.record_event_time_span(load, time.time() - 0.5, time.time(),
                                      fun_name="jit(callers)")
    recs = {r.ids.get("fun_name", "span") + "/" + r.ids.get("cache", "")
            : r for r in _new_records(last)}
    assert set(recs) == {"span/", "jit(fed)/", "jit(fed)/hit", "jit(two)/none",
                         "jit(callers)/miss"}
    lo, hit = recs["jit(fed)/"], recs["jit(fed)/hit"]
    assert lo.name == tracer.SPAN_BUILD_LOWER
    assert hit.name == recs["jit(two)/none"].name == tracer.SPAN_BUILD_LOAD
    for r in (lo, hit, recs["jit(two)/none"]):
        assert r.parent == call.rec.id and "by" not in r.ids
    assert recs["jit(callers)/miss"].parent is None
    # the interval, moved from time.time() onto perf_counter
    assert abs(hit.t0 - (now_perf - 0.25)) < 0.02
    assert abs(hit.seconds - 0.20) < 1e-6 and abs(lo.seconds - 0.15) < 1e-6
    assert call.rec.t0 - 0.5 < lo.t0 < hit.t0 < call.rec.t1


def test_a_compile_the_programs_code_asked_for_names_its_asker():
    """A real compile: an eager op of the package's own code is a program,
    and its records say which function asked (`by`); the same op asked for
    by the test says nothing."""
    import jax.numpy as jnp

    from fedml_tpu.core.pytree import tree_weighted_mean

    last = _last_id()
    stacked = {"w": jnp.ones((3, 35, 7), jnp.float32)}
    jax.block_until_ready(tree_weighted_mean(stacked, jnp.ones((3,))))
    mine = [r for r in _new_records(last) if r.name == tracer.SPAN_BUILD_LOAD]
    asked = [r for r in mine if "by" in r.ids]
    # the arrays the test itself made (jnp.ones) name no asker
    assert asked and len(asked) < len(mine)
    assert all(r.ids["by"].startswith("fedml_tpu.core.pytree:")
               and r.ids["fun_name"].startswith("jit(") and r.parent is None
               and r.ids["cache"] in ("hit", "miss", "none") for r in asked)


def test_timed_build_spans_record_attempts_and_counters_record_successes():
    """timed_build's ONE timing mechanism: each interval is a
    fedml/round/build set-up record (phase, program, shape_key), the
    counters are read off the records of the intervals that succeeded, in
    aggregate and per program."""
    from fedml_tpu.obs import compile_counters, timed_build

    g = compile_counters()
    before, last = g.as_dict(), _last_id()
    with pytest.raises(ZeroDivisionError):
        timed_build("t35_build", ("k", 1), lambda: 1 / 0)
    calls = {"n": 0}

    def fn(x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ValueError("first call dies")
        return x + 1

    with pytest.raises(ZeroDivisionError):
        timed_build("t35_build", ("k", 1), lambda: 1 / 0)
    assert g.as_dict() == before
    step = timed_build("t35_build", ("k", 1), lambda: fn)
    with pytest.raises(ValueError):
        step(1)
    assert step(1) == 2 and step(2) == 3
    recs = [r for r in _new_records(last) if r.name == tracer.SPAN_BUILD]
    assert all(r.ids["program"] == "t35_build"
               and r.ids["shape_key"] == "('k', 1)" for r in recs)
    # attempts: three constructions (two raised), two first calls (one did)
    assert [r.ids["phase"] for r in recs] == [
        "construct", "construct", "construct", "first_call", "first_call"]
    assert g["misses.t35_build"] == 1
    assert g["build_ms.t35_build"] == pytest.approx(recs[2].seconds * 1e3)
    assert g["first_call_ms.t35_build"] == pytest.approx(recs[4].seconds * 1e3)
    assert g["first_call_ms"] == pytest.approx(
        before.get("first_call_ms", 0.0) + recs[4].seconds * 1e3)
    assert g["build_ms"] == pytest.approx(
        before.get("build_ms", 0.0) + recs[2].seconds * 1e3)


def test_the_constructors_name_their_parts_in_the_setup_log():
    """fedml/setup/api around the whole constructor (a subclass's wraps its
    base's), init_variables / local_train / place_data inside it with the
    bytes put, every program's construction a fedml/round/build record."""
    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel.mesh import client_mesh

    last = _last_id()
    api = _packed_api()
    recs = _new_records(last)
    by_id = {r.id: r for r in recs}
    (top,) = [r for r in recs if r.name == tracer.SPAN_SETUP_API]
    assert top.ids == {"api": "FedAvgAPI"} and top.parent is None
    parts = {r.name: r for r in recs if r.parent == top.id}
    assert {tracer.SPAN_SETUP_INIT, tracer.SPAN_SETUP_LOCAL_TRAIN,
            tracer.SPAN_SETUP_PLACE, tracer.SPAN_BUILD} <= set(parts)
    assert parts[tracer.SPAN_SETUP_INIT].ids == {"model": "lr",
                                                 "jitted": False}
    placed = sum(int(a.nbytes) for a in api._dev_train)
    assert parts[tracer.SPAN_SETUP_PLACE].ids == {"bytes": placed} and placed
    assert parts[tracer.SPAN_BUILD].ids["phase"] == "construct"
    assert sum(r.seconds for r in parts.values()) <= top.seconds
    # every compile of the constructor is a descendant of its span
    for r in recs:
        if r.name in (tracer.SPAN_BUILD_LOWER, tracer.SPAN_BUILD_LOAD):
            while r.parent in by_id:
                r = by_id[r.parent]
            assert r is top

    last = _last_id()
    ds = make_synthetic_classification(
        "mesh-tr", (6,), 3, 4, records_per_client=8,
        partition_method="homo", batch_size=4, seed=0)
    CrossSiloFedAvgAPI(
        ds, _mesh_cfg(None),
        create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:]),
        mesh=client_mesh(2))
    recs = _new_records(last)
    outer, inner = sorted((r for r in recs if r.name == tracer.SPAN_SETUP_API),
                          key=lambda r: r.t0)
    assert outer.parent is None and inner.parent == outer.id
    assert outer.ids == inner.ids == {"api": "CrossSiloFedAvgAPI"}
    mesh_parts = [r for r in recs if r.parent == outer.id]
    assert [r.ids["bytes"] > 0 for r in mesh_parts
            if r.name == tracer.SPAN_SETUP_PLACE] == [True]
    assert [r.ids["program"] for r in mesh_parts
            if r.name == tracer.SPAN_BUILD] == ["mesh_packed_round"]


def test_steady_rounds_write_nothing_to_the_setup_log():
    """Set-up's records never come one a round: after the rounds that build
    the programs, 50 more leave the log as it was."""
    api = _packed_api()
    for r in range(2):
        jax.block_until_ready(api.run_round(r))
    last, size = _last_id(), len(obs.setup_log())
    assert [r for r in obs.setup_log().records() if r.name == tracer.SPAN_BUILD
            and r.ids["phase"] == "first_call"]
    for r in range(2, 52):
        loss = api.run_round(r)
    jax.block_until_ready(loss)
    assert _new_records(last) == [] and len(obs.setup_log()) == size


# -- fedsketch: deterministic head-based span sampling (ISSUE 10) -----------

def test_span_sampled_is_a_pure_function():
    """The keep/drop verdict is a pure hash of (seed, round, entity): same
    inputs -> same verdict, across calls and regardless of global state;
    fractions track the rate; rate 0/1 are exact."""
    from fedml_tpu.obs.tracer import span_sampled

    keep = [r for r in range(2000) if span_sampled(r, rate=0.3, seed=11)]
    assert keep == [r for r in range(2000) if span_sampled(r, rate=0.3, seed=11)]
    assert 0.25 < len(keep) / 2000 < 0.35
    assert all(span_sampled(r, rate=1.0, seed=11) for r in range(50))
    assert not any(span_sampled(r, rate=0.0, seed=11) for r in range(50))
    # seed and entity both shift the verdict stream (decorrelated heads)
    assert keep != [r for r in range(2000) if span_sampled(r, rate=0.3, seed=12)]
    assert keep != [r for r in range(2000)
                    if span_sampled(r, 5, rate=0.3, seed=11)]
    # a kept round at rate r stays kept at any higher rate (nested samples:
    # raising --trace_sample_rate only ADDs rounds, never swaps them)
    for r in range(200):
        if span_sampled(r, rate=0.2, seed=11):
            assert span_sampled(r, rate=0.6, seed=11)


def test_sampled_tracing_sim_bit_identical_and_subset(tmp_path):
    """The ISSUE 10 sampling pin (sim half): a --trace_sample_rate run
    computes exactly the unsampled run's model state, and its trace holds
    exactly the rounds span_sampled predicts — a bounded, reproducible
    subset."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.obs.tracer import span_sampled

    def run(trace_dir, rate):
        obs.reset()
        ds = make_synthetic_classification(
            "tr-samp", (6,), 3, 4, records_per_client=8,
            partition_method="homo", batch_size=4, seed=0)
        cfg = FedConfig(model="lr", client_num_in_total=4,
                        client_num_per_round=4, comm_round=8, batch_size=4,
                        lr=0.1, frequency_of_the_test=100, seed=0,
                        trace_dir=trace_dir, trace_sample_rate=rate)
        api = FedAvgAPI(ds, cfg)
        api.train()
        return api

    sampled = run(str(tmp_path / "s"), 0.5)
    full = run(str(tmp_path / "f"), 1.0)
    plain = run(None, 0.5)
    for a, b, c in zip(jax.tree.leaves(sampled.variables),
                       jax.tree.leaves(full.variables),
                       jax.tree.leaves(plain.variables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    def round_spans(d):
        events = [json.loads(l)
                  for l in open(os.path.join(d, "trace-rank0.jsonl"))]
        return {e["args"]["round"] for e in events
                if e.get("name") == "round" and e.get("ph") == "X"}

    predicted = {r for r in range(8) if span_sampled(r, rate=0.5, seed=0)}
    assert round_spans(str(tmp_path / "s")) == predicted
    assert predicted < set(range(8))          # a real subset...
    assert predicted                          # ...but not empty
    assert round_spans(str(tmp_path / "f")) == set(range(8))


def test_sampled_tracing_grpc_edge_bit_identical(tmp_path):
    """The ISSUE 10 sampling pin (edge half): a 4-rank grpc federation
    under head sampling computes the unsampled weights, and every rank
    agrees on the per-round verdict — the sampled trace has no rounds
    missing ranks, it just has fewer rounds."""
    pytest.importorskip("grpc")
    from fedml_tpu.comm.grpc_backend import GRPCCommManager
    from fedml_tpu.obs.tracer import span_sampled

    def run(trace_dir, rate, port):
        obs.reset()
        return run_fedavg_edge(
            _edge_ds(), _edge_cfg(seed=1, trace_dir=trace_dir,
                                  trace_sample_rate=rate),
            worker_num=3,
            comm_factory=lambda r: GRPCCommManager(
                rank=r, size=4, base_port=port, host="127.0.0.1"))

    on = run(str(tmp_path / "s"), 0.5, _free_base_port(4))
    off = run(None, 1.0, _free_base_port(4))
    assert [h["loss"] for h in on.test_history] \
        == [h["loss"] for h in off.test_history]
    for a, b in zip(jax.tree.leaves(on.get_global_model_params()),
                    jax.tree.leaves(off.get_global_model_params())):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    predicted = {r for r in range(2) if span_sampled(r, rate=0.5, seed=1)}
    assert predicted == {1}    # seed 1 drops round 0, keeps round 1
    per_rank_rounds = {}
    for r in range(4):
        path = tmp_path / "s" / f"trace-rank{r}.jsonl"
        events = [json.loads(l) for l in open(path)] if path.exists() else []
        per_rank_rounds[r] = {e["args"]["round"] for e in events
                              if e.get("name") == "round"
                              and e.get("ph") == "X"}
    # every rank derived the SAME verdict: the kept round is on all ranks,
    # the dropped round on none
    assert all(rounds == predicted for rounds in per_rank_rounds.values()), \
        per_rank_rounds


def test_tracer_if_sampled_disabled_path_allocates_nothing():
    """tracer_if_sampled keeps the disabled-path contract: tracing off is
    one global read returning None, no hashing, no allocation."""
    import tracemalloc

    from fedml_tpu.obs.tracer import tracer_if_sampled

    assert tracer_if_sampled(0, 0) is None
    gc.collect()
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for r in range(2000):
        tr = tracer_if_sampled(0, r)
        if tr is not None:                    # never taken: tracing is off
            tr.instant("x")
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    growth = sum(s.size_diff for s in after.compare_to(before, "lineno")
                 if s.size_diff > 0)
    assert growth < 64_000, f"disabled tracer_if_sampled leaked {growth} bytes"


# -- fedsketch: simulated two-host sketch merge golden (ISSUE 10) -----------

def test_two_host_sketch_merge_golden(tmp_path, capsys):
    """Two hosts' pulse streams (the per-host flush naming) sit beside a
    trace: trace_report folds their sketch lanes with the exact merge and
    reports ONE distribution. The merged numbers are golden — pure integer
    bucket addition over a deterministic map, so they can never drift."""
    from fedml_tpu.obs.sketch import Sketch

    d = tmp_path / "tr"
    d.mkdir()
    with open(d / "trace-rank0.jsonl", "w") as f:
        f.write(json.dumps(
            {"ph": "X", "name": "round", "cat": "round", "ts": 10,
             "rank": 0, "dur": 5, "sid": 1, "args": {"round": 0}}) + "\n")

    def host_stream(name, train_vals, stale_vals):
        tr_sk, st_sk = Sketch(), Sketch()
        tr_sk.add(train_vals)
        st_sk.add(stale_vals)
        snap = {"v": 1, "ts_ms": 1, "round": 0, "source": "edge_server",
                "sketches": {
                    "train_ms": {**tr_sk.summary(), "enc": tr_sk.encode()},
                    "staleness": {**st_sk.summary(), "enc": st_sk.encode()}}}
        with open(d / name, "w") as f:
            f.write(json.dumps(snap) + "\n")
        return tr_sk, st_sk

    # host 0 is the fast host, host 1 the slow one: only the MERGED view
    # sees the true p90/p99 (each host alone would report its own tail)
    a_tr, a_st = host_stream("pulse.jsonl", [10.0] * 90, [0.0] * 90)
    b_tr, b_st = host_stream("pulse-p1.jsonl", [1000.0] * 10, [4.0] * 10)

    tr = _load_trace_report()
    rc = tr.main([str(d)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "merged across 2 pulse stream(s)" in out
    # golden: the merged lanes equal a single sketch fed with everything
    merged_tr = a_tr.copy().merge(b_tr).summary()
    merged_st = a_st.copy().merge(b_st).summary()
    assert merged_tr["count"] == 100 and merged_st["count"] == 100
    # p50 from the fast host, p99 from the slow one — within 1% buckets
    assert abs(merged_tr["p50"] - 10.0) / 10.0 < 0.02
    assert abs(merged_tr["p99"] - 1000.0) / 1000.0 < 0.02
    assert merged_st["p50"] == 0.0 and merged_st["p99"] > 3.5
    # the report's rendered numbers ARE the merged sketches' numbers
    assert "(n=100)" in out
    assert f"p99 {merged_tr['p99']:>10g}" in out
    assert f"p99 {merged_st['p99']:>10g}" in out


def test_sketch_merge_tolerates_mismatched_and_corrupt_streams(
        tmp_path, capsys):
    """Exit-code contract under bad inputs: a host launched with a
    different --sketch_alpha (unmergeable universe) or a corrupted 'enc'
    is skipped with a stderr note — the report still renders what merges
    and exits by the span graph alone."""
    from fedml_tpu.obs.sketch import Sketch

    d = tmp_path / "tr"
    d.mkdir()
    with open(d / "trace-rank0.jsonl", "w") as f:
        f.write(json.dumps(
            {"ph": "X", "name": "round", "cat": "round", "ts": 10,
             "rank": 0, "dur": 5, "sid": 1, "args": {"round": 0}}) + "\n")

    def stream(name, sk_dict):
        with open(d / name, "w") as f:
            f.write(json.dumps({"v": 1, "ts_ms": 1, "round": 0,
                                "source": "x", "sketches": sk_dict}) + "\n")

    good = Sketch()
    good.add([10.0] * 50)
    other = Sketch(alpha=0.02)           # different universe: won't merge
    other.add([99.0] * 50)
    stream("pulse.jsonl",
           {"train_ms": {**good.summary(), "enc": good.encode()}})
    stream("pulse-p1.jsonl",
           {"train_ms": {**other.summary(), "enc": other.encode()},
            "staleness": {"count": 1, "enc": {"v": 99, "garbage": True}}})
    tr = _load_trace_report()
    rc = tr.main([str(d)])
    out = capsys.readouterr()
    assert rc == 0                        # span graph is clean -> exit 0
    assert "different --sketch_alpha" in out.err
    assert "undecodable sketch 'staleness'" in out.err
    # the deterministic winner (finest alpha on the stream-count/sample
    # tie) is the default-universe stream; the excluded one does NOT
    # inflate the reported stream count
    assert "merged across 1 pulse stream(s)" in out.out
    assert "(n=50)" in out.out
    assert "p50     10.075" in out.out    # the winner's data, not ~99


# -- the round path's one span primitive, and the scopes (ISSUE 24) ---------

def test_span_with_the_tracer_off_is_a_trace_annotation_and_nothing_else():
    assert not obs.tracing_enabled()
    sp = obs.span(tracer.SPAN_PLAN, round=3)
    assert type(sp) is jax.profiler.TraceAnnotation
    with sp:
        pass
    assert obs.get_tracer(0).drain() == []       # the shared disabled tracer


def test_span_with_trace_dir_is_in_the_ring_with_round_id_and_parent(tmp_path):
    obs.configure(str(tmp_path / "t"))
    with obs.span(tracer.SPAN_ROUND, round=5):
        with obs.span(tracer.SPAN_PLAN, round=5):
            pass
        with obs.span(tracer.SPAN_H2D, round=6, chunk=1):
            pass
    ev = {(e["cat"], e["name"]): e for e in obs.get_tracer(0).drain()}
    # the ring keeps its cat / name split: trace_report keys on (round, round)
    assert set(ev) == {("round", "round"), ("round", "plan"),
                       ("prefetch", "h2d")}
    rnd = ev["round", "round"]
    assert rnd["args"] == {"round": 5} and rnd["ph"] == "X" and "psid" not in rnd
    assert ev["round", "plan"]["psid"] == rnd["sid"]
    assert ev["prefetch", "h2d"]["args"] == {"round": 6, "chunk": 1}
    assert ev["prefetch", "h2d"]["psid"] == rnd["sid"]
    # sampled out: the annotation alone, no ring record
    obs.configure(str(tmp_path / "t"), sample_rate=0.5, sample_seed=1)
    assert not tracer.span_sampled(0)
    assert type(obs.span(tracer.SPAN_ROUND, round=0)) is \
        jax.profiler.TraceAnnotation
    assert not hasattr(tracer, "_JAX_BRIDGE")
    import inspect
    assert "jax_bridge" not in inspect.signature(obs.configure).parameters


def _packed_api(**kw):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI

    ds = make_synthetic_classification(
        "tr", (6,), 3, 4, records_per_client=8, partition_method="homo",
        batch_size=4, seed=0)
    cfg = FedConfig(**{**dict(
        model="lr", client_num_in_total=4, client_num_per_round=4,
        comm_round=2, batch_size=4, lr=0.1, frequency_of_the_test=1,
        device_data="on", pack_lanes=2, async_rounds=True), **kw})
    return FedAvgAPI(ds, cfg)


def _packed_step_and_args(api):
    """Round 0's packed round program of ``api``, unbuilt by any cache, and
    the arguments ``_run_packed_round`` would call it with."""
    import jax.numpy as jnp

    from fedml_tpu.parallel.packed import plan_arrays_tuple

    round_plan = api._round_plan(0)
    sampled, plan = round_plan.sampled, round_plan.lanes
    step = api.build_round_step_packed(plan.shape_key)
    tx, ty, tm, _ = api._dev_train
    counts = np.asarray(api.dataset.train_counts, np.float32)[sampled]
    return step, (api.variables, api.server_state, tx, ty, tm,
                  jnp.asarray(sampled, jnp.int32), jnp.asarray(counts),
                  jax.random.PRNGKey(0),
                  tuple(jnp.asarray(a) for a in plan_arrays_tuple(plan)))


def test_profiler_trace_holds_the_round_spans_nested_and_changes_no_bit(tmp_path):
    """Any jax.profiler session sees the program's spans with no switch in
    the program, and a profiled run computes what an unprofiled one does."""
    from jax.profiler import ProfileData

    api = _packed_api()
    with jax.profiler.trace(str(tmp_path / "prof")):
        for r in range(2):
            jax.block_until_ready(api.run_round(r))
    plain = _packed_api()
    for r in range(2):
        jax.block_until_ready(plain.run_round(r))
    for a, b in zip(jax.tree.leaves(api.variables),
                    jax.tree.leaves(plain.variables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    import glob
    (path,) = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                        recursive=True)
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
              dict(ev.stats))
             for pl in ProfileData.from_file(path).planes
             for ln in pl.lines for ev in ln.events
             if ev.name.startswith("fedml/")]
    for r in range(2):
        mine = {n: (s, e) for s, e, n, st in sorted(spans)
                if st.get("round") == r and n != tracer.SPAN_PLAN}
        plans = [(s, e) for s, e, n, st in spans
                 if st.get("round") == r and n == tracer.SPAN_PLAN]
        rs, re_ = mine[tracer.SPAN_ROUND]
        es, ee = mine[tracer.SPAN_ENQUEUE]
        assert plans and all(rs <= s and e <= es for s, e in plans)
        assert rs <= es and ee <= re_
    # the new program's build is a span of its own, inside round 0's enqueue
    # (construction, then the first call, which says how the lanes run)
    builds = [st for _s, _e, n, st in sorted(spans) if n == tracer.SPAN_BUILD]
    key = builds[0]["shape_key"]
    assert builds == [{"program": "packed_step", "phase": "construct",
                       "shape_key": key},
                      {"program": "packed_step", "phase": "first_call",
                       "shape_key": key, "lanes": 2, "lane_width": 2}]
    assert len([1 for *_x, n, _st in spans if n != tracer.SPAN_BUILD]) <= 2 * 6


@pytest.mark.parametrize("kw,missing", [
    (dict(), set()),                                        # packed lanes
    (dict(pack_lanes=0), {tracer.SCOPE_STEP_RESET}),        # the gather step
])
def test_lowered_round_program_names_every_scope(kw, missing):
    import jax.numpy as jnp

    api = _packed_api(**kw)
    plan = api._round_plan(0)
    sampled, bucket = plan.sampled, plan.bucket
    rk = jax.random.PRNGKey(0)
    if api.config.pack_lanes > 0:
        step, args = _packed_step_and_args(api)
    else:
        step = api.build_round_step_gather(bucket)
        args = (api.variables, api.server_state, *api._dev_train,
                jnp.asarray(sampled, jnp.int32),
                jnp.ones((len(sampled),), jnp.float32), rk)
    text = step.lower(*args).as_text(debug_info=True)
    table = {v for k, v in vars(tracer).items() if k.startswith("SCOPE_")}
    # the parts of a decoder LM's step are named by LM programs only
    # (tests/test_latent_moe.py holds those)
    lm = {v for k, v in vars(tracer).items() if k.startswith("SCOPE_LM_")}
    assert len(table) == 21 and len(lm) == 12
    import re
    found = set(re.findall(r"fedml\.[a-z_.]+", text))
    assert found == table - lm - missing
    # a scope is metadata: the program's text without locations has none
    assert "fedml." not in step.lower(*args).as_text()


def test_build_span_and_counters_carry_the_lane_width(tmp_path):
    """A conv model at 4 lanes runs them 2 at a time (parallel/packed.
    lane_vmap_width): the first call's fedml/round/build span and the
    compile counters say so, and the chunk loop is a ``while`` of the
    fedml.step scope, around the lane scan's own."""
    import glob
    import re

    from jax.profiler import ProfileData

    from fedml_tpu.algorithms.fedavg import FedAvgAPI

    ds = make_synthetic_classification(
        "tr-conv", (8, 8, 1), 3, 4, records_per_client=8,
        partition_method="homo", batch_size=4, seed=0)
    api = FedAvgAPI(ds, FedConfig(
        model="cnn", client_num_in_total=4, client_num_per_round=4,
        comm_round=2, batch_size=4, lr=0.1, frequency_of_the_test=1,
        device_data="on", pack_lanes=4, async_rounds=True))
    with jax.profiler.trace(str(tmp_path / "prof")):
        jax.block_until_ready(api.run_round(0))
    (path,) = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                        recursive=True)
    builds = [dict(ev.stats) for pl in ProfileData.from_file(path).planes
              for ln in pl.lines for ev in ln.events
              if ev.name == tracer.SPAN_BUILD]
    key = builds[0]["shape_key"]
    ids = {"program": "packed_step", "phase": "first_call", "shape_key": key,
           "lanes": 4, "lane_width": 2}
    assert ids in builds and {"program": "packed_step", "phase": "construct",
                              "shape_key": key} in builds
    g = obs.compile_counters()
    assert g["lanes.packed_step"] == 4 and g["lane_width.packed_step"] == 2

    step, args = _packed_step_and_args(api)
    assert step.lane_ids == {"lanes": 4, "lane_width": 2}
    text = step.lower(*args).as_text(debug_info=True)
    # every loop's name stack up to its first "while": the chunk loop
    # directly under fedml.step, the lane scan (named from inside the chunk
    # loop's body) under vmap(fedml.step)
    heads = {n[:n.index("/while")] for n in re.findall(r'loc\("([^"]*)"', text)
             if "/while" in n}
    assert heads == {"jit(round_step)/" + tracer.SCOPE_STEP,
                     f"vmap({tracer.SCOPE_STEP})"}

    # the flagship's own point, 2 lanes: the width is the lane count
    lr = _packed_api()
    jax.block_until_ready(lr.run_round(0))
    assert g["lanes.packed_step"] == 2 and g["lane_width.packed_step"] == 2


def test_device_memory_sample_reports_the_running_programs_scratch(
        monkeypatch, tmp_path):
    """peak_bytes_in_use leaves out the running program's scratch
    (bytes_reserved: 4,160 of the flagship cell's 4,836 MB, PR 23)."""
    from fedml_tpu.obs import sample_device_memory

    class Dev:
        id = 0

        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    full = {"bytes_in_use": 10, "peak_bytes_in_use": 20,
            "bytes_reserved": 30, "peak_bytes_reserved": 40}
    obs.configure(str(tmp_path / "t"))
    tr = obs.get_tracer(0)
    monkeypatch.setattr(jax, "local_devices", lambda: [Dev(full)])
    assert sample_device_memory(tr, 1) == {
        "d0/bytes_in_use": 10, "d0/peak_bytes": 20,
        "d0/bytes_reserved": 30, "d0/peak_bytes_reserved": 40}
    monkeypatch.setattr(jax, "local_devices",
                        lambda: [Dev({"bytes_in_use": 7})])
    assert sample_device_memory(tr, 2) == {"d0/bytes_in_use": 7}
    (ev,) = [e for e in tr.drain() if e["args"].get("round") == 1]
    assert ev["name"] == "device_mem" and ev["args"]["values"][
        "d0/peak_bytes_reserved"] == 40
