"""Flagship benchmark: FedAvg on CIFAR-10-shaped data with ResNet-56,
32 non-IID clients (BASELINE.md north-star config), standalone-simulation
paradigm on the available device (TPU when present).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Metric: federated training throughput in REAL images/sec through local SGD
(the round is one jitted program: vmap over the sampled cohort of a
lax.scan over minibatch SGD steps + weighted aggregation; cohort-bucketing
trims the scan to the sampled cohort's real max size). Only the cohort's
real records count — masked padding steps are excluded, matching what the
reference's ragged Python loop would process.

vs_baseline: the reference publishes no throughput numbers (SURVEY.md §6),
so the baseline constant is an estimate of the reference stack on its own
headline hardware, 8xV100 (FedML paper, arXiv:2007.13518): 8 workers
training ResNet-56/CIFAR-10 in parallel at ~1500 img/s/GPU fp32 = 12000
img/s cluster-wide, ignoring its MPI state-dict exchange + 0.3 s/message
poll overhead (com_manager.py:78) — i.e., a GENEROUS baseline.

Measured complement (round 3): `tools/ref_bench.py` RUNS the reference's
execution model (torch, sequential clients, per-batch Python loop) on this
host's CPU next to fedml_tpu on the same CPU — measured numbers and the
honest backend attribution live in docs/perf.md §"Measured reference-stack
baseline". The 12k estimate stays as the vs_baseline divisor because the
single-CPU measurement cannot be extrapolated to the 8xV100 cluster.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

BASELINE_IMG_PER_SEC = 12000.0  # 8xV100 estimate, see module docstring

# FLOPs-and-peak accounting lives in fedml_tpu/obs/cost.py (fedcost) so the
# bench headline, tools/roofline_report.py and tools/trace_report.py share
# ONE peak table and ONE cost-model convention — `mfu`/`mfu_basis` are
# computed by the exact logic that used to live inline here. Imported
# lazily (fedml_tpu pulls in jax; keep module import light for tooling).


def _peak_flops(device):
    from fedml_tpu.obs.cost import peak_flops

    return peak_flops(device)


def _fwd_flops_per_image(bundle, variables, input_shape, batch, dtype):
    from fedml_tpu.obs.cost import fwd_flops_per_image

    return fwd_flops_per_image(bundle, variables, input_shape, batch, dtype)

# Bench config (north star: 32 non-IID clients, ResNet-56, CIFAR-10 shapes)
NUM_CLIENTS = 32
CLIENTS_PER_ROUND = 8
RECORDS_PER_CLIENT = 1562  # 50000/32
BATCH_SIZE = 64
EPOCHS = 1
MEASURE_ROUNDS = 5


def _bench_crosssilo(tiny: bool, model: str, rounds: int, batch: int,
                     clients_override: int | None = None):
    """Cross-silo distributed FedAvg on the same chip: full participation
    over a 1-device 'clients' mesh, resident-sharded data, psum aggregation.
    Reports its own real-images/sec so the mesh path's overhead vs the
    simulation paradigm is a measured number, not an assumption."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.synthetic import make_synthetic_classification
    from fedml_tpu.models import create_model
    from fedml_tpu.parallel.mesh import client_mesh

    # BENCH_CS_ALGO: measure a zoo algorithm through the same machinery
    # (the packed schedule carries the cross-silo hooks, so FedOpt/FedNova/
    # AGC ride it — this knob puts a number on that claim)
    algo = os.environ.get("BENCH_CS_ALGO", "fedavg")
    if algo != "fedavg":
        from fedml_tpu.algorithms.fedagc import CrossSiloFedAGCAPI
        from fedml_tpu.algorithms.fednova import CrossSiloFedNovaAPI
        from fedml_tpu.algorithms.fedopt import CrossSiloFedOptAPI

        classes = {
            "fedopt": CrossSiloFedOptAPI,
            "fednova": CrossSiloFedNovaAPI,
            "fedagc": CrossSiloFedAGCAPI,
        }
        if algo not in classes:
            raise ValueError(
                f"BENCH_CS_ALGO={algo!r}: choose one of "
                f"{['fedavg', *sorted(classes)]}")
        CrossSiloFedAvgAPI = classes[algo]

    # BENCH_CS_CLIENTS: silo-count override for the weak-scaling fit
    # (docs/perf.md): per-client records stay constant, so round compute
    # scales with the count and T(c) = a + b*c can be fitted from whole runs.
    clients = 4 if tiny else int(
        clients_override or os.environ.get("BENCH_CS_CLIENTS", NUM_CLIENTS))
    records = 8 if tiny else RECORDS_PER_CLIENT
    ds = make_synthetic_classification(
        "cifar10-bench-cs", (32, 32, 3), 10, clients,
        records_per_client=records,
        partition_method="homo" if tiny else "hetero",
        partition_alpha=0.5, batch_size=batch, seed=0,
    )
    cfg = FedConfig(
        model=model, dataset="cifar10", client_num_in_total=clients,
        client_num_per_round=clients,     # full participation: silo standard
        comm_round=rounds, batch_size=batch, epochs=EPOCHS, lr=0.1,
        momentum=0.9, dtype="bfloat16", frequency_of_the_test=10_000,
        seed=0, async_rounds=True,
        # packed mesh schedule: 2 lanes/device measured best at 32 silos
        # (docs/mfu_experiments.md H5); 0 runs the resident-sharded vmap
        pack_lanes=int(os.environ.get("BENCH_PACK_LANES_CS", "2")),
        # force residency even on the CPU smoke path so tiny mode exercises
        # the same resident-sharded branch the TPU run measures
        device_data="on",
    )
    bundle = create_model(model, 10, dtype=jnp.bfloat16,
                          input_shape=ds.train_x.shape[2:],
                          bn_impl=os.environ.get("BENCH_BN", "xla"),
                          conv_impl=os.environ.get("BENCH_CONV", "xla"))
    api = CrossSiloFedAvgAPI(ds, cfg, bundle, mesh=client_mesh(1))
    # warm TWICE: the first pass's outputs carry fresh shardings, so the
    # second pass triggers one more trace/compile specialization — it must
    # land in the warm-up, not the measured pass
    for _pass in range(2):
        for r in range(1, rounds + 1):
            last = api.run_round(r)
        jax.block_until_ready(last)
    t0 = time.perf_counter()
    for r in range(1, rounds + 1):
        last = api.run_round(r)
    jax.block_until_ready(last)
    dt = time.perf_counter() - t0
    real = padded = 0
    for r in range(1, rounds + 1):
        re, pa = api.round_counts(r)
        real += re * EPOCHS
        padded += pa * EPOCHS
    return {
        "paradigm": "crosssilo shard_map psum, full participation, "
                    "resident-sharded",
        "algorithm": algo,
        "clients": clients,
        "packed_schedule": api._packed_mesh is not None,
        "images_per_sec": round(real / dt, 1),
        "padded_images_per_sec": round(padded / dt, 1),
        "rounds_per_sec": round(rounds / dt, 4),
    }


def _bench_crossdevice_r05_basis(tiny: bool):
    """Cross-device paradigm at the reference's own scale: 342,477 logical
    clients, 50 sampled per round (stackoverflow row,
    reference benchmark/README.md:57). The client stack is virtual
    (data/crossdevice.py) — each round materializes ONLY its cohort
    host-side and ships it; this row measures that whole sampled path:
    sampling at 342k, cohort materialization, host->device, the round
    program, aggregation. Measured as a host-round-pipeline A/B:
    --host_pipeline_depth 0 (serial) vs BENCH_XDEV_DEPTH (default 2)
    prefetched rounds, with stage timings (utils/metrics.round_stats).
    Since ISSUE 13 this is the SAME-HOST BASIS row the fedsched block's
    uplift is judged against (the r05 artifact's 46.8 clients/s operating
    point, re-measured on whatever host runs this bench)."""
    import jax

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data import load_dataset
    from fedml_tpu.models import create_model
    from fedml_tpu.utils.metrics import round_stats

    clients = 1000 if tiny else int(
        os.environ.get("BENCH_XDEV_CLIENTS", "342477"))
    cohort = 10 if tiny else 50
    rounds = 1 if tiny else 3
    depth = int(os.environ.get("BENCH_XDEV_DEPTH", "2"))
    ds = load_dataset("stackoverflow_lr_full", client_num_in_total=clients,
                      batch_size=10)
    bundle = create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:])

    from fedml_tpu.obs import pulse_if_enabled

    plane = pulse_if_enabled()

    def measure(pipeline_depth: int):
        cfg = FedConfig(
            model="lr", dataset="stackoverflow_lr",
            client_num_in_total=clients, client_num_per_round=cohort,
            comm_round=rounds, batch_size=10, epochs=1, lr=0.05, seed=0,
            frequency_of_the_test=10_000,
            # bf16 halves the dominant cost of this row: the per-round
            # uplink of the materialized cohort (10k-dim features, 140 MB
            # as f32)
            dtype="bfloat16", async_rounds=True,
            host_pipeline_depth=pipeline_depth,
            host_pipeline_workers=int(
                os.environ.get("BENCH_XDEV_WORKERS", "0")))
        api = FedAvgAPI(ds, cfg, bundle)
        for r in range(1, rounds + 1):      # warm the compile
            last = api.run_round(r)
        jax.block_until_ready(last)
        api._stage_rows.clear()
        ds.materialized_rows = 0
        pf = api._host_prefetcher()
        if pf is not None:
            # steady state for the measured window: in a long run every
            # round is prefetched during its predecessor; without this the
            # window's FIRST round pays a cold on-demand build and a
            # 3-round measurement understates the pipeline by ~1/3
            pf.prime(1, wait=True)
        # fresh per-client profiles for the MEASURED window only: the warm
        # rounds above (and the other A/B arm's identical cohorts) would
        # otherwise double participation counts and seed EMA train-ms with
        # compile-dominated warmup walls
        if plane is not None and plane.profiler is not None:
            plane.profiler.reset()
        t0 = time.perf_counter()
        for r in range(1, rounds + 1):
            last = api.run_round(r)
        jax.block_until_ready(last)
        dt = time.perf_counter() - t0
        real = sum(api.round_counts(r)[0] for r in range(1, rounds + 1))
        row = {
            "rounds_per_sec": round(rounds / dt, 4),
            "clients_per_sec": round(rounds * cohort / dt, 2),
            "examples_per_sec": round(real / dt, 1),
            # with the pipeline on this includes speculative prefetches of
            # rounds past the measured window — real work the pipeline does
            "materialized_rows": int(ds.materialized_rows),
            "stage": round_stats(api._stage_rows, pipeline_depth),
        }
        api.close()
        return row

    off = measure(0)
    on = measure(depth) if depth > 0 else None
    head = on or off
    # fedpulse profiler aggregates of the HEAD arm (the last measured):
    # per-client EMA train-ms spread, participation fairness, store bytes,
    # and the fedsketch percentile lanes (p50/p90/p99 train-ms etc — the
    # `sketches` block tools/bench_report.py's p99 trajectory columns read)
    # — the live-telemetry evidence at the 342k-client operating point
    profiler_agg = plane.aggregates() if plane is not None else None
    return {
        "paradigm": "cross-device sampled materialization (virtual client "
                    "stack, O(cohort) memory, host round pipeline)",
        "clients_total": clients,
        "clients_per_round": cohort,
        "rounds_per_sec": head["rounds_per_sec"],
        "clients_per_sec": head["clients_per_sec"],
        "examples_per_sec": head["examples_per_sec"],
        "materialized_rows": head["materialized_rows"],
        "device_resident": False,
        "profiler": profiler_agg,
        "pipeline_ab": {
            "off": off, "on": on, "depth": depth,
            "speedup": (round(on["rounds_per_sec"] / off["rounds_per_sec"], 3)
                        if on else None),
        },
    }


def _bench_fedsched(tiny: bool):
    """fedsched (ISSUE 13): the scheduled, streaming cross-device round
    path at MILLION-client scale — thousand-client cohorts streamed
    through the O(1) accumulator in packed-lane sub-cohort chunks, with a
    cohort-policy A/B (uniform vs speed).

    Three arms on one million-client synthetic cross-device stack
    (lognormal per-client record counts — the heterogeneity the policy
    schedules against):

    - ``cohort50_batch``: today's path (uniform draw, batch aggregation)
      at the r05 operating point's cohort — the same-dataset scaling basis;
    - ``streamed_uniform``: 1000-client cohorts in ``--cohort_chunk``
      packed-lane chunks folded into the streaming accumulator, uniform
      draw — isolates cohort-scale + streaming;
    - ``streamed_speed``: + ``--cohort_policy speed`` over the population
      count prior (``snapshot_from_counts``: every client's dataset size
      is registration-time metadata; ``ms_per_record`` is calibrated from
      the streamed_uniform arm's measured per-client EMA when the pulse
      profiler is on) — the policy A/B's treatment arm.

    Per arm: clients/s, examples/s (the speed policy trades per-round
    example mass for round rate — both reported), the fedsketch p99
    train-ms tail (shrinks under ``speed``), and the streaming
    accumulator's measured bytes (O(1) in cohort size)."""
    import jax

    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.crossdevice import make_synthetic_crossdevice
    from fedml_tpu.data.sched import snapshot_from_counts
    from fedml_tpu.models import create_model
    from fedml_tpu.obs import pulse_if_enabled

    clients = 20_000 if tiny else int(
        os.environ.get("BENCH_SCHED_CLIENTS", "1000000"))
    cohort = 40 if tiny else int(
        os.environ.get("BENCH_SCHED_COHORT", "1000"))
    chunk = 10 if tiny else int(os.environ.get("BENCH_SCHED_CHUNK", "250"))
    lanes = int(os.environ.get("BENCH_SCHED_LANES", "4"))
    # measured best at depth 0 on a 1-core host (the pipeline thread
    # contends with the chunk programs); >0 overlaps chunk materialization
    # on hosts with cores to spare
    depth = int(os.environ.get("BENCH_SCHED_DEPTH", "0"))
    rounds = 1 if tiny else 3
    dim, classes = (64, 8) if tiny else (1024, 32)
    ds = make_synthetic_crossdevice(
        "xdev-sched", dim, classes, clients, batch_size=8,
        mean_records=12.0, max_records=96, seed=0)
    bundle = create_model("lr", ds.class_num, input_shape=(dim,))
    plane = pulse_if_enabled()

    def measure(label, cohort_n, policy="uniform", streaming=False,
                snapshot=None):
        cfg = FedConfig(
            model="lr", dataset="xdev-sched",
            client_num_in_total=clients, client_num_per_round=cohort_n,
            comm_round=rounds, batch_size=8, epochs=1, lr=0.1, seed=0,
            frequency_of_the_test=10_000, async_rounds=True,
            cohort_policy=policy,
            stream_aggregate="deterministic" if streaming else "off",
            cohort_chunk=chunk if streaming else 0,
            pack_lanes=lanes if streaming else 0,
            host_pipeline_depth=depth if streaming else 0)
        api = FedAvgAPI(ds, cfg, bundle)
        if snapshot is not None:
            # static signal BEFORE the warm pass: warm and measured rounds
            # must compile/run the identical scheduled cohorts
            api.set_cohort_profiler(snapshot)
        for r in range(1, rounds + 1):
            last = api.run_round(r)
        jax.block_until_ready(last)
        if plane is not None and plane.profiler is not None:
            plane.profiler.reset()   # profile the measured pass only
        t0 = time.perf_counter()
        for r in range(1, rounds + 1):
            last = api.run_round(r)
        jax.block_until_ready(last)
        dt = time.perf_counter() - t0
        real = sum(api.round_counts(r)[0] for r in range(1, rounds + 1))
        row = {
            "arm": label,
            "clients_per_round": cohort_n,
            "policy": policy,
            "stream_aggregate": cfg.stream_aggregate,
            "rounds_per_sec": round(rounds / dt, 4),
            "clients_per_sec": round(rounds * cohort_n / dt, 2),
            "examples_per_sec": round(real / dt, 1),
        }
        if plane is not None and plane.profiler is not None:
            sk = plane.profiler.sketch_summaries().get("train_ms") or {}
            row["p99_train_ms"] = sk.get("p99")
            row["p50_train_ms"] = sk.get("p50")
        if api.stream_stats is not None:
            row["stream"] = dict(api.stream_stats)
        api.close()
        return row

    basis = measure("cohort50_batch", min(50, cohort))
    uniform = measure("streamed_uniform", cohort, streaming=True)
    # count-prior snapshot for the speed arm: ms_per_record calibrated
    # from the uniform arm's measured per-client EMAs when available
    # (the prior's RANKING is scale-invariant, so 1.0 is a safe fallback)
    ms_per_record = 1.0
    if plane is not None and plane.profiler is not None:
        snap = plane.profiler.snapshot()
        if snap.n_seen:
            seen_counts = np.asarray(ds.train_counts)[snap.ids]
            ok = seen_counts > 0
            if ok.any():
                ms_per_record = float(np.median(
                    snap.ema_train_ms[ok] / seen_counts[ok]))
    prior = snapshot_from_counts(ds.train_counts, ms_per_record)
    speed = measure("streamed_speed", cohort, policy="speed",
                    streaming=True, snapshot=prior)
    return {
        "clients_total": clients,
        "clients_per_round": cohort,
        "cohort_chunk": chunk,
        "pack_lanes": lanes,
        "policy": "speed",
        "stream_aggregate": "deterministic",
        "ms_per_record_prior": round(ms_per_record, 6),
        "arms": [basis, uniform, speed],
        # the policy A/B: clients/s uplift and the shrinking p99 tail
        "policy_uplift_clients_per_sec": round(
            speed["clients_per_sec"] / uniform["clients_per_sec"], 3),
        "p99_train_ms": {"uniform": uniform.get("p99_train_ms"),
                         "speed": speed.get("p99_train_ms")},
        "accumulator_bytes": (speed.get("stream") or {}).get(
            "accumulator_bytes"),
    }


def _bench_fedbuff(tiny: bool):
    """fedbuff (ISSUE 14): sync-vs-async A/B under injected stragglers.

    One small edge federation (threads, local transport), three arms on
    the same dataset/model with the same per-message chaos delay — the
    WAN-like iid latency whose per-round MAX gates a synchronous round:

    - ``sync``: fedavg_edge rounds (strict barrier) — every round pays the
      slowest worker's down+up latency;
    - ``async_uniform``: fedbuff arrival mode, ``buffer_k = workers`` —
      folds land at each worker's OWN pace, so a version emits as soon as
      any K contributions arrive and the latency tail stops gating;
    - ``async_speed``: + ``--cohort_policy speed`` over the count prior
      (async dispatch composes with the fedsched CohortScheduler).

    Per arm: clients/s (logical client trainings per wall second — the
    async acceptance is async >= sync under the same injected delay) and
    the version-lag p99 from the fold log (the staleness the decay
    weighting absorbed instead of dropping)."""
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.synthetic import make_synthetic_classification
    from fedml_tpu.distributed.fedavg_edge import run_fedavg_edge
    from fedml_tpu.distributed.fedbuff_edge import run_fedbuff_edge

    workers = int(os.environ.get("BENCH_FEDBUFF_WORKERS", "3"))
    cohort = workers * 2            # every fold trains exactly 2 clients
    delay = 40.0 if tiny else float(
        os.environ.get("BENCH_FEDBUFF_DELAY_MS", "120"))
    versions = 3 if tiny else int(
        os.environ.get("BENCH_FEDBUFF_VERSIONS", "8"))
    dim = 16 if tiny else 64
    ds = make_synthetic_classification(
        "fedbuff-bench", (dim,), 5, cohort, records_per_client=24,
        partition_method="hetero", partition_alpha=0.5, batch_size=8,
        seed=0)

    def cfg(**kw):
        base = dict(
            model="lr", dataset="fedbuff-bench", client_num_in_total=cohort,
            client_num_per_round=cohort, comm_round=versions, batch_size=8,
            epochs=1, lr=0.1, seed=0, frequency_of_the_test=10_000,
            device_data="off")
        base.update(kw)
        return FedConfig(**base)

    # absorb the jitted local-train compile OUTSIDE the timed arms (both
    # paradigms share the jit signature, so one warm run serves all)
    run_fedavg_edge(ds, cfg(comm_round=1), worker_num=workers)

    chaos = dict(chaos_delay_ms=delay, chaos_seed=3)

    def measure(label, runner, **kw):
        t0 = time.perf_counter()
        agg = runner(ds, cfg(**chaos, **kw), worker_num=workers)
        dt = time.perf_counter() - t0
        row = {"arm": label, "wall_s": round(dt, 3)}
        if hasattr(agg, "buffer"):
            trained = agg.uploads_folded * (cohort // workers)
            stal = [r["staleness"] for r in agg.buffer.fold_log]
            row.update({
                "versions": agg.versions_emitted,
                "folds": agg.uploads_folded,
                "clients_per_sec": round(trained / dt, 2),
                "version_lag_p99": (round(float(
                    np.percentile(stal, 99)), 3) if stal else None),
                "version_lag_mean": (round(float(np.mean(stal)), 4)
                                     if stal else None),
            })
        else:
            row.update({
                "rounds": versions,
                "clients_per_sec": round(versions * cohort / dt, 2),
            })
        return row

    sync = measure("sync", run_fedavg_edge)
    uniform = measure("async_uniform", run_fedbuff_edge,
                      buffer_k=workers, buffer_mode="arrival")
    from fedml_tpu.data.sched import snapshot_from_counts

    counts = np.asarray([float(ds.client_slice_cached(c)[3][0])
                         for c in range(cohort)])
    speed = measure("async_speed",
                    lambda d, c, worker_num: run_fedbuff_edge(
                        d, c, worker_num=worker_num,
                        profile_snapshot=snapshot_from_counts(counts)),
                    buffer_k=workers, buffer_mode="arrival",
                    cohort_policy="speed")
    return {
        "workers": workers,
        "buffer_k": workers,
        "buffer_mode": "arrival",
        "delay_ms": delay,
        "versions": versions,
        "arms": [sync, uniform, speed],
        "sync_clients_per_sec": sync["clients_per_sec"],
        "async_clients_per_sec": uniform["clients_per_sec"],
        "async_vs_sync": round(
            uniform["clients_per_sec"] / sync["clients_per_sec"], 3),
        "version_lag_p99": uniform.get("version_lag_p99"),
    }


def _bench_gateway(tiny: bool):
    """fedgate (ISSUE 16): multi-tenant gateway scaling + noisy neighbor.

    One in-process gateway (distributed/gateway.py, local transport)
    multiplexing N concurrent federations over one shared listener, at
    N = 1/4/8 tenants (tiny: 1/2). At every multi-tenant point the FIRST
    tenant is a noisy neighbor — 30% seeded drop chaos — whose retransmit
    storm hits the same shared listener as everyone else; the lanes are
    capped (``wire_inbox_cap``) so flow control actually engages.

    Per point: aggregate and per-tenant rounds/s, the flow-control counts
    (WIRE_BUSY push-backs + stale sheds — the load the cap absorbed,
    never silently), and the p99 upload latency a HEALTHY tenant's pulse
    sketch recorded while the neighbor misbehaved — the isolation
    headline: how much tail latency one tenant's chaos costs another."""
    import shutil
    import tempfile

    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.synthetic import make_synthetic_classification
    from fedml_tpu.distributed.fedavg_edge import run_fedavg_edge
    from fedml_tpu.distributed.gateway import run_gateway

    workers = 2 if tiny else int(os.environ.get("BENCH_GATEWAY_WORKERS",
                                                "13"))
    tenant_points = (1, 2) if tiny else (1, 4, 8)
    rounds = 2
    cap = max(2, workers // 2)
    cohort = workers * 2
    dim = 16 if tiny else 64
    ds = make_synthetic_classification(
        "gateway-bench", (dim,), 5, cohort, records_per_client=16,
        partition_method="hetero", partition_alpha=0.5, batch_size=8,
        seed=0)

    def cfg(**kw):
        base = dict(
            model="lr", dataset="gateway-bench", client_num_in_total=cohort,
            client_num_per_round=cohort, comm_round=rounds, batch_size=8,
            epochs=1, lr=0.1, seed=0, frequency_of_the_test=10_000,
            device_data="off", wire_reliable=True, wire_inbox_cap=cap,
            wire_retry_base_s=0.02, wire_retry_max=8)
        base.update(kw)
        return FedConfig(**base)

    # absorb the jitted local-train compile OUTSIDE the timed points
    run_fedavg_edge(ds, cfg(comm_round=1, wire_inbox_cap=0),
                    worker_num=workers)

    def _last_snap(path):
        last = {}
        try:
            with open(path) as f:
                for line in f:
                    try:
                        s = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    if isinstance(s, dict) and "round" in s:
                        last = s
        except OSError:
            pass
        return last

    def point(n_tenants):
        pulse_dir = tempfile.mkdtemp(prefix="bench-gw-")
        tenants = []
        for i in range(n_tenants):
            kw = {}
            if n_tenants > 1 and i == 0:
                # the noisy neighbor: 30% drop on tenant 0's wire
                kw = dict(chaos_drop=0.3, chaos_dup=0.1, chaos_seed=11)
            tenants.append((f"t{i}", ds, cfg(**kw), workers))
        t0 = time.perf_counter()
        res = run_gateway(tenants, transport="local", timeout=600.0,
                          pulse_dir=pulse_dir, max_tenants=n_tenants)
        dt = time.perf_counter() - t0
        healthy = res[f"t{n_tenants - 1}"]   # never the noisy one
        sk = (_last_snap(healthy["pulse_path"]).get("sketches") or {})
        busy = sum(r["wire"].get("gw_busy_sent", 0) for r in res.values())
        shed = sum(r["wire"].get("gw_shed_stale", 0) for r in res.values())
        row = {
            "tenants": n_tenants,
            "workers": n_tenants * workers,
            "wall_s": round(dt, 3),
            "rounds_per_sec_per_tenant": round(rounds / dt, 3),
            "rounds_per_sec_total": round(n_tenants * rounds / dt, 3),
            "busy_sent": busy,
            "shed_stale": shed,
            "healthy_upload_p99_ms": (sk.get("upload_ms") or {}).get("p99"),
            "errors": [f"{t}: {r['error']}" for t, r in res.items()
                       if r["error"]],
        }
        shutil.rmtree(pulse_dir, ignore_errors=True)
        return row

    points = [point(n) for n in tenant_points]
    top = points[-1]
    return {
        "workers_per_tenant": workers,
        "rounds": rounds,
        "inbox_cap": cap,
        "noisy_chaos_drop": 0.3,
        "scale": points,
        "tenants": top["tenants"],
        "rounds_per_sec_per_tenant": top["rounds_per_sec_per_tenant"],
        "rounds_per_sec_total": top["rounds_per_sec_total"],
        "busy_sent": top["busy_sent"],
        "shed_stale": top["shed_stale"],
        "healthy_upload_p99_ms": top["healthy_upload_p99_ms"],
    }


def _bench_crossdevice(tiny: bool):
    """The cross-device block since ISSUE 13: headline numbers come from
    the fedsched scheduled+streamed path at million-client scale (the
    ``streamed_speed`` arm), with the r05 stackoverflow operating point
    re-measured in the same run as the same-host basis the uplift is
    judged against (the archived r05 artifact's 46.8 clients/s was a
    different host; clients/s only compares within one run). Since ISSUE
    14 it also carries the fedbuff sync-vs-async block — LAST, because the
    edge launchers' ``configure_from`` tears down the bench's profiler-only
    pulse plane (pulse_path is authoritative), and every plane consumer
    above has snapshotted by then."""
    basis = _bench_crossdevice_r05_basis(tiny)
    sched = _bench_fedsched(tiny)
    fedbuff = None
    if not os.environ.get("BENCH_NO_FEDBUFF"):
        fedbuff = _bench_fedbuff(tiny)
    # fedgate (ISSUE 16) runs after fedbuff, same caveat: its warm run is
    # an edge launcher whose configure_from tears down the bench pulse
    # plane (run_gateway itself streams to its own per-tenant planes)
    gateway = None
    if not os.environ.get("BENCH_NO_GATEWAY"):
        gateway = _bench_gateway(tiny)
    head = sched["arms"][-1]      # streamed_speed
    return {
        "paradigm": "cross-device scheduled streaming rounds (fedsched: "
                    "profiler-scheduled cohorts, O(1) streaming "
                    "aggregation, packed-lane sub-cohort chunks)",
        "clients_total": sched["clients_total"],
        "clients_per_round": sched["clients_per_round"],
        "policy": sched["policy"],
        "rounds_per_sec": head["rounds_per_sec"],
        "clients_per_sec": head["clients_per_sec"],
        "examples_per_sec": head["examples_per_sec"],
        "device_resident": False,
        "fedsched": sched,
        "fedbuff": fedbuff,
        "gateway": gateway,
        "r05_basis": basis,
        "uplift_vs_r05_basis": (
            round(head["clients_per_sec"] / basis["clients_per_sec"], 2)
            if basis.get("clients_per_sec") else None),
    }


def main():
    import jax
    import jax.numpy as jnp

    # Persistent compilation cache: the bench compiles one XLA program per
    # distinct round plan (cohort bucket or lane shape); caching makes repeat
    # bench invocations skip straight to the measured pass.
    from fedml_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.synthetic import make_synthetic_classification
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.models import create_model
    from fedml_tpu.obs import cost as fedcost

    # fedcost roofline attribution: every round program the bench builds
    # (the sim packed and gather steps, the mesh packed round)
    # is lowered once more at build time and its per-op GEMM/lane-fill
    # table recorded — pure tracing during the WARMUP pass, so the timed
    # pass is untouched. BENCH_NO_ROOFLINE=1 opts out.
    if not os.environ.get("BENCH_NO_ROOFLINE"):
        fedcost.reset_cost_tables()   # this run's programs only
        fedcost.enable_cost_attribution(True)

    # fedpulse: a profiler-only plane (no pulse stream unless
    # BENCH_PULSE_PATH names one) so the tail carries end-of-run per-client
    # aggregates — participation fairness and EMA train-ms spread become
    # part of the TPU-host trajectory. BENCH_NO_PULSE=1 opts out.
    from fedml_tpu.obs import live as fedpulse

    pulse_plane = None
    if not os.environ.get("BENCH_NO_PULSE"):
        pulse_plane = fedpulse.configure(
            os.environ.get("BENCH_PULSE_PATH"), profile_store=True)

    # fedlens: arm the learning-signal lane for the flagship pass — output-
    # only reductions riding the round program (bit-identical weights,
    # obs/lens.py), so the tail carries the per-client update-norm/drift
    # distribution tails at the flagship operating point. Needs the pulse
    # plane (its profiler owns the sketch lanes). BENCH_NO_LENS=1 opts out.
    from fedml_tpu.obs import lens as fedlens

    if pulse_plane is not None and not os.environ.get("BENCH_NO_LENS"):
        fedlens.configure(True)

    # BENCH_SCALE=tiny: CI/CPU smoke of the same code path (not a benchmark).
    tiny = os.environ.get("BENCH_SCALE") == "tiny"
    model = os.environ.get("BENCH_MODEL", "resnet56")
    records = 8 if tiny else RECORDS_PER_CLIENT
    rounds = 1 if tiny else MEASURE_ROUNDS
    batch = int(os.environ.get("BENCH_BATCH", 8 if tiny else BATCH_SIZE))
    cohort = 2 if tiny else CLIENTS_PER_ROUND

    ds = make_synthetic_classification(
        "cifar10-bench", (32, 32, 3), 10, NUM_CLIENTS,
        records_per_client=records,
        partition_method="homo" if tiny else "hetero",
        partition_alpha=0.5, batch_size=batch, seed=0,
    )
    cfg = FedConfig(
        model=model, dataset="cifar10", client_num_in_total=NUM_CLIENTS,
        client_num_per_round=cohort, comm_round=rounds,
        batch_size=batch, epochs=EPOCHS, lr=0.1, momentum=0.9,
        dtype="bfloat16", frequency_of_the_test=10_000, seed=0,
        # packed schedule (parallel/packed.py): 2 lanes measured best for
        # the cohort-8 sim round — round-4 campaign, docs/mfu_experiments.md
        # H5 (0 restores the bucketed gather schedule)
        pack_lanes=int(os.environ.get("BENCH_PACK_LANES", "2")),
        scan_unroll=int(os.environ.get("BENCH_UNROLL", "1")),
        cohort_vmap_width=int(os.environ.get("BENCH_COHORT_WIDTH", "0")),
        # rounds return device-scalar losses (no per-round host sync): the
        # timed loop pipelines dispatches and blocks ONCE at the end, so the
        # host's dispatch work overlaps device compute instead of
        # serializing after it
        async_rounds=True,
    )
    bundle = create_model(model, 10, dtype=jnp.bfloat16,
                          input_shape=ds.train_x.shape[2:],
                          bn_impl=os.environ.get("BENCH_BN", "xla"),
                          conv_impl=os.environ.get("BENCH_CONV", "xla"))
    api = FedAvgAPI(ds, cfg, bundle)

    # Warmup pass: run every measured round once so each distinct cohort
    # bucket's XLA program is compiled before the timed pass (run_round(r)
    # samples deterministically from r, so the timed pass reuses the exact
    # same programs — warm exactly the measured rounds 1..N).
    # (async_rounds: no per-round sync — the end-of-pass barrier waits on
    # the LAST round's loss, which data-depends on every prior round.)
    for r in range(1, rounds + 1):
        last = api.run_round(r)
    jax.block_until_ready(last)

    # profile the MEASURED pass only: the warmup pass above already fed the
    # same cohorts (participation would double, EMA would blend compiles)
    if pulse_plane is not None and pulse_plane.profiler is not None:
        pulse_plane.profiler.reset()
    t0 = time.perf_counter()
    for r in range(1, rounds + 1):
        last = api.run_round(r)
    jax.block_until_ready(last)  # one sync for the whole pipelined pass
    dt = time.perf_counter() - t0

    # Real images trained in the measured period (padding steps are masked
    # no-ops and do not count), plus the padded count for the curious.
    # round_counts reports the same plan run_round executed — one source
    # of truth for the throughput accounting.
    real_images = padded_images = 0
    for r in range(1, rounds + 1):
        real, padded = api.round_counts(r)
        real_images += real * EPOCHS
        padded_images += padded * EPOCHS

    img_per_sec = real_images / dt
    rounds_per_sec = rounds / dt

    # MFU accounting: fwd FLOPs/image from XLA's cost model, x3 for the
    # training step (fwd + ~2x bwd). Executed compute = the PADDED rate
    # (masked padding steps still burn MXU cycles), so
    # mfu = padded_rate * train_flops_per_image / device bf16 peak — the
    # honest device-utilization number for the roofline discussion
    # (VERDICT r1 weak#1; see docs/perf.md).
    fwd_flops, flops_backend = _fwd_flops_per_image(
        bundle, api.variables, ds.train_x.shape[2:], batch, jnp.bfloat16)
    train_flops = fwd_flops * 3.0 if fwd_flops else None
    peak, peak_entry = _peak_flops(jax.devices()[0])
    mfu = (round(padded_images / dt * train_flops / peak, 4)
           if (train_flops and peak) else None)

    # flagship attribution snapshot NOW, before the paradigm benches below
    # build their own programs: a cross-device FedAvgAPI is the same class,
    # so its host-path programs would overwrite the flagship's records
    # under the same names (tables were reset at attribution enable, so
    # everything recorded so far is the flagship's)
    flagship_tables = fedcost.cost_tables()
    # flagship profiler snapshot for the same reason: the paradigm benches
    # reuse client ids 0..31, which would merge into the flagship's profiles
    flagship_profiler = None
    if pulse_plane is not None:
        flagship_profiler = pulse_plane.aggregates()
        if pulse_plane.profiler is not None:
            pulse_plane.profiler.reset()
    # fedlens summary for the tail: the measured pass's update-norm/drift
    # sketch summaries (bench_report's `p99 update norm` / `drift p99`
    # columns read these) plus the session fold accounting. None when the
    # lens (or the pulse plane it feeds) is off — missing keys render "-".
    lens_summary = None
    if pulse_plane is not None and fedlens.lens_enabled():
        sk = (flagship_profiler or {}).get("sketches") or {}
        st = fedlens.session_stats()
        lens_summary = {"update_norm": sk.get("update_norm"),
                        "drift": sk.get("drift"),
                        "folds": st["folds"], "suspects": st["suspects"]}

    # Cross-silo paradigm on the same hardware (VERDICT r2 #3): the north
    # star names DISTRIBUTED FedAvg, so measure the shard_map mesh path too —
    # full participation (the standard silo deployment), dataset resident and
    # sharded over a 1-device 'clients' mesh, aggregation by weighted psum.
    crosssilo = None
    if not os.environ.get("BENCH_NO_CROSSSILO"):
        crosssilo = _bench_crosssilo(tiny, model, rounds, batch)

    # Cross-device paradigm at the reference's 342,477-client scale
    # (VERDICT r4 #2): sampling + O(cohort) materialization + round.
    crossdevice = None
    if not os.environ.get("BENCH_NO_CROSSDEVICE"):
        crossdevice = _bench_crossdevice(tiny)

    # every HEADLINE program is built by now: snapshot the attribution and
    # switch it off BEFORE the weak-scaling probes re-run smaller configs —
    # cost_tables() keeps latest-wins per program name, so a probe rebuild
    # would overwrite the mesh entry with a shape the headline numbers were
    # never measured on. Disabling here also restores the process-global
    # flag for whoever runs after main() (the tier-1 tiny smoke).
    roofline_tables = fedcost.cost_tables()
    fedcost.enable_cost_attribution(False)

    # Weak-scaling regression pin (VERDICT r4 #8): measure T(c) at c=8/16
    # next to the 32-silo row above, fit T(c) = a + b*c through the
    # endpoints, and check the midpoint against the fit — model drift or a
    # perf regression in the mesh round shows up as a failed tolerance in
    # the artifact itself (docs/perf.md weak-scaling section).
    weak_scaling = None
    if (crosssilo and not tiny and crosssilo["clients"] > 16
            and not os.environ.get("BENCH_NO_WEAKSCALING")):
        c_hi = crosssilo["clients"]   # respect a BENCH_CS_CLIENTS override
        pts = {c_hi: 1.0 / crosssilo["rounds_per_sec"]}
        for c in (8, 16):
            row = _bench_crosssilo(tiny, model, rounds, batch,
                                   clients_override=c)
            pts[c] = 1.0 / row["rounds_per_sec"]
        b = (pts[c_hi] - pts[8]) / (c_hi - 8)
        a = pts[8] - b * 8
        pred16 = a + b * 16
        err = abs(pred16 - pts[16]) / pts[16]
        weak_scaling = {
            "round_seconds": {str(c): round(t, 4) for c, t in pts.items()},
            "fit_overhead_ms": round(a * 1e3, 2),
            "fit_per_silo_ms": round(b * 1e3, 2),
            "midpoint_pred_s": round(pred16, 4),
            "midpoint_err": round(err, 4),
            "ok": bool(err < 0.15),
        }
        if not weak_scaling["ok"]:
            import sys

            print(f"WEAK-SCALING DRIFT: midpoint error {err:.1%} exceeds "
                  f"15% — T(c) is no longer linear in silos; investigate",
                  file=sys.stderr)

    # End-of-run registry snapshot (fedml_tpu/obs): the time/wire/compile
    # counter groups land in the BENCH JSON tail, so the TPU-host trajectory
    # tracks compile amortization (program builds, LRU hits, first-call
    # trace+XLA ms) across PRs — not just wall-clock throughput.
    from fedml_tpu.obs import default_registry

    reg = default_registry()
    registry_snapshot = {}
    for ns in ("time", "wire", "compile"):
        snap = reg.snapshot(ns)
        if snap:
            registry_snapshot[ns] = {
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in snap.items()}

    # fedcost roofline block: the per-op lane table of every program this
    # run built, plus the flagship's flop-weighted MXU output-lane ceiling —
    # mfu above is judged AGAINST this ceiling, not against the datasheet
    # (docs/perf.md "MFU and the roofline"). Static attribution: the same
    # table tools/roofline_report.py derives, embedded so the TPU-host
    # trajectory carries it per PR.
    roofline = None
    tables = roofline_tables
    mfu_vs_lane_ceiling = None
    if tables or flagship_tables:
        # flagship entries win name collisions with the later paradigm
        # benches (same class -> same program names on the host path)
        tables = {**tables, **flagship_tables}
        roofline = {"programs": {}}
        for pname, rec in sorted(tables.items()):
            s = rec["summary"]
            roofline["programs"][pname] = {
                "shape_key": rec["shape_key"],
                "gemm_gflops_per_invocation": round(
                    s["gemm_flops_per_invocation"] / 1e9, 3),
                "out_lane_ceiling": s["out_lane_ceiling"],
                "red_lane_ceiling": s["red_lane_ceiling"],
                "by_output_channels": s["by_output_channels"],
                "top_ops": s["top_ops"][:5],
            }
        # the flagship program = the FLOP-dominant record of the flagship
        # pass (model-agnostic: packed, gather or host round)
        flag_rec = max(
            flagship_tables.values(),
            key=lambda r: r["summary"]["gemm_flops_per_invocation"],
            default=None)
        if flag_rec is not None:
            roofline["flagship_program"] = flag_rec["program"]
            roofline["flagship_out_lane_ceiling"] = \
                flag_rec["summary"]["out_lane_ceiling"]
            # MAC-basis MFU over the measured pass (obs/cost.roofline):
            # the `mfu` headline counts every HLO flop (BN/elementwise VPU
            # work included), which is NOT comparable to a GEMM-MAC lane
            # ceiling — dividing those would overstate the schedule's share
            # of what the lanes allow. One program x `rounds` invocations
            # is the dominant-program approximation (exact for the packed
            # default, where one program executes every round).
            rf = fedcost.roofline(flag_rec["summary"], dt,
                                  invocations=rounds, peak=peak)
            roofline["flagship_mfu_mac"] = rf["mfu_mac"]
            if "mfu_vs_ceiling" in rf:
                mfu_vs_lane_ceiling = rf["mfu_vs_ceiling"]

    result = {
        "metric": f"fedavg_local_sgd_images_per_sec ({model}, CIFAR-10 shapes, 32 non-IID clients, 8/round, bf16)",
        "value": round(img_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / BASELINE_IMG_PER_SEC, 3),
        "rounds_per_sec": round(rounds_per_sec, 4),
        "padded_images_per_sec": round(padded_images / dt, 1),
        "model_flops_per_image": round(train_flops) if train_flops else None,
        "mfu": mfu,
        "crosssilo": crosssilo,
        "crossdevice": crossdevice,
        "weak_scaling": weak_scaling,
        # mfu is an ESTIMATE: fwd FLOPs from XLA's cost model on the named
        # backend x3 for the train step, over the bf16 peak recorded for
        # this exact device_kind — provenance recorded so a cost-model
        # change is visible in the JSON itself
        "mfu_basis": {"flops_cost_model_backend": flops_backend,
                      "fwd_bwd_multiplier": 3.0,
                      "peak_table_entry": peak_entry,
                      "peak_bf16_flops": peak},
        # MAC-basis MFU / lane ceiling: the schedule's share of what the
        # model's GEMM shapes allow (1.0 = lanes are the only limit) —
        # both sides of the division count GEMM multiply-accumulates only
        "mfu_vs_lane_ceiling": mfu_vs_lane_ceiling,
        # fedpulse end-of-run profiler aggregates for the flagship pass
        # (the cross-device block embeds its own at 342k-client scale);
        # carries the fedsketch `sketches` summaries (count + p50/p90/p99
        # per lane) that bench_report's trajectory columns parse
        "profiler": flagship_profiler,
        # fedlens learning-signal tails at the flagship operating point
        "lens": lens_summary,
        "roofline": roofline,
        "registry": registry_snapshot,
        "device": str(jax.devices()[0]),
        # the comparability stamp (ISSUE 13): throughput numbers only mean
        # something against the same device/core-count/model basis —
        # bench_report's >10%-drop gate compares consecutive artifacts ONLY
        # when their bases match (a container/host change re-bases the
        # trajectory instead of reading as a regression; artifacts without
        # the stamp form their own legacy lineage)
        "host_basis": {"device": str(jax.devices()[0]),
                       "cpus": os.cpu_count(), "model": model},
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
