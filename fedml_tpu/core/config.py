"""Typed configuration.

The reference drives everything through ~20 raw argparse flags repeated in
every ``main_*.py`` (fedml_experiments/distributed/fedavg/main_fedavg.py:48-120)
plus bash positional launchers and ad-hoc YAML/CSV sidecars. Here the flag
surface is one dataclass with validation, an argparse bridge that reproduces
the reference flag names, and YAML load/save.
"""

from __future__ import annotations

import argparse
import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional

try:
    import yaml
except ImportError:  # pragma: no cover
    yaml = None


@dataclass
class FedConfig:
    """Union of the reference's experiment flags (main_fedavg.py:48-120,
    main_fedopt.py:54-60, main_fedgkt.py:37-88) with validated defaults."""

    # model / data
    model: str = "lr"
    dataset: str = "mnist"
    data_dir: str = "./data"
    partition_method: str = "hetero"
    partition_alpha: float = 0.5
    class_num: Optional[int] = None

    # federation topology
    client_num_in_total: int = 10
    client_num_per_round: int = 10
    comm_round: int = 10
    group_num: int = 1               # hierarchical FL (group_comm_round below)
    group_comm_round: int = 1

    # local training
    batch_size: int = 32
    client_optimizer: str = "sgd"    # sgd | adam
    lr: float = 0.03
    wd: float = 0.0
    momentum: float = 0.0
    epochs: int = 1
    grad_clip: Optional[float] = None  # reference clips local grads at 1.0 for some trainers

    # server optimizer (FedOpt; reference main_fedopt.py:54-60)
    server_optimizer: str = "sgd"
    server_lr: float = 1.0
    server_momentum: float = 0.0

    # FedProx (reference omitted the prox term — we implement it; mu flag)
    fedprox_mu: float = 0.1

    # robustness (fedavg_robust main flags)
    norm_bound: Optional[float] = None
    stddev: Optional[float] = None
    attack_type: Optional[str] = None
    poison_frac: float = 0.0

    # FedNAS (main_fednas.py --unrolled: second-order DARTS architect)
    unrolled: int = 0

    # FedGKT (main_fedgkt.py:37-88)
    temperature: float = 3.0
    alpha_distill: float = 1.0
    model_client: str = "resnet8"
    model_server: str = "resnet56_server"
    epochs_server: int = 1           # reference --epochs_server / epoch strategy

    # runtime / backend
    backend: str = "mesh"            # mesh | inproc | grpc | mqtt (reference: MPI|GRPC|MQTT)
    # Multi-process deployment (reference: mpirun -np N, run_fedavg_
    # distributed_pytorch.sh:21-23 — one OS process per participant). When
    # rank is set, the entry point starts ONLY this rank's manager over a
    # real transport (gRPC, rank→IP resolved from grpc_ipconfig_path like
    # the reference's grpc_ipconfig.csv, grpc_comm_manager.py:59-60) and
    # blocks until the federation finishes. rank=None (default) keeps the
    # single-process in-memory launch used by simulations and tests.
    rank: Optional[int] = None
    world_size: Optional[int] = None
    grpc_ipconfig_path: Optional[str] = None  # csv "receiver_id,ip"; None = all loopback
    grpc_base_port: int = 50000      # reference: port 50000 + rank
    # Edge-transport payload compression (core/compression.py):
    # "raw" (exact) | "q8" (uint8 affine quantization, ~4x smaller) |
    # "topk:<ratio>" (magnitude sparsification — for update deltas).
    # The reference's --is_mobile JSON-list path is the counterpart
    # (fedavg/utils.py:7-16) — it converts format without saving bytes.
    wire_codec: str = "raw"
    # Edge FedAvg uploads (local - global) deltas with an error-feedback
    # residual instead of full weights (DGC-style). Lossless under
    # wire_codec="raw"; pairs with "topk:<r>"/"q8", whose un-sent mass
    # re-enters the next round's upload.
    wire_delta: bool = False
    # Reliable wire delivery (comm/reliable.py): per-pair sequence numbers,
    # ACK/retransmit with exponential backoff, receiver-side dedup — every
    # protocol handler sees exact-once semantics over a lossy wire. With
    # zero faults the layer is bit-identical to the bare transports
    # (tests/test_chaos.py), so the only cost of enabling it is the ack
    # traffic. Required whenever chaos drop/dup/reorder rates are set.
    wire_reliable: bool = False
    # Reliable-layer retry schedule: exponential backoff from
    # wire_retry_base_s (cap at 20x the base) for up to wire_retry_max
    # retransmits before a message is abandoned (gave_up — the dead-peer
    # oracle fedbuff ejects by). The defaults reproduce the layer's
    # historical schedule (~6.6 s to exhaustion); a LAN/CI federation can
    # shrink detection latency by an order of magnitude, a lossy WAN can
    # deepen the budget. The teardown drain window derives from the
    # schedule automatically.
    wire_retry_base_s: float = 0.05
    wire_retry_max: int = 10
    # Bounded inboxes (comm/local.py, grpc_backend.py, mqtt_backend.py) and
    # the gateway's per-tenant lane queues (comm/flow.py): 0 keeps the
    # historical unbounded queues; > 0 caps delivery-queue depth. On bare
    # transports a full inbox BLOCKS the producer (queue put / gRPC flow
    # control / broker TCP); at the gateway a full lane answers WIRE_BUSY,
    # so the cap requires wire_reliable=True there (the sender's reliable
    # layer consumes the push-back).
    wire_inbox_cap: int = 0
    # Federation gateway quotas (distributed/gateway.py): over-admission is
    # rejected with a typed terminal NACK, never silently. max_tenants caps
    # concurrent federations; tenant_workers (0 = unlimited) caps any one
    # tenant's worker count.
    gateway_max_tenants: int = 8
    gateway_tenant_workers: int = 0
    # Chaos injection (comm/chaos.py): seeded, deterministic wire faults for
    # robustness testing. Rates are per-transmission probabilities; delay is
    # the max per-message latency in ms (uniform draw). chaos_crash_rank /
    # chaos_crash_after crash-stop one rank after that many sends (the
    # killed-process model the straggler deadline handles).
    chaos_seed: int = 0
    chaos_drop: float = 0.0
    chaos_dup: float = 0.0
    chaos_delay_ms: float = 0.0
    chaos_reorder: float = 0.0
    chaos_crash_rank: Optional[int] = None
    chaos_crash_after: Optional[int] = None
    # crash_restart fate: the crash-stopped rank REVIVES after this many
    # seconds of total silence (both directions) and its protocol layer
    # re-announces itself (JOIN) — the recovery path, not just death.
    # None (default) keeps crash-stop permanent.
    chaos_crash_restart_s: Optional[float] = None
    frequency_of_the_test: int = 5
    is_mobile: int = 0
    seed: int = 0
    ci: int = 0                      # --ci fast path (reference CI-script-fedavg.sh)

    # TPU-specific
    mesh_shape: tuple = ()           # e.g. (8,) client axis; () = auto
    dtype: str = "float32"           # compute dtype: float32 | bfloat16
    donate: bool = True
    # Defer the per-round host sync: run_round returns the loss as a device
    # scalar instead of float()ing it, so consecutive rounds pipeline through
    # the dispatch queue (a forced sync idles the device until the host
    # dispatches again; eval/logging rounds still sync when they read the
    # value).
    async_rounds: bool = False
    # Keep the full stacked client dataset resident in HBM and gather the
    # sampled cohort ON DEVICE each round ("auto"|"on"|"off"). The reference
    # re-ships the cohort host->device every round (its DataLoader contract);
    # on TPU that transfer dominates the round (host->device bandwidth), so
    # auto places train data on device whenever it fits the budget below.
    device_data: str = "auto"
    device_data_max_bytes: int = 6_000_000_000
    # Cohort bucketing: pad each round's scan length to the max REAL record
    # count of the sampled cohort, quantized to this many batches (0 = always
    # pad to the global max). Under hetero (LDA) partitions the global n_pad
    # is set by the single biggest client, so every round otherwise burns
    # dead masked SGD steps on pure padding (~40% of compute at alpha=0.5).
    # Each distinct bucket compiles its own XLA program (bounded by
    # n_pad/quantum programs; quantization keeps that small). Note: the
    # per-epoch shuffle draws a permutation of the (truncated) record axis,
    # so a bucketed run composes real records into different minibatches
    # than an unbucketed run — same distribution, different trajectory.
    # Runs are still deterministic per (seed, config).
    bucket_quantum_batches: int = 8
    # Client-packing schedule (parallel/packed.py): pack the sampled cohort
    # into this many fixed-length scan lanes, clients back-to-back with
    # optimizer reset at boundaries — padding shrinks from cohort-max
    # granularity to one batch per client plus what a lane lacks to the
    # longest lane vmapped with it (parallel/packed.chunk_bounds). 0 = off.
    # Each client's trajectory replays the canonical unbucketed program
    # exactly; the aggregate matches up to float summation order. Serves
    # every algorithm with a plain weighted mean OR a crosssilo_hooks contract
    # (FedOpt/FedNova/FedAGC/robust — server state threads through the
    # packed round); only rewired build_local_train / hookless custom
    # aggregate() fall back, with a warning.
    pack_lanes: int = 0
    # lax.scan unroll factor for the local-SGD minibatch loop: XLA fuses
    # across adjacent steps (amortizing per-step loop/weight-traffic
    # overheads) without changing the math — same updates in the same
    # order. Measured on v5e: see docs/mfu_experiments.md.
    scan_unroll: int = 1
    # Host round pipeline (data/pipeline.CohortPrefetcher): keep this many
    # FUTURE rounds' cohorts in flight on background threads — cohort
    # materialization, host bf16 cast, and host->device transfer all overlap
    # the in-flight round's device compute. Applies to the non-device-
    # resident (host) round paths only: the sampled cross-device
    # materialization path and the streaming paradigm. The per-round plan is
    # a pure function of (seed, round_idx), so prefetched rounds are
    # bit-identical to the serial path (0 = serial, today's behavior).
    host_pipeline_depth: int = 0
    # Worker threads fanning cohort materialization out over clients inside
    # one prefetched round (per-client RNG streams are independent, so the
    # parallel materialization is bit-identical to serial). 0 = auto.
    host_pipeline_workers: int = 0
    # fedsched cohort-selection policy (data/sched.py): how the round's
    # cohort is drawn from the client population. "uniform" (default) is
    # today's deterministic draw, bit-identical by construction. "speed"
    # packs cohorts from the fedpulse ClientProfiler's observed EMA
    # train-ms (an oversampled uniform pool, keep the fastest) so one slow
    # client no longer gates the round; "fair" is speed packing with a
    # fixed fraction of the cohort reserved for the least-participated
    # candidates. Profiler-driven policies are pure in (seed, round,
    # profiler-snapshot-at-schedule-time); with no profiler (pulse plane
    # off) they schedule uniform cold-starts and warn once.
    cohort_policy: str = "uniform"
    # fedbuff: asynchronous buffered aggregation (algorithms/fedbuff.py +
    # distributed/fedbuff_edge.py). The server folds each client upload
    # (an update delta against the model version the client trained from)
    # into a StreamAccumulator with a staleness-decayed weight
    # ``n * (1 + staleness)^-buffer_staleness_alpha`` where staleness =
    # server_version - trained_version, and emits a new model version every
    # ``buffer_k`` contributions — no round barrier, no straggler deadline:
    # slow clients contribute with decayed weight instead of being dropped.
    buffer_k: int = 4
    buffer_staleness_alpha: float = 0.5
    # Fold-order contract (mirrors --stream_aggregate): "arrival" folds
    # each upload the moment it lands (the production fast path — results
    # depend on arrival order through float summation + version grouping);
    # "deterministic" folds in the canonical (train-tag, worker) frontier
    # order, making the WHOLE async schedule a pure function of
    # (seed, chaos_seed) — bit-identical replayable under chaos
    # (tests/test_fedbuff.py pins it on local + grpc).
    buffer_mode: str = "arrival"
    # Streaming server-side aggregation (core/streaming.py + the chunked
    # host round path): fold each client contribution into a running
    # weighted accumulator instead of buffering the whole cohort — O(1)
    # memory in cohort size. "off" (default) keeps today's batch
    # aggregation, bit-identical. "deterministic" folds in the fixed plan
    # order (chunk order on the sim path, worker-index order on the edge
    # via hold-and-fold) so results are independent of arrival timing;
    # unchunked it is bit-identical to batch aggregation by construction.
    # "arrival" folds strictly on arrival (the O(1)-strict edge mode);
    # numerics match batch within the fedseg tolerance (float summation
    # order only).
    stream_aggregate: str = "off"
    # Sub-cohort chunk size for the streaming host round path: the sampled
    # cohort materializes, ships and trains in chunks of this many clients,
    # each folded into the streaming accumulator as it finishes — cohort
    # size is bounded by the accumulator (one model copy), not by one
    # jitted program's buffers, which is what thousand-client cohorts
    # need. 0 = whole cohort in one program. Requires stream_aggregate on.
    # With pack_lanes > 0 each chunk rides the packed-lanes round program
    # (clients packed back-to-back in scan lanes — the MXU fast path).
    cohort_chunk: int = 0
    # Cohort execution schedule: 0 (default) trains the whole sampled cohort
    # under one vmap — per-client convs fuse into ONE grouped convolution
    # (feature_group_count = cohort), which XLA's TPU lowering expands
    # ~cohort-fold (docs/mfu_experiments.md H4). k > 0 instead runs the
    # cohort as lax.map over chunks of k vmapped clients (k=1 = fully
    # sequential clients, plain convs). EXACT same per-client math and
    # aggregate either way — this only reorders independent client programs.
    # Simulation paradigm only (measured FLAT there, H4); the cross-silo
    # mesh rounds always vmap the per-device client block and warn if set.
    cohort_vmap_width: int = 0

    # observability
    run_name: str = "fedml_tpu"
    enable_wandb: bool = False
    # fedtrace span tracing (fedml_tpu/obs, DESIGN.md §12): when set, every
    # rank writes <trace_dir>/trace-rank<r>.jsonl — spans for rounds,
    # message send/recv (stitched cross-rank by message id), pipeline
    # stages, wire retransmits — for tools/trace_report.py or a Perfetto
    # export. None (default) disables tracing entirely: the hot paths see
    # one global flag check and allocate nothing, and a traced run is
    # bit-identical to an untraced one (the tracer only reads clocks).
    trace_dir: Optional[str] = None
    # ring-buffer bound per rank: oldest events fall off instead of
    # growing the heap on a weeks-long federation
    trace_buffer_events: int = 65536
    # fedsketch head-based span sampling (obs/tracer.span_sampled): keep
    # only this fraction of the ROUND span trees — the keep/drop verdict
    # is a pure hash of (seed, round), so every rank/host/re-run samples
    # the SAME rounds and the trace stays a consistent subset. Sampled-out
    # rounds still feed counters, pulse snapshots and the sketch lanes —
    # percentiles stay exact while span volume is bounded. 1.0 = keep all.
    trace_sample_rate: float = 1.0
    # fedsketch relative accuracy for the profiler's distribution lanes
    # (train-ms / upload-latency / payload-bytes / staleness): a quantile
    # estimate is within this fraction of the true value. Smaller = more
    # buckets (memory grows ~1/alpha, still structurally capped).
    sketch_alpha: float = 0.01
    # fedcost static roofline attribution (obs/cost, DESIGN.md §13): when
    # on, every round program built through obs/compile.timed_build is
    # ALSO lowered to HLO and read back as a per-op GEMM table (conv/dot
    # M/K/N shapes, FLOPs, MXU lane fills, flop-weighted lane ceiling),
    # stored process-wide (obs.cost_tables()) and — under tracing — emitted
    # as a "program_cost" event for tools/trace_report.py's cost section.
    # Pure static analysis: one extra trace per program build (no compile,
    # no device sync), numerics bit-identical on or off.
    cost_attribution: bool = False
    # fedpulse live telemetry plane (obs/live + obs/profile, DESIGN.md §14):
    # when set, every round boundary appends ONE atomic JSON snapshot
    # (registry time/wire/chaos/compile lanes, host-stage row, per-client
    # profiler aggregates, cost-attribution MFU, health verdict) to this
    # file — tail it live with tools/fedtop.py. None (default) disables the
    # whole plane: the hot path sees one global read and allocates nothing,
    # and a pulse-on run is bit-identical to a pulse-off run (the plane
    # only reads counters and clocks).
    pulse_path: Optional[str] = None
    # optional Prometheus textfile-collector mirror: each snapshot also
    # atomically rewrites <dir>/fedpulse.prom as flat gauges (requires
    # pulse_path)
    pulse_prometheus_dir: Optional[str] = None
    # fedpulse health watchdog (obs/health): rules evaluated at every round
    # boundary while the plane is on. NaN-loss and wire gave_up are always
    # armed; the knobs below arm/tune the rest (0/None = that rule off).
    health_loss_limit: float = 0.0        # loss > limit -> divergent_loss
    health_stall_sec: Optional[float] = None  # round wall > this -> stall
    health_stale_spike: int = 8           # stale_uploads delta/round -> warn
    health_skew: float = 4.0              # p95/p50 EMA train-ms -> warn
    # fedbuff version-lag rule: warn when THIS round's staleness-sketch
    # delta p99 (rounds/versions behind per contribution) reaches this
    # many versions; escalates to critical when the p99 grows strictly
    # monotonically for VERSION_LAG_MONOTONIC_N consecutive snapshots —
    # the buffered-async divergence signature (clients falling ever
    # further behind the emitted version). 0 = rule off (sync runs keep
    # their stale_spike rule; async launchers arm this one).
    health_version_lag: float = 0.0
    # fedlens learning-signal attribution rules (require --lens on to have
    # data): warn when THIS round's update-norm / drift sketch delta p99
    # reaches the threshold, carrying the round's top-k suspect client
    # ids. 0 = rule off. The aligned_suspects critical rule needs no knob:
    # it arms whenever the lens surfaces suspects.
    health_update_norm: float = 0.0
    health_drift: float = 0.0
    # escalate-to-raise: any critical health event raises
    # FederationHealthError AFTER its pulse snapshot is written
    health_escalate: bool = False
    # fedlens in-program learning-signal telemetry (obs/lens, DESIGN.md
    # §22): 'on' arms per-client update-norm / loss-delta / alignment
    # reductions INSIDE the round programs (output-only — aggregation is
    # bit-identical to 'off', pinned by tests/test_lens.py) and feeds the
    # pulse plane's `learning` block, the profiler's update_norm/drift
    # sketch lanes, and the attributed watchdog rules. 'off' (default)
    # builds the exact lens-free programs.
    lens: str = "off"
    # how many ranked suspect client ids each learning block / watchdog
    # event / incident bundle carries
    lens_topk: int = 5
    # fedflight anomaly-triggered flight recorder (obs/flight, DESIGN.md
    # §21): when set, the process retains the last --flight_window rounds
    # of FULL-rate round spans (a second per-rank ring beside the sampled
    # trace stream — the head sampler keeps gating what streams, the
    # recorder keeps everything recent), pulse snapshots with per-round
    # counter-lane deltas, and watchdog transitions — and dumps a
    # self-contained incident-<id>/ bundle into this directory when a
    # trigger fires (watchdog escalation BEFORE the raise, gateway
    # quarantine, reliable-layer peer_dead, manual/SIGUSR2). The bundle
    # manifest names the EXACT replay command from (seed, chaos_seed,
    # non-default flags); incident ids are pure in (seed, round, rule) so
    # every rank converges on one bundle; analyze with tools/fedpost.py.
    # None (default) disarms the recorder: hot paths see one attribute
    # check and allocate nothing, and a recorder-on run is bit-identical
    # to a recorder-off run (the recorder only reads what the round
    # already produced).
    flight_dir: Optional[str] = None
    # rounds of full-rate retrospective capture retained per rank
    # (ring bound = flight_window * obs.flight.EVENTS_PER_ROUND events)
    flight_window: int = 8
    # comma list arming the trigger inventory: escalate (watchdog),
    # quarantine (gateway lane), peer_dead (reliable layer), manual
    # (obs.flight.trigger() / SIGUSR2)
    flight_on: str = "escalate,quarantine,peer_dead,manual"
    # fedscope device-memory sampler: when tracing is on, snapshot
    # jax.local_devices() memory_stats (bytes_in_use + peak watermark) at
    # every round boundary into a "device" counter lane (one allocator read
    # per device per round, host-side, never syncs the device stream; CPU
    # backends fall back to one process-RSS read). Off = spans only.
    trace_device_sampler: bool = True

    # checkpoint/resume (absent in the reference, SURVEY.md §5.4)
    checkpoint_dir: Optional[str] = None
    checkpoint_frequency: int = 10   # rounds between checkpoints when dir set
    resume_from: Optional[str] = None

    # failure injection / elastic rounds (SURVEY.md §5.3: reference has none)
    failure_prob: float = 0.0        # P(sampled client fails a round)
    # Fault-tolerant EDGE rounds (reference: one dead worker hangs the
    # federation until MPI.Abort, client_manager.py:66-69; the mesh path
    # here already has elastic rounds). When set, the edge server
    # aggregates whichever uploads arrived within this many seconds of a
    # round's broadcast, marks missing workers dead (skipping their sends
    # so a dead peer can't stall the loop), re-deals their logical clients
    # to survivors next round, and accepts rejoining workers. None (default)
    # keeps the strict all-workers barrier.
    straggler_deadline_sec: Optional[float] = None

    # jax profiler (SURVEY.md §5.1): device traces for TensorBoard
    profile_dir: Optional[str] = None

    def __post_init__(self):
        if self.client_num_per_round > self.client_num_in_total:
            raise ValueError(
                f"client_num_per_round ({self.client_num_per_round}) > "
                f"client_num_in_total ({self.client_num_in_total})"
            )
        if self.partition_method not in ("homo", "hetero", "hetero-fix", "given"):
            raise ValueError(f"unknown partition_method {self.partition_method!r}")
        if self.dtype not in ("float32", "bfloat16"):
            raise ValueError(f"dtype must be float32|bfloat16, got {self.dtype!r}")
        if self.device_data not in ("auto", "on", "off"):
            raise ValueError(f"device_data must be auto|on|off, got {self.device_data!r}")
        if self.pack_lanes < 0:
            raise ValueError(f"pack_lanes must be >= 0, got {self.pack_lanes}")
        if self.cohort_policy not in ("uniform", "speed", "fair"):
            raise ValueError(
                f"cohort_policy must be uniform|speed|fair, got "
                f"{self.cohort_policy!r}")
        if self.stream_aggregate not in ("off", "deterministic", "arrival"):
            raise ValueError(
                f"stream_aggregate must be off|deterministic|arrival, got "
                f"{self.stream_aggregate!r}")
        if self.wire_retry_base_s <= 0:
            raise ValueError(
                f"wire_retry_base_s must be > 0, got {self.wire_retry_base_s}")
        if self.wire_retry_max < 1:
            raise ValueError(
                f"wire_retry_max must be >= 1, got {self.wire_retry_max}")
        if self.wire_inbox_cap < 0:
            raise ValueError(
                f"wire_inbox_cap must be >= 0 (0 = unbounded), got "
                f"{self.wire_inbox_cap}")
        if self.gateway_max_tenants < 1:
            raise ValueError(
                f"gateway_max_tenants must be >= 1, got "
                f"{self.gateway_max_tenants}")
        if self.gateway_tenant_workers < 0:
            raise ValueError(
                f"gateway_tenant_workers must be >= 0 (0 = unlimited), got "
                f"{self.gateway_tenant_workers}")
        if self.buffer_k < 1:
            raise ValueError(
                f"buffer_k must be >= 1, got {self.buffer_k}: a version "
                "emits every buffer_k folded contributions")
        if self.buffer_staleness_alpha < 0.0:
            raise ValueError(
                f"buffer_staleness_alpha must be >= 0, got "
                f"{self.buffer_staleness_alpha} (0 = no staleness decay)")
        if self.buffer_mode not in ("deterministic", "arrival"):
            raise ValueError(
                f"buffer_mode must be deterministic|arrival, got "
                f"{self.buffer_mode!r}")
        if self.cohort_chunk < 0:
            raise ValueError(
                f"cohort_chunk must be >= 0, got {self.cohort_chunk}")
        if self.cohort_chunk > 0 and self.stream_aggregate == "off":
            raise ValueError(
                "cohort_chunk > 0 needs stream_aggregate: sub-cohort chunks "
                "only exist to be folded into the streaming accumulator — "
                "set --stream_aggregate deterministic (or arrival)")
        if self.host_pipeline_depth < 0:
            raise ValueError(
                f"host_pipeline_depth must be >= 0, got {self.host_pipeline_depth}")
        if self.host_pipeline_workers < 0:
            raise ValueError(
                f"host_pipeline_workers must be >= 0, got {self.host_pipeline_workers}")
        if self.trace_buffer_events < 1:
            raise ValueError(
                f"trace_buffer_events must be >= 1, got {self.trace_buffer_events}")
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ValueError(
                f"trace_sample_rate must be in [0, 1], got "
                f"{self.trace_sample_rate}")
        if not 0.0 < self.sketch_alpha < 0.5:
            raise ValueError(
                f"sketch_alpha must be in (0, 0.5), got {self.sketch_alpha}")
        if self.pulse_prometheus_dir and not self.pulse_path:
            raise ValueError(
                "pulse_prometheus_dir requires pulse_path: the Prometheus "
                "mirror re-renders the pulse snapshots, which only exist "
                "when the pulse stream is on")
        if self.health_loss_limit < 0:
            raise ValueError(
                f"health_loss_limit must be >= 0, got {self.health_loss_limit}")
        if self.health_stall_sec is not None and self.health_stall_sec <= 0:
            raise ValueError(
                f"health_stall_sec must be > 0, got {self.health_stall_sec}")
        if self.health_stale_spike < 0:
            raise ValueError(
                f"health_stale_spike must be >= 0, got {self.health_stale_spike}")
        if self.health_skew < 0:
            raise ValueError(
                f"health_skew must be >= 0, got {self.health_skew}")
        if self.flight_window < 1:
            raise ValueError(
                f"flight_window must be >= 1, got {self.flight_window}")
        _flight_allowed = {"escalate", "quarantine", "peer_dead", "manual"}
        _flight_toks = {t.strip() for t in (self.flight_on or "").split(",")
                        if t.strip()}
        if _flight_toks - _flight_allowed:
            raise ValueError(
                f"flight_on has unknown trigger(s) "
                f"{sorted(_flight_toks - _flight_allowed)}; allowed: "
                f"{sorted(_flight_allowed)}")
        if self.checkpoint_frequency < 1:
            raise ValueError(
                f"checkpoint_frequency must be >= 1, got {self.checkpoint_frequency}"
            )
        if not 0.0 <= self.failure_prob < 1.0:
            raise ValueError(
                f"failure_prob must be in [0, 1), got {self.failure_prob}"
            )
        if self.straggler_deadline_sec is not None and self.straggler_deadline_sec <= 0:
            raise ValueError(
                f"straggler_deadline_sec must be > 0 (got "
                f"{self.straggler_deadline_sec}); a non-positive deadline "
                "would mark every worker dead before it can train"
            )
        if self.rank is not None:
            if self.world_size is None or self.world_size < 2:
                raise ValueError(
                    "--rank requires --world_size >= 2 (1 server + >=1 worker)"
                )
            if not 0 <= self.rank < self.world_size:
                raise ValueError(
                    f"rank {self.rank} out of range for world_size {self.world_size}"
                )
        for f_ in ("chaos_drop", "chaos_dup", "chaos_reorder"):
            v = getattr(self, f_)
            if not 0.0 <= v < 1.0:
                raise ValueError(f"{f_} must be in [0, 1), got {v}")
        if self.chaos_delay_ms < 0:
            raise ValueError(
                f"chaos_delay_ms must be >= 0, got {self.chaos_delay_ms}")
        if (self.chaos_drop or self.chaos_dup or self.chaos_reorder) \
                and not self.wire_reliable:
            raise ValueError(
                "chaos drop/dup/reorder need wire_reliable=True: without the "
                "reliable layer a dropped message hangs the message-counting "
                "barriers and a duplicated upload double-aggregates"
            )
        if (self.chaos_crash_rank is None) != (self.chaos_crash_after is None):
            raise ValueError(
                "chaos_crash_rank and chaos_crash_after must be set together"
            )
        if self.chaos_crash_restart_s is not None:
            if self.chaos_crash_rank is None:
                raise ValueError(
                    "chaos_crash_restart_s needs chaos_crash_rank/"
                    "chaos_crash_after: a restart delay without a crash "
                    "fate has nothing to revive")
            if self.chaos_crash_restart_s <= 0:
                raise ValueError(
                    f"chaos_crash_restart_s must be > 0, got "
                    f"{self.chaos_crash_restart_s}")
        if self.health_version_lag < 0:
            raise ValueError(
                f"health_version_lag must be >= 0, got "
                f"{self.health_version_lag}")
        if self.lens not in ("off", "on"):
            raise ValueError(
                f"lens must be 'off' or 'on', got {self.lens!r}")
        if self.lens_topk < 1:
            raise ValueError(
                f"lens_topk must be >= 1, got {self.lens_topk}")
        if self.health_update_norm < 0:
            raise ValueError(
                f"health_update_norm must be >= 0, got "
                f"{self.health_update_norm}")
        if self.health_drift < 0:
            raise ValueError(
                f"health_drift must be >= 0, got {self.health_drift}")
        from fedml_tpu.core.compression import parse_codec

        parse_codec(self.wire_codec)   # raises on an unknown codec spec
        if self.wire_codec.startswith("topk") and not self.wire_delta:
            raise ValueError(
                "wire_codec='topk:..' sparsifies uploads destructively unless "
                "they are error-feedback deltas; set wire_delta=True (q8 and "
                "raw work with either mode)"
            )
        if self.ci:
            # CI fast path: shrink everything (reference fedavg_api.py:157-162).
            self.comm_round = min(self.comm_round, 2)
            self.epochs = min(self.epochs, 1)

    def replace(self, **kw) -> "FedConfig":
        return dataclasses.replace(self, **kw)

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "FedConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    @classmethod
    def from_yaml(cls, path: str) -> "FedConfig":
        if yaml is None:
            raise RuntimeError("pyyaml not available")
        with open(path) as f:
            return cls.from_dict(yaml.safe_load(f) or {})

    def to_yaml(self, path: str) -> None:
        if yaml is None:
            raise RuntimeError("pyyaml not available")
        with open(path, "w") as f:
            yaml.safe_dump(self.to_dict(), f)


def add_args(parser: Optional[argparse.ArgumentParser] = None) -> argparse.ArgumentParser:
    """Argparse bridge exposing the reference's flag names
    (main_fedavg.py:48-120) so launch scripts translate 1:1."""
    p = parser or argparse.ArgumentParser(description="fedml_tpu experiment")
    defaults = FedConfig()
    p.add_argument("--model", type=str, default=defaults.model)
    p.add_argument("--dataset", type=str, default=defaults.dataset)
    p.add_argument("--data_dir", type=str, default=defaults.data_dir)
    p.add_argument("--partition_method", type=str, default=defaults.partition_method)
    p.add_argument("--partition_alpha", type=float, default=defaults.partition_alpha)
    p.add_argument("--client_num_in_total", type=int, default=defaults.client_num_in_total)
    p.add_argument("--client_num_per_round", type=int, default=defaults.client_num_per_round)
    p.add_argument("--comm_round", type=int, default=defaults.comm_round)
    p.add_argument("--group_num", type=int, default=defaults.group_num)
    p.add_argument("--group_comm_round", type=int, default=defaults.group_comm_round)
    p.add_argument("--unrolled", type=int, default=defaults.unrolled)
    p.add_argument("--batch_size", type=int, default=defaults.batch_size)
    p.add_argument("--client_optimizer", type=str, default=defaults.client_optimizer)
    p.add_argument("--lr", type=float, default=defaults.lr)
    p.add_argument("--wd", type=float, default=defaults.wd)
    p.add_argument("--momentum", type=float, default=defaults.momentum)
    p.add_argument("--epochs", type=int, default=defaults.epochs)
    p.add_argument("--server_optimizer", type=str, default=defaults.server_optimizer)
    p.add_argument("--server_lr", type=float, default=defaults.server_lr)
    p.add_argument("--server_momentum", type=float, default=defaults.server_momentum)
    p.add_argument("--fedprox_mu", type=float, default=defaults.fedprox_mu)
    p.add_argument("--norm_bound", type=float, default=None)
    p.add_argument("--stddev", type=float, default=None)
    p.add_argument("--temperature", type=float, default=defaults.temperature)
    p.add_argument("--alpha_distill", type=float, default=defaults.alpha_distill)
    p.add_argument("--model_client", type=str, default=defaults.model_client)
    p.add_argument("--model_server", type=str, default=defaults.model_server)
    p.add_argument("--epochs_server", type=int, default=defaults.epochs_server)
    p.add_argument("--backend", type=str, default=defaults.backend)
    p.add_argument("--rank", type=int, default=None,
                   help="start ONLY this rank as its own OS process (0=server)")
    p.add_argument("--world_size", type=int, default=None,
                   help="total ranks (1 server + N workers) for --rank mode")
    p.add_argument("--grpc_ipconfig_path", type=str, default=None,
                   help="rank->IP csv (reference grpc_ipconfig.csv); default loopback")
    p.add_argument("--grpc_base_port", type=int, default=defaults.grpc_base_port)
    p.add_argument("--frequency_of_the_test", type=int, default=defaults.frequency_of_the_test)
    # reference-parity flag: its JSON wire format lives in
    # core/serialization.tree_to_jsonable and is superseded by --wire_codec;
    # kept so reference launch scripts parse unchanged.
    p.add_argument("--is_mobile", type=int, default=defaults.is_mobile)  # fedlint: disable=config-flag-drift
    p.add_argument("--seed", type=int, default=defaults.seed)
    p.add_argument("--ci", type=int, default=defaults.ci)
    p.add_argument("--dtype", type=str, default=defaults.dtype)
    p.add_argument("--device_data", type=str, default=defaults.device_data,
                   choices=("auto", "on", "off"))
    p.add_argument("--device_data_max_bytes", type=int,
                   default=defaults.device_data_max_bytes)
    p.add_argument("--bucket_quantum_batches", type=int,
                   default=defaults.bucket_quantum_batches)
    p.add_argument("--pack_lanes", type=int, default=defaults.pack_lanes,
                   help="pack the cohort into N scan lanes (0 = off)")
    p.add_argument("--host_pipeline_depth", type=int,
                   default=defaults.host_pipeline_depth,
                   help="prefetch this many future rounds' cohorts on "
                        "background threads (host round paths; 0 = serial)")
    p.add_argument("--host_pipeline_workers", type=int,
                   default=defaults.host_pipeline_workers,
                   help="threads fanning one cohort's materialization out "
                        "over its clients (0 = auto)")
    p.add_argument("--cohort_policy", type=str,
                   default=defaults.cohort_policy,
                   choices=("uniform", "speed", "fair"),
                   help="fedsched cohort selection: uniform draw (default, "
                        "bit-identical), speed packing from the profiler's "
                        "EMA train-ms, or fairness-bounded speed packing")
    p.add_argument("--stream_aggregate", type=str,
                   default=defaults.stream_aggregate,
                   choices=("off", "deterministic", "arrival"),
                   help="streaming server-side aggregation: fold client "
                        "updates into a running weighted accumulator (O(1) "
                        "memory in cohort size) in fixed plan order "
                        "(deterministic) or strictly on arrival")
    p.add_argument("--buffer_k", type=int, default=defaults.buffer_k,
                   help="fedbuff: emit a model version every K folded "
                        "contributions (async buffered aggregation)")
    p.add_argument("--buffer_staleness_alpha", type=float,
                   default=defaults.buffer_staleness_alpha,
                   help="fedbuff staleness decay: fold weight = "
                        "n * (1 + staleness)^-alpha (0 = no decay)")
    p.add_argument("--buffer_mode", type=str, default=defaults.buffer_mode,
                   choices=("deterministic", "arrival"),
                   help="fedbuff fold order: canonical (tag, worker) "
                        "frontier — bit-identical replayable from (seed, "
                        "chaos_seed) — or strictly on arrival (fast path)")
    p.add_argument("--cohort_chunk", type=int, default=defaults.cohort_chunk,
                   help="stream the host round in sub-cohorts of this many "
                        "clients through the accumulator (0 = whole cohort; "
                        "requires --stream_aggregate)")
    p.add_argument("--scan_unroll", type=int, default=defaults.scan_unroll)
    p.add_argument("--cohort_vmap_width", type=int,
                   default=defaults.cohort_vmap_width)
    p.add_argument("--wire_codec", type=str, default=defaults.wire_codec,
                   help="edge payload compression: raw | q8 | topk:<ratio>")
    p.add_argument("--wire_delta", type=lambda s: bool(int(s)),
                   default=defaults.wire_delta,
                   help="edge FedAvg uploads error-feedback deltas (0|1)")
    p.add_argument("--wire_reliable", type=lambda s: bool(int(s)),
                   default=defaults.wire_reliable,
                   help="ACK/retransmit + dedup wire layer (0|1)")
    p.add_argument("--wire_retry_base_s", type=float,
                   default=defaults.wire_retry_base_s,
                   help="reliable-layer backoff base (cap = 20x base)")
    p.add_argument("--wire_retry_max", type=int,
                   default=defaults.wire_retry_max,
                   help="retransmits before a message gives up (the "
                        "dead-peer detection budget)")
    p.add_argument("--wire_inbox_cap", type=int,
                   default=defaults.wire_inbox_cap,
                   help="bounded inbox / gateway lane depth (0 = unbounded; "
                        "gateway lanes answer WIRE_BUSY over the cap)")
    p.add_argument("--gateway_max_tenants", type=int,
                   default=defaults.gateway_max_tenants,
                   help="concurrent federations one gateway admits (excess "
                        "gets a typed NACK)")
    p.add_argument("--gateway_tenant_workers", type=int,
                   default=defaults.gateway_tenant_workers,
                   help="per-tenant worker quota at the gateway (0 = "
                        "unlimited)")
    p.add_argument("--chaos_seed", type=int, default=defaults.chaos_seed)
    p.add_argument("--chaos_drop", type=float, default=defaults.chaos_drop,
                   help="P(drop) per transmission (needs --wire_reliable 1)")
    p.add_argument("--chaos_dup", type=float, default=defaults.chaos_dup,
                   help="P(duplicate) per transmission")
    p.add_argument("--chaos_delay_ms", type=float,
                   default=defaults.chaos_delay_ms,
                   help="max per-message injected latency in ms")
    p.add_argument("--chaos_reorder", type=float,
                   default=defaults.chaos_reorder,
                   help="P(hold a message until the next send overtakes it)")
    p.add_argument("--chaos_crash_rank", type=int, default=None,
                   help="crash-stop this rank after --chaos_crash_after sends")
    p.add_argument("--chaos_crash_after", type=int, default=None)
    p.add_argument("--chaos_crash_restart_s", type=float, default=None,
                   help="crash_restart fate: revive the crash-stopped rank "
                        "after this many seconds (None = crash is final)")
    p.add_argument("--trace_dir", type=str, default=None,
                   help="write per-rank span traces (fedml_tpu/obs) here; "
                        "analyze with tools/trace_report.py")
    p.add_argument("--trace_buffer_events", type=int,
                   default=defaults.trace_buffer_events,
                   help="per-rank trace ring-buffer bound (events)")
    p.add_argument("--trace_sample_rate", type=float,
                   default=defaults.trace_sample_rate,
                   help="keep this fraction of round span trees — "
                        "deterministic head sampling keyed on (seed, "
                        "round); sampled-out rounds still feed sketches "
                        "(1.0 = trace every round)")
    p.add_argument("--sketch_alpha", type=float,
                   default=defaults.sketch_alpha,
                   help="fedsketch relative accuracy for the percentile "
                        "lanes (smaller = more buckets)")
    p.add_argument("--pulse_path", type=str, default=None,
                   help="fedpulse live telemetry: append one atomic JSON "
                        "snapshot per round boundary to this file; tail it "
                        "with tools/fedtop.py (None = plane off)")
    p.add_argument("--pulse_prometheus_dir", type=str, default=None,
                   help="also mirror each pulse snapshot as Prometheus "
                        "textfile gauges (<dir>/fedpulse.prom)")
    p.add_argument("--health_loss_limit", type=float,
                   default=defaults.health_loss_limit,
                   help="watchdog: loss above this is divergent_loss "
                        "(0 = rule off; NaN loss is always critical)")
    p.add_argument("--health_stall_sec", type=float, default=None,
                   help="watchdog: a round wall beyond this many seconds "
                        "is a round_stall (None = rule off)")
    p.add_argument("--health_stale_spike", type=int,
                   default=defaults.health_stale_spike,
                   help="watchdog: stale_uploads growth per round that "
                        "counts as a spike (0 = rule off)")
    p.add_argument("--health_skew", type=float, default=defaults.health_skew,
                   help="watchdog: p95/p50 EMA train-ms ratio flagged as "
                        "straggler skew (0 = rule off)")
    p.add_argument("--health_version_lag", type=float,
                   default=defaults.health_version_lag,
                   help="watchdog: per-round staleness-sketch delta p99 "
                        "(versions behind) that warns; monotonic growth "
                        "escalates to critical (0 = rule off)")
    p.add_argument("--health_update_norm", type=float,
                   default=defaults.health_update_norm,
                   help="watchdog (fedlens): per-round update-norm sketch "
                        "delta p99 that warns with suspect client ids "
                        "(0 = rule off; needs --lens on)")
    p.add_argument("--health_drift", type=float,
                   default=defaults.health_drift,
                   help="watchdog (fedlens): per-round drift sketch delta "
                        "p99 (1 - cosine vs aggregate) that warns with "
                        "suspect client ids (0 = rule off; needs --lens on)")
    p.add_argument("--health_escalate", type=lambda s: bool(int(s)),
                   default=defaults.health_escalate,
                   help="raise FederationHealthError on critical health "
                        "events (0|1; snapshot is written first)")
    p.add_argument("--lens", type=str, choices=("off", "on"),
                   default=defaults.lens,
                   help="fedlens in-program learning-signal telemetry: "
                        "per-client update norm / loss delta / alignment "
                        "computed inside the round programs (output-only; "
                        "aggregation bit-identical to off)")
    p.add_argument("--lens_topk", type=int, default=defaults.lens_topk,
                   help="ranked suspect client ids carried by each "
                        "learning block / attributed watchdog event")
    p.add_argument("--flight_dir", type=str, default=None,
                   help="fedflight black-box recorder: retain the last "
                        "--flight_window rounds at FULL rate and dump a "
                        "self-contained incident-<id>/ bundle here on "
                        "trigger (watchdog escalation before the raise, "
                        "gateway quarantine, peer_dead, SIGUSR2); analyze "
                        "with tools/fedpost.py (None = recorder off)")
    p.add_argument("--flight_window", type=int,
                   default=defaults.flight_window,
                   help="rounds of full-rate retrospective capture the "
                        "flight recorder retains per rank")
    p.add_argument("--flight_on", type=str, default=defaults.flight_on,
                   help="comma list arming flight triggers: escalate, "
                        "quarantine, peer_dead, manual")
    p.add_argument("--trace_device_sampler", type=lambda s: bool(int(s)),
                   default=defaults.trace_device_sampler,
                   help="sample per-device memory at round boundaries into "
                        "the trace's device lane (0|1; traced runs only)")
    p.add_argument("--cost_attribution", type=lambda s: bool(int(s)),
                   default=defaults.cost_attribution,
                   help="fedcost static roofline attribution of every built "
                        "round program (0|1): per-op GEMM/lane-fill table "
                        "via obs/cost; report with tools/trace_report.py or "
                        "tools/roofline_report.py")
    p.add_argument("--run_name", type=str, default=defaults.run_name)
    p.add_argument("--checkpoint_dir", type=str, default=None)
    p.add_argument("--checkpoint_frequency", type=int, default=defaults.checkpoint_frequency)
    p.add_argument("--resume_from", type=str, default=None)
    p.add_argument("--failure_prob", type=float, default=defaults.failure_prob)
    p.add_argument("--straggler_deadline_sec", type=float, default=None,
                   help="edge rounds: aggregate the received subset after "
                        "this many seconds instead of waiting forever")
    p.add_argument("--profile_dir", type=str, default=None)
    p.add_argument("--config_yaml", type=str, default=None, help="optional YAML overriding flags")
    return p


def config_from_args(args: argparse.Namespace) -> FedConfig:
    d = vars(args).copy()
    yaml_path = d.pop("config_yaml", None)
    cfg = FedConfig.from_dict(d)
    if yaml_path:
        if yaml is None:
            raise RuntimeError("pyyaml not available but --config_yaml was passed")
        base = cfg.to_dict()
        with open(yaml_path) as f:
            base.update(yaml.safe_load(f) or {})
        cfg = FedConfig.from_dict(base)
    return cfg
