"""FedAvg — the canonical algorithm, standalone-simulation paradigm.

Counterpart of reference fedml_api/standalone/fedavg/fedavg_api.py:12-115:
the round loop samples clients, trains each on the global weights, and
sample-weight-averages the results. Differences by design:

- the reference trains sampled clients SEQUENTIALLY with a deepcopy of the
  global state dict per client (fedavg_api.py:55-66); here the whole cohort
  trains in parallel under one ``vmap`` inside one jit — a single XLA
  program per round,
- aggregation is `tree_weighted_mean` on device (no host round-trip),
- client sampling is host-side (np, round-deterministic like the reference's
  np.random.seed(round_idx) at fedavg_api.py:83-91) and enters the program
  as a gather of the stacked client arrays.
"""

from __future__ import annotations

import logging
import time
import warnings
from collections import deque
from functools import partial, wraps
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.pytree import map_chunks, tree_weighted_mean
from fedml_tpu.core.rng import round_key, sample_clients, seed_everything, server_key
from fedml_tpu.core.tasks import get_task
from fedml_tpu.data import FedDataset
from fedml_tpu.models import COUNTERS, ModelBundle, create_model
from fedml_tpu.obs.tracer import (SCOPE_AGGREGATE, SCOPE_PROLOGUE,
                                  SCOPE_SERVER, SPAN_ENQUEUE, SPAN_H2D,
                                  SPAN_MATERIALIZE, SPAN_PLAN, SPAN_ROUND,
                                  SPAN_SETUP_API, SPAN_SETUP_LOCAL_TRAIN,
                                  SPAN_SETUP_PLACE, SPAN_WAIT_INPUTS,
                                  setup_span, span)
from fedml_tpu.parallel.local import (
    LocalResult,
    finalize_metrics,
    make_eval_fn,
    make_local_train_fn,
)

log = logging.getLogger(__name__)


def _donation_quiet(jitted):
    """Wrap a donate-argnums jitted step and silence its one warning.

    JAX hands a donated buffer only to an output of the same shape and
    dtype (or, failing that, the same size). The streaming accumulator has
    one and is really consumed; the cohort blocks (cx, cy, cm) have none,
    so their donation is dropped — with one "not usable" warning per
    compiled shape — and they stay valid until their last Python reference
    goes. That is the same on every backend: the CPU implements donation
    under jax 0.9.0, and the v5e behaved identically (PR 21 chip probe:
    the matching argument ``is_deleted()`` after the call, the unmatched
    block was not). So no donated step can read a freed cohort block."""
    def step(*args):
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return jitted(*args)

    return step


#: The programs that can run a round: the closed set ``RoundPlan.path``
#: takes. An API object chooses its path once, at construction
#: (``_choose_path``); ``_run_round_inner`` dispatches on it through the
#: class's ``_ROUND_RUNNERS`` and ``round_counts`` reports the same record.
PATH_PACKED = "packed"                # resident stack, packed lanes
PATH_GATHER = "gather"                # resident stack, vmap over the cohort
PATH_HOST = "host"                    # cohort shipped from the host, serial
PATH_HOST_PIPELINE = "host_pipeline"  # ... built ahead by the prefetcher
PATH_STREAM = "stream"                # host chunks folded as they finish
PATH_STREAM_PACKED = "stream_packed"  # ... each chunk as packed lanes
PATH_MESH_PACKED = "mesh_packed"      # sharded over the mesh, packed lanes
PATH_MESH_SHARDED = "mesh_sharded"    # sharded over the mesh, vmap a device
_PACKED_PATHS = (PATH_PACKED, PATH_STREAM_PACKED, PATH_MESH_PACKED)


def _setup_api_span(init):
    """A round driver's ``__init__`` under a ``fedml/setup/api`` set-up span
    (``api=<class>``). A subclass constructor that wraps itself nests the
    base's span inside its own: readers take the outermost."""

    @wraps(init)
    def wrapped(self, *args, **kw):
        with setup_span(SPAN_SETUP_API, api=type(self).__name__):
            init(self, *args, **kw)

    return wrapped


def _placed(span_, arrays):
    """``arrays`` (a placement's result, or None) with its bytes put on the
    ``fedml/setup/place_data`` span that holds the call."""
    span_.set("bytes", sum(int(a.nbytes) for a in jax.tree.leaves(arrays)))
    return arrays


class RoundPlan(NamedTuple):
    """What one round trains on and which program runs it: ``run_round``
    executes exactly this record and ``round_counts`` reports it."""

    path: str
    sampled: np.ndarray             # cohort, in the order the program sees it
    live: Optional[np.ndarray]      # {0,1} per cohort slot; None = all live
    bucket: Optional[int]           # scan length; None = the full record axis
    #: the packed paths' lane plan (parallel/packed.plan_packing; one a
    #: chunk, in a tuple, on ``stream_packed``); None on the vmap paths
    lanes: object
    padded_slots: int               # record slots one epoch executes


class FedAvgAPI:
    """Standalone FedAvg simulator (vmap-over-clients on one chip/mesh)."""

    #: subclasses that shard round inputs themselves (cross-silo) opt out
    supports_device_data: bool = True

    @_setup_api_span
    def __init__(self, dataset: FedDataset, config: FedConfig, bundle: Optional[ModelBundle] = None):
        self.dataset = dataset
        self.config = config
        self.bundle = bundle or create_model(
            config.model, dataset.class_num,
            input_shape=dataset.train_x.shape[2:] or None,
        )
        self.task = get_task(dataset.task, dataset.class_num)
        #: Silo per-client exit mask (set_client_active); None = all active
        self._client_active = None
        self._client_active_version = 0
        self.root_key = seed_everything(config.seed)
        self.variables = self.bundle.init(self.root_key)
        with setup_span(SPAN_SETUP_LOCAL_TRAIN):
            self._local_train = self.build_local_train()
            self._eval = make_eval_fn(self.bundle, self.task)
            self.server_state = self.init_server_state()
        # the default (host-cohort) round program rides the same fedscope
        # compile telemetry + fedcost attribution hook as the packed and
        # gather programs — a vanilla run is not a blind spot.
        # Subclass paradigms build a DIFFERENT program from the same
        # __init__, so their records are name-qualified: one process running
        # several API types (bench.py) keeps one attribution per program
        # instead of latest-wins overwrites under a shared "round_step".
        from fedml_tpu.obs import timed_build

        self._round_step = timed_build(
            self._program_name("round_step"), ("default",),
            self.build_round_step)
        with setup_span(SPAN_SETUP_PLACE) as placing:
            self._dev_train = _placed(placing, self._maybe_place_train_data())
        self._gather_steps: dict[Optional[int], Callable] = {}
        self._packed_steps: dict[tuple, Callable] = {}
        # recently computed round plans (round_idx -> RoundPlan) —
        # stashed by _run_round_inner AND the prefetcher's background
        # builds so the fedpulse wrapper can reuse the plan the round
        # ALREADY computed instead of re-paying the O(client_num_in_total)
        # sampling draw per round (the same cost _host_round_inputs'
        # plan= parameter exists to avoid). Dict (not a single slot)
        # because pipelined builds of several FUTURE rounds race the
        # consuming round; bounded, entries popped on use.
        self._plan_stash: dict = {}
        # host round pipeline (data/pipeline.CohortPrefetcher): lazy — built
        # by the first host-path round when config.host_pipeline_depth > 0
        self._prefetcher = None
        self._donated_step = None
        # fedsched cohort scheduler: the ONE owner of per-round sampling —
        # uniform policy is bit-identical to the old sample_clients call by
        # construction; profiler policies read boundary snapshots fed by
        # run_round's notify (data/sched.py)
        from fedml_tpu.data.sched import CohortScheduler

        self._cohort_sched = CohortScheduler(
            config.cohort_policy, config.seed,
            dataset.num_clients
            if config.client_num_in_total > dataset.num_clients
            else config.client_num_in_total,
            min(config.client_num_per_round, dataset.num_clients))
        # streaming chunked host rounds (fedsched): compiled chunk programs,
        # the chunk-indexed prefetcher, and the last round's stream stats
        # (the O(1)-accumulator evidence tests and the bench read)
        self._stream_steps: dict = {}
        self._stream_pf = None
        self._stream_finish_fn = None
        self._stream_mode_memo: Optional[str] = None
        self.stream_stats: Optional[dict] = None
        #: per-round stage timings for utils/metrics.round_stats (host path)
        self._stage_rows: deque = deque(maxlen=1024)
        if self._dev_train is not None and config.stream_aggregate != "off":
            # same explicit-ignore discipline as device_data/host-pipeline:
            # the device-resident round aggregates inside its own program
            # (no host buffering to stream away), so the flags are inert
            log.warning(
                "stream_aggregate=%r (and cohort_chunk) ignored: the "
                "dataset is device-resident, so the whole-cohort round "
                "program already aggregates in-program; streaming applies "
                "to the host round path", config.stream_aggregate)
        self.history: dict[str, list] = {"round": [], "Test/Acc": [], "Test/Loss": []}
        self._path = self._choose_path()

    def _choose_path(self) -> str:
        """Which program runs this API's rounds: the one decision, taken
        once, here. Whether the algorithm packs (``_packing_hooks``) and
        whether it streams (``_stream_mode``) are properties of the API
        object, asked here and not per round, each with its one warning.
        The one per-round case, a cohort with no record to pack, is
        ``_round_plan``'s."""
        c = self.config
        if self._dev_train is not None:
            packs = c.pack_lanes > 0 and self._packing_hooks() is not None
            return PATH_PACKED if packs else PATH_GATHER
        if self._stream_mode() != "off":
            return PATH_STREAM_PACKED if c.pack_lanes > 0 else PATH_STREAM
        return PATH_HOST_PIPELINE if c.host_pipeline_depth > 0 else PATH_HOST

    def _maybe_place_train_data(self):
        """Ship the full stacked client dataset to HBM once so rounds gather
        the cohort on device instead of re-shipping it from host every round
        (the reference's DataLoader contract re-materializes client data per
        round, fedavg_api.py:56-66 — on TPU that host->device hop dominates).
        Returns (train_x, train_y, train_mask, train_counts) on device or
        None when disabled/too large."""
        c = self.config
        if not self.supports_device_data or c.device_data == "off":
            if (c.device_data == "on" and not self.supports_device_data
                    and not getattr(self, "handles_own_device_data", False)):
                log.warning(
                    "device_data='on' ignored: %s shards round inputs itself; "
                    "using the host-slice path", type(self).__name__,
                )
            return None
        if type(self).build_round_step is not FedAvgAPI.build_round_step:
            # subclass rewired the round program (hierarchical/turboaggregate/
            # ...); the gather wrapper only mirrors the base body
            if c.device_data == "on":
                log.warning(
                    "device_data='on' ignored: %s overrides build_round_step, "
                    "which the gather path cannot mirror; using the host-slice "
                    "path", type(self).__name__,
                )
            return None
        x = self._eligible_device_train_x()
        if x is None:
            return None
        ds = self.dataset
        return (
            jax.device_put(x),
            jax.device_put(ds.train_y),
            jax.device_put(ds.train_mask),
            jax.device_put(jnp.asarray(ds.train_counts, jnp.float32)),
        )

    def _eligible_device_train_x(self, shard_factor: int = 1):
        """Shared device-residency eligibility + bf16 pre-cast for train_x.

        ``shard_factor`` = number of devices the stacked arrays will be
        sharded across (1 = fully replicated/single-device): the 'auto'
        byte budget applies to the PER-DEVICE footprint. Auto also declines
        CPU backends — there is no host->device hop to avoid, and a second
        in-RAM copy of the dataset would be pure cost ('on' still forces
        it, e.g. for tests). Returns train_x (bf16-cast when training in
        bf16) or None when ineligible."""
        c = self.config
        ds = self.dataset
        if getattr(ds, "virtual", False):
            # cross-device scale: the client stack does not exist; rounds
            # materialize O(cohort) slices host-side (data/crossdevice.py)
            if c.device_data == "on":
                log.warning(
                    "device_data='on' ignored: %s is a virtual cross-device "
                    "dataset (%d clients); using the sampled host-slice path",
                    ds.name, ds.num_clients)
            return None
        x = ds.train_x
        cast_bf16 = c.dtype == "bfloat16" and np.issubdtype(x.dtype, np.floating)
        nbytes = ((x.size * 2 if cast_bf16 else x.nbytes) + ds.train_y.nbytes
                  + ds.train_mask.nbytes + ds.train_counts.nbytes)
        if c.device_data == "auto" and (
            jax.default_backend() == "cpu"
            or nbytes / max(shard_factor, 1) > c.device_data_max_bytes
        ):
            return None
        if cast_bf16:
            from fedml_tpu.utils.dtypes import host_bf16_cast

            return host_bf16_cast(x, c.dtype)
        return x

    # -- factory methods subclasses override ---------------------------------

    def _local_train_kwargs(self) -> dict:
        """The ONE config->trainer kwargs mapping (parallel/local.py
        local_train_kwargs), shared by every build_local_train — subclasses
        add to it rather than re-listing it, so a new config knob cannot be
        silently dropped by one algorithm."""
        from fedml_tpu.parallel.local import local_train_kwargs

        return local_train_kwargs(self.config)

    def build_local_train(self):
        return make_local_train_fn(self.bundle, self.task,
                                   **self._local_train_kwargs())

    def init_server_state(self):
        """State threaded through aggregate() across rounds (FedOpt's server
        optimizer moments, FedNova's momentum buffer, ...). {} = stateless."""
        return {}

    def crosssilo_hooks(self) -> Optional[dict]:
        """Mesh-path translation of this algorithm's ``aggregate``: a dict of
        make_crosssilo_round hooks (client_transform / reduce_extras /
        server_update) or None for the plain weighted psum. Algorithms whose
        aggregation is more than a weighted mean implement this so their
        CrossSilo* variant runs in-mesh (the counterpart of the reference's
        one-Aggregator-subclass-per-algorithm MPI deployments, e.g.
        FedOptAggregator.py:70-120). Only consulted by the cross-silo
        paradigm's build_round_step."""
        return None

    def aggregate(self, variables, stacked_vars, counts, infos: LocalResult, rng, server_state):
        """Weighted average (fedavg_api.py:100-115). Subclasses change this.
        Returns (new_variables, new_server_state); must be jit-pure."""
        return tree_weighted_mean(stacked_vars, counts), server_state

    def _cohort_train(self, variables, cx, cy, cm, counts, keys) -> LocalResult:
        """Train a stacked cohort: one vmap (default), or — with
        config.cohort_vmap_width = k > 0 — lax.map over chunks of k vmapped
        clients. The chunked schedule computes the exact same per-client
        results in the same stacking order; it exists because the full vmap
        fuses all clients' convs into one grouped convolution whose TPU
        lowering pads cohort-fold (docs/mfu_experiments.md H4)."""
        vt = jax.vmap(self._local_train, in_axes=(None, 0, 0, 0, 0, 0))
        n = cx.shape[0]
        w = self.config.cohort_vmap_width
        if w <= 0 or w >= n or n % w:
            if 0 < w < n and n % w and not getattr(self, "_warned_cohort_width", False):
                log.warning(
                    "cohort_vmap_width=%d does not divide a cohort of %d "
                    "clients; falling back to the full vmap schedule", w, n)
                # warn-once bookkeeping on a shape-static branch: executes at
                # trace time only and never feeds a traced value
                self._warned_cohort_width = True  # fedlint: disable=traced-purity
            return vt(variables, cx, cy, cm, counts, keys)

        return map_chunks(lambda *chunk: vt(variables, *chunk),
                          (cx, cy, cm, counts, keys), w)

    def _round_body(self, variables, server_state, cx, cy, cm, counts, rng):
        with jax.named_scope(SCOPE_PROLOGUE):
            keys = jax.random.split(rng, cx.shape[0])
        res = self._cohort_train(variables, cx, cy, cm, counts, keys)
        return self._finish_round(variables, server_state, res, counts, rng)

    def _finish_round(self, variables, server_state, res, counts, rng):
        """Aggregate the cohort's local results + elastic-round guard +
        weighted train loss."""
        # aggregate() is the algorithm's own (a weighted mean, or a server
        # optimizer on top of one): all of it is the aggregation layer here
        with jax.named_scope(SCOPE_AGGREGATE):
            new_vars, new_state = self.aggregate(
                variables, res.variables, counts, res, server_key(rng), server_state
            )
        # elastic rounds: failed clients enter with count 0 and drop out of
        # the weighted mean; an all-failed round is a full no-op — weights
        # AND server state (FedOpt moments etc.) roll back, else the server
        # optimizer would absorb the garbage zero-aggregate pseudo-gradient
        with jax.named_scope(SCOPE_SERVER):
            total = jnp.sum(counts)
            keep = total > 0
            new_vars = jax.tree.map(lambda n, o: jnp.where(keep, n, o), new_vars, variables)
            new_state = jax.tree.map(lambda n, o: jnp.where(keep, n, o), new_state, server_state)
        with jax.named_scope(SCOPE_AGGREGATE):
            train_loss = jnp.sum(res.train_loss * counts) / jnp.maximum(total, 1e-12)
        if self._lens_armed:
            # fedlens lane (obs/lens.py): output-only reductions over the
            # stacked cohort result the program already holds — nothing
            # here feeds new_vars/new_state, so an armed program computes
            # bit-identical weights (pinned by tests/test_lens.py)
            from fedml_tpu.obs.lens import stacked_lens

            return (new_vars, new_state, train_loss,
                    stacked_lens(variables, res, counts))
        return new_vars, new_state, train_loss

    def build_round_step(self):
        body = self._round_body

        @jax.jit
        def round_step(variables, server_state, cx, cy, cm, counts, rng):
            return body(variables, server_state, cx, cy, cm, counts, rng)

        return round_step

    def build_round_step_gather(self, bucket: Optional[int] = None):
        """Round step over device-resident data: the sampled cohort enters as
        an index vector; the gather happens in HBM inside the same program.
        ``live`` [cohort] zeroes failed clients' weights (elastic rounds).
        ``bucket`` (static) truncates the per-client record axis to the
        cohort's real maximum — loaders put real records first, so the tail
        holds no real data and the trimmed steps were masked no-ops (the
        epoch shuffle stream does change with the axis length; see
        FedConfig.bucket_quantum_batches)."""
        body = self._round_body

        @jax.jit
        def round_step(variables, server_state, tx, ty, tm, tcounts, idx, live, rng):
            with jax.named_scope(SCOPE_PROLOGUE):
                cx = jnp.take(tx, idx, axis=0)
                cy = jnp.take(ty, idx, axis=0)
                cm = jnp.take(tm, idx, axis=0)
                if bucket is not None:
                    cx, cy, cm = cx[:, :bucket], cy[:, :bucket], cm[:, :bucket]
                counts = jnp.take(tcounts, idx, axis=0) * live
            return body(variables, server_state, cx, cy, cm, counts, rng)

        return round_step

    def _round_bucket(self, sampled: np.ndarray, live: Optional[np.ndarray]) -> Optional[int]:
        """Static scan length for this round: max real count over the live
        cohort, rounded up to bucket_quantum_batches*batch_size. None = use
        the global n_pad (bucketing off, or nothing to trim)."""
        c = self.config
        n_pad = int(self.dataset.train_x.shape[1])
        q = c.bucket_quantum_batches * c.batch_size
        if c.bucket_quantum_batches <= 0 or q >= n_pad:
            return None
        counts = np.asarray(self.dataset.train_counts, np.float64)[sampled]
        if live is not None:
            counts = counts * live
        maxc = float(counts.max()) if counts.size else 0.0
        bucket = int(np.ceil(max(maxc, 1.0) / q) * q)
        return None if bucket >= n_pad else bucket

    def _program_name(self, base: str) -> str:
        """Telemetry/attribution name for a round program built in the
        shared ``__init__``: subclasses build a DIFFERENT program from the
        same code path, so qualify by class. Base-class instances keep the
        bare name (existing counter keys and goldens unchanged)."""
        if type(self) is FedAvgAPI:
            return base
        return f"{base}.{type(self).__name__}"

    def _lru_step(self, cache: dict, key, builder, name: str, cap: int = 64):
        """Shared LRU for compiled round programs: bound the cache — with
        failure injection the per-round plan varies and the key space is
        large — and make every eviction VISIBLE
        (history counter + log), since each one implies a fresh XLA compile
        (seconds to minutes for a flagship program) next time the key recurs;
        a pathological config shows up here instead of as mystery slowness.
        Dict order is recency: hits re-insert, eviction pops the oldest.

        Builds route through fedscope compile telemetry (obs/compile): the
        "compile" registry group counts hits/misses, and each build is two
        fedml/round/build set-up spans keyed by the program's shape key."""
        from fedml_tpu.obs import record_cache_hit, timed_build

        # class-qualified like the __init__-built programs: a subclass's
        # packed/gather program is a different program and must not
        # overwrite the base class's attribution record or merge counters
        name = self._program_name(name)
        step = cache.get(key)
        if step is None:
            if len(cache) >= cap:
                cache.pop(next(iter(cache)))
                n_evict = self.history.get(f"{name}_evictions", 0) + 1
                self.history[f"{name}_evictions"] = n_evict
                log.info("%s cache full: evicted 1 of %d compiled round "
                         "programs (total evictions %d)", name, cap, n_evict)
            step = cache[key] = timed_build(name, key, builder)
        else:
            cache[key] = cache.pop(key)
            record_cache_hit(name)
        return step

    # -- packed schedule (parallel/packed.py) --------------------------------

    def _packing_hooks(self) -> Optional[dict]:
        """The packed schedule's algorithm contract (packed-everywhere):
        the weighted mean folds INTO the lane scan, and everything beyond
        it rides the SAME three-hook contract the mesh paradigm uses
        (crosssilo_hooks: client_transform at lane emit, reduce_extras
        accumulated in the scan, server_update post-aggregation with
        threaded server state). Returns ``{}`` for plain weighted-mean
        algorithms, the hook dict for the zoo (FedOpt/FedNova/AGC/robust —
        hooks now live on the BASE algorithm classes), or None when
        packing cannot mirror this subclass (rewired build_local_train,
        or a custom aggregate() with no hook translation)."""
        if type(self).build_local_train is not FedAvgAPI.build_local_train:
            if not getattr(self, "_warned_no_pack", False):
                log.warning(
                    "pack_lanes=%d ignored: %s rewires build_local_train, "
                    "which the packed lane builder cannot mirror",
                    self.config.pack_lanes, type(self).__name__)
                self._warned_no_pack = True
            return None
        hooks = self.crosssilo_hooks()
        if hooks is None:
            if type(self).aggregate is not FedAvgAPI.aggregate:
                if not getattr(self, "_warned_no_pack", False):
                    log.warning(
                        "pack_lanes=%d ignored: %s overrides aggregate() "
                        "without crosssilo hooks", self.config.pack_lanes,
                        type(self).__name__)
                    self._warned_no_pack = True
                return None
            hooks = {}
        return hooks

    def _tag_packed_program(self, step, n_lanes: int):
        """What a built packed program says of itself, set in this one
        place for the sim, streamed-chunk and mesh builders alike:
        ``.lane_ids`` (obs/compile.timed_build reads it into the build span
        and the compile counters), how many lanes one device runs and how
        many of them advance together
        (parallel/packed.lane_vmap_width's choice)."""
        n_lanes = int(n_lanes)
        step.lane_ids = {"lanes": n_lanes,
                         "lane_width": self._lane_width(n_lanes)}
        return step

    def packed_status(self) -> dict:
        """Introspection for the packed-coverage contract (the tier-1
        matrix test pins it): ``{"scheduled": <the round path runs packed
        lanes>, "reason": <None or why it does not>}``: ``pack_lanes`` 0,
        an algorithm the lane builder cannot mirror, or a round path that
        runs no lanes."""
        c = self.config
        if self._path in _PACKED_PATHS:
            return {"scheduled": True, "reason": None}
        if c.pack_lanes <= 0:
            reason = "pack_lanes=0"
        elif self._packing_hooks() is None:
            reason = (f"{type(self).__name__} has no packed-lane "
                      "algorithm mirror")
        else:
            reason = f"the {self._path} round path runs no packed lanes"
        return {"scheduled": False, "reason": reason}

    def _packed_plan(self, ids: np.ndarray):
        """The lane plan of a cohort (or streamed chunk) of these clients;
        None when it holds no record."""
        from fedml_tpu.parallel.packed import plan_packing

        c = self.config
        counts = self._counts_view(np.float64)[ids]
        # finer quantum than the bucketed schedule: a lane amortizes its
        # rounding tail over several clients, and the tail is pure waste —
        # the quantum only bounds how many distinct XLA programs the
        # varying per-round plans can demand (LRU-capped anyway)
        return plan_packing(counts, c.batch_size, c.epochs, c.pack_lanes,
                            t_quantum=max(1, c.bucket_quantum_batches // 4))

    def _lane_width(self, n_lanes: int) -> int:
        """How many of a device's ``n_lanes`` lanes its packed program
        advances together: parallel/packed.lane_vmap_width's choice."""
        from fedml_tpu.parallel.packed import lane_vmap_width

        return lane_vmap_width(self.variables, n_lanes)

    def _lane_slots(self, lanes, devices: int = 1) -> int:
        """Record slots one EPOCH of a lane plan executes: the lane-steps
        its loops walk over the whole round (PackPlan.executed_slots at the
        width the program runs the lanes of one of ``devices`` at: a chunk
        of lanes stops at its last live step, not at T); report one epoch's
        share, rounded to nearest (exact at epochs=1, the bench recipe; off
        by <1 batch otherwise)."""
        c = self.config
        width = self._lane_width(lanes.n_lanes // devices)
        return round(lanes.executed_slots(width, c.scan_unroll)
                     / max(c.epochs, 1)) * c.batch_size

    def build_round_step_packed(self, shape_key: tuple):
        from fedml_tpu.parallel.crosssilo import apply_server_and_rollback
        from fedml_tpu.parallel.packed import make_packed_cohort_train

        n_pad = int(self.dataset.train_x.shape[1])
        hooks = self._packing_hooks() or {}
        server_update = hooks.get("server_update")
        has_extras = hooks.get("reduce_extras") is not None
        lens_on = self._lens_armed
        reduce_extras = hooks.get("reduce_extras")
        # a model's own counts (models.COUNTERS) are SUMS over the clients'
        # steps: what each client added rides the scan's extras, and the
        # new global's counts are the old ones plus that sum, not the
        # weighted mean every other leaf is
        counted = COUNTERS in self.variables and not has_extras
        if counted:
            def reduce_extras(variables0, res, w):
                return jax.tree.map(lambda v, z: jnp.sum(
                    (w > 0).reshape((-1,) + (1,) * z.ndim) * (v - z), axis=0),
                    res.variables[COUNTERS], variables0[COUNTERS])
        packed = make_packed_cohort_train(
            self.bundle, self.task, n_pad, shape_key,
            client_transform=hooks.get("client_transform"),
            reduce_extras=reduce_extras,
            lens=lens_on,
            **self._local_train_kwargs())

        @jax.jit
        def round_step(variables, server_state, tx, ty, tm, rows, weights,
                       rng, plan_arrays):
            out = packed(
                variables, tx, ty, tm, rows, weights, rng, plan_arrays)
            acc, acc_w, acc_loss, _tau, extras = out[:5]
            with jax.named_scope(SCOPE_AGGREGATE):
                denom = jnp.maximum(acc_w, 1e-12)
                agg = jax.tree.map(
                    lambda a, v: (a / denom).astype(v.dtype), acc, variables)
            # the one shared post-aggregation tail (crosssilo.py): server
            # hook on the aggregate with the round's server key, elastic
            # all-failed rollback of weights AND server state
            new_vars, new_state = apply_server_and_rollback(
                variables, agg, extras if has_extras else None, acc_w,
                server_state, rng, server_update)
            with jax.named_scope(SCOPE_AGGREGATE):
                if counted:
                    new_vars = {**new_vars, COUNTERS: jax.tree.map(
                        jnp.add, variables[COUNTERS], extras)}
                if lens_on:
                    from fedml_tpu.obs.lens import packed_lens

                    upd, lf, ll, mw = out[5]
                    return (new_vars, new_state, acc_loss / denom,
                            packed_lens(upd, lf, ll, mw))
                return new_vars, new_state, acc_loss / denom

        return self._tag_packed_program(round_step, shape_key[0])

    def _run_packed_round(self, round_idx: int, plan: RoundPlan):
        """Execute the round under the packed schedule. ``plan.live``
        already folds the Silo client-active mask, and ``plan.lanes`` the
        STRUCTURAL lane freeze of exited clients (_round_plan: their plan
        steps masked dead in the same compiled program, never a vmap
        fallback). The ``fedml/round/plan`` span says how many of the
        plan's steps the program walks (``steps_run`` of
        ``steps_planned``: chunks of lanes x steps) and on how many of a
        lane's steps a client starts or ends (``tree_pass_steps``)."""
        from fedml_tpu.parallel.packed import plan_arrays_tuple

        sampled, live, lanes = plan.sampled, plan.live, plan.lanes
        rk = round_key(self.root_key, round_idx)
        width = self._lane_width(lanes.n_lanes)
        with span(SPAN_PLAN, round=round_idx,
                  steps_planned=lanes.n_lanes // width * lanes.T,
                  steps_run=lanes.executed_slots(
                      width, self.config.scan_unroll) // width,
                  tree_pass_steps=lanes.tree_pass_steps(
                      width, self.config.scan_unroll)):
            counts = np.asarray(self.dataset.train_counts, np.float32)[sampled]
            weights = (counts if live is None
                       else counts * np.asarray(live, np.float32))
            plan_arrays = plan_arrays_tuple(lanes)
        key = lanes.shape_key
        step = self._lru_step(self._packed_steps, key,
                              lambda: self.build_round_step_packed(key),
                              "packed_step")
        tx, ty, tm, _tc = self._dev_train
        with span(SPAN_ENQUEUE, round=round_idx):
            out = step(self.variables, self.server_state, tx, ty, tm,
                       jnp.asarray(sampled, jnp.int32), jnp.asarray(weights),
                       rk, tuple(jnp.asarray(a) for a in plan_arrays))
        if len(out) == 4:
            # packed_lens flattens [n_lanes, k_max] in member_pos order;
            # padding slots (member_valid 0) and dead/exited members
            # (weight 0) are dropped host-side via the valid mask
            mp = np.asarray(lanes.member_pos, np.int64).reshape(-1)
            mv = np.asarray(plan_arrays[7], np.float64).reshape(-1)
            valid = (mv > 0) & (np.asarray(weights, np.float64)[mp] > 0)
            out = self._lens_absorb(round_idx, out,
                                    np.asarray(sampled, np.int64)[mp], valid)
        self.variables, self.server_state, train_loss = out
        return train_loss

    def _run_gather_round(self, round_idx: int, plan: RoundPlan):
        """Execute the round as one vmap over the cohort, gathered in HBM
        from the resident stack, at the plan's scan length."""
        sampled, bucket = plan.sampled, plan.bucket
        rk = round_key(self.root_key, round_idx)
        live_np = (np.ones((len(sampled),), np.float32) if plan.live is None
                   else np.asarray(plan.live, np.float32))
        step = self._lru_step(
            self._gather_steps, bucket,
            lambda: self.build_round_step_gather(bucket), "gather_step")
        with span(SPAN_ENQUEUE, round=round_idx):
            out = step(
                self.variables, self.server_state, *self._dev_train,
                jnp.asarray(sampled, jnp.int32), jnp.asarray(live_np), rk)
        self.variables, self.server_state, train_loss = \
            self._lens_absorb(round_idx, out, sampled, live_np > 0)
        return train_loss

    def _sample_failures(self, round_idx: int, cohort: int,
                         record: bool = True) -> Optional[np.ndarray]:
        """Deterministic per-round fault injection (SURVEY.md §5.3: the
        reference has NO failure detection or fault injection — its only
        failure handling is MPI.Abort). With ``config.failure_prob`` > 0
        each sampled client independently fails this round; the aggregation
        then runs elastically over the survivors. Returns a {0,1} live
        vector or None when injection is off. ``record=False`` computes the
        same deterministic outcome without logging/history side effects
        (for :meth:`round_counts`)."""
        p = self.config.failure_prob
        if not p:
            return None
        elastic_ok = (type(self).build_round_step is FedAvgAPI.build_round_step
                      or getattr(type(self), "elastic_rounds_ok", False))
        if not elastic_ok:
            if not getattr(self, "_warned_no_elastic", False):
                log.warning(
                    "failure_prob=%s ignored: %s rewires the round program "
                    "without an elastic (zero-weight) aggregation guard",
                    p, type(self).__name__)
                self._warned_no_elastic = True
            return None
        rng = np.random.default_rng([self.config.seed, 0x0F41, round_idx])
        live = (rng.random(cohort) >= p).astype(np.float32)
        if record:
            n_failed = int(cohort - live.sum())
            if n_failed:
                log.info("round %d: %d/%d clients failed (injected)",
                         round_idx, n_failed, cohort)
            self.history.setdefault("failed_clients", []).append(n_failed)
        return live

    def set_client_active(self, active) -> None:
        """Per-client participation mask (the Silo harness's per-client
        early EXIT, algorithms/silo.py): a client whose entry is 0 stops
        contributing — its aggregation weight zeroes on every schedule,
        and the packed paths additionally freeze its lane span structurally
        (parallel/packed.masked_plan) inside the SAME compiled
        program. ``active``: [num_clients] {0,1}-ish, or None to clear.
        Takes effect from the next round."""
        if active is None:
            self._client_active = None
        else:
            a = np.asarray(active, np.float32)
            self._client_active = None if a.all() else a
        self._client_active_version += 1

    def _round_live(self, round_idx: int, sampled: np.ndarray,
                    record: bool) -> Optional[np.ndarray]:
        """The round's {0,1} weight mask over ``sampled``: injected
        failures times the Silo client-active mask, so every path honors
        an exit the same way it honors a failure: weight zero."""
        live = self._sample_failures(round_idx, len(sampled), record=record)
        if self._client_active is not None:
            av = self._client_active[sampled]
            live = av if live is None else live * av
        return live

    def _round_plan(self, round_idx: int, record: bool = False) -> RoundPlan:
        """The deterministic per-round plan: the sampled cohort, its live
        mask, the program that runs it and the slots that program
        executes. run_round executes exactly this plan; round_counts
        reports it — one source of truth for what a round trains on."""
        sampled = self._cohort_sched.sample(round_idx)
        live = self._round_live(round_idx, sampled, record)
        bucket = self._round_bucket(sampled, live)
        path, lanes = self._path, None
        # every sampled client counts (failure injection only zeroes
        # weights): at the shared scan length on the vmap paths, as its
        # lanes' batch steps on the packed ones
        n_pad = int(self.dataset.train_x.shape[1])
        padded = (n_pad if bucket is None else bucket) * len(sampled)
        if path == PATH_PACKED:
            lanes = self._packed_plan(sampled)
            if lanes is None:
                path = PATH_GATHER      # a cohort with no record to pack
            else:
                if self._client_active is not None:
                    # the plan the program is handed, and so the one counted
                    from fedml_tpu.parallel.packed import masked_plan

                    lanes = masked_plan(
                        lanes, self._client_active[sampled][lanes.member_pos])
                padded = self._lane_slots(lanes)
        elif path == PATH_STREAM_PACKED:
            lanes = tuple(self._packed_plan(sampled[start:start + size])
                          for start, size in
                          self._stream_chunk_spec(len(sampled)))
            padded = sum(self._lane_slots(pk) for pk in lanes
                         if pk is not None)
        return RoundPlan(path, sampled, live, bucket, lanes, padded)

    def round_counts(self, round_idx: int) -> tuple:
        """(real, padded) training examples one epoch of this round
        processes, read off the plan run_round executes: real = the live
        cohort's actual record counts (masked padding excluded; failed
        clients' work is discarded by aggregation, so it isn't "real"
        training), padded = the record slots the device EXECUTES. The
        benchmark divides by these, so throughput accounting cannot drift
        from run_round."""
        plan = self._round_plan(round_idx)
        counts = self._counts_view(np.float64)[plan.sampled]
        if plan.live is not None:
            counts = counts * plan.live
        return int(counts.sum()), int(plan.padded_slots)

    # -- host round pipeline -------------------------------------------------

    def _host_round_inputs(self, round_idx: int, pool=None, n_chunks: int = 0,
                           plan=None):
        """Host-side inputs for one non-device-resident round — the ONE
        builder the serial path and the prefetcher share, so the pipeline
        cannot drift from the serial path: materialize the sampled cohort,
        trim it to the round's bucket, bf16-cast on host, zero failed
        clients' aggregation weights. Pure in (seed, round_idx); ``plan``
        passes an already-computed ``_round_plan`` result (the serial call
        site has one — sampling draws O(client_num_in_total) per call)."""
        from fedml_tpu.data.pipeline import materialize_cohort
        from fedml_tpu.utils.dtypes import host_bf16_cast

        if plan is None:
            # prefetcher path: this build's plan is THE round's plan — the
            # consuming round (its lens ids, its pulse hook) reads it from
            # the stash instead of re-paying the sampling draw on the
            # critical path
            plan = self._round_plan(round_idx)
            self._stash_plan(round_idx, plan)
        sampled, live, bucket = plan.sampled, plan.live, plan.bucket
        cx, cy, cm, counts = materialize_cohort(
            self.dataset, sampled, pool, n_chunks)
        if bucket is not None:
            cx, cy, cm = cx[:, :bucket], cy[:, :bucket], cm[:, :bucket]
        # bf16 training casts on device anyway — casting on HOST first
        # halves the per-round uplink (the dominant cost for big-input
        # host-path rounds, e.g. the 342k-client cross-device row's
        # 140 MB/round of 10k-dim features)
        cx = host_bf16_cast(np.asarray(cx), self.config.dtype)
        counts = np.asarray(counts, np.float32)
        if live is not None:
            counts = counts * live
        return cx, cy, cm, counts

    def _prefetch_build(self, round_idx: int, pool):
        """Background stage of the host round pipeline: materialize + cast
        (fanned out over the cohort's clients on ``pool``), then ship
        host->device — all while the in-flight round computes. Returns the
        device-resident payload plus stage timings (round_stats)."""
        # the prefetch spans carry the round they build FOR (and follow
        # its head-sampling verdict in the tracer's ring). They live on the
        # prefetcher's background threads — in the timeline they sit beside
        # (not under) the consuming round, which is exactly the overlap the
        # pipeline exists to create
        t0 = time.perf_counter()
        with span(SPAN_MATERIALIZE, round=round_idx):
            cx, cy, cm, counts = self._host_round_inputs(
                round_idx, pool, n_chunks=getattr(pool, "_max_workers", 0))
        t1 = time.perf_counter()
        with span(SPAN_H2D, round=round_idx):
            payload = (jax.device_put(cx), jax.device_put(cy),
                       jax.device_put(cm), jax.device_put(counts))
            jax.block_until_ready(payload)
        t2 = time.perf_counter()
        return payload, {"materialize_ms": (t1 - t0) * 1e3,
                         "h2d_ms": (t2 - t1) * 1e3}

    def _host_prefetcher(self):
        """The lazy CohortPrefetcher for the host round path; None when the
        pipeline is off (depth 0) or rounds are device-resident."""
        c = self.config
        if c.host_pipeline_depth <= 0 or self._dev_train is not None:
            return None
        if self._prefetcher is None:
            from fedml_tpu.data.pipeline import CohortPrefetcher

            # speculate within the training schedule only — train() pops
            # rounds [0, comm_round), so building past the end is pure
            # waste; a driver that pops beyond it (the bench re-runs
            # [1, comm_round]) raises the bound itself
            self._prefetcher = CohortPrefetcher(
                self._prefetch_build, c.host_pipeline_depth,
                workers=c.host_pipeline_workers,
                max_round=c.comm_round)
        return self._prefetcher

    def _host_pipeline_step(self):
        """Round step for the pipeline path. When this API runs the base
        round program, the cohort buffers are offered for donation
        (config.donate): the round step is their last consumer. No output
        has their shape, so JAX drops the offer (see ``_donation_quiet``)
        and the blocks are freed when the popped payload's last reference
        goes, after the call. Subclasses that rewire build_round_step keep
        their own (non-donating) step."""
        if (not self.config.donate
                or type(self).build_round_step is not FedAvgAPI.build_round_step):
            return self._round_step
        if self._donated_step is None:
            from fedml_tpu.obs import timed_build

            jitted = timed_build(
                self._program_name("donated_step"), ("donated",),
                lambda: jax.jit(self._round_body, donate_argnums=(2, 3, 4)))
            self._donated_step = _donation_quiet(jitted)
        return self._donated_step

    # -- streaming chunked host rounds (fedsched) ----------------------------

    def _stream_mode(self) -> str:
        """Effective streaming-aggregation mode for THIS API: the config
        mode when the base round machinery applies, else "off" with one
        warning — streaming folds a plain weighted mean, so a rewired
        local trainer / round program / custom aggregate() keeps its batch
        path (the same exception discipline as the packed schedule)."""
        memo = self._stream_mode_memo
        if memo is not None:
            return memo
        c = self.config
        mode = c.stream_aggregate
        if mode != "off" and (
                type(self).aggregate is not FedAvgAPI.aggregate
                or self.crosssilo_hooks() is not None
                or type(self).build_local_train is not FedAvgAPI.build_local_train
                or type(self).build_round_step is not FedAvgAPI.build_round_step):
            log.warning(
                "stream_aggregate=%r ignored: %s rewires aggregation (or "
                "carries crosssilo hooks) or the round program, which the "
                "streaming fold cannot mirror; using the batch path",
                mode, type(self).__name__)
            mode = "off"
        self._stream_mode_memo = mode
        return mode

    def _counts_view(self, dtype) -> "np.ndarray":
        """Cached float view of the population counts table: the streamed
        chunk path indexes it once per sub-cohort and the pulse feed once
        per round, so a million-client table is converted once per run,
        not re-cast (~8 MB of memcpy) on every lookup."""
        cache = getattr(self, "_counts_view_cache", None)
        if cache is None:
            cache = self._counts_view_cache = {}
        src = self.dataset.train_counts
        key = (id(src), np.dtype(dtype).name)
        v = cache.get(key)
        if v is None:
            if any(k[0] != id(src) for k in cache):
                cache.clear()    # dataset swapped: drop the old table's views
            v = cache[key] = np.asarray(src, dtype)
        return v

    @property
    def _stream_chunks_per_round(self) -> int:
        c = self.config
        cohort = min(c.client_num_per_round, self.dataset.num_clients)
        if c.cohort_chunk <= 0 or c.cohort_chunk >= cohort:
            return 1
        return -(-cohort // c.cohort_chunk)

    def _stream_chunk_spec(self, cohort_n: int) -> list:
        """[(start, size)] half-open sub-cohort chunks in plan order."""
        chunk = self.config.cohort_chunk
        if chunk <= 0 or chunk >= cohort_n:
            return [(0, cohort_n)]
        return [(s, min(chunk, cohort_n - s))
                for s in range(0, cohort_n, chunk)]

    def _stream_chunk_inputs(self, round_idx: int, ci: int, pool=None,
                             n_chunks: int = 0, plan=None):
        """Host-side inputs for ONE sub-cohort chunk — pure in
        (seed, round_idx, ci) like _host_round_inputs: materialize just the
        chunk's clients, trim to the ROUND's shared bucket (vmap chunks —
        the packed program needs the full record axis for its canonical
        replay tables), bf16-cast, zero failed clients' weights, and derive
        the full-round-normalized aggregation weights the deterministic
        fold needs (the total weight is known from the plan, so the fold
        can use exactly tree_weighted_mean's normalize-first arithmetic).
        ``plan`` passes the round's plan where the caller has it (the
        serial path); a prefetcher build makes its own."""
        from fedml_tpu.data.pipeline import materialize_cohort
        from fedml_tpu.utils.dtypes import host_bf16_cast

        if plan is None:
            plan = self._round_plan(round_idx)
        sampled, live, bucket = plan.sampled, plan.live, plan.bucket
        start, size = self._stream_chunk_spec(len(sampled))[ci]
        packed = plan.path == PATH_STREAM_PACKED
        cx, cy, cm, counts = materialize_cohort(
            self.dataset, sampled[start:start + size], pool, n_chunks)
        if bucket is not None and not packed:
            cx, cy, cm = cx[:, :bucket], cy[:, :bucket], cm[:, :bucket]
        cx = host_bf16_cast(np.asarray(cx), self.config.dtype)
        counts = np.asarray(counts, np.float32)
        w_full = self._counts_view(np.float32)[sampled]
        if live is not None:
            lv = np.asarray(live, np.float32)
            counts = counts * lv[start:start + size]
            w_full = w_full * lv
        # f32 normalize-first, bit-matching tree_weighted_mean's
        # w / max(sum(w), 1e-12): the weights are integer-valued f32, so
        # the host sum is exact and order-free
        denom = np.maximum(np.float32(w_full.sum()), np.float32(1e-12))
        w_norm = (counts / denom).astype(np.float32)
        return (cx, cy, cm, counts, w_norm), (len(sampled), start, size,
                                              bucket)

    def _stream_prefetch_build(self, gidx: int, pool):
        """Background build for global chunk index ``gidx`` = round *
        chunks_per_round + chunk — the CohortPrefetcher speculates over
        this monotone sequence exactly as it does over rounds, so its
        in-flight memory is depth x ONE CHUNK, never a whole cohort."""
        C = self._stream_chunks_per_round
        r, ci = divmod(gidx, C)
        t0 = time.perf_counter()
        with span(SPAN_MATERIALIZE, round=r, chunk=ci):
            payload_np, meta = self._stream_chunk_inputs(
                r, ci, pool, n_chunks=getattr(pool, "_max_workers", 0))
        t1 = time.perf_counter()
        with span(SPAN_H2D, round=r, chunk=ci):
            payload = tuple(jax.device_put(a) for a in payload_np)
            jax.block_until_ready(payload)
        t2 = time.perf_counter()
        return (payload, meta), {"materialize_ms": (t1 - t0) * 1e3,
                                 "h2d_ms": (t2 - t1) * 1e3}

    def _stream_prefetcher(self):
        """Chunk-granular CohortPrefetcher for the streaming round path
        (depth counts CHUNKS, so memory in flight is depth sub-cohorts)."""
        c = self.config
        if c.host_pipeline_depth <= 0:
            return None
        if self._stream_pf is None:
            from fedml_tpu.data.pipeline import CohortPrefetcher

            C = self._stream_chunks_per_round
            self._stream_pf = CohortPrefetcher(
                self._stream_prefetch_build, c.host_pipeline_depth,
                workers=c.host_pipeline_workers,
                max_round=(None if c.comm_round is None
                           else c.comm_round * C),
                name="stream-prefetch")
        return self._stream_pf

    def build_round_step_stream_chunk(self, cohort: int, start: int,
                                      size: int):
        """One sub-cohort's jitted streaming step: train the chunk under
        the SAME vmap schedule as the batch round (per-client keys =
        split(rng, cohort)[position] — identical per-client math), then
        fold its normalize-first weighted sums into the running
        accumulator. With ONE chunk this computes bit-for-bit
        tree_weighted_mean + _finish_round's loss: the deterministic
        streaming mode's bit-identity to batch aggregation is by
        construction, not by tolerance."""
        cohort_train = self._cohort_train

        def chunk_step(variables, acc, acc_w, acc_loss, cx, cy, cm, counts,
                       w_norm, rng):
            with jax.named_scope(SCOPE_PROLOGUE):
                keys = jax.random.split(rng, cohort)[start:start + size]
            res = cohort_train(variables, cx, cy, cm, counts, keys)

            def wadd(a, x):
                wb = w_norm.reshape((-1,) + (1,) * (x.ndim - 1))
                return a + jnp.sum(x.astype(jnp.float32) * wb, axis=0)

            with jax.named_scope(SCOPE_AGGREGATE):
                acc = jax.tree.map(wadd, acc, res.variables)
                w = counts.astype(jnp.float32)
                return (acc, acc_w + jnp.sum(w),
                        acc_loss + jnp.sum(res.train_loss * w))

        if not self.config.donate:
            return jax.jit(chunk_step)
        # donate the accumulator (replaced every chunk: aliased in place,
        # so chunked memory stays flat) and offer the chunk buffers (this
        # step is their last consumer; no output matches them, so JAX
        # drops that part — see _donation_quiet)
        return _donation_quiet(jax.jit(chunk_step, donate_argnums=(1, 4, 5, 6)))

    def build_round_step_stream_packed(self, cohort: int, start: int,
                                       size: int, shape_key: tuple):
        """Packed-lanes variant of the streaming chunk step: the chunk's
        clients pack back-to-back into scan lanes
        (parallel/packed.make_packed_cohort_train over the materialized
        chunk arrays, key_slice preserving the canonical per-client keys),
        and the lane program's native weighted sums fold into the
        accumulator — the MXU fast path bounded by the accumulator, not by
        one program's cohort buffers."""
        from fedml_tpu.parallel.packed import make_packed_cohort_train

        n_pad = int(self.dataset.train_x.shape[1])
        packed = make_packed_cohort_train(
            self.bundle, self.task, n_pad, shape_key,
            key_slice=(cohort, start),
            **self._local_train_kwargs())
        rows = jnp.arange(size, dtype=jnp.int32)

        def chunk_step(variables, acc, acc_w, acc_loss, cx, cy, cm, counts,
                       rng, plan_arrays):
            a, w, l, _tau, _extras = packed(
                variables, cx, cy, cm, rows, counts, rng, plan_arrays)
            with jax.named_scope(SCOPE_AGGREGATE):
                acc = jax.tree.map(
                    lambda s, p: s + p.astype(jnp.float32), acc, a)
                return acc, acc_w + w.astype(jnp.float32), \
                    acc_loss + l.astype(jnp.float32)

        step = (_donation_quiet(jax.jit(chunk_step,
                                        donate_argnums=(1, 4, 5, 6)))
                if self.config.donate else jax.jit(chunk_step))
        return self._tag_packed_program(step, shape_key[0])

    def _stream_finish(self, packed: bool):
        """Round-close for the streaming fold: elastic all-failed rollback
        + weighted loss, mirroring _finish_round's arithmetic. The vmap
        fold accumulates normalize-first sums (the aggregate IS acc); the
        packed fold accumulates unnormalized lane sums (aggregate =
        acc / acc_w, the packed round's own tail)."""
        if self._stream_finish_fn is None:
            @jax.jit
            def finish_vmap(variables, acc, acc_w, acc_loss):
                with jax.named_scope(SCOPE_AGGREGATE):
                    keep = acc_w > 0
                    new_vars = jax.tree.map(
                        lambda a, v: jnp.where(keep, a.astype(v.dtype), v),
                        acc, variables)
                    return new_vars, acc_loss / jnp.maximum(acc_w, 1e-12)

            @jax.jit
            def finish_packed(variables, acc, acc_w, acc_loss):
                with jax.named_scope(SCOPE_AGGREGATE):
                    denom = jnp.maximum(acc_w, 1e-12)
                    keep = acc_w > 0
                    new_vars = jax.tree.map(
                        lambda a, v: jnp.where(
                            keep, (a / denom).astype(v.dtype), v),
                        acc, variables)
                    return new_vars, acc_loss / denom

            self._stream_finish_fn = (finish_vmap, finish_packed)
        return self._stream_finish_fn[1 if packed else 0]

    def _run_streaming_round(self, round_idx: int, plan: RoundPlan):
        """Execute one host round as streamed sub-cohort chunks: each chunk
        materializes (prefetched when the pipeline is on), trains, and
        folds into the running accumulator as it finishes on device —
        server memory is ONE f32 model sum regardless of cohort size.
        Unchunked deterministic mode computes the batch program's
        arithmetic bit-for-bit."""
        c = self.config
        rk = round_key(self.root_key, round_idx)
        cohort_n = len(plan.sampled)
        spec = self._stream_chunk_spec(cohort_n)
        C = len(spec)
        packed = plan.path == PATH_STREAM_PACKED
        acc = jax.tree.map(lambda v: jnp.zeros(v.shape, jnp.float32),
                           self.variables)
        acc_w = jnp.zeros((), jnp.float32)
        acc_loss = jnp.zeros((), jnp.float32)
        pf = self._stream_prefetcher()
        mat_ms = h2d_ms = wait_ms = compute_ms = 0.0
        for ci, (start, size) in enumerate(spec):
            if pf is not None:
                with span(SPAN_WAIT_INPUTS, round=round_idx, chunk=ci):
                    (payload, meta), stages, w_ms = pf.pop(round_idx * C + ci)
                mat_ms += stages["materialize_ms"]
                h2d_ms += stages["h2d_ms"]
                wait_ms += w_ms
            else:
                t0 = time.perf_counter()
                with span(SPAN_MATERIALIZE, round=round_idx, chunk=ci):
                    payload, meta = self._stream_chunk_inputs(
                        round_idx, ci, plan=plan)
                dt = (time.perf_counter() - t0) * 1e3
                mat_ms += dt
                wait_ms += dt    # serial: the host stage is fully exposed
            cx, cy, cm, counts, w_norm = payload
            t0 = time.perf_counter()
            if packed:
                from fedml_tpu.parallel.packed import plan_arrays_tuple

                lanes = plan.lanes[ci]
                key = ("p", cohort_n, start, size, lanes.shape_key)
                step = self._lru_step(
                    self._stream_steps, key,
                    lambda: self.build_round_step_stream_packed(
                        cohort_n, start, size, lanes.shape_key),
                    "stream_step")
                with span(SPAN_ENQUEUE, round=round_idx, chunk=ci):
                    acc, acc_w, acc_loss = step(
                        self.variables, acc, acc_w, acc_loss, cx, cy, cm,
                        jnp.asarray(counts), rk,
                        tuple(jnp.asarray(a)
                              for a in plan_arrays_tuple(lanes)))
            else:
                key = ("v", cohort_n, start, size, meta[3])
                step = self._lru_step(
                    self._stream_steps, key,
                    lambda: self.build_round_step_stream_chunk(
                        cohort_n, start, size),
                    "stream_step")
                with span(SPAN_ENQUEUE, round=round_idx, chunk=ci):
                    acc, acc_w, acc_loss = step(
                        self.variables, acc, acc_w, acc_loss, cx, cy, cm,
                        jnp.asarray(counts), jnp.asarray(w_norm), rk)
            compute_ms += (time.perf_counter() - t0) * 1e3
        self.variables, train_loss = self._stream_finish(packed)(
            self.variables, acc, acc_w, acc_loss)
        if not c.async_rounds:
            train_loss = float(train_loss)
        row = {"materialize_ms": mat_ms, "h2d_ms": h2d_ms,
               "wait_ms": wait_ms, "round": round_idx,
               "compute_ms": compute_ms}
        self._stage_rows.append(row)
        from fedml_tpu.obs import default_registry, tracer_if_sampled

        default_registry().append_row("stage", row)
        tr = tracer_if_sampled(0, round_idx)
        if tr is not None:
            tr.counter("host_stages", {
                k: row[k] for k in
                ("materialize_ms", "h2d_ms", "compute_ms", "wait_ms")},
                args={"round": round_idx})
        # the O(1)-memory evidence: the server-side round state is ONE f32
        # model-shaped accumulator + two scalars, independent of cohort
        self.stream_stats = {
            "mode": c.stream_aggregate, "cohort": cohort_n, "chunks": C,
            "chunk_clients": c.cohort_chunk if C > 1 else cohort_n,
            "packed_lanes": c.pack_lanes if packed else 0,
            "accumulator_bytes": int(sum(
                int(np.prod(v.shape)) * 4
                for v in jax.tree.leaves(self.variables)) + 8)}
        return train_loss

    def _traced_device_step(self, path: str, round_idx: int, step, *args):
        """Run one device round program under a ``mesh_step`` span so the
        trace can attribute the in-mesh device leg per round (the mesh
        counterpart of the edge paradigm's train leg). With async_rounds
        the span measures DISPATCH (+ trace/compile on a program's first
        call) — the tracer never forces a device sync."""
        from fedml_tpu.obs import tracer_if_sampled
        from fedml_tpu.obs.tracer import NOOP_SPAN

        tr = tracer_if_sampled(0, round_idx)
        ring = NOOP_SPAN if tr is None else tr.span(
            "mesh_step", cat="device",
            args={"round": round_idx, "path": path})
        with span(SPAN_ENQUEUE, round=round_idx, path=path), ring:
            return step(*args)

    def close(self) -> None:
        """Drain and tear down background machinery (the host round
        pipeline) and publish the model's own counters (``bundle.counters``
        of the current variables: a sync on a few small arrays). Idempotent;
        the API stays usable — the next host-path round lazily rebuilds the
        prefetcher."""
        if self.bundle.counters is not None:
            from fedml_tpu.obs import model_counters

            g = model_counters()
            for k, v in self.bundle.counters(self.variables).items():
                g[k] = v
        pf = self._prefetcher
        self._prefetcher = None
        if pf is not None:
            pf.close()
        spf = self._stream_pf
        self._stream_pf = None
        if spf is not None:
            spf.close()

    # -- driver --------------------------------------------------------------

    def run_round(self, round_idx: int) -> "float | jax.Array":
        """Execute one round; returns the weighted train loss — a host float,
        or (config.async_rounds) the un-synced device scalar so consecutive
        rounds pipeline; callers that do host arithmetic must float() it.

        THE traced wrapper: every paradigm's round logic lives in
        ``_run_round_inner`` (subclasses override THAT, never this — the
        fedlint ``trace-coverage`` rule enforces it), so one ``fedml/round``
        span per round (always a profiler annotation; a ring record too
        under ``--trace_dir``) plus the round-boundary device-memory sample
        cover the whole zoo.
        The fedpulse plane rides the same wrapper: with ``--pulse_path``
        set, every round feeds the per-client profiler and appends one
        snapshot to the pulse stream — both gates are one global read when
        off, and neither touches the round's math. Under
        ``--trace_sample_rate`` the tracer gate is the deterministic
        head-sampling verdict for THIS round: a sampled-out round emits no
        spans, but the pulse/sketch feed below still sees it."""
        from fedml_tpu.obs import (pulse_if_enabled, sample_device_memory,
                                   tracer_if_sampled)

        pulse = pulse_if_enabled()
        t0 = time.perf_counter()
        with span(SPAN_ROUND, round=round_idx):
            out = self._run_round_inner(round_idx)
        tr = tracer_if_sampled(0, round_idx)
        if tr is not None and getattr(self.config, "trace_device_sampler",
                                      True):
            sample_device_memory(tr, round_idx)
        if pulse is not None:
            # with async_rounds `out` is an un-synced device scalar and the
            # wall measured dispatch; the plane never float()s it (that
            # would force the sync the flag exists to avoid)
            pulse.on_sim_round(self, round_idx,
                               out, (time.perf_counter() - t0) * 1e3)
        # fedsched boundary: snapshot the profiler AFTER this round's pulse
        # feed, so the plan for round r + SCHED_LAG sees it
        sched = self._cohort_sched
        if sched.wants_notify:
            sched.notify_round_done(round_idx)
        return out

    def set_cohort_profiler(self, source) -> None:
        """Freeze the fedsched scheduling signal to ``source`` (a
        ClientProfiler or ProfileSnapshot; None clears): every plan then
        derives from this one snapshot — timing- and pipeline-depth-
        independent, the determinism mode tools/xdev_ab.py --policy pins."""
        self._cohort_sched.set_static_profile(source)

    def _stash_plan(self, round_idx: int, plan: RoundPlan) -> None:
        """Record a computed round plan for its later readers (the host
        round's lens ids, :meth:`_pulse_cohort`); a single dict store under
        the GIL — the prefetcher's background builds and the main thread
        may both write, always to distinct round keys."""
        stash = self._plan_stash
        stash[int(round_idx)] = plan
        while len(stash) > 16:   # bound: pipeline depth + slack
            stash.pop(next(iter(stash)))

    def _pulse_cohort(self, round_idx: int) -> Optional[np.ndarray]:
        """Logical client ids this round actually TRAINED, for the fedpulse
        profiler. Default: the round plan's live cohort, reusing the plan
        the round (or its background prefetch build) already stashed —
        the fallback re-derivation is deterministic but re-pays the
        O(client_num_in_total) sampling draw. Paradigms whose rounds
        train a different population than the sampled cohort (the
        decentralized gossip family trains EVERY node) override this —
        otherwise the pulse stream would profile a phantom cohort."""
        plan = (self._plan_stash.pop(int(round_idx), None)
                or self._round_plan(round_idx))
        ids = np.asarray(plan.sampled, np.int64)
        if plan.live is not None:
            ids = ids[np.asarray(plan.live) > 0]
        return ids

    # -- fedlens (obs/lens.py) ----------------------------------------------

    #: class-level defaults so subclasses need no __init__ surgery; the
    #: armed state is snapshotted at the FIRST armed-check (i.e. the first
    #: round program trace), mirroring the tracer's arm-before-build rule
    _lens_state: "Optional[bool]" = None
    _lens_stash = None
    _lens_prev = None

    @property
    def _lens_armed(self) -> bool:
        on = self._lens_state
        if on is None:
            from fedml_tpu.obs.lens import lens_enabled

            # one-time snapshot BY DESIGN: the armed bit is frozen at the
            # first round-program trace so lens on/off can never re-trace
            # mid-run (the trace-time-only behavior the rule warns about
            # is exactly the contract)  # fedlint: disable=traced-purity
            on = self._lens_state = bool(lens_enabled())
        return on

    def _lens_absorb(self, round_idx: int, out, ids, valid=None):
        """Strip + stash the lens element when an armed round program
        returned one (device arrays stay un-synced); 3-tuples pass
        through. ``ids`` are the logical client ids in the lens arrays'
        stacking order; ``valid`` masks padding/failed entries."""
        if len(out) == 4:
            self._lens_stash = (
                int(round_idx), np.asarray(ids, np.int64),
                None if valid is None else np.asarray(valid, bool), out[3])
            out = out[:3]
        return out

    def _pulse_lens(self, round_idx: int):
        """The round's lens stats as host arrays for the pulse feed —
        ``(round, ids, {"update_norm", "align"[, "loss_delta"]})`` or
        None. Under ``--async_rounds`` conversion runs one round LATE (the
        previous round's arrays are already materialized), so the feed
        never forces a host sync on the round just dispatched; ids ride
        with their stats, so the one-round lag cannot misattribute."""
        cur, self._lens_stash = self._lens_stash, None
        if self.config.async_rounds:
            cur, self._lens_prev = self._lens_prev, cur
        if cur is None:
            return None
        r, ids, valid, dev = cur
        stats = {k: np.asarray(v, np.float64) for k, v in dev.items()}
        if valid is not None:
            ids = ids[valid]
            stats = {k: v[valid] for k, v in stats.items()}
        if ids.size == 0:
            return None
        return r, ids, stats

    def _pulse_cohort_shares(self, ids) -> "Optional[np.ndarray]":
        """Per-client share of the round wall for the fedpulse profiler
        feed: proportional to each client's record count — within a fused
        cohort a client with 3x the records consumed ~3x the materialize +
        compute, so count-weighted attribution is the honest amortization
        (and the signal that lets the ``speed`` policy tell a heavy client
        from a light one). None = even split (paradigms whose cohorts
        don't map to the stacked count table override _pulse_cohort and
        may not have counts for every id)."""
        counts = self._counts_view(np.float64)
        ids = np.asarray(ids, np.int64)
        if ids.size == 0 or ids.max(initial=-1) >= counts.shape[0]:
            return None
        c = counts[ids]
        total = float(c.sum())
        return c / total if total > 0 else None

    def _run_round_inner(self, round_idx: int) -> "float | jax.Array":
        plan = None
        if self._path != PATH_HOST_PIPELINE:
            with span(SPAN_PLAN, round=round_idx):
                plan = self._round_plan(round_idx, record=True)
                self._stash_plan(round_idx, plan)
        runner = self._ROUND_RUNNERS[self._path if plan is None
                                     else plan.path]
        train_loss = runner(self, round_idx, plan)
        return train_loss if self.config.async_rounds else float(train_loss)

    def _run_host_round(self, round_idx: int, plan: RoundPlan):
        """The serial host path: materialize the plan's cohort, ship it,
        run the round program; the host stages are fully exposed."""
        t0 = time.perf_counter()
        with span(SPAN_MATERIALIZE, round=round_idx):
            inputs = self._host_round_inputs(round_idx, plan=plan)
        mat_ms = (time.perf_counter() - t0) * 1e3
        return self._host_step(
            round_idx, plan, self._round_step, inputs,
            {"materialize_ms": mat_ms, "h2d_ms": 0.0}, wait_ms=mat_ms)

    def _run_pipelined_round(self, round_idx: int, plan):
        """The pipelined host path (``plan`` is None here): the
        prefetcher's background build made this round's plan (and stashed
        it) and its device-resident inputs, so only the record=True side
        effects (failure history + log) run here — NOT the
        O(client_num_in_total) sampling draw, which would sit on the
        critical path this pipeline exists to clear."""
        self._sample_failures(
            round_idx,
            min(self.config.client_num_per_round, self.dataset.num_clients),
            record=True)
        with span(SPAN_WAIT_INPUTS, round=round_idx):
            inputs, stages, wait_ms = self._host_prefetcher().pop(round_idx)
        return self._host_step(
            round_idx, self._plan_stash.get(int(round_idx)),
            self._host_pipeline_step(), inputs, stages, wait_ms)

    def _host_step(self, round_idx: int, plan: Optional[RoundPlan], step,
                   inputs, stages: dict, wait_ms: float):
        """The host paths' shared tail: run ``step`` on the shipped cohort
        and record the round's stage row."""
        cx, cy, cm, counts = inputs
        rk = round_key(self.root_key, round_idx)
        t0 = time.perf_counter()
        with span(SPAN_ENQUEUE, round=round_idx):
            out = step(
                self.variables, self.server_state, cx, cy, cm,
                jnp.asarray(counts, jnp.float32), rk
            )
        if len(out) == 4:
            # host-path cohort order is the plan's sampled order; the
            # prefetcher stashes its plans too, so the id mapping survives
            # pipelining (absent plan = lens skipped)
            if plan is not None:
                out = self._lens_absorb(
                    round_idx, out, plan.sampled,
                    None if plan.live is None else np.asarray(plan.live) > 0)
            else:
                out = out[:3]
        self.variables, self.server_state, train_loss = out
        if not self.config.async_rounds:
            train_loss = float(train_loss)
        row = dict(stages, wait_ms=wait_ms, round=round_idx,
                   compute_ms=(time.perf_counter() - t0) * 1e3)
        self._stage_rows.append(row)
        from fedml_tpu.obs import default_registry, tracer_if_sampled

        # the registry's stage-row record mirrors _stage_rows (the
        # round_stats view) so registry readers (MetricsLogger,
        # tests) see the same numbers the summary reports; the trace
        # analyzer gets its copy via the host_stages counter below
        default_registry().append_row("stage", row)
        tr = tracer_if_sampled(0, round_idx)
        if tr is not None:
            tr.counter("host_stages", {
                k: row[k] for k in
                ("materialize_ms", "h2d_ms", "compute_ms", "wait_ms")},
                args={"round": round_idx})
        return train_loss

    #: path -> the method that runs a round on it (``_run_round_inner``)
    _ROUND_RUNNERS = {
        PATH_PACKED: _run_packed_round,
        PATH_GATHER: _run_gather_round,
        PATH_HOST: _run_host_round,
        PATH_HOST_PIPELINE: _run_pipelined_round,
        PATH_STREAM: _run_streaming_round,
        PATH_STREAM_PACKED: _run_streaming_round,
    }

    def save(self, path: str, round_idx: int = 0, orbax: bool = False) -> None:
        """Checkpoint variables + server state (+ resume round). The
        reference cannot do this at all (SURVEY.md §5.4: duck-typed
        save_model, no resume); ``orbax=True`` writes a sharded checkpoint."""
        from fedml_tpu.utils import checkpoint as ckpt

        if orbax:
            ckpt.save_checkpoint_orbax(path, self.variables, self.server_state, round_idx)
        else:
            ckpt.save_checkpoint(path, jax.tree.map(np.asarray, self.variables),
                                 jax.tree.map(np.asarray, self.server_state),
                                 round_idx)

    def restore(self, path: str, orbax: bool = False) -> int:
        """Load a checkpoint into this API; returns the round index to
        resume from. Training continued from here is identical to an
        uninterrupted run (per-round RNG is derived from round_idx)."""
        from fedml_tpu.utils import checkpoint as ckpt

        if orbax:
            # the live state is the restore template: orbax rebuilds optax
            # namedtuples (and shardings) only when given the matching pytree
            state = ckpt.load_checkpoint_orbax(
                path, template={"variables": self.variables,
                                "server_state": self.server_state})
        else:
            state = ckpt.load_checkpoint(path)
        self.variables = jax.tree.map(jnp.asarray, state["variables"])
        self.server_state = jax.tree.map(jnp.asarray, state["server_state"])
        return int(state["round_idx"])

    def _resident_train_x(self):
        """The device-resident stacked client features, or None when rounds
        ship their cohort from the host."""
        return None if self._dev_train is None else self._dev_train[0]

    def placement(self) -> dict:
        """Where this run's arrays live, read off the arrays themselves —
        the record chip_smoke.py (and any benchmark stamp) checks so a run
        cannot claim a device it did not use: the devices holding the model,
        whether ``device_data`` resolved to a resident client stack and how
        that stack is sharded, and each device's bytes in use."""
        devs = jax.devices()
        x = self._resident_train_x()
        stats = {d.id: d.memory_stats() for d in jax.local_devices()}
        return {
            "platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "variables_on": sorted({
                f"{d.platform}:{d.id}"
                for leaf in jax.tree.leaves(self.variables)
                for d in leaf.devices()}),
            "device_resident": x is not None,
            "resident_shards": [] if x is None else [
                [s.device.id, list(s.data.shape)]
                for s in x.addressable_shards],
            # memory_stats() is None on backends that keep no allocator
            # statistics (CPU)
            "bytes_in_use": {str(i): int(st["bytes_in_use"])
                             for i, st in stats.items() if st},
        }

    def evaluate_global(self) -> dict:
        variables = self.variables
        if jax.process_count() > 1:
            # round outputs are replicated over the multi-process mesh;
            # eval is process-local, so pull the (fully-replicated) host
            # view first — mixing global and local arrays in one jit is
            # not a valid multi-process program
            variables = jax.tree.map(np.asarray, variables)
        sums = self._eval(
            variables, self.dataset.test_x, self.dataset.test_y, self.dataset.test_mask
        )
        return finalize_metrics(jax.tree.map(np.asarray, sums))

    def train(self) -> dict:
        from fedml_tpu.obs import (configure_from, default_registry,
                                   flush_all, tracing_enabled)
        from fedml_tpu.utils.metrics import MetricsLogger, RoundTimer, profile_trace

        c = self.config
        configure_from(c)
        # the registry row store is process-wide; start this run's stage
        # record clean so readers don't see earlier runs' rounds interleaved
        default_registry().clear_rows("stage")
        timer = RoundTimer()
        logger = MetricsLogger(c.run_name, c.enable_wandb, config=c.to_dict())
        start_round = 0
        if c.resume_from:
            start_round = self.restore(c.resume_from)
            log.info("resumed from %s at round %d", c.resume_from, start_round)
        try:
            with profile_trace(c.profile_dir):
                self._train_rounds(start_round, timer, logger)
        finally:
            # drain the host round pipeline: no background thread may
            # outlive the run (speculative builds are dropped harmlessly —
            # every payload is a pure function of round_idx)
            self.close()
            if tracing_enabled():
                flush_all()
        timing = timer.summary()
        if self._stage_rows:
            from fedml_tpu.utils.metrics import round_stats

            timing["host_pipeline"] = round_stats(
                self._stage_rows, c.host_pipeline_depth)
        if c.async_rounds:
            # run_round returned un-synced device scalars, so the 'train'
            # phase timed DISPATCH only; only eval rounds (float(loss)) and
            # the final eval actually blocked. Wall-clock — and
            # rounds_per_sec, which divides by it — still ends on a real
            # sync, so those stay honest.
            timing["time/train_is_dispatch_only"] = True
        self.history["rounds_per_sec"] = timing["rounds_per_sec"]
        self.history["timing"] = timing
        self.history["placement"] = self.placement()
        self.metrics_logger = logger
        logger.close()
        return self.history

    def _eval_at(self, r: int) -> bool:
        """Whether to run the periodic eval after round ``r`` (self.variables
        holds the post-round-r model at that point)."""
        c = self.config
        return r % c.frequency_of_the_test == 0 or r == c.comm_round - 1

    def _train_rounds(self, start_round, timer, logger):
        c = self.config
        for r in range(start_round, c.comm_round):
            with timer.phase("train"):
                loss = self.run_round(r)
            timer.tick_round()
            if self._eval_at(r):
                with timer.phase("eval"):
                    m = self.evaluate_global()
                self.history["round"].append(r)
                self.history["Test/Acc"].append(m.get("acc"))
                self.history["Test/Loss"].append(m.get("loss"))
                logger.log(
                    {"Train/Loss": float(loss), "Test/Acc": m.get("acc"),
                     "Test/Loss": m.get("loss")}, r,
                )
            if c.checkpoint_dir and (
                (r + 1) % c.checkpoint_frequency == 0 or r == c.comm_round - 1
            ):
                import os

                self.save(os.path.join(c.checkpoint_dir, "latest.ckpt"), r + 1)


class CrossSiloFedAvgAPI(FedAvgAPI):
    """Cross-silo distributed paradigm: clients sharded over a device mesh,
    aggregation = weighted psum on ICI (replaces the reference's MPI
    ServerManager/ClientManager star, SURVEY.md §3.2).

    The sampled cohort size must be a multiple of the mesh size; each device
    trains cohort/mesh_size clients per round under vmap.
    """

    supports_device_data = False  # base gather path replaced by _dev_sharded
    handles_own_device_data = True  # _maybe_place_sharded honors the flag
    elastic_rounds_ok = True      # the psum path guards zero total weight

    @_setup_api_span
    def __init__(self, dataset, config, bundle=None, mesh=None, **kw):
        from fedml_tpu.parallel.mesh import client_mesh

        self.mesh = mesh or client_mesh()
        super().__init__(dataset, config, bundle, **kw)
        axis_sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        if "clients" not in axis_sizes:
            raise ValueError(f"mesh must have a 'clients' axis, got {self.mesh.axis_names}")
        n_clients_axis = axis_sizes["clients"]
        # The EFFECTIVE cohort (run_round clamps to the dataset's client count)
        # is what gets sharded — validate that, not the raw config value.
        cohort = min(config.client_num_per_round, dataset.num_clients)
        if cohort % n_clients_axis:
            raise ValueError(
                f"effective cohort size ({cohort}) must be a multiple of the "
                f"mesh 'clients' axis ({n_clients_axis})"
            )
        if config.cohort_vmap_width > 0:
            # the mesh round programs vmap each device's client block inside
            # shard_map; the chunked schedule applies to the simulation
            # paradigm only (where H5 / H11 put its optimum at 2, the width
            # the packed lanes now run at by themselves)
            log.warning(
                "cohort_vmap_width=%d ignored: the cross-silo mesh round "
                "always vmaps the per-device client block",
                config.cohort_vmap_width)
        # the mesh's own paths are resident, full-participation and static:
        # one plan for every round (only ``live`` varies), made here. With
        # neither, the round runs the host path the base class chose.
        self._packed_mesh = self._dev_sharded = self._static_plan = None
        everyone = np.arange(dataset.num_clients)
        if config.pack_lanes > 0:
            self._packed_mesh = self._mesh_packed_setup(cohort)
        if self._packed_mesh is not None:
            lanes = self._packed_mesh["plan"]
            self._static_plan = RoundPlan(
                PATH_MESH_PACKED, everyone, None, None, lanes,
                self._lane_slots(lanes, self.mesh.shape["clients"]))
        else:
            with setup_span(SPAN_SETUP_PLACE) as placing:
                self._dev_sharded = _placed(
                    placing, self._maybe_place_sharded(cohort))
            if self._dev_sharded is not None:
                self._static_plan = RoundPlan(
                    PATH_MESH_SHARDED, everyone, None, None, None,
                    int(dataset.train_x.shape[1]) * dataset.num_clients)
        if self._static_plan is not None:
            self._path = self._static_plan.path

    def _resident_train_x(self):
        if self._path == PATH_MESH_PACKED:
            return self._packed_mesh["data"][0]
        if self._path == PATH_MESH_SHARDED:
            return self._dev_sharded[0]
        return None

    def _round_plan(self, round_idx: int, record: bool = False) -> RoundPlan:
        if self._static_plan is None:
            return super()._round_plan(round_idx, record)
        plan = self._static_plan._replace(live=self._round_live(
            round_idx, self._static_plan.sampled, record))
        if plan.path == PATH_MESH_PACKED and self._client_active is not None:
            # the plan the program is handed, and so the one counted
            lanes = self._mesh_lanes()[0]
            plan = plan._replace(lanes=lanes, padded_slots=self._lane_slots(
                lanes, self.mesh.shape["clients"]))
        return plan

    def _mesh_packed_setup(self, cohort: int):
        """Resident placement + program for the packed mesh schedule
        (parallel/packed.py): per-device lanes, one psum tail. Returns None
        when packing doesn't apply (the resident-sharded path is next)."""
        from fedml_tpu.parallel.packed import (
            make_crosssilo_packed_round,
            plan_packing_mesh,
        )

        c, ds = self.config, self.dataset
        # ONE packability gate for both paradigms (_packing_hooks): the
        # mesh and sim packed paths must agree on which algorithms mirror
        # onto the lanes — a condition added to one must gate the other
        hooks = self._packing_hooks()
        if hooks is None:
            return None
        if cohort != ds.num_clients:
            log.warning(
                "pack_lanes=%d ignored on the mesh path: the packed "
                "schedule is resident-sharded and needs full participation "
                "(cohort %d != clients %d)", c.pack_lanes, cohort,
                ds.num_clients)
            return None
        D = self.mesh.shape["clients"]
        lanes_dev = max(1, -(-c.pack_lanes // D))
        # full participation -> ONE static plan, compiled once: no reason to
        # quantize the lane length at all
        out = plan_packing_mesh(
            np.asarray(ds.train_counts), c.batch_size, c.epochs, D, lanes_dev,
            t_quantum=1)
        if out is None:
            return None
        perm, plan = out
        x = self._eligible_device_train_x(shard_factor=D)
        if x is None:
            return None
        from fedml_tpu.parallel.mesh import shard_client_batch

        n_pad = int(ds.train_x.shape[1])
        from fedml_tpu.parallel.packed import plan_arrays_tuple

        with setup_span(SPAN_SETUP_PLACE) as placing:
            data = shard_client_batch(self.mesh, (
                x[perm], np.asarray(ds.train_y)[perm],
                np.asarray(ds.train_mask)[perm]))
            plan_arrays = shard_client_batch(self.mesh,
                                             plan_arrays_tuple(plan))
            _placed(placing, (data, plan_arrays))
        from fedml_tpu.obs import timed_build

        # fedscope compile telemetry: the packed mesh program is the most
        # expensive build in the tree (shard_map over vmapped lanes); its
        # shape key is the lane geometry that determines the XLA program
        def _build():
            rf = make_crosssilo_packed_round(
                self.bundle, self.task, n_pad, self.mesh, **hooks,
                **self._local_train_kwargs())
            # one DEVICE runs lanes_dev of the plan's lanes
            return self._tag_packed_program(rf, plan.n_lanes // D)

        round_fn = timed_build(
            "mesh_packed_round",
            (n_pad, D, lanes_dev, plan.shape_key), _build)
        return dict(perm=perm, plan=plan, data=data, plan_arrays=plan_arrays,
                    counts_perm=np.asarray(ds.train_counts, np.float32)[perm],
                    round_fn=round_fn)

    def _maybe_place_sharded(self, cohort: int):
        """Full-participation cross-silo (the standard silo deployment:
        every silo trains every round) keeps the whole dataset RESIDENT and
        SHARDED over the mesh — each device holds its clients' records in
        its own HBM, so rounds have zero host->device data movement (the
        in-mesh analogue of the simulation paradigm's device_data gather).
        Partial participation keeps the per-round host slice (a gather
        across shards would move data anyway)."""
        c = self.config
        ds = self.dataset
        if c.device_data == "off":
            return None
        if cohort != ds.num_clients:
            if c.device_data == "on":
                log.warning(
                    "device_data='on' ignored for cross-silo partial "
                    "participation (%d/%d clients); resident sharding needs "
                    "full participation", cohort, ds.num_clients)
            return None
        x = self._eligible_device_train_x(shard_factor=self.mesh.shape["clients"])
        if x is None:
            return None
        from fedml_tpu.parallel.mesh import shard_client_batch

        return shard_client_batch(
            self.mesh,
            (x, ds.train_y, ds.train_mask,
             np.asarray(ds.train_counts, np.float32)),
        )

    def _mesh_lanes(self) -> tuple:
        """(plan, placed arrays) of the packed mesh, with the Silo
        client-active mask applied as a STRUCTURAL lane freeze
        (parallel/packed.masked_plan) when set — re-placed over the mesh
        once per mask version, so exits cost one host->device plan upload,
        never a recompile (shapes unchanged)."""
        pm = self._packed_mesh
        if self._client_active is None:
            return pm["plan"], pm["plan_arrays"]
        cached = getattr(self, "_masked_mesh_plan", None)
        if cached is not None and cached[0] == self._client_active_version:
            return cached[1:]
        from fedml_tpu.parallel.mesh import shard_client_batch
        from fedml_tpu.parallel.packed import (masked_plan,
                                               mesh_member_active,
                                               plan_arrays_tuple)

        lanes = masked_plan(pm["plan"], mesh_member_active(
            pm["plan"], self.mesh.shape["clients"],
            np.asarray(self._client_active, np.float32)[pm["perm"]]))
        placed = shard_client_batch(self.mesh, plan_arrays_tuple(lanes))
        self._masked_mesh_plan = (self._client_active_version, lanes, placed)
        return lanes, placed

    def _run_mesh_packed_round(self, round_idx: int, plan: RoundPlan):
        from fedml_tpu.parallel.mesh import shard_client_batch

        pm = self._packed_mesh
        w = pm["counts_perm"]
        if plan.live is not None:
            # weight-zero failures and exits; an exit also gets the
            # structural lane freeze via _mesh_lanes
            w = w * np.asarray(plan.live, np.float32)[pm["perm"]]
        rk = round_key(self.root_key, round_idx)
        (w_dev,) = shard_client_batch(self.mesh, (w,))
        self.variables, self.server_state, train_loss = \
            self._traced_device_step(
                "packed_mesh", round_idx, pm["round_fn"],
                self.variables, self.server_state, *pm["data"], w_dev,
                jnp.asarray(pm["perm"], jnp.int32), rk,
                self._mesh_lanes()[1])
        return train_loss

    def _run_mesh_sharded_round(self, round_idx: int, plan: RoundPlan):
        cx, cy, cm, counts = self._dev_sharded
        if plan.live is not None:
            counts = counts * jnp.asarray(plan.live, jnp.float32)
        rk = round_key(self.root_key, round_idx)
        out = self._traced_device_step(
            "sharded", round_idx, self._round_step,
            self.variables, self.server_state, cx, cy, cm, counts, rk)
        # fedlens (plain mesh): full participation in dataset order, so the
        # logical ids are the plan's; failure/exit masks drop zero-weight
        # clients from the stash host-side
        self.variables, self.server_state, train_loss = self._lens_absorb(
            round_idx, out, plan.sampled,
            None if plan.live is None else np.asarray(plan.live) > 0)
        return train_loss

    _ROUND_RUNNERS = {
        **FedAvgAPI._ROUND_RUNNERS,
        PATH_MESH_PACKED: _run_mesh_packed_round,
        PATH_MESH_SHARDED: _run_mesh_sharded_round,
    }

    def _crosssilo_hooks_checked(self) -> dict:
        hooks = self.crosssilo_hooks()
        if hooks is None:
            if type(self).aggregate is not FedAvgAPI.aggregate:
                raise NotImplementedError(
                    f"{type(self).__name__} overrides aggregate(), which the in-mesh "
                    "psum path cannot honor; implement crosssilo_hooks() (see "
                    "make_crosssilo_round), override build_round_step, or use the "
                    "simulation paradigm (FedAvgAPI)."
                )
            hooks = {}
        return hooks

    def build_round_step(self):
        from fedml_tpu.parallel.crosssilo import make_crosssilo_round, place_round_inputs
        from fedml_tpu.parallel.mesh import replicated

        round_fn = make_crosssilo_round(self._local_train, self.mesh,
                                        lens=self._lens_armed,
                                        **self._crosssilo_hooks_checked())

        def round_step(variables, server_state, cx, cy, cm, counts, rng):
            from fedml_tpu.parallel.mesh import global_put

            keys = jax.random.split(rng, cx.shape[0])
            variables, cx, cy, cm, counts, keys = place_round_inputs(
                self.mesh, variables, cx, cy, cm, counts, keys
            )
            server_state = global_put(server_state, replicated(self.mesh))
            return round_fn(variables, server_state, cx, cy, cm, counts, keys,
                            global_put(rng, replicated(self.mesh)))

        return round_step
