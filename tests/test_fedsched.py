"""fedsched (ISSUE 13): profiler-scheduled cohorts + streaming aggregation.

Pins the two contracts the scheduled cross-device round path rests on:

1. **Scheduling** (data/sched.py): `uniform` is bit-identical to the
   pre-scheduler `sample_clients` draw; `speed`/`fair` are pure in
   (seed, round, snapshot); ids the profiler never saw — cold starts AND
   ids dropped at the `max_clients` cap — schedule as uniform cold-starts
   instead of raising (the ISSUE's dropped-id satellite).
2. **Streaming aggregation** (core/streaming.py + the chunked host round
   path + the edge StreamingFedAVGAggregator): deterministic mode is a
   pure function of the contribution SET (bit-identical across arrival
   orders; unchunked on the sim path, bit-identical to batch aggregation
   outright), fold-on-arrival tracks batch at the streaming tolerance
   (rtol 1e-6 / atol 1e-7, test_streaming_fedavg.py's pin), accumulator
   memory is O(1) in cohort size, and — under seeded chaos with
   deadline-closed rounds — no upload ever folds twice.
"""

import numpy as np
import pytest

import jax

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.rng import sample_clients
from fedml_tpu.core.streaming import StreamAccumulator
from fedml_tpu.data.crossdevice import make_synthetic_crossdevice
from fedml_tpu.data.sched import (SCHED_LAG, CohortScheduler,
                                  ProfileSnapshot, plan_cohort,
                                  snapshot_from_counts)
from fedml_tpu.models import create_model

RTOL, ATOL = 1e-6, 1e-7   # the streaming-paradigm tolerance (fold order)

N_CLIENTS = 240
COHORT = 12


def _snap(n=1000, fast_below=500, fast_ms=5.0, slow_ms=500.0):
    ids = np.arange(n, dtype=np.int64)
    ema = np.where(ids < fast_below, fast_ms, slow_ms).astype(np.float32)
    return ProfileSnapshot(ids=ids, ema_train_ms=ema,
                           participation=np.ones(n, np.int32))


# -- scheduling: policies, purity, the dropped-id contract ------------------

def test_uniform_policy_is_bit_identical_to_sample_clients():
    for r in (0, 3, 17):
        want = sample_clients(r, 1000, 20, seed=4)
        assert np.array_equal(plan_cohort(r, 1000, 20, 4, "uniform"), want)
        # a non-uniform policy with NO snapshot is the same cold-start draw
        assert np.array_equal(plan_cohort(r, 1000, 20, 4, "speed"), want)
    sched = CohortScheduler("uniform", 4, 1000, 20)
    assert not sched.wants_notify   # uniform never needs boundary snapshots
    assert np.array_equal(sched.sample(3), sample_clients(3, 1000, 20, 4))


def test_speed_policy_packs_fast_clients_from_the_snapshot():
    snap = _snap()
    plan = plan_cohort(3, 1000, 20, 0, "speed", snap)
    assert plan.shape == (20,) and len(np.unique(plan)) == 20
    assert (plan < 500).all()       # every pick is from the fast half
    # the plan is a subset of the round's OVERSAMPLED uniform pool — the
    # policy reranks a deterministic draw, it never invents candidates
    pool = sample_clients(3, 1000, 80, seed=0)
    assert np.isin(plan, pool).all()
    # pure: same (seed, round, snapshot) -> byte-identical plan
    assert np.array_equal(plan, plan_cohort(3, 1000, 20, 0, "speed", snap))


def test_fair_policy_reserves_least_participated_slots():
    n = 1000
    ids = np.arange(n, dtype=np.int64)
    # fast clients are also the MOST participated: pure speed would starve
    # the rest forever, the fairness reservation must not
    part = np.where(ids < 500, 100, 0).astype(np.int32)
    ema = np.where(ids < 500, 5.0, 500.0).astype(np.float32)
    snap = ProfileSnapshot(ids=ids, ema_train_ms=ema, participation=part)
    plan = plan_cohort(3, n, 20, 0, "fair", snap)
    assert plan.shape == (20,) and len(np.unique(plan)) == 20
    reserved = int((plan >= 500).sum())
    assert reserved >= max(1, round(0.25 * 20))   # the reservation held
    assert (plan < 500).sum() > 0                 # the rest packs fast


def test_dropped_and_unseen_ids_schedule_as_uniform_cold_starts():
    """The ISSUE satellite pin: candidates missing from the snapshot —
    cold starts, and ids the profiler dropped at its max_clients cap —
    rank at the pool's median EMA instead of raising or being starved."""
    # a snapshot covering almost nothing of a million-client population
    tiny = ProfileSnapshot(ids=np.array([3, 7], np.int64),
                           ema_train_ms=np.array([1.0, 2.0], np.float32),
                           participation=np.array([4, 5], np.int32))
    for policy in ("speed", "fair"):
        plan = plan_cohort(3, 1_000_000, 50, 0, policy, tiny)
        assert plan.shape == (50,) and plan.max() < 1_000_000
    # an EMPTY snapshot degrades to exactly the uniform draw
    empty = ProfileSnapshot(ids=np.empty(0, np.int64),
                            ema_train_ms=np.empty(0, np.float32),
                            participation=np.empty(0, np.int32))
    assert np.array_equal(plan_cohort(3, 1000, 20, 0, "speed", empty),
                          sample_clients(3, 1000, 20, seed=0))
    # integration: a REAL profiler whose cap dropped high ids produces a
    # snapshot the scheduler plans from without touching the dropped range
    from fedml_tpu.obs.profile import ClientProfiler

    prof = ClientProfiler(max_clients=64)
    prof.observe(np.arange(0, 200, 4), 0, train_ms=7.0)   # 16 kept, 34 drop
    assert prof.dropped == 34
    snap = prof.snapshot()
    assert snap.ids.max() < 64
    plan = plan_cohort(1, 1000, 20, 0, "speed", snap)
    assert plan.shape == (20,) and len(np.unique(plan)) == 20


def test_snapshot_from_counts_is_the_population_prior():
    counts = np.array([10, 40, 5, 80], np.int64)
    snap = snapshot_from_counts(counts, ms_per_record=2.5)
    assert snap.n_seen == 4
    np.testing.assert_allclose(snap.ema_train_ms, [25.0, 100.0, 12.5, 200.0])
    # the speed policy over a count prior packs the LIGHT clients
    big = snapshot_from_counts(np.arange(1, 1001, dtype=np.int64))
    plan = plan_cohort(2, 1000, 20, 0, "speed", big)
    pool = sample_clients(2, 1000, 80, seed=0)
    assert np.array_equal(plan, np.sort(plan))     # ascending, by contract
    assert np.isin(plan, pool).all()
    assert plan.mean() < np.asarray(pool).mean()   # lighter than the pool


def test_scheduler_ledger_and_static_snapshot_purity():
    sched = CohortScheduler("speed", 0, 1000, 20)
    sched.set_static_profile(_snap())
    assert not sched.wants_notify      # static mode needs no boundary feed
    p1 = sched.sample(9)
    # plans replay from the ledger even if the signal later changes
    sched._static = _snap(fast_below=10)
    assert np.array_equal(sched.sample(9), p1)
    # live mode: the plan for round r reads the snapshot at r - SCHED_LAG
    live = CohortScheduler("speed", 0, 1000, 20,
                           profile_source=lambda: None)
    assert live.wants_notify
    # no signal at all -> uniform cold-start (warned once, never raises)
    assert np.array_equal(live.sample(1), sample_clients(1, 1000, 20, 0))
    live._snaps.append((5, _snap()))
    early = live.sample(5 + SCHED_LAG - 1)   # snapshot not yet eligible
    assert np.array_equal(
        early, sample_clients(5 + SCHED_LAG - 1, 1000, 20, 0))
    eligible = live.sample(5 + SCHED_LAG)
    assert (eligible < 500).all()            # now scheduled by speed


# -- the streaming accumulator: order independence + O(1) memory ------------

def _fake_updates(n, shape=(6, 4), seed=0):
    rng = np.random.default_rng(seed)
    return [({"w": rng.standard_normal(shape).astype(np.float32),
              "b": rng.standard_normal(shape[1:]).astype(np.float32)},
             float(rng.integers(1, 50))) for _ in range(n)]


def _ref_mean(ups):
    acc = {k: np.zeros_like(v, dtype=np.float64)
           for k, v in ups[0][0].items()}
    tw = 0.0
    for tree, w in ups:
        for k in acc:
            acc[k] += np.asarray(tree[k], np.float64) * w
        tw += w
    return {k: (v / tw).astype(np.float32) for k, v in acc.items()}


def test_deterministic_fold_is_bit_identical_across_arrival_orders():
    ups = _fake_updates(16)
    template = ups[0][0]
    rng = np.random.default_rng(7)
    outs = []
    for _trial in range(4):
        order = rng.permutation(len(ups))
        acc = StreamAccumulator("deterministic")
        for i in order:
            acc.add(int(i), *ups[i])
        outs.append(acc.finalize(template))
        # held buffer bounded by the contribution count, drained at close
        assert acc.peak_held <= len(ups) and not acc._held
    for out in outs[1:]:
        for k in outs[0]:
            np.testing.assert_array_equal(outs[0][k], out[k])
    # ...and the pinned order is the canonical index-order f64 fold
    ref = _ref_mean(ups)
    for k in ref:
        np.testing.assert_array_equal(outs[0][k], ref[k])
    # in-order arrivals never hold anything
    acc = StreamAccumulator("deterministic")
    for i, (t, w) in enumerate(ups):
        acc.add(i, t, w)
    assert acc.peak_held == 1   # each contribution lands and folds at once


def test_arrival_fold_tracks_batch_at_streaming_tolerance():
    ups = _fake_updates(16, seed=3)
    acc = StreamAccumulator("arrival")
    for i in np.random.default_rng(1).permutation(len(ups)):
        acc.add(int(i), *ups[i])
    out = acc.finalize(ups[0][0])
    ref = _ref_mean(ups)
    for k in ref:
        np.testing.assert_allclose(out[k], ref[k], rtol=RTOL, atol=ATOL)


def test_accumulator_memory_is_o1_in_cohort_size():
    """The acceptance pin: the running accumulator holds ONE f64 model sum
    regardless of how many contributions folded through it."""
    sizes = {}
    for n in (4, 64, 256):
        acc = StreamAccumulator("arrival")
        for i, (t, w) in enumerate(_fake_updates(n, seed=n)):
            acc.add(i, t, w)
        assert acc.folded == n
        sizes[n] = acc.nbytes
    model_f64 = (6 * 4 + 4) * 8     # one f64 copy of the test model
    assert sizes[4] == sizes[64] == sizes[256] == model_f64


def test_zero_weight_contributions_and_rounds():
    ups = _fake_updates(3)
    acc = StreamAccumulator("deterministic")
    acc.add(0, ups[0][0], 0.0)      # failed client: exact no-op term
    acc.add(1, ups[1][0], 2.0)
    out = acc.finalize(ups[0][0])
    for k in out:
        np.testing.assert_array_equal(out[k],
                                      ups[1][0][k].astype(np.float32))
    # all-zero-weight round finalizes to None: the caller's elastic no-op
    acc = StreamAccumulator("deterministic")
    acc.add(0, ups[0][0], 0.0)
    assert acc.finalize(ups[0][0]) is None
    with pytest.raises(ValueError, match="deterministic|arrival"):
        StreamAccumulator("bogus")


# -- the sim paradigm: streamed chunked rounds vs the batch program ---------

@pytest.fixture(scope="module")
def ds():
    return make_synthetic_crossdevice(
        "fedsched-test", 16, 6, N_CLIENTS, batch_size=4, mean_records=9.0,
        max_records=21, seed=5)


def _run_sim(ds, rounds=3, **kw):
    cfg = FedConfig(
        model="lr", dataset="xdev", client_num_in_total=N_CLIENTS,
        client_num_per_round=COHORT, comm_round=rounds, batch_size=4,
        epochs=1, lr=0.1, seed=0, frequency_of_the_test=10_000, **kw)
    api = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num,
                                          input_shape=(16,)))
    try:
        losses = [float(api.run_round(r)) for r in range(1, rounds + 1)]
        leaves = [np.asarray(l) for l in jax.tree.leaves(api.variables)]
        stats = api.stream_stats
    finally:
        api.close()
    return losses, leaves, stats


def test_uniform_off_keeps_the_committed_round_plan(ds):
    """--cohort_policy uniform --stream_aggregate off samples EXACTLY the
    pre-scheduler sample_clients draw (the scheduler replaced the call
    site, not the arithmetic) and takes the batch path untouched."""
    cfg = FedConfig(model="lr", dataset="xdev",
                    client_num_in_total=N_CLIENTS,
                    client_num_per_round=COHORT, comm_round=2, batch_size=4,
                    epochs=1, lr=0.1, seed=0, frequency_of_the_test=10_000)
    api = FedAvgAPI(ds, cfg, create_model("lr", ds.class_num,
                                          input_shape=(16,)))
    try:
        for r in (1, 2, 9):
            plan = api._round_plan(r)
            assert np.array_equal(
                plan.sampled, sample_clients(r, N_CLIENTS, COHORT, seed=0))
            assert plan.path == "host"
        assert api._stream_mode() == "off"
    finally:
        api.close()


def test_streaming_deterministic_unchunked_is_bit_identical_to_batch(ds):
    l0, v0, s0 = _run_sim(ds)
    l1, v1, s1 = _run_sim(ds, stream_aggregate="deterministic")
    assert s0 is None and s1 is not None
    assert l0 == l1
    for a, b in zip(v0, v1):
        np.testing.assert_array_equal(a, b)


def test_streaming_chunked_parity_pipeline_and_o1_stats(ds):
    l0, v0, _ = _run_sim(ds)
    lc, vc, sc = _run_sim(ds, stream_aggregate="deterministic",
                          cohort_chunk=5)
    # chunked fold differs from one stacked sum only by f32 fold order
    np.testing.assert_allclose(lc, l0, rtol=RTOL, atol=ATOL)
    for a, b in zip(vc, v0):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)
    assert sc["chunks"] == -(-COHORT // 5) and sc["cohort"] == COHORT
    # pipelined chunks are bit-identical to serial chunks (purity of the
    # per-chunk inputs in (seed, round, chunk))
    lp, vp, _ = _run_sim(ds, stream_aggregate="deterministic",
                         cohort_chunk=5, host_pipeline_depth=2)
    assert lp == lc
    for a, b in zip(vp, vc):
        np.testing.assert_array_equal(a, b)
    # arrival mode on the sim path folds the same chunk order: identical
    la, va, _ = _run_sim(ds, stream_aggregate="arrival", cohort_chunk=5)
    assert la == lc
    # O(1) evidence: the accumulator footprint is one f32 model + scalars,
    # IDENTICAL whether the round streams 3 chunks or 1
    s_one = _run_sim(ds, stream_aggregate="deterministic")[2]
    assert sc["accumulator_bytes"] == s_one["accumulator_bytes"]
    model_bytes = sum(int(np.prod(np.shape(v))) * 4 for v in vc) + 8
    assert sc["accumulator_bytes"] == model_bytes


def test_streaming_packed_chunks_replay_the_canonical_program(ds):
    """pack_lanes > 0: streamed chunks ride the packed-lanes program with
    key_slice, so every client consumes the same per-round key as the
    whole-cohort program — results match the unchunked packed round at
    fold-order tolerance."""
    lp, vp, sp = _run_sim(ds, stream_aggregate="deterministic",
                          pack_lanes=2)
    lc, vc, sc = _run_sim(ds, stream_aggregate="deterministic",
                          pack_lanes=2, cohort_chunk=5)
    assert sp["packed_lanes"] == 2 and sc["packed_lanes"] == 2
    np.testing.assert_allclose(lc, lp, rtol=RTOL, atol=ATOL)
    for a, b in zip(vc, vp):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_streaming_with_failures_matches_batch_zero_weighting(ds):
    """Failed clients fold as zero-weight no-ops — same elastic semantics
    as the batch path, bit-identical unchunked."""
    l0, v0, _ = _run_sim(ds, failure_prob=0.3)
    l1, v1, _ = _run_sim(ds, failure_prob=0.3,
                         stream_aggregate="deterministic")
    assert l0 == l1
    for a, b in zip(v0, v1):
        np.testing.assert_array_equal(a, b)


def test_cohort_chunk_requires_streaming():
    with pytest.raises(ValueError, match="stream_aggregate"):
        FedConfig(model="lr", dataset="x", client_num_in_total=4,
                  client_num_per_round=2, comm_round=1, batch_size=4,
                  epochs=1, lr=0.1, seed=0, cohort_chunk=2)
    with pytest.raises(ValueError, match="cohort_policy"):
        FedConfig(model="lr", dataset="x", client_num_in_total=4,
                  client_num_per_round=2, comm_round=1, batch_size=4,
                  epochs=1, lr=0.1, seed=0, cohort_policy="fastest")


# -- the sequential streaming paradigm ---------------------------------------

def test_streaming_paradigm_fold_parity(ds):
    from fedml_tpu.algorithms.streaming_fedavg import StreamingFedAvgAPI

    def run(**kw):
        cfg = FedConfig(
            model="lr", dataset="xdev", client_num_in_total=N_CLIENTS,
            client_num_per_round=5, comm_round=2, batch_size=4, epochs=1,
            lr=0.1, seed=0, frequency_of_the_test=10_000, **kw)
        api = StreamingFedAvgAPI(ds, cfg, create_model(
            "lr", ds.class_num, input_shape=(16,)))
        try:
            losses = [float(api.run_round(r)) for r in range(1, 3)]
            leaves = [np.asarray(l) for l in jax.tree.leaves(api.variables)]
        finally:
            api.close()
        return losses, leaves, api.stream_stats

    l0, v0, _ = run()
    l1, v1, s1 = run(stream_aggregate="deterministic")
    np.testing.assert_allclose(l1, l0, rtol=RTOL, atol=ATOL)
    for a, b in zip(v1, v0):
        np.testing.assert_allclose(a, b, rtol=RTOL, atol=ATOL)
    assert s1["accumulator_bytes"] == sum(
        int(np.prod(np.shape(v))) * 4 for v in v1) + 8


# -- the edge: streaming server aggregation -----------------------------------

def _edge_cfg(**kw):
    base = dict(
        model="lr", dataset="synthetic_1_1", client_num_in_total=6,
        client_num_per_round=6, comm_round=2, batch_size=10, lr=0.1,
        epochs=1, frequency_of_the_test=1, seed=5, device_data="off",
    )
    base.update(kw)
    return FedConfig(**base)


def _edge_ds():
    from fedml_tpu.data import load_dataset

    return load_dataset("synthetic_1_1", num_clients=6, batch_size=10,
                        seed=5)


def _edge_hist(agg):
    return ([h["round"] for h in agg.test_history],
            [h["acc"] for h in agg.test_history],
            [h["loss"] for h in agg.test_history])


def test_edge_streaming_aggregator_order_independence_and_batch_parity():
    from fedml_tpu.distributed.fedavg_edge import (FedAVGAggregator,
                                                   StreamingFedAVGAggregator,
                                                   make_aggregator)

    bundle = create_model("lr", 6, input_shape=(10,))
    v0 = bundle.init(jax.random.PRNGKey(0))
    ups = []
    rng = np.random.default_rng(2)
    for i in range(6):
        t = jax.tree.map(
            lambda x: np.asarray(x)
            + rng.standard_normal(np.shape(x)).astype(np.float32), v0)
        ups.append((i, t, float(rng.integers(1, 40))))

    def streamed(order, mode="deterministic"):
        agg = StreamingFedAVGAggregator(
            v0, 6, _edge_cfg(stream_aggregate=mode))
        for i in order:
            agg.add_local_trained_result(*ups[i])
        return agg, jax.tree.leaves(agg.aggregate())

    in_order, a = streamed(range(6))
    shuffled, b = streamed([3, 0, 5, 1, 4, 2])
    assert shuffled.stream_peak_held >= 2        # hold-and-fold engaged...
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))  # ...yet
    # batch parity at the streaming tolerance (tree_weighted_mean's one
    # f32 stacked sum vs the f64 sequential fold)
    batch = FedAVGAggregator(v0, 6, _edge_cfg())
    for u in ups:
        batch.add_local_trained_result(*u)
    for x, y in zip(jax.tree.leaves(batch.aggregate()), a):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=RTOL, atol=ATOL)
    # a second same-round upload cannot fold twice: first wins, counted
    dup = StreamingFedAVGAggregator(
        v0, 6, _edge_cfg(stream_aggregate="deterministic"))
    dup.add_local_trained_result(*ups[0])
    dup.add_local_trained_result(*ups[0])
    assert dup.duplicate_uploads == 1 and dup._stream.folded == 1
    # zero-weight round: the elastic no-op
    zero = StreamingFedAVGAggregator(v0, 2, _edge_cfg(
        stream_aggregate="deterministic"))
    zero.add_local_trained_result(0, ups[0][1], 0.0)
    for x, y in zip(jax.tree.leaves(zero.aggregate()),
                    jax.tree.leaves(v0)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the config switch routes the edge launchers
    assert isinstance(make_aggregator(v0, 2, _edge_cfg()), FedAVGAggregator)
    assert isinstance(
        make_aggregator(v0, 2, _edge_cfg(stream_aggregate="arrival")),
        StreamingFedAVGAggregator)


def test_edge_streaming_chaos_run_is_bit_identical_to_clean_streaming():
    """Seeded chaos (drop/dup/reorder at the acceptance rates) over the
    STREAMING aggregator: the run completes, every upload folds exactly
    once, and — deterministic mode's whole point — retransmit storms and
    reordering cannot move the result a bit from the clean streaming run."""
    from fedml_tpu.distributed.fedavg_edge import (
        StreamingFedAVGAggregator, run_fedavg_edge)

    clean = run_fedavg_edge(
        _edge_ds(), _edge_cfg(stream_aggregate="deterministic"),
        worker_num=3)
    assert isinstance(clean, StreamingFedAVGAggregator)
    chaos = run_fedavg_edge(
        _edge_ds(), _edge_cfg(stream_aggregate="deterministic",
                              wire_reliable=True, chaos_drop=0.2,
                              chaos_dup=0.1, chaos_reorder=0.1,
                              chaos_seed=7),
        worker_num=3)
    assert _edge_hist(chaos) == _edge_hist(clean)
    for a, b in zip(jax.tree.leaves(clean.variables),
                    jax.tree.leaves(chaos.variables)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # exact-once under chaos: 2 rounds x 3 workers, no double folds
    assert chaos.uploads_accepted == 2 * 3
    assert chaos.duplicate_uploads == 0
    assert chaos.wire_stats["chaos/dropped"] > 0


def test_edge_streaming_stale_upload_after_deadline_close_never_folds():
    """The deadline pin, streaming edition (mirrors test_chaos.py's batch
    test): worker 1 misses the deadline, the round closes and aggregates
    the survivor's fold; worker 1's late round-0 upload arrives after the
    close and must be dropped as stale — never folded into round 1's
    fresh accumulator."""
    from fedml_tpu.comm import Message
    from fedml_tpu.core.rng import seed_everything
    from fedml_tpu.distributed.fedavg_edge import (
        MSG_ARG_KEY_GEN,
        MSG_ARG_KEY_MODEL_PARAMS,
        MSG_ARG_KEY_NUM_SAMPLES,
        MSG_ARG_KEY_ROUND,
        MSG_TYPE_C2S_SEND_MODEL,
        FedAvgEdgeServerManager,
        StreamingFedAVGAggregator,
        _edge_args,
    )

    ds = _edge_ds()
    cfg = _edge_cfg(straggler_deadline_sec=30.0,
                    frequency_of_the_test=10_000,
                    stream_aggregate="deterministic")

    class _Comm:
        def add_observer(self, o):
            pass

        def send_message(self, m):
            pass

        def inject_local(self, m):
            pass

        def supports_local_injection(self):
            return True

        def stop_receive_message(self):
            pass

    bundle = create_model("lr", ds.class_num,
                          input_shape=ds.train_x.shape[2:])
    root = seed_everything(cfg.seed)
    agg = StreamingFedAVGAggregator(bundle.init(root), 2, cfg, dataset=ds,
                                    bundle=bundle)
    server = FedAvgEdgeServerManager(_edge_args(cfg, ds), _Comm(), 0, 3, agg)
    server._assignment_map = server._assignments(0)
    server._broadcast_model(2, agg.get_global_model_params(),
                            server._assignment_map)

    def upload(worker, round_tag):
        m = Message(MSG_TYPE_C2S_SEND_MODEL, worker + 1, 0)
        m.add_params(MSG_ARG_KEY_ROUND, round_tag)
        m.add_params(MSG_ARG_KEY_GEN, server._bcast_gen)
        m.add_params(MSG_ARG_KEY_MODEL_PARAMS, bundle.init(root))
        m.add_params(MSG_ARG_KEY_NUM_SAMPLES, 10.0)
        return m

    server.handle_message_receive_model_from_client(upload(0, 0))
    assert agg.uploads_accepted == 1 and agg._stream.folded == 1
    deadline = Message(99, 0, 0)
    deadline.add_params(MSG_ARG_KEY_ROUND, 0)
    server.handle_round_deadline(deadline)
    assert server.round_idx == 1 and not server._alive[1]
    # the close finalized and re-armed the accumulator: fresh round state
    assert agg._stream.folded == 0
    # worker 1's retransmitted round-0 upload lands AFTER the close: the
    # manager drops it as stale BEFORE it can reach the fold
    server.handle_message_receive_model_from_client(upload(1, 0))
    assert server.stale_uploads == 1
    assert agg.uploads_accepted == 1
    assert agg._stream.folded == 0 and agg.duplicate_uploads == 0
    server._cancel_timer()
