"""Client-packing schedule (parallel/packed.py).

Pins the three claims the schedule makes:
1. each client's trajectory REPLAYS the canonical unbucketed local-train
   program bit-for-bit (same permutations, same batch keys, same steps);
2. the round aggregate equals the unpacked round's weighted mean (up to
   float summation order);
3. padding collapses to one-batch granularity: executed/real >= 90% on a
   heterogeneous cohort where the bucketed schedule wastes far more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.rng import round_key, seed_everything
from fedml_tpu.core.tasks import get_task
from fedml_tpu.data.synthetic import make_synthetic_classification
from fedml_tpu.models import create_model
from fedml_tpu.parallel.local import make_local_train_fn
from fedml_tpu.parallel.packed import (make_packed_cohort_train,
                                       plan_arrays_tuple, plan_packing)


def _ds(C=12, records=160, seed=9, bs=8):
    return make_synthetic_classification(
        "pack-t", (6,), 4, C, records_per_client=records,
        partition_method="hetero", partition_alpha=0.3, batch_size=bs,
        seed=seed,
    )


def _cfg(**kw):
    base = dict(model="lr", dataset="pack-t", client_num_in_total=12,
                client_num_per_round=12, comm_round=4, batch_size=8, lr=0.2,
                momentum=0.9, epochs=2, frequency_of_the_test=1, seed=13,
                device_data="on", bucket_quantum_batches=1)
    base.update(kw)
    return FedConfig(**base)


def test_plan_covers_every_client_exactly_once():
    counts = np.array([37, 5, 80, 16, 3, 64, 22, 9])
    plan = plan_packing(counts, batch_size=8, epochs=3, n_lanes=3)
    seen = {}
    for l in range(plan.n_lanes):
        for k in range(plan.k_max):
            if plan.member_valid[l, k]:
                pos = int(plan.member_pos[l, k])
                assert pos not in seen
                seen[pos] = (l, k)
                assert plan.steps_real[l, k] == -(-counts[pos] // 8)
    assert sorted(seen) == list(range(len(counts)))
    # executed steps account: live steps == sum of epochs*steps_real
    total = int(plan.live.sum())
    assert total == int(3 * np.ceil(counts / 8).sum())
    # each client resets once and emits once
    assert int(plan.reset.sum()) == len(counts)
    assert int(plan.emit.sum()) == len(counts)


def test_packed_single_lane_replays_local_train_bit_exact():
    """One lane, one client: acc_vars must equal count * local_train's
    result EXACTLY — the packed scan replays the canonical program."""
    ds = _ds()
    cfg = _cfg()
    bundle = create_model("lr", ds.class_num, input_shape=ds.train_x.shape[2:])
    task = get_task(ds.task, ds.class_num)
    root = seed_everything(cfg.seed)
    variables = bundle.init(root)
    n_pad = int(ds.train_x.shape[1])
    kwargs = dict(optimizer="sgd", lr=cfg.lr, momentum=cfg.momentum,
                  epochs=cfg.epochs, batch_size=cfg.batch_size)

    local_train = jax.jit(make_local_train_fn(bundle, task, **kwargs))
    rk = round_key(root, 0)
    cohort = ds.num_clients
    keys = jax.random.split(rk, cohort)

    for ci in (0, 5, 11):
        counts_all = np.asarray(ds.train_counts, np.float64)
        plan = plan_packing(counts_all[[ci]], cfg.batch_size, cfg.epochs,
                            n_lanes=1)
        packed = make_packed_cohort_train(
            bundle, task, n_pad, plan.shape_key, **kwargs)
        plan_arrays = tuple(jnp.asarray(a) for a in (
            plan.slot, plan.epoch, plan.sie, plan.reset, plan.emit, plan.live,
            plan.member_pos, plan.member_valid, plan.steps_real))
        w = np.float32(counts_all[ci])
        # sampled_rows maps cohort position 0 -> stack row ci; the packed
        # key for position 0 must be the key client ci consumes in the
        # cohort program, so pass a single-position rng stream via fold
        acc, acc_w, acc_loss, acc_tau, _extras = jax.jit(packed)(
            variables,
            jnp.asarray(ds.train_x), jnp.asarray(ds.train_y),
            jnp.asarray(ds.train_mask),
            jnp.asarray([ci], jnp.int32), jnp.asarray([w]), rk, plan_arrays)

        ref = local_train(variables, ds.train_x[ci], ds.train_y[ci],
                          ds.train_mask[ci], jnp.float32(w),
                          jax.random.split(rk, 1)[0])
        assert float(acc_w) == float(w)
        for a, v in zip(jax.tree.leaves(acc), jax.tree.leaves(ref.variables)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(v) * w)
        np.testing.assert_allclose(float(acc_loss), float(ref.train_loss) * w,
                                   rtol=1e-6)
        np.testing.assert_allclose(float(acc_tau), float(ref.tau) * w, rtol=0)


def test_packed_round_matches_unpacked_weighted_mean():
    """Full API rounds: pack_lanes vs the canonical unbucketed schedule
    (bucket_quantum_batches=0 pads every client to n_pad) must agree to
    float-sum tolerance, history included."""
    ds = _ds()
    packed_api = FedAvgAPI(ds, _cfg(pack_lanes=4))
    ref_api = FedAvgAPI(ds, _cfg(bucket_quantum_batches=0))
    hp = packed_api.train()
    hr = ref_api.train()
    np.testing.assert_allclose(hp["Test/Loss"], hr["Test/Loss"], rtol=2e-5)
    np.testing.assert_allclose(hp["Test/Acc"], hr["Test/Acc"], atol=1e-6)
    for a, b in zip(jax.tree.leaves(packed_api.variables),
                    jax.tree.leaves(ref_api.variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_packed_round_with_failures_matches_unpacked():
    ds = _ds()
    packed_api = FedAvgAPI(ds, _cfg(pack_lanes=3, failure_prob=0.3))
    ref_api = FedAvgAPI(ds, _cfg(bucket_quantum_batches=0, failure_prob=0.3))
    hp = packed_api.train()
    hr = ref_api.train()
    np.testing.assert_allclose(hp["Test/Loss"], hr["Test/Loss"], rtol=2e-5)


def test_packed_padding_efficiency():
    """The point of the schedule: executed/real slots >= 90% on a cohort
    whose unbucketed schedule wastes half its slots."""
    ds = _ds(C=16, records=240, bs=8)
    api = FedAvgAPI(ds, _cfg(client_num_in_total=16, client_num_per_round=16,
                             pack_lanes=4))
    real, padded = api.round_counts(0)
    n_pad = int(ds.train_x.shape[1])
    unpacked_padded = n_pad * 16
    assert padded < unpacked_padded, "packing must beat full padding"
    assert real / padded >= 0.90, (real, padded)


def test_packed_fedprox_carries_the_proximal_term():
    """FedProx is packing-eligible (prox is client-side, injected via
    _local_train_kwargs); the packed rounds must match the canonical
    unbucketed FedProx rounds — i.e. the mu term must NOT be dropped."""
    from fedml_tpu.algorithms.fedprox import FedProxAPI

    ds = _ds()
    mu = 0.5   # large mu so dropping it would visibly diverge
    packed = FedProxAPI(ds, _cfg(pack_lanes=4, fedprox_mu=mu))
    ref = FedProxAPI(ds, _cfg(bucket_quantum_batches=0, fedprox_mu=mu))
    plain = FedAvgAPI(ds, _cfg(bucket_quantum_batches=0))
    hp = packed.train()
    hr = ref.train()
    ha = plain.train()
    np.testing.assert_allclose(hp["Test/Loss"], hr["Test/Loss"], rtol=2e-5)
    # sanity: mu=0.5 separates FedProx from FedAvg, so the equality above
    # could not pass with the prox term silently dropped
    assert abs(hr["Test/Loss"][-1] - ha["Test/Loss"][-1]) > 1e-4


def test_packed_rides_adaptive_aggregation(caplog):
    """Packed-everywhere: FedOpt's server optimizer rides the packed
    schedule in the SIMULATION paradigm via the same hook contract the
    mesh path uses (server state threaded through the packed round) — the
    pre-refactor behavior (silent fall-back to the grouped schedule with a
    warning) is the regression this now guards against."""
    from fedml_tpu.algorithms.fedopt import FedOptAPI

    ds = _ds()
    api = FedOptAPI(ds, _cfg(pack_lanes=4, comm_round=2,
                             server_optimizer="adam", server_lr=0.05))
    h = api.train()
    assert len(h["Test/Loss"]) == 2
    assert api._packed_steps, "packed round program must engage"
    assert not any("pack_lanes" in r.message for r in caplog.records)
    # the server moments advanced through the packed round
    import jax

    leaves = jax.tree.leaves(api.server_state)
    assert leaves and any(np.abs(np.asarray(l)).max() > 0 for l in leaves)
    # and the packed run equals the plain (unpacked) run
    ref = FedOptAPI(ds, _cfg(pack_lanes=0, bucket_quantum_batches=0,
                             device_data="off", comm_round=2,
                             server_optimizer="adam", server_lr=0.05))
    hr = ref.train()
    np.testing.assert_allclose(h["Test/Loss"], hr["Test/Loss"], rtol=2e-5)


def test_crosssilo_packed_matches_sim(caplog):
    """Mesh packed schedule (8-device virtual mesh): per-device lanes, one
    psum tail — must agree with the canonical unbucketed simulation run."""
    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI

    ds = _ds(C=32, records=200, bs=8)
    # 32 clients / 8 devices = 4 per device, one packed lane each
    cfg = _cfg(client_num_in_total=32, client_num_per_round=32, pack_lanes=8)
    mesh_api = CrossSiloFedAvgAPI(ds, cfg)
    assert mesh_api._packed_mesh is not None, "packed mesh setup must engage"
    hm = mesh_api.train()
    ref = FedAvgAPI(ds, _cfg(client_num_in_total=32, client_num_per_round=32,
                             bucket_quantum_batches=0)).train()
    np.testing.assert_allclose(hm["Test/Loss"], ref["Test/Loss"], rtol=3e-5)
    np.testing.assert_allclose(hm["Test/Acc"], ref["Test/Acc"], atol=1e-6)

    # padding accounting: the packed mesh must clear 90% real/executed
    real, padded = mesh_api.round_counts(0)
    assert real / padded >= 0.85, (real, padded)


def test_crosssilo_packed_elastic_failures():
    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI

    ds = _ds(C=16, records=240, bs=8)
    cfg = _cfg(client_num_in_total=16, client_num_per_round=16, pack_lanes=16,
               failure_prob=0.3)
    api = CrossSiloFedAvgAPI(ds, cfg)
    assert api._packed_mesh is not None
    h = api.train()
    assert np.isfinite(h["Test/Loss"]).all()
    ref = FedAvgAPI(ds, _cfg(client_num_in_total=16, client_num_per_round=16,
                             bucket_quantum_batches=0, failure_prob=0.3)).train()
    np.testing.assert_allclose(h["Test/Loss"], ref["Test/Loss"], rtol=3e-5)


# -- the lane vmap width (parallel/packed.lane_vmap_width) --------------------

#: which side of ``lane_vmap_width``'s one test each registered model falls
#: on: "narrow" has a convolution kernel (a 4-D parameter leaf) with fewer
#: output channels than the MXU has columns, so its lanes advance
#: LANE_VMAP_WIDTH at a time; "wide" (dense-only models, the LMs, a ResNet
#: of 128 channels throughout) vmaps all of them together. zaya1_tiny's
#: narrow kernels are its compressed attention's depthwise convolutions at
#: the tiny width; at the published width (zaya1_8b) they are 1,280 wide
LANE_SIDE = {
    "cnn": "narrow", "cnn_dropout": "narrow", "deeplab_lite": "narrow",
    **{f"efficientnet-b{i}": "narrow" for i in range(8)},
    "granite4_h_micro": "wide", "granite4h_tiny": "wide",
    "kanana2_30b_a3b": "wide", "kanana2_tiny": "wide",
    "laguna_tiny": "wide", "laguna_xs2": "wide",
    "ling3_flash_vl": "wide", "ling3_tiny": "wide", "lr": "wide",
    "mobilenet": "narrow", "mobilenet_v3": "narrow",
    "nemotron3_super": "wide", "nemotron3_super_tiny": "wide",
    "resnet110": "narrow",
    "resnet18_gn": "narrow", "resnet20": "narrow", "resnet56": "narrow",
    "resnet56_nonorm": "narrow", "resnet56_w128": "wide",
    "resnet56_w64": "narrow", "rnn": "wide", "rnn_stackoverflow": "wide",
    "transformer": "wide", "transformer_nwp": "wide", "unet": "narrow",
    "vgg11": "narrow", "vgg16": "narrow", "vgg19": "narrow",
    "zaya1_8b": "wide", "zaya1_tiny": "narrow",
}


@pytest.mark.parametrize("name", sorted(LANE_SIDE))
def test_lane_width_is_read_from_the_model(name):
    """How many lanes advance together is the program's own reading of the
    model's kernel shapes (no flag): from the shapes of the registered
    model's init alone, nothing compiled."""
    from fedml_tpu.models import known_models
    from fedml_tpu.parallel.packed import LANE_VMAP_WIDTH, lane_vmap_width

    known = known_models()
    if name.startswith("efficientnet") and name not in known:
        pytest.skip("the optional efficientnet family is not importable")
    assert set(known) <= set(LANE_SIDE), "a new model: say which side"
    bundle = create_model(name, 10)
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    narrow = LANE_SIDE[name] == "narrow"
    assert lane_vmap_width(shapes, 8) == (LANE_VMAP_WIDTH if narrow else 8)
    # nothing to split: no more lanes than the width, or an odd count
    assert lane_vmap_width(shapes, LANE_VMAP_WIDTH) == LANE_VMAP_WIDTH
    assert lane_vmap_width(shapes, 3) == 3


def _lanes_ds(model, features=6):
    shape = {"lr": (features,), "cnn": (8, 8, 1)}.get(model, (8, 8, 3))
    return make_synthetic_classification(
        "pack-w", shape, 4, 10, records_per_client=12,
        partition_method="hetero", partition_alpha=0.5, batch_size=4, seed=3)


def _lanes_case(model, plan, hooks, lens):
    """A jitted packed cohort program with its arguments: 10 LDA clients of
    8x8 images (or 6 features for the dense model), batch 4, under ``plan``
    (a lane count: the cohort packed into that many lanes). ``cnn`` (two
    convolutions of 32 and 64 channels) is as narrow to ``lane_vmap_width``
    as ``resnet20`` and compiles in a fraction of its time; ``resnet20``
    stays where BatchNorm's ``batch_stats`` have to ride the lanes."""
    ds = _lanes_ds(model)
    bundle = create_model(model, ds.class_num,
                          input_shape=ds.train_x.shape[2:])
    task = get_task(ds.task, ds.class_num)
    counts = np.asarray(ds.train_counts, np.float64)
    if isinstance(plan, int):
        n_lanes = plan
        plan = plan_packing(counts, 4, 1, n_lanes=n_lanes)
        assert plan.n_lanes == n_lanes and plan.k_max > 1
    kw = dict(optimizer="sgd", lr=0.05, momentum=0.9, epochs=plan.epochs,
              batch_size=4, lens=lens)
    if hooks:
        def client_transform(gvars, stacked):
            out = dict(stacked)
            out["params"] = jax.tree.map(
                lambda g, v: v + 0.5 * (g[None] - v), gvars["params"],
                stacked["params"])
            return out

        def reduce_extras(gvars, res, w):
            return {"tau": jnp.sum(w * res.tau),
                    "sq": jnp.sum(w * res.train_loss ** 2)}

        kw.update(client_transform=client_transform,
                  reduce_extras=reduce_extras)
    args = (bundle.init(jax.random.PRNGKey(0)), jnp.asarray(ds.train_x),
            jnp.asarray(ds.train_y), jnp.asarray(ds.train_mask),
            jnp.arange(10, dtype=jnp.int32), jnp.asarray(counts, jnp.float32),
            jax.random.PRNGKey(5),
            tuple(jnp.asarray(a) for a in plan_arrays_tuple(plan)))

    def build():
        return jax.jit(make_packed_cohort_train(
            bundle, task, int(ds.train_x.shape[1]), plan.shape_key, **kw))

    return build, args


@pytest.mark.parametrize("model,n_lanes,hooks,lens,chunked", [
    ("cnn", 8, True, False, True),
    ("resnet20", 4, True, True, True),       # batch_stats through the chunks
    ("cnn", 8, False, True, True),
    ("cnn", 2, False, False, False),         # the flagship's own point
    ("cnn", 3, False, False, False),         # lanes that do not split evenly
    ("lr", 8, True, False, False),           # dense only: the wide matmul
], ids=["cnn-8-hooks", "resnet20-4-hooks-lens", "cnn-8-lens",
        "cnn-2", "cnn-3", "lr-8-hooks"])
def test_lanes_run_lane_vmap_width_at_a_time(monkeypatch, model, n_lanes,
                                             hooks, lens, chunked):
    """Narrow-conv models with more than LANE_VMAP_WIDTH lanes run them in
    chunks of that width, one ``lax.map`` around the lane scan, and compute
    what the single vmap computes (accumulators, weights, loss, tau, extras,
    the lens stacks) to float32 rounding: the grouped convolutions have 2
    groups, not L. Everything else lowers to the single vmap's program, to
    the byte."""
    from fedml_tpu.parallel import packed

    build, args = _lanes_case(model, n_lanes, hooks, lens)
    assert packed.lane_vmap_width(args[0], n_lanes) == (
        packed.LANE_VMAP_WIDTH if chunked else n_lanes)
    step = build()
    text = step.lower(*args).as_text()
    monkeypatch.setattr(packed, "LANE_VMAP_WIDTH", 1 << 30)   # one vmap
    assert packed.lane_vmap_width(args[0], n_lanes) == n_lanes
    whole = build()
    whole_text = whole.lower(*args).as_text()
    if not chunked:
        assert text == whole_text
        return
    assert (text.count("stablehlo.while")
            == whole_text.count("stablehlo.while") + 1)
    got, want = step(*args), whole(*args)
    assert len(got) == (6 if lens else 5)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=2e-6 * max(np.abs(b).max(), 1e-6))


# -- a chunk of lanes stops at its own last live step (ISSUE 36) ---------------

def _whole_plan(live, width, unroll=1):
    """A test double of ``chunk_bounds``: every chunk walks all T steps, as
    a concrete count, so the loop lowers to the static scan it was."""
    return np.full(live.shape[0] // width, live.shape[1])


def _bound_case(name):
    """-> (model, plan, width): a plan whose chunks end at different steps."""
    from fedml_tpu.parallel.packed import masked_plan

    ragged = np.array([12, 11, 9, 8, 7, 5, 4, 2, 0, 0], np.float64)
    counts = np.asarray(_lanes_ds("cnn").train_counts, np.float64)
    if name == "8-lanes-of-one":
        return "cnn", plan_packing(ragged, 4, 1, n_lanes=8), 2
    if name == "lanes-of-several":
        return "cnn", plan_packing(counts, 4, 1, n_lanes=4), 2
    if name == "lanes-of-several-2-epochs":
        return "cnn", plan_packing(counts, 4, 2, n_lanes=4), 2
    if name == "one-lane":
        return "cnn", plan_packing(counts, 4, 1, n_lanes=1,
                                   t_quantum=5), 1
    if name == "full-vmap":                        # dense: all lanes together
        return "lr", plan_packing(counts, 4, 1, n_lanes=5, t_quantum=4), 5
    plan = plan_packing(counts, 4, 1, n_lanes=4)
    assert (plan.member_valid.sum(axis=1) >= 2).all()
    active = np.ones((4, plan.k_max), np.float32)
    if name == "exits-mid-and-tail":
        last = int(plan.member_valid[2].sum()) - 1
        active[0, 0] = active[2, last] = 0.0       # lane 0's first, lane 2's last
    elif name == "all-dead-lane":
        active[1] = 0.0
    elif name == "all-dead-chunk":
        active[2:] = 0.0
    else:
        raise KeyError(name)
    # batch_stats frozen on a dead step: the one case BatchNorm has to see
    return ("resnet20" if name == "exits-mid-and-tail" else "cnn",
            masked_plan(plan, active), 2)


BOUND_CASES = ["8-lanes-of-one", "lanes-of-several",
               "lanes-of-several-2-epochs", "one-lane", "full-vmap",
               "exits-mid-and-tail", "all-dead-lane", "all-dead-chunk"]


@pytest.mark.parametrize("case", BOUND_CASES)
def test_bounded_lane_loop_is_the_whole_scan_bit_for_bit(monkeypatch, case):
    """The lane loop ends at a bound that arrives as data (``chunk_bounds``
    of the lanes vmapped together): every accumulator, weight, loss, tau,
    extra and lens stack equals, bit for bit, what the same program computes
    when it walks all T steps (a static scan: the parent's program), and the
    steps it walks are the plan's count, not chunks x T."""
    from fedml_tpu.parallel import packed

    model, plan, width = _bound_case(case)
    walked = []
    batch_step = packed.make_batch_sgd_step

    def counting(*a, **kw):
        step = batch_step(*a, **kw)

        def counted(*args):
            jax.debug.callback(lambda: walked.append(1))
            return step(*args)

        return counted

    monkeypatch.setattr(packed, "make_batch_sgd_step", counting)
    build, args = _lanes_case(model, plan, hooks=True, lens=True)
    assert packed.lane_vmap_width(args[0], plan.n_lanes) == width
    bounds = packed.chunk_bounds(plan.live, width)
    assert 0 < bounds.sum() < len(bounds) * plan.T, "no chunk ends early"
    assert plan.executed_slots(width) == width * bounds.sum()

    got = jax.block_until_ready(build()(*args))
    jax.effects_barrier()
    assert len(walked) == bounds.sum()
    del walked[:]
    monkeypatch.setattr(packed, "chunk_bounds", _whole_plan)
    whole = build()
    assert "stablehlo.while" in whole.lower(*args).as_text()
    want = jax.block_until_ready(whole(*args))
    jax.effects_barrier()
    assert len(walked) == len(bounds) * plan.T
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(got[1]) > 0 and np.isfinite(float(got[2]))


def test_chunk_bounds_reads_the_last_live_step_not_the_live_count():
    """One definition for the plan's count (NumPy) and the program (JAX):
    a dead span in the middle of a lane is walked, a chunk's bound is its
    longest lane's, a lane with no live step has bound 0, and ``unroll``
    rounds a bound up to whole blocks."""
    from fedml_tpu.parallel.packed import chunk_bounds

    live = np.zeros((6, 8), np.float32)
    live[0, :5] = 1
    live[1, :2] = 1
    live[2, [0, 1, 5]] = 1                      # dead in the middle
    live[3, :3] = 1
    for xp in (np, jnp):
        assert chunk_bounds(xp.asarray(live), 1).tolist() == [5, 2, 6, 3, 0, 0]
        assert chunk_bounds(xp.asarray(live), 2).tolist() == [5, 6, 0]
        assert chunk_bounds(xp.asarray(live), 6).tolist() == [6]
        assert chunk_bounds(xp.asarray(live), 2, unroll=4).tolist() == [8, 8, 0]
    assert jax.jit(lambda a: chunk_bounds(a, 3))(live).tolist() == [6, 3]


@pytest.mark.parametrize("unroll", [1, 3])
@pytest.mark.parametrize("pack_lanes,width", [(8, 2), (2, 2), (1, 1)],
                         ids=["chunked", "one-vmap", "one-lane"])
def test_round_counts_reports_the_steps_the_chunks_walk(pack_lanes, width,
                                                        unroll):
    """``round_counts``' padded slots are the sum over the chunks of lanes of
    width x the chunk's bound (``scan_unroll`` rounds a bound up to whole
    blocks), from the plan the program is handed: the masked one under
    ``set_client_active``."""
    ds = _lanes_ds("resnet20")
    api = FedAvgAPI(ds, FedConfig(
        model="resnet20", dataset="pack-w", client_num_in_total=10,
        client_num_per_round=10, comm_round=1, batch_size=4, lr=0.05,
        epochs=1, seed=1, device_data="on", bucket_quantum_batches=20,
        pack_lanes=pack_lanes, scan_unroll=unroll,
        frequency_of_the_test=10_000))

    def by_hand(live):
        last = [max((t + 1 for t in range(live.shape[1]) if lane[t] > 0),
                    default=0) for lane in live]
        return sum(width * -(-max(last[i:i + width]) // unroll) * unroll
                   for i in range(0, len(last), width)) * 4

    try:
        plan = api._round_plan(0)
        assert plan.lanes.n_lanes == pack_lanes and plan.lanes.T % 5 == 0
        real, padded = api.round_counts(0)
        assert padded == plan.padded_slots == by_hand(plan.lanes.live)
        assert real == int(np.asarray(ds.train_counts).sum()) <= padded
        if unroll == 1:
            assert padded < pack_lanes * plan.lanes.T * 4
        # every lane's last client exits: every chunk ends earlier
        last = (plan.lanes.member_valid.sum(axis=1) - 1).astype(int)
        gone = plan.lanes.member_pos[np.arange(pack_lanes), last]
        active = np.ones(10, np.float32)
        active[plan.sampled[gone]] = 0.0
        api.set_client_active(active)
        masked = api._round_plan(0)
        assert masked.lanes.live.sum() < plan.lanes.live.sum()
        assert api.round_counts(0)[1] == by_hand(masked.lanes.live)
        if unroll == 1:
            assert api.round_counts(0)[1] < padded
    finally:
        api.close()


def test_plan_span_says_how_many_steps_the_round_walked(tmp_path):
    """The ``fedml/round/plan`` span of a packed round carries
    ``steps_planned`` (chunks x T), ``steps_run`` (the chunks' bounds) and
    ``tree_pass_steps`` (the reset and emit flags on the walked steps)."""
    import glob

    from jax.profiler import ProfileData

    from fedml_tpu.obs import tracer
    from fedml_tpu.parallel.packed import chunk_bounds

    ds = _lanes_ds("cnn")
    api = FedAvgAPI(ds, FedConfig(
        model="cnn", dataset="pack-w", client_num_in_total=10,
        client_num_per_round=10, comm_round=1, batch_size=4, lr=0.05,
        epochs=1, seed=1, device_data="on", bucket_quantum_batches=16,
        pack_lanes=4, frequency_of_the_test=10_000, async_rounds=True))
    try:
        with jax.profiler.trace(str(tmp_path / "prof")):
            jax.block_until_ready(api.run_round(0))
        lanes = api._round_plan(0).lanes
    finally:
        api.close()
    (path,) = glob.glob(str(tmp_path / "prof" / "**" / "*.xplane.pb"),
                        recursive=True)
    plans = [dict(ev.stats) for pl in ProfileData.from_file(path).planes
             for ln in pl.lines for ev in ln.events
             if ev.name == tracer.SPAN_PLAN]
    bounds = chunk_bounds(lanes.live, 2)
    assert bounds.sum() < 2 * lanes.T
    # 10 clients: each resets once and emits once, and every flag lies under
    # its chunk's bound (lanes 2 at a time), on a step the program walks
    walked = np.arange(lanes.T) < np.repeat(bounds, 2)[:, None]
    assert ((lanes.reset > 0) <= walked).all()
    assert ((lanes.emit > 0) <= walked).all()
    assert lanes.tree_pass_steps(2) == 20
    # a bound cut short leaves the flags past it out of the count
    assert lanes._replace(live=lanes.live * (np.arange(lanes.T) < 2)
                          ).tree_pass_steps(2) == int(
        (lanes.reset[:, :2] > 0).sum() + (lanes.emit[:, :2] > 0).sum())
    assert {"round": 0, "steps_planned": 2 * lanes.T,
            "steps_run": int(bounds.sum()),
            "tree_pass_steps": 20} in plans


# -- a one-lane round resets and emits under a branch (ISSUE 42) ---------------

def _zoo_hooks():
    """FedNova's ``reduce_extras`` and the robust clip's ``client_transform``,
    from the algorithms themselves (tests/test_packed_zoo.py rides them on
    the mesh)."""
    from fedml_tpu.algorithms.fednova import FedNovaAPI
    from fedml_tpu.algorithms.robust import FedAvgRobustAPI

    ds = _lanes_ds("lr")
    kw = dict(model="lr", dataset="pack-w", client_num_in_total=10,
              client_num_per_round=10, comm_round=1, batch_size=4, lr=0.05,
              momentum=0.9, seed=1, frequency_of_the_test=10_000)
    hooks = {}
    for cls, extra, name in ((FedNovaAPI, {}, "reduce_extras"),
                             (FedAvgRobustAPI, {"norm_bound": 0.05},
                              "client_transform")):
        api = cls(ds, FedConfig(**kw, **extra))
        try:
            hooks[name] = api.crosssilo_hooks()[name]
        finally:
            api.close()
    return hooks


#: name -> (model, the lane's clients (rows of ``_lanes_ds``: counts 15 8 12
#: 12 16 15 13 9 12 8, batch 4), make_lane_train's keywords, which of the
#: lane's members stay active)
ONE_LANE_CASES = {
    "3-unequal-clients": ("lr", (1, 3, 4), {}, None),
    "2-epochs": ("lr", (1, 3, 4), {"epochs": 2}, None),
    "last-partial-batch": ("lr", (7, 6), {}, None),
    "unroll-2-padded-tail": ("lr", (1, 3, 4), {"scan_unroll": 2}, None),
    "dead-client-mid-lane": ("lr", (1, 3, 4), {}, (1.0, 0.0, 1.0)),
    "momentum": ("lr", (1, 3, 4), {"momentum": 0.9}, None),
    "batch-stats": ("resnet20", (1, 7), {"momentum": 0.9}, None),
    "zoo-hooks": ("lr", (1, 3, 4), {"momentum": 0.9, "hooks": True}, None),
    "lens": ("lr", (1, 3, 4), {"lens": True}, None),
    # 128 features: a [128, 4] kernel, which the TPU keeps column-major and
    # the branches are told so (parallel/packed._on_flag)
    "column-major-leaf": ("lr", (1, 3, 4), {"momentum": 0.9, "features": 128}, None),
}


def _one_lane_case(name):
    """-> (lane_train, its arguments but ``branch``, plan): ONE lane of
    :func:`make_lane_train`, called as ``make_lanes_train`` calls it at
    ``L == 1`` (no lane axis), with the arguments
    ``make_packed_cohort_train``'s prologue would hand it."""
    from fedml_tpu.parallel.packed import (chunk_bounds, make_lane_train,
                                           masked_plan)

    model, rows, kw, active = ONE_LANE_CASES[name]
    kw, rows = dict(kw), np.asarray(rows)
    ds = _lanes_ds(model, kw.pop("features", 6))
    counts = np.asarray(ds.train_counts, np.int64)
    assert len(set(counts[rows])) == len(rows) and counts[rows].min() > 0
    if kw.pop("hooks", False):
        kw.update(_zoo_hooks())
    epochs, unroll = kw.get("epochs", 1), kw.get("scan_unroll", 1)
    plan = plan_packing(counts[rows].astype(np.float64), 4, epochs, n_lanes=1)
    if active is not None:
        plan = masked_plan(plan, np.asarray([active], np.float32))
    bundle = create_model(model, ds.class_num,
                          input_shape=ds.train_x.shape[2:])
    n_pad = int(ds.train_x.shape[1])
    lane_train = make_lane_train(
        bundle, get_task(ds.task, ds.class_num), n_pad, optimizer="sgd",
        lr=0.05, batch_size=4, **kw)
    tx, ty, tm = (jnp.asarray(a) for a in
                  (ds.train_x, ds.train_y, ds.train_mask))
    C = tx.shape[0]
    pos = plan.member_pos[0]
    args = (bundle.init(jax.random.PRNGKey(0)),
            tx.reshape((C * n_pad,) + tx.shape[2:]),
            ty.reshape((C * n_pad,) + ty.shape[2:]), tm.reshape(C * n_pad),
            tm, jnp.asarray(rows[pos], jnp.int32),
            jax.random.split(jax.random.PRNGKey(5), len(rows))[pos],
            jnp.asarray(counts[rows][pos] * plan.member_valid[0], jnp.float32),
            *(jnp.asarray(a[0]) for a in (
                plan.steps_real, plan.slot, plan.epoch, plan.sie, plan.reset,
                plan.emit, plan.live)),
            jnp.asarray(chunk_bounds(plan.live, 1, unroll)[0]))
    return lane_train, args, plan


@pytest.mark.parametrize("case", list(ONE_LANE_CASES))
def test_one_lane_branch_is_the_select_form_bit_for_bit(case):
    """A lane with no lane axis resets and emits under ``lax.cond`` on the
    step's own flag (``branch``); the select form of the SAME lane program
    makes both passes on every step. Every output leaf is equal, bit for
    bit: sums, weight, loss, tau, the hooks' extras, the lens stacks.
    (``np.array_equal`` takes ``-0.0`` for ``+0.0``: the one difference the
    forms may have, since ``a + 0 * v`` turns a ``-0.0`` sum into ``+0.0``.)
    The cases: the shapes of plan and the carries a wrong reset or a
    forgotten accumulator would show in."""
    import functools

    lane_train, args, plan = _one_lane_case(case)
    _, _, kw, active = ONE_LANE_CASES[case]
    if case == "batch-stats":
        assert set(args[0]) == {"params", "batch_stats"}
    if case == "column-major-leaf":
        from fedml_tpu.parallel.packed import _kept_transposed

        assert sorted((v.shape, _kept_transposed(v.shape))
                      for v in jax.tree.leaves(args[0])) == [
            ((4,), False), ((128, 4), True)]
    if case == "unroll-2-padded-tail":
        assert plan.T % 2 == 1
    if case == "last-partial-batch":
        assert (np.asarray(args[7]) % 4 > 0).all()
    if active is not None:
        assert plan.live[0, 0] and plan.live[0, -1] and not plan.live[0].all()
        assert plan.tree_pass_steps(1) == 4
    else:
        assert plan.tree_pass_steps(1) == 2 * plan.k_max < 2 * plan.T
    # LLVM at -O0: the CPU backend otherwise contracts ``a * b + c`` into a
    # fused multiply-add where a fusion's shape invites it, not alike in two
    # programs (BatchNorm's running mean read 1 ulp apart); with no
    # contraction the arithmetic that is written is the arithmetic that runs
    got, want = (jax.block_until_ready(
        jax.jit(functools.partial(lane_train, branch=b)).lower(*args).compile(
            compiler_options={"xla_backend_optimization_level": 0})(*args))
        for b in (True, False))
    assert len(got) == (6 if kw.get("lens") else 5)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and np.isfinite(a).all()
        assert np.array_equal(a, b)
    assert float(got[1]) > 0 and float(got[2]) > 0
    if kw.get("hooks"):
        assert float(got[4]["na"]) > 0
        assert any(np.abs(np.asarray(v)).max() > 0
                   for v in jax.tree.leaves(got[4]["pd"]))


def _step_loop(jaxpr):
    """-> (equations, ``cond`` equations) inside the body of the program's
    largest ``while``: the lane loop (``_walk_steps``), counted through
    every nested jaxpr."""
    from jax.extend import core as jex_core

    def subjaxprs(eqn):
        for v in eqn.params.values():
            for x in (v if isinstance(v, (tuple, list)) else (v,)):
                if isinstance(x, jex_core.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jex_core.Jaxpr):
                    yield x

    def count(j):
        n = c = 0
        for eqn in j.eqns:
            n, c = n + 1, c + (eqn.primitive.name == "cond")
            for sub in subjaxprs(eqn):
                dn, dc = count(sub)
                n, c = n + dn, c + dc
        return n, c

    def whiles(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "while":
                yield count(eqn.params["body_jaxpr"].jaxpr)
            for sub in subjaxprs(eqn):
                yield from whiles(sub)

    return max(whiles(jaxpr))


@pytest.mark.parametrize("n_lanes", [1, 2, 8])
def test_lanes_under_vmap_trace_no_branch(monkeypatch, n_lanes):
    """``make_lanes_train`` branches only where the lanes have no lane axis
    (``L == 1``: two ``cond`` in the loop's body, the reset's and the
    emit's). Lanes under ``vmap`` (2 together; 8, two at a time) trace the
    select form and nothing else: no ``cond`` in the body and the select
    form's count of equations. The count is a witness: a branch under
    ``vmap`` has a batched predicate, is lowered to both sides and a select
    (no ``cond`` left to see), and counts more equations."""
    from fedml_tpu.parallel import packed

    make_lane_train = packed.make_lane_train

    def forced(value):
        def make(*a, **kw):
            lane = make_lane_train(*a, **kw)
            return lambda *args, branch=False: lane(*args, branch=value)
        return make

    def body():
        build, args = _lanes_case("cnn", n_lanes, hooks=True, lens=True)
        return _step_loop(jax.make_jaxpr(build())(*args).jaxpr)

    as_built = body()
    monkeypatch.setattr(packed, "make_lane_train", forced(False))
    select = body()
    monkeypatch.setattr(packed, "make_lane_train", forced(True))
    branch = body()
    assert select[1] == 0
    if n_lanes == 1:
        assert as_built == branch and branch[1] == 2
        assert branch[0] != select[0]
    else:
        assert as_built == select
        assert branch[1] == 0 and branch[0] > select[0]


def test_crosssilo_one_lane_a_device_branches_and_matches_sim(monkeypatch):
    """The mesh reaches the same ``make_lanes_train``: a device that holds
    one lane takes the branch under ``shard_map`` (each device on its own
    flags; no collective is inside the loop), and the rounds agree with the
    canonical unbucketed simulation run."""
    from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI
    from fedml_tpu.parallel import packed
    from fedml_tpu.parallel.mesh import client_mesh

    traced = []
    make_lane_train = packed.make_lane_train

    def spy(*a, **kw):
        lane = make_lane_train(*a, **kw)

        def lane_train(*args, branch=False):
            traced.append(branch)
            return lane(*args, branch=branch)
        return lane_train

    monkeypatch.setattr(packed, "make_lane_train", spy)
    ds = _ds(C=8, records=200, bs=8)
    kw = dict(client_num_in_total=8, client_num_per_round=8)
    mesh_api = CrossSiloFedAvgAPI(ds, _cfg(pack_lanes=2, **kw),
                                  mesh=client_mesh(2))
    assert mesh_api._packed_mesh["plan"].shape_key[:2] == (2, 4)
    hm = mesh_api.train()
    assert traced and all(traced)
    ref = FedAvgAPI(ds, _cfg(bucket_quantum_batches=0, **kw)).train()
    np.testing.assert_allclose(hm["Test/Loss"], ref["Test/Loss"], rtol=3e-5)
    np.testing.assert_allclose(hm["Test/Acc"], ref["Test/Acc"], atol=1e-6)


# -- the benchmark cells' schedules, without a chip ----------------------------

def _cell_plans(name):
    """-> (cell's batch size, [(plan, live counts) of round indices 1 and 2],
    lane width): the lane plans ``FedAvgAPI._packed_plan`` makes for the
    cell's cohorts, from the cell's own files and generator."""
    import json
    import pathlib

    from benchmarks.traffic import cifar_like_lda, token_clients
    from fedml_tpu.core.rng import sample_clients
    from fedml_tpu.parallel.packed import lane_vmap_width

    root = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
    cell = json.loads((root / "workloads" / f"{name}.json").read_text())
    config = json.loads(
        (root / "configs" / f"{cell['config']}.json").read_text())
    if "train_records" in config["data"]:
        counts = np.array([len(y) for y in
                           cifar_like_lda.client_labels(config, cell)])
    else:
        counts = token_clients.client_counts(config, cell)
    fed, recipe = cell["fed_config"], config["recipe"]
    rounds = range(cell["rounds"]["first"],
                   cell["rounds"]["first"] + cell["rounds"]["cycle"])
    plans = []
    for r in rounds:
        sampled = sample_clients(r, cell["clients"],
                                 fed["client_num_per_round"],
                                 cell["sampling_seed"])
        plans.append((plan_packing(
            counts[sampled].astype(np.float64), recipe["batch_size"],
            recipe["epochs"], fed["pack_lanes"],
            t_quantum=max(1, FedConfig().bucket_quantum_batches // 4)),
            int(counts[sampled].sum())))
    bundle = create_model(
        config["model"]["program_name"], 10,
        input_shape=tuple(config["data"].get("input_shape", ())) or None)
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    return (recipe["batch_size"], plans,
            lane_vmap_width(shapes, plans[0][0].n_lanes))


def test_flagship_cells_chunks_stop_at_their_own_last_live_step():
    """``resnet56_sim_c8``: 8 lanes of one client, two at a time; LPT emits
    them in descending order of load, so neighbours share a chunk, and the
    four chunks walk 104 and 107 steps of the 128 the plan's shape has."""
    from fedml_tpu.parallel.packed import chunk_bounds

    batch, plans, width = _cell_plans("resnet56_sim_c8")
    assert width == 2 and [n for _, n in plans] == [12_680, 12_770]
    want = [((31, 29, 26, 26, 26, 25, 21, 18), (31, 26, 26, 21), 13_312),
            ((31, 29, 29, 28, 25, 25, 22, 15), (31, 29, 25, 22), 13_696)]
    for (plan, _), (loads, bounds, padded) in zip(plans, want):
        assert plan.shape_key == (8, 1, 32, 1)
        assert tuple(plan.live.sum(axis=1).astype(int)) == loads
        assert tuple(chunk_bounds(plan.live, width)) == bounds
        assert plan.executed_slots(width) * batch == padded


@pytest.mark.parametrize("cell", ["kanana2_sim_c2", "ling3_sim_c2",
                                  "laguna_sim_c2"])
def test_lm_cells_one_lane_walks_every_step(cell):
    """The LM cells bypass the bound: one lane whose 8 steps are all live in
    both round indices (their padding is half of a last batch, not a step)."""
    from fedml_tpu.parallel.packed import chunk_bounds

    _, plans, width = _cell_plans(cell)
    assert width == 1
    for plan, _ in plans:
        assert plan.shape_key == (1, 2, 8, 1)
        assert chunk_bounds(plan.live, width).tolist() == [plan.T] == [8]
