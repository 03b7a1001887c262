"""Which program runs a round is decided once, and round_counts reports the
plan run_round executes (ISSUE 28).

An API object chooses its round path at construction
(``FedAvgAPI._choose_path``; the mesh overrides it with its static plan);
``_round_plan`` returns one ``RoundPlan`` record a round — the cohort, the
path and the slots that path's program executes — ``_run_round_inner``
dispatches on it and ``round_counts`` returns its ``padded_slots``. The
second test is the guard round_counts' docstring always promised: the slots
it reports are the slots the device program was handed.
"""

import logging

import numpy as np
import pytest

from fedml_tpu.algorithms.fedavg import CrossSiloFedAvgAPI, FedAvgAPI
from fedml_tpu.core.config import FedConfig
from fedml_tpu.data.synthetic import make_synthetic_classification
from fedml_tpu.models import create_model

CLIENTS, BATCH = 4, 4


@pytest.fixture(scope="module")
def ds():
    # ragged on purpose: small cohorts trim to a bucket, lanes pack unevenly
    return make_synthetic_classification(
        "rp", (6,), 3, CLIENTS, records_per_client=24,
        partition_method="hetero", partition_alpha=0.3, batch_size=BATCH,
        seed=3)


def _cfg(**kw):
    base = dict(model="lr", dataset="rp", client_num_in_total=CLIENTS,
                client_num_per_round=CLIENTS, comm_round=2, batch_size=BATCH,
                epochs=1, lr=0.1, seed=0, frequency_of_the_test=10_000,
                bucket_quantum_batches=1)
    base.update(kw)
    return FedConfig(**base)


class _RewiredTrainer(FedAvgAPI):
    """An algorithm whose local trainer the lane builder cannot mirror."""

    def build_local_train(self):
        return super().build_local_train()


def _mesh(n):
    from fedml_tpu.parallel.mesh import client_mesh

    return dict(mesh=client_mesh(n))


RESIDENT = dict(device_data="on")
HOST = dict(device_data="off")

CASES = {
    "resident-lanes": (FedAvgAPI, dict(RESIDENT, pack_lanes=2), "packed"),
    "resident": (FedAvgAPI, dict(RESIDENT), "gather"),
    "resident-lanes-rewired": (_RewiredTrainer, dict(RESIDENT, pack_lanes=2),
                               "gather"),
    "host-stream": (FedAvgAPI, dict(HOST, stream_aggregate="deterministic"),
                    "stream"),
    "host-stream-lanes": (FedAvgAPI, dict(HOST, pack_lanes=2,
                                          stream_aggregate="deterministic"),
                          "stream_packed"),
    "host-pipeline": (FedAvgAPI, dict(HOST, host_pipeline_depth=1),
                      "host_pipeline"),
    "host": (FedAvgAPI, dict(HOST), "host"),
    "mesh-lanes": (CrossSiloFedAvgAPI, dict(RESIDENT, pack_lanes=2),
                   "mesh_packed"),
    "mesh": (CrossSiloFedAvgAPI, dict(RESIDENT), "mesh_sharded"),
    # partial participation: nothing of the mesh's own applies, and the
    # round runs the host path the base class chose
    "mesh-partial": (CrossSiloFedAvgAPI,
                     dict(RESIDENT, pack_lanes=2, client_num_per_round=2),
                     "host"),
}


def _build(ds, cls, kw):
    bundle = create_model("lr", ds.class_num, input_shape=(6,))
    extra = _mesh(2) if issubclass(cls, CrossSiloFedAvgAPI) else {}
    return cls(ds, _cfg(**kw), bundle, **extra)


@pytest.mark.parametrize("case", sorted(CASES))
def test_round_path_is_chosen_once(ds, case, caplog, monkeypatch):
    cls, kw, want = CASES[case]
    with caplog.at_level(logging.WARNING, logger="fedml_tpu"):
        api = _build(ds, cls, kw)
        try:
            assert api._path == want
            # from here on nothing decides again: the rounds, the counts and
            # the status all read the one decision
            monkeypatch.setattr(
                cls, "_choose_path",
                lambda self: pytest.fail("the path was chosen a second time"))
            ran = []
            api._ROUND_RUNNERS = {
                path: (lambda self, r, plan, _p=path, _f=fn:
                       (ran.append(_p), _f(self, r, plan))[1])
                for path, fn in cls._ROUND_RUNNERS.items()}
            for r in range(2):
                assert api._round_plan(r).path == want
                assert np.isfinite(float(api.run_round(r)))
                assert api.round_counts(r)[1] > 0
            assert ran == [want, want]
            assert api.packed_status()["scheduled"] == (
                want in ("packed", "stream_packed", "mesh_packed"))
        finally:
            api.close()
    ignored = [rec.getMessage() for rec in caplog.records
               if "pack_lanes=2 ignored" in rec.getMessage()]
    # an algorithm that cannot pack says so once, however many rounds run;
    # the mesh says once that partial participation cannot
    assert len(ignored) == (1 if case in ("resident-lanes-rewired",
                                          "mesh-partial") else 0), ignored


def test_the_lane_program_has_no_flag():
    """One form of the lane program (ISSUE 43): the parser knows no
    ``--packed_conv``, ``FedConfig`` no such field, and the counts of both
    are what that deletion left."""
    import dataclasses

    from fedml_tpu.core.config import add_args

    parser = add_args()
    with pytest.raises(SystemExit):
        parser.parse_args(["--packed_conv", "blockdiag"])
    flags = [a for a in parser._actions if a.option_strings
             and a.option_strings != ["-h", "--help"]]
    names = {f.name for f in dataclasses.fields(FedConfig)}
    assert "packed_conv" not in names
    assert (len(names), len(flags)) == (104, 97)
    with pytest.raises(TypeError):
        FedConfig(packed_conv="off")


def test_a_cohort_with_no_record_is_planned_as_gather(ds):
    """The one per-round case: ``plan_packing`` has nothing to pack, and
    ``_round_plan`` itself plans the round the gather program then runs."""
    empty = ds.__class__(**{**ds.__dict__,
                            "train_counts": np.zeros_like(ds.train_counts),
                            "train_mask": np.zeros_like(ds.train_mask)})
    api = _build(empty, FedAvgAPI, dict(RESIDENT, pack_lanes=2))
    assert api._path == "packed"
    plan = api._round_plan(0)
    assert plan.path == "gather" and plan.lanes is None
    api.run_round(0)
    assert list(api._gather_steps) and not api._packed_steps


# -- round_counts against what the device program was handed -----------------

def _lane_slots(plan_arrays, epochs, devices=1):
    """The slots a packed program walks, from the ``live`` rows it was
    handed: the dense model's lanes all advance together on their device,
    as far as the last live step of the longest of them."""
    live = np.asarray(plan_arrays[5])            # [lanes, T]
    steps = 0
    for dev in np.split(live, devices):
        last = [max((t + 1 for t in range(len(lane)) if lane[t] > 0),
                    default=0) for lane in dev]
        steps += len(dev) * max(last)
    return steps * BATCH // epochs


def _wrap_builder(api, name, slots_of):
    """Make ``api.<name>(key)`` return its program wrapped so that each call
    records ``slots_of(key, args)``."""
    seen = []
    build = getattr(api, name)

    def wrapped_build(key):
        step = build(key)

        def call(*args):
            seen.append(slots_of(key, args))
            return step(*args)

        return call

    setattr(api, name, wrapped_build)
    return seen


def _wrap_step(holder, key, slots_of):
    seen = []
    step = holder[key] if isinstance(holder, dict) else getattr(holder, key)

    def call(*args):
        seen.append(slots_of(args))
        return step(*args)

    if isinstance(holder, dict):
        holder[key] = call
    else:
        setattr(holder, key, call)
    return seen


def _cohort_by_scan(args):
    cx = args[2]                                  # [cohort, scan length, ...]
    return cx.shape[0] * cx.shape[1]


COUNT_CASES = {
    # 2 of 4 ragged clients a round: the scan trims to the cohort's bucket
    "gather-bucket": (FedAvgAPI, dict(RESIDENT, client_num_per_round=2)),
    # bucketing off: the full record axis
    "gather-full": (FedAvgAPI, dict(RESIDENT, bucket_quantum_batches=0)),
    "packed": (FedAvgAPI, dict(RESIDENT, pack_lanes=2)),
    "host": (FedAvgAPI, dict(HOST, client_num_per_round=2)),
    "mesh_packed": (CrossSiloFedAvgAPI, dict(RESIDENT, pack_lanes=2)),
    "mesh_sharded": (CrossSiloFedAvgAPI, dict(RESIDENT)),
}


@pytest.mark.parametrize("case", sorted(COUNT_CASES))
def test_round_counts_is_the_plan_run_round_executes(ds, case):
    cls, kw = COUNT_CASES[case]
    api = _build(ds, cls, kw)
    n_pad = int(ds.train_x.shape[1])
    if case.startswith("gather"):
        seen = _wrap_builder(
            api, "build_round_step_gather",
            lambda bucket, args: len(args[6]) * (bucket or n_pad))  # idx
    elif case == "packed":
        seen = _wrap_builder(
            api, "build_round_step_packed",
            lambda _key, args: _lane_slots(args[8], api.config.epochs))
    elif case == "mesh_packed":
        seen = _wrap_step(
            api._packed_mesh, "round_fn",
            lambda args: _lane_slots(args[8], api.config.epochs, devices=2))
    else:                       # host, mesh_sharded: the default round step
        seen = _wrap_step(api, "_round_step", _cohort_by_scan)
    try:
        rounds = range(3)
        for r in rounds:
            api.run_round(r)
    finally:
        api.close()
    assert len(seen) == len(rounds)
    counts = np.asarray(ds.train_counts)
    for r, executed in zip(rounds, seen):
        plan = api._round_plan(r)
        real, padded = api.round_counts(r)
        assert executed == padded == plan.padded_slots
        assert real == int(counts[plan.sampled].sum()) <= padded
    if case == "gather-bucket":
        assert any(s < 2 * n_pad for s in seen), "no round trimmed its scan"
    if case in ("packed", "mesh_packed"):
        assert max(seen) < CLIENTS * n_pad, "lanes should beat the full scan"
