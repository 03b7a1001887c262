"""What the block's new freedom costs the configurations that were there:
nothing. Each accepted tiny cell's packed round program, variable tree and
seeded leaves hash as the PARENT of PR 44 read them on this container
(``a632f7c``: sha256, first 16 digits, of the lowered round program's text /
of every leaf's path, shape and dtype / of the seeded leaves' bytes, seed 3):
a layer that may be one sub-layer, an expert's form as data, the latent's
projections and the shared MLP's own width leave the five LM fixtures and
the conv fixture bit for bit where they were. A PR that changes a round
program on purpose reads the three again on its own parent and pins those."""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))

PARENT = {
    "tiny_kanana2_sim": ("BENCHMARK.tiny_lm.json", "169d9e53bcf95975",
                         "b9304ccd22cf7e0b", "560c3eeadc4ef6d2"),
    "tiny_ling3_sim": ("BENCHMARK.tiny_hybrid.json", "0c9561cb82786cc8",
                       "0df0a4069b8087a9", "ef1a3f0079685427"),
    "tiny_laguna_sim": ("BENCHMARK.tiny_laguna.json", "f55846d538a8acfd",
                        "7ca829878fa2e135", "aa9d5c7b715911ba"),
    "tiny_granite4h_sim": ("BENCHMARK.tiny_granite4h.json", "89aba7293233dd8e",
                           "320f576a32837658", "27a2c6dfc931a2e7"),
    "tiny_zaya1_sim": ("BENCHMARK.tiny_zaya1.json", "ca174cb1a64dc4a3",
                       "19fd79c1f6cf588f", "7d31f354826cdde1"),
    "tiny_sim": ("BENCHMARK.tiny.json", "c92949c6b6c60051", "84ead68582ee8011",
                 "50db80126c531efa"),
}


def _h(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


@pytest.mark.parametrize("cell_name", sorted(PARENT))
def test_accepted_fixture_hashes_as_the_parent(cell_name):
    from benchmarks.harness.cell import build_api, seed_program
    from benchmarks.harness.spec import Spec
    from fedml_tpu.core.rng import round_key
    from fedml_tpu.parallel.packed import plan_arrays_tuple

    fixture, program, tree, leaves = PARENT[cell_name]
    spec = Spec(os.path.join(HERE, "benchmark", "fixtures", fixture))
    cell = spec.cell(cell_name)
    config = spec.config(cell["config"])
    ref = spec.module("references", config["reference"])
    dataset, _rows = spec.module("traffic", config["generator"]).make(
        config, cell, 3)
    api = build_api(config, cell, dataset)
    seed_program(api, ref, config, 3)
    r = int(cell["rounds"]["first"])
    plan = api._round_plan(r)
    step = api.build_round_step_packed(plan.lanes.shape_key)
    tx, ty, tm, _tc = api._dev_train
    n = len(plan.sampled)
    text = step.lower(
        api.variables, api.server_state, tx, ty, tm, jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), jnp.float32), round_key(api.root_key, r),
        tuple(jnp.asarray(a) for a in plan_arrays_tuple(plan.lanes))).as_text()
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(api.variables))[0]
    api.close()
    assert _h(json.dumps([(jax.tree_util.keystr(p), list(a.shape), str(a.dtype))
                          for p, a in flat]).encode()) == tree
    assert _h(b"".join(np.ascontiguousarray(a).tobytes()
                       for _p, a in flat)) == leaves
    assert _h(text.encode()) == program
