"""The comparison that decides ``correct``.

Set-up drives the program from seeded weights through the first rounds of
the cell's own cycle, by the window's own call (``run_round``) on the
cell's own cohorts, and keeps each round's loss and state. Once the window
has closed and the program's state is freed, the configuration's plain
reference follows the same rounds on the same rows in the same order, its
clients one after another. The numbers below are computed and printed in
every run; a number is judged where the cell's file gives it a limit
(``limits``, set from the chip's readings at the cell's own size: PERF.md
section 2; ``benchmarks/readings.py`` reads them):

- ``loss_rel[n]``: each followed round's mean local loss, relative;
- ``update_norm_gap``: the aggregated update of the first round (new global
  minus old: what a server optimizer would be handed), all parameter
  leaves as one vector: the gap between the program's norm and the
  reference's, over the reference's. The worst single leaf is named beside
  it. Batch statistics are left out of every update number: their change
  is a small difference of numbers near 1;
- ``change_norm_gap``: the same over all the rounds followed;
- ``update_l2``: the norm of (the program's first update minus the
  reference's) over the reference's, all parameter leaves as one vector;
- ``update_leaf_l2``: the same leaf by leaf, the median over the leaves. A
  round is tens of SGD steps; the whole vector's norm is carried by the few
  leaves that move most and hardly shows the precision a round was
  computed in, while the typical leaf does, and a median does not swing
  with one leaf as a widest gap does;
- ``lowp_share``: the share of the first new global's non-zero values that
  a 16-bit float holds exactly (the low 16 bits of the float32 pattern are
  zero), over the reference's own share: parameters or an aggregate kept
  in bf16 show exactly in their bits.

Imports numpy and jax only, nothing of the program.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.harness import protocol


def flat(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = np.asarray(v, np.float64)
    return out


def same_tree(a: dict, b: dict) -> list:
    """Leaves whose path or shape differs between two variable trees."""
    fa = {k: v.shape for k, v in flat(a).items()}
    fb = {k: v.shape for k, v in flat(b).items()}
    return sorted(k for k in set(fa) | set(fb) if fa.get(k) != fb.get(k))


def reference_rounds(ref, config: dict, cell: dict, rows, init: dict,
                     seed: int, rounds: list, variant: str = "reference"):
    """Follow ``rounds`` from ``init`` with the plain reference, clients one
    after another, their results averaged by their record counts. Returns
    ``(losses, states)``: per round the count-weighted mean local loss and
    the new global variables (host trees)."""
    import jax.numpy as jnp

    recipe = config["recipe"]
    batch, epochs = int(recipe["batch_size"]), int(recipe["epochs"])
    n_total = int(cell["clients"])
    n_round = int(cell["fed_config"]["client_num_per_round"])
    root = protocol.run_key(seed)
    state = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), init)
    losses, states = [], []
    for r in rounds:
        ids = protocol.sample_cohort(r, n_total, n_round,
                                     int(cell["sampling_seed"]))
        keys = protocol.client_keys(root, r, len(ids))
        x, y, m, counts = rows(ids)
        total = float(np.sum(counts))
        acc, loss_acc = None, 0.0
        for j in range(len(ids)):
            order = protocol.epoch_orders(keys[j], epochs, m[j])
            steps = m.shape[1] // batch

            def batched(a):
                return a[order].reshape((epochs, steps, batch) + a.shape[1:])

            new, loss = ref.local_train(
                config, state, batched(x[j]), batched(y[j]), batched(m[j]),
                -(-int(counts[j]) // batch), variant)
            w = float(counts[j]) / total
            part = jax.tree.map(lambda a: w * a, new)
            acc = part if acc is None else jax.tree.map(jnp.add, acc, part)
            loss_acc = loss_acc + w * loss
        agg = getattr(ref, "AGGREGATE_DTYPE", {}).get(variant)
        if agg is not None:
            # a control that keeps its aggregate in a type of its own
            acc = jax.tree.map(
                lambda a: a.astype(agg).astype(jnp.float32), acc)
        state = acc
        losses.append(float(loss_acc))
        states.append(jax.device_get(acc))
    return losses, states


def leaf_norms(prog_new: dict, ref_new: dict, init: dict,
               under: str = "params/") -> dict:
    """Per leaf under ``under``: the norm of the program's change from
    ``init``, of the reference's, and of their difference.
    -> {leaf: (program, reference, difference)}."""
    p, r, i = flat(prog_new), flat(ref_new), flat(init)
    return {k: (float(np.linalg.norm(p[k] - i[k])),
                float(np.linalg.norm(r[k] - i[k])),
                float(np.linalg.norm(p[k] - r[k])))
            for k in r if k.startswith(under)}


def _finite(v: float) -> float:
    return v if np.isfinite(v) else float("inf")


def norm_gap(norms: dict) -> tuple:
    """Gap between the program's and the reference's norm of the change,
    over the reference's, all leaves of ``norms`` taken as one vector. Also
    names the worst single leaf (its own gap over its own or the median
    leaf's norm, whichever is larger), as information: a widest gap swings
    from seed to seed and carries no limit.
    -> (gap, "worst leaf <path> <gap>")."""
    tot_p = float(np.sqrt(sum(v[0] ** 2 for v in norms.values())))
    tot_r = float(np.sqrt(sum(v[1] ** 2 for v in norms.values())))
    gap = abs(tot_p - tot_r) / max(tot_r, 1e-30)
    med = float(np.median([v[1] for v in norms.values()]))

    def leaf_gap(k):
        return abs(norms[k][0] - norms[k][1]) / max(norms[k][1], med, 1e-30)

    leaf = max(norms, key=leaf_gap)
    return _finite(gap), f"worst leaf {leaf} {leaf_gap(leaf):.4g}"


def diff_l2(norms: dict) -> tuple:
    """Norm of (program's change - reference's) over the reference's:
    -> (all leaves as one vector, the median over the leaves of each
    leaf's own)."""
    tot_d = float(np.sqrt(sum(v[2] ** 2 for v in norms.values())))
    tot_r = float(np.sqrt(sum(v[1] ** 2 for v in norms.values())))
    leaves = [v[2] / max(v[1], 1e-30) for v in norms.values()]
    return (_finite(tot_d / max(tot_r, 1e-30)),
            _finite(float(np.median(leaves))))


def lowp_share(state: dict) -> float:
    """Share of ``state``'s non-zero values exactly representable in a
    16-bit float with float32's exponent (bfloat16, and anything coarser)."""
    held = total = 0
    for leaf in jax.tree.leaves(state):
        a = np.ascontiguousarray(np.asarray(leaf, np.float32)).reshape(-1)
        nz = a != 0
        total += int(nz.sum())
        held += int(((a.view(np.uint32) & 0xFFFF) == 0)[nz].sum())
    return held / max(total, 1)


def compare(prog_losses: list, prog_states: list, ref_losses: list,
            ref_states: list, init: dict, limits: dict) -> dict:
    """-> {"ok": bool, "numbers": [(name, value, limit, ok, note)]}; a
    number that ``limits`` does not name has the limit None and is not
    judged."""
    numbers = []
    for n, (lp, lr) in enumerate(zip(prog_losses, ref_losses)):
        rel = abs(lp - lr) / max(abs(lr), 1e-30)
        numbers.append((f"loss_rel[{n}]", _finite(rel), limits.get("loss_rel"),
                        f"program {lp:.6g} reference {lr:.6g}"))
    first = leaf_norms(prog_states[0], ref_states[0], init)
    g, leaf = norm_gap(first)
    numbers.append(("update_norm_gap", g, limits.get("update_norm_gap"), leaf))
    g, leaf = norm_gap(leaf_norms(prog_states[-1], ref_states[-1], init))
    numbers.append(("change_norm_gap", g, limits.get("change_norm_gap"), leaf))
    whole, median = diff_l2(first)
    numbers.append(("update_l2", whole, limits.get("update_l2"),
                    "all parameter leaves as one vector"))
    numbers.append(("update_leaf_l2", median, limits.get("update_leaf_l2"),
                    f"median of {len(first)} leaves"))
    sp, sr = lowp_share(prog_states[0]), lowp_share(ref_states[0])
    numbers.append(("lowp_share", max(sp - sr, 0.0), limits.get("lowp_share"),
                    f"program {sp:.6g} reference {sr:.6g}"))
    unknown = set(limits) - {n[0].split("[")[0] for n in numbers}
    if unknown:
        raise KeyError(f"limits name no number of the check: {sorted(unknown)}")
    rows = [(name, v, lim, bool(lim is None or v <= lim), note)
            for name, v, lim, note in numbers]
    return {"ok": all(r[3] for r in rows), "numbers": rows}


def run(ref, config, cell, rows, init, seed, rounds, prog_losses,
        prog_states, variant: str = "reference") -> dict:
    t0 = time.perf_counter()
    ref_losses, ref_states = reference_rounds(
        ref, config, cell, rows, init, seed, rounds, variant)
    out = compare(prog_losses, prog_states, ref_losses, ref_states,
                  jax.tree.map(np.asarray, init), cell["limits"])
    out["check_s"] = time.perf_counter() - t0
    return out
