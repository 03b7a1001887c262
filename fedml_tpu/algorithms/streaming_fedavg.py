"""Streaming FedAvg — federated rounds for datasets exceeding the device
budget (VERDICT r2 #6).

The in-memory paradigm (FedAvgAPI) holds the stacked federation in HBM and
trains the cohort as one vmapped program. At ImageNet/Landmarks scale that
stack does not fit; the reference streams every dataset through DataLoader
worker processes instead (cifar10/data_loader.py:160-233). This is the
TPU-native counterpart: client records stay HOST-resident, the native
threaded pipeline (fedml_tpu/native.HostPipeline, C++ workers) assembles
shuffled batches off-GIL into a bounded ring, `device_stream` keeps
transfers in flight ahead of the consumer, and the device runs one jitted
per-batch SGD step — host batch assembly, host->device transfer, and device
compute all overlap; host memory is bounded by the pipeline ring
(depth x batch), device memory by one client's working set.

Numerical parity with the in-memory path is EXACT by construction, not
approximate: the pipeline runs in explicit-order mode with the same
per-epoch shuffle the jitted scan derives (perm = random.permutation(ekey),
real-records-first stable sort; batch keys split(fold_in(ekey, 0x5ba7)) —
see parallel/local.make_local_train_fn), and the in-memory path's masked
padding steps are no-ops (live=0 freezes params/opt/stats and zeroes the
loss), so streaming ONLY the real batches reproduces the identical update
sequence. tests/test_streaming_fedavg.py pins rounds equal to FedAvgAPI.
"""

from __future__ import annotations

import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core.rng import round_key
from fedml_tpu.parallel.local import LocalResult

log = logging.getLogger(__name__)

# must match parallel/local.make_local_train_fn's batch-key derivation
_BATCH_KEY_TAG = 0x5BA7


class StreamingFedAvgAPI(FedAvgAPI):
    """FedAvg whose clients stream host-resident batches through the native
    pipeline; cohort clients train sequentially on the device (the price of
    not fitting in HBM), aggregation and the elastic-round guard are the
    shared ``_finish_round``."""

    supports_device_data = False  # the point is that data does NOT go resident
    elastic_rounds_ok = True      # zero-weight failures via _finish_round

    def __init__(self, dataset, config, bundle=None, n_threads: int = 2,
                 depth: int = 4):
        self.n_threads, self.depth = n_threads, depth
        super().__init__(dataset, config, bundle)
        self._batch_step = self._build_batch_step()
        self._opt_init = jax.jit(lambda p: self._opt_tx.init(p))
        self._finish_jit = jax.jit(self._finish_round)
        self._stream_fold = None

    def _stream_mode(self) -> str:
        """This paradigm HAS no single round program to mirror — the base
        gate's build_round_step check doesn't apply. Streaming folds the
        plain weighted mean, so only a custom aggregate() opts out."""
        memo = self._stream_mode_memo
        if memo is not None:
            return memo
        mode = self.config.stream_aggregate
        if mode != "off" and type(self).aggregate is not FedAvgAPI.aggregate:
            log.warning(
                "stream_aggregate=%r ignored: %s overrides aggregate(), "
                "which the streaming fold cannot mirror", mode,
                type(self).__name__)
            mode = "off"
        self._stream_mode_memo = mode
        return mode

    def build_round_step(self):
        # rounds are driven batch-by-batch in run_round; there is no single
        # whole-round XLA program to build on this paradigm
        return None

    def _build_batch_step(self):
        from fedml_tpu.parallel.local import make_batch_sgd_step, make_optimizer

        c = self.config
        tx = make_optimizer(c.client_optimizer, c.lr, c.momentum, c.wd)
        self._opt_tx = tx
        # the SAME per-batch step make_local_train_fn scans — shared
        # definition, so the streaming path cannot drift from the in-memory
        # one (params0 threaded for FedProx-style subclasses)
        step = make_batch_sgd_step(
            self.bundle, self.task, tx, grad_clip=c.grad_clip,
            compute_dtype=jnp.bfloat16 if c.dtype == "bfloat16" else None,
        )
        return jax.jit(step)

    def _client_orders(self, mask, count, rng):
        """The jitted scan's exact per-epoch order, truncated to the real
        batches: perm(ekey) stable-sorted real-first; only the first
        ceil(count/bs) batches carry live steps (the rest are frozen no-ops
        in the in-memory path), so only they are streamed."""
        c = self.config
        n_pad = mask.shape[0]
        bs = c.batch_size
        steps_real = int(np.ceil(max(float(count), 1.0) / bs))
        mask_d = jnp.asarray(mask)
        ekeys = jax.random.split(rng, c.epochs)
        orders = []
        for e in range(c.epochs):
            perm = jax.random.permutation(ekeys[e], n_pad)
            order = perm[jnp.argsort(-mask_d[perm], stable=True)]
            orders.append(np.asarray(order[: steps_real * bs]))
        return np.stack(orders), ekeys, steps_real

    def _prefetch_build(self, round_idx: int, pool):
        """Streaming rides the host round pipeline with a HOST payload: the
        materialized per-client arrays, no trim/cast/device_put — the
        per-batch stream ships records to the device batch-by-batch as
        today. Only the materialization moves off the round's critical
        path, and it goes through the SAME client_slice_cached LRU the
        serial client_arrays path uses — live clients only, cross-round
        repeats served from cache — so the work done (and a cross-device
        dataset's materialized_rows) is identical to the serial path by
        construction. Payload maps cohort position -> (x, y, mask)."""
        t0 = time.perf_counter()
        plan = self._round_plan(round_idx)
        sampled, live = plan.sampled, plan.live
        keep = [int(p) for p in (range(len(sampled)) if live is None
                                 else np.flatnonzero(live > 0))]
        ids = [int(sampled[p]) for p in keep]
        # cap covers the pipeline's steady-state working set (depth + 1
        # cohorts), so in-flight rounds cannot evict each other's clients
        cap = max(64, len(sampled) * (self.config.host_pipeline_depth + 1))

        def fetch(k):
            return self.dataset.client_slice_cached(k, cap=cap)

        parts = (list(pool.map(fetch, ids)) if pool is not None
                 else [fetch(k) for k in ids])
        rows = {p: (x[0], y[0], m[0])
                for p, (x, y, m, _c) in zip(keep, parts)}
        return rows, {
            "materialize_ms": (time.perf_counter() - t0) * 1e3,
            "h2d_ms": 0.0}

    def _train_client_streaming(self, k: int, rng, data=None):
        """One client's local run: ordered native pipeline over its host
        slice + the per-batch jitted step. ``data`` = prefetched (x, y,
        mask) host arrays from the round pipeline; None materializes on
        demand. Returns (variables, last-epoch mean loss, tau)."""
        from fedml_tpu.data.pipeline import HostPipeline, device_stream

        c = self.config
        bs = c.batch_size
        # one client's host arrays: a view for stacked datasets, an
        # O(1-client) materialization for virtual cross-device ones
        x, y, mask = data if data is not None else self.dataset.client_arrays(int(k))
        x, y = np.asarray(x), np.asarray(y)
        mask = np.asarray(mask)
        count = float(self.dataset.train_counts[k])
        orders, ekeys, steps_real = self._client_orders(mask, count, rng)
        n_pad = mask.shape[0]
        steps_full = n_pad // bs

        variables = self.variables
        params0 = variables["params"]
        opt_state = self._opt_init(params0)
        pipe = HostPipeline(x, None, bs, n_threads=self.n_threads,
                            depth=self.depth, orders=orders)
        try:
            stream = device_stream(pipe, n_batches=c.epochs * steps_real)
            for e in range(c.epochs):
                bkeys = jax.random.split(
                    jax.random.fold_in(ekeys[e], _BATCH_KEY_TAG), steps_full)
                # labels/mask are tiny next to x: stage the whole epoch's
                # once so the hot loop has no per-step host->device hops
                # beyond the prefetched x stream
                by_e = jnp.asarray(y[orders[e]]).reshape((steps_real, bs)
                                                         + y.shape[1:])
                bm_e = jnp.asarray(mask[orders[e]], jnp.float32).reshape(
                    (steps_real, bs))
                ep_loss = jnp.zeros(())
                for s in range(steps_real):
                    bx, _ = next(stream)
                    variables, opt_state, l = self._batch_step(
                        variables, opt_state, params0, bx, by_e[s], bm_e[s],
                        bkeys[s])
                    ep_loss = ep_loss + l
                last_loss = ep_loss / max(steps_real, 1)
        finally:
            pipe.close()
        tau = jnp.float32(c.epochs * steps_real)
        return variables, last_loss, tau

    def _build_stream_fold(self):
        """Device fold for --stream_aggregate: one client's result folds
        into the running f32 accumulator (normalize-first weights — the
        round total is known from the plan), so the round holds ONE
        model-shaped sum instead of the O(cohort) stacked list."""
        @jax.jit
        def fold(acc, acc_loss, v, loss, w_norm, w):
            acc = jax.tree.map(
                lambda a, x: a + x.astype(jnp.float32) * w_norm, acc, v)
            return acc, acc_loss + loss * w

        return fold

    def _run_round_streamed(self, round_idx, sampled, counts, keys, cohort):
        """The sequential client loop with the streaming fold (O(1) server
        memory); aggregation mirrors _finish_round's arithmetic at the
        fedseg tolerance (per-client fold order vs one stacked sum)."""
        if self._stream_fold is None:
            self._stream_fold = self._build_stream_fold()
        acc = jax.tree.map(lambda v: jnp.zeros(v.shape, jnp.float32),
                           self.variables)
        acc_loss = jnp.zeros(())
        total = np.float32(counts.sum())
        denom = np.maximum(total, np.float32(1e-12))
        for i, k in enumerate(sampled):
            if counts[i] <= 0:
                continue   # zero weight: its term in the mean is exactly 0
            data = None if cohort is None else cohort[i]
            v, l, _tau = self._train_client_streaming(int(k), keys[i], data)
            acc, acc_loss = self._stream_fold(
                acc, acc_loss, v, l,
                jnp.float32(counts[i] / denom), jnp.float32(counts[i]))
        keep = total > 0
        if keep:
            self.variables = jax.tree.map(
                lambda a, v: a.astype(v.dtype), acc, self.variables)
        self.stream_stats = {
            "mode": self.config.stream_aggregate, "cohort": len(sampled),
            "chunks": len(sampled),
            "accumulator_bytes": int(sum(
                int(np.prod(v.shape)) * 4
                for v in jax.tree.leaves(self.variables)) + 8)}
        return acc_loss / jnp.maximum(jnp.float32(total), 1e-12)

    def _run_round_inner(self, round_idx: int):
        # traced via the base run_round wrapper (one "round" span per round)
        plan = self._round_plan(round_idx, record=True)
        sampled, live = plan.sampled, plan.live
        rk = round_key(self.root_key, round_idx)
        keys = jax.random.split(rk, len(sampled))
        outs, losses, taus = [], [], []
        counts = np.asarray(self.dataset.train_counts, np.float32)[sampled]
        if live is not None:
            counts = counts * live
        pf = self._host_prefetcher()
        cohort = stages = None
        wait_ms = 0.0
        if pf is not None:
            cohort, stages, wait_ms = pf.pop(round_idx)
        t0 = time.perf_counter()
        streamed = None
        if self._stream_mode() != "off":
            streamed = self._run_round_streamed(
                round_idx, sampled, counts, keys, cohort)
        else:
            for i, k in enumerate(sampled):
                if counts[i] <= 0:
                    # failed client: zero aggregation weight — its (skipped)
                    # training result cannot influence the round, so train a
                    # placeholder from the current globals for tree shape only
                    outs.append(self.variables)
                    losses.append(jnp.zeros(()))
                    taus.append(jnp.zeros(()))
                    continue
                # prefetched rows exist exactly for live positions (the
                # counts[i] > 0 guard above matches the build's live filter)
                data = None if cohort is None else cohort[i]
                v, l, tau = self._train_client_streaming(int(k), keys[i], data)
                outs.append(v)
                losses.append(l)
                taus.append(tau)
        if stages is not None:
            row = dict(stages, wait_ms=wait_ms, round=round_idx,
                       compute_ms=(time.perf_counter() - t0) * 1e3)
            self._stage_rows.append(row)
            from fedml_tpu.obs import default_registry

            default_registry().append_row("stage", row)
        if streamed is not None:
            return (streamed if self.config.async_rounds
                    else float(streamed))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        res = LocalResult(stacked, jnp.stack(losses), jnp.stack(taus))
        out = self._finish_jit(
            self.variables, self.server_state, res,
            jnp.asarray(counts, jnp.float32), rk)
        # fedlens rides the shared _finish_round (norm + align; no
        # loss_delta — the sequential trainer reports one mean loss)
        self.variables, self.server_state, train_loss = self._lens_absorb(
            round_idx, out, np.asarray(sampled, np.int64), counts > 0)
        return train_loss if self.config.async_rounds else float(train_loss)
