"""Mixture-of-experts transformer blocks with expert parallelism.

Two layers live here. The SPARSE one (:class:`SharedRoutedMoe`,
:class:`LatentMoeLM`, below the dense one) is what today's open MoE language
models run: sigmoid scores, selection with a correction bias, shared
experts, and a layer that is told which experts it holds, routes over all of
them and computes only the rows its own experts were chosen for (a grouped
matmul over rows sorted by expert, no dropped token: a row capacity chosen
each step from the router's own count). The DENSE
one (:class:`MoeMlp`) is the older GSPMD baseline, kept for the expert-axis
sharding tests: every expert computes every token.

--- the dense-dispatch baseline ---

The reference has no MoE (and no LLM-era parallelism at all, SURVEY.md
§2.6); this exists so the framework's parallelism surface covers the EP
axis alongside dp/tp/sp/clients/group.

Design: the MoE MLP keeps expert weights stacked on a leading expert axis
``[E, ...]`` — sharding that axis over an 'ep' mesh axis IS expert
parallelism (each device stores and computes only its experts). Routing is
a dense softmax-weighted top-k dispatch expressed as einsums over the
expert axis, which makes the layer exactly equal to its single-device
form under GSPMD (no capacity dropping, no load-balancing noise) — the
right correctness baseline for a framework; a capacity-limited all_to_all
dispatch is a performance specialization of the same parameter layout.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

import dataclasses
from typing import Optional

from fedml_tpu.models import COUNTERS, ModelBundle, register_model
from fedml_tpu.models.transformer import (CompressedConvAttention,
                                          DeltaAttention, GroupedAttention,
                                          LatentAttention, Linear, MLP_FORMS,
                                          Mamba2Mixer, RMSNorm, SelfAttention,
                                          _normal, fan_in_uniform,
                                          yarn_frequencies)
from fedml_tpu.obs.tracer import (SCOPE_LM_DENSE, SCOPE_LM_EXPERTS,
                                  SCOPE_LM_LATENT, SCOPE_LM_ROUTE)
from fedml_tpu.ops.grouped_matmul import (embed_rows, fan_out_rows,
                                          grouped_matmul, permute_rows)
from fedml_tpu.ops.ssd import SSD_CHUNK


def top_k_probs(router_logits: jax.Array, top_k: int) -> jax.Array:
    """Softmax the router logits, keep each token's top-k experts, and
    renormalize so the kept weights sum to 1 (fully differentiable)."""
    E = router_logits.shape[-1]
    probs = jax.nn.softmax(router_logits, axis=-1)
    if top_k < E:
        kth = jnp.sort(probs, axis=-1)[..., E - top_k][..., None]
        probs = jnp.where(probs >= kth, probs, 0.0)
        probs = probs / jnp.maximum(jnp.sum(probs, axis=-1, keepdims=True), 1e-9)
    return probs


class MoeMlp(nn.Module):
    """Softmax-routed top-k mixture of expert MLPs (dense dispatch)."""

    dim: int
    num_experts: int = 4
    mlp_ratio: int = 4
    top_k: int = 2
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h):
        E, D, F = self.num_experts, self.dim, self.mlp_ratio * self.dim
        router = nn.Dense(E, dtype=jnp.float32, name="router")(
            h.astype(jnp.float32))                      # [B, T, E]
        probs = top_k_probs(router, self.top_k)
        w_up = self.param("w_up", nn.initializers.lecun_normal(),
                          (E, D, F), jnp.float32).astype(self.dtype)
        b_up = self.param("b_up", nn.initializers.zeros, (E, F), jnp.float32)
        w_dn = self.param("w_dn", nn.initializers.lecun_normal(),
                          (E, F, D), jnp.float32).astype(self.dtype)
        b_dn = self.param("b_dn", nn.initializers.zeros, (E, D), jnp.float32)
        h = h.astype(self.dtype)
        # every expert computes every token; the router weights combine.
        # einsum over the (sharded) expert axis -> per-device partial sums,
        # one psum inserted by GSPMD at the combine.
        up = jnp.einsum("btd,edf->ebtf", h, w_up) + b_up[:, None, None, :].astype(self.dtype)
        act = nn.gelu(up)
        down = jnp.einsum("ebtf,efd->ebtd", act, w_dn) + b_dn[:, None, None, :].astype(self.dtype)
        out = jnp.einsum("bte,ebtd->btd", probs.astype(self.dtype), down)
        return out


class MoeBlock(nn.Module):
    dim: int
    heads: int
    num_experts: int = 4
    mlp_ratio: int = 4
    top_k: int = 2
    attn_impl: str = "auto"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, train: bool = False):
        a = SelfAttention(self.dim, self.heads, self.attn_impl,
                          dtype=self.dtype, name="attn")(
            nn.LayerNorm(dtype=self.dtype)(h))
        h = h + a
        m = MoeMlp(self.dim, self.num_experts, self.mlp_ratio, self.top_k,
                   self.dtype, name="moe")(nn.LayerNorm(dtype=self.dtype)(h))
        return h + m


class MoeTransformerLM(nn.Module):
    """Decoder-only LM with MoE MLPs — the EP counterpart of TransformerLM."""

    vocab_size: int
    dim: int = 256
    heads: int = 8
    layers: int = 4
    num_experts: int = 4
    mlp_ratio: int = 4
    top_k: int = 2
    max_len: int = 4096
    attn_impl: str = "auto"
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False, pos_offset=0):
        t = x.shape[1]
        h = nn.Embed(self.vocab_size, self.dim, dtype=self.dtype,
                     name="tok_embed")(x.astype(jnp.int32))
        pos = pos_offset + jnp.arange(t)
        h = h + nn.Embed(self.max_len, self.dim, dtype=self.dtype,
                         name="pos_embed")(pos)[None]
        for i in range(self.layers):
            h = MoeBlock(self.dim, self.heads, self.num_experts,
                         self.mlp_ratio, self.top_k, self.attn_impl,
                         self.dtype, name=f"block{i}")(h, train)
        h = nn.LayerNorm(dtype=self.dtype)(h)
        return nn.Dense(self.vocab_size, dtype=jnp.float32, name="lm_head")(h)


# ---------------------------------------------------------------------------
# The sparse layer and the LM built on it (DeepSeek-V3's block, as
# kakaocorp/kanana-2-30b-a3b's config.json fixes it)
# ---------------------------------------------------------------------------

def chosen_groups(biased: jax.Array, n_group: int, topk_group: int):
    """``biased [N, E]`` (``score + bias``) -> ``[N, n_group]`` bool: the
    ``topk_group`` groups (``E / n_group`` consecutive experts each) whose
    two largest entries sum highest."""
    n, e = biased.shape
    top2, _ = jax.lax.top_k(biased.reshape(n, n_group, e // n_group), 2)
    _, groups = jax.lax.top_k(jnp.sum(top2, axis=-1), topk_group)
    return jnp.any(jax.nn.one_hot(groups, n_group, dtype=jnp.bool_), axis=1)


def route(scores: jax.Array, bias: jax.Array, top_k: int, scaling: float,
          groups: Optional[jax.Array] = None, normalise: bool = True):
    """``scores [N, E]`` (sigmoid or softmax, float32) -> ``(idx [N, k],
    weights [N, k])``: the ``k`` experts with the largest ``score + bias``
    (``bias`` None: the largest scores), weighted by
    their own scores over the chosen scores' sum (not ``normalise``: by
    their own scores as they are), times ``scaling``. With
    ``groups [N, n_group]`` (:func:`chosen_groups`) the choice is
    group-limited: only the experts of a token's chosen groups stand for it.
    Gradients flow through the scores, not through the selection or the
    bias. (No gather: a gather's transpose is a scatter, which a TPU
    serialises; the one-hot product's transpose is a product.)"""
    biased = jax.lax.stop_gradient(
        scores if bias is None else scores + bias)
    if groups is not None:
        size = scores.shape[-1] // groups.shape[-1]
        biased = jnp.where(jnp.repeat(groups, size, axis=1), biased, -jnp.inf)
    _, idx = jax.lax.top_k(biased, top_k)
    hot = jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype)
    chosen = jnp.einsum("nke,ne->nk", hot, scores)
    if normalise:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return idx, chosen * scaling


@jax.custom_vjp
def pulled(weights: jax.Array, bias: jax.Array, pull: jax.Array):
    """``weights`` as they are. On the way back ``bias`` takes ``pull`` as
    its cotangent, whatever reaches ``weights`` (which passes): a balancing
    bias has no gradient of the loss's (:func:`route`), and the client's
    optimizer moves it by what the LOAD asks instead, ``bias <- bias - lr *
    pull``, in the step that moves every other leaf."""
    return weights


def _pulled_fwd(weights, bias, pull):
    return weights, pull


def _pulled_bwd(pull, ct):
    return ct, pull, jnp.zeros_like(pull)


pulled.defvjp(_pulled_fwd, _pulled_bwd)


def load_pull(scores: jax.Array, idx: jax.Array, rate: float) -> jax.Array:
    """What a balancing bias is moved by, ``[E]``: each choice's excess load
    over an even share, ``E * load - 1`` (``load`` the share of the batch's
    (token, choice) pairs that chose it) held to ``+- 1``, times the scores'
    own spread (their standard deviation over the batch: a bias is worth
    what the scores it stands beside are apart) times ``rate``. A choice
    that took too much falls, one that took too little rises. The bound: a
    batch's padded sequence is one id 4,096 times, all of it one choice's,
    and moves that choice's bias no further than a choice with twice its
    share would."""
    e = scores.shape[-1]
    load = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1))
    return jax.lax.stop_gradient(
        rate * jnp.std(scores.astype(jnp.float32))
        * jnp.clip(load * e - 1.0, -1.0, 1.0))


#: The row capacities a sparse layer chooses from, as shares of its ``n * k``
#: (token, choice) pairs. A layer that holds ``h`` of ``E`` experts fills
#: about ``h / E`` of the pairs (the cell: 16 of 128, 11 - 16% by the
#: program's own counter), and the rows' moves, masks and grouped matmuls all
#: cost by the capacity they walk, not by the rows that are filled. Alone on
#: the chip at the cell's shapes (``tools/rows_sweep.py``, 4,543 rows filled;
#: ``PERF.md`` section 6, PR 29, has the table) a rung's forward + backward
#: take 9.03 ms at 6,144 slots, 10.48 at 12,288, 12.94 at 24,576 and 20.40
#: at 49,152: 7.6 ms + 0.26 ms a 1,024 slots, so a finer ladder than
#: halvings would win under 0.3% of a round.
ROW_RUNG_SHARES = (1 / 8, 1 / 4, 1 / 2, 1)
#: rungs are multiples of this many rows (where the pairs allow eight such)
ROW_RUNG_ALIGN = 1024


def row_rungs(pairs: int) -> tuple:
    """The static row capacities of a layer with ``pairs = n * k`` (token,
    choice) pairs, ascending, from ``pairs`` alone; the last is ``pairs``
    itself, which holds any routing."""
    align = max(1, min(ROW_RUNG_ALIGN, pairs // 8))
    return tuple(sorted({
        min(pairs, -(-math.ceil(pairs * share) // align) * align)
        for share in ROW_RUNG_SHARES}))


def _rung_index(rungs: tuple, sizes: jax.Array) -> jax.Array:
    """Index of the first rung that holds every row of ``sizes``."""
    total = jnp.sum(sizes)
    return sum((total > c).astype(jnp.int32) for c in rungs[:-1])


def _swiglu_rows(rows, sizes, live, w_gate, w_up, w_down):
    g = grouped_matmul(rows, w_gate, sizes)
    u = grouped_matmul(rows, w_up, sizes)
    return grouped_matmul(nn.silu(g) * u, w_down, sizes), ()


def _relu2_rows(rows, sizes, live, w_up, w_down):
    u = grouped_matmul(rows, w_up, sizes)
    y = grouped_matmul(jnp.square(nn.relu(u)), w_down, sizes)
    return y, (jnp.sum((u > 0) & live, dtype=jnp.float32),)


#: a held expert's form by name: its matrices' names, in the order the rows'
#: function takes them, and ``(rows [C, d], sizes, live [C, 1], *weights) ->
#: (rows out [C, d], stats)``, the grouped matmuls and what lies between
#: them. ``swiglu``: ``down(silu(gate x) * up x)``, no statistic; ``relu2``:
#: ``down(relu(up x)^2)`` and the live rows' hidden units that are positive
#: before the square (float32)
EXPERT_FORMS = {"swiglu": (("gate", "up", "down"), _swiglu_rows),
                "relu2": (("up", "down"), _relu2_rows)}


@functools.cache
def _rung(capacity: int, form: str = "swiglu"):
    """The held experts' part of a sparse layer over the first ``capacity``
    sorted row slots: ``(xf [n, d], order [k*n], inv [k*n], sizes [held],
    mine [k, n], weights [n, k], *w) -> ([n, d] float32, stats)`` (``w``:
    the matrices of the experts' ``form``, the parameters as they are,
    ``stats`` its statistics: :data:`EXPERT_FORMS`). Exact whenever
    ``sum(sizes) <= capacity``: a slot past the last group goes in and comes
    out as zeros (so that nothing a kernel leaves there, and no cotangent of
    it, reaches a token), and a pair whose slot was not kept reads a zero
    row. One jitted function a capacity and form, so that every sparse layer
    of a model traces it once."""

    # the trace counts a capacity's layer-steps by this name
    rows_name = f"moe_rows_{capacity}"
    expert_rows = EXPERT_FORMS[form][1]

    def rung(xf, order, inv, sizes, mine, weights, *w):
        (k, n), d = mine.shape, xf.shape[-1]
        with jax.named_scope(rows_name):
            with jax.named_scope(SCOPE_LM_ROUTE):
                live = (jnp.arange(capacity) < jnp.sum(sizes))[:, None]
                rows = jnp.where(
                    live, fan_out_rows(xf, order[:capacity], inv), 0)
            with jax.named_scope(SCOPE_LM_EXPERTS):
                y, stats = expert_rows(rows, sizes, live, *w)
            with jax.named_scope(SCOPE_LM_ROUTE):
                y = jnp.where(live, y, 0)
                back = permute_rows(y, inv, order[:capacity]).reshape(k, n, d)
                wt = jnp.where(mine, weights.T, 0.0)
                return jnp.einsum("knd,kn->nd", back.astype(jnp.float32),
                                  wt), stats

    return jax.jit(rung)


@functools.cache
def _rung_vjp(capacity: int, form: str = "swiglu"):
    """``(operands, ct) ->`` the cotangents of ``_rung(capacity, form)``'s
    floating operands, from a forward rebuilt at that capacity; the experts'
    weights' in the parameters' own dtype, as the grouped matmul leaves
    them."""

    def rung_vjp(operands, ct):
        xf, order, inv, sizes, mine, weights, *w = operands
        _, vjp = jax.vjp(
            lambda xf, weights, *w: _rung(capacity, form)(
                xf, order, inv, sizes, mine, weights, *w), xf, weights, *w)
        return vjp(ct)

    return jax.jit(rung_vjp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def routed_rows(rungs: tuple, form: str, xf, order, inv, sizes, mine, weights,
                *w) -> tuple:
    """``_rung(C, form)`` at the first ``C`` of ``rungs`` that holds the rows
    ``sizes`` counts, on a rung's operands (``w``: the experts' matrices,
    the float32 parameters as they are: the grouped matmul rounds the tile
    it multiplies, ``ops/grouped_matmul.py``): a ``lax.switch`` on the
    router's own count, no row dropped (the last rung is every pair). The
    backward pass saves the operands alone, switches on the same index and rebuilds
    that rung's forward: a plain ``lax.switch`` would save the union of
    every rung's residuals, 1.9 times the full capacity's. Under a ``vmap``
    (packed lanes) the index is batched and JAX runs every rung and
    selects: still exact, only slow (and the TPU refuses a batched grouped
    matmul there anyway). -> ``([n, d] float32, the form's statistics)``."""
    return jax.lax.switch(
        _rung_index(rungs, sizes), [_rung(c, form) for c in rungs],
        xf, order, inv, sizes, mine, weights, *w)


def _routed_fwd(rungs, form, *operands):
    return routed_rows(rungs, form, *operands), operands


def _routed_bwd(rungs, form, operands, ct):
    with jax.named_scope(SCOPE_LM_ROUTE):
        dx, dweights, *dw = jax.lax.switch(
            _rung_index(rungs, operands[3]),
            [_rung_vjp(c, form) for c in rungs], operands, ct)
    return (dx, None, None, None, None, dweights, *dw)


routed_rows.defvjp(_routed_fwd, _routed_bwd)


class MlpRouter(nn.Module):
    """A router that is an MLP and reads the router of the layer before it
    (the ZAYA1 router, arXiv:2511.17127). ``s = x W_d + b_d`` in ``hidden``
    channels; with a ``carry`` (what the router of the layer before ended
    this step with, its own carry in it) ``s += gamma * carry``, ``gamma``
    one learned number seeded at 0.5; ``r = RMSNorm(s)``, two layers ``r <-
    GELU(r W + b)`` (the erf form), ``logits = r W_3`` over ``n_out``
    choices; -> ``(softmax(logits) [N, n_out], bias [n_out], s [N,
    hidden])``. ``bias`` is the balancing bias the choice adds to the
    scores (:func:`route`: no gradient of the loss reaches it; the layer's
    ``balance_rate`` moves it against the load, :func:`load_pull`; zeros
    here, and a seeded router's choices then take uneven shares: the
    benchmark's seeded weights carry a bias balanced on a batch drawn from
    the seed, ``benchmarks/references/zaya1_8b.py``). The four
    matrices and their biases start as ``torch.nn.Linear``'s default does
    (uniform over ``+- fan_in^-0.5``), so that the logits' spread does not
    depend on the widths. All of it in float32 at the highest matmul
    precision, as the linear router: a token's ONE choice decides its whole
    MLP."""

    n_out: int
    hidden: int
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x, carry=None):
        f32 = jnp.float32

        def dense(name, a, features, bias=True):
            init = fan_in_uniform(a.shape[-1])
            w = self.param(f"{name}_kernel", init, (a.shape[-1], features),
                           f32)
            y = jnp.dot(a, w, precision=jax.lax.Precision.HIGHEST)
            if bias:
                y = y + self.param(f"{name}_bias", init, (features,), f32)
            return y

        s = dense("down", x.astype(f32), self.hidden)
        if carry is not None:
            s = s + carry * self.param(
                "gamma", nn.initializers.constant(0.5), (), f32)
        r = RMSNorm(self.eps, f32, name="norm")(s)
        for name in ("fc1", "fc2"):
            r = jax.nn.gelu(dense(name, r, self.hidden), approximate=False)
        logits = dense("out", r, self.n_out, bias=False)
        bias = self.param("bias", nn.initializers.zeros, (self.n_out,), f32)
        return jax.nn.softmax(logits, axis=-1), bias, s


class SharedRoutedMoe(nn.Module):
    """Shared experts on every token + the weighted sum of each token's
    chosen routed experts, for the experts HELD here.

    The router keeps all ``n_routed`` outputs, its ``top_k`` choices and the
    weights normalised over all the chosen. The layer holds experts
    ``held_first .. held_first + held_count - 1`` (all, when ``held_count``
    is None): it takes the (token, choice) pairs whose expert it holds,
    sorts them by expert, runs one grouped matmul per projection over the
    rows and adds the weighted rows back. No row is dropped: the rows' moves
    and matmuls walk a static capacity chosen each step from the router's
    own count (:func:`row_rungs`, :func:`routed_rows`), the smallest that
    holds every row of a held expert; a layer that holds all its experts
    always takes the last, every pair. What the absent experts would have
    added is left out: in an expert-parallel deployment their chips add it,
    and nothing here stands in for them or for the exchange.

    ``n_group > 1``: the router's choice is group-limited (:func:`route`).
    ``score``: ``"sigmoid"`` scores each expert by itself and chooses by
    ``score + bias`` (DeepSeek-V3's router); ``"softmax"`` scores over all
    ``n_routed`` logits and has no bias (the Qwen-MoE lineage's). Both are
    one matrix inside the layer. ``router_hidden``: the router is an
    :class:`MlpRouter` of that width instead, a module of its own
    (``router/...``) that takes the ``carry`` of the layer before and hands
    on its own: the layer returns ``(output, carry)``, ``carry`` None where
    the router keeps none; it has one output more than there are experts, a
    choice that is NO expert (index ``n_routed``: nobody's pair on any
    share, no row, nothing added). With ONE choice a
    token its weight is the score itself (over the chosen scores' sum it
    would be the constant 1 and the router would learn nothing).
    ``balance_rate``: the balancing bias follows the load. No gradient of
    the loss reaches it; each training step hands the client's optimizer
    :func:`load_pull` in its place (:func:`pulled`), so plain SGD moves it
    by ``- lr * balance_rate * std(scores) * (E * load - 1)`` between steps
    and the round's aggregate is the clients' weighted mean of it, as of any
    parameter (0: the bias stays as it was loaded).

    ``form``: what an expert and the shared MLP are (:data:`EXPERT_FORMS`,
    ``transformer.MLP_FORMS``): a SwiGLU of three matrices, or ``relu2``,
    ``down(relu(up x)^2)`` of two. ``latent``: the routed experts work in a
    latent of that width, between two projections of the layer's own,
    ``out = latent_out(sum_i w_i expert_i(latent_in x)) + shared(x)``: the
    rows that move are ``latent`` wide, the experts' matrices ``[latent,
    width]`` and ``[width, latent]``; the router and the shared MLP read the
    layer's input (0: the experts read and write the model's width).
    ``shared_width``: the shared MLP's width where it is published by
    itself (``moe_shared_expert_intermediate_size``; 0: ``n_shared *
    width``).

    The ``counters`` collection (``models.COUNTERS``) carries
    ``expert_rows`` (rows each held expert has computed, summed over the
    training steps) and ``steps``: the load statistic that the published
    bias update reads; under a group-limited router also ``group_tokens``,
    the tokens among whose chosen groups is one with an expert held here.
    The packed simulation round sums them over the round's clients
    (float32: exact up to 2**24 rows an expert), and
    ``ModelBundle.counters`` reads them on the host. Under an MLP router
    also ``skipped``, the tokens whose choice was no expert; where the form
    counts them (``relu2``) also ``live_units``, the share of the hidden
    units of the held experts' rows and of the shared MLP that are positive
    before the square, summed over the training steps.
    """

    n_routed: int
    top_k: int
    width: int
    n_shared: int = 0
    scaling: float = 1.0
    held_first: int = 0
    held_count: Optional[int] = None
    dtype: Any = jnp.float32
    n_group: int = 1
    topk_group: int = 1
    score: str = "sigmoid"
    router_hidden: int = 0
    eps: float = 1e-6
    balance_rate: float = 0.0
    form: str = "swiglu"
    latent: int = 0
    shared_width: int = 0

    @nn.compact
    def __call__(self, x, train: bool = False, carry=None):
        b, t, d = x.shape
        n, k, dt = b * t, self.top_k, self.dtype
        held = self.n_routed if self.held_count is None else self.held_count
        xf = x.reshape(n, d)
        names, _ = EXPERT_FORMS[self.form]
        out, shared_stats, shared_width = 0.0, (), 0
        if self.n_shared:
            shared_width = self.shared_width or self.n_shared * self.width
            with jax.named_scope(SCOPE_LM_DENSE):
                out, shared_stats = MLP_FORMS[self.form](
                    shared_width, dt, name="shared")(xf, stats=True)
        xl = None
        if self.latent:
            with jax.named_scope(SCOPE_LM_LATENT):
                xl = Linear(self.latent, dt, name="latent_in")(xf)
        dl = self.latent or d
        # an expert's last matrix leads back to the rows' width
        w = [self.param(name, _normal(),
                        (held, self.width, dl) if name == "down"
                        else (held, dl, self.width), jnp.float32)
             for name in names]
        with jax.named_scope(SCOPE_LM_ROUTE):
            if self.router_hidden:
                scores, bias, carry = MlpRouter(
                    self.n_routed + 1, self.router_hidden, self.eps,
                    name="router")(xf, carry)
            else:
                scores, bias = self._linear_scores(xf)
            groups = None
            if self.n_group > 1:
                groups = chosen_groups(jax.lax.stop_gradient(scores + bias),
                                       self.n_group, self.topk_group)
            idx, weights = route(scores, bias, k, self.scaling, groups,
                                 normalise=k > 1)
            if self.balance_rate:
                weights = pulled(weights, bias, load_pull(
                    scores, idx, self.balance_rate))
            self.sow("intermediates", "choices", idx)
            # pairs are laid out choice-major, [k, N]: a [k, N, D] view pads
            # no axis to the TPU's tiles, which [N, k, D] would (k = 6)
            local = (idx - self.held_first).T
            mine = (local >= 0) & (local < held)
            # pairs sorted by held expert; the pairs of absent experts last
            key = jnp.where(mine, local, held).reshape(-1)
            order = jnp.argsort(key, stable=True)
            inv = jnp.argsort(order)
            sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32),
                            axis=0)[:held]
            routed, stats = routed_rows(
                row_rungs(n * k), self.form,
                xf.astype(dt) if xl is None else xl, order, inv, sizes, mine,
                weights, *w)
        if self.latent:
            with jax.named_scope(SCOPE_LM_LATENT):
                routed = Linear(d, dt, name="latent_out")(routed.astype(dt))
        seen = self.variable(COUNTERS, "expert_rows",
                             lambda: jnp.zeros((held,), jnp.float32))
        steps = self.variable(COUNTERS, "steps",
                              lambda: jnp.zeros((), jnp.float32))
        if train and not self.is_initializing():
            seen.value = seen.value + sizes.astype(jnp.float32)
            steps.value = steps.value + 1.0
        if self.n_group > 1:
            reached = self.variable(COUNTERS, "group_tokens",
                                    lambda: jnp.zeros((), jnp.float32))
            if train and not self.is_initializing():
                size = self.n_routed // self.n_group
                mine_g = groups[:, self.held_first // size:
                                (self.held_first + held - 1) // size + 1]
                reached.value = reached.value + jnp.sum(
                    jnp.any(mine_g, axis=1).astype(jnp.float32))
        if self.router_hidden:
            skipped = self.variable(COUNTERS, "skipped",
                                    lambda: jnp.zeros((), jnp.float32))
            if train and not self.is_initializing():
                skipped.value = skipped.value + jnp.sum(
                    (idx == self.n_routed).astype(jnp.float32))
        if stats:          # the form counts its live hidden units
            live = self.variable(COUNTERS, "live_units",
                                 lambda: jnp.zeros((), jnp.float32))
            if train and not self.is_initializing():
                units = (jnp.sum(sizes).astype(jnp.float32) * self.width
                         + n * shared_width)
                live.value = live.value + jax.lax.stop_gradient(
                    (stats[0] + sum(shared_stats)) / jnp.maximum(units, 1.0))
        return (out + routed.astype(dt)).reshape(b, t, d), carry

    def _linear_scores(self, xf):
        """The router as one ``[d, n_routed]`` matrix of the layer's own:
        -> ``(scores [N, n_routed], bias or None)``."""
        w_r = self.param("router", _normal(), (xf.shape[-1], self.n_routed),
                         jnp.float32)
        logits = jnp.dot(xf.astype(jnp.float32), w_r,
                         precision=jax.lax.Precision.HIGHEST)
        if self.score == "softmax":
            if self.n_group > 1:
                raise ValueError("a softmax router has no groups")
            return jax.nn.softmax(logits, axis=-1), None
        bias = self.param("e_score_correction_bias", _normal(0.01),
                          (self.n_routed,), jnp.float32)
        return jax.nn.sigmoid(logits), bias


@dataclasses.dataclass(frozen=True)
class LatentMoeSizes:
    """Every size of a :class:`LatentMoeLM` but its vocabulary (the
    registered names' values: :data:`LATENT_MOE_PRESETS`)."""

    dim: int
    heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    layers: int
    first_dense: int
    dense_width: int
    n_routed: int
    top_k: int
    expert_width: int
    n_shared: int
    routed_scaling: float
    rope_theta: float
    eps: float
    held_first: int = 0
    held_count: Optional[int] = None
    remat: bool = True
    dtype: Any = jnp.float32
    #: the mixer of each layer: ``"latent"``, ``"delta"``, ``"ssd"`` (a
    #: Mamba-2 state-space mixer), ``"cca"``, grouped-query attention over
    #: all the keys (``"full"``) or under a sliding window (``"window"``), or
    #: ``"none"`` (the layer is its MLP alone); empty: all latent
    mixers: tuple = ()
    #: the MLP of each layer: ``"dense"``, ``"sparse"`` or ``"none"`` (the
    #: layer is its mixer alone: with ``mixers`` a model whose layers are ONE
    #: sub-layer each); empty: the first ``first_dense`` dense, the others
    #: sparse
    mlps: tuple = ()
    #: what every MLP is, the dense one, an expert and the shared MLP
    #: (``transformer.MLP_FORMS``, :data:`EXPERT_FORMS`): ``"swiglu"`` or
    #: ``"relu2"``
    mlp_form: str = "swiglu"
    #: the routed experts' latent width, between two projections of the
    #: sparse layer's own (0: they work at ``dim``)
    moe_latent: int = 0
    #: the shared MLP's width where a configuration publishes it as a key of
    #: its own (``moe_shared_expert_intermediate_size``, beside
    #: ``n_shared_experts`` 1 and another ``moe_intermediate_size``: the
    #: file's ``n_shared`` then stays the published count); 0: ``n_shared *
    #: expert_width``
    shared_width: int = 0
    #: group-limited routing (1: none)
    n_group: int = 1
    topk_group: int = 1
    #: the latent mixer's per-head query / key norms; both mixers' output
    #: norm and head-wise gate
    qk_norm: bool = False
    out_gate: bool = False
    #: the delta mixer: its head size (q, k and v alike), the short
    #: convolution's positions and the log-decay's lower bound
    delta_head_dim: int = 128
    delta_conv: int = 4
    delta_lower_bound: float = -5.0
    #: the grouped-query mixers: ``heads`` query heads in a ``full`` layer
    #: and ``window_heads`` in a ``window`` layer, over ``kv_heads``
    #: key-value heads of ``v_dim`` channels each (queries and keys too);
    #: a query of a window layer sees ``window`` keys, its own included.
    #: Full layers turn the first ``rope`` channels of a head, by
    #: ``rope_theta`` under YaRN where ``yarn_factor`` is set (its original
    #: positions, two betas and attention factor beside it); window layers
    #: turn the whole head at ``window_rope_theta``. ``out_gate`` is here
    #: the head-wise sigmoid gate alone, without a norm
    kv_heads: int = 0
    window_heads: int = 0
    window: int = 0
    window_rope_theta: float = 10000.0
    yarn_factor: float = 0.0
    yarn_original: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.0
    #: the router's score function (:class:`SharedRoutedMoe`)
    score: str = "sigmoid"
    #: the state-space mixers: ``ssd_heads`` heads of ``ssd_head_dim``
    #: channels over a state of ``ssd_state``, a convolution of ``ssd_conv``
    #: positions, the recurrence in chunks of ``ssd_chunk``
    ssd_heads: int = 0
    ssd_head_dim: int = 64
    ssd_state: int = 128
    ssd_conv: int = 4
    ssd_chunk: int = SSD_CHUNK
    #: the four multipliers: ``h_0 = embed_scale * E[id]``; a block adds
    #: ``residual_scale`` times its mixer's and its MLP's output; a full
    #: layer's scores are ``q . k * attn_scale`` (None: ``v_dim^-0.5``);
    #: ``logits = head(h) / logit_scale``. ``tied_head``: the head is the
    #: embedding's table
    embed_scale: float = 1.0
    residual_scale: float = 1.0
    attn_scale: Optional[float] = None
    logit_scale: float = 1.0
    tied_head: bool = False
    #: the ``"cca"`` mixers (compressed convolutional attention: ``heads``
    #: query heads over ``kv_heads`` key-value heads of ``v_dim``, rotary
    #: over the first ``rope`` channels at ``rope_theta``): the positions of
    #: the depthwise and of the head-wise convolution
    cca_conv: tuple = (2, 2)
    #: the sparse layers' router as an MLP of this width that reads the
    #: router of the layer before (0: one matrix, :class:`SharedRoutedMoe`),
    #: with one output more that is no expert
    router_hidden: int = 0
    #: the sparse layers' balancing bias moves against the load between
    #: steps, by this rate (:func:`load_pull`; 0: it stays as loaded)
    balance_rate: float = 0.0
    #: ``h <- (a_r h + b_r) + (a_b branch + b_b)`` after each sub-layer, four
    #: learned vectors (:class:`ScaledMerge`), in place of ``residual_scale``
    scaled_residual: bool = False


class ScaledMerge(nn.Module):
    """``(res_scale * h + res_bias) + (branch_scale * branch +
    branch_bias)``: a residual add with a learned scale and bias a channel
    on the stream and on the branch, scales seeded at 1 and biases at 0; in
    float32, the sum in ``dtype``."""

    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, h, branch):
        def vec(name, init):
            return self.param(name, init, (h.shape[-1],), jnp.float32)

        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        h = h.astype(jnp.float32) * vec("res_scale", ones) + vec(
            "res_bias", zeros)
        branch = branch.astype(jnp.float32) * vec(
            "branch_scale", ones) + vec("branch_bias", zeros)
        return (h + branch).astype(self.dtype)


class LatentMoeBlock(nn.Module):
    """``h += r Mixer(RMSNorm(h))``; ``h += r Mlp(RMSNorm(h))`` with ``r``
    the sizes' ``residual_scale`` (or each add a :class:`ScaledMerge`): the
    mixer attention of one of four kinds (``attn``), the delta rule
    (``delta``) or the state-space recurrence (``ssd``), the MLP (``mlp``)
    ``"dense"``, of ``dense_width``, or ``"sparse"``, both in the sizes'
    ``mlp_form``. Either sub-layer may be ``"none"``: the layer is then the
    other alone, with its one norm. ``(h, carry) -> (h, carry)``: ``carry``
    is what a sparse layer's router hands the router of the next layer, None
    where it keeps none."""

    sizes: LatentMoeSizes
    mlp: str
    mixer: str = "latent"

    @nn.compact
    def __call__(self, h, train: bool = False, carry=None):
        c = self.sizes

        def add(h, branch, name):
            if c.scaled_residual:
                return ScaledMerge(c.dtype, name=name)(h, branch)
            if c.residual_scale != 1.0:
                branch = branch * jnp.asarray(c.residual_scale, branch.dtype)
            return h + branch

        def mix(a):
            """The layer's mixer on its normed input."""
            if self.mixer == "latent":
                a = LatentAttention(c.heads, c.nope, c.rope, c.v_dim,
                                    c.kv_rank, c.rope_theta, c.eps, c.dtype,
                                    c.qk_norm, c.out_gate, name="attn")(a)
            elif self.mixer == "delta":
                a = DeltaAttention(c.heads, c.delta_head_dim, c.delta_conv,
                                   c.delta_lower_bound, c.eps, c.dtype,
                                   name="delta")(a)
            elif self.mixer == "ssd":
                a = Mamba2Mixer(c.ssd_heads, c.ssd_head_dim, c.ssd_state,
                                c.ssd_conv, c.ssd_chunk, c.eps, c.dtype,
                                name="ssd")(a, train)
            elif self.mixer == "cca":
                a = CompressedConvAttention(c.heads, c.kv_heads, c.v_dim,
                                            c.rope, c.rope_theta, c.cca_conv,
                                            c.dtype, name="attn")(a)
            elif self.mixer == "window":
                a = GroupedAttention(c.window_heads, c.kv_heads, c.v_dim,
                                     c.v_dim, c.window_rope_theta,
                                     window=c.window, gate=c.out_gate,
                                     dtype=c.dtype, name="attn")(a)
            else:
                yarn, scale = None, 1.0
                if c.yarn_factor:
                    scale = c.yarn_attention_factor
                    yarn = tuple(yarn_frequencies(
                        c.rope, c.rope_theta, c.yarn_factor, c.yarn_original,
                        c.yarn_beta_fast, c.yarn_beta_slow).tolist())
                a = GroupedAttention(c.heads, c.kv_heads, c.v_dim, c.rope,
                                     c.rope_theta, yarn, scale,
                                     gate=c.out_gate, dtype=c.dtype,
                                     scale=c.attn_scale, name="attn")(a)
            return a

        if self.mixer != "none":
            a = RMSNorm(c.eps, c.dtype, name="attn_norm")(h)
            h = add(h, mix(a), "attn_merge")
        if self.mlp == "none":
            return h, carry
        m = RMSNorm(c.eps, c.dtype, name="mlp_norm")(h)
        if self.mlp == "sparse":
            m, carry = SharedRoutedMoe(
                c.n_routed, c.top_k, c.expert_width, c.n_shared,
                c.routed_scaling, c.held_first, c.held_count, c.dtype,
                c.n_group, c.topk_group, c.score, c.router_hidden, c.eps,
                c.balance_rate, c.mlp_form, c.moe_latent, c.shared_width,
                name="mlp")(m, train, carry)
        else:
            with jax.named_scope(SCOPE_LM_DENSE):
                m = MLP_FORMS[c.mlp_form](c.dense_width, c.dtype,
                                          name="mlp")(m)
        return add(h, m, "mlp_merge"), carry


class LatentMoeLM(nn.Module):
    """Decoder-only LM of blocks with sparse experts: an embedding,
    ``layers`` blocks (layer ``i``'s mixer is ``mixers[i]``, latent
    attention where none is named, its MLP ``mlps[i]``, where none is named
    dense in the first ``first_dense`` layers and sparse after; a layer may
    be one of the two alone),
    a final RMSNorm and a head of its own, or, ``tied_head``, the
    embedding's table again; no learned positions. Beside ``h`` the blocks
    hand on what a sparse layer's router gives the next one (None where no
    router keeps such a state; the first layer receives none). Each block
    is rematerialised in the backward pass (``remat``)."""

    vocab_size: int
    sizes: LatentMoeSizes

    @nn.compact
    def __call__(self, x, train: bool = False):
        c = self.sizes
        table = self.param("embed", _normal(), (self.vocab_size, c.dim),
                           jnp.float32)
        h = embed_rows(table, x.astype(jnp.int32))
        if c.embed_scale != 1.0:
            h = h * c.embed_scale
        h = h.astype(c.dtype)
        block = (nn.remat(LatentMoeBlock, static_argnums=(2,)) if c.remat
                 else LatentMoeBlock)
        mixers = c.mixers or ("latent",) * c.layers
        if len(mixers) != c.layers or set(mixers) - {
                "latent", "delta", "ssd", "full", "window", "cca", "none"}:
            raise ValueError(f"mixers {mixers}: one of 'latent' / 'delta' / "
                             f"'ssd' / 'full' / 'window' / 'cca' / 'none' for "
                             f"each of the {c.layers} layers")
        mlps = c.mlps or tuple("sparse" if i >= c.first_dense else "dense"
                               for i in range(c.layers))
        if (len(mlps) != c.layers or set(mlps) - {"dense", "sparse", "none"}
                or ("none", "none") in zip(mixers, mlps)):
            raise ValueError(f"mlps {mlps}: one of 'dense' / 'sparse' / "
                             f"'none' for each of the {c.layers} layers, and "
                             f"no layer without a mixer and without an MLP")
        carry = None
        for i in range(c.layers):
            h, carry = block(c, mlps[i], mixers[i],
                             name=f"layer_{i}")(h, train, carry)
        h = RMSNorm(c.eps, c.dtype, name="final_norm")(h)
        with jax.named_scope(SCOPE_LM_DENSE):
            if c.tied_head:
                logits = jnp.einsum("btd,vd->btv", h, table.astype(c.dtype),
                                    preferred_element_type=jnp.float32)
            else:
                logits = Linear(self.vocab_size, c.dtype, jnp.float32,
                                name="lm_head")(h)
        return logits / c.logit_scale if c.logit_scale != 1.0 else logits


def layer_counters(variables: dict) -> dict:
    """Host numbers from the ``counters`` the layers keep, sums over every
    training step since the variables were seeded, where the packed
    simulation round trained them: a sparse layer's ``rows.<layer>.<expert>``
    and ``steps.<layer>`` (and ``group_tokens.<layer>`` under a
    group-limited router, ``skipped.<layer>`` where a choice is no expert,
    ``live_units.<layer>`` where the experts' form counts its live hidden
    units), a state-space mixer's ``decay.<layer>`` (its mean
    ``exp(dt A)`` a step, summed) and ``steps.<layer>``. A model whose layers
    keep none gives ``{}``."""
    out = {}
    for layer, stats in sorted(variables.get(COUNTERS, {}).items()):
        ssd, mlp = stats.get("ssd", {}), stats.get("mlp", {})
        if "decay" in ssd:
            out[f"decay.{layer}"] = float(jax.device_get(ssd["decay"]))
            out[f"steps.{layer}"] = float(jax.device_get(ssd["steps"]))
        if "expert_rows" not in mlp:
            continue
        for e, rows in enumerate(jax.device_get(mlp["expert_rows"])):
            out[f"rows.{layer}.{e}"] = float(rows)
        out[f"steps.{layer}"] = float(jax.device_get(mlp["steps"]))
        for extra in ("group_tokens", "skipped", "live_units"):
            if extra in mlp:
                out[f"{extra}.{layer}"] = float(jax.device_get(mlp[extra]))
    return out


#: the registered names' sizes: what ``create_model(name, vocab)`` builds
#: when it is passed nothing else. ``kanana2_30b_a3b`` is one chip's share
#: (8 chips share each layer) of kakaocorp/kanana-2-30b-a3b-instruct-2601,
#: cut to 5 layers: ``benchmarks/configs/kanana2_30b_a3b.json`` holds the
#: same numbers in its ``model`` block and a test holds the two equal.
LATENT_MOE_PRESETS = {
    "kanana2_30b_a3b": dict(
        dim=2048, heads=32, nope=128, rope=64, v_dim=128, kv_rank=512,
        layers=5, first_dense=1, dense_width=6144, n_routed=128, top_k=6,
        expert_width=768, n_shared=2, routed_scaling=2.448, rope_theta=1e6,
        eps=1e-6, held_first=0, held_count=16, seq_len=4096),
    # one chip's share (64 chips share each layer) of the text decoder of
    # inclusionAI/Ling-3.0-flash-VL, cut to 7 layers: the leading dense layer
    # and one period of 6 (published layers 2 - 7), five delta-rule mixers to
    # one latent
    # (``benchmarks/configs/ling3_flash_vl.json``, held equal by a test)
    "ling3_flash_vl": dict(
        dim=2560, heads=32, nope=128, rope=64, v_dim=128, kv_rank=512,
        layers=7, first_dense=1, dense_width=6144, n_routed=512, top_k=8,
        expert_width=768, n_shared=1, routed_scaling=2.5, rope_theta=6e6,
        eps=1e-6, held_first=0, held_count=8, seq_len=4096,
        mixers=["delta", "delta", "delta", "delta", "latent", "delta",
                "delta"],
        n_group=8, topk_group=4, qk_norm=True, out_gate=True,
        delta_head_dim=128, delta_conv=4, delta_lower_bound=-5.0),
    # one chip's share (8 chips share each layer) of poolside/Laguna-XS.2,
    # cut to 5 layers: the leading dense layer and one period of 4
    # (published layers 1 - 4), window attention 3 : 1 full, 64 and 48 query
    # heads over 8 key-value heads, a softmax router
    # (``benchmarks/configs/laguna_xs2.json``, held equal by a test)
    "laguna_xs2": dict(
        dim=2048, heads=48, nope=64, rope=64, v_dim=128, kv_rank=0,
        layers=5, first_dense=1, dense_width=8192, n_routed=256, top_k=8,
        expert_width=512, n_shared=1, routed_scaling=2.5, rope_theta=5e5,
        eps=1e-6, held_first=0, held_count=32, seq_len=4096,
        mixers=["full", "window", "window", "window", "full"],
        out_gate=True, kv_heads=8, window_heads=64, window=512,
        window_rope_theta=1e4, yarn_factor=64.0, yarn_original=4096,
        yarn_beta_fast=64.0, yarn_beta_slow=1.0,
        yarn_attention_factor=1.4158883083359672, score="softmax"),
    # the first pipeline stage (one whole period of ``layer_types``: the
    # published layers 0 - 9, nine Mamba-2 mixers to one position-free
    # grouped-query attention layer, every MLP dense) of
    # ibm-granite/granite-4.0-h-micro, with an eighth of the tied table's rows
    # (``benchmarks/configs/granite4_h_micro.json``, held equal by a test)
    "granite4_h_micro": dict(
        dim=2048, heads=32, nope=64, rope=0, v_dim=64, kv_rank=0, layers=10,
        first_dense=10, dense_width=8192, n_routed=0, top_k=0,
        expert_width=0, n_shared=0, routed_scaling=1.0, rope_theta=1e4,
        eps=1e-5, seq_len=4096,
        mixers=["ssd"] * 5 + ["full"] + ["ssd"] * 4, kv_heads=8,
        ssd_heads=64, ssd_head_dim=64, ssd_state=128, ssd_conv=4,
        ssd_chunk=256, embed_scale=12.0, residual_scale=0.22,
        attn_scale=0.015625, logit_scale=8.0, tied_head=True),
    # one of two expert-parallel chips' share of the first six layers of
    # Zyphra/ZAYA1-8B: compressed convolutional attention in a latent of half
    # the model's width, an MLP router that reads the router of the layer
    # before it, ONE choice a token of 16 experts (8 held) or none, scaled
    # residuals, an eighth of the tied table's rows
    # (``benchmarks/configs/zaya1_8b.json``, held equal by a test)
    "zaya1_8b": dict(
        dim=2048, heads=8, nope=64, rope=64, v_dim=128, kv_rank=0, layers=6,
        first_dense=0, dense_width=0, n_routed=16, top_k=1,
        expert_width=2048, n_shared=0, routed_scaling=1.0, rope_theta=5e6,
        eps=1e-5, held_first=0, held_count=8, seq_len=4096,
        mixers=["cca"] * 6, kv_heads=2, cca_conv=[2, 2], router_hidden=256,
        balance_rate=130.0, scaled_residual=True, tied_head=True),
    # one of 64 chips' share of the first eleven layers (one pipeline stage of
    # eight, a whole period ``MEMEMEM*EME``) of
    # nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, each layer ONE sub-layer:
    # a Mamba-2 mixer (one of its 8 groups, 16 heads: the mixers are
    # tensor-parallel over 8 chips), position-free attention (4 query heads
    # over 1 key-value head) or a sparse MLP of squared-ReLU experts in a
    # latent of 1,024 (8 of 512 held, 22 a token) beside a shared MLP of
    # 5,376, an eighth of the untied table's rows
    # (``benchmarks/configs/nemotron3_super_120b.json``, held equal by a test)
    "nemotron3_super": dict(
        dim=4096, heads=4, nope=128, rope=0, v_dim=128, kv_rank=0, layers=11,
        first_dense=0, dense_width=0, n_routed=512, top_k=22,
        expert_width=2688, n_shared=1, routed_scaling=5.0, rope_theta=1e4,
        eps=1e-5, held_first=0, held_count=8, seq_len=4096,
        mixers=["ssd", "none", "ssd", "none", "ssd", "none", "ssd", "full",
                "none", "ssd", "none"],
        mlps=["none", "sparse", "none", "sparse", "none", "sparse", "none",
              "none", "sparse", "none", "sparse"],
        kv_heads=1, ssd_heads=16, ssd_head_dim=64, ssd_state=128, ssd_conv=4,
        ssd_chunk=256, mlp_form="relu2", moe_latent=1024, shared_width=5376),
    "nemotron3_super_tiny": dict(
        dim=32, heads=2, nope=16, rope=0, v_dim=16, kv_rank=0, layers=4,
        first_dense=0, dense_width=0, n_routed=16, top_k=4, expert_width=24,
        n_shared=1, routed_scaling=5.0, rope_theta=1e4, eps=1e-5,
        held_first=0, held_count=4, seq_len=32,
        mixers=["ssd", "none", "full", "none"],
        mlps=["none", "sparse", "none", "sparse"], kv_heads=1, ssd_heads=8,
        ssd_head_dim=8, ssd_state=16, ssd_conv=4, ssd_chunk=8,
        mlp_form="relu2", moe_latent=16, shared_width=48),
    "zaya1_tiny": dict(
        dim=32, heads=4, nope=4, rope=4, v_dim=8, kv_rank=0, layers=3,
        first_dense=0, dense_width=0, n_routed=16, top_k=1, expert_width=32,
        n_shared=0, routed_scaling=1.0, rope_theta=5e6, eps=1e-5,
        held_first=0, held_count=8, seq_len=32, mixers=["cca"] * 3,
        kv_heads=2, cca_conv=[2, 2], router_hidden=16, balance_rate=4.0,
        scaled_residual=True, tied_head=True),
    "granite4h_tiny": dict(
        dim=32, heads=4, nope=8, rope=0, v_dim=8, kv_rank=0, layers=4,
        first_dense=4, dense_width=64, n_routed=0, top_k=0, expert_width=0,
        n_shared=0, routed_scaling=1.0, rope_theta=1e4, eps=1e-5, seq_len=32,
        mixers=["ssd", "ssd", "full", "ssd"], kv_heads=2, ssd_heads=8,
        ssd_head_dim=8, ssd_state=16, ssd_conv=4, ssd_chunk=8,
        embed_scale=12.0, residual_scale=0.22, attn_scale=0.125,
        logit_scale=8.0, tied_head=True),
    "laguna_tiny": dict(
        dim=32, heads=6, nope=8, rope=8, v_dim=16, kv_rank=0, layers=3,
        first_dense=1, dense_width=96, n_routed=16, top_k=4, expert_width=24,
        n_shared=1, routed_scaling=2.5, rope_theta=5e5, eps=1e-6,
        held_first=0, held_count=4, seq_len=32,
        mixers=["full", "window", "full"],
        out_gate=True, kv_heads=2, window_heads=8, window=8,
        window_rope_theta=1e4, yarn_factor=4.0, yarn_original=8,
        yarn_beta_fast=4.0, yarn_beta_slow=1.0,
        yarn_attention_factor=1.1386294361119891, score="softmax"),
    "ling3_tiny": dict(
        dim=32, heads=2, nope=16, rope=8, v_dim=16, kv_rank=16, layers=4,
        first_dense=1, dense_width=96, n_routed=16, top_k=4, expert_width=24,
        n_shared=1, routed_scaling=2.5, rope_theta=6e6, eps=1e-6,
        held_first=0, held_count=4, seq_len=32,
        mixers=["delta", "delta", "latent", "delta"],
        n_group=4, topk_group=2, qk_norm=True, out_gate=True,
        delta_head_dim=16, delta_conv=4, delta_lower_bound=-5.0),
    "kanana2_tiny": dict(
        dim=32, heads=2, nope=16, rope=8, v_dim=16, kv_rank=16, layers=3,
        first_dense=1, dense_width=96, n_routed=8, top_k=2, expert_width=24,
        n_shared=2, routed_scaling=2.448, rope_theta=1e6, eps=1e-6,
        held_first=0, held_count=4, seq_len=16),
}


def _latent_moe_bundle(name: str, output_dim: int, **kw) -> ModelBundle:
    sizes = {**LATENT_MOE_PRESETS[name], **kw}
    seq_len = sizes.pop("seq_len")
    for key in ("mixers", "mlps", "cca_conv"):
        if key in sizes:
            sizes[key] = tuple(sizes[key])
    module = LatentMoeLM(vocab_size=output_dim, sizes=LatentMoeSizes(**sizes))
    return ModelBundle(
        name=name, module=module, input_shape=(seq_len,),
        input_dtype=jnp.int32, task="nwp",
        # parameter shapes do not depend on the sequence length
        init_shape=(8,), counters=layer_counters)


@register_model("kanana2_30b_a3b")
def _kanana2(output_dim: int = 16032, **kw):
    return _latent_moe_bundle("kanana2_30b_a3b", output_dim or 16032, **kw)


@register_model("ling3_flash_vl")
def _ling3(output_dim: int = 19648, **kw):
    return _latent_moe_bundle("ling3_flash_vl", output_dim or 19648, **kw)


@register_model("laguna_xs2")
def _laguna(output_dim: int = 12544, **kw):
    return _latent_moe_bundle("laguna_xs2", output_dim or 12544, **kw)


@register_model("granite4_h_micro")
def _granite4h(output_dim: int = 12544, **kw):
    return _latent_moe_bundle("granite4_h_micro", output_dim or 12544, **kw)


@register_model("zaya1_8b")
def _zaya1(output_dim: int = 32784, **kw):
    return _latent_moe_bundle("zaya1_8b", output_dim or 32784, **kw)


@register_model("nemotron3_super")
def _nemotron3s(output_dim: int = 16384, **kw):
    return _latent_moe_bundle("nemotron3_super", output_dim or 16384, **kw)


@register_model("nemotron3_super_tiny")
def _nemotron3s_tiny(output_dim: int = 64, **kw):
    return _latent_moe_bundle("nemotron3_super_tiny", output_dim or 64, **kw)


@register_model("zaya1_tiny")
def _zaya1_tiny(output_dim: int = 64, **kw):
    return _latent_moe_bundle("zaya1_tiny", output_dim or 64, **kw)


@register_model("granite4h_tiny")
def _granite4h_tiny(output_dim: int = 64, **kw):
    return _latent_moe_bundle("granite4h_tiny", output_dim or 64, **kw)


@register_model("laguna_tiny")
def _laguna_tiny(output_dim: int = 64, **kw):
    return _latent_moe_bundle("laguna_tiny", output_dim or 64, **kw)


@register_model("ling3_tiny")
def _ling3_tiny(output_dim: int = 64, **kw):
    return _latent_moe_bundle("ling3_tiny", output_dim or 64, **kw)


@register_model("kanana2_tiny")
def _kanana2_tiny(output_dim: int = 64, **kw):
    return _latent_moe_bundle("kanana2_tiny", output_dim or 64, **kw)
