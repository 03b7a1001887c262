"""The delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692), computed in chunks: the first op here whose state runs
along the sequence.

A head keeps a state ``S [dk, dv]`` (float32, zeros before position 0). At
position ``t``, with a key ``k_t`` (L2-normalised), a value ``v_t``, a
per-channel decay ``alpha_t = exp(g_t)`` in ``(0, 1]^dk`` and a step
``beta_t`` in ``(0, 1)``::

    S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`kda_recurrent` is that, token by token (the tests' yardstick).
:func:`kda_chunked` computes the same in chunks of ``C`` positions (the WY
form). With ``G_i`` the sum of ``g`` from the chunk's first position to
``i`` (so ``G_i <= 0``) and ``u_i = beta_i (v_i - S~_i^T k_i)`` the delta
position ``i`` writes (``S~_i`` the decayed state it meets), the chunk
unrolls to ``S_i = diag(e^{G_i}) S_0 + sum_{j<=i} diag(e^{G_i-G_j}) k_j
u_j^T``, so::

    A_ij = beta_i sum_c k_ic k_jc e^{G_ic - G_jc}     (j <  i)
    P_ij =        sum_c q_ic k_jc e^{G_ic - G_jc}     (j <= i)
    (I + A) [W_v | W_k] = [beta v | beta (k e^{G})]   (a triangular solve)
    U   = W_v - W_k S_0
    O   = (q e^{G}) S_0 + P U
    S_C = diag(e^{G_C}) S_0 + (k e^{G_C - G})^T U

Everything but the three lines with ``S_0`` is computed for all chunks at
once on the ``jax.numpy`` path; a ``lax.scan`` over the chunks carries the
state.

**No ``exp(+G)`` is ever formed.** ``e^{G_i - G_j}`` does not factor into
``e^{G_i} e^{-G_j}`` safely: at the gate's bound of -5 a position, 64
positions are ``e^{320}``. Rows are taken in sub-blocks of ``sub``
positions, each about a reference row ``r`` (its first): ``e^{G_i - G_r}``
on the row side is at most 1, and ``e^{G_r - G_j}`` on the column side is
at most 1 for the columns before the sub-block, at most ``e^{5 (sub - 1)}``
inside it (``e^{75}`` at 16: float32 and bfloat16 share the exponent), and
is SET to zero for the columns after it, which the causal mask drops
anyway. So a sub-block's row of ``A`` or ``P`` is one matmul of bounded
operands, exact in exponent.

The state, the cumulative log-decays and the solve are float32; the
matmuls take their operands in ``dtype`` (the module's) and accumulate in
float32. Two paths, one algorithm (``impl``; ``'auto'`` is the kernels on a
TPU):

- ``'xla'``: plain ``jax.numpy`` with JAX's own backward through the scan.
  The intra-chunk part is recomputed (``jax.checkpoint``), and so are the
  chunk steps between two kept states: a state every ``keep`` chunks. The
  CPU's program, the parity yardstick, and what a shape the kernels do not
  tile falls back to.
- ``'pallas'``: a kernel pair under a ``jax.custom_vjp``. A grid step is a
  GROUP of ``keep`` chunks of a few heads; the chunk axis of the grid is
  sequential and each head's state stays in VMEM from step to step. Both
  kernels read ``q, k, v, g, beta`` as they lie in HBM and make a chunk's
  operands themselves (:func:`_chunk_operands`: the same products of the
  same bounded factors as :func:`_intra`; the solve by substitution in
  float32), so the stacked operands never exist. The forward kernel
  (:func:`_kda_fwd`) writes ``o`` and, differentiated, the state each group
  starts from. The backward kernel (:func:`_kda_bwd`) walks the groups from
  the last: it rebuilds a group's chunk-start states in VMEM from the kept
  one, then takes the chunks in reverse with the state's cotangent in VMEM:
  the chunk step's adjoint by hand (:func:`_step_adjoint`), and JAX's own
  backward of :func:`_chunk_operands`, traced into the kernel, from the
  operands' cotangents to the inputs'. Between the two calls only the
  inputs and the kept states live in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from fedml_tpu.ops.attention import _pick_impl
from fedml_tpu.ops.common import interpret

#: positions a chunk and a sub-block, the chunks between two states the
#: backward pass keeps, and the heads a grid step of the kernels takes (the
#: largest divisor of B x H up to it: independent chains of small matmuls
#: the scheduler interleaves). Alone on the v5e at [1, 32, 4096, 128]
#: (``tools/kda_sweep.py``; PERF.md, PR 31), forward / forward + backward in
#: ms: the kernels at chunk:keep:heads 64:1:4 2.27 / 7.63, 64:2:2 2.28 /
#: 8.48, 128:1:2 2.04 / 7.94, 32:1:4 3.39 / 8.82, 32:2:4 3.32 / 9.68, 16:4:4
#: 4.07 / 15.49; the ``jax.numpy`` scan 4.98 / 18.00 at 32 kept every 4th
#: (7.29 / 25.01 at 64, 14.61 / 49.13 at 128: PR 30). A kept state is 64 KB
#: a head: 134 MB a layer at 64:1, between a block's two kernel calls only
KDA_CHUNK = 64
KDA_SUB = 16
KDA_KEEP = 1
KDA_HEADS = 4


def kda_recurrent(q, k, v, g, beta):
    """``q, k, g [B, H, T, dk]``, ``v [B, H, T, dv]``, ``beta [B, H, T]`` ->
    ``o [B, H, T, dv]``: the recurrence itself, one position at a time, in
    float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    hi = lax.Precision.HIGHEST

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = s * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", s, kt,
                                             precision=hi))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=hi)

    b, h, _, dk = q.shape
    s0 = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v, g, beta))
    _, o = lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 2)


def _mm(eq: str, a, b, dtype):
    # float32 operands mean float32 products: the TPU's default would round
    # them to bfloat16 all the same
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32,
                      precision=(lax.Precision.HIGHEST
                                 if dtype == jnp.float32 else None))


def _intra(q, k, v, g, beta, sub: int, dtype):
    """All chunks at once. ``q, k, g [..., N, C, dk]``, ``v [..., N, C,
    dv]``, ``beta [..., N, C]`` -> the scan's per-chunk operands ``(w_v
    [.., C, dv], w_k [.., C, dk], p [.., C, C], q_in [.., C, dk], k_out
    [.., C, dk], decay [.., dk])``."""
    f32 = jnp.float32
    c, dk, dv = q.shape[-2], q.shape[-1], v.shape[-1]
    n_sub = c // sub
    qf, kf, bf = q.astype(f32), k.astype(f32), beta.astype(f32)[..., None]
    G = jnp.cumsum(g.astype(f32), axis=-2)                    # <= 0
    lead = G.shape[:-2]

    def blocks(a):                                            # [.., I, sub, dk]
        return a.reshape(lead + (n_sub, sub, dk))

    # the reference row of each sub-block: its first
    ref = blocks(G)[..., 0, :]                                # [.., I, dk]
    row = jnp.exp(blocks(G) - ref[..., None, :])              # <= 1
    # columns of sub-block I: every position up to its last, about ref_I
    pos = jnp.arange(c)
    seen = (pos < (jnp.arange(n_sub)[:, None] + 1) * sub)[..., None]  # [I, C, 1]
    diff = ref[..., :, None, :] - G[..., None, :, :]          # [.., I, C, dk]
    col = jnp.where(seen, jnp.exp(jnp.where(seen, diff, 0.0)), 0.0)
    k_col = kf[..., None, :, :] * col
    a = _mm("...isd,...ijd->...isj", blocks(kf) * row, k_col, dtype).reshape(
        lead + (c, c))
    p = _mm("...isd,...ijd->...isj", blocks(qf) * row, k_col, dtype).reshape(
        lead + (c, c))
    a = jnp.where(pos[:, None] > pos[None, :], a, 0.0) * bf
    p = jnp.where(pos[:, None] >= pos[None, :], p, 0.0)
    decayed = jnp.exp(G)                                      # from the start
    rhs = jnp.concatenate([bf * v.astype(f32), bf * kf * decayed], axis=-1)
    w = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=f32), rhs, lower=True, unit_diagonal=True)
    g_last = G[..., -1:, :]
    # what the scan only multiplies goes to it in the matmuls' dtype
    return (w[..., :dv], w[..., dv:].astype(dtype), p.astype(dtype),
            (qf * decayed).astype(dtype),
            (kf * jnp.exp(g_last - G)).astype(dtype),
            jnp.exp(g_last[..., 0, :]))


def _step(state, x, dtype):
    """One chunk of the ``jax.numpy`` scan, every head at once: ``state [B,
    H, dk, dv]`` and the chunk's operands -> ``(state', o)``."""
    w_v, w_k, p, q_in, k_out, decay = x
    u = w_v - _mm("bhck,bhkv->bhcv", w_k, state, dtype)
    o = (_mm("bhck,bhkv->bhcv", q_in, state, dtype)
         + _mm("bhcj,bhjv->bhcv", p, u, dtype))
    state = (state * decay[..., None]
             + _mm("bhck,bhcv->bhkv", k_out, u, dtype))
    return state, o


# ---------------------------------------------------------------------------
# Pallas path. The kernels hold a head's state TRANSPOSED (``S^T [dv, dk]``):
# the decay then scales lanes, a ``[1, dk]`` row, and its cotangent is a sum
# over sublanes.
# ---------------------------------------------------------------------------

_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _dot(a, b, contract, dtype):
    """``_mm``'s product of two 2-D blocks: operands in ``dtype``, float32
    out. ``contract``: ``_NN`` ``a b``, ``_NT`` ``a b^T``, ``_TN`` ``a^T
    b``. Differentiated (the backward kernel runs JAX's backward of
    :func:`_chunk_operands` inside itself), the cotangent is an operand like
    any other: in ``dtype``, as the TPU's default precision takes it on the
    ``jax.numpy`` path."""
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), (contract, ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST if dtype == jnp.float32 else None)


def _dot_bwd(contract, dtype, operands, ct):
    a, b = operands
    da, db = {_NN: ((ct, b, _NT), (a, ct, _TN)),
              _NT: ((ct, b, _NN), (ct, a, _TN)),
              _TN: ((b, ct, _NT), (a, ct, _NN))}[contract]
    return _dot(*da, dtype).astype(a.dtype), _dot(*db, dtype).astype(b.dtype)


_dot.defvjp(lambda a, b, contract, dtype: (_dot(a, b, contract, dtype), (a, b)),
            _dot_bwd)


def _running_sum(x, reverse: bool):
    """Sums along the rows of ``x [C, d]``, from the first row down (or from
    the last up), by doubling: ``log2 C`` sublane rotations."""
    from jax.experimental.pallas import tpu as pltpu

    c = x.shape[0]
    pos = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    step = 1
    while step < c:
        moved = pltpu.roll(x, c - step if reverse else step, 0)
        x = x + jnp.where(pos < c - step if reverse else pos >= step, moved,
                          0.0)
        step *= 2
    return x


@jax.custom_vjp
def _cumsum_rows(g):
    """The cumulative log-decay from the chunk's first position."""
    return _running_sum(g, reverse=False)


_cumsum_rows.defvjp(lambda g: (_running_sum(g, reverse=False), None),
                    lambda _, ct: (_running_sum(ct, reverse=True),))


def _chunk_operands(q, k, v, g, beta, *, sub, dtype):
    """:func:`_intra` for ONE chunk of one head, inside a kernel: ``q, k, g
    [C, dk]``, ``v [C, dv]`` float32, ``beta [C, 1]`` -> ``(w_v, w_k, p,
    q_in, k_out, decay [1, dk])`` as :func:`_intra` returns them. The same
    products of the same bounded factors; the solve by substitution, a
    sub-block's columns one after another on the VPU (float32), the
    sub-blocks before it by one float32 product."""
    f32 = jnp.float32
    c, dk = q.shape
    n_sub = c // sub
    pos = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    G = _cumsum_rows(g)
    a_rows, p_rows = [], []
    for i in range(n_sub):
        blk = slice(i * sub, (i + 1) * sub)
        ref = G[i * sub:i * sub + 1]                      # [1, dk]
        row = jnp.exp(G[blk] - ref)                       # <= 1
        seen = pos < (i + 1) * sub
        col = jnp.where(seen, jnp.exp(jnp.where(seen, ref - G, 0.0)), 0.0)
        ap = _dot(jnp.concatenate([k[blk] * row, q[blk] * row]), k * col,
                  _NT, dtype)                             # [2 sub, C]
        a_rows.append(ap[:sub])
        p_rows.append(ap[sub:])
    at = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    a = jnp.where(pos > at, jnp.concatenate(a_rows), 0.0) * beta
    p = jnp.where(pos >= at, jnp.concatenate(p_rows), 0.0)
    decayed = jnp.exp(G)
    # (I + a) [w_v | w_k] = [beta v | beta k e^G]
    w_v, w_k = [], []
    for i in range(n_sub):
        blk = slice(i * sub, (i + 1) * sub)
        r_v, r_k = beta[blk] * v[blk], beta[blk] * k[blk] * decayed[blk]
        if i:
            before = a[blk, :i * sub]
            r_v = r_v - _dot(before, jnp.concatenate(w_v), _NN, f32)
            r_k = r_k - _dot(before, jnp.concatenate(w_k), _NN, f32)
        own = a[blk, blk]
        for j in range(sub - 1):
            cj = own[:, j:j + 1]                          # zero up to row j
            r_v = r_v - cj * r_v[j:j + 1]
            r_k = r_k - cj * r_k[j:j + 1]
        w_v.append(r_v)
        w_k.append(r_k)
    g_last = G[c - 1:c]
    return (jnp.concatenate(w_v), jnp.concatenate(w_k).astype(dtype),
            p.astype(dtype), (q * decayed).astype(dtype),
            (k * jnp.exp(g_last - G)).astype(dtype), jnp.exp(g_last))


def _delta(st, w_v, w_k, dtype):
    """``u = w_v - w_k S`` ``[C, dv]``, from the transposed state."""
    return w_v - _dot(w_k, st, _NT, dtype)


def _next_state(st, u, k_out, decay, dtype):
    """``S'^T = S^T diag(decay) + u^T k_out``; ``decay [1, dk]``."""
    return st * decay + _dot(u, k_out, _TN, dtype)


def _step_adjoint(st, u, dst, do, w_k, p, q_in, k_out, decay, dtype):
    """The chunk step's adjoint by hand (it is bilinear). ``st`` the chunk's
    transposed start state, ``u`` its delta, ``dst`` the cotangent of the
    NEXT chunk's (transposed) state, ``do [C, dv]`` -> ``(dst of this
    chunk's start state, (dw_v, dw_k, dp, dq_in, dk_out, ddecay [1,
    dk]))``."""
    du = _dot(p, do, _TN, dtype) + _dot(k_out, dst, _NT, dtype)
    grads = (du, -_dot(du, st, _NN, dtype), _dot(do, u, _NT, dtype),
             _dot(do, st, _NN, dtype), _dot(u, dst, _NN, dtype),
             jnp.sum(st * dst, axis=0, keepdims=True))
    dst = (_dot(do, q_in, _TN, dtype) + dst * decay
           - _dot(du, w_k, _TN, dtype))
    return dst, grads


def _each_head(heads: int, head):
    """``head(h)`` for every head of a grid step's block. One trace of the
    body, unrolled when the kernel is lowered: the heads stay independent
    chains the scheduler interleaves, and the Python trace of a round program
    (set-up) holds a chunk's few hundred ops once a kernel, not once a
    head."""
    lax.fori_loop(0, heads, lambda h, _: head(h), None, unroll=True)


def _chunk_inputs(refs, h, rows):
    """One head's chunk off the kernels' five input blocks, in float32."""
    return tuple(ref[h, rows].astype(jnp.float32) for ref in refs)


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, chunk,
                    sub, group, dtype):
    """``rest`` is the states' scratch, and before it, where the backward
    follows, the output for the state each group starts from."""
    import jax.experimental.pallas as pl

    st_s = rest[-1]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        st_s[...] = jnp.zeros_like(st_s)

    if len(rest) > 1:
        rest[0][:, 0] = st_s[...]
    inputs = (q_ref, k_ref, v_ref, g_ref, beta_ref)

    def head(h):
        st = st_s[h]
        for j in range(group):
            rows = slice(j * chunk, (j + 1) * chunk)
            w_v, w_k, p, q_in, k_out, decay = _chunk_operands(
                *_chunk_inputs(inputs, h, rows), sub=sub, dtype=dtype)
            u = _delta(st, w_v, w_k, dtype)
            o_ref[h, rows] = (_dot(q_in, st, _NT, dtype)
                              + _dot(p, u, _NN, dtype))
            st = _next_state(st, u, k_out, decay, dtype)
        st_s[h] = st

    _each_head(q_ref.shape[0], head)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, kept_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dst_s, st_s, *,
                    chunk, sub, group, dtype):
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(1) == 0)               # the LAST group: no later one
    def _start():
        dst_s[...] = jnp.zeros_like(dst_s)

    inputs = (q_ref, k_ref, v_ref, g_ref, beta_ref)
    operands = functools.partial(_chunk_operands, sub=sub, dtype=dtype)

    def head(h):
        # the group's chunk-start states, forward from the kept one
        st = kept_ref[h, 0]
        for j in range(group):
            st_s[h, j] = st
            if j + 1 < group:
                rows = slice(j * chunk, (j + 1) * chunk)
                w_v, w_k, _, _, k_out, decay = operands(
                    *_chunk_inputs(inputs, h, rows))
                st = _next_state(st, _delta(st, w_v, w_k, dtype), k_out, decay,
                                 dtype)
        dst = dst_s[h]
        for j in reversed(range(group)):
            rows = slice(j * chunk, (j + 1) * chunk)
            # the chunk's operands again, with JAX's backward of them; the
            # step's adjoint by hand in between
            made, backward = jax.vjp(operands, *_chunk_inputs(inputs, h, rows))
            w_v, w_k, p, q_in, k_out, decay = made
            st = st_s[h, j]
            dst, grads = _step_adjoint(
                st, _delta(st, w_v, w_k, dtype), dst, do_ref[h, rows], w_k, p,
                q_in, k_out, decay, dtype)
            grads = backward(tuple(g.astype(m.dtype)
                                   for g, m in zip(grads, made)))
            for ref, grad in zip((dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref),
                                 grads):
                ref[h, rows] = grad.astype(ref.dtype)
        dst_s[h] = dst

    _each_head(q_ref.shape[0], head)


def _grid_specs(heads, rows, n_groups, reverse):
    """BlockSpecs over the grid ``(head blocks, groups)``: ``rows(d)`` for a
    ``[BH, T, d]`` array, ``per_group(a, b)`` for a ``[BH, groups, a, b]``
    one; ``reverse`` walks the groups from the last."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def at(g):
        return n_groups - 1 - g if reverse else g

    def spec(block, index_map):
        return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)

    return (lambda d: spec((heads, rows, d), lambda i, g: (i, at(g), 0)),
            lambda a, b: spec((heads, 1, a, b), lambda i, g: (i, at(g), 0, 0)),
            pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")))


# Each kernel call is a jitted function of its own, as the attention's are:
# traced once for a model's six layers, and the trace names its calls.

_STATIC = ("chunk", "sub", "group", "heads", "dtype", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC + ("keep_states",))
def _kda_fwd(q, k, v, g, beta, *, chunk: int, sub: int, group: int, heads: int,
             dtype, interpret: bool, keep_states: bool):
    """``q, k, g [BH, T, dk]``, ``v [BH, T, dv]``, ``beta [BH, T, 1]`` ->
    ``[o [BH, T, dv]]`` float32, and with ``keep_states`` after it the state
    each group of ``group`` chunks starts from, ``[BH, groups, dv, dk]``
    float32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, t, dk), dv = q.shape, v.shape[-1]
    n_groups = t // (chunk * group)
    rows, per_group, params = _grid_specs(heads, chunk * group, n_groups,
                                          reverse=False)
    out_specs = [rows(dv)]
    out_shape = [jax.ShapeDtypeStruct((bh, t, dv), jnp.float32)]
    if keep_states:
        out_specs.append(per_group(dv, dk))
        out_shape.append(jax.ShapeDtypeStruct((bh, n_groups, dv, dk),
                                              jnp.float32))
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, chunk=chunk, sub=sub, group=group,
                          dtype=dtype),
        grid=(bh // heads, n_groups),
        in_specs=[rows(dk), rows(dk), rows(dv), rows(dk), rows(1)],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=params, interpret=interpret,
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _kda_bwd(q, k, v, g, beta, kept, do, *, chunk: int, sub: int, group: int,
             heads: int, dtype, interpret: bool):
    """The inputs and kept states of :func:`_kda_fwd` and ``do [BH, T, dv]``
    -> the five inputs' cotangents, each in its input's shape and dtype.
    Groups from the last to the first."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bh, t, dk), dv = q.shape, v.shape[-1]
    n_groups = t // (chunk * group)
    rows, per_group, params = _grid_specs(heads, chunk * group, n_groups,
                                          reverse=True)
    inputs = (q, k, v, g, beta)
    specs = [rows(dk), rows(dk), rows(dv), rows(dk), rows(1)]
    return pl.pallas_call(
        functools.partial(_kda_bwd_kernel, chunk=chunk, sub=sub, group=group,
                          dtype=dtype),
        grid=(bh // heads, n_groups),
        in_specs=specs + [per_group(dv, dk), rows(dv)],
        out_specs=specs,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in inputs],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32),        # dS^T
                        pltpu.VMEM((heads, group, dv, dk), jnp.float32)],  # S^T
        compiler_params=params, interpret=interpret,
    )(*inputs, kept, do)


@functools.lru_cache(maxsize=None)
def _kernels_with_vjp(chunk: int, sub: int, keep: int, heads: int, dtype,
                      interpret: bool):
    """``kda_chunked`` on the Pallas path, ``q, k, v, g [B H, T, .]``, ``beta
    [B H, T, 1]`` -> ``o [B H, T, dv]``: kernels both ways. Saved for the
    backward: the inputs and a state every ``keep`` chunks."""
    common = dict(chunk=chunk, sub=sub, group=keep, heads=heads, dtype=dtype,
                  interpret=interpret)

    @jax.custom_vjp
    def f(*inputs):
        return _kda_fwd(*inputs, keep_states=False, **common)[0]

    def fwd(*inputs):
        o, kept = _kda_fwd(*inputs, keep_states=True, **common)
        return o, (inputs, kept)

    def bwd(res, do):
        inputs, kept = res
        return tuple(_kda_bwd(*inputs, kept, do.astype(jnp.float32), **common))

    f.defvjp(fwd, bwd)
    return f


def _heads_a_step(bh: int) -> int:
    h = KDA_HEADS
    while bh % h:
        h -= 1
    return h


def kernel_tiles(t: int, dk: int, dv: int, chunk: int, sub: int) -> bool:
    """Whether the kernel pair takes the shape: whole chunks of ``chunk``
    positions, head widths in whole 128-lane tiles, chunks and sub-blocks in
    whole sublane tiles (16 rows of bfloat16, 8 of float32)."""
    return (t >= chunk and t % chunk == 0 and chunk % 16 == 0
            and sub % 8 == 0 and dk % 128 == 0 and dv % 128 == 0)


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "keep", "dtype",
                                             "impl"))
def kda_chunked(q, k, v, g, beta, *, chunk: int = KDA_CHUNK,
                sub: int = KDA_SUB, keep: int = KDA_KEEP, dtype=jnp.bfloat16,
                impl: str = "auto"):
    """The recurrence of :func:`kda_recurrent` in chunks of ``chunk``
    positions (clamped to ``T``; ``T`` a multiple of it, ``chunk`` of
    ``sub``), a state kept for the backward pass every ``keep`` chunks (every
    chunk where ``keep`` does not divide their number). ``g`` is the
    log-decay, ``<= 0``; returns ``o`` in float32. ``impl``: ``'pallas'``
    the kernel pair (a shape it does not tile takes the other path all the
    same), ``'xla'`` the ``jax.numpy`` scan, ``'auto'`` the kernels on a
    TPU."""
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, t)
    s = min(sub, c)
    if t % c or c % s:
        raise ValueError(f"kda_chunked: T {t} is no multiple of the chunk "
                         f"{c}, or the chunk none of the sub-block {s}")
    n = t // c
    # the backward keeps a state every ``keep`` chunks and replays between
    kp = keep if n % keep == 0 else 1
    if _pick_impl(impl) == "pallas" and kernel_tiles(t, dk, dv, chunk, s):
        return _kernels_with_vjp(c, s, kp, _heads_a_step(b * h), dtype,
                                 interpret())(
            *(a.reshape(b * h, t, -1) for a in (q, k, v, g, beta))
        ).reshape(b, h, t, dv)

    def chunks(a):
        return a.reshape(a.shape[:2] + (n, c) + a.shape[3:])

    parts = jax.checkpoint(functools.partial(_intra, sub=s, dtype=dtype))(
        *(chunks(a) for a in (q, k, v, g, beta)))

    @jax.checkpoint
    def steps(state, xs):
        return lax.scan(functools.partial(_step, dtype=dtype), state, xs)

    def grouped(a):
        a = jnp.moveaxis(a, 2, 0)
        return a.reshape((n // kp, kp) + a.shape[1:])

    s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    _, o = lax.scan(steps, s0, tuple(grouped(a) for a in parts))
    o = o.reshape((n,) + o.shape[2:])
    return jnp.moveaxis(o, 0, 2).reshape(b, h, t, dv)
