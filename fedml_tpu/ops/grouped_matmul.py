"""Grouped matmul over token rows sorted by expert, and the row moves
around it.

A sparse-expert layer sorts its (token, choice) pairs by expert and runs ONE
matmul per projection over the sorted rows: rows ``[start_g, start_g +
size_g)`` of ``x`` meet ``w[g]``. No dropped row: the layer chooses, each
step, a static row capacity from the router's own count that holds every
row the router filled (``models/moe.py: row_rungs``), and the last capacity
is every pair; an expert may hold no row at all, or all of them.

:func:`grouped_matmul` takes the rows in their dtype (bf16 in the LM cells)
and the experts' matrices AS THE PARAMETERS ARE (float32). On the TPU it is
three Pallas calls under one ``custom_vjp`` (:func:`_gmm_fwd`,
:func:`_gmm_dx`, :func:`_gmm_dw`; a trace shows the calls by these names):
a call walks the row tiles each group touches (:func:`_visits`), holds ONE
expert's whole float32 matrix in VMEM while the next one's is on its way (a
double buffer the kernel fills itself: an expert's matrix crosses HBM once a
call however many row tiles its group has), casts the panel it multiplies
to the rows' dtype THERE and accumulates in float32. ``d_rows`` reads the
same float32 matrix, contracted over its other axis; ``d_w`` leaves as the
float32 accumulator it is. No copy of an expert's matrix in the rows' dtype
and no weight gradient in it ever exists in HBM. The row tile follows the
operands' shapes (:func:`_tiles`: short, so that a group's edge costs no
full masked tile, and shorter where the capacity gives a group few rows).
Off the TPU, under a batching ``vmap``, and for a shape the kernels do not
tile, it is ``jax.lax.ragged_dot`` (the compiler's own grouped kernels,
forward and both gradients through its transpose rules) on the matrices
cast outside; ``tests/test_lm_ops.py`` holds both against a per-expert
loop.

The moves are gathers in BOTH directions: XLA's transpose of a row gather
is a scatter-add, which a TPU serialises row by row. A permutation's
transpose is the inverse permutation's gather, and a fan-out's transpose is
a gather and a sum over the fan, so each gets its own VJP. Both moves take
the FIRST ``C`` sorted slots only (the layer's row capacity): a slot past
them reads as a zero row, which is what the pair of an absent expert adds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fedml_tpu.ops.attention import _pick_impl
from fedml_tpu.ops.common import interpret

#: the most VMEM a call asks for (of a v5e's 128 MiB), and what it leaves
#: the compiler beside the blocks counted in :func:`_tiles`
GMM_VMEM_LIMIT = 100 * 2 ** 20
GMM_VMEM_SPARE = 12 * 2 ** 20


def _panel(n: int) -> int:
    """The widest multiple of 128 up to 512 that divides ``n`` (0: none):
    the slice of an expert's matrix one product of a kernel's loop takes."""
    return next((p for p in (512, 384, 256, 128) if n % p == 0), 0)


def _tiles(m: int, k: int, n: int, groups: int, x_bytes: int, w_bytes: int):
    """Row tile of the three calls for ``x [m, k]`` against ``w [groups, k,
    n]``, or None where the kernels do not tile the shape: ``k`` and ``n``
    in whole 128-lane tiles, ``m`` in whole row tiles, and two experts'
    matrices beside a row tile's operands within the VMEM a call may ask
    for. 256 rows where the capacity gives a group that many, else 128: a
    group's edge costs a masked tile of rows, so a short tile wastes least,
    but every visit casts the whole matrix, which 128 rows do not hide (my
    chip run, PR 45, bf16 rows on float32 ``[8, 2048, 2048]``, 4,000 rows:
    0.41 / 0.37 / 0.43 ms a forward call at 128 / 256 / 512 rows a tile)."""
    if k % 128 or n % 128 or m % 128:
        return None
    tm = 256 if m % 256 == 0 and m // groups >= 256 else 128
    held = 2 * k * n * max(w_bytes, 4)            # w twice, or d_w twice
    rows = 2 * 2 * tm * (k + n) * x_bytes         # both row operands, twice
    if held + rows + GMM_VMEM_SPARE > GMM_VMEM_LIMIT:
        return None
    return tm


@functools.cache
def _traced(call, *operands, **static):
    return jax.make_jaxpr(functools.partial(call, **static))(*operands)


def _bind(call, *args, **static) -> list:
    """``call(*args, **static)`` of a jitted ``call``, flat, traced ONCE a
    signature: JAX traces a rung several times (the forward, the forward
    under its JVP, the backward's rebuild) and its cache of traced functions
    does not reach from one of them to the next, and a kernel's trace is the
    dearest part of a rung's; from the second time on the call is bound as
    the one equation the first trace made of it."""
    from jax.extend.core import jaxpr_as_fun

    return jaxpr_as_fun(_traced(call, *(
        jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args), **static))(
            *args)


@functools.partial(jax.jit, static_argnames=("m", "tm", "empty"))
def _visits(sizes: jax.Array, *, m: int, tm: int, empty: bool):
    """The row tiles a call walks, in order: ``(starts [G], ends [G], group
    [T], tile [T], first [T], slot [T], after [T], count [1])`` int32 (as
    ``sizes`` is) with ``T = m // tm + G``. Visit ``t < count`` takes row
    tile ``tile[t]`` for group ``group[t]``, whose rows are ``starts[g] ..
    ends[g]``; a group is visited once a tile it has a row in,
    consecutively, and with ``empty`` a group without rows once (``d_w``
    writes its zeros). ``first``: the visit is its group's first; ``slot``:
    which half of a double buffer the group has (visited groups alternate);
    ``after``: the next visited group, -1 after the last. Jitted and bound
    by :func:`_bind`: the calls of a capacity trace it once between them
    (and XLA computes it once a branch)."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tile0 = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - tile0 + 1, int(empty))
    stop = jnp.cumsum(tiles)
    begin = stop - tiles
    t = jax.lax.iota(jnp.int32, m // tm + sizes.shape[0])
    # past the last visit ``group`` is G: nothing reads those entries
    group = jnp.sum(stop[None, :] <= t[:, None], axis=1, dtype=jnp.int32)
    first = (t == begin[group]).astype(jnp.int32)
    then = stop[group]                  # the visit the next group starts at
    return (starts, ends, group, (tile0 - begin)[group] + t, first,
            (jnp.cumsum(first) - 1) % 2,
            jnp.where(then < stop[-1], group[then], -1), stop[-1:])


def _row_mask(starts, ends, group, tile, t, tm):
    """``[tm, 1]``: which rows of visit ``t``'s tile belong to its group."""
    g = group[t]
    row = tile[t] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (row >= starts[g]) & (row < ends[g])


def _gmm_kernel(starts, ends, group, tile, first, slot, after, x_ref, w_hbm,
                o_ref, w_buf, sem, *, tm: int, panel: int, transposed: bool):
    """One visit of ``x @ w[g]`` (``transposed``: ``x @ w[g]^T``): the
    group's matrix waits in ``w_buf[slot]`` as it lies in HBM; a panel of
    it at a time is cast to the rows' dtype and multiplied, and the group's
    rows of the product replace the output tile's."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = pl.program_id(0)
    g, s = group[t], slot[t]

    def copy(which, into):
        return pltpu.make_async_copy(w_hbm.at[which], w_buf.at[into],
                                     sem.at[into])

    @pl.when(t == 0)
    def _first_matrix():
        copy(g, s).start()

    @pl.when(first[t] == 1)
    def _next_matrix():
        copy(g, s).wait()

        @pl.when(after[t] >= 0)
        def _():
            copy(after[t], 1 - s).start()

    x = x_ref[...]
    mine = _row_mask(starts, ends, group, tile, t, tm)
    contract = (((1,), (1 if transposed else 0,)), ((), ()))

    def product(j, carry):
        at = pl.ds(pl.multiple_of(j * panel, panel), panel)
        w = w_buf[s, at, :] if transposed else w_buf[s, :, at]
        y = jax.lax.dot_general(x, w.astype(x.dtype), contract,
                                preferred_element_type=jnp.float32)
        o_ref[:, at] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[:, at])
        return carry

    jax.lax.fori_loop(0, o_ref.shape[1] // panel, product, 0)


def _cost(m: int, k: int, n: int, *nbytes: int):
    """What a call costs, for XLA's scheduler: the row slots' products (the
    static capacity's, as XLA counts a ``ragged_dot``) and each operand and
    the result across HBM once."""
    import jax.experimental.pallas as pl

    return pl.CostEstimate(flops=2 * m * k * n, transcendentals=0,
                           bytes_accessed=sum(nbytes))


def _gmm_call(x, w, sizes, *, tm: int, transposed: bool, interpret: bool):
    """``x @ w[g]`` (``transposed``: ``x @ w[g]^T``) for each group's rows:
    one grid step a visit, ``w`` left in HBM for the kernel's own copies."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, wide = x.shape[0], w.shape[1 if transposed else 2]
    *visits, count = _bind(_visits, sizes, m=m, tm=tm, empty=False)

    def rows(width):
        return pl.BlockSpec((tm, width), lambda t, *v: (v[3][t], 0))

    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, panel=_panel(wide),
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(visits), grid=(count[0],),
            in_specs=[rows(x.shape[1]), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=rows(wide),
            scratch_shapes=[pltpu.VMEM((2,) + w.shape[1:], w.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((m, wide), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=GMM_VMEM_LIMIT),
        cost_estimate=_cost(m, *w.shape[1:], x.nbytes, w.nbytes,
                            m * wide * x.dtype.itemsize),
        interpret=interpret,
    )(*visits, x, w)
    # a tile no group has a row in is never visited: zeros, not what was there
    live = jnp.arange(m)[:, None] < visits[1][-1]
    return jnp.where(live, out, 0)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _gmm_fwd(x, w, sizes, *, tm: int, interpret: bool):
    """``x [M, K]``, ``w [G, K, N]`` -> ``[M, N]`` in ``x``'s dtype: rows of
    group ``g`` times ``w[g]``, rows of no group zeros."""
    return _gmm_call(x, w, sizes, tm=tm, transposed=False,
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _gmm_dx(dy, w, sizes, *, tm: int, interpret: bool):
    """``dy [M, N]``, ``w [G, K, N]`` -> ``[M, K]``: rows of group ``g``
    times ``w[g]^T``, off the same matrix as the forward call's."""
    return _gmm_call(dy, w, sizes, tm=tm, transposed=True,
                     interpret=interpret)


def _gmm_dw_kernel(starts, ends, group, tile, x_ref, dy_ref, o_ref, *,
                   tm: int, panel: int):
    """One visit of ``d_w[g] += x_g^T dy_g``: the output block IS the
    accumulator (float32, in VMEM while the group's visits last)."""
    import jax.experimental.pallas as pl

    t = pl.program_id(0)

    @pl.when((t == 0) | (group[jnp.maximum(t - 1, 0)] != group[t]))
    def _start():
        o_ref[...] = jnp.zeros_like(o_ref)

    mine = _row_mask(starts, ends, group, tile, t, tm)
    # 0 * (whatever a row of no group holds) must stay 0: mask both
    dy = jnp.where(mine, dy_ref[...], 0)

    def product(j, carry):
        at = pl.ds(pl.multiple_of(j * panel, panel), panel)
        o_ref[at, :] += jax.lax.dot_general(
            jnp.where(mine, x_ref[:, at], 0), dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0] // panel, product, 0)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _gmm_dw(x, dy, sizes, *, tm: int, interpret: bool):
    """``x [M, K]``, ``dy [M, N]`` -> ``[G, K, N]`` float32: each group's
    ``x_g^T dy_g``, zeros for a group without rows."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (m, k), n = x.shape, dy.shape[1]
    *visits, _, _, _, count = _bind(_visits, sizes, m=m, tm=tm, empty=True)

    def rows(width):
        return pl.BlockSpec((tm, width), lambda t, *v: (v[3][t], 0))

    return pl.pallas_call(
        functools.partial(_gmm_dw_kernel, tm=tm, panel=_panel(k)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(visits), grid=(count[0],),
            in_specs=[rows(k), rows(n)],
            out_specs=pl.BlockSpec((None, k, n),
                                   lambda t, *v: (v[2][t], 0, 0))),
        out_shape=jax.ShapeDtypeStruct((sizes.shape[0], k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=GMM_VMEM_LIMIT),
        cost_estimate=_cost(m, k, n, x.nbytes, dy.nbytes,
                            sizes.shape[0] * k * n * 4),
        interpret=interpret,
    )(*visits, x, dy)


# The three calls as ``lax.ragged_dot``: what a batching ``vmap`` takes, and
# (``_plain`` with JAX's own gradients) every call off the TPU.

def _plain(x, w, sizes):
    return jax.lax.ragged_dot(x, w.astype(x.dtype), sizes)


def _plain_dx(dy, w, sizes):
    return jax.lax.ragged_dot(dy, jnp.swapaxes(w, 1, 2).astype(dy.dtype),
                              sizes)


#: ``x [M, K]``, ``dy [M, N]`` -> ``[G, K, N]``: the rows are contracted
_OVER_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _plain_dw(x, dy, sizes):
    return jax.lax.ragged_dot_general(x, dy, sizes, _OVER_ROWS,
                                      preferred_element_type=jnp.float32)


def _unbatched(call, plain, tm: int, interpret: bool):
    """``call`` (one of the three jitted calls) as it is, and ``plain`` under
    a batching ``vmap`` (a Pallas call with a grid the operands decide has
    no batched form)."""

    @jax.custom_batching.custom_vmap
    def fn(*args):
        return _bind(call, *args, tm=tm, interpret=interpret)[0]

    @fn.def_vmap
    def _batched(axis_size, in_batched, *args):
        # ragged_dot batches all three operands or none
        args = [a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip(args, in_batched)]
        return jax.vmap(plain)(*args), True

    return fn


@functools.cache
def _kernels_with_vjp(tm: int, interpret: bool):
    """The three calls at a row tile as one differentiable function."""
    fwd_call = _unbatched(_gmm_fwd, _plain, tm, interpret)
    dx_call = _unbatched(_gmm_dx, _plain_dx, tm, interpret)
    dw_call = _unbatched(_gmm_dw, _plain_dw, tm, interpret)

    @jax.custom_vjp
    def f(x, w, sizes):
        return fwd_call(x, w, sizes)

    def fwd(x, w, sizes):
        return fwd_call(x, w, sizes), (x, w, sizes)

    def bwd(res, dy):
        x, w, sizes = res
        return (dx_call(dy, w, sizes),
                dw_call(x, dy, sizes).astype(w.dtype), None)

    f.defvjp(fwd, bwd)
    return f


def grouped_matmul(x: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """``x [M, K]`` rows sorted by group, ``w [G, K, N]`` (any float dtype:
    the parameters as they are), ``group_sizes [G]`` int with ``sum <= M``
    -> ``[M, N]`` in ``x``'s dtype, ``w`` rounded to it on the way to the
    product; rows past the last group belong to no group and come out as
    zeros. The kernels of this module on a TPU where they tile the shape
    (:func:`_tiles`); ``lax.ragged_dot`` on ``w`` cast outside elsewhere,
    and under a ``vmap`` (more than one packed lane), where the TPU compiler
    refuses the batched ``ragged_dot`` too ("number of batch dimensions
    should be 0")."""
    sizes = group_sizes.astype(jnp.int32)
    tm = _tiles(x.shape[0], *w.shape[1:], w.shape[0], x.dtype.itemsize,
                w.dtype.itemsize)
    if _pick_impl("auto") == "pallas" and tm:
        return _kernels_with_vjp(tm, interpret())(x, w, sizes)
    return _plain(x, w, sizes)


def _rows_or_zero(x: jax.Array, at: jax.Array) -> jax.Array:
    """``x[at]``, and a zero row where ``at`` is past ``x``'s last row."""
    return jnp.take(x, at, axis=0, mode="fill", fill_value=0)


@jax.custom_vjp
def permute_rows(x: jax.Array, perm: jax.Array, inv: jax.Array) -> jax.Array:
    """``x [C, D]`` (the first ``C`` sorted slots) -> ``[P, D]``, one row a
    pair: ``out[p] = x[perm[p]]``, a zero row where ``perm[p] >= C``. ``inv
    [C]`` is the first ``C`` entries of ``perm``'s inverse permutation; the
    cotangent comes back by its gather, ``C`` rows."""
    return _rows_or_zero(x, perm)


def _permute_fwd(x, perm, inv):
    return _rows_or_zero(x, perm), inv


def _permute_bwd(inv, ct):
    return jnp.take(ct, inv, axis=0), None, None


permute_rows.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def fan_out_rows(x: jax.Array, perm: jax.Array, inv: jax.Array) -> jax.Array:
    """``x [N, D]`` -> ``[C, D]`` with ``out[i] = x[perm[i] % N]``: row
    ``n`` is copied to its ``k`` (choice, token) slots ``c*N + n`` (choice-
    major, so that ``[k, N, D]`` views pad no axis), the slots are permuted
    by the sort whose first ``C`` entries are ``perm`` (inverse ``inv [k*N]``),
    and the first ``C`` are kept. The cotangent is un-permuted by a gather
    (zero for a slot that was not kept) and summed over each token's ``k``
    slots."""
    return jnp.take(x, perm % x.shape[0], axis=0)


def _fan_fwd(x, perm, inv):
    # an empty array carries the token count to the backward pass
    return fan_out_rows(x, perm, inv), (inv, jnp.zeros((x.shape[0], 0)))


def _fan_bwd(res, ct):
    inv, tokens = res
    back = _rows_or_zero(ct, inv).reshape(-1, tokens.shape[0], ct.shape[-1])
    return (jnp.sum(back.astype(jnp.float32), axis=0).astype(ct.dtype),
            None, None)


fan_out_rows.defvjp(_fan_fwd, _fan_bwd)


@jax.custom_vjp
def embed_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """``table[ids]`` whose gradient is a one-hot matmul (float32
    accumulation) instead of a scatter-add of one row per token."""
    return jnp.take(table, ids, axis=0)


def _embed_fwd(table, ids):
    # the table rides along only for its shape and dtype (no copy is made)
    return jnp.take(table, ids, axis=0), (ids, table)


def _embed_bwd(res, ct):
    ids, table = res
    flat = ct.reshape(-1, ct.shape[-1])
    hot = jax.nn.one_hot(ids.reshape(-1), table.shape[0], dtype=flat.dtype,
                         axis=0)
    return (jnp.dot(hot, flat, preferred_element_type=jnp.float32)
            .astype(table.dtype), None)


embed_rows.defvjp(_embed_fwd, _embed_bwd)
