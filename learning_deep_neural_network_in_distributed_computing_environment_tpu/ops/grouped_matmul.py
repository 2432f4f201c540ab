"""Grouped matrix product for routed experts: ``out[r] = lhs[r] @ rhs[g]``
for every row ``r`` of group ``g``.

The rows of ``lhs`` [M, K] come sorted by group: group ``g`` owns the
``group_sizes[g]`` rows after those of the groups before it, and the rows
past ``sum(group_sizes)`` belong to none.  ``rhs`` [G, K, N] holds one
matrix a group.  Nothing is padded to a capacity and no row is dropped: a
group may own every row or none.

On the TPU this is a Pallas kernel (``_gmm``; ``_tgmm`` for the weights'
gradient) that walks a static list of ``M / tm + G`` steps, one for each
(group, row tile) pair that holds a row; a step past the last such pair is
skipped and loads nothing.  Each group's matrix is read once, each row
tile once a group it holds rows of, and a tile two groups share is
written under a row mask.  **Rows that belong to no group are never
written**: what ``out`` holds there is whatever the buffer held, so a
caller selects its rows (``jnp.where``, never a product with zero) before
it uses them.  The gradient rules keep to the same contract.

Where Pallas cannot run (the CPU interpreter inside ``shard_map``, as for
the flash kernels) the same products go through ``jax.lax.ragged_dot``.

The device trace tells these calls from the flash kernels by their four
operands (two scalar-prefetch arrays, ``lhs``, ``rhs``) and one output:
``benchmarks/kernels/moe_gmm.json``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_ops

TM = 256                      # rows a step
BLOCK_BYTES = 10 * 2**20      # most that one group's [K, N] block may take
VMEM_LIMIT = 100 * 2**20      # of the v5e's 128 MiB


def row_tile(m: int) -> int:
    """Rows a step for an ``m``-row product: TM where it divides, else the
    whole of a small ``m`` (callers pad ``m`` to a multiple of 8)."""
    if m % TM == 0:
        return TM
    if m < TM and m % 8 == 0:
        return m
    raise ValueError(f"grouped_matmul needs its {m} rows to be a multiple "
                     f"of {TM}, or fewer and a multiple of 8")


def _check_block(k: int, n: int, itemsize: int) -> None:
    """One group's whole [k, n] matrix is one block in VMEM."""
    if k * n * itemsize > BLOCK_BYTES:
        raise ValueError(
            f"a [{k}, {n}] group matrix takes more than {BLOCK_BYTES} bytes "
            "of VMEM as one block; tile its columns before widening it")


def group_steps(group_sizes, m: int, tm: int, visit_empty: bool = False):
    """The kernels' schedule, as their two scalar-prefetch operands.

    ``steps`` [2 * S] int32, S = m / tm + G: the group of each step, then
    the row tile of each step, in group order; steps past the last real one
    repeat it.  ``bounds`` [G + 2] int32: the first row of each group, the
    end of the last, and the number of real steps.  ``visit_empty`` gives a
    group without rows one step all the same (the weights' gradient has to
    write its zeros)."""
    g = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, m // tm - 1)
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                      1 if visit_empty else 0)
    tile_ends = jnp.cumsum(tiles)
    real = tile_ends[-1]
    s = jnp.minimum(jnp.arange(m // tm + g, dtype=jnp.int32),
                    jnp.maximum(real - 1, 0))
    group = jnp.minimum(jnp.searchsorted(tile_ends, s, side="right"),
                        g - 1).astype(jnp.int32)
    tile = first[group] + s - (tile_ends[group] - tiles[group])
    tile = jnp.clip(tile, 0, m // tm - 1)
    steps = jnp.concatenate([group, tile]).astype(jnp.int32)
    bounds = jnp.concatenate([starts, ends[-1:], real[None]]).astype(
        jnp.int32)
    return steps, bounds


def _row_mask(steps_ref, bounds_ref, s, n_steps: int, tm: int):
    """[tm, 1] bool: the rows of step ``s``'s tile that its group owns."""
    group, tile = steps_ref[s], steps_ref[n_steps + s]
    rows = tile * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (rows >= bounds_ref[group]) & (rows < bounds_ref[group + 1])


def _gmm_kernel(steps_ref, bounds_ref, lhs_ref, rhs_ref, out_ref, *,
                n_steps: int, groups: int, tm: int, transpose_rhs: bool):
    s = pl.program_id(0)

    @pl.when(s < bounds_ref[groups + 1])
    def _step():
        dims = (((1,), (1,)), ((), ())) if transpose_rhs else (
            ((1,), (0,)), ((), ()))
        acc = lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                              preferred_element_type=jnp.float32)
        mask = _row_mask(steps_ref, bounds_ref, s, n_steps, tm)
        # a tile that two groups share stays in VMEM between their steps:
        # each writes its own rows over what is there
        out_ref[...] = jnp.where(mask, acc, out_ref[...].astype(jnp.float32)
                                 ).astype(out_ref.dtype)


def _tgmm_kernel(steps_ref, bounds_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                 n_steps: int, groups: int, tm: int):
    s = pl.program_id(0)
    real = bounds_ref[groups + 1]
    group = steps_ref[s]
    active = s < real
    first = (s == 0) | (steps_ref[jnp.maximum(s - 1, 0)] != group)
    last = (s == real - 1) | (
        steps_ref[jnp.minimum(s + 1, n_steps - 1)] != group)

    @pl.when(active & first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(active)
    def _step():
        # rows of another group, or of none, are selected away on both
        # sides: what they hold may not be a number
        mask = _row_mask(steps_ref, bounds_ref, s, n_steps, tm)
        lhs = jnp.where(mask, lhs_ref[...], 0)
        rhs = jnp.where(mask, rhs_ref[...], 0)
        acc_ref[...] += lax.dot_general(
            lhs, rhs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(active & last)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=VMEM_LIMIT)


def _gmm(lhs, rhs, group_sizes, transpose_rhs: bool = False):
    """[M, K] x [G, K, N] -> [M, N] (``transpose_rhs``: rhs is [G, N, K])."""
    m, k = lhs.shape
    g = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm = row_tile(m)
    _check_block(k, n, rhs.dtype.itemsize)
    n_steps = m // tm + g
    steps, bounds = group_steps(group_sizes, m, tm)
    rhs_spec = pl.BlockSpec((None, n, k) if transpose_rhs else (None, k, n),
                            lambda s, st, bd: (st[s], 0, 0))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_steps=n_steps, groups=g, tm=tm,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype,
                                       vma=jax.typeof(lhs).vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_steps,),
            in_specs=[
                pl.BlockSpec((tm, k), lambda s, st, bd: (st[n_steps + s], 0)),
                rhs_spec],
            out_specs=pl.BlockSpec(
                (tm, n), lambda s, st, bd: (st[n_steps + s], 0))),
        compiler_params=_params(), interpret=pallas_ops._interpret(), name="moe_gmm",
    )(steps, bounds, lhs, rhs)


def _tgmm(lhs, rhs, group_sizes, groups: int):
    """[M, K] x [M, N] -> [G, K, N]: each group's ``lhs.T @ rhs`` over its
    own rows; a group without rows gets zeros."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tm = row_tile(m)
    _check_block(k, n, 4)
    n_steps = m // tm + groups
    steps, bounds = group_steps(group_sizes, m, tm, visit_empty=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, n_steps=n_steps, groups=groups,
                          tm=tm),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype,
                                       vma=jax.typeof(lhs).vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_steps,),
            in_specs=[
                pl.BlockSpec((tm, k), lambda s, st, bd: (st[n_steps + s], 0)),
                pl.BlockSpec((tm, n), lambda s, st, bd: (st[n_steps + s], 0))],
            out_specs=pl.BlockSpec((None, k, n),
                                   lambda s, st, bd: (st[s], 0, 0)),
            scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)]),
        compiler_params=_params(), interpret=pallas_ops._interpret(), name="moe_tgmm",
    )(steps, bounds, lhs, rhs)


def vary_alike(*arrays):
    """The arrays, each made to vary over every mesh axis that any of them
    varies over (inside ``shard_map``; outside it, as they are): tokens
    that an ``expert`` axis shares meet matrices that it does not.  A
    ``custom_vjp`` has to hand back cotangents of its arguments' own types,
    so its arguments are cast BEFORE they enter it, and the cast's own
    transpose sums what comes back."""
    axes = frozenset().union(*(jax.typeof(a).vma for a in arrays))
    return tuple(lax.pcast(a, tuple(axes - jax.typeof(a).vma), to="varying")
                 if axes - jax.typeof(a).vma else a for a in arrays)


@jax.custom_vjp
def _kernel(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes)


def _fwd(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _bwd(res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    return (_gmm(g, rhs, group_sizes, transpose_rhs=True),
            _tgmm(lhs, g, group_sizes, rhs.shape[0]), None)


_kernel.defvjp(_fwd, _bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """[M, K] x [G, K, N] -> [M, N], rows sorted by group (module
    docstring).  Differentiable in ``lhs`` and ``rhs``."""
    lhs, rhs, group_sizes = vary_alike(lhs, rhs, group_sizes)
    # the Pallas interpreter (the CPU) cannot lower a kernel whose operands
    # are mesh-varying inside shard_map: ops/pallas_ops.py, flash_attention
    in_shard_map = bool(jax.typeof(lhs).vma)
    if pallas_ops._interpret() and in_shard_map:
        return lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
    return _kernel(lhs, rhs, group_sizes)
