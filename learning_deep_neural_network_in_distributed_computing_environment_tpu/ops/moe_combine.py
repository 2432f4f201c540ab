"""The routed layer's way back into the tokens: ``out[t] = sum of
weight[r] * rows[r]`` over the live rows ``r`` of token ``t``, in float32.

The rows come as ``models/moe.py::routed_apply`` leaves them: sorted by
held expert (stably), so within an expert in token order.  The rows that
one expert sends to a tile of ``TT`` tokens are then ONE contiguous run of
the buffer, and the runs' bounds are a cumulative count of the choices
(``run_edges``): ``held * N / TT + 1`` int32, found once a call beside the
sort and shared by the forward pass, its recomputation and the backward
pass.

On the TPU this is one Pallas kernel, ``moe_combine``.  Its grid walks the
token tiles; the tile's ``[TT, H]`` float32 sums stay in VMEM and are
written once.  Within a tile it walks rounds: round ``m`` copies rows
``[m * R, (m + 1) * R)`` of every held expert's run, each from HBM
(``memory_space=ANY``) into its own slot of a VMEM buffer of ``held * R``
rows, double-buffered, the next round's copies in flight while this one
computes (the walk crosses tiles); a copy's first row is clamped so that
it stays in the buffer.  A tile takes as many rounds as its longest run
needs, ``R`` rows a round (a power of two near the mean run, from the
shapes alone: ``plan``).  **Rows outside their run, and the rows no
expert wrote, are selected to zero in VMEM before any product**: the
grouped products leave the rows past the last group unwritten, and they
may hold anything (``ops/grouped_matmul.py``).  The slots' rows are then
summed into the tile by one product on the MXU, ``onehot [TT, held * R]``
(each row's place in the tile, its weight as the value) times the
``[held * R, H]`` slots: stacking every expert's run makes the contraction
``held * R`` deep where one run alone would fill a sixteenth of the MXU.

A product of two bfloat16 numbers is exact in float32, so bfloat16 rows
go through exactly: in one pass where the value is 1 (the backward's sum)
and in three where it is a float32 weight, split into bfloat16 hi + mid +
lo, which reproduce ``w * y`` to float32 rounding.  Float32 rows take one
pass at full precision.  The sum of a token's terms from several experts
runs in the MXU's order, so it may differ from XLA's scatter-add in the
last bits.

The tokens and weights of the rows are whole blocks in VMEM, a lane a row;
a slot's tokens come out of them by a dynamic lane rotation.

Where Pallas cannot run (the CPU interpreter inside ``shard_map``, as for
the flash kernels and ``grouped_matmul``) the same sum is XLA's
scatter-add.  The call takes five operands (the two scalar-prefetch
schedule arrays, the rows, the tokens and the weights) and returns one
array: no picker in ``benchmarks/kernels/`` takes that shape, so the trace
reads it under its own name and no roofline counts it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_ops
from .grouped_matmul import VMEM_LIMIT, vary_alike
from .pallas_ops import LANES

MAX_TT = 128                  # tokens a tile
ALIGN = 8                     # rows of an HBM tile: where a copy may start


def token_tile(n: int) -> int:
    """Tokens a tile for ``n`` tokens: 128 where it divides, else the
    largest power of two that does (at least 8), else all of them."""
    return next((t for t in (MAX_TT, 64, 32, 16, 8) if n % t == 0), n)


def plan(n: int, c: int, held: int, itemsize: int):
    """``(tt, r, slots)``: tokens a tile; rows a run's window copies a round,
    a power of two at least the mean run of a full ``c``-row buffer plus
    half an ``ALIGN`` (a window starts on a whole HBM tile row), at least
    the rows' sublane tile, at most 128 and at most ``c``; and the VMEM
    buffer's slots: ``held``, or more where ``held * r`` has to be filled
    up to whole 128-lane blocks."""
    tt = token_tile(n)
    mean = -(-c * tt // (n * held)) + ALIGN // 2
    r = max(32 // itemsize, ALIGN)
    while r < min(mean, LANES):
        r *= 2
    while r > c and r > ALIGN:
        r //= 2
    k = held * r
    slots = held if k <= LANES else -(-k // LANES) * LANES // r
    return tt, r, slots


def run_edges(expert, n: int, held: int):
    """The runs' bounds in the buffer that sorts the pairs by held expert
    (stably, so each expert's rows in token order): the first row of each
    (expert, token tile), expert-major, and the end of the last,
    ``[held * n / TT + 1]`` int32.  ``expert`` [n, k] is each pair's held
    expert, ``held`` or more for one held elsewhere.  A run's length is a
    count of the pairs, so the bounds are a cumulative sum: no search of
    the sorted rows."""
    tt = token_tile(n)
    runs = (expert.reshape(n // tt, tt, -1, 1) == jnp.arange(held)).sum(
        (1, 2), dtype=jnp.int32)
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(runs.T.reshape(-1))])


def _rounds(edges, tiles: int, r: int):
    """Each tile's rounds: as many as its longest run needs, the run's
    windows starting at its first row rounded down to ``ALIGN``; at least
    one (a tile without rows still writes its zeros)."""
    first, end = edges[:-1], edges[1:]
    runs = jnp.where(end > first, end - first // ALIGN * ALIGN, 0)
    need = -(-runs.reshape(-1, tiles) // r)
    return jnp.maximum(need.max(0), 1)


def row_slots(edges, n: int, c: int, held: int, itemsize: int):
    """The rows the kernel's products walk for one ``c``-row window: its
    rounds times the slots' rows."""
    tt, r, slots = plan(n, c, held, itemsize)
    return _rounds(edges, n // tt, r).sum() * (slots * r)


def _kernel(edges_ref, steps_ref, rows_hbm, tok_ref, val_ref, out_ref, buf,
            sem, *, tt: int, r: int, held: int, slots: int, tiles: int,
            c: int, parts: int):
    i = pl.program_id(0)
    first, last, total = steps_ref[i], steps_ref[i + 1], steps_ref[tiles]
    k = slots * r
    width = min(k, LANES)
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    sub = lax.broadcasted_iota(jnp.int32, (r, 1), 0)

    def windows(tile, m):
        """Round ``m`` of each expert's run in ``tile``: its live rows
        ``[lo, hi)`` and the first of the ``r`` rows copied, a multiple of
        ``ALIGN``."""
        out = []
        for g in range(held):
            a = edges_ref[g * tiles + tile]
            w = a - lax.rem(a, ALIGN) + m * r
            lo = jnp.maximum(a, w)
            hi = jnp.minimum(edges_ref[g * tiles + tile + 1], w + r)
            out.append((lo, hi, pl.multiple_of(jnp.minimum(w, c - r), ALIGN)))
        return out

    def copy(s, g, start):
        return pltpu.make_async_copy(rows_hbm.at[pl.ds(start, r)],
                                     buf.at[s % 2, pl.ds(g * r, r)],
                                     sem.at[s % 2])

    def fetch(s, tile, m):
        for g, (lo, hi, start) in enumerate(windows(tile, m)):
            pl.when(lo < hi)(copy(s, g, start).start)

    @pl.when(i == 0)
    def _prologue():
        if slots > held:
            buf[:, held * r:, :] = jnp.zeros(
                (2, k - held * r, buf.shape[2]), buf.dtype)
        fetch(0, 0, 0)

    out_ref[...] = jnp.zeros_like(out_ref)

    def step(s, carry):
        nxt = s + 1

        @pl.when(nxt < total)
        def _prefetch():
            same = nxt < last
            fetch(nxt, jnp.where(same, i, i + 1),
                  jnp.where(same, nxt - first, 0))

        slot = s % 2
        runs = windows(i, s - first)
        for g, (lo, hi, _) in enumerate(runs):
            # a wait needs the copy's size and semaphore alone
            pl.when(lo < hi)(copy(s, g, 0).wait)
        base = i * tt
        places, values = [], []
        for blk in range(max(k // LANES, 1)):
            place = jnp.full((1, LANES), -1, jnp.int32)
            value = jnp.zeros((1, LANES), jnp.float32)
            for g in range(blk * width // r, min((blk + 1) * width // r, held)):
                lo, hi, start = runs[g]
                # lane off + j of the block gets the run's row start + j: the
                # two 128-lane rows of the tokens and weights that hold
                # ``[start, start + r)``, rotated into place
                q = lax.div(start, LANES)
                o = start - q * LANES
                off = g * r % LANES
                shift = lax.rem(off - o + LANES, LANES)
                near = lane < LANES - o + off
                q2 = jnp.minimum(q + 1, tok_ref.shape[0] - 1)
                pick = lambda ref: jnp.where(
                    near, pltpu.roll(ref[pl.ds(q, 1), :], shift, 1),
                    pltpu.roll(ref[pl.ds(q2, 1), :], shift, 1))
                live = (lane >= off + lo - start) & (lane < off + hi - start)
                place = jnp.where(live, pick(tok_ref) - base, place)
                value = jnp.where(live, pick(val_ref), value)
                rows = buf[slot, pl.ds(g * r, r), :]
                buf[slot, pl.ds(g * r, r), :] = jnp.where(
                    (sub >= lo - start) & (sub < hi - start), rows,
                    jnp.zeros_like(rows))
            places.append(place[:, :width])
            values.append(value[:, :width])
        place = jnp.concatenate(places, axis=1)
        rest = jnp.concatenate(values, axis=1)
        hit = lax.broadcasted_iota(jnp.int32, (tt, k), 0) == place
        rows = buf[slot]
        acc = out_ref[...]
        for _ in range(parts):
            part = rest.astype(rows.dtype)
            rest = rest - part.astype(jnp.float32)
            onehot = jnp.where(hit, part.astype(jnp.float32), 0.0)
            acc += lax.dot_general(
                onehot.astype(rows.dtype), rows, (((1,), (0,)), ((), ())),
                precision=(lax.Precision.HIGHEST if rows.dtype.itemsize >= 4
                           else None),
                preferred_element_type=jnp.float32)
        out_ref[...] = acc
        return carry

    lax.fori_loop(first, last, step, 0)


# jitted so that every call site (chunk 0, the overflow loops, the forward,
# its recomputation and the backward, the layers of a period) shares one
# trace and one lowered function: a Pallas call is traced anew wherever it
# is called, and that tracing is set-up time on the chip's host
@functools.partial(jax.jit, static_argnames=("n", "parts"))
def _pallas(rows, tok, value, edges, n: int, parts: int):
    c, h = rows.shape
    held = (edges.shape[0] - 1) * token_tile(n) // n
    tt, r, slots = plan(n, c, held, rows.dtype.itemsize)
    tiles = n // tt
    steps = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                             jnp.cumsum(_rounds(edges, tiles, r))])
    pad = -c % LANES
    flat = lambda a: jnp.pad(a, (0, pad)).reshape(-1, LANES)
    tok, value = flat(tok.astype(jnp.int32)), flat(value.astype(jnp.float32))
    whole = pl.BlockSpec(tok.shape, lambda i, e, s: (0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, tt=tt, r=r, held=held, slots=slots,
                          tiles=tiles, c=c, parts=parts),
        out_shape=jax.ShapeDtypeStruct((n, h), jnp.float32,
                                       vma=jax.typeof(rows).vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), whole, whole],
            out_specs=pl.BlockSpec((tt, h), lambda i, e, s: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, slots * r, h), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        # every copy's window lies in rows [0, C) by construction (its first
        # row clamped to C - R); the runtime checks of the copies' bounds
        # took about a fifth of a backward round's bundles
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT, disable_bounds_checks=True),
        interpret=pallas_ops._interpret(), name="moe_combine",
    )(edges, steps, rows, tok, value)


def moe_combine(rows, tok, edges, weight=None, *, n: int):
    """``[n, H]`` float32: each live row of ``rows`` [C, H], times its
    ``weight`` (float32 [C]; 1 where None), added into its token
    ``tok`` [C].  The live rows are those of the runs ``edges`` bounds
    (``run_edges``, made relative to this buffer): the rows of a held
    expert.  What the other rows hold is never read into a product."""
    c = rows.shape[0]
    value = jnp.ones((c,), jnp.float32) if weight is None else weight
    rows, tok, value, edges = vary_alike(rows, tok, value, edges)
    if pallas_ops._interpret() and jax.typeof(rows).vma:
        live = jnp.arange(c) < edges[-1]
        y = rows.astype(jnp.float32) * value[:, None]
        return jnp.zeros((n, rows.shape[1]), jnp.float32).at[tok].add(
            jnp.where(live[:, None], y, 0))
    parts = 1 if weight is None or rows.dtype.itemsize >= 4 else 3
    return _pallas(rows, tok, value, edges, n, parts)
