"""The synchronization matrix: one pytree-level ``aggregate`` for all 12 DP
sync modes.

The reference splits this across three ``communication.py`` flavors with
asymmetric interfaces (model-level for all-reduce,
``Balanced All-Reduce/communication.py:4-31``; tensor-level with the trainer
iterating parameters for ring/double-ring,
``Balanced Ring/communication.py:5-62``, ``Balanced Double-Ring/
communication.py:5-77``) over two backends (torch.distributed, mpi4py).
Here it is a single pure function on pytrees, executed *inside*
``shard_map``/``jit`` with XLA collectives over the mesh's data axis:

- ``allreduce`` -> ``lax.pmean`` / ``lax.psum`` (NCCL/gloo all_reduce
  equivalent, rides ICI);
- ``ring``      -> ``lax.ppermute`` shift-by-1 (the reference's 1-neighbor
  Isend/Irecv gossip, ``Balanced Ring/communication.py:19-25``);
- ``double_ring`` -> two ``ppermute`` shifts (1 and 2) (2-neighbor gossip,
  ``Balanced Double-Ring/communication.py:5-40``).

Semantics notes (SURVEY.md 2.5):

- "Ring" is one gossip exchange per sync — NOT a reduce-scatter/all-gather
  ring all-reduce; consensus emerges over repeated global epochs.  That is
  the observable behavior being reproduced.
- The reference's ring gossip silently no-ops on GPU (2.5.2); the behavior
  matched here is the correct CPU path.
- ``weighted`` all-reduce (2.5.10): ``new = w*own + (1-w)*(sum-own)/(N-1)``
  — the self-exclusive peer mean blended with the own value.  The reference
  divides by zero when N == 1; here N == 1 returns the own value unchanged
  (every topology is the identity on a single worker).
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import DATA_AXIS, SLICE_AXIS

PyTree = Any

TOPOLOGIES = ("allreduce", "ring", "double_ring")
HOWS = ("equal", "weighted")
BYS = ("gradients", "weights")

# Wire hops per gossip round: ring sends each bucket once (shift-1);
# double-ring sends it twice (shift-1 and shift-2, issued concurrently).
GOSSIP_HOPS = {"ring": 1, "double_ring": 2}

# Default sharded-sync bucket size.  Buckets batch many small parameter
# leaves into one collective so the per-collective launch overhead
# amortizes, while staying small enough that reduce-scatter/all-gather of
# one bucket pipelines against the pack/unpack of the next under XLA's
# scheduler.
DEFAULT_BUCKET_BYTES = 4 << 20


def ring_neighbors(n: int, shift: int = 1) -> list[tuple[int, int]]:
    """The gossip ring's ppermute permutation for ``n`` workers: rank i
    sends to ``(i + shift) % n``.  Derived from the AXIS SIZE alone —
    which is what makes the ring elastic (ISSUE 8): a membership change
    rebuilds the round program on the resized mesh and this table is
    re-derived for the new ``n``, so the ring always closes over exactly
    the live workers and a departed rank can never strand a neighbor
    waiting on it.  Exposed for the elastic tests/telemetry to assert
    that property (a valid table is a single cycle covering 0..n-1 when
    gcd(n, shift) == 1)."""
    return [(i, (i + shift) % n) for i in range(n)]


def _shift(x: jnp.ndarray, n: int, shift: int, axis_name: str) -> jnp.ndarray:
    """Receive the value of ``rank - shift`` (mod n): each rank i sends to
    ``i + shift``, matching the reference's Isend(to rank+1)/Irecv(from
    rank-1) gossip pattern."""
    return lax.ppermute(x, axis_name, ring_neighbors(n, shift))


def aggregate(tree: PyTree, *, how: str = "equal",
              topology: str = "allreduce", local_weight: float = 0.5,
              axis_name: str = DATA_AXIS, poison=None):
    """Aggregate a per-worker pytree across the data axis.

    Must be called inside ``shard_map`` (or any context where ``axis_name``
    is bound).  Works on parameter or gradient pytrees alike — the
    gradients/weights choice ("aggregation_by") is the caller's, matching
    the reference's dispatch (``Balanced All-Reduce/trainer.py:141-150``).

    ``poison`` (ISSUE 12 integrity screen): when not None, this worker's
    contribution is screened sender-side (poisoned/non-finite values
    enter the collectives as exact zeros) and every blend renormalizes
    over the valid contributions — the dense twin of the fast engines'
    screen, so the quarantine semantics are identical whichever sync
    path a chaos run resolves.  Clean rounds select the unscreened
    arithmetic (bitwise-identical).  The return is then
    ``(aggregated, ok)`` with ``ok`` this worker's fp32 0/1 flag.
    """
    if how not in HOWS:
        raise ValueError(f"how must be one of {HOWS}, got {how!r}")
    if topology not in TOPOLOGIES:
        raise ValueError(f"topology must be one of {TOPOLOGIES}, got {topology!r}")
    n = lax.axis_size(axis_name)
    if n == 1:
        if poison is not None:
            ok1 = _contribution_ok(
                poison, jax.tree_util.tree_leaves(tree), None)
            return tree, ok1.astype(jnp.float32)
        return tree
    w = local_weight
    ok = okf = valid = None
    if poison is not None:
        ok = _contribution_ok(poison, jax.tree_util.tree_leaves(tree),
                              None)
        okf = ok.astype(jnp.float32)
        valid = jnp.maximum(lax.psum(okf, axis_name), 1.0)
        all_ok = valid >= n
        ok1f = _shift(okf, n, 1, axis_name)
        ok2f = (_shift(okf, n, 2, axis_name)
                if topology == "double_ring" else None)

    def per_leaf(x: jnp.ndarray) -> jnp.ndarray:
        xs = x if ok is None else jnp.where(ok, x, jnp.zeros_like(x))
        if topology == "allreduce":
            if how == "equal":
                out = lax.pmean(x, axis_name)
                if ok is None:
                    return out
                return jnp.where(all_ok, out,
                                 lax.psum(xs, axis_name) / valid)
            total = lax.psum(xs, axis_name)
            peers_mean = (total - x) / (n - 1)
            out = w * x + (1.0 - w) * peers_mean
            if ok is None:
                return out
            peers = jnp.maximum(valid - 1.0, 1.0)
            screened = jnp.where(
                ok, w * x + (1.0 - w) * (total - xs) / peers,
                total / valid)
            return jnp.where(all_ok, out, screened)
        if topology == "ring":
            r = _shift(xs, n, 1, axis_name)
            out = (x + r) / 2.0 if how == "equal" \
                else w * x + (1.0 - w) * r
            if ok is None:
                return out
            r_ok = ok1f > 0
            if how == "equal":
                cnt = okf + ok1f
                screened = jnp.where(
                    cnt > 0, (xs + r) / jnp.maximum(cnt, 1.0), x)
            else:
                screened = jnp.where(
                    jnp.logical_and(ok, r_ok), out,
                    jnp.where(r_ok, r, x))
            return jnp.where(jnp.logical_and(ok, r_ok), out, screened)
        # double_ring: blend with the two predecessors
        r1 = _shift(xs, n, 1, axis_name)
        r2 = _shift(xs, n, 2, axis_name)
        out = (x + r1 + r2) / 3.0 if how == "equal" \
            else w * x + ((1.0 - w) / 2.0) * (r1 + r2)
        if ok is None:
            return out
        every = jnp.logical_and(ok, jnp.logical_and(ok1f > 0, ok2f > 0))
        cnt = okf + ok1f + ok2f
        if how == "equal":
            screened = jnp.where(
                cnt > 0, (xs + r1 + r2) / jnp.maximum(cnt, 1.0), x)
        else:
            pc = ok1f + ok2f
            pmean = (r1 + r2) / jnp.maximum(pc, 1.0)
            screened = jnp.where(
                ok, jnp.where(pc > 0, w * x + (1.0 - w) * pmean, x),
                jnp.where(pc > 0, pmean, x))
        return jnp.where(every, out, screened)

    agg = jax.tree_util.tree_map(per_leaf, tree)
    if poison is not None:
        return agg, okf
    return agg


# --------------------------------------------------------------------------
# Simulated many-worker aggregation (ISSUE 14): the flat-primitives
# reference path as PURE STACKED MATH — no mesh, no axis names
# --------------------------------------------------------------------------
# ``aggregate`` above runs inside shard_map with one real device per
# worker; ``aggregate_sim`` runs the SAME arithmetic on worker-stacked
# [N, ...] leaves living on a single chip (the scenario-lab engine,
# sim.py).  The two are bitwise-identical in fp32 because every collective
# has an exact stacked twin on XLA:
#
# - psum/pmean accumulate in RANK ORDER (a sequential left-fold over the
#   participants) — ``sim_fold`` reproduces that fold with a lax.scan over
#   the leading axis (a reassociating ``jnp.sum`` does NOT match, which is
#   why the fold is spelled out);
# - ppermute's receive-from-(rank - shift) is ``jnp.roll(x, shift,
#   axis=0)`` — pure data movement, trivially bitwise;
# - the blends are elementwise and identical by construction.
#
# The ``ok`` mask is the dense path's poison/validity screen reused as the
# scenario surface: client sampling and worker dropout exclude rows from
# the blend exactly the way a quarantined contribution is excluded, and a
# mask of all-ones selects the unscreened VALUES (the same all_ok-select
# construction ``aggregate`` uses; equal blends bitwise, weighted blends
# to fp32 FMA-contraction tolerance — the masked program's extra branches
# change LLVM's fusion context).  The parity gate never sees a mask at
# all: scenario knobs at their defaults compile none of this machinery
# (sim.SimEngine.scenario_on).


def sim_fold(x: jnp.ndarray) -> jnp.ndarray:
    """Sequential left-fold of a stacked [N, ...] array over its leading
    axis, in row order — the stacked twin of ``lax.psum`` (XLA's
    all-reduce accumulates participants in rank order, so ``x[0] + x[1] +
    ... + x[N-1]`` reproduces it bitwise; asserted against the real
    collective in tests/test_sim.py)."""
    if x.shape[0] == 1:
        return x[0]
    def add(acc, row):
        return acc + row, None
    acc, _ = lax.scan(add, x[0], x[1:])
    return acc


def _sim_rows(v: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """A per-worker [N] vector broadcast against a stacked [N, ...] leaf."""
    return v.reshape(v.shape[0], *([1] * (leaf.ndim - 1)))


def sim_wire_bytes(tree: PyTree, n: int, *, topology: str = "allreduce",
                   wire_dtype=None) -> int:
    """Per-worker bytes ONE simulated worker's sync WOULD move per round
    — the ``results["sim"]`` accounting of the fabric the simulation
    stands in for.  Per-leaf wire model: every leaf rides the fabric once
    per hop (gossip: ``GOSSIP_HOPS``; allreduce: one injection, the dense
    accounting), in ``wire_dtype`` when the simulated wire is compressed.
    fp32 equals ``sync_wire_bytes(mode="dense")`` exactly."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves or n <= 1:
        return 0
    hops = GOSSIP_HOPS.get(topology, 1)
    item = lambda x: (jnp.dtype(wire_dtype).itemsize
                      if wire_dtype is not None
                      else jnp.dtype(x.dtype).itemsize)
    return hops * sum(_leaf_size(x) * item(x) for x in leaves)


def aggregate_sim(tree: PyTree, *, how: str = "equal",
                  topology: str = "allreduce", local_weight: float = 0.5,
                  ok: jnp.ndarray | None = None, wire_dtype=None,
                  residual: PyTree | None = None
                  ) -> tuple[PyTree, PyTree | None]:
    """``aggregate`` on a worker-STACKED pytree: every leaf is [N, ...]
    and the collectives are stacked math on the leading axis (no mesh).

    fp32 with no mask is BITWISE the dense reference path (the module
    note above says why); that is the simulator's correctness gate.

    ``ok`` — optional [N] per-worker contribution-validity mask (bool or
    0/1 float): masked-out rows are excluded from every blend and the
    survivors renormalize, mirroring ``aggregate``'s poison screen
    row-for-row (an all-ones mask selects the unscreened values via the
    all_ok construction).  The scenario lab drives it with the
    client-sampling x dropout draw.

    ``wire_dtype`` + ``residual`` — the simulated compressed wire
    (bfloat16/int8) with single-stage error feedback: each worker's
    TRANSMITTED payload is encoded per worker row (int8: per-row
    symmetric max/127 scale), every value received from the fabric is
    the decoded fp32 payload, own values blend exactly, and the residual
    carries each worker's own transmission rounding into the next round
    — the gossip engine's wire model (comms.gossip_sync), applied
    per-leaf and extended to the allreduce topology (where the fabric's
    reduce likewise sees only wire payloads).  The bucketed engines'
    per-bucket scales/two-stage EF are engine artifacts the simulation
    does not reproduce; compressed parity is semantic, not bitwise
    (docs/ARCHITECTURE.md).  Returns ``(aggregated, new_residual)`` —
    ``new_residual`` is None when no error feedback is armed.
    """
    if how not in HOWS:
        raise ValueError(f"how must be one of {HOWS}, got {how!r}")
    if topology not in TOPOLOGIES:
        raise ValueError(
            f"topology must be one of {TOPOLOGIES}, got {topology!r}")
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return tree, residual
    n = int(leaves[0].shape[0])
    compressed = (wire_dtype is not None
                  and jnp.dtype(wire_dtype) != jnp.dtype(jnp.float32))
    ef = compressed and residual is not None
    if n == 1:
        return tree, residual
    w = local_weight
    okf = okb = valid = all_ok = ok1f = ok2f = None
    if ok is not None:
        okf = ok.astype(jnp.float32)
        okb = okf > 0
        valid = jnp.maximum(sim_fold(okf), 1.0)
        all_ok = valid >= n
        ok1f = jnp.roll(okf, 1, axis=0)
        if topology == "double_ring":
            ok2f = jnp.roll(okf, 2, axis=0)
    if compressed:
        _, encode = _wire_codec(jnp.dtype(wire_dtype))
        enc_rows = jax.vmap(lambda r: encode(r)[1])   # decoded payloads

    def per_leaf(x: jnp.ndarray, res):
        x32 = x.astype(jnp.float32)
        contrib = x32 + res if ef else x32
        if compressed:
            dec = enc_rows(contrib)
            new_res = contrib - dec if ef else None
        else:
            dec, new_res = contrib, None
        rows = lambda v: _sim_rows(v, x)
        xs = dec if okb is None else jnp.where(rows(okb), dec,
                                               jnp.zeros_like(dec))
        if topology == "allreduce":
            if how == "equal":
                out = jnp.broadcast_to(sim_fold(dec) / n, x.shape)
                if okb is None:
                    return out, new_res
                screened = jnp.broadcast_to(sim_fold(xs) / valid, x.shape)
                return jnp.where(all_ok, out, screened), new_res
            total = sim_fold(xs)
            peers_mean = (total - dec) / (n - 1)
            out = w * x + (1.0 - w) * peers_mean
            if okb is None:
                return out, new_res
            peers = jnp.maximum(valid - 1.0, 1.0)
            screened = jnp.where(
                rows(okb), w * x + (1.0 - w) * (total - xs) / peers,
                jnp.broadcast_to(total / valid, x.shape))
            return jnp.where(all_ok, out, screened), new_res
        if topology == "ring":
            r = jnp.roll(xs, 1, axis=0)
            out = (x + r) / 2.0 if how == "equal" else w * x + (1.0 - w) * r
            if okb is None:
                return out, new_res
            r_ok = rows(ok1f > 0)
            both = jnp.logical_and(rows(okb), r_ok)
            if how == "equal":
                cnt = rows(okf + ok1f)
                screened = jnp.where(
                    cnt > 0, (xs + r) / jnp.maximum(cnt, 1.0), x)
            else:
                screened = jnp.where(both, out, jnp.where(r_ok, r, x))
            return jnp.where(both, out, screened), new_res
        # double_ring: blend with the two predecessors
        r1 = jnp.roll(xs, 1, axis=0)
        r2 = jnp.roll(xs, 2, axis=0)
        out = (x + r1 + r2) / 3.0 if how == "equal" \
            else w * x + ((1.0 - w) / 2.0) * (r1 + r2)
        if okb is None:
            return out, new_res
        every = jnp.logical_and(rows(okb), jnp.logical_and(
            rows(ok1f > 0), rows(ok2f > 0)))
        cnt = rows(okf + ok1f + ok2f)
        if how == "equal":
            screened = jnp.where(
                cnt > 0, (xs + r1 + r2) / jnp.maximum(cnt, 1.0), x)
        else:
            pc = rows(ok1f + ok2f)
            pmean = (r1 + r2) / jnp.maximum(pc, 1.0)
            screened = jnp.where(
                rows(okb), jnp.where(pc > 0, w * x + (1.0 - w) * pmean, x),
                jnp.where(pc > 0, pmean, x))
        return jnp.where(every, out, screened), new_res

    flat, treedef = jax.tree_util.tree_flatten(tree)
    res_flat = (jax.tree_util.tree_leaves(residual) if ef
                else [None] * len(flat))
    outs = [per_leaf(x, r) for x, r in zip(flat, res_flat)]
    agg = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_residual = (jax.tree_util.tree_unflatten(
        treedef, [o[1] for o in outs]) if ef else None)
    return agg, new_residual


def _wire_codec(wdt):
    """Wire codec for one bucket's dtype: ``(quantized, encode)``.

    ``encode(x32)`` -> (wire payload, fp32 decode of the payload,
    per-bucket fp32 scale or None).  bf16 is a plain downcast; int8 is
    symmetric round-to-nearest on a max|x|/127 grid with the sender's
    fp32 scale riding next to the payload."""
    quantized = wdt == jnp.dtype(jnp.int8)

    def encode(x32):
        if not quantized:
            y = x32.astype(wdt)
            return y, y.astype(jnp.float32), None
        scale = jnp.maximum(jnp.max(jnp.abs(x32)) / 127.0,
                            jnp.float32(1e-30))
        q = jnp.clip(jnp.round(x32 / scale), -127.0, 127.0).astype(
            jnp.int8)
        return q, q.astype(jnp.float32) * scale, scale

    return quantized, encode


# --------------------------------------------------------------------------
# Semi-synchronous delivery blend (ISSUE 16)
# --------------------------------------------------------------------------
# Under ``--sync_staleness K`` the standalone sync program no longer hands
# its blend straight back as the next round's params — round R+1 has
# already dispatched off the PRE-sync params T_R by the time sync R
# finishes.  Instead the sync emits the consensus DELTA
#
#     D_R = blend(T_R) - T_R
#
# and the engine folds it into whatever params exist when the delta is
# delivered (the entry of round R+K+1):  params' = params + D_R.  The two
# halves below are the whole contract:
#
# * additivity is what makes the schedule composable — K deltas in flight
#   fold in any params state without re-reading T_R (whose buffers round
#   R+1's donated round program has already consumed);
# * at K=0 the pair is exact identity in fp32 IF the engine skips it
#   entirely (x + (b - x) == b does NOT hold bitwise in floating point),
#   which is why the K=0 path never routes through these helpers — the
#   bitwise gate is structural, not arithmetic;
# * EF residuals compose because the residual update is a function of the
#   sync's OWN wire rounding, computed inside the sync program against
#   T_R — the delta just carries the post-EF blend's displacement;
# * weighted (straggler-proportional) blends compose for the same reason:
#   the blend weights are resolved inside the sync program, the delta is
#   its output displacement;
# * scatter-resident params do NOT compose (delivery needs full
#   replicated trees on both sides) — config rejects / auto-demotes.


def stale_delta(blended: PyTree, base: PyTree) -> PyTree:
    """Consensus displacement ``blended - base`` per leaf, in the leaf's
    own dtype — the payload a stale sync program returns instead of the
    blend itself (``base`` is the pre-sync params snapshot the sync was
    computed from)."""
    return jax.tree_util.tree_map(lambda b, t: b - t, blended, base)


def deliver_stale(params: PyTree, delta: PyTree) -> PyTree:
    """Fold a stale consensus delta into freshly trained params:
    ``params + delta`` per leaf.  Pure elementwise math — the engine jits
    it with both inputs donated (the delta dies here; the params buffer
    is replaced by the delivered tree)."""
    return jax.tree_util.tree_map(lambda p, d: p + d, params, delta)


# --------------------------------------------------------------------------
# Sharded round sync: flatten-and-bucket -> reduce-scatter -> scale the
# 1/N shard -> all-gather (ISSUE 2 tentpole)
# --------------------------------------------------------------------------
# The dense path above all-reduces every fully-replicated parameter, so each
# worker's per-round wire traffic is the whole model, and the scale/average
# arithmetic runs on all S elements per worker.  The reduce-scatter form
# ("Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
# Training", PAPERS.md) assigns each worker ownership of a contiguous 1/N
# shard of every bucket: the scatter sums each shard on its owner, the
# average (or straggler-weighted blend) runs on S/N elements, and the
# all-gather redistributes the result.  Per-worker send traffic is
# 2(N-1)/N x S x wire_bytes per bucket (the two phases each move (N-1)/N of
# the bucket) versus the dense path's full replicated buffer per collective,
# and — unlike the dense form — the reduction work itself parallelizes
# across the worker axis.  In fp32 the result is BIT-IDENTICAL to the dense
# all-reduce: both sum the same N addends through the same XLA reduction
# and divide by N (asserted by tests/test_sync.py).


class _Bucket(NamedTuple):
    """One contiguous 1D collective segment of the flattened pytree."""

    dtype: Any                 # numpy dtype of every leaf in the bucket
    padded: int                # total elements incl. zero padding; % n == 0
    items: tuple               # ((leaf_index, offset, size), ...)


def _leaf_size(x) -> int:
    return int(math.prod(x.shape)) if x.shape else 1


def bucket_plan(leaves, n: int, bucket_bytes: int = DEFAULT_BUCKET_BYTES
                ) -> list[_Bucket]:
    """Greedy bucketing of flattened leaves into ~``bucket_bytes`` segments.

    Leaves are taken in pytree-flatten order and grouped by dtype (a bucket
    is one collective; mixed dtypes would force a common wire type).  A
    bucket closes once it reaches the target byte size; a single leaf larger
    than the target gets its own bucket (leaves are never split, so every
    leaf occupies one contiguous segment).  Each bucket is padded with zeros
    to a multiple of ``n`` so the reduce-scatter tiles evenly; padding
    participates in the collectives (it sums to zero) and is dropped at
    unpack, so the round trip is exact.
    """
    groups: dict[Any, list[int]] = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    out: list[_Bucket] = []
    for dtype, idxs in groups.items():
        target = max(1, int(bucket_bytes) // max(1, dtype.itemsize))
        items: list[tuple] = []
        offset = 0
        for i in idxs:
            size = _leaf_size(leaves[i])
            items.append((i, offset, size))
            offset += size
            if offset >= target:
                out.append(_Bucket(dtype, -(-offset // n) * n, tuple(items)))
                items, offset = [], 0
        if items:
            out.append(_Bucket(dtype, -(-offset // n) * n, tuple(items)))
    return out


def sync_wire_bytes(tree: PyTree, n: int, *, mode: str = "sharded",
                    wire_dtype=None,
                    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                    topology: str = "allreduce") -> int:
    """Per-worker bytes SENT by one round sync of ``tree`` (shapes only —
    leaves may be arrays or ShapeDtypeStructs).

    Accounting model (one number per worker, per round):

    - ``dense``: every collective carries the full replicated buffer — each
      worker injects S x 4 bytes (the dense path is always fp32), once per
      gossip hop for ring/double-ring topologies;
    - ``sharded``: reduce-scatter sends (N-1)/N of each padded bucket and
      all-gather sends its (N-1)/N again, in the wire dtype —
      2(N-1)/N x padded x itemsize per bucket (int8's per-bucket fp32
      scale adds 8 bytes per worker per bucket — noise next to the
      payload; excluded from the accounting);
    - ``gossip``: each hop ppermutes every packed bucket once in the wire
      dtype — hops x filled x itemsize per bucket (no padding: ppermute
      has no tiling constraint; the int8 scale scalar is again excluded).
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves or n <= 1:
        return 0
    hops = GOSSIP_HOPS.get(topology, 1)
    if mode == "dense":
        return hops * sum(_leaf_size(x) * jnp.dtype(x.dtype).itemsize
                          for x in leaves)
    wire_item = lambda b: (jnp.dtype(wire_dtype).itemsize
                           if wire_dtype is not None else b.dtype.itemsize)
    if mode == "gossip":
        return sum(hops * sum(size for (_i, _off, size) in b.items)
                   * wire_item(b)
                   for b in bucket_plan(leaves, n, bucket_bytes))
    return sum(2 * (n - 1) * (b.padded // n) * wire_item(b)
               for b in bucket_plan(leaves, n, bucket_bytes))


def sharded_sync(tree: PyTree, *, how: str = "equal",
                 local_weight: float = 0.5, axis_name: str = DATA_AXIS,
                 wire_dtype=None, residual: PyTree | None = None,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 opt_placement: str = "sharded",
                 residency: str = "replicated"
                 ) -> tuple[PyTree, PyTree | None]:
    """Sharded all-reduce aggregation of a per-worker pytree.

    Must be called inside ``shard_map`` (``axis_name`` bound), like
    ``aggregate``.  Semantics match ``aggregate(topology="allreduce")``:
    ``equal`` is the cross-worker mean, ``weighted`` the self-exclusive
    peer-mean blend — in fp32 both are bit-identical to the dense path.

    ``wire_dtype`` compresses the two collective phases: bfloat16 halves
    the wire bytes (plain downcast); int8 quarters them via symmetric
    per-bucket quantization — each worker scales its bucket by
    ``max|x| / 127`` (an fp32 scalar riding a tiny all-gather next to the
    int8 payload), rounds to the nearest int8 step, and receivers
    dequantize with the sender's scale before the fp32 accumulation, so
    the sum is exact in fp32 given the quantized contributions.
    ``residual`` enables error feedback for the compression:
    each worker carries (a) the fp32 rounding error of its own compressed
    contribution and (b) n x the rounding error of the gathered mean over
    the shard it owns, both re-injected through next round's sum — so
    quantization error accumulates in the residual instead of in the
    parameters, and sub-quantum parameter movement still gets through.
    Returns ``(synced_tree, new_residual)`` — ``new_residual`` is
    ``residual`` unchanged (possibly None) when no error feedback is
    active.

    ``opt_placement`` places the apply stage (the blend scaling between
    the two collective phases — ISSUE 9): ``"sharded"`` scales on the
    1/N psum_scatter shard so only post-update values ride the
    all_gather; ``"replicated"`` gathers the raw shard sums and scales
    the full buffer on every worker — the ZeRO-1 paper's A/B twin,
    bit-identical in fp32 (elementwise scaling commutes with the gather
    bit-for-bit).  Compressed wires require the sharded placement: the
    gathered payload IS the encoded mean, so the scale must run before
    the encode on the shard (config.py validates).
    """
    synced, new_res, _ = sharded_opt_sync(
        tree, how=how, local_weight=local_weight, axis_name=axis_name,
        wire_dtype=wire_dtype, residual=residual,
        bucket_bytes=bucket_bytes, opt_placement=opt_placement,
        residency=residency)
    return synced, new_res


# Round-optimizer tracker (ISSUE 9): torch.optim.Adam moment defaults,
# matching the engine's per-batch Adam (train.py scale_by_adam betas).
ROUND_ADAM_B1 = 0.9
ROUND_ADAM_B2 = 0.999

OPT_PLACEMENTS = ("replicated", "sharded")


def _bucket_name(i: int) -> str:
    return f"b{i:04d}"


def round_opt_init(per_worker_tree: PyTree, n: int, *, placement: str,
                   bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> dict:
    """Zero-initialized round-optimizer moments for ``per_worker_tree``
    (leaves may be arrays or ShapeDtypeStructs — per-worker shapes, no
    worker axis), worker-STACKED for the engine state.

    Layout per bucket of the sync engine's plan: ``sharded`` stores each
    worker's OWN 1/N shard row — ``[n, padded // n]`` — so per-worker
    resident bytes are 1/N of the moment vector; ``replicated`` stores
    the full padded vector on every worker — ``[n, padded]`` — the
    N-copies baseline the ZeRO-1 scheme removes.  Both track the same
    worker-invariant quantity (Adam moments of the cross-worker mean of
    the aggregated tree), so rows of the replicated layout are
    identical and the sharded layout is its exact row-partition
    (bitwise-gated in tests/test_opt_placement.py)."""
    if placement not in OPT_PLACEMENTS:
        raise ValueError(
            f"placement must be one of {OPT_PLACEMENTS}, got {placement!r}")
    leaves = jax.tree_util.tree_leaves(per_worker_tree)
    out: dict = {}
    for i, b in enumerate(bucket_plan(leaves, n, bucket_bytes)):
        row = b.padded // n if placement == "sharded" else b.padded
        out[_bucket_name(i)] = {
            "mu": jnp.zeros((n, row), jnp.float32),
            "nu": jnp.zeros((n, row), jnp.float32)}
    return out


def round_opt_relayout(tracker: dict, per_worker_tree: PyTree, n_new: int,
                       *, placement: str,
                       bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> dict:
    """Re-layout a HOST round-optimizer tracker for a new worker count
    (elastic membership change, ISSUE 9 satellite).

    The tracked quantity is worker-invariant, so a membership change
    never edits rows the way per-worker state does: the moment VECTOR
    is reconstructed (concatenate the shard rows / take the replicated
    row), re-padded for the new bucket tiling (padding positions carry
    exactly-zero moments — the padded mean is zero every round — so
    trimming or extending the pad is exact), and re-split.  ``tracker``
    layout must match ``placement``; returns numpy arrays."""
    import numpy as np

    leaves = jax.tree_util.tree_leaves(per_worker_tree)
    plan = bucket_plan(leaves, max(1, n_new), bucket_bytes)
    out: dict = {}
    for i, b in enumerate(plan):
        name = _bucket_name(i)
        if name not in tracker:
            raise ValueError(
                f"round-optimizer tracker has no bucket {name} "
                f"({len(tracker)} buckets vs plan {len(plan)})")
        filled = sum(size for (_i, _off, size) in b.items)
        row_new = b.padded // n_new if placement == "sharded" else b.padded
        out[name] = {}
        for m in ("mu", "nu"):
            arr = np.asarray(tracker[name][m])
            vec = (arr.reshape(-1) if placement == "sharded"
                   else arr[0])
            if vec.size < filled:
                raise ValueError(
                    f"round-optimizer bucket {name}/{m} carries "
                    f"{vec.size} elements but the plan needs {filled}")
            vec = vec[:filled]
            pad = (n_new * row_new if placement == "sharded"
                   else b.padded) - filled
            if pad:
                vec = np.concatenate([vec, np.zeros(pad, vec.dtype)])
            if placement == "sharded":
                out[name][m] = vec.reshape(n_new, row_new)
            else:
                out[name][m] = np.broadcast_to(
                    vec, (n_new, b.padded)).copy()
    return out


# ----------------------------------------------------------------------
# Scatter-resident consensus params (ISSUE 11): the between-round
# parameter layout of the round-loop FSDP scheme.  One bucket of the
# sync engine's plan maps to one [n, padded // n] array whose row w is
# worker w's contiguous 1/N shard of the packed consensus vector — the
# exact psum_scatter output layout, which is what lets the sync END at
# the scatter (apply on the shard, no trailing all_gather) and the NEXT
# round's entry gather reconstruct the full tree bit-for-bit.  Padding
# positions carry exactly-zero values (the padded mean is zero every
# round), so re-tiling for a new worker count is exact — the same
# invariant the round-optimizer tracker relies on.
# ----------------------------------------------------------------------

PARAM_RESIDENCIES = ("replicated", "resident")


def resident_from_tree(per_worker_tree: PyTree, n: int, *,
                       bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                       n_rows: int | None = None) -> dict:
    """HOST: pack one worker's CONSENSUS params into the resident layout.

    ``per_worker_tree`` holds the shared consensus values (equal-blend
    weights mode: every worker's post-sync params are identical, so any
    row is the consensus).  Returns ``{bucket: [n, padded // n]}`` numpy
    arrays — row w is worker w's shard.  Used at engine init (broadcast
    init IS a consensus) and by the cross-residency checkpoint/elastic
    re-layouts.

    ``n_rows`` (ISSUE 13): the hierarchical mesh stacks S slices of W
    workers, so the worker axis carries ``n_rows = S x n`` rows while
    the bucket tiling stays per-INNER-shard (``padded // n``); the one
    consensus is tiled across the slice groups (a broadcast init, or a
    global consensus restored from a flat checkpoint, IS every slice's
    consensus)."""
    import numpy as np

    rows = n_rows or n
    if rows % n:
        raise ValueError(
            f"resident layout rows ({rows}) must be a multiple of the "
            f"inner shard count ({n})")
    leaves = jax.tree_util.tree_leaves(per_worker_tree)
    out: dict = {}
    for i, b in enumerate(bucket_plan(leaves, n, bucket_bytes)):
        parts = [np.asarray(leaves[j]).reshape(-1).astype(b.dtype)
                 for (j, _off, _size) in b.items]
        vec = np.concatenate(parts) if len(parts) > 1 else parts[0]
        pad = b.padded - vec.size
        if pad:
            vec = np.concatenate([vec, np.zeros(pad, vec.dtype)])
        shards = vec.reshape(n, b.padded // n)
        out[_bucket_name(i)] = (shards if rows == n
                                else np.tile(shards, (rows // n, 1)))
    return out


def resident_to_tree(resident: dict, per_worker_template: PyTree, *,
                     bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> PyTree:
    """HOST: unpack a resident layout back into the consensus tree.

    The host twin of the round-entry device gather — concatenating the
    shard rows IS the all_gather (pure data movement, bit-exact), so
    final-eval / checkpoint-relayout consumers reconstruct exactly the
    tree the round program would have gathered.  The worker count is
    read off the rows."""
    import numpy as np

    leaves, treedef = jax.tree_util.tree_flatten(per_worker_template)
    n = None
    for arr in resident.values():
        n = int(np.shape(arr)[0])
        break
    if not n:
        raise ValueError("resident params layout is empty")
    out: list = [None] * len(leaves)
    plan = bucket_plan(leaves, n, bucket_bytes)
    for i, b in enumerate(plan):
        name = _bucket_name(i)
        if name not in resident:
            raise ValueError(
                f"resident params layout has no bucket {name} "
                f"({len(resident)} buckets vs plan {len(plan)})")
        arr = np.asarray(resident[name])
        if arr.shape != (n, b.padded // n):
            raise ValueError(
                f"resident params bucket {name} has shape {arr.shape}, "
                f"expected {(n, b.padded // n)} (sync_bucket_mb or "
                "worker count changed since the state was built?)")
        vec = arr.reshape(-1)
        for (j, off, size) in b.items:
            out[j] = vec[off:off + size].reshape(
                np.shape(leaves[j])).astype(np.dtype(leaves[j].dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def resident_relayout(resident: dict, per_worker_template: PyTree,
                      n_new: int, *,
                      bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> dict:
    """Re-tile a HOST resident params layout for a new worker count
    (elastic membership change, ISSUE 11).

    The consensus vector is worker-invariant, so the re-layout mirrors
    ``round_opt_relayout``: reconstruct the vector from the shard rows,
    re-pad for the new bucket tiling (pad positions carry exactly-zero
    values — the padded mean is zero every round — so trimming or
    extending the pad is exact), and re-split."""
    import numpy as np

    leaves = jax.tree_util.tree_leaves(per_worker_template)
    plan = bucket_plan(leaves, max(1, n_new), bucket_bytes)
    out: dict = {}
    for i, b in enumerate(plan):
        name = _bucket_name(i)
        if name not in resident:
            raise ValueError(
                f"resident params layout has no bucket {name} "
                f"({len(resident)} buckets vs plan {len(plan)})")
        vec = np.asarray(resident[name]).reshape(-1)
        filled = sum(size for (_j, _off, size) in b.items)
        if vec.size < filled:
            raise ValueError(
                f"resident params bucket {name} carries {vec.size} "
                f"elements but the plan needs {filled}")
        vec = vec[:filled]
        pad = b.padded - filled
        if pad:
            vec = np.concatenate([vec, np.zeros(pad, vec.dtype)])
        out[name] = vec.reshape(n_new, b.padded // n_new)
    return out


def buddy_wire_bytes(tree: PyTree, n: int, *, wire_dtype=None,
                     bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                     params: bool = True, tracker: bool = False,
                     ef: bool = False) -> int:
    """Per-worker bytes SENT by the ISSUE 12 buddy-redundancy hop —
    ONE extra ppermute per bucket at scatter exit, carrying exactly the
    shard-resident rows: the ``padded/N`` resident params row in the
    WIRE dtype (``params``), the two fp32 tracker rows (``tracker``),
    and the fp32 residual own-span (``ef``).  Zero when nothing is
    shard-resident (n <= 1 or an empty tree) — the accounting twin of
    ``sync_wire_bytes``, asserted in tests/test_sync.py."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves or n <= 1:
        return 0
    total = 0
    for b in bucket_plan(leaves, n, bucket_bytes):
        row = b.padded // n
        wire_item = (jnp.dtype(wire_dtype).itemsize
                     if wire_dtype is not None else b.dtype.itemsize)
        if params:
            total += row * wire_item
        if ef:
            total += row * 4
        if tracker:
            total += 2 * row * 4
    return total


def derive_buddy(per_worker_template: PyTree, n: int, *,
                 bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                 params_resident: dict | None = None,
                 round_opt: dict | None = None,
                 residual: PyTree | None = None,
                 opt_placement: str = "sharded") -> dict | None:
    """HOST: the buddy layout implied by a state's shard-resident rows
    (ISSUE 12) — ``buddy[bucket][comp][w]`` is worker ``(w-1) % n``'s
    component row, exactly what the device hop's ring ppermute delivers
    (``ring_neighbors(n, 1)``: every worker holds its PREDECESSOR's
    spans).

    Used wherever the state is (re)built on host and the device copy
    does not exist yet: engine init, checkpoint restore (buddy rows are
    STRIPPED from checkpoints — they are derivable, and saving them
    would couple the manifest layout to the redundancy flag), and the
    elastic re-tile.  ``residual`` contributes each worker's OWN-span
    slice of its packed fp32 residual (the span carrying the stage-2
    consensus correction).  Returns None when nothing is
    shard-resident."""
    import numpy as np

    if n < 2:
        return None
    leaves = jax.tree_util.tree_leaves(per_worker_template)
    if not leaves:
        return None
    res_rows = (None if residual is None
                else [np.asarray(x) for x in
                      jax.tree_util.tree_leaves(residual)])
    tracker_on = round_opt is not None and opt_placement == "sharded"
    if params_resident is None and res_rows is None and not tracker_on:
        return None
    out: dict = {}
    for i, b in enumerate(bucket_plan(leaves, n, bucket_bytes)):
        name = _bucket_name(i)
        row = b.padded // n
        bud: dict = {}
        if params_resident is not None:
            arr = np.asarray(params_resident[name])
            if arr.shape != (n, row):
                raise ValueError(
                    f"resident params bucket {name} has shape "
                    f"{arr.shape}, expected {(n, row)}")
            bud["params"] = np.roll(arr, 1, axis=0).copy()
        if res_rows is not None:
            mat = np.zeros((n, b.padded), np.float32)
            for (j, off, size) in b.items:
                mat[:, off:off + size] = res_rows[j].reshape(n, -1)
            spans = np.stack([mat[w, w * row:(w + 1) * row]
                              for w in range(n)])
            bud["res"] = np.roll(spans, 1, axis=0).copy()
        if tracker_on:
            for m in ("mu", "nu"):
                arr = np.asarray(round_opt[name][m])
                if arr.shape != (n, row):
                    raise ValueError(
                        f"round-opt bucket {name}/{m} has shape "
                        f"{arr.shape}, expected {(n, row)} (buddy "
                        "redundancy covers the SHARDED placement)")
                bud[m] = np.roll(arr, 1, axis=0).copy()
        out[name] = bud
    return out


def buddy_restore_rows(host_state_parts: dict, buddy: dict,
                       lost_positions: list[int],
                       per_worker_template: PyTree, *,
                       bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> dict:
    """HOST: reconstruct CRASHED workers' shard-resident rows from their
    buddy copies (ISSUE 12 recovery).

    ``host_state_parts`` maps component name -> layout:
    ``{"params_resident": {bucket: [n, row]},
       "round_opt": {bucket: {"mu"/"nu": [n, row]}},
       "residual": params-shaped [n, ...] pytree}`` (absent components
    omitted).  For each lost position ``p`` the holder is ``(p+1) % n``
    — its buddy row IS the lost worker's span, by the ring hop's
    construction.  A holder that is itself lost is a DOUBLE FAULT and
    raises (the caller falls back to the newest committed checkpoint).
    The residual component is FOLDED into the holder's own residual at
    the lost span's positions (the pending stage-2 consensus correction
    survives the crash instead of vanishing with the row); resident
    params / tracker rows are patched in place.  Returns the patched
    ``host_state_parts`` (new arrays, inputs untouched)."""
    import numpy as np

    leaves = jax.tree_util.tree_leaves(per_worker_template)
    resident = host_state_parts.get("params_resident")
    round_opt = host_state_parts.get("round_opt")
    residual = host_state_parts.get("residual")
    n = None
    for comp in (resident, round_opt):
        if comp:
            first = next(iter(comp.values()))
            arr = first.get("mu") if isinstance(first, dict) else first
            n = int(np.shape(arr)[0])
            break
    if n is None and residual is not None:
        n = int(np.shape(jax.tree_util.tree_leaves(residual)[0])[0])
    if n is None:
        raise ValueError("nothing shard-resident to restore")
    lost = sorted(set(int(p) for p in lost_positions))
    for p in lost:
        if not 0 <= p < n:
            raise ValueError(f"lost position {p} outside worker axis {n}")
        holder = (p + 1) % n
        if holder in lost:
            raise ValueError(
                f"double fault: crashed worker at position {p} and its "
                f"buddy at position {holder} are both lost — the span "
                "exists nowhere in memory (fall back to the newest "
                "committed checkpoint)")
    plan = bucket_plan(leaves, n, bucket_bytes)
    out = dict(host_state_parts)
    if resident is not None:
        patched = {k: np.asarray(v).copy() for k, v in resident.items()}
        for i, b in enumerate(plan):
            name = _bucket_name(i)
            for p in lost:
                patched[name][p] = np.asarray(
                    buddy[name]["params"])[(p + 1) % n]
        out["params_resident"] = patched
    if round_opt is not None and any(
            "mu" in bud for bud in buddy.values()):
        patched = {k: {m: np.asarray(v).copy() for m, v in d.items()}
                   for k, d in round_opt.items()}
        for i, b in enumerate(plan):
            name = _bucket_name(i)
            for p in lost:
                for m in ("mu", "nu"):
                    patched[name][m][p] = np.asarray(
                        buddy[name][m])[(p + 1) % n]
        out["round_opt"] = patched
    if residual is not None and any(
            "res" in bud for bud in buddy.values()):
        res_leaves, res_def = jax.tree_util.tree_flatten(residual)
        res_leaves = [np.asarray(x).copy() for x in res_leaves]
        for i, b in enumerate(plan):
            name = _bucket_name(i)
            row = b.padded // n
            for p in lost:
                holder = (p + 1) % n
                span = np.asarray(buddy[name]["res"])[holder]
                lo, hi = p * row, (p + 1) * row
                for (j, off, size) in b.items:
                    a, z = max(off, lo), min(off + size, hi)
                    if a >= z:
                        continue
                    flat = res_leaves[j][holder].reshape(-1)
                    flat[a - off:z - off] += span[a - lo:z - lo]
        out["residual"] = jax.tree_util.tree_unflatten(res_def,
                                                       res_leaves)
    return out


def resident_gather(shards: dict, per_worker_template: PyTree, *,
                    axis_name: str = DATA_AXIS,
                    bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> PyTree:
    """The round-entry gather (ISSUE 11 tentpole): inside ``shard_map``,
    all_gather each bucket's resident shard row over the worker axis and
    unpack the full consensus tree.

    ``shards`` holds this worker's squeezed per-worker rows
    (``[padded // n]`` per bucket); the gathered full buffers are
    transient compute-scope values — XLA frees them with the program, so
    the RESIDENT state never exceeds 1/N per worker.  Bit-exactness: the
    gather concatenates the same shard values the sync's trailing
    all_gather used to move, so entry-gather(exit-scatter) reproduces
    the replicated twin's tree bit-for-bit."""
    leaves, treedef = jax.tree_util.tree_flatten(per_worker_template)
    n = lax.axis_size(axis_name)
    out: list = [None] * len(leaves)
    plan = bucket_plan(leaves, n, bucket_bytes)
    for i, b in enumerate(plan):
        name = _bucket_name(i)
        if name not in shards:
            raise ValueError(
                f"resident params layout has no bucket {name} "
                f"({len(shards)} buckets vs plan {len(plan)})")
        row = shards[name]
        if tuple(row.shape) != (b.padded // n,):
            raise ValueError(
                f"resident params bucket {name} row has shape "
                f"{tuple(row.shape)}, expected {(b.padded // n,)} "
                "(sync_bucket_mb or worker count changed?)")
        full = lax.all_gather(row, axis_name, tiled=True)
        for (j, off, size) in b.items:
            leaf = leaves[j]
            out[j] = full[off:off + size].reshape(leaf.shape).astype(
                leaf.dtype)
    return jax.tree_util.tree_unflatten(treedef, out)


def make_resident_gather(mesh, per_worker_template: PyTree, *,
                         bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                         donate: bool = False):
    """Jitted stand-alone round-entry gather over a worker-stacked
    resident layout (tests/test_param_residency.py): takes ``{bucket:
    [n, padded // n]}`` and returns the worker-stacked full tree
    ([n, ...] leaves).  ``donate=True`` donates the resident input —
    the engine's enter program shape."""
    from jax.sharding import PartitionSpec as P

    from .mesh import stack_axes

    # slice-aware (ISSUE 13): on a hierarchical mesh the rows stack over
    # (slice, data) and the gather still runs over the inner ``data``
    # axis only — each slice reconstructs ITS OWN consensus
    spec = P(stack_axes(mesh))

    def _gather(shards):
        def inner(sh):
            sq = jax.tree_util.tree_map(lambda x: x[0], sh)
            tree = resident_gather(sq, per_worker_template,
                                   bucket_bytes=bucket_bytes)
            return jax.tree_util.tree_map(lambda x: x[None], tree)
        return jax.shard_map(inner, mesh=mesh, in_specs=(spec,),
                             out_specs=spec)(shards)

    return jax.jit(_gather, donate_argnums=(0,) if donate else ())


def _contribution_ok(poison, leaves, res_leaves):
    """Per-worker validity of this worker's sync contribution (ISSUE 12
    integrity screen): not poisoned AND every leaf (plus the EF residual
    it folds in) entirely finite.  A scalar bool, computed inside
    shard_map."""
    ok = jnp.logical_not(jnp.asarray(poison, bool).reshape(()))
    for x in leaves:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(
            x.astype(jnp.float32))))
    if res_leaves is not None:
        for x in res_leaves:
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(
                x.astype(jnp.float32))))
    return ok


def sharded_opt_sync(tree: PyTree, *, how: str = "equal",
                     local_weight: float = 0.5, axis_name: str = DATA_AXIS,
                     wire_dtype=None, residual: PyTree | None = None,
                     bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                     opt_placement: str = "sharded",
                     tracker: dict | None = None,
                     residency: str = "replicated",
                     buddy: bool = False,
                     poison=None
                     ) -> tuple:
    """``sharded_sync`` with the full apply-stage surface (ISSUE 9):
    optimizer placement plus the round-level Adam moment tracker.

    ``residency`` (ISSUE 11) places the sync's OUTPUT: ``"replicated"``
    all_gathers the post-apply values home (the full synced tree on
    every worker, as always); ``"resident"`` ENDS the program at the
    scatter — the first return value is then the ``{bucket:
    [padded // n]}`` resident shard layout (this worker's decoded
    post-apply shard), the trailing all_gather is gone, and the next
    round's ``resident_gather`` reconstructs the full tree bit-for-bit
    at entry.  Resident output requires the equal blend on the sharded
    placement: the weighted blend's own-term is irreducibly per-worker
    and a replicated apply has no shard-side output (config.py resolves
    the combinations eagerly).

    ``tracker`` (per-worker slices of a ``round_opt_init`` tree, i.e.
    already squeezed inside shard_map) updates Adam moments of the
    CROSS-WORKER MEAN of ``tree`` — the worker-invariant aggregated
    quantity, which is what makes the moments shardable at all.  Under
    ``opt_placement="sharded"`` each worker updates only the moment
    slice of the bucket shard it owns (1/N state, 1/N FLOPs); under
    ``"replicated"`` every worker updates the full vector from the
    gathered sums — N identical copies of the same arithmetic, kept as
    the bitwise A/B twin.

    ``buddy`` (ISSUE 12) fuses ONE extra per-bucket ppermute hop at
    scatter exit: each worker also sends its post-apply resident shard
    row (the ``residency="resident"`` output — the WIRE-dtype payload
    plus its scale, decoded buddy-side, so the copy is bitwise the
    owner's row), the sharded tracker's new mu/nu rows, and (under EF)
    the owned span of its fp32 residual to its ring SUCCESSOR
    (``ring_neighbors(n, 1)``) — so every 1/N span of shard-resident
    state lives on exactly two workers and an abrupt worker loss is
    recoverable from the buddy copy.  Pure data movement: every other
    output is bitwise-unchanged.

    ``poison`` (ISSUE 12 integrity screen) is this worker's scalar
    poison flag: when not None, each worker's contribution is screened
    sender-side (poisoned or non-finite contributions enter the
    collectives as exact zeros) and the blend renormalizes over the
    count of valid workers — the quarantined worker receives the
    survivors' consensus.  When every worker is valid the outputs are
    bitwise-identical to the unscreened program (the screened branch is
    selected away by a ``where`` on the full-count predicate).

    Returns ``(synced, new_residual, new_tracker)``, with the buddy
    layout appended when ``buddy`` and this worker's validity flag (an
    fp32 0/1 scalar) appended when ``poison is not None`` — callers
    unpack exactly what they armed."""
    if how not in HOWS:
        raise ValueError(f"how must be one of {HOWS}, got {how!r}")
    if opt_placement not in OPT_PLACEMENTS:
        raise ValueError(
            f"opt_placement must be one of {OPT_PLACEMENTS}, got "
            f"{opt_placement!r}")
    if residency not in PARAM_RESIDENCIES:
        raise ValueError(
            f"residency must be one of {PARAM_RESIDENCIES}, got "
            f"{residency!r}")
    resident = residency == "resident"
    if resident and (how != "equal" or opt_placement != "sharded"):
        raise ValueError(
            "a scatter-resident output requires the equal blend on the "
            "sharded placement: the weighted own-term blend is "
            "irreducibly per-worker and a replicated apply produces no "
            f"shard-side output (got how={how!r}, "
            f"opt_placement={opt_placement!r}; config.py resolves these "
            "combinations to the replicated residency)")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    n = lax.axis_size(axis_name)
    if buddy and n < 2:
        raise ValueError(
            "buddy redundancy needs a worker axis of size >= 2 (a lone "
            "worker has no ring successor to back its shard up on)")
    if not leaves or n == 1:
        if resident:
            raise ValueError(
                "a scatter-resident output needs a worker axis of size "
                ">= 2 and a non-empty tree (nothing to shard)")
        if poison is not None:
            ok1 = _contribution_ok(poison, leaves, None)
            return tree, residual, tracker, ok1.astype(jnp.float32)
        return tree, residual, tracker
    res_leaves = None
    if residual is not None:
        res_leaves = jax.tree_util.tree_leaves(residual)
        if len(res_leaves) != len(leaves):
            raise ValueError(
                "residual must mirror the synced tree: "
                f"{len(res_leaves)} leaves vs {len(leaves)}")
    ok = okf = valid = None
    if poison is not None:
        ok = _contribution_ok(poison, leaves, res_leaves)
        okf = ok.astype(jnp.float32)
        valid = jnp.maximum(lax.psum(okf, axis_name), 1.0)
        all_ok = valid >= n   # every contribution finite -> the
        #                       unscreened arithmetic is selected below,
        #                       so clean rounds stay bitwise-identical
    compressed_wire = (wire_dtype is not None
                       and jnp.dtype(wire_dtype) != jnp.dtype(jnp.float32))
    if compressed_wire and opt_placement != "sharded":
        raise ValueError(
            "a compressed wire quantizes the gathered mean, which forces "
            "the scale-then-encode apply onto the shard: opt_placement "
            f"must be 'sharded', got {opt_placement!r}")
    new_tracker: dict | None = {} if tracker is not None else None
    resident_out: dict = {}
    buddy_out: dict = {}
    out: list = [None] * len(leaves)
    new_res: list | None = [None] * len(leaves) if res_leaves is not None \
        else None
    w = local_weight
    for bi, b in enumerate(bucket_plan(leaves, n, bucket_bytes)):
        parts, filled = [], 0
        for (i, _off, size) in b.items:
            x = leaves[i].astype(jnp.float32).reshape(-1)
            if res_leaves is not None:
                x = x + res_leaves[i].astype(jnp.float32).reshape(-1)
            parts.append(x)
            filled += size
        if b.padded > filled:
            parts.append(jnp.zeros((b.padded - filled,), jnp.float32))
        buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        if ok is not None:
            # sender-side quarantine: a poisoned/non-finite contribution
            # enters the collectives as exact zeros (where, not a
            # multiply — NaN payloads must not leak through 0 * NaN);
            # the worker's EF residual resets with it (err below is then
            # exactly zero, a fresh EF start after the bad round)
            buf = jnp.where(ok, buf, jnp.zeros_like(buf))
        wdt = jnp.dtype(wire_dtype) if wire_dtype is not None else b.dtype
        quantized, encode = _wire_codec(wdt)

        def gather_decoded(payload, scale):
            """all_gather the wire payload (+ its per-worker scale for
            int8) and decode each worker's segment with ITS scale."""
            full = lax.all_gather(payload, axis_name, tiled=True).astype(
                jnp.float32)
            if not quantized:
                return full
            scales = lax.all_gather(scale, axis_name)           # [n]
            return (full.reshape(n, -1) * scales[:, None]).reshape(-1)

        sent, sent32, sent_scale = encode(buf)
        if new_res is not None:
            # error feedback: what wire rounding dropped from THIS
            # worker's contribution rides into next round's
            # pre-compression sum
            err = buf - sent32
        compressed = wdt != jnp.dtype(jnp.float32)
        if compressed:
            # compressed reduce-scatter as all-to-all of wire-dtype shard
            # slices + LOCAL fp32 accumulation.  psum_scatter on bf16
            # would accumulate IN bf16, where one worker's grid-crossing
            # update can vanish into the sum's coarser grid (at sum ~ n|p|
            # the quantum is ~n x larger) — an error no residual can see,
            # because the fp32 truth never exists anywhere.  (int8 cannot
            # ride psum_scatter at all: integer accumulation would wrap
            # and each worker has its own scale.)  Wire traffic is
            # identical to reduce-scatter: each worker sends (n-1)/n of
            # the bucket.
            pieces = lax.all_to_all(sent.reshape(n, b.padded // n),
                                    axis_name, 0, 0)
            if quantized:
                scales = lax.all_gather(sent_scale, axis_name)   # [n]
                shard32 = jnp.sum(pieces.astype(jnp.float32)
                                  * scales[:, None], axis=0)
            else:
                shard32 = jnp.sum(pieces.astype(jnp.float32), axis=0)
        else:
            shard32 = lax.psum_scatter(sent, axis_name, scatter_dimension=0,
                                   tiled=True).astype(jnp.float32)
        track32 = None   # fp32 mean the round-optimizer tracker consumes
        if how == "equal":
            if opt_placement == "replicated" and not compressed:
                # replicated apply (the ZeRO-1 paper's baseline, kept as
                # the A/B twin): gather the RAW shard sums and scale the
                # full buffer on EVERY worker — N copies of the same
                # arithmetic.  Elementwise scaling commutes with the
                # gather bit-for-bit, so the result is bitwise-identical
                # to the shard-resident apply below.
                gathered = lax.all_gather(shard32, axis_name,
                                          tiled=True).astype(jnp.float32)
                full = gathered / n
                if ok is not None:
                    # quarantine renormalization: the screened sum holds
                    # only the valid contributions, so the mean divides
                    # by their count; the full-count predicate keeps
                    # clean rounds on the literal-n division (bitwise)
                    full = jnp.where(all_ok, full, gathered / valid)
                track32 = full
            else:
                # shard-resident apply: the scale (and, compressed, the
                # mean's wire encode + stage-2 EF) runs on the 1/N shard;
                # only the post-update values ride the all_gather home
                mean32 = shard32 / n
                if ok is not None:
                    mean32 = jnp.where(all_ok, mean32, shard32 / valid)
                mean, mean32_dec, mean_scale = encode(mean32)
                if new_res is not None and compressed:
                    # second-stage error feedback: the gathered mean is
                    # ALSO wire-quantized, and that rounding recurs every
                    # round on the same grid (sub-quantum drift of the
                    # mean would stall without it).  The shard's owner
                    # folds n x the rounding error into its own residual
                    # at the shard's positions — next round's mean
                    # divides the n back out, delivering the correction
                    # one round delayed.
                    e2 = mean32 - mean32_dec
                    err = err + lax.dynamic_update_slice(
                        jnp.zeros((b.padded,), jnp.float32), n * e2,
                        (lax.axis_index(axis_name) * (b.padded // n),))
                if resident:
                    # ISSUE 11: the program ENDS at the scatter — the
                    # decoded post-apply shard IS the between-round
                    # state, and next round's entry gather concatenates
                    # exactly these values (what gather_decoded would
                    # have produced), so the handoff is bit-exact even
                    # on a compressed wire
                    resident_out[_bucket_name(bi)] = mean32_dec
                    full = None
                    if buddy:
                        # ISSUE 12 buddy hop, fused at scatter exit: the
                        # WIRE-dtype payload (+ its scale) rides one
                        # ppermute to the ring successor and decodes
                        # there — the buddy copy is bitwise the owner's
                        # resident row (decode is a pure function of the
                        # permuted payload), at wire-dtype hop cost
                        nb = ring_neighbors(n, 1)
                        brow = lax.ppermute(mean, axis_name, nb)
                        if quantized:
                            bsc = lax.ppermute(mean_scale, axis_name, nb)
                            b32 = brow.astype(jnp.float32) * bsc
                        else:
                            b32 = brow.astype(jnp.float32)
                        bud = {"params": b32}
                        if new_res is not None:
                            # the owned span of the fp32 residual carries
                            # the stage-2 consensus correction (n x e2 at
                            # this worker's scatter positions) — state no
                            # other worker holds; back it up alongside
                            row = b.padded // n
                            span = lax.dynamic_slice_in_dim(
                                err, lax.axis_index(axis_name) * row, row)
                            bud["res"] = lax.ppermute(span, axis_name, nb)
                        buddy_out[_bucket_name(bi)] = bud
                else:
                    full = gather_decoded(mean, mean_scale)
                track32 = mean32
        else:
            # weighted needs the per-worker OWN value elementwise, so the
            # gather redistributes the raw sum and the blend runs locally;
            # own is the compressed own contribution — the value the peers
            # actually received.  The own-blend is irreducibly per-worker
            # (each worker's output is a different function of its own
            # value) and stays replicated under BOTH placements — the
            # shardable part of the weighted apply is the reduction and
            # the tracker's mean scale (docs/ARCHITECTURE.md).
            tq, _tq32, tq_scale = encode(shard32)
            total = gather_decoded(tq, tq_scale)
            own = sent32
            full = w * own + (1.0 - w) * (total - own) / (n - 1)
            track32 = (shard32 / n if opt_placement == "sharded"
                       else total / n)
            if ok is not None:
                # quarantine under the weighted blend: a valid worker's
                # peer mean renormalizes over the valid peer count (its
                # own screened term is already in total); a quarantined
                # worker adopts the valid consensus mean — its own value
                # is the garbage being quarantined
                peers = jnp.maximum(valid - 1.0, 1.0)
                screened = jnp.where(
                    ok, w * own + (1.0 - w) * (total - own) / peers,
                    total / valid)
                full = jnp.where(all_ok, full, screened)
                track32 = jnp.where(
                    all_ok, track32,
                    (shard32 if opt_placement == "sharded" else total)
                    / valid)
        if new_tracker is not None:
            # round-level Adam moments of the cross-worker mean — the
            # worker-invariant quantity whose state the sharded placement
            # stores at 1/N per worker (the replicated layout updates the
            # identical full vector N times over)
            name = _bucket_name(bi)
            if name not in tracker:
                raise ValueError(
                    f"round-optimizer tracker has no bucket {name} "
                    f"(bucket plan / tracker layout mismatch)")
            mu, nu = tracker[name]["mu"], tracker[name]["nu"]
            expect = b.padded // n if opt_placement == "sharded" \
                else b.padded
            if mu.shape[-1] != expect:
                raise ValueError(
                    f"round-optimizer bucket {name} row has "
                    f"{mu.shape[-1]} elements, expected {expect} for "
                    f"opt_placement={opt_placement!r} (sync_bucket_mb "
                    "or placement changed since the state was built?)")
            g = track32
            new_tracker[name] = {
                "mu": ROUND_ADAM_B1 * mu + (1.0 - ROUND_ADAM_B1) * g,
                "nu": ROUND_ADAM_B2 * nu + (1.0 - ROUND_ADAM_B2) * (g * g)}
            if buddy and opt_placement == "sharded":
                # ISSUE 12: the sharded tracker rows are 1/N state no
                # other worker holds — one fp32 ppermute each backs the
                # fresh moments up on the ring successor
                nb = ring_neighbors(n, 1)
                buddy_out.setdefault(name, {}).update(
                    mu=lax.ppermute(new_tracker[name]["mu"], axis_name,
                                    nb),
                    nu=lax.ppermute(new_tracker[name]["nu"], axis_name,
                                    nb))
        for (i, off, size) in b.items:
            leaf = leaves[i]
            if full is not None:
                out[i] = full[off:off + size].reshape(leaf.shape).astype(
                    leaf.dtype)
            if new_res is not None:
                new_res[i] = err[off:off + size].reshape(leaf.shape)
    res_out = (residual if new_res is None
               else jax.tree_util.tree_unflatten(treedef, new_res))
    first = (resident_out if resident
             else jax.tree_util.tree_unflatten(treedef, out))
    ret: list = [first, res_out, new_tracker]
    if buddy:
        ret.append(buddy_out)
    if poison is not None:
        ret.append(okf)
    return tuple(ret)


# --------------------------------------------------------------------------
# Bucketed gossip round sync: flatten-and-bucket -> per-bucket ppermute
# shifts -> local fp32 blend (ISSUE 4 tentpole)
# --------------------------------------------------------------------------
# The legacy ``aggregate`` path runs ring/double-ring gossip leaf by leaf:
# every parameter tensor is its own ppermute (dozens of sub-MB collectives
# per round, each paying launch latency), always dense, always fp32.  The
# gossip engine reuses the sharded-sync bucketer: the pytree flattens into
# ~bucket_bytes fp32 segments, each HOP moves one contiguous buffer per
# bucket (collective count ~ buckets x hops, not leaves x hops), and the
# blend arithmetic runs once on the packed buffer.  Unlike the
# reduce-scatter engine the buckets need NO padding — ppermute moves the
# buffer wholesale, nothing tiles by worker count.
#
# In fp32 the bucketed round is BIT-IDENTICAL to the dense path: the blend
# evaluates the exact dense expressions ((x + r) / 2, (x + r1 + r2) / 3,
# and their local_weight forms) elementwise on the same values — packing
# and slicing move bytes, never round them.
#
# Compressed wire (bf16 / int8) casts only the PERMUTED payload: the own
# term of the blend stays full-precision fp32, so per-round error is one
# wire rounding of the neighbor term.  Error feedback carries the fp32
# rounding error of the worker's OWN transmission in its residual and
# re-injects it into the next round's payload (send = x + e), so repeated
# gossip rounds still contract to the dense consensus fixed point: what
# this round's quantization dropped, the neighbors receive next round.
# (Gossip needs only this single EF stage — there is no shared quantized
# mean whose rounding recurs on a fixed grid, unlike the sharded engine's
# second stage.)


def gossip_sync(tree: PyTree, *, topology: str, how: str = "equal",
                local_weight: float = 0.5, axis_name: str = DATA_AXIS,
                wire_dtype=None, residual: PyTree | None = None,
                bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                poison=None) -> tuple:
    """One bucketed ring/double-ring gossip round over the data axis.

    Must be called inside ``shard_map`` (``axis_name`` bound), like
    ``aggregate``.  Semantics match ``aggregate(topology=...)`` per
    element: ``ring`` blends with the shift-1 predecessor, ``double_ring``
    with the shift-1 and shift-2 predecessors; ``equal`` is the uniform
    blend, ``weighted`` the ``local_weight`` own/peer blend (the
    Disbalanced variants' straggler weighting).  In fp32 the result is
    bit-identical to the dense per-leaf path.

    ``wire_dtype`` compresses the permuted payload only (bf16 downcast or
    per-bucket-scale int8, the scale ppermuted alongside); the local term
    and the blend accumulate in fp32.  ``residual`` enables error
    feedback: each worker transmits ``encode(x + residual)`` and carries
    the fp32 rounding error of that transmission forward, so repeated
    rounds converge to the dense fixed point within EF tolerance instead
    of plateauing at the wire quantum.  Returns
    ``(blended_tree, new_residual)``; ``new_residual`` is ``residual``
    unchanged (possibly None) when no error feedback is active.

    ``poison`` (ISSUE 12 integrity screen): when not None, each
    worker's TRANSMISSION is screened sender-side (poisoned/non-finite
    payloads travel as exact zeros, the validity flag ppermutes
    alongside) and the blend renormalizes over the valid terms — a
    worker whose predecessor is quarantined keeps its own value, a
    quarantined worker adopts its valid neighbor terms.  Clean rounds
    select the unscreened arithmetic (bitwise-identical).  The return
    gains this worker's fp32 0/1 validity flag:
    ``(blended, new_residual, ok)``.

    Double-ring issues the shift-1 and shift-2 exchanges back to back and
    fences them with ``optimization_barrier`` before either blend term is
    consumed, so the shift-2 hop rides the wire while the shift-1 blend
    computes (the PR 2 two-rounds-in-flight trick, inside one program).
    """
    if topology not in GOSSIP_HOPS:
        raise ValueError(
            f"topology must be one of {tuple(GOSSIP_HOPS)}, got "
            f"{topology!r} (allreduce rides sharded_sync)")
    if how not in HOWS:
        raise ValueError(f"how must be one of {HOWS}, got {how!r}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    n = lax.axis_size(axis_name)
    if not leaves or n == 1:
        if poison is not None:
            ok1 = _contribution_ok(poison, leaves, None)
            return tree, residual, ok1.astype(jnp.float32)
        return tree, residual
    res_leaves = None
    if residual is not None:
        res_leaves = jax.tree_util.tree_leaves(residual)
        if len(res_leaves) != len(leaves):
            raise ValueError(
                "residual must mirror the synced tree: "
                f"{len(res_leaves)} leaves vs {len(leaves)}")
    ok = okf = None
    if poison is not None:
        ok = _contribution_ok(poison, leaves, res_leaves)
        okf = ok.astype(jnp.float32)
    out: list = [None] * len(leaves)
    new_res: list | None = [None] * len(leaves) if res_leaves is not None \
        else None
    w = local_weight
    for b in bucket_plan(leaves, n, bucket_bytes):
        # pack the bucket; no zero padding — ppermute has no tiling
        # constraint, so the wire carries exactly the filled elements
        own_parts = [leaves[i].astype(jnp.float32).reshape(-1)
                     for (i, _off, _size) in b.items]
        buf = jnp.concatenate(own_parts) if len(own_parts) > 1 \
            else own_parts[0]
        send = buf
        if res_leaves is not None:
            res_parts = [res_leaves[i].astype(jnp.float32).reshape(-1)
                         for (i, _off, _size) in b.items]
            send = buf + (jnp.concatenate(res_parts) if len(res_parts) > 1
                          else res_parts[0])
        if ok is not None:
            # sender-side quarantine: a poisoned/non-finite transmission
            # travels as exact zeros, and the validity flag ppermutes
            # alongside so receivers renormalize their blend terms
            send = jnp.where(ok, send, jnp.zeros_like(send))
        wdt = jnp.dtype(wire_dtype) if wire_dtype is not None else b.dtype
        quantized, encode = _wire_codec(wdt)
        sent, sent32, sent_scale = encode(send)
        if new_res is not None:
            # error feedback: what wire rounding dropped from THIS
            # worker's transmission rides into next round's payload —
            # the neighbors receive the correction one round delayed
            err = send - sent32

        def hop(shift):
            """Permuted (payload, scale, validity) from the shift-th
            predecessor; int8 payloads travel with their sender's fp32
            scale."""
            r = _shift(sent, n, shift, axis_name)
            s = _shift(sent_scale, n, shift, axis_name) if quantized \
                else None
            o = _shift(okf, n, shift, axis_name) if okf is not None \
                else None
            return r, s, o

        def dec(trip):
            r, s, _o = trip
            r32 = r.astype(jnp.float32)
            return r32 * s if s is not None else r32

        if topology == "ring":
            h1 = hop(1)
            r1 = dec(h1)
            blended = (buf + r1) / 2.0 if how == "equal" \
                else w * buf + (1.0 - w) * r1
            if ok is not None:
                r1ok = h1[2] > 0
                safe_buf = jnp.where(ok, buf, jnp.zeros_like(buf))
                if how == "equal":
                    num = safe_buf + jnp.where(r1ok, r1,
                                               jnp.zeros_like(r1))
                    cnt = okf + h1[2]
                    screened = jnp.where(cnt > 0,
                                         num / jnp.maximum(cnt, 1.0), buf)
                else:
                    screened = jnp.where(
                        jnp.logical_and(ok, r1ok),
                        w * buf + (1.0 - w) * r1,
                        jnp.where(r1ok, r1, buf))
                blended = jnp.where(jnp.logical_and(ok, r1ok), blended,
                                    screened)
        else:
            # both shifts issued before either blend term is consumed:
            # the barrier keeps XLA from serializing the shift-2
            # collective behind the shift-1 blend, so the second hop's
            # wire time overlaps the first hop's arithmetic
            h1, h2 = lax.optimization_barrier((hop(1), hop(2)))
            r1, r2 = dec(h1), dec(h2)
            # exact dense expressions (comms.aggregate per_leaf) for the
            # fp32 bit-identity guarantee
            blended = (buf + r1 + r2) / 3.0 if how == "equal" \
                else w * buf + ((1.0 - w) / 2.0) * (r1 + r2)
            if ok is not None:
                r1ok, r2ok = h1[2] > 0, h2[2] > 0
                every = jnp.logical_and(ok, jnp.logical_and(r1ok, r2ok))
                safe_buf = jnp.where(ok, buf, jnp.zeros_like(buf))
                num = (safe_buf
                       + jnp.where(r1ok, r1, jnp.zeros_like(r1))
                       + jnp.where(r2ok, r2, jnp.zeros_like(r2)))
                cnt = okf + h1[2] + h2[2]
                if how == "equal":
                    screened = jnp.where(cnt > 0,
                                         num / jnp.maximum(cnt, 1.0), buf)
                else:
                    pn = (jnp.where(r1ok, r1, jnp.zeros_like(r1))
                          + jnp.where(r2ok, r2, jnp.zeros_like(r2)))
                    pc = h1[2] + h2[2]
                    pmean = pn / jnp.maximum(pc, 1.0)
                    screened = jnp.where(
                        ok,
                        jnp.where(pc > 0, w * buf + (1.0 - w) * pmean,
                                  buf),
                        jnp.where(pc > 0, pmean, buf))
                blended = jnp.where(every, blended, screened)
        for (i, off, size) in b.items:
            leaf = leaves[i]
            out[i] = blended[off:off + size].reshape(leaf.shape).astype(
                leaf.dtype)
            if new_res is not None:
                new_res[i] = err[off:off + size].reshape(leaf.shape)
    synced = jax.tree_util.tree_unflatten(treedef, out)
    res_out = (residual if new_res is None
               else jax.tree_util.tree_unflatten(treedef, new_res))
    if poison is not None:
        return synced, res_out, okf
    return synced, res_out


# --------------------------------------------------------------------------
# Hierarchical two-level round sync: inner sharded allreduce over ICI x
# outer compressed gossip over DCN (ISSUE 13 tentpole)
# --------------------------------------------------------------------------
# The paper's topology matrix keeps its engines flat: ONE worker axis,
# either all-reduced (PR 2's psum_scatter/all_gather program) or gossiped
# (PR 4's per-bucket ppermute program).  A multi-pod deployment has two
# very different wires at once — ICI within a slice (fast, low-latency)
# and DCN between slices (slow, high-latency) — and the production shape
# (arXiv 2204.06514's multi-pod pjit recipe; arXiv 2412.14374's
# DCN-traffic hiding) is the COMPOSITION: every slice's W workers
# all-reduce over ICI, and only the S slice consensuses cross DCN, via
# gossip hops that can take the compressed int8+EF wire.
#
# The decisive layout property: the outer hop rides the 1/W SCATTER
# SHARD, never the full tree.  The inner psum_scatter already leaves each
# worker holding its span of the slice SUM; dividing by W makes it the
# slice mean — worker-invariant within the slice, so worker (s, i) and
# its counterpart (s', i) in every other slice hold the SAME span of
# their slices' means.  One ppermute over the ``slice`` axis per bucket
# therefore gossips the whole slice-mean tree at bucket_bytes / W wire
# cost per hop, and the trailing inner all_gather distributes the
# gossip-blended consensus back to every worker of the slice.  DCN bytes
# per round per worker: hops x padded/W x outer_wire_itemsize per bucket
# — exactly 1/N_inner of what a flat gossip over the full tree would
# move (asserted in tests/test_hier_sync.py::TestHierWireBytes).
#
# Semantics ("gossip of means"): g_s = gossip_blend(m_s, m_{s-1}[, m_{s-2}])
# where m_s is slice s's equal mean.  ``equal`` output is g_s for every
# worker of slice s; ``weighted`` (the straggler blend, flowing through
# both levels) keeps the flat form with the gossiped mean standing in
# for the local one: out_i = w*own_i + (1-w)*(W*g_s - own_i)/(W-1) — the
# self-exclusive peer mean whose peer pool has been gossip-blended
# across slices (at S=1 this IS the flat weighted allreduce, the
# 1-slice-limit contract).  In fp32 the bucketed program is BIT-IDENTICAL
# to ``aggregate_hier`` below — the same expressions evaluated per leaf
# from the flat primitives (lax.pmean + the dense gossip blends), i.e.
# the flat S*W-worker gossip-of-means reference.
#
# EF is PER LEVEL: the inner residual keeps its two flat stages (own
# contribution rounding + W x the gather-payload rounding at the owner's
# span); a NEW outer residual carries the fp32 rounding of each worker's
# own outer-hop transmission — the single-stage gossip EF, per slice,
# on the shard span.  Stage-2 corrections now deliver THROUGH the gossip
# mixing (next round's mean carries them into the blend), gossip-weighted
# rather than exact — the usual EF contraction argument still holds, and
# the fp32 fast path stays bitwise (no EF active).


def aggregate_hier(tree: PyTree, *, topology: str, how: str = "equal",
                   local_weight: float = 0.5,
                   inner_axis: str = DATA_AXIS,
                   outer_axis: str = SLICE_AXIS) -> PyTree:
    """Dense per-leaf hierarchical twin — THE flat gossip-of-means
    reference the bucketed program is bitwise-gated against.

    Built from the flat engines' own primitives, per leaf, no bucketing
    or compression: ``lax.pmean`` over the inner (worker) axis is the
    flat dense slice mean, the ring/double-ring blend expressions over
    the outer (slice) axis are ``comms.aggregate``'s gossip forms, and
    the weighted own-term blend is the flat allreduce's.  Must be called
    inside ``shard_map`` with both axes bound."""
    if topology not in GOSSIP_HOPS:
        raise ValueError(
            f"hierarchical outer topology must be one of "
            f"{tuple(GOSSIP_HOPS)}, got {topology!r} (an allreduce outer "
            "level is the flat S*W engine)")
    if how not in HOWS:
        raise ValueError(f"how must be one of {HOWS}, got {how!r}")
    nw = lax.axis_size(inner_axis)
    ns = lax.axis_size(outer_axis)
    w = local_weight

    def per_leaf(x: jnp.ndarray) -> jnp.ndarray:
        m = lax.pmean(x, inner_axis)
        r1 = _shift(m, ns, 1, outer_axis)
        if topology == "ring":
            g = (m + r1) / 2.0 if how == "equal" \
                else w * m + (1.0 - w) * r1
        else:
            r2 = _shift(m, ns, 2, outer_axis)
            g = (m + r1 + r2) / 3.0 if how == "equal" \
                else w * m + ((1.0 - w) / 2.0) * (r1 + r2)
        if how == "equal":
            return g
        # the straggler-weighted blend through both levels: the flat
        # self-exclusive peer-mean form, with the peer pool's mean
        # gossip-blended across slices (W*g is the blended slice total)
        return w * x + (1.0 - w) * (nw * g - x) / (nw - 1)

    return jax.tree_util.tree_map(per_leaf, tree)


def hier_wire_bytes(tree: PyTree, n_inner: int, *, topology: str,
                    wire_dtype=None, outer_wire_dtype=None,
                    bucket_bytes: int = DEFAULT_BUCKET_BYTES) -> dict:
    """Per-worker bytes SENT by one hierarchical round sync, split by
    level: ``{"ici": inner_bytes, "dcn": outer_bytes}`` (shapes only —
    leaves may be arrays or ShapeDtypeStructs).

    - ``ici``: the inner sharded engine, unchanged from the flat
      accounting — 2(W-1)/W x padded x inner_wire_itemsize per bucket
      (reduce-scatter + all-gather each move (W-1)/W);
    - ``dcn``: hops x (padded // W) x outer_wire_itemsize per bucket —
      the gossip hop rides the 1/W scatter shard, so the outer payload
      is exactly 1/N_inner of what a flat gossip over the same tree
      would permute per hop (when the bucket needs no padding; padding
      rides the wire like everywhere else in the engine).  The int8
      per-bucket scale scalar is excluded, as in ``sync_wire_bytes``.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    hops = GOSSIP_HOPS.get(topology, 1)
    if not leaves or n_inner < 1:
        return {"ici": 0, "dcn": 0}
    ici = dcn = 0
    for b in bucket_plan(leaves, n_inner, bucket_bytes):
        inner_item = (jnp.dtype(wire_dtype).itemsize
                      if wire_dtype is not None else b.dtype.itemsize)
        outer_item = (jnp.dtype(outer_wire_dtype).itemsize
                      if outer_wire_dtype is not None else b.dtype.itemsize)
        row = b.padded // n_inner
        ici += 2 * (n_inner - 1) * row * inner_item
        dcn += hops * row * outer_item
    return {"ici": ici, "dcn": dcn}


def hier_outer_residual_init(per_worker_tree: PyTree, n_inner: int,
                             n_rows: int, *,
                             bucket_bytes: int = DEFAULT_BUCKET_BYTES
                             ) -> dict:
    """Zero-initialized OUTER-level EF residual, worker-stacked: one
    ``[n_rows, padded // n_inner]`` fp32 array per sync bucket — row
    (s*W + i) carries worker (s, i)'s fp32 rounding error of its own
    outer-hop transmission (its span of slice s's mean), re-injected
    into the next round's payload exactly like the flat gossip EF."""
    leaves = jax.tree_util.tree_leaves(per_worker_tree)
    return {_bucket_name(i): jnp.zeros((n_rows, b.padded // n_inner),
                                       jnp.float32)
            for i, b in enumerate(bucket_plan(leaves, n_inner,
                                              bucket_bytes))}


def hierarchical_sync(tree: PyTree, *, topology: str, how: str = "equal",
                      local_weight: float = 0.5,
                      inner_axis: str = DATA_AXIS,
                      outer_axis: str = SLICE_AXIS,
                      wire_dtype=None, outer_wire_dtype=None,
                      residual: PyTree | None = None,
                      outer_residual: dict | None = None,
                      bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                      residency: str = "replicated") -> tuple:
    """One hierarchical round sync (ISSUE 13): bucketed inner
    reduce-scatter over ``inner_axis`` -> per-bucket outer gossip hop(s)
    on the 1/W shard over ``outer_axis`` -> apply -> inner all_gather,
    as one program.  Must be called inside ``shard_map`` with BOTH axes
    bound.

    ``wire_dtype`` compresses the inner (ICI) collectives exactly like
    ``sharded_opt_sync``; ``outer_wire_dtype`` independently compresses
    the outer (DCN) gossip payload (the per-bucket int8 scale ppermutes
    alongside, decoded with the sender's scale).  ``residual`` is the
    flat inner EF state (params-shaped, stage 1 + stage 2);
    ``outer_residual`` the per-level outer EF state ({bucket:
    [padded // W]} rows, already squeezed inside shard_map) — each
    enables its level's error feedback independently.

    ``residency="resident"`` (ISSUE 11 composed): the program ENDS at
    the inner scatter — the first return value is the ``{bucket:
    [padded // W]}`` decoded post-apply shard of THIS SLICE's consensus
    (worker-invariant within the slice under the equal blend), and the
    next round's ``resident_gather`` over the inner axis reconstructs
    it bit-for-bit.  Scatter-resident state is exactly 1/N_inner per
    worker between rounds.

    Returns ``(out_or_resident, new_residual, new_outer_residual)``.
    """
    if topology not in GOSSIP_HOPS:
        raise ValueError(
            f"hierarchical outer topology must be one of "
            f"{tuple(GOSSIP_HOPS)}, got {topology!r} (an allreduce outer "
            "level is the flat S*W engine)")
    if how not in HOWS:
        raise ValueError(f"how must be one of {HOWS}, got {how!r}")
    if residency not in PARAM_RESIDENCIES:
        raise ValueError(
            f"residency must be one of {PARAM_RESIDENCIES}, got "
            f"{residency!r}")
    resident = residency == "resident"
    if resident and how != "equal":
        raise ValueError(
            "a scatter-resident hierarchical output requires the equal "
            "blend: the weighted own-term makes every worker's output "
            "per-worker state (config.py resolves weighted to the "
            "replicated residency)")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    nw = lax.axis_size(inner_axis)
    ns = lax.axis_size(outer_axis)
    if nw < 2:
        raise ValueError(
            "the hierarchical sync needs an inner worker axis of size "
            ">= 2 (the outer gossip rides the 1/W scatter shard; with "
            "W = 1 there is no inner level — run the flat gossip engine)")
    if not leaves:
        return tree, residual, outer_residual
    res_leaves = None
    if residual is not None:
        res_leaves = jax.tree_util.tree_leaves(residual)
        if len(res_leaves) != len(leaves):
            raise ValueError(
                "residual must mirror the synced tree: "
                f"{len(res_leaves)} leaves vs {len(leaves)}")
    out: list = [None] * len(leaves)
    new_res: list | None = [None] * len(leaves) if res_leaves is not None \
        else None
    new_outer: dict | None = {} if outer_residual is not None else None
    resident_out: dict = {}
    w = local_weight
    for bi, b in enumerate(bucket_plan(leaves, nw, bucket_bytes)):
        name = _bucket_name(bi)
        row = b.padded // nw
        # ---- pack + inner encode (the flat sharded engine's stage) ----
        parts, filled = [], 0
        for (i, _off, size) in b.items:
            x = leaves[i].astype(jnp.float32).reshape(-1)
            if res_leaves is not None:
                x = x + res_leaves[i].astype(jnp.float32).reshape(-1)
            parts.append(x)
            filled += size
        if b.padded > filled:
            parts.append(jnp.zeros((b.padded - filled,), jnp.float32))
        buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        wdt_in = jnp.dtype(wire_dtype) if wire_dtype is not None \
            else b.dtype
        quantized_in, encode_in = _wire_codec(wdt_in)
        sent, sent32, sent_scale = encode_in(buf)
        if new_res is not None:
            err = buf - sent32
        compressed_in = wdt_in != jnp.dtype(jnp.float32)
        if compressed_in:
            # compressed reduce-scatter as all-to-all + LOCAL fp32
            # accumulation (the sharded_opt_sync recipe and rationale)
            pieces = lax.all_to_all(sent.reshape(nw, row),
                                    inner_axis, 0, 0)
            if quantized_in:
                scales = lax.all_gather(sent_scale, inner_axis)   # [W]
                shard32 = jnp.sum(pieces.astype(jnp.float32)
                                  * scales[:, None], axis=0)
            else:
                shard32 = jnp.sum(pieces.astype(jnp.float32), axis=0)
        else:
            shard32 = lax.psum_scatter(sent, inner_axis, scatter_dimension=0,
                                   tiled=True).astype(jnp.float32)
        # ---- the slice mean on the shard: worker-invariant WITHIN the
        # slice, which is what lets the outer hop ride the shard ----
        m32 = shard32 / nw
        # ---- outer gossip hop(s) over the slice axis ----
        o_send = m32
        if new_outer is not None:
            if name not in outer_residual:
                raise ValueError(
                    f"outer residual has no bucket {name} (bucket plan "
                    "/ outer-residual layout mismatch)")
            o_res = outer_residual[name]
            if tuple(o_res.shape) != (row,):
                raise ValueError(
                    f"outer residual bucket {name} row has shape "
                    f"{tuple(o_res.shape)}, expected {(row,)} "
                    "(sync_bucket_mb or worker count changed?)")
            o_send = m32 + o_res.astype(jnp.float32)
        wdt_out = jnp.dtype(outer_wire_dtype) \
            if outer_wire_dtype is not None else b.dtype
        quantized_out, encode_out = _wire_codec(wdt_out)
        osent, osent32, osent_scale = encode_out(o_send)
        if new_outer is not None:
            # outer-level EF: the fp32 rounding this hop's wire dropped
            # from THIS worker's transmission rides into next round's
            # payload (the flat gossip engine's single stage, per level)
            new_outer[name] = o_send - osent32

        def hop(shift):
            r = _shift(osent, ns, shift, outer_axis)
            s = (_shift(osent_scale, ns, shift, outer_axis)
                 if quantized_out else None)
            return r, s

        def dec(trip):
            r, s = trip
            r32 = r.astype(jnp.float32)
            return r32 * s if s is not None else r32

        if topology == "ring":
            r1 = dec(hop(1))
            g32 = (m32 + r1) / 2.0 if how == "equal" \
                else w * m32 + (1.0 - w) * r1
        else:
            # both shifts issued before either blend term is consumed
            # (the PR 4 double-ring overlap fence): the shift-2 hop's
            # DCN time rides under the shift-1 blend
            h1, h2 = lax.optimization_barrier((hop(1), hop(2)))
            r1, r2 = dec(h1), dec(h2)
            g32 = (m32 + r1 + r2) / 3.0 if how == "equal" \
                else w * m32 + ((1.0 - w) / 2.0) * (r1 + r2)

        # ---- apply on the shard + home gather (inner wire) ----
        gq, gq_dec, gq_scale = encode_in(g32)
        if new_res is not None and compressed_in and how == "equal":
            # stage-2 inner EF (the flat engine's): the gather payload
            # is wire-quantized every round on the same grid; the span
            # owner folds W x the rounding error into its residual —
            # delivery now flows THROUGH next round's mean + gossip
            # blend (gossip-weighted, one round delayed)
            e2 = g32 - gq_dec
            err = err + lax.dynamic_update_slice(
                jnp.zeros((b.padded,), jnp.float32), nw * e2,
                (lax.axis_index(inner_axis) * row,))

        def gather_decoded(payload, scale):
            full = lax.all_gather(payload, inner_axis,
                                  tiled=True).astype(jnp.float32)
            if not quantized_in:
                return full
            scales = lax.all_gather(scale, inner_axis)           # [W]
            return (full.reshape(nw, -1) * scales[:, None]).reshape(-1)

        if how == "equal":
            if resident:
                # ISSUE 11 composed: the program ends at the scatter —
                # the decoded shard IS the between-round state, and the
                # next round's entry gather (over the inner axis)
                # concatenates exactly these values
                resident_out[name] = gq_dec
                full = None
            else:
                full = gather_decoded(gq, gq_scale)
        else:
            gfull = gather_decoded(gq, gq_scale)
            own = sent32
            # the flat weighted form with the gossip-blended peer pool:
            # W*g is the blended slice total, own excluded as ever
            full = w * own + (1.0 - w) * (nw * gfull - own) / (nw - 1)
        for (i, off, size) in b.items:
            leaf = leaves[i]
            if full is not None:
                out[i] = full[off:off + size].reshape(leaf.shape).astype(
                    leaf.dtype)
            if new_res is not None:
                new_res[i] = err[off:off + size].reshape(leaf.shape)
    res_out = (residual if new_res is None
               else jax.tree_util.tree_unflatten(treedef, new_res))
    outer_out = outer_residual if new_outer is None else new_outer
    first = (resident_out if resident
             else jax.tree_util.tree_unflatten(treedef, out))
    return first, res_out, outer_out


def make_hier_host_sync(mesh, *, topology: str, how: str = "equal",
                        local_weight: float = 0.5, wire_dtype=None,
                        outer_wire_dtype=None,
                        bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                        residency: str = "replicated"):
    """Jitted stand-alone hierarchical round sync over worker-stacked
    pytrees (tests/test_hier_sync.py) — the two-level twin of
    ``make_host_sync``.  Leaves carry a leading worker axis of size
    S x W sharded over ``(slice, data)`` (slice-major rows).  Returns
    ``run(tree, residual=None, outer_residual=None)`` ->
    ``(out_or_resident, new_residual, new_outer_residual)``."""
    from jax.sharding import PartitionSpec as P

    spec = P((SLICE_AXIS, DATA_AXIS))

    def _sync(tree, residual, outer_res):
        def inner(shard, res, ores):
            sq = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
            ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
            outs = hierarchical_sync(
                sq(shard), topology=topology, how=how,
                local_weight=local_weight, wire_dtype=wire_dtype,
                outer_wire_dtype=outer_wire_dtype, residual=sq(res),
                outer_residual=sq(ores), bucket_bytes=bucket_bytes,
                residency=residency)
            return tuple(ex(o) for o in outs)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=(spec,) * 3,
            out_specs=(spec,) * 3)(tree, residual, outer_res)

    jitted = jax.jit(_sync)

    def run(tree, residual=None, outer_residual=None):
        return jitted(tree, residual, outer_residual)

    return run


def make_hier_host_aggregator(mesh, *, topology: str, how: str = "equal",
                              local_weight: float = 0.5):
    """Jitted stand-alone DENSE hierarchical aggregator — the flat
    gossip-of-means reference program (``aggregate_hier`` per leaf) the
    bucketed engine is bitwise-gated against in fp32."""
    from jax.sharding import PartitionSpec as P

    spec = P((SLICE_AXIS, DATA_AXIS))

    def _agg(tree):
        def inner(shard):
            squeezed = jax.tree_util.tree_map(lambda x: x[0], shard)
            out = aggregate_hier(squeezed, topology=topology, how=how,
                                 local_weight=local_weight)
            return jax.tree_util.tree_map(lambda x: x[None], out)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=(spec,), out_specs=spec)(tree)

    return jax.jit(_agg)


def make_host_sync(mesh, *, mode: str = "sharded", how: str = "equal",
                   local_weight: float = 0.5, wire_dtype=None,
                   bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                   topology: str = "allreduce",
                   opt_placement: str = "sharded",
                   track_opt: bool = False,
                   param_residency: str = "replicated",
                   redundancy: str = "off",
                   screen: bool = False):
    """Jitted stand-alone round sync over worker-stacked pytrees.

    The sync-engine twin of ``make_host_aggregator`` (tests, federated
    checkpoint averaging): takes worker-stacked pytrees
    ([N, ...] leaves over the mesh's data axis) plus an optional residual
    pytree of the same structure, and returns ``(synced, new_residual)``.
    ``mode="dense"`` routes through ``aggregate`` (per-leaf, any
    topology) so the engines can be compared under identical harnesses;
    ``mode="gossip"`` runs the bucketed gossip engine for ring /
    double_ring; ``mode="sharded"`` the reduce-scatter engine
    (allreduce).

    ``opt_placement`` places the sharded engine's apply stage (ISSUE 9,
    ``sharded_sync``); ``track_opt=True`` additionally threads a
    round-optimizer tracker (``round_opt_init`` layout, worker-stacked)
    through the program — the returned callable then takes
    ``(tree, residual, tracker)`` and returns
    ``(synced, new_residual, new_tracker)``.

    ``param_residency="resident"`` (ISSUE 11, sharded mode only) ends
    the program at the scatter: the first return value is the
    worker-stacked resident layout (``{bucket: [n, padded // n]}``)
    instead of the synced tree — feed it to ``make_resident_gather`` to
    reconstruct the full tree bit-for-bit.

    ``redundancy="buddy"`` / ``screen=True`` (ISSUE 12) arm the buddy
    hop and the NaN/Inf integrity screen; the returned callable then
    takes ``(tree, residual=None, tracker=None, poison=None)`` and
    returns a DICT ``{"out", "residual", "tracker", "buddy", "ok"}``
    (keys present per arming) — the unit-test surface for the
    failure-domain program shapes.
    """
    from jax.sharding import PartitionSpec as P

    if param_residency == "resident" and mode != "sharded":
        raise ValueError(
            "param_residency 'resident' is a sharded-engine output "
            f"layout; mode {mode!r} has no scatter to end at")
    buddy_on = redundancy == "buddy"
    if buddy_on and mode != "sharded":
        raise ValueError(
            "buddy redundancy backs up shard-resident rows, which only "
            f"the sharded engine produces; mode {mode!r} has none")
    if buddy_on and param_residency != "resident" and not (
            track_opt and opt_placement == "sharded"):
        raise ValueError(
            "buddy redundancy needs something shard-resident: "
            "param_residency 'resident' and/or a sharded-placement "
            "tracker (track_opt=True)")
    spec = P(DATA_AXIS)

    def _sync(tree, residual, tracker, poison):
        def inner(shard, res, trk, poi):
            sq = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
            ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
            # squeeze the tracker too: the dense/gossip branches pass it
            # through untouched, and ``ex`` below must restore exactly
            # the worker-stacked layout it arrived in
            t, r, new_t = sq(shard), sq(res), sq(trk)
            # when the screen is armed the wrapper below guarantees a
            # poison vector (all-clear default), so poi is never None
            p = sq(poi) if screen else None
            extra: dict = {}
            if mode == "dense":
                if screen:
                    out, okf = aggregate(
                        t, how=how, topology=topology,
                        local_weight=local_weight, poison=p)
                    extra["ok"] = okf
                else:
                    out = aggregate(t, how=how, topology=topology,
                                    local_weight=local_weight)
                new_r = r
            elif mode == "gossip":
                rets = gossip_sync(
                    t, topology=topology, how=how,
                    local_weight=local_weight, wire_dtype=wire_dtype,
                    residual=r, bucket_bytes=bucket_bytes,
                    poison=p if screen else None)
                out, new_r = rets[0], rets[1]
                if screen:
                    extra["ok"] = rets[2]
            else:
                rets = sharded_opt_sync(
                    t, how=how, local_weight=local_weight,
                    wire_dtype=wire_dtype, residual=r,
                    bucket_bytes=bucket_bytes,
                    opt_placement=opt_placement, tracker=new_t,
                    residency=param_residency, buddy=buddy_on,
                    poison=p if screen else None)
                out, new_r, new_t = rets[0], rets[1], rets[2]
                idx = 3
                if buddy_on:
                    extra["buddy"] = rets[idx]
                    idx += 1
                if screen:
                    extra["ok"] = rets[idx]
            if not buddy_on and not screen:
                return ex(out), ex(new_r), ex(new_t)
            return {"out": ex(out), "residual": ex(new_r),
                    "tracker": ex(new_t),
                    **{k: ex(v) for k, v in extra.items()}}
        n_in = 4 if (buddy_on or screen) else 3
        args = (tree, residual, tracker, poison)[:n_in]
        return jax.shard_map(inner if n_in == 4 else
                             (lambda a, b, c: inner(a, b, c, None)),
                             mesh=mesh, in_specs=(spec,) * n_in,
                             out_specs=spec if (buddy_on or screen)
                             else (spec, spec, spec))(*args)

    jitted = jax.jit(_sync)

    if buddy_on or screen:
        n_workers = int(mesh.shape[DATA_AXIS])

        def run_full(tree, residual=None, tracker=None, poison=None):
            import numpy as np
            if screen and poison is None:
                poison = np.zeros(n_workers, np.bool_)
            return jitted(tree, residual, tracker, poison)
        return run_full

    if track_opt:
        def run_tracked(tree, residual=None, tracker=None):
            return jitted(tree, residual, tracker, None)
        return run_tracked

    def run(tree, residual=None):
        out, new_r, _ = jitted(tree, residual, None, None)
        return out, new_r

    return run


def make_host_aggregator(mesh, *, how: str, topology: str,
                         local_weight: float = 0.5):
    """Jitted stand-alone aggregator over worker-stacked pytrees.

    Takes pytrees whose leaves carry a leading worker axis of size
    ``mesh.shape['data']`` (the framework's representation of N independent
    local-SGD replicas) and returns the synchronized pytree.  The train loop
    fuses aggregation into its round program; this wrapper exists for tests
    and for ad-hoc use (e.g. federated averaging of checkpoints).
    """
    from jax.sharding import PartitionSpec as P

    spec = P(DATA_AXIS)

    def _agg(tree):
        def inner(shard):
            squeezed = jax.tree_util.tree_map(lambda x: x[0], shard)
            out = aggregate(squeezed, how=how, topology=topology,
                            local_weight=local_weight)
            return jax.tree_util.tree_map(lambda x: x[None], out)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=(spec,), out_specs=spec)(tree)

    return jax.jit(_agg)
