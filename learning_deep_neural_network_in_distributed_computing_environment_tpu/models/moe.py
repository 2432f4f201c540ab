"""Mixture-of-Experts FFN with expert parallelism over the ``expert``
mesh axis.

Beyond-reference capability (the reference is data-parallel only,
SURVEY.md 2.3).  Two routed layers over ONE dispatch (``routed_apply``:
the (token, expert) pairs sorted by expert, grouped products over a row
buffer sized by the share of the experts held here, the buffer's rows
summed back into their tokens by one kernel, ``ops/moe_combine.py``; no
``[tokens, E, C]`` tensor):

- ``RoutedExperts``: sparse SwiGLU experts as deployed, top-k of E with
  renormalised weights, no capacity and no dropped token, told which
  experts it holds (one expert-parallel rank's share of a layer);
- ``MoEFFN``: Switch-Transformer-style top-1 routing with a capacity
  limit, the capacity being a weight of 0 on the tokens past it:

- the gate (replicated) scores every token against all ``num_experts``
  experts; each token goes to its top-1 expert, capped at
  ``capacity = ceil(capacity_factor * tokens / num_experts)`` tokens per
  expert (overflow tokens are dropped — the residual connection in the
  caller carries them through, standard Switch behavior);
- expert weights are STACKED with a leading [num_experts] axis; under
  ``shard_map`` that axis is sharded over ``expert`` and each device
  dispatches only to its local slice, contributing its experts' outputs
  to a cross-shard ``psum``;
- the load-balance auxiliary loss (Switch: E * sum(f_e * P_e)) is sown
  into the ``aux`` variable collection; the training engine adds it to
  the objective with ``moe_aux_weight``.

The dense twin (``expert_axis=None``, ``ep_size=1``) computes the exact
same function with the full expert stack — one parameter structure for
both worlds, as with tensor parallelism.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .arch import Router

_init = nn.initializers.normal(stddev=0.02)
# the ``counters`` key under which a routed layer whose router has a
# ``bias_step`` sows how many (token, choice) pairs chose each of ALL its
# experts: a vector a layer, which the training step takes out of the
# counters and moves the layer's ``select_bias`` by
CHOICE_COUNTS = "choice_counts"


class MoEFFN(nn.Module):
    num_experts: int               # GLOBAL expert count
    ffn_dim: int                   # GLOBAL per-expert FFN width
    capacity_factor: float = 1.25
    dtype: Any = jnp.float32
    expert_axis: Optional[str] = None  # mesh axis experts shard over
    ep_size: int = 1               # expert-axis size (local = E / ep_size)
    tp_size: int = 1               # tensor-parallel size (F local = F / tp)
    model_axis: Optional[str] = None   # mesh axis the F dim shards over

    @nn.compact
    def __call__(self, x, *, train: bool = False, aux_scale=1.0):
        """``aux_scale`` multiplies the sown load-balance loss: the GPipe
        schedule passes validity/(num_microbatches) so bubble steps sow
        exactly zero and valid microbatch contributions average to the
        full-batch scale (parallel/pp.py).

        Tensor parallelism (MoE x TP, VERDICT r3 'next' #4): each expert's
        FFN is Megatron-sharded over ``model_axis`` — w1/b1 column-parallel
        on the F dim, w2 row-parallel — while the gate and the routing stay
        replicated (every shard routes the identical full token set), so
        the capacity and aux-loss semantics are EXACTLY those of the
        unsharded MoE and the composition is golden-testable against it.
        The per-shard partial outputs and the expert shards reduce in one
        ``psum`` over both axes; b2 (post-reduction bias) is scaled by
        1/tp so the psum restores it exactly once."""
        b, t, h = x.shape
        e, ep = self.num_experts, self.ep_size
        if e % ep:
            raise ValueError(f"num_experts {e} not divisible by "
                             f"expert-parallel size {ep}")
        e_local = e // ep
        if self.ffn_dim % self.tp_size:
            raise ValueError(f"ffn_dim {self.ffn_dim} not divisible by "
                             f"tp_size {self.tp_size} (column-parallel "
                             "expert FFN)")
        f_local = self.ffn_dim // self.tp_size
        toks = x.reshape(b * t, h)
        n_tok = b * t
        cap = max(int(math.ceil(self.capacity_factor * n_tok / e)), 1)

        # --- top-1 routing (computed identically on every expert shard) --
        gate_logits = nn.Dense(e, use_bias=False, dtype=jnp.float32,
                               kernel_init=_init, name="gate")(
                                   toks.astype(jnp.float32))
        probs = jax.nn.softmax(gate_logits, axis=-1)         # [N, E]
        expert_idx = jnp.argmax(probs, axis=-1)              # [N]
        gate = jnp.max(probs, axis=-1)                       # [N]
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)
        # Switch load-balance loss: E * sum_e f_e * P_e
        self.sow("aux", "load_balance",
                 jnp.asarray(aux_scale, jnp.float32)
                 * e * jnp.sum(onehot.mean(0) * probs.mean(0)))
        # position of each token within its expert's queue; a token past
        # the capacity keeps its place in the dispatch and loses its weight:
        # Switch's drop, as a case of the routed layer below
        pos = jnp.einsum("ne,ne->n", jnp.cumsum(onehot, axis=0) - 1.0,
                         onehot).astype(jnp.int32)
        keep = (pos < cap).astype(jnp.float32)

        # --- local expert slice ------------------------------------------
        first = (lax.axis_index(self.expert_axis) * e_local
                 if self.expert_axis is not None else 0)
        w1 = self.param("w1", _init, (e_local, h, f_local))
        b1 = self.param("b1", nn.initializers.zeros, (e_local, f_local))
        w2 = self.param("w2", _init, (e_local, f_local, h))
        b2 = self.param("b2", nn.initializers.zeros, (e_local, h))
        # row-parallel w2: per-shard partial sums over the local F slice;
        # b2 is scaled so the cross-shard psum below adds it exactly once
        b2_scale = 1.0 / self.tp_size if self.model_axis is not None else 1.0

        def experts(params, rows, sizes, expert_of_row):
            from ..ops.grouped_matmul import grouped_matmul
            w1, b1, w2, b2 = params
            e_row = jnp.minimum(expert_of_row, e_local - 1)
            h1 = nn.gelu(grouped_matmul(rows, w1, sizes) + b1[e_row],
                         approximate=False)
            return grouped_matmul(h1, w2, sizes) + b2_scale * b2[e_row]

        out, *_ = routed_apply(
            toks.astype(self.dtype), expert_idx[:, None],
            (gate * keep)[:, None], first, e_local,
            Experts(experts, tuple(p.astype(self.dtype)
                                   for p in (w1, b1, w2, b2)), e))
        reduce_axes = tuple(a for a in (self.expert_axis, self.model_axis)
                            if a is not None)
        if reduce_axes:
            out = lax.psum(out, reduce_axes)
        return out.reshape(b, t, h)


# ----------------------------------------------------------------------
# Routed experts without a capacity: sort, grouped products over a row
# buffer sized by the held share, the rows summed back into the tokens
# ----------------------------------------------------------------------

# rows of buffer for each row the held share of the router expects: the
# cells' sparse layers read 0.94-1.12 of that share (PERF.md section 6,
# PR 32 and PR 33); what does not fit goes through the body again
HEADROOM = 1.25


class Experts(NamedTuple):
    """The experts ``routed_apply`` sends rows through.  ``apply(params,
    rows [C, H], sizes [held], expert_of_row [C]) -> [C, H]`` runs grouped
    products (``ops.grouped_matmul``) over rows sorted by expert; it reads
    its matrices from ``params`` and closes over no array, because the
    dispatch states its own derivative and hands ``params`` their
    gradient.  ``router_width`` is the number of experts the router chooses
    among, held here or not."""
    apply: Callable
    params: Any
    router_width: int


def _tile_up(rows: int) -> int:
    from ..ops.grouped_matmul import TM
    return -(-rows // TM) * TM if rows >= TM else -(-rows // 8) * 8


def row_buffer(pairs: int, held: int, router_width: int):
    """``(m_pad, c)`` for ``pairs`` (token, choice) pairs a step: the worst
    case, every pair on a held expert, and the rows of the buffer the
    layer computes on, ``HEADROOM`` times the held share of the pairs and
    never more than the worst case; both rounded up to the kernels' row
    tile.  ``c == m_pad`` where every expert is held."""
    m_pad = _tile_up(pairs)
    return m_pad, min(m_pad, _tile_up(
        math.ceil(HEADROOM * pairs * held / router_width)))


def overflow_chunks(sizes, c: int):
    """How many further passes over the ``c``-row buffer the counted rows
    need after the first: 0 where they fit."""
    return jnp.maximum(-(-sizes.sum() // c) - 1, 0)


def _zeros(shape, dtype, like):
    """Zeros that vary over the mesh axes ``like`` varies over (a loop's
    carry has to have its body's type inside ``shard_map``)."""
    from ..ops.grouped_matmul import vary_alike
    return vary_alike(jnp.zeros(shape, dtype), like)[0]


def _take(a, idx):
    return a.at[idx].get(mode="promise_in_bounds")


class _Chunk(NamedTuple):
    pair: Any      # [c] the flat (token, choice) pair of each row
    tok: Any       # [c] its token
    valid: Any     # [c] the row is a pair of a held expert
    sizes: Any     # [held] each group's rows inside this window
    expert: Any    # [c] each row's expert (``held`` past the last group)
    weight: Any    # [c] float32, the pair's weight
    edges: Any     # the (expert, token tile) runs' bounds inside the window


def _chunk(i, weights, order, sizes, expert_of_row, edges) -> _Chunk:
    """Chunk ``i`` of the sorted pairs: rows ``[i * c, (i + 1) * c)``, row
    ``i`` of ``order`` and ``expert_of_row`` [chunks, c].  The groups'
    overlaps with the window are its group sizes, so within it the rows
    past ``sum(sizes)`` again belong to none; so are the runs' (``edges``,
    ``ops.moe_combine.run_edges`` over the whole order)."""
    n, k = weights.shape
    c = order.shape[1]
    start = i * c
    pair = order[i]
    ends = jnp.cumsum(sizes)
    inside = lambda r: jnp.clip(r - start, 0, c)
    return _Chunk(
        pair, jnp.minimum(pair // k, n - 1),
        start + jnp.arange(c, dtype=jnp.int32) < ends[-1],
        inside(ends) - inside(ends - sizes), expert_of_row[i],
        _take(weights.reshape(-1), jnp.minimum(pair, n * k - 1)),
        inside(edges))


def _combine(ch: _Chunk, rows, n: int, weighted: bool = True):
    """The chunk's rows, each times its pair's weight (``weighted``),
    summed into their ``n`` tokens in float32 (``ops.moe_combine``).  The
    kernels never wrote the rows of no group: the sum selects them away."""
    from ..ops.moe_combine import moe_combine
    return moe_combine(rows, ch.tok, ch.edges,
                       ch.weight if weighted else None, n=n)


def _overflow_loop(order, sizes, body, carry):
    """``body(i, carry)`` over the chunks after the first that hold rows:
    a trip count from the counted sizes, and no loop at all where one
    chunk is the whole order."""
    if order.shape[0] == 1:
        return carry
    return lax.fori_loop(1, overflow_chunks(sizes, order.shape[1]) + 1, body,
                         carry)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(apply, toks, weights, params, order, sizes, expert_of_row,
              edges):
    """The routed layer on a buffer of ``c`` rows, ``order`` and
    ``expert_of_row`` being [chunks, c]: chunk 0 of the sorted pairs
    straight-line, the chunks after it in a loop that runs as often as
    ``sizes`` says, which is not at all where the rows fit.  A loop of a
    dynamic trip count has no reverse-mode derivative, so the layer states
    its own: the backward walks the same chunks, chunk 0 from the forward's
    residuals and the others recomputed."""
    return _dispatch_fwd(apply, toks, weights, params, order, sizes,
                         expert_of_row, edges)[0]


def _dispatch_fwd(apply, toks, weights, params, order, sizes, expert_of_row,
                  edges):
    ints = (order, sizes, expert_of_row, edges)
    ch = _chunk(0, weights, *ints)
    # named activation "moe_dispatch" (ISSUE 15): the tokens in expert
    # order, the residual a save_names: / offload_names: policy may pin
    rows = checkpoint_name(_take(toks, ch.tok), "moe_dispatch")
    y, pull = jax.vjp(lambda p, r: apply(p, r, ch.sizes, ch.expert),
                      params, rows)
    n = toks.shape[0]
    acc = _combine(ch, y, n)

    def more(i, acc):
        ch = _chunk(i, weights, *ints)
        return acc + _combine(ch, apply(params, _take(toks, ch.tok),
                                        ch.sizes, ch.expert), n)

    acc = _overflow_loop(order, sizes, more, acc)
    return acc.astype(toks.dtype), (pull, y, toks, weights, params, ints)


def _dispatch_bwd(apply, res, g):
    pull, y0, toks, weights, params, ints = res
    n, k = weights.shape

    def back(ch: _Chunk, y, pull, d_toks, d_pairs):
        """One chunk's gradient with respect to ``params``, and its part
        added into the tokens' [N, H] and the flat pairs' weights' [N * k],
        both float32."""
        g_rows = _take(g, ch.tok).astype(jnp.float32)
        d_y = jnp.where(ch.valid[:, None], g_rows * ch.weight[:, None], 0)
        d_params, d_rows = pull(d_y.astype(y.dtype))
        d_w = (g_rows * y.astype(jnp.float32)).sum(-1)
        return (d_params, d_toks + _combine(ch, d_rows, n, weighted=False),
                d_pairs.at[ch.pair].add(jnp.where(ch.valid, d_w, 0),
                                        mode="drop"))

    def more(i, grads):
        ch = _chunk(i, weights, *ints)
        y, pull = jax.vjp(lambda p, r: apply(p, r, ch.sizes, ch.expert),
                          params, _take(toks, ch.tok))
        d_params, *rest = back(ch, y, pull, *grads[1:])
        return (jax.tree_util.tree_map(jnp.add, grads[0], d_params), *rest)

    d_params, d_toks, d_pairs = _overflow_loop(*ints[:2], more, back(
        _chunk(0, weights, *ints), y0, pull,
        _zeros(toks.shape, jnp.float32, toks),
        _zeros((n * k,), jnp.float32, toks)))
    return (d_toks.astype(toks.dtype),
            d_pairs.reshape(n, k).astype(weights.dtype), d_params,
            None, None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def routed_apply(toks, expert_idx, weights, first, held: int,
                 experts: Experts):
    """Send every (token, chosen expert) pair whose expert is one of the
    ``held`` experts from ``first`` on through ``experts`` and add the
    results back into the tokens, weighted.  No capacity and no dropped
    pair, for any routing: the pairs are sorted by expert and the held
    ones, which sort first, go through ``experts.apply`` a buffer of ``C``
    rows at a time and come back into their tokens in float32 by one kernel
    (``ops/moe_combine.py``), which reads each held expert's run of rows
    for a tile of tokens: the runs' bounds are counted once, beside the
    sort, and shared by the forward and the backward pass.  ``C``
    (``row_buffer``) follows the share of the router's experts held here,
    with ``HEADROOM``: one pass where the router is near balance, and as
    many more as the counted rows need where it is not (``_dispatch``).
    Where every expert is held ``C`` is the worst case ``m_pad``, every
    pair, and no loop is built.  Only the sort's int32 vectors have
    ``m_pad`` entries.  Within a buffer the rows past the last group are
    never written by the kernels and are selected away before use.

    ``toks`` [N, H], ``expert_idx`` / ``weights`` [N, k].  Returns ``(out
    [N, H], sizes [held], walked)``: the held experts' part of the layer's
    sum, the rows each of them got, and the row slots the way back's
    products walk in one forward pass (``ops.moe_combine.row_slots`` over
    the buffers that run)."""
    from ..ops.grouped_matmul import vary_alike
    from ..ops.moe_combine import row_slots, run_edges
    n, k = expert_idx.shape
    m = n * k
    m_pad, c = row_buffer(m, held, experts.router_width)
    local = expert_idx.reshape(-1).astype(jnp.int32) - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    key = jnp.pad(local, (0, m_pad - m), constant_values=held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    edges = run_edges(local.reshape(n, k), n, held)
    sizes = jnp.diff(edges).reshape(held, -1).sum(1)
    # [chunks, c] windows of the sorted order; the last may reach past
    # m_pad, where no group has rows
    past = -m_pad % c
    expert_of_row = jnp.pad(key[order], (0, past),
                            constant_values=held).reshape(-1, c)
    order = jnp.pad(order, (0, past), constant_values=m_pad).reshape(-1, c)
    chunks = jnp.arange(order.shape[0])
    walked = jax.vmap(lambda e: row_slots(
        e, n, c, held, toks.dtype.itemsize))(
            jnp.clip(edges - c * chunks[:, None], 0, c))
    walked = jnp.where(chunks <= overflow_chunks(sizes, c), walked, 0).sum()
    leaves, tree = jax.tree_util.tree_flatten(experts.params)
    toks, weights, order, sizes, expert_of_row, edges, *leaves = vary_alike(
        toks, weights.astype(jnp.float32), order, sizes, expert_of_row, edges,
        *leaves)
    out = _dispatch(experts.apply, toks, weights,
                    jax.tree_util.tree_unflatten(tree, leaves), order, sizes,
                    expert_of_row, edges)
    return out, sizes, walked


# jitted so that every call site (chunk 0, the overflow loops, their
# derivatives, the layers of a scanned period) shares one trace and one
# lowered function: a Pallas call is traced anew wherever it is called,
# and that tracing is set-up time on the chip's host (PERF.md, PR 33)
@jax.jit
def _swiglu_experts(params, rows, sizes, _):
    from ..ops.grouped_matmul import grouped_matmul
    w1, w3, w2 = params
    gate = grouped_matmul(rows, w1, sizes)
    up = grouped_matmul(rows, w3, sizes)
    return grouped_matmul(nn.silu(gate) * up, w2, sizes)


class RoutedExperts(nn.Module):
    """Sparse SwiGLU experts as deployed: ``top_k`` of ``num_experts`` a
    token, weights renormalised over the chosen, no capacity, no dropped
    token, no auxiliary loss.  ``router`` (``arch.Router``) is the rule
    that scores and chooses; its selection bias, where it has one, is a
    parameter that enters the choice alone: it gets no gradient, so the
    optimizer leaves it to the bit.  Where the rule has a ``bias_step`` the
    layer also sows, under ``CHOICE_COUNTS``, the pairs that chose each of
    the ``num_experts`` experts, held here or not: what the step's rule
    moves the bias by (``train.move_select_bias``).

    ``experts_held = (first, count)`` makes this one expert-parallel
    rank's layer: the router scores all ``num_experts``, the top-k and the
    renormalisation are over all of them, and the layer holds, applies and
    returns the part of ``count`` experts from ``first`` on.  The ranks'
    outputs sum to the whole layer's; nothing stands in for the experts
    held elsewhere.  Sown into ``counters``: the rows that landed on held
    experts, the fullest held expert's rows over the mean, the passes the
    rows needed over the buffer after the first, and the share of the row
    slots that the way back into the tokens walks which carry a row
    (``combine_row_fill``, %)."""

    num_experts: int               # the router's width
    ffn_dim: int                   # per-expert SwiGLU width
    top_k: int
    experts_held: Optional[tuple] = None   # (first, count); None: all
    dtype: Any = jnp.float32
    router: Router = Router()

    @nn.compact
    def __call__(self, x):
        b, t, h = x.shape
        first, held = self.experts_held or (0, self.num_experts)
        toks = x.reshape(b * t, h)
        rule = self.router
        with jax.named_scope("moe_route"):
            logits = nn.Dense(self.num_experts, use_bias=False,
                              dtype=jnp.float32, kernel_init=_init,
                              name="gate")(toks.astype(jnp.float32))
            scores = {"softmax": lambda z: jax.nn.softmax(z, axis=-1),
                      "sigmoid": jax.nn.sigmoid}[rule.score](logits)
            if rule.select_bias:
                bias = self.param(
                    "select_bias", nn.initializers.normal(rule.bias_std),
                    (self.num_experts,), jnp.float32)
                _, idx = lax.top_k(scores + bias, self.top_k)
                weights = jnp.take_along_axis(scores, idx, axis=-1)
                if rule.bias_step:
                    self.sow("counters", CHOICE_COUNTS, (
                        idx[..., None] == jnp.arange(self.num_experts)
                    ).sum((0, 1), dtype=jnp.float32))
            else:
                weights, idx = lax.top_k(scores, self.top_k)
            # (a rule without eps or scale traces to the program it had)
            total = weights.sum(-1, keepdims=True)
            weights = weights / (total + rule.eps if rule.eps else total)
            if rule.scale != 1.0:
                weights = weights * rule.scale
        w1, w3 = (self.param(name, _init, (held, h, self.ffn_dim)).astype(
            self.dtype) for name in ("w1", "w3"))
        w2 = self.param("w2", _init, (held, self.ffn_dim, h)).astype(
            self.dtype)

        out, sizes, walked = routed_apply(
            toks.astype(self.dtype), idx, weights, first, held,
            Experts(_swiglu_experts, (w1, w3, w2), self.num_experts))
        rows = sizes.sum().astype(jnp.float32)
        self.sow("counters", "expert_rows", rows)
        self.sow("counters", "combine_row_fill",
                 100.0 * rows / jnp.maximum(walked, 1).astype(jnp.float32))
        self.sow("counters", "expert_load_max_over_mean",
                 sizes.max() * held / jnp.maximum(rows, 1.0))
        self.sow("counters", "expert_overflow_chunks", overflow_chunks(
            sizes, row_buffer(idx.size, held, self.num_experts)[1]).astype(
                jnp.float32))
        return out.reshape(b, t, h)


def _expert_stack(names: list) -> bool:
    """A leaf under a ``moe`` module that carries the experts on an axis:
    not the router's matrix, not its selection bias."""
    return ("moe" in names and "gate" not in names
            and names[-1] != "select_bias")


def with_expert_overlay(specs_fn, *, axis: str = "expert"):
    """Wrap a PartitionSpec-tree builder (e.g. ``bert.tp_param_specs`` /
    ``bert.pp_tp_param_specs``) so MoE expert-stack leaves additionally
    shard their EXPERT dim over ``axis`` — the EP x TP (and PP x EP x TP)
    composition: inner F dims come from the wrapped Megatron pattern, the
    expert dim (leading, or right behind the stacked-layer dim) from the
    overlay."""
    from jax.sharding import PartitionSpec as P

    def fn(params):
        specs = specs_fn(params)

        def fix(path, leaf_spec):
            names = [getattr(p_, "key", str(p_)) for p_ in path]
            if not _expert_stack(names):
                return leaf_spec
            i = 1 if "layers" in names else 0
            parts = list(leaf_spec)
            while len(parts) <= i:
                parts.append(None)
            if parts[i] is not None:
                raise ValueError(
                    f"expert dim {i} of {'/'.join(names)} already sharded "
                    f"over {parts[i]!r}")
            parts[i] = axis
            return P(*parts)

        return jax.tree_util.tree_map_with_path(
            fix, specs, is_leaf=lambda x: isinstance(x, P))
    return fn


def ep_param_specs(params, axis: str = "expert"):
    """PartitionSpec tree sharding MoE expert stacks over ``axis`` (no
    worker axis — the engine prepends it): w1/b1/w2/b2 leaves under any
    ``moe`` submodule get their EXPERT dim sharded — the leading dim, or
    dim 1 under a ``layer_scan`` stacked ``layers`` collection (the layer
    dim stays unsharded; ``pp_ep_param_specs`` is the twin that puts it
    on ``pipe``); the gate and everything else replicated."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = [getattr(p_, "key", str(p_)) for p_ in path]
        if _expert_stack(names):
            if "layers" in names:
                return P(None, axis, *([None] * (leaf.ndim - 2)))
            return P(axis, *([None] * (leaf.ndim - 1)))
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)


def pp_ep_param_specs(params, *, pipe_axis: str = "pipe",
                      axis: str = "expert"):
    """PartitionSpec tree for a ``scan_layers`` MoE model under BOTH
    pipeline and expert parallelism: leaves under the stacked ``layers``
    collection shard their leading (layer) dim over ``pipe_axis``, and the
    expert stacks (now at dim 1, behind the layer dim) additionally shard
    over ``axis``; everything outside the stack replicated."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = [getattr(p_, "key", str(p_)) for p_ in path]
        expert = _expert_stack(names)
        if "layers" in names:
            if expert:
                return P(pipe_axis, axis, *([None] * (leaf.ndim - 2)))
            return P(pipe_axis, *([None] * (leaf.ndim - 1)))
        if expert:
            return P(axis, *([None] * (leaf.ndim - 1)))
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)
