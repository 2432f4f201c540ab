"""Mixture-of-Experts FFN with expert parallelism over the ``expert``
mesh axis.

Beyond-reference capability (the reference is data-parallel only,
SURVEY.md 2.3).  Two routed layers over ONE dispatch (``routed_apply``:
the (token, expert) pairs sorted by expert, grouped products over the
groups, a gather back; no ``[tokens, E, C]`` tensor):

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
from typing import Any, Optional

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

        def experts(rows, sizes, expert_of_row):
            from ..ops.grouped_matmul import grouped_matmul
            e_row = jnp.minimum(expert_of_row, e_local - 1)
            h1 = nn.gelu(
                grouped_matmul(rows, w1.astype(self.dtype), sizes)
                + b1.astype(self.dtype)[e_row], approximate=False)
            return (grouped_matmul(h1, w2.astype(self.dtype), sizes)
                    + b2_scale * b2.astype(self.dtype)[e_row])

        out, _ = routed_apply(toks.astype(self.dtype), expert_idx[:, None],
                              (gate * keep)[:, None], first, e_local, experts)
        reduce_axes = tuple(a for a in (self.expert_axis, self.model_axis)
                            if a is not None)
        if reduce_axes:
            out = lax.psum(out, reduce_axes)
        return out.reshape(b, t, h)


# ----------------------------------------------------------------------
# Routed experts without a capacity: sort, grouped products, gather back
# ----------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _spread(toks, order, inv, owned, k):
    """[N, H] tokens -> [M, H] rows in expert order: sorted row r is the
    token of flat (token, choice) pair ``order[r]``.  The transpose is a
    gather too (``inv`` is the sort's inverse), with the rows of pairs
    that no held expert owns selected away: they were never written."""
    return toks[jnp.minimum(order // k, toks.shape[0] - 1)]


def _spread_fwd(toks, order, inv, owned, k):
    return _spread(toks, order, inv, owned, k), (inv, owned, toks.shape[0])


def _spread_bwd(k, res, g):
    inv, owned, n = res
    back = jnp.where(owned[:, None], g[inv], 0)[:n * k]
    return back.reshape(n, k, -1).sum(1), None, None, None


_spread.defvjp(_spread_fwd, _spread_bwd)


@jax.custom_vjp
def _collect(rows, inv, order):
    """[M, H] rows in expert order -> [M, H] in flat pair order; the
    transpose is the gather by ``order``."""
    return rows[inv]


_collect.defvjp(lambda rows, inv, order: (rows[inv], order),
                lambda order, g: (g[order], None, None))


def routed_apply(toks, expert_idx, weights, first, held: int, expert_fn):
    """Send every (token, chosen expert) pair whose expert is one of the
    ``held`` experts from ``first`` on through ``expert_fn`` and add the
    results back into the tokens, weighted.  No capacity and no dropped
    pair: the rows are sorted by expert, ``expert_fn(rows [M, H], sizes
    [held], expert_of_row [M])`` runs grouped products over them
    (``ops.grouped_matmul``) and the rows come back by a gather.  M is the
    worst case, every pair on a held expert, rounded up to the kernels' row
    tile; pairs of experts held elsewhere sort behind the last group, where
    the kernels write nothing, and are selected away before use.

    ``toks`` [N, H], ``expert_idx`` / ``weights`` [N, k].  Returns ``(out
    [N, H], sizes [held])``: the held experts' part of the layer's sum and
    the rows each of them got."""
    from ..ops.grouped_matmul import TM, vary_alike
    n, k = expert_idx.shape
    m = n * k
    m_pad = -(-m // TM) * TM if m >= TM else -(-m // 8) * 8
    local = expert_idx.reshape(-1).astype(jnp.int32) - first
    owned = (local >= 0) & (local < held)
    key = jnp.pad(jnp.where(owned, local, held), (0, m_pad - m),
                  constant_values=held)
    owned = jnp.pad(owned, (0, m_pad - m))
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inv = jnp.zeros(m_pad, jnp.int32).at[order].set(
        jnp.arange(m_pad, dtype=jnp.int32), unique_indices=True)
    sizes = (key[:, None] == jnp.arange(held)[None]).sum(0, dtype=jnp.int32)
    # named activation "moe_dispatch" (ISSUE 15): the tokens in expert
    # order, the residual a save_names: / offload_names: policy may pin
    toks, order, inv, owned = vary_alike(toks, order, inv, owned)
    rows = checkpoint_name(_spread(toks, order, inv, owned, k),
                           "moe_dispatch")
    y = _collect(*vary_alike(expert_fn(rows, sizes, key[order]), inv, order))
    y = jnp.where(owned[:, None], y, 0)[:m].reshape(n, k, -1)
    return (y * weights[..., None].astype(y.dtype)).sum(1), sizes


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
    experts and the fullest held expert's rows over the mean."""

    num_experts: int               # the router's width
    ffn_dim: int                   # per-expert SwiGLU width
    top_k: int
    experts_held: Optional[tuple] = None   # (first, count); None: all
    dtype: Any = jnp.float32
    router: Router = Router()

    @nn.compact
    def __call__(self, x):
        from ..ops.grouped_matmul import grouped_matmul
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

        def experts(rows, sizes, _):
            gate = grouped_matmul(rows, w1, sizes)
            up = grouped_matmul(rows, w3, sizes)
            return grouped_matmul(nn.silu(gate) * up, w2, sizes)

        out, sizes = routed_apply(toks.astype(self.dtype), idx, weights,
                                  first, held, experts)
        rows = sizes.sum().astype(jnp.float32)
        self.sow("counters", "expert_rows", rows)
        self.sow("counters", "expert_load_max_over_mean",
                 sizes.max() * held / jnp.maximum(rows, 1.0))
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
