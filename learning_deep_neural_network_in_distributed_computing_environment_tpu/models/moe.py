"""Mixture-of-Experts FFN with expert parallelism over the ``expert``
mesh axis.

Beyond-reference capability (the reference is data-parallel only,
SURVEY.md 2.3).  Switch-Transformer-style top-1 token routing with a
capacity limit, formulated TPU-first as dispatch/combine einsums (dense
one-hot dispatch tensors -> MXU work, no gather/scatter):

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

import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

_init = nn.initializers.normal(stddev=0.02)


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
        # position of each token within its expert's queue; drop overflow
        pos = jnp.einsum("ne,ne->n", jnp.cumsum(onehot, axis=0) - 1.0,
                         onehot).astype(jnp.int32)
        keep = (pos < cap).astype(jnp.float32)
        dispatch = (onehot * keep[:, None])[..., None] * jax.nn.one_hot(
            jnp.clip(pos, 0, cap - 1), cap,
            dtype=jnp.float32)[:, None, :]                      # [N, E, C]

        # --- local expert slice ------------------------------------------
        if self.expert_axis is not None:
            off = lax.axis_index(self.expert_axis) * e_local
            dispatch_local = lax.dynamic_slice_in_dim(dispatch, off, e_local,
                                                      axis=1)
        else:
            dispatch_local = dispatch

        w1 = self.param("w1", _init, (e_local, h, f_local))
        b1 = self.param("b1", nn.initializers.zeros, (e_local, f_local))
        w2 = self.param("w2", _init, (e_local, f_local, h))
        b2 = self.param("b2", nn.initializers.zeros, (e_local, h))

        dl = dispatch_local.astype(self.dtype)
        # named activation "moe_dispatch" (ISSUE 15): the expert-batched
        # dispatched tokens [E, C, H] — the MoE-specific residual a
        # save_names:/offload_names: policy may pin (recomputing it
        # re-pays the dense one-hot dispatch einsum)
        xe = checkpoint_name(
            jnp.einsum("nec,nh->ech", dl, toks.astype(self.dtype)),
            "moe_dispatch")
        h1 = nn.gelu(jnp.einsum("ech,ehf->ecf", xe, w1.astype(self.dtype))
                     + b1[:, None, :].astype(self.dtype), approximate=False)
        # row-parallel w2: per-shard partial sums over the local F slice;
        # b2 is scaled so the cross-shard psum below adds it exactly once
        b2_scale = 1.0 / self.tp_size if self.model_axis is not None else 1.0
        ye = jnp.einsum("ecf,efh->ech", h1, w2.astype(self.dtype)) \
            + b2_scale * b2[:, None, :].astype(self.dtype)
        combine = dl * gate[:, None, None].astype(self.dtype)
        out = jnp.einsum("nec,ech->nh", combine, ye)
        reduce_axes = tuple(a for a in (self.expert_axis, self.model_axis)
                            if a is not None)
        if reduce_axes:
            out = lax.psum(out, reduce_axes)
        return out.reshape(b, t, h)


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
            if "moe" not in names or "gate" in names:
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
        if "moe" in names and "gate" not in names:
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
        expert = "moe" in names and "gate" not in names
        if "layers" in names:
            if expert:
                return P(pipe_axis, axis, *([None] * (leaf.ndim - 2)))
            return P(pipe_axis, *([None] * (leaf.ndim - 1)))
        if expert:
            return P(axis, *([None] * (leaf.ndim - 1)))
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)
