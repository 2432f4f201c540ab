"""Llama-style causal language model (beyond-reference model family).

The modern decoder recipe, from scratch in flax (no ``transformers``
dependency): pre-norm **RMSNorm**, **rotary position embeddings** (RoPE,
rotate-half convention — no learned position table, so sequence length is
unbounded by parameters), **SwiGLU** FFN, no biases anywhere, and an
UNTIED vocab-parallel-capable LM head.  The reference has no sequence
models at all (its model is a CNN, SURVEY.md 2.3).

All parallelism plumbing is shared with the BERT/GPT stack:

- attention IS ``bert.SelfAttention(causal=True, rope_theta=..., use_bias=
  False)`` — one shared module for dense, Pallas flash, and causal ring /
  Ulysses sequence-parallel attention; it applies RoPE (``ops.attention.
  rope``) to q/k before ``attend`` with absolute positions (offset by
  ``lax.axis_index`` under sequence parallelism), so rotated keys travel
  the ring already position-encoded;
- tensor parallelism uses the Megatron construction with the shared
  param-name patterns (``qkv``/``out`` sharded by head, ``ffn_in``/
  ``ffn_up`` column-parallel, ``ffn_out`` row-parallel, ``lm_head``
  vocab-parallel — ``bert._tp_parts``), so ``bert.tp_param_specs`` and
  ``bert.pp_tp_param_specs`` apply unchanged;
- ``scan_layers=True`` stacks the blocks for the GPipe schedule
  (``bert.apply_scanned_stack``);
- ``num_experts > 0`` swaps SwiGLU for the Switch-MoE FFN.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import rope  # noqa: F401  (re-export; tests use it)
from ..parallel.tp import copy_to_tp_region, reduce_from_tp_region
from .bert import SelfAttention

_init = nn.initializers.normal(stddev=0.02)


def swiglu(x, ffn_dim: int, *, dtype=jnp.float32, tp_size: int = 1,
           model_axis: Optional[str] = None):
    """``ffn_out(silu(ffn_in(x)) * ffn_up(x))``, no biases, column- then
    row-parallel under a model axis.  Called inside a module's compact
    ``__call__``: the three ``Dense`` layers become that module's own
    (``LlamaBlock`` keeps them beside its norms; ``SwiGLU`` below is the
    same MLP as a submodule)."""
    if ffn_dim % tp_size:
        raise ValueError(
            f"ffn_dim {ffn_dim} not divisible by tp_size {tp_size} "
            "(column-parallel SwiGLU)")
    f_in = copy_to_tp_region(x, model_axis)
    gate = nn.Dense(ffn_dim // tp_size, use_bias=False, kernel_init=_init,
                    dtype=dtype, name="ffn_in")(f_in)
    up = nn.Dense(ffn_dim // tp_size, use_bias=False, kernel_init=_init,
                  dtype=dtype, name="ffn_up")(f_in)
    f = nn.Dense(x.shape[-1], use_bias=False, kernel_init=_init, dtype=dtype,
                 name="ffn_out")(nn.silu(gate) * up)
    return reduce_from_tp_region(f, model_axis)


class SwiGLU(nn.Module):
    """``swiglu`` as a submodule of its own (``models/decoder.py``: a dense
    layer's MLP, a sparse layer's shared experts)."""

    ffn_dim: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        return swiglu(x, self.ffn_dim, dtype=self.dtype)


class LlamaBlock(nn.Module):
    """Pre-norm decoder block: x + attn(rms1(x)); x + swiglu(rms2(x))."""

    num_heads: int
    ffn_dim: int                   # GLOBAL SwiGLU hidden width
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    axis_name: Optional[str] = None
    tp_size: int = 1
    model_axis: Optional[str] = None
    rope_theta: float = 10000.0
    num_kv_heads: Optional[int] = None   # < num_heads => GQA
    num_experts: int = 0
    expert_axis: Optional[str] = None
    ep_size: int = 1
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, *, train: bool = False, aux_scale=1.0):
        norm = lambda name: nn.RMSNorm(epsilon=1e-5, dtype=self.dtype,
                                       name=name)
        # named activations (ISSUE 15, models.REMAT_NAMES): inert
        # identity labels a save_names:/offload_names: policy selects
        a = checkpoint_name(
            SelfAttention(self.num_heads, dtype=self.dtype,
                          attention_impl=self.attention_impl,
                          axis_name=self.axis_name, tp_size=self.tp_size,
                          model_axis=self.model_axis, causal=True,
                          rope_theta=self.rope_theta, use_bias=False,
                          num_kv_heads=self.num_kv_heads,
                          name="attn")(norm("rms1")(x)), "attn_out")
        x = x + a
        f = norm("rms2")(x)
        if self.num_experts:
            from .moe import MoEFFN
            f = MoEFFN(self.num_experts, self.ffn_dim,
                       capacity_factor=self.capacity_factor,
                       dtype=self.dtype, expert_axis=self.expert_axis,
                       ep_size=self.ep_size, tp_size=self.tp_size,
                       model_axis=self.model_axis, name="moe")(
                           f, train=train, aux_scale=aux_scale)
        else:
            f = swiglu(f, self.ffn_dim, dtype=self.dtype,
                       tp_size=self.tp_size, model_axis=self.model_axis)
        f = checkpoint_name(f, "mlp_out")
        return checkpoint_name(x + f, "block_out")


class _ScanLlamaBlock(nn.Module):
    """carry-API adapter so ``nn.scan`` can stack LlamaBlocks.  Second
    (broadcast) arg: MoE aux-loss scale (None => 1.0; the GPipe schedule
    passes its bubble mask — parallel/pp.py)."""

    num_heads: int
    ffn_dim: int
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    axis_name: Optional[str] = None
    tp_size: int = 1
    model_axis: Optional[str] = None
    rope_theta: float = 10000.0
    num_kv_heads: Optional[int] = None
    num_experts: int = 0
    expert_axis: Optional[str] = None
    ep_size: int = 1
    capacity_factor: float = 1.25
    train: bool = False

    @nn.compact
    def __call__(self, x, aux_scale):
        y = LlamaBlock(self.num_heads, self.ffn_dim, dtype=self.dtype,
                       attention_impl=self.attention_impl,
                       axis_name=self.axis_name, tp_size=self.tp_size,
                       model_axis=self.model_axis,
                       rope_theta=self.rope_theta,
                       num_kv_heads=self.num_kv_heads,
                       num_experts=self.num_experts,
                       expert_axis=self.expert_axis, ep_size=self.ep_size,
                       capacity_factor=self.capacity_factor, name="layer")(
                           x, train=self.train,
                           aux_scale=1.0 if aux_scale is None
                           else aux_scale)
        return y, None


class LlamaForCausalLM(nn.Module):
    """Token ids [B, L] -> next-token logits [B, L, vocab] (or the LOCAL
    vocab slice under tensor parallelism — vocab-parallel LM head)."""

    num_classes: int = 32000       # vocab size (engine passes num_classes)
    num_layers: int = 16
    hidden: int = 1024
    num_heads: int = 16
    ffn_dim: int = 2816            # SwiGLU hidden (~2.75x hidden)
    rope_theta: float = 10000.0
    num_kv_heads: Optional[int] = None   # < num_heads => GQA (Llama-2/3)
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    axis_name: Optional[str] = None
    tp_size: int = 1
    model_axis: Optional[str] = None
    scan_layers: bool = False
    pipeline_axis: Optional[str] = None
    pp_size: int = 1
    num_microbatches: int = 0      # 0 => pp_size
    remat: bool = False            # [compat alias] remat_policy="everything"
    remat_policy: Optional[str] = None  # none | dots_saveable | everything
    num_experts: int = 0           # >0 => Switch-MoE FFN in every block
    expert_axis: Optional[str] = None
    ep_size: int = 1
    capacity_factor: float = 1.25

    # class marker: with tp_size > 1 the untied lm_head outputs its LOCAL
    # vocab slice and the engine's loss goes vocab-parallel
    vocab_parallel_head = True

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False,
                 mode: str = "full"):
        """``mode`` partitions the forward for the 1F1B engine path
        (parallel/pp.py): 'embed' / 'stage' / 'head' — see
        ``bert.BertForMLM.__call__``."""
        if self.tp_size > 1 and self.num_classes % self.tp_size:
            raise ValueError(
                f"vocab size {self.num_classes} not divisible by tp_size "
                f"{self.tp_size} (vocab-parallel LM head)")
        if mode == "head":
            return self._lm_head(input_ids)
        if mode != "stage":
            x = nn.Embed(self.num_classes, self.hidden,
                         embedding_init=_init, dtype=self.dtype,
                         name="tok_emb")(input_ids)
            if mode == "embed":
                return x
        else:
            if not self.scan_layers:
                raise ValueError("mode='stage' requires scan_layers=True")
            x = input_ids  # activations: apply the local stage layers only
        # no position table: RoPE inside attention carries all position info
        if self.scan_layers:
            from .bert import apply_scanned_stack, resolve_remat_policy
            x = apply_scanned_stack(
                _ScanLlamaBlock, x, num_layers=self.num_layers,
                pp_size=self.pp_size,
                pipeline_axis=None if mode == "stage"
                else self.pipeline_axis,
                remat_policy=resolve_remat_policy(self.remat,
                                                  self.remat_policy),
                num_microbatches=self.num_microbatches, train=train,
                num_heads=self.num_heads, ffn_dim=self.ffn_dim,
                dtype=self.dtype, attention_impl=self.attention_impl,
                axis_name=self.axis_name, tp_size=self.tp_size,
                model_axis=self.model_axis, rope_theta=self.rope_theta,
                num_kv_heads=self.num_kv_heads,
                num_experts=self.num_experts,
                expert_axis=self.expert_axis, ep_size=self.ep_size,
                capacity_factor=self.capacity_factor)
        else:
            for i in range(self.num_layers):
                x = LlamaBlock(self.num_heads, self.ffn_dim,
                               dtype=self.dtype,
                               attention_impl=self.attention_impl,
                               axis_name=self.axis_name,
                               tp_size=self.tp_size,
                               model_axis=self.model_axis,
                               rope_theta=self.rope_theta,
                               num_kv_heads=self.num_kv_heads,
                               num_experts=self.num_experts,
                               expert_axis=self.expert_axis,
                               ep_size=self.ep_size,
                               capacity_factor=self.capacity_factor,
                               name=f"layer{i}")(x, train=train)
        if mode == "stage":
            return x
        return self._lm_head(x)

    def _lm_head(self, x):
        x = nn.RMSNorm(epsilon=1e-5, dtype=self.dtype, name="rms_f")(x)
        if self.tp_size > 1:
            x = copy_to_tp_region(x, self.model_axis)
        return nn.Dense(self.num_classes // self.tp_size, use_bias=False,
                        kernel_init=_init, dtype=self.dtype,
                        name="lm_head")(x)
