"""GPT-2-style causal language model (beyond-reference model family).

A from-scratch flax decoder (no ``transformers`` dependency): pre-LN
blocks, learned position embeddings, GELU FFN, and a TIED LM head (logits
= hidden @ token_embedding^T, the GPT-2 construction — ``gpt2_small``
matches the canonical 124,439,808-parameter count).  The reference has no
sequence models at all (its model is a CNN, SURVEY.md 2.3); this family
extends the framework's BASELINE ladder beyond BERT to autoregressive
training.

All the parallelism plumbing is shared with BERT (``models/bert.py``):

- attention is ``ops.attention.attend(..., causal=True)`` so the same
  module runs dense, flash (Pallas causal kernel), or causal ring /
  Ulysses sequence-parallel attention;
- tensor parallelism uses the identical Megatron construction and param
  names (``qkv``/``out``/``ffn_in``/``ffn_out``), so ``bert.tp_param_specs``
  applies unchanged;
- ``scan_layers=True`` stacks the blocks for pipeline parallelism
  (``parallel/pp.py`` GPipe schedule).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..parallel.tp import copy_to_tp_region, reduce_from_tp_region
from .bert import SelfAttention

_init = nn.initializers.normal(stddev=0.02)


class GPTBlock(nn.Module):
    """Pre-LN decoder block: x + attn(ln1(x)); x + ffn(ln2(x)).

    ``num_experts > 0`` swaps the dense FFN for the Switch-MoE FFN
    (``models/moe.py``), shardable over an ``expert`` mesh axis."""

    num_heads: int
    ffn_dim: int                   # GLOBAL FFN width
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    axis_name: Optional[str] = None
    tp_size: int = 1
    model_axis: Optional[str] = None
    num_experts: int = 0
    expert_axis: Optional[str] = None
    ep_size: int = 1
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, *, train: bool = False, aux_scale=1.0):
        h = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="ln1")(x)
        # named activations (ISSUE 15, models.REMAT_NAMES): inert
        # identity labels a save_names:/offload_names: policy selects
        a = checkpoint_name(
            SelfAttention(self.num_heads, dtype=self.dtype,
                          attention_impl=self.attention_impl,
                          axis_name=self.axis_name, tp_size=self.tp_size,
                          model_axis=self.model_axis, causal=True,
                          name="attn")(h), "attn_out")
        x = x + a
        f = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="ln2")(x)
        if self.num_experts:
            from .moe import MoEFFN
            f = MoEFFN(self.num_experts, self.ffn_dim,
                       capacity_factor=self.capacity_factor,
                       dtype=self.dtype, expert_axis=self.expert_axis,
                       ep_size=self.ep_size, tp_size=self.tp_size,
                       model_axis=self.model_axis, name="moe")(
                           f, train=train, aux_scale=aux_scale)
        else:
            if self.ffn_dim % self.tp_size:
                raise ValueError(
                    f"ffn_dim {self.ffn_dim} not divisible by tp_size "
                    f"{self.tp_size} (column-parallel FFN)")
            f = copy_to_tp_region(f, self.model_axis)
            f = nn.Dense(self.ffn_dim // self.tp_size, kernel_init=_init,
                         dtype=self.dtype, name="ffn_in")(f)
            f = nn.gelu(f, approximate=True)
            f = nn.Dense(x.shape[-1], kernel_init=_init, use_bias=False,
                         dtype=self.dtype, name="ffn_out")(f)
            f = reduce_from_tp_region(f, self.model_axis)
            f = f + self.param("ffn_bias", nn.initializers.zeros,
                               (x.shape[-1],)).astype(f.dtype)
        f = checkpoint_name(f, "mlp_out")
        return checkpoint_name(x + f, "block_out")


class _ScanBlock(nn.Module):
    """carry-API adapter so ``nn.scan`` can stack GPTBlocks.  Second
    (broadcast) arg: MoE aux-loss scale (None => 1.0; the GPipe schedule
    passes its bubble mask — parallel/pp.py)."""

    num_heads: int
    ffn_dim: int
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    axis_name: Optional[str] = None
    tp_size: int = 1
    model_axis: Optional[str] = None
    num_experts: int = 0
    expert_axis: Optional[str] = None
    ep_size: int = 1
    capacity_factor: float = 1.25
    train: bool = False

    @nn.compact
    def __call__(self, x, aux_scale):
        y = GPTBlock(self.num_heads, self.ffn_dim, dtype=self.dtype,
                     attention_impl=self.attention_impl,
                     axis_name=self.axis_name, tp_size=self.tp_size,
                     model_axis=self.model_axis,
                     num_experts=self.num_experts,
                     expert_axis=self.expert_axis, ep_size=self.ep_size,
                     capacity_factor=self.capacity_factor, name="layer")(
                         x, train=self.train,
                         aux_scale=1.0 if aux_scale is None else aux_scale)
        return y, None


class GPTForCausalLM(nn.Module):
    """Token ids [B, L] -> next-token logits [B, L, vocab].

    The data pipeline provides shifted labels (``labels[t] = input[t+1]``,
    final position -1/ignore — ``data/sources.py synthetic_lm``), so the
    model itself is a pure sequence-to-logits map like BERT.
    """

    num_classes: int = 50257       # vocab size (engine passes num_classes)
    num_layers: int = 12
    hidden: int = 768
    num_heads: int = 12
    ffn_dim: int = 3072
    max_len: int = 1024
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

    # tied head, vocab-parallel under TP (r4): the embedding table shards
    # over 'model' on the VOCAB dim, the lookup masks+psums the local
    # rows, and attend() emits the LOCAL vocab slice of the logits — the
    # Megatron vocab-parallel construction applied to a TIED head, so the
    # full [B, L, V] logits never materialize on one device and the
    # engine's loss goes through vocab_parallel_token_stats
    vocab_parallel_head = True

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False,
                 mode: str = "full"):
        """``mode`` partitions the forward for the 1F1B engine path
        (parallel/pp.py): 'embed' -> embedded activations, 'stage' ->
        apply this device's local scanned layers to activations (no
        pipeline schedule), 'head' -> final LN + tied decode on
        activations.  'full' (default) is the ordinary forward; init
        always uses it so every mode shares one parameter structure."""
        if self.tp_size > 1 and self.num_classes % self.tp_size:
            raise ValueError(
                f"vocab size {self.num_classes} not divisible by tp_size "
                f"{self.tp_size} (vocab-parallel tied head)")
        tok_emb = nn.Embed(self.num_classes // self.tp_size, self.hidden,
                           embedding_init=_init, dtype=self.dtype,
                           name="tok_emb")
        if mode == "stage":
            return self._decode_scanned(input_ids, train, as_stage=True)
        if mode == "head":
            x = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype,
                             name="ln_f")(input_ids)
            return tok_emb.attend(x)
        b, l = input_ids.shape
        tok = self._embed(tok_emb, input_ids)
        pos_ids = jnp.arange(l)
        if self.axis_name is not None:
            # sequence-parallel: this device holds chunk axis_index of the
            # sequence, so absolute positions are offset by index * chunk
            from jax import lax
            pos_ids = pos_ids + lax.axis_index(self.axis_name) * l
        pos = nn.Embed(self.max_len, self.hidden, embedding_init=_init,
                       dtype=self.dtype, name="pos_emb")(pos_ids[None, :])
        x = jnp.asarray(tok + pos, self.dtype)
        if mode == "embed":
            return x
        if self.scan_layers:
            x = self._decode_scanned(x, train)
        else:
            for i in range(self.num_layers):
                x = GPTBlock(self.num_heads, self.ffn_dim, dtype=self.dtype,
                             attention_impl=self.attention_impl,
                             axis_name=self.axis_name, tp_size=self.tp_size,
                             model_axis=self.model_axis,
                             num_experts=self.num_experts,
                             expert_axis=self.expert_axis,
                             ep_size=self.ep_size,
                             capacity_factor=self.capacity_factor,
                             name=f"layer{i}")(x, train=train)
        x = nn.LayerNorm(epsilon=1e-5, dtype=self.dtype, name="ln_f")(x)
        # tied LM head: logits = x @ tok_emb^T (shares the embedding
        # table; the LOCAL vocab slice under tensor parallelism)
        return tok_emb.attend(x)

    def _embed(self, tok_emb, input_ids):
        """Token lookup; under TP each shard holds vocab rows
        [idx*V/tp, (idx+1)*V/tp) and the masked local lookups psum to the
        full embedding (transpose: each shard's table gradient is its
        local scatter-add — stays sharded)."""
        if self.tp_size <= 1:
            return tok_emb(input_ids)
        from jax import lax
        v_local = self.num_classes // self.tp_size
        off = lax.axis_index(self.model_axis) * v_local
        loc = input_ids - off
        hit = (loc >= 0) & (loc < v_local)
        tok = tok_emb(jnp.clip(loc, 0, v_local - 1))
        tok = jnp.where(hit[..., None], tok, jnp.zeros_like(tok))
        return lax.psum(tok, self.model_axis)

    def _decode_scanned(self, x, train: bool, as_stage: bool = False):
        from .bert import apply_scanned_stack, resolve_remat_policy
        return apply_scanned_stack(
            _ScanBlock, x, num_layers=self.num_layers, pp_size=self.pp_size,
            pipeline_axis=None if as_stage else self.pipeline_axis,
            remat_policy=resolve_remat_policy(self.remat, self.remat_policy),
            num_microbatches=self.num_microbatches, train=train,
            num_heads=self.num_heads, ffn_dim=self.ffn_dim,
            dtype=self.dtype, attention_impl=self.attention_impl,
            axis_name=self.axis_name, tp_size=self.tp_size,
            model_axis=self.model_axis, num_experts=self.num_experts,
            expert_axis=self.expert_axis, ep_size=self.ep_size,
            capacity_factor=self.capacity_factor)
