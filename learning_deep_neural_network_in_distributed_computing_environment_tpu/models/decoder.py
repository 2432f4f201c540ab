"""The decoder-only LM that a ``models.arch.DecoderArch`` describes: one
block definition, ``x + Attn_t(RMSNorm(x))`` then ``x + F(RMSNorm(x))``,
whose attention takes its kind ``t`` (window or full, and that kind's
rotary parameters, or none) from the layer's place in the period, and is
grouped-query (with a norm on q and k and a gate on its output where the
record says so) or latent.  ``F`` is the routed experts, plus the shared
experts every token passes where the record has them; in the record's
leading dense layers it is one SwiGLU MLP.  Under ``sandwich_norm`` each
sublayer's output passes a norm of its own before the add: ``x +
RMSNorm(Attn_t(RMSNorm(x)))``, four norms a block.

The stack is scanned a PERIOD at a time (``bert.apply_scanned_stack`` over
``_ScanPeriod``): the layers of a period share one parameter shape and
differ only in static arguments of attention, so a period is that many
calls of the one block, named ``layer_<i>``, and the stacked ``layers``
collection carries the periods on its leading axis.  Rematerialisation is
per layer, inside the period: a period is the whole of a short stack.

The leading dense layers are of another parameter shape than a period, so
they run before the scan as plain modules ``lead_<i>``, rematerialised by
the same policy.

Untied head, no biases, no position table (rotary), no auxiliary loss.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

import jax

from .arch import DecoderArch
from .bert import (LatentAttention, SelfAttention, apply_scanned_stack,
                   resolve_remat_policy)
from .llama import SwiGLU
from .moe import RoutedExperts

_init = nn.initializers.normal(stddev=0.02)


class DecoderBlock(nn.Module):
    arch: DecoderArch
    layer_type: str                # "sliding" | "full"
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    dense_ffn: int = 0             # > 0: one SwiGLU MLP of this width

    @nn.compact
    def __call__(self, x):
        a = self.arch
        norm = lambda name: nn.RMSNorm(epsilon=a.norm_eps, dtype=self.dtype,
                                       name=name)
        post = lambda name, y: norm(name)(y) if a.sandwich_norm else y
        rope = a.rope_of(self.layer_type)      # None: no rotary on this kind
        if a.latent:
            if a.qk_norm or a.attn_gate:
                raise ValueError("latent attention takes no qk_norm and no "
                                 "attn_gate")
            attn = LatentAttention(
                a.heads, a.latent, rope_theta=rope.theta,
                rope_interleaved=a.rope_interleaved, norm_eps=a.norm_eps,
                dtype=self.dtype, attention_impl=self.attention_impl,
                name="attn")
        else:
            attn = SelfAttention(
                a.heads, dtype=self.dtype,
                attention_impl=self.attention_impl, causal=True,
                use_bias=False, num_kv_heads=a.kv_heads, head_dim=a.head_dim,
                window=a.window_of(self.layer_type),
                rope_theta=rope and rope.theta, rope_yarn=rope and rope.yarn,
                qk_norm=a.norm_eps if a.qk_norm else None, gate=a.attn_gate,
                name="attn")
        x = x + checkpoint_name(
            post("rms1_post", attn(norm("rms1")(x))), "attn_out")
        h = norm("rms2")(x)
        if self.dense_ffn:
            f = SwiGLU(self.dense_ffn, dtype=self.dtype, name="mlp")(h)
        else:
            f = RoutedExperts(a.experts, a.expert_ffn, a.experts_per_token,
                              experts_held=a.experts_held, dtype=self.dtype,
                              router=a.router, name="moe")(h)
            if a.shared_ffn:
                with jax.named_scope("moe_shared"):
                    f = f + SwiGLU(a.shared_ffn, dtype=self.dtype,
                                   name="shared")(h)
        return checkpoint_name(
            x + checkpoint_name(post("rms2_post", f), "mlp_out"), "block_out")


def _block(layer_remat: Optional[str]):
    """``DecoderBlock``, rematerialised per layer under a named policy."""
    if not layer_remat:
        return DecoderBlock
    from . import checkpoint_policy
    return nn.remat(DecoderBlock, prevent_cse=False,
                    policy=checkpoint_policy(layer_remat))


class _ScanPeriod(nn.Module):
    """carry-API adapter: one period of the stack a scan step."""

    arch: DecoderArch
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    layer_remat: Optional[str] = None   # a named policy, per layer
    train: bool = False

    @nn.compact
    def __call__(self, x, _):
        block = _block(self.layer_remat)
        for i, kind in enumerate(self.arch.layer_types):
            x = block(self.arch, kind, dtype=self.dtype,
                      attention_impl=self.attention_impl,
                      name=f"layer_{i}")(x)
        return x, None


class DecoderLM(nn.Module):
    """Token ids [B, L] -> next-token logits [B, L, rows of the head held]."""

    arch: DecoderArch
    num_classes: int = 0           # the engine's name for the vocabulary
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    scan_layers: bool = True
    remat_policy: Optional[str] = None  # a named policy, applied per layer

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False):
        a = self.arch
        if not self.scan_layers:
            raise ValueError(
                "a DecoderArch model has one parameter layout, the stacked "
                "periods: run it with --layer_scan auto|on")
        if self.num_classes != a.vocab:
            raise ValueError(
                f"the data has {self.num_classes} token ids and this model "
                f"holds {a.vocab} rows of its vocabulary")
        x = nn.Embed(a.vocab, a.hidden,
                     embedding_init=nn.initializers.normal(a.embed_std),
                     dtype=self.dtype, name="tok_emb")(input_ids)
        if a.embed_scale != 1.0:
            x = x * jnp.asarray(a.embed_scale, x.dtype)
        layer_remat = resolve_remat_policy(False, self.remat_policy)
        lead_layers, lead_width = a.lead_dense
        for i in range(lead_layers):
            x = _block(layer_remat)(
                a, a.layer_types[0], dtype=self.dtype,
                attention_impl=self.attention_impl, dense_ffn=lead_width,
                name=f"lead_{i}")(x)
        x = apply_scanned_stack(
            _ScanPeriod, x, num_layers=a.periods, pp_size=1,
            pipeline_axis=None, num_microbatches=0, train=train,
            arch=a, dtype=self.dtype, attention_impl=self.attention_impl,
            layer_remat=layer_remat)
        x = nn.RMSNorm(epsilon=a.norm_eps, dtype=self.dtype, name="rms_f")(x)
        return nn.Dense(a.vocab, use_bias=False, kernel_init=_init,
                        dtype=self.dtype, name="lm_head")(x)
