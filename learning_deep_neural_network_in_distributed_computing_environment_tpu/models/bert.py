"""BERT-base masked-LM (BASELINE.md config ladder entry 5).

A from-scratch flax implementation (no ``transformers`` dependency):
post-LN encoder, learned position embeddings, GELU FFN, untied MLM head.
Attention is factored through ``ops.attention.attend`` so the same model
runs dense, flash (Pallas), or ring/all-to-all sequence-parallel attention
(``parallel/sp.py``) without touching the module.

Tensor parallelism (``parallel/tp.py``, Megatron construction): with
``tp_size > 1`` the module computes its LOCAL shard — ``num_heads/tp``
attention heads and ``ffn_dim/tp`` hidden units — and the row-parallel
output projections carry explicit biases added AFTER the cross-shard
reduction.  The dense module (``tp_size=1``) has the identical parameter
STRUCTURE, so a TP mesh run and a dense run share checkpoints: the global
parameter arrays are simply sharded over the ``model`` axis
(``tp_param_specs``).

Defaults are BERT-base: 12 layers, hidden 768, 12 heads, FFN 3072,
vocab 30522, max position 512.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..parallel.tp import copy_to_tp_region, reduce_from_tp_region

_init = nn.initializers.normal(stddev=0.02)


class SelfAttention(nn.Module):
    num_heads: int                 # GLOBAL head count
    dtype: Any = jnp.float32
    attention_impl: str = "dense"  # dense | flash | ring | all_to_all
    axis_name: Optional[str] = None   # mesh axis for seq-parallel attention
    tp_size: int = 1
    model_axis: Optional[str] = None  # mesh axis for tensor parallelism
    causal: bool = False           # autoregressive masking (decoder models)
    rope_theta: Optional[float] = None  # apply RoPE to q/k (Llama recipe)
    use_bias: bool = True          # False => no qkv / output biases (Llama)
    num_kv_heads: Optional[int] = None  # < num_heads => grouped-query
    #                                     attention (separate q / kv
    #                                     projections, kv heads shared by
    #                                     num_heads // num_kv_heads queries)
    head_dim: Optional[int] = None  # given (None: hidden // num_heads)
    window: Optional[int] = None    # causal sliding window, itself counted
    rope_yarn: Optional[tuple] = None  # ops.attention.rope_frequencies
    qk_norm: Optional[float] = None  # eps: RMSNorm over each head's q and
    #                                  k before the rotary, one learned
    #                                  head_dim-wide scale each
    gate: bool = False              # sigmoid(x Wg), a value a head and
    #                                  column, times attention's output

    @nn.compact
    def __call__(self, x, mask=None):
        from ..ops.attention import attend
        d = x.shape[-1]
        head_dim = self.head_dim or d // self.num_heads
        if self.num_heads % self.tp_size:
            raise ValueError(
                f"num_heads {self.num_heads} not divisible by tp_size "
                f"{self.tp_size} (head-sharded tensor parallelism)")
        h_local = self.num_heads // self.tp_size
        x_in = copy_to_tp_region(x, self.model_axis)
        # falsy num_kv_heads (None or the config's 0 sentinel) means MHA
        gqa = bool(self.num_kv_heads) and self.num_kv_heads != self.num_heads
        if gqa:
            if self.num_heads % self.num_kv_heads:
                raise ValueError(
                    f"num_heads {self.num_heads} not divisible by "
                    f"num_kv_heads {self.num_kv_heads}")
            if self.num_kv_heads % self.tp_size:
                raise ValueError(
                    f"num_kv_heads {self.num_kv_heads} not divisible by "
                    f"tp_size {self.tp_size}")
            kv_local = self.num_kv_heads // self.tp_size
            q = nn.DenseGeneral((h_local, head_dim), kernel_init=_init,
                                use_bias=self.use_bias, dtype=self.dtype,
                                name="q")(x_in)
            kv = nn.DenseGeneral((2, kv_local, head_dim), kernel_init=_init,
                                 use_bias=self.use_bias, dtype=self.dtype,
                                 name="kv")(x_in)
            k, v = kv[..., 0, :, :], kv[..., 1, :, :]
        else:
            qkv = nn.DenseGeneral((3, h_local, head_dim), kernel_init=_init,
                                  use_bias=self.use_bias, dtype=self.dtype,
                                  name="qkv")(x_in)
            q, k, v = (qkv[..., 0, :, :], qkv[..., 1, :, :],
                       qkv[..., 2, :, :])
        if self.qk_norm is not None:
            with jax.named_scope("qk_norm"):
                q, k = (nn.RMSNorm(epsilon=self.qk_norm, dtype=self.dtype,
                                   name=name)(t)
                        for name, t in (("q_norm", q), ("k_norm", k)))
        if self.rope_theta is not None:
            from jax import lax
            from ..ops.attention import rope
            pos = jnp.arange(x.shape[1])
            if self.axis_name is not None:
                # sequence-parallel: this device holds chunk axis_index, so
                # absolute positions are offset by index * chunk length —
                # rotated keys travel the ring already position-encoded
                pos = pos + lax.axis_index(self.axis_name) * x.shape[1]
            q = rope(q, pos, self.rope_theta, self.rope_yarn)
            k = rope(k, pos, self.rope_theta, self.rope_yarn)
        # GQA K/V are passed GROUPED ([B, L, kv_local, D]) straight into
        # attend: every impl — dense (grouped einsum), flash kernel
        # (grouped block specs), ring (rep-x smaller rotating blocks),
        # Ulysses — consumes them without a repeat-to-full-heads expansion,
        # so the K/V bandwidth saving GQA exists for actually materializes
        out = attend(q, k, v, mask=mask, impl=self.attention_impl,
                     axis_name=self.axis_name, causal=self.causal,
                     window=self.window)
        if self.gate:
            with jax.named_scope("attn_gate"):
                out = out * jax.nn.sigmoid(nn.DenseGeneral(
                    (h_local, head_dim), kernel_init=_init, use_bias=False,
                    dtype=self.dtype, name="gate")(x_in))
        y = nn.DenseGeneral(d, axis=(-2, -1), kernel_init=_init,
                            use_bias=False, dtype=self.dtype,
                            name="out")(out)
        y = reduce_from_tp_region(y, self.model_axis)
        if not self.use_bias:
            return y
        return y + self.param("out_bias", nn.initializers.zeros,
                              (d,)).astype(y.dtype)


class LatentAttention(nn.Module):
    """Latent attention (MLA) without a query bottleneck, in the expanded
    form that trains and prefills: ``q = x Wq`` as heads of ``nope + rope``;
    ``(c, k_rope) = split(x Wkv_a)``; ``c = RMSNorm(c)``; ``c Wkv_b`` as
    heads of ``nope + v``, split into each head's key part and its value;
    rotary on each head's last ``rope`` query columns and on the ONE
    ``k_rope``, which every head's key ends in.  Scores are ``nope + rope``
    wide, values ``v`` wide: ``attend`` takes the two widths.  Causal, no
    biases.  The rotary key is copied to the heads before the kernels (one
    k operand, as a grouped-query call has).  The absorbed form that
    decodes from a cache of latents is a serving matter and not here."""

    num_heads: int
    latent: Any                    # arch.Latent
    rope_theta: float
    rope_interleaved: bool = False
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    attention_impl: str = "dense"

    @nn.compact
    def __call__(self, x):
        from ..ops.attention import attend, rope
        m, h = self.latent, self.num_heads
        b, l, d = x.shape
        rotary = functools.partial(
            rope, pos=jnp.arange(l), theta=self.rope_theta,
            interleaved=self.rope_interleaved)
        proj = functools.partial(nn.DenseGeneral, kernel_init=_init,
                                 use_bias=False, dtype=self.dtype)
        q = proj((h, m.nope + m.rope), name="q")(x)
        with jax.named_scope("mla_latent"):
            c = proj(m.rank + m.rope, name="kv_a")(x)
            k_rope = rotary(c[..., None, m.rank:])           # [B, L, 1, rope]
            c = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype,
                           name="kv_norm")(c[..., :m.rank])
            kv = proj((h, m.nope + m.v), name="kv_b")(c)
        q = jnp.concatenate([q[..., :m.nope], rotary(q[..., m.nope:])], -1)
        k = jnp.concatenate(
            [kv[..., :m.nope], jnp.broadcast_to(k_rope, (b, l, h, m.rope))],
            -1)
        out = attend(q, k, kv[..., m.nope:], impl=self.attention_impl,
                     causal=True)
        return proj(d, axis=(-2, -1), name="out")(out)


class EncoderLayer(nn.Module):
    num_heads: int
    ffn_dim: int                   # GLOBAL FFN width
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    axis_name: Optional[str] = None
    tp_size: int = 1
    model_axis: Optional[str] = None
    num_experts: int = 0           # >0 => MoE FFN (models/moe.py)
    expert_axis: Optional[str] = None
    ep_size: int = 1
    capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, mask=None, *, train: bool = False, aux_scale=1.0):
        # post-LN (original BERT): sublayer -> residual -> LayerNorm.
        # checkpoint_name labels (ISSUE 15: attn_out / mlp_out /
        # block_out, models.REMAT_NAMES) mark the activations a
        # --remat_policy save_names:/offload_names: set may pin on
        # device / offload to host — inert identities otherwise
        a = checkpoint_name(
            SelfAttention(self.num_heads, dtype=self.dtype,
                          attention_impl=self.attention_impl,
                          axis_name=self.axis_name, tp_size=self.tp_size,
                          model_axis=self.model_axis, name="attn")(x, mask),
            "attn_out")
        # LN output follows the compute dtype (flax does the mean/var math
        # in f32 internally); an f32 LN output would round-trip every
        # activation through HBM at twice the width
        x = nn.LayerNorm(epsilon=1e-12, dtype=self.dtype, name="ln_attn")(x + a)
        if self.num_experts:
            from .moe import MoEFFN
            f = MoEFFN(self.num_experts, self.ffn_dim,
                       capacity_factor=self.capacity_factor,
                       dtype=self.dtype, expert_axis=self.expert_axis,
                       ep_size=self.ep_size, tp_size=self.tp_size,
                       model_axis=self.model_axis, name="moe")(
                           x, train=train, aux_scale=aux_scale)
        else:
            if self.ffn_dim % self.tp_size:
                raise ValueError(
                    f"ffn_dim {self.ffn_dim} not divisible by tp_size "
                    f"{self.tp_size} (column-parallel FFN)")
            f_in = copy_to_tp_region(x, self.model_axis)
            f = nn.Dense(self.ffn_dim // self.tp_size, kernel_init=_init,
                         dtype=self.dtype, name="ffn_in")(f_in)
            f = nn.gelu(f, approximate=False)
            f = nn.Dense(x.shape[-1], kernel_init=_init, use_bias=False,
                         dtype=self.dtype, name="ffn_out")(f)
            f = reduce_from_tp_region(f, self.model_axis)
            f = f + self.param("ffn_bias", nn.initializers.zeros,
                               (x.shape[-1],)).astype(f.dtype)
        f = checkpoint_name(f, "mlp_out")
        return checkpoint_name(
            nn.LayerNorm(epsilon=1e-12, dtype=self.dtype,
                         name="ln_ffn")(x + f), "block_out")


class _ScanLayer(nn.Module):
    """carry-API adapter so ``nn.scan`` can stack EncoderLayers.  The
    second (broadcast) argument is the MoE aux-loss scale — None outside
    the GPipe schedule, bubble-masked ``valid / num_microbatches`` inside
    it (parallel/pp.py)."""

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
        y = EncoderLayer(self.num_heads, self.ffn_dim, dtype=self.dtype,
                         attention_impl=self.attention_impl,
                         axis_name=self.axis_name, tp_size=self.tp_size,
                         model_axis=self.model_axis,
                         num_experts=self.num_experts,
                         expert_axis=self.expert_axis,
                         ep_size=self.ep_size,
                         capacity_factor=self.capacity_factor,
                         name="layer")(
                             x, train=self.train,
                             aux_scale=1.0 if aux_scale is None
                             else aux_scale)
        return y, None


def resolve_remat_policy(remat: bool, remat_policy):
    """Effective named policy from the legacy bool + the named flag:
    ``remat_policy`` wins when set; ``remat=True`` is the "everything"
    alias; falsy/"none" means no rematerialization."""
    if remat_policy and remat_policy != "none":
        return remat_policy
    return "everything" if remat else None


def apply_scanned_stack(scan_layer_cls, x, *, num_layers: int, pp_size: int,
                        pipeline_axis, num_microbatches: int, train: bool,
                        remat_policy=None, **layer_kw):
    """``nn.scan`` the stacked ``layers`` collection and run it plain or as
    a GPipe schedule — shared by BERT/GPT/ViT/Llama.  The stacked
    collection's leading [num_layers] axis is what ``pp_param_specs``
    shards over ``pipe``; with a ``pipeline_axis`` this device applies its
    ``num_layers // pp_size`` local layers per schedule step.

    MoE composes: ``variable_axes['aux'] = 0`` stacks each layer's sown
    load-balance loss along the scan axis (the engine sums leaves fully),
    and the broadcast second argument carries the GPipe bubble mask down
    to ``MoEFFN.aux_scale``."""
    if num_layers % pp_size:
        raise ValueError(f"num_layers {num_layers} not divisible "
                         f"by pp_size {pp_size}")
    n_local = num_layers // pp_size
    cls = scan_layer_cls
    if remat_policy and remat_policy != "none":
        # rematerialize each layer on the backward pass under a named
        # jax.checkpoint policy: "everything" saves only the layer-
        # boundary activations (the GPipe paper's own memory recipe,
        # ~1/3 extra forward compute); "dots_saveable" keeps matmul
        # outputs and recomputes only the cheap elementwise chains
        # between them (the pjit/TPUv4 selective-remat default);
        # "save_names:<set>" / "offload_names:<set>" (ISSUE 15) keep
        # exactly the checkpoint_name-annotated activations in the set
        # on device / offloaded to pinned host memory
        # (models.checkpoint_policy demotes offload to same-set save on
        # backends without a host memory space)
        from . import checkpoint_policy
        cls = nn.remat(scan_layer_cls, prevent_cse=False,
                       policy=checkpoint_policy(remat_policy))
    scanned = nn.scan(
        cls, variable_axes={"params": 0, "aux": 0, "counters": 0},
        split_rngs={"params": True}, in_axes=nn.broadcast,
        length=n_local)(
            train=train, name="layers", **layer_kw)
    if pipeline_axis is None:
        return scanned(x, None)[0]
    from ..parallel.pp import gpipe_apply_scanned
    return gpipe_apply_scanned(scanned, x, pipeline_axis, pp_size,
                               num_microbatches)


class BertForMLM(nn.Module):
    """Token ids [B, L] -> MLM logits [B, L, vocab]; ``mode='encode'``
    stops before the head and ``mode='head'`` is the head alone, on rows
    of any leading shape (how the engine runs it on the labelled
    positions only: ``labelled_rows_head`` below).

    ``scan_layers=True`` stores the encoder stack STACKED (one ``layers``
    collection with a leading [num_layers] axis, applied via ``nn.scan``)
    instead of ``layer{i}`` loop unrolling — required for pipeline
    parallelism (the layer axis is what shards over ``pipe``) and much
    faster to compile at depth.  ``pipeline_axis``/``pp_size`` run the
    stack as a GPipe schedule (``parallel/pp.py``): this device applies
    its ``num_layers/pp_size`` local layers per schedule step.
    """

    num_classes: int = 30522       # vocab size (engine passes num_classes)
    num_layers: int = 12
    hidden: int = 768
    num_heads: int = 12
    ffn_dim: int = 3072
    max_len: int = 512
    dtype: Any = jnp.float32
    attention_impl: str = "dense"
    axis_name: Optional[str] = None
    tp_size: int = 1
    model_axis: Optional[str] = None
    scan_layers: bool = False
    pipeline_axis: Optional[str] = None
    pp_size: int = 1               # pipe-axis size (static; local layer
    #                                count = num_layers // pp_size)
    num_microbatches: int = 0      # 0 => pp_size
    remat: bool = False            # [compat alias] remat_policy="everything"
    remat_policy: Optional[str] = None  # none | dots_saveable | everything
    num_experts: int = 0           # >0 => MoE FFN in every layer
    expert_axis: Optional[str] = None
    ep_size: int = 1
    capacity_factor: float = 1.25

    # class marker (not a field): with tp_size > 1 this model's output is
    # its LOCAL vocab slice and the loss must be vocab-parallel
    vocab_parallel_head = True
    # class marker: the task labels a minority of positions (MLM masks
    # 15%) and the head is position-wise (Dense -> gelu -> LayerNorm ->
    # Dense), so head(rows[i]) == head(rows)[i] and the engine may run
    # head and loss on the labelled rows alone (train.py,
    # ``_labelled_row_sums``).  Its value names the parameter collections
    # that ``mode='head'`` reads.  A causal LM labels every position: no
    # marker, and its programs are untouched.
    labelled_rows_head = ("mlm_dense", "mlm_ln", "mlm_decoder")

    @nn.compact
    def __call__(self, input_ids, *, train: bool = False,
                 mode: str = "full"):
        """``mode`` partitions the forward: 'embed' -> embedded
        activations, 'stage' -> apply this device's local scanned layers
        to activations (no pipeline schedule), 'head' -> MLM transform +
        decode on activations (the three of the 1F1B engine path,
        parallel/pp.py), 'encode' -> the encoder's output [B, L, hidden]
        without the head.  'full' (default) is the ordinary forward; init
        always uses it so every mode shares one parameter structure."""
        if self.tp_size > 1 and self.num_classes % self.tp_size:
            raise ValueError(
                f"vocab size {self.num_classes} not divisible by tp_size "
                f"{self.tp_size} (vocab-parallel MLM head)")
        if mode == "stage":
            return self._encode_scanned(input_ids, train, as_stage=True)
        if mode == "head":
            return self._mlm_head(input_ids)
        b, l = input_ids.shape
        tok = nn.Embed(self.num_classes, self.hidden, embedding_init=_init,
                       name="tok_emb")(input_ids)
        pos_ids = jnp.arange(l)
        if self.axis_name is not None:
            # sequence-parallel: this device holds chunk axis_index of the
            # sequence, so absolute positions are offset by index * chunk
            from jax import lax
            pos_ids = pos_ids + lax.axis_index(self.axis_name) * l
        pos = nn.Embed(self.max_len, self.hidden, embedding_init=_init,
                       name="pos_emb")(pos_ids[None, :])
        x = nn.LayerNorm(epsilon=1e-12, name="ln_emb")(tok + pos)
        x = jnp.asarray(x, self.dtype)
        if mode == "embed":
            return x
        if self.scan_layers:
            x = self._encode_scanned(x, train)
        else:
            for i in range(self.num_layers):
                x = EncoderLayer(self.num_heads, self.ffn_dim,
                                 dtype=self.dtype,
                                 attention_impl=self.attention_impl,
                                 axis_name=self.axis_name,
                                 tp_size=self.tp_size,
                                 model_axis=self.model_axis,
                                 num_experts=self.num_experts,
                                 expert_axis=self.expert_axis,
                                 ep_size=self.ep_size,
                                 capacity_factor=self.capacity_factor,
                                 name=f"layer{i}")(x, train=train)
        if mode == "encode":
            return x
        return self._mlm_head(x)

    def _mlm_head(self, x):
        # untied MLM head: transform + LayerNorm + decode.  The head runs
        # in the compute dtype: at bf16 the [*, hidden, vocab] decode
        # matmul hits the MXU's full bf16 rate and the [B, L, vocab]
        # logits cost half the HBM; the loss upcasts to f32 for the
        # log-softmax either way (train.softmax_cross_entropy).
        # Under tensor parallelism the decode is VOCAB-PARALLEL (Megatron):
        # each shard computes logits for its vocab slice and the engine's
        # loss uses parallel.tp.vocab_parallel_token_stats — the full
        # [B, L, V] logits never materialize on one device.
        x = nn.Dense(self.hidden, kernel_init=_init, dtype=self.dtype,
                     name="mlm_dense")(x)
        x = nn.gelu(x, approximate=False)
        x = nn.LayerNorm(epsilon=1e-12, dtype=self.dtype, name="mlm_ln")(x)
        if self.tp_size > 1:
            x = copy_to_tp_region(x, self.model_axis)
        return nn.Dense(self.num_classes // self.tp_size, kernel_init=_init,
                        dtype=self.dtype, name="mlm_decoder")(x)

    def _encode_scanned(self, x, train: bool, as_stage: bool = False):
        return apply_scanned_stack(
            _ScanLayer, x, num_layers=self.num_layers, pp_size=self.pp_size,
            pipeline_axis=None if as_stage else self.pipeline_axis,
            remat_policy=resolve_remat_policy(self.remat, self.remat_policy),
            num_microbatches=self.num_microbatches, train=train,
            num_heads=self.num_heads, ffn_dim=self.ffn_dim,
            dtype=self.dtype, attention_impl=self.attention_impl,
            axis_name=self.axis_name, tp_size=self.tp_size,
            model_axis=self.model_axis, num_experts=self.num_experts,
            expert_axis=self.expert_axis, ep_size=self.ep_size,
            capacity_factor=self.capacity_factor)


def _tp_parts(names: list, ndim: int, axis: str,
              shard_tok_emb: bool = False):
    """Megatron sharding pattern for one leaf, as a parts list of length
    ``ndim`` (the UNSTACKED leaf rank — callers with a leading layer dim
    pass ``leaf.ndim - 1``).

    ``shard_tok_emb``: shard the token-embedding table's VOCAB dim — the
    vocab-parallel TIED head (GPT: the same table is the decode matrix,
    so sharding it shards both the lookup and the logits; models/gpt.py
    ``_embed``).  BERT/Llama keep their lookup tables replicated (their
    decodes are separate vocab-parallel Dense kernels).

    qkv kernel [H, 3, heads, hd] / bias [3, heads, hd]: heads dim sharded;
    attn out kernel [heads, hd, H] and ffn_out kernel [F, H]: dim 0 sharded
    (row-parallel); ffn_in kernel [H, F] / bias [F]: F sharded (column-
    parallel); the MLM decode is vocab-parallel (kernel [H, V]: V sharded
    — column-parallel over the vocabulary); everything else (embeddings,
    LNs, post-reduce biases, the MLM transform) replicated.
    """
    parts = [None] * ndim
    if "moe" in names:
        # MoE x TP (models/moe.py): per-expert Megatron sharding on the F
        # dim — w1 [E, H, F] / b1 [E, F] column-parallel, w2 [E, F, H]
        # row-parallel; gate and b2 (post-psum bias) replicated.  The
        # leading E dim is the EXPERT dim (overlaid with the 'expert' axis
        # by moe.with_expert_overlay when EP is also on).
        if "w1" in names and ndim == 3:
            parts[2] = axis
        elif "b1" in names and ndim == 2:
            parts[1] = axis
        elif "w2" in names and ndim == 3:
            parts[1] = axis
        return parts
    if "qkv" in names:
        parts[2 if ndim == 4 else 1] = axis
    elif "q" in names:
        # GQA query projection: kernel [H, heads, hd] / bias [heads, hd]
        parts[1 if ndim == 3 else 0] = axis
    elif "kv" in names:
        # GQA kv projection: kernel [H, 2, kv_heads, hd] / bias [2, kv, hd]
        parts[2 if ndim == 4 else 1] = axis
    elif "out" in names and ndim == 3:   # kernel [heads, hd, H]
        parts[0] = axis
    elif "ffn_in" in names or "ffn_up" in names:
        # column-parallel: ffn_in kernel [H, F] / bias [F]; ffn_up is the
        # SwiGLU second input projection (models/llama.py), same pattern
        parts[1 if ndim == 2 else 0] = axis
    elif "ffn_out" in names and ndim == 2:   # kernel [F, H]
        parts[0] = axis
    elif "mlm_decoder" in names or "lm_head" in names:
        # vocab-parallel decode: kernel [H, V] / bias [V]
        parts[1 if ndim == 2 else 0] = axis
    elif shard_tok_emb and "tok_emb" in names and ndim == 2:
        parts[0] = axis              # embedding table [V, H]: V sharded
    return parts


def tp_param_specs(params, axis: str = "model", *,
                   shard_tok_emb: bool = False):
    """PartitionSpec tree sharding BERT parameters over the TP ``axis``
    (no worker axis — the engine prepends it); pattern in ``_tp_parts``.

    Handles BOTH parameter layouts: unrolled ``layer{i}`` trees and the
    ``layer_scan`` stacked ``layers`` collection, whose leaves carry a
    leading [num_layers] dim (unsharded here — ``pp_tp_param_specs`` is
    the twin that puts it on ``pipe``) with the Megatron pattern applied
    to the inner dims."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        if "layers" in names:
            return P(None, *_tp_parts(names, leaf.ndim - 1, axis))
        return P(*_tp_parts(names, leaf.ndim, axis,
                            shard_tok_emb=shard_tok_emb))
    return jax.tree_util.tree_map_with_path(spec, params)


def pp_tp_param_specs(params, *, pipe_axis: str = "pipe",
                      axis: str = "model", shard_tok_emb: bool = False):
    """PartitionSpec tree for a ``scan_layers`` model under BOTH pipeline
    and tensor parallelism: leaves under the stacked ``layers`` collection
    shard their leading (layer) dim over ``pipe_axis`` AND their inner dims
    per the Megatron pattern; everything outside the stack (embeddings,
    the vocab-parallel MLM decode) gets the plain TP pattern."""
    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        if "layers" in names:
            return P(pipe_axis, *_tp_parts(names, leaf.ndim - 1, axis))
        return P(*_tp_parts(names, leaf.ndim, axis,
                            shard_tok_emb=shard_tok_emb))
    return jax.tree_util.tree_map_with_path(spec, params)
