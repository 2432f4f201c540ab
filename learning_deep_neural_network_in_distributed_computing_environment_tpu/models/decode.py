"""Cache-aware autoregressive decode over the scanned-layer stack.

The serving twin of the training forward (ISSUE 7): the same stacked
parameters the layer-scan compile engine stores (``layers/layer`` with a
leading [num_layers] axis) applied token-incrementally against a **paged
KV cache** instead of recomputing the whole sequence per token.

Layout (vLLM-style paged attention, formulated as dense XLA gathers — no
custom kernel, so it runs on every backend the repo tests on):

- the cache is one pool of ``num_pages`` fixed-size pages per layer:
  ``k/v [num_layers, num_pages, page_size, kv_heads, head_dim]``;
- each sequence owns a **page table** row ``[pages_per_seq]`` of page ids
  mapping global position ``p`` to ``(table[p // page_size],
  p % page_size)``;
- page id 0 is the **trash page**: the allocator never hands it out, and
  every masked write (prefill padding beyond the prompt, inactive decode
  slots) is routed there, so the compiled programs stay fixed-shape with
  no conditionals;
- attention gathers a slot's pages back into a ``[pages_per_seq *
  page_size]`` key/value run and applies the **cache-offset causal
  mask** ``kpos <= q_position`` — stale data on recycled pages sits at
  positions the mask excludes, so pages never need zeroing between
  sequences.

``forward_paged`` is ONE function covering both serving programs: prefill
calls it with ``[1, bucket]`` tokens at ``lengths == 0``, the decode step
with ``[max_batch, 1]`` tokens at the current lengths.  The layer stack
runs under ``lax.scan`` (carry = activations, per-layer cache slices as
scanned inputs/outputs), so the block traces once at any depth — the
PR 3 compile story carried over to inference.

Numerics: the block math here mirrors ``models/gpt.py`` /
``models/llama.py`` / ``models/moe.py`` operation-for-operation (same
einsum formulations, fp32 softmax/normalizer, same dtype casts).
``tests/test_serve.py`` gates paged logits against the full-sequence
``model.apply`` forward at fp32 tolerance with argmax equality.  MoE
decode routes each token to its top-1 expert WITHOUT a capacity limit
(a decode step has no token queue to overflow); it matches the training
forward whenever the forward's capacity dropped nothing.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import dot_product_attention, rope

TRASH_PAGE = 0   # reserved page id for masked writes (never allocated)


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    """Static architecture facts the decode program needs — derived from
    a model instance (``spec_from_model``), never restated by the user."""

    family: str                  # "gpt" | "llama"
    num_layers: int
    hidden: int
    num_heads: int
    num_kv_heads: int            # == num_heads for MHA
    head_dim: int
    vocab: int
    max_len: int                 # gpt position-table bound (0 = unbounded)
    rope_theta: float            # llama
    num_experts: int             # > 0 => MoE FFN blocks
    dtype: Any = jnp.float32


def spec_from_model(model) -> DecodeSpec:
    """Build the decode spec for a supported autoregressive model."""
    fam = {"GPTForCausalLM": "gpt", "LlamaForCausalLM": "llama"}.get(
        type(model).__name__)
    if fam is None:
        raise ValueError(
            f"serving supports the autoregressive families (gpt_*/llama_*, "
            f"optionally MoE); got model class {type(model).__name__} — "
            "bert/vit/cnn models have no decode path")
    if not getattr(model, "scan_layers", False):
        raise ValueError(
            "serving decodes over the STACKED layer collection "
            "(layer_scan); rebuild the model with scan_layers=True — "
            "training checkpoints of the autoregressive families use the "
            "stacked layout by default (--layer_scan auto)")
    if getattr(model, "tp_size", 1) > 1 or model.axis_name is not None:
        raise ValueError("serving runs the single-replica dense twin; "
                         "TP/SP train-model variants are not servable")
    kv = getattr(model, "num_kv_heads", None) or model.num_heads
    return DecodeSpec(
        family=fam, num_layers=model.num_layers, hidden=model.hidden,
        num_heads=model.num_heads, num_kv_heads=kv,
        head_dim=model.hidden // model.num_heads,
        vocab=model.num_classes,
        max_len=getattr(model, "max_len", 0) or 0,
        rope_theta=getattr(model, "rope_theta", 10000.0),
        num_experts=getattr(model, "num_experts", 0),
        dtype=model.dtype)


def init_paged_cache(spec: DecodeSpec, num_pages: int, page_size: int):
    """Zeroed (k, v) page pools [L, P, page_size, KV, head_dim]."""
    shape = (spec.num_layers, num_pages, page_size, spec.num_kv_heads,
             spec.head_dim)
    return (jnp.zeros(shape, spec.dtype), jnp.zeros(shape, spec.dtype))


# ----------------------------------------------------------------------
# Shared numerics (mirrors of the flax modules' math)
# ----------------------------------------------------------------------

def _layernorm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True) - mu * mu
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rmsnorm(x, scale, eps=1e-5):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def paged_attend(q, k_new, v_new, *, positions, num_valid, page_table,
                 k_pages, v_pages):
    """The cache-aware attention core shared by prefill and decode.

    ``q/k_new/v_new`` [B, T, H|KV, D] are this call's projections at
    global ``positions`` [B, T]; the new K/V are scattered into the page
    pool first (rows ``i >= num_valid[b]`` — prefill padding, inactive
    slots — go to the trash page), then each slot's table is gathered
    back to a [S = pages_per_seq * page_size] run and attended under the
    cache-offset causal mask ``kpos <= position``.  Returns
    ``(out [B, T, H, D], k_pages', v_pages')``.
    """
    b, t = q.shape[:2]
    page_size = k_pages.shape[1]
    pages_per_seq = page_table.shape[1]
    flat_pos = positions.reshape(b, t)
    page_idx = jnp.clip(flat_pos // page_size, 0, pages_per_seq - 1)
    dest_page = jnp.take_along_axis(page_table, page_idx, axis=1)  # [B, T]
    valid = jnp.arange(t, dtype=jnp.int32)[None, :] < num_valid[:, None]
    dest_page = jnp.where(valid, dest_page, TRASH_PAGE).reshape(-1)
    dest_row = (flat_pos % page_size).reshape(-1)
    kv_shape = (b * t, *k_new.shape[2:])
    k_pages = k_pages.at[dest_page, dest_row].set(k_new.reshape(kv_shape))
    v_pages = v_pages.at[dest_page, dest_row].set(v_new.reshape(kv_shape))
    # gather each slot's pages into a contiguous [S] key/value run
    s = pages_per_seq * page_size
    k_all = k_pages[page_table].reshape(b, s, *k_pages.shape[2:])
    v_all = v_pages[page_table].reshape(b, s, *v_pages.shape[2:])
    kpos = jnp.arange(s, dtype=jnp.int32)
    mask = kpos[None, None, None, :] <= positions[:, None, :, None]
    out = dot_product_attention(q, k_all, v_all, mask=mask)
    return out, k_pages, v_pages


# ----------------------------------------------------------------------
# Per-family block decode (one scanned layer)
# ----------------------------------------------------------------------

def _dense_general(x, kernel, bias=None, *, dtype):
    """flax DenseGeneral over the trailing feature dim: contract x's last
    axis with kernel dim 0, appending the kernel's remaining dims.
    Input, kernel and bias are cast to the compute ``dtype`` first, as
    the flax modules' ``dtype=`` does (``promote_dtype``): the kernels
    are STORED float32, and multiplying a bfloat16 residual by them
    would promote the scan carry to float32."""
    y = lax.dot_general(x.astype(dtype), kernel.astype(dtype),
                        (((x.ndim - 1,), (0,)), ((), ())))
    return y if bias is None else y + bias.astype(dtype)


def _moe_ffn(mp, x, dtype):
    """Top-1 expert FFN, capacity-free (decode twin of models/moe.py:
    identical gate/expert math, no token queue to cap — see module doc)."""
    b, t, h = x.shape
    toks = x.reshape(b * t, h)
    gate_logits = toks.astype(jnp.float32) @ mp["gate"]["kernel"].astype(
        jnp.float32)
    probs = jax.nn.softmax(gate_logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)
    gate = jnp.max(probs, axis=-1)
    onehot = jax.nn.one_hot(expert_idx, probs.shape[-1], dtype=jnp.float32)
    w1, b1 = mp["w1"].astype(dtype), mp["b1"].astype(dtype)
    w2, b2 = mp["w2"].astype(dtype), mp["b2"].astype(dtype)
    h1 = jax.nn.gelu(jnp.einsum("nh,ehf->nef", toks.astype(dtype), w1)
                     + b1[None], approximate=False)
    ye = jnp.einsum("nef,efh->neh", h1, w2) + b2[None]
    combine = (onehot * gate[:, None]).astype(dtype)
    return jnp.einsum("ne,neh->nh", combine, ye).reshape(b, t, h)


def _attn_proj(lp, x, spec: DecodeSpec, positions):
    """q/k/v projections of one block's attention at ``positions``
    (RoPE-rotated for llama so cached keys carry their encoding)."""
    ap = lp["attn"]
    dense = functools.partial(_dense_general, dtype=spec.dtype)
    if "qkv" in ap:
        qkv = dense(x, ap["qkv"]["kernel"], ap["qkv"].get("bias"))
        q, k, v = qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]
    else:  # grouped-query attention: separate q / kv projections
        q = dense(x, ap["q"]["kernel"])
        kv = dense(x, ap["kv"]["kernel"])
        k, v = kv[..., 0, :, :], kv[..., 1, :, :]
    if spec.family == "llama":
        # rope() takes [L]-shaped positions; rows differ per slot, so
        # vmap the rotation over the batch
        rot = jax.vmap(lambda xb, pb: rope(xb[None], pb,
                                           spec.rope_theta)[0])
        q, k = rot(q, positions), rot(k, positions)
    return q, k, v


def _block(spec: DecodeSpec, lp, x, positions, num_valid, page_table,
           kc, vc):
    """One decoder block against the paged cache; ``lp`` is this layer's
    slice of the stacked params, ``kc/vc`` its [P, ps, KV, D] pool."""
    dense = functools.partial(_dense_general, dtype=spec.dtype)
    if spec.family == "gpt":
        h = _layernorm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
    else:
        h = _rmsnorm(x, lp["rms1"]["scale"])
    q, k, v = _attn_proj(lp, h, spec, positions)
    out, kc, vc = paged_attend(q, k, v, positions=positions,
                               num_valid=num_valid, page_table=page_table,
                               k_pages=kc, v_pages=vc)
    a = dense(out.reshape(*out.shape[:2], -1),
              lp["attn"]["out"]["kernel"].reshape(-1, spec.hidden))
    if "out_bias" in lp["attn"]:
        a = a + lp["attn"]["out_bias"].astype(a.dtype)
    x = x + a
    if spec.family == "gpt":
        f = _layernorm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
        if spec.num_experts:
            f = _moe_ffn(lp["moe"], f, spec.dtype)
        else:
            f = dense(f, lp["ffn_in"]["kernel"], lp["ffn_in"]["bias"])
            f = jax.nn.gelu(f, approximate=True)
            f = dense(f, lp["ffn_out"]["kernel"])
            f = f + lp["ffn_bias"].astype(f.dtype)
    else:
        f = _rmsnorm(x, lp["rms2"]["scale"])
        if spec.num_experts:
            f = _moe_ffn(lp["moe"], f, spec.dtype)
        else:
            gate = dense(f, lp["ffn_in"]["kernel"])
            up = dense(f, lp["ffn_up"]["kernel"])
            f = dense(jax.nn.silu(gate) * up, lp["ffn_out"]["kernel"])
    return x + f, kc, vc


# ----------------------------------------------------------------------
# The full paged forward (prefill AND decode are this one function)
# ----------------------------------------------------------------------

def forward_paged(spec: DecodeSpec, params, tokens, lengths, num_valid,
                  page_table, k_pages, v_pages,
                  positions: Optional[jnp.ndarray] = None):
    """Apply the model to ``tokens [B, T]`` whose rows sit at cache
    offsets ``lengths [B]`` (tokens already cached per slot).

    ``num_valid [B]`` counts the REAL new tokens per row (prefill
    padding and inactive decode slots write to the trash page);
    ``page_table [B, pages_per_seq]``.  Returns ``(logits [B, T, vocab],
    k_pages', v_pages')``.  The layer stack runs under ``lax.scan`` over
    the stacked ``layers/layer`` collection — one traced block at any
    depth, the serving twin of the layer-scan compile engine.
    """
    if positions is None:
        positions = lengths[:, None] + jnp.arange(
            tokens.shape[1], dtype=jnp.int32)[None, :]
    emb = params["tok_emb"]["embedding"]
    x = emb.astype(spec.dtype)[tokens]
    if spec.family == "gpt":
        pos_tab = params["pos_emb"]["embedding"].astype(spec.dtype)
        x = x + pos_tab[jnp.clip(positions, 0, pos_tab.shape[0] - 1)]
    x = x.astype(spec.dtype)
    stacked = params["layers"]["layer"]

    def body(carry, layer_in):
        lp, kc, vc = layer_in
        y, kc, vc = _block(spec, lp, carry, positions, num_valid,
                           page_table, kc, vc)
        return y, (kc, vc)

    x, (k_pages, v_pages) = lax.scan(body, x, (stacked, k_pages, v_pages))
    if spec.family == "gpt":
        x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
        logits = jnp.einsum("bth,vh->btv", x, emb.astype(spec.dtype))
    else:
        x = _rmsnorm(x, params["rms_f"]["scale"])
        logits = _dense_general(x, params["lm_head"]["kernel"],
                                dtype=spec.dtype)
    return logits, k_pages, v_pages


def speculative_accept(logits, draft):
    """Fused greedy accept/reject of one speculation burst (ISSUE 18).

    ``logits [B, k+1, vocab]`` are the TARGET model's verify logits at
    positions ``C .. C+k`` (the pending token plus the k drafted
    tokens); ``draft [B, k]`` the draft model's proposals.  Greedy-only:
    the target's token at position ``C+j`` is ``t_j = argmax`` — the
    bitwise-identical twin of the non-speculative decode step's
    ``sample_tokens`` at temperature 0.

    Acceptance is CAPPED at ``k - 1`` drafted tokens, with the bonus
    token always emitted: ``acc = min(longest matching prefix, k-1)``,
    ``emitted = d_1 .. d_acc, t_acc``.  The cap costs nothing (when all
    k drafts match, the bonus ``t_{k-1}`` IS ``d_k``, so the emitted
    stream is identical) and buys the cache invariant the schedule
    rides on: after committing ``acc + 1`` tokens both KV pools are
    filled exactly to the new length — the draft pool wrote positions
    ``C .. C+k-1`` and ``acc + 1 <= k`` always, so no catch-up program
    of a second shape ever exists.  Rejected positions hold garbage at
    ``>= new length``; the next burst overwrites them before the causal
    mask can see them, so rollback is pure page-table arithmetic (no
    zeroing).

    Returns ``(emitted [B, k] int32, acc [B] int32)``: row i's burst is
    ``emitted[i, :acc[i] + 1]``; tail entries are -1.
    """
    k = draft.shape[1]
    tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)        # [B, k+1]
    match = (draft == tgt[:, :-1]).astype(jnp.int32)           # [B, k]
    n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)        # [B]
    acc = jnp.minimum(n_acc, k - 1).astype(jnp.int32)
    bonus = jnp.take_along_axis(tgt, acc[:, None], axis=1)     # [B, 1]
    idx = jnp.arange(k, dtype=jnp.int32)[None, :]
    emitted = jnp.where(idx < acc[:, None], draft,
                        jnp.where(idx == acc[:, None], bonus, -1))
    return emitted.astype(jnp.int32), acc


def sample_tokens(logits, temps, rids, gen_pos, seed: int):
    """Greedy (temp <= 0) or temperature sampling of one token per row.

    The PRNG key is derived ONLY from (seed, request id, absolute
    position of the token being generated) — independent of decode-slot
    index and batch composition, so batched continuous decoding samples
    the identical token stream a single-sequence decode would."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(rid, pos, lg, t):
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(seed), rid), pos)
        return jax.random.categorical(
            key, lg.astype(jnp.float32) / jnp.maximum(t, 1e-6))

    sampled = jax.vmap(one)(rids, gen_pos, logits, temps).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)
