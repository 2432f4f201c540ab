"""Attention ops: one entry point, three implementations.

``attend(q, k, v, impl=..., causal=...)`` with tensors in
[batch, seq, heads, head_dim]:

- ``dense``: reference XLA dot-product attention (fp32 softmax);
- ``flash``: Pallas blockwise-softmax kernel (``ops.pallas_ops``), falling
  back to dense where Pallas TPU lowering is unavailable;
- ``ring``:  ring attention over a sequence-sharded mesh axis
  (``parallel.sp``) — each device holds a sequence block and K/V blocks
  rotate around the ICI ring with online-softmax accumulation;
- ``all_to_all``: Ulysses-style sequence parallelism (``parallel.sp``).

``causal=True`` gives autoregressive (decoder) masking in every impl:
dense masks the score matrix, flash skips fully-future blocks in-kernel,
ring masks per rotation step by source-chunk position.

The reference has no attention at all (its model is a CNN; SURVEY.md 2.3) —
this subsystem is the long-context capability required of the framework.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rope_frequencies(head_dim: int, theta: float,
                     yarn: Optional[tuple] = None):
    """``(inv_freq [head_dim / 2] f32, scale)`` of a rotary embedding.

    Plain: ``inv_freq_n = theta ** (-2n / head_dim)``, scale 1.  ``yarn``
    = ``(factor, original_max_position, beta_fast, beta_slow,
    attention_factor)`` blends each frequency between itself and itself
    over ``factor`` (YaRN's "NTK by parts"): dimensions that turn more than
    ``beta_fast`` times over the original context keep their frequency,
    those that turn fewer than ``beta_slow`` times are interpolated, with a
    linear ramp between; cos and sin are then multiplied by
    ``attention_factor``."""
    import math
    import numpy as np
    half = head_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if yarn is None:
        return inv, 1.0
    factor, original, beta_fast, beta_slow, attention_factor = yarn
    turns_at = lambda b: (head_dim * math.log(original / (2 * math.pi * b))
                          / (2 * math.log(theta)))
    low = max(math.floor(turns_at(beta_fast)), 0)
    high = min(math.ceil(turns_at(beta_slow)), head_dim - 1)
    ramp = jnp.asarray(np.clip((np.arange(half) - low)
                               / max(high - low, 1e-3), 0, 1), jnp.float32)
    return inv / factor * ramp + inv * (1 - ramp), float(attention_factor)


def rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float = 10000.0,
         yarn: Optional[tuple] = None, interleaved: bool = False):
    """Rotary position embedding, rotate-half convention; ``interleaved``:
    on the pairs ``(x_2n, x_2n+1)`` instead of ``(x_n, x_n+half)``.

    ``x`` [B, L, H, Dh], ``pos`` [L] absolute token positions.  Angles are
    computed in f32 (bf16 positions lose integer precision past 256) and
    the result is cast back to ``x.dtype``.  Used by the Llama recipe
    (``models/llama.py``) via ``models.bert.SelfAttention(rope_theta=...)``;
    ``yarn`` as in ``rope_frequencies``.
    """
    half = x.shape[-1] // 2
    freqs, scale = rope_frequencies(x.shape[-1], theta, yarn)       # [Dh/2]
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]         # [L, Dh/2]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    if interleaved:
        pairs = x.reshape(*x.shape[:-1], half, 2).astype(jnp.float32)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def causal_mask(lq: int, lk: int, q_offset: int = 0, k_offset: int = 0,
                window: Optional[int] = None):
    """[lq, lk] bool mask: query at global position q_offset+i may attend
    key positions <= it, and with ``window`` only the last ``window`` of
    them, its own included."""
    qpos = q_offset + jnp.arange(lq)[:, None]
    kpos = k_offset + jnp.arange(lk)[None, :]
    if window is None:
        return kpos <= qpos
    return (kpos <= qpos) & (kpos > qpos - window)


def kv_group_size(q: jnp.ndarray, k: jnp.ndarray) -> int:
    """Queries per K/V head (1 = MHA).  K/V may carry FEWER heads than Q
    (grouped-query attention): every impl consumes the grouped [B, L, KV, D]
    K/V directly — the repeat-to-full-heads expansion that would forfeit
    GQA's K/V bandwidth saving never happens."""
    h, kv = q.shape[2], k.shape[2]
    if h % kv:
        raise ValueError(f"query heads ({h}) not divisible by kv heads "
                         f"({kv})")
    return h // kv


def dot_product_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          mask: Optional[jnp.ndarray] = None,
                          causal: bool = False,
                          window: Optional[int] = None) -> jnp.ndarray:
    """[B, Lq, H, D] x [B, Lk, KV, D] -> [B, Lq, H, Dv]; softmax in fp32.
    ``v`` may be of another width than ``q`` and ``k`` (latent attention):
    the scale is the scores' width, the output has the values'.

    KV == H is plain multi-head attention; KV < H (divisible) is
    grouped-query attention, computed with grouped einsums so the K/V
    operands are never expanded to the full head count."""
    d = q.shape[-1]
    rep = kv_group_size(q, k)
    scale = jnp.sqrt(jnp.asarray(d, jnp.float32))
    if rep == 1:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) / scale
    else:
        b, lq, h = q.shape[:3]
        # head h <-> (group g = h // rep, member r = h % rep) — the same
        # convention as repeat(k, rep, axis=2) would produce
        qg = q.reshape(b, lq, h // rep, rep, d)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k,
                       preferred_element_type=jnp.float32) / scale
        s = s.reshape(b, h, lq, k.shape[1])
    if causal:
        cm = causal_mask(q.shape[1], k.shape[1], window=window)
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    if rep == 1:
        return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
    b, h, lq, lk = w.shape
    wg = w.astype(v.dtype).reshape(b, h // rep, rep, lq, lk)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", wg, v)
    return out.reshape(b, lq, h, v.shape[-1])


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
           mask: Optional[jnp.ndarray] = None, impl: str = "dense",
           axis_name: Optional[str] = None,
           causal: bool = False,
           window: Optional[int] = None) -> jnp.ndarray:
    """``window`` (with ``causal``): a sliding window, query i attends keys
    ``i - window < j <= i``; dense and flash take it, and values of
    another width than the scores'."""
    if impl == "dense":
        return dot_product_attention(q, k, v, mask, causal=causal,
                                     window=window)
    if impl == "flash":
        from .pallas_ops import flash_attention
        return flash_attention(q, k, v, mask, causal=causal, window=window)
    if impl in ("ring", "ring_zigzag", "all_to_all"):
        if window is not None:
            raise NotImplementedError(
                f"{impl} attention has no sliding window; use dense or "
                "flash")
        if v.shape[-1] != q.shape[-1]:
            raise NotImplementedError(
                f"{impl} attention has one width for q, k and v; use dense "
                "or flash for latent attention")
        if axis_name is None:
            raise ValueError(f"{impl} attention requires axis_name (the mesh "
                             "axis the sequence is sharded over)")
        if mask is not None:
            raise NotImplementedError(
                f"{impl} attention supports full bidirectional or causal "
                "attention (mask=None); arbitrary masks are not sharded")
        if impl == "ring_zigzag":
            if not causal:
                raise ValueError(
                    "ring_zigzag exists to balance CAUSAL masking work; "
                    "bidirectional attention has no dead blocks — use "
                    "impl='ring'")
            from ..parallel.sp import ring_attention_zigzag
            return ring_attention_zigzag(q, k, v, axis_name)
        if impl == "ring":
            from ..parallel.sp import ring_attention
            return ring_attention(q, k, v, axis_name, causal=causal)
        from ..parallel.sp import ulysses_attention
        return ulysses_attention(q, k, v, axis_name, causal=causal)
    raise ValueError(f"unknown attention impl {impl!r}")
