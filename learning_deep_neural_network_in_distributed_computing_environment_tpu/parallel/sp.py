"""Sequence/context parallelism: ring attention over an ICI ring.

Long-context capability: the sequence dimension is sharded over a mesh axis;
each device holds a [B, L/n, H, D] block of Q, K, V.  K/V blocks rotate
around the ring with ``lax.ppermute`` while each device accumulates its
queries' attention over every block using the online-softmax (running max /
running denominator) recurrence — numerically identical to full dense
softmax attention, with O(L/n) memory per device and ICI-bandwidth overlap.

This is the same ``ppermute``-ring building block the reference's gossip
topology maps to (SURVEY.md 2.3 note) applied to attention, per the ring
attention construction of Liu et al.; no reference equivalent exists (the
reference has no sequence models).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import NEG_INF, causal_mask


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = False) -> jnp.ndarray:
    """Blockwise ring attention (bidirectional or causal).

    Args: q, k, v [B, Lc, H, D] — the local sequence chunk on each device of
    the ``axis_name`` ring.  Returns the local chunk of the attention output,
    exactly equal to dense attention over the gathered sequence.

    ``causal=True``: at rotation step t this device holds the K/V chunk
    that started on device ``(idx - t) mod n``, so global key positions are
    ``src*Lc + j`` against query positions ``idx*Lc + i`` — future chunks
    mask to -1e30 and contribute exp(-1e30 - m) = 0.  The running max is
    real from step 0 on (t=0 is the diagonal chunk: every query attends at
    least itself).  All n rotations still run (lock-step SPMD); the
    zig-zag block reordering that halves causal ring latency is a later
    optimization.
    """
    n = lax.axis_size(axis_name)
    b, lc, h, d = q.shape
    # K/V may carry fewer heads (grouped-query attention): scores/outputs
    # use grouped einsums, and — the point of GQA here — the K/V blocks
    # that rotate around the ring are ``rep``x smaller, cutting the ICI
    # traffic per rotation by the group factor.
    from ..ops.attention import kv_group_size
    rep = kv_group_size(q, k)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    # matmul inputs stay in the model dtype (bf16 on TPU: full MXU rate)
    # with f32 accumulation via preferred_element_type; only the softmax
    # state is f32
    qf = q if rep == 1 else q.reshape(b, lc, h // rep, rep, d)
    idx = lax.axis_index(axis_name)

    def block(kb, vb, t):
        """Scores of local queries against one K/V block (fp32)."""
        if rep == 1:
            s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb,
                           preferred_element_type=jnp.float32) * scale
        else:
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, kb,
                           preferred_element_type=jnp.float32) * scale
            s = s.reshape(b, h, lc, kb.shape[1])
        if causal:
            src = (idx - t) % n                     # chunk's home device
            cm = causal_mask(lc, lc, q_offset=idx * lc, k_offset=src * lc)
            s = jnp.where(cm[None, None], s, NEG_INF)
        return s, vb

    # online-softmax accumulators.  Under shard_map the scan carry must have
    # a consistent varying-axes type: the body derives these from q/k (which
    # vary over the seq axis — and over any other manual axis the caller's
    # shard_map carries, e.g. data), so the zero initializers must be cast
    # to q's exact varying-axis set or tracing rejects the carry (found by
    # running: round-1 shipped this unexecuted and it failed on first use).
    vma = set(jax.typeof(qf).vma) | {axis_name}
    vary = lambda x: lax.pcast(x, tuple(sorted(vma)), to="varying")
    o = vary(jnp.zeros((b, h, lc, d), jnp.float32))       # weighted-value accum
    m = vary(jnp.full((b, h, lc), -jnp.inf, jnp.float32))  # running max
    l = vary(jnp.zeros((b, h, lc), jnp.float32))           # running denominator

    def body(carry, t):
        kb, vb, o, m, l = carry
        s, vb_ = block(kb, vb, t)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(axis=-1)
        if rep == 1:
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vb_.dtype), vb_,
                            preferred_element_type=jnp.float32)
        else:
            lk = vb_.shape[1]
            pv = jnp.einsum("bgrqk,bkgd->bgrqd",
                            p.astype(vb_.dtype).reshape(
                                b, h // rep, rep, lc, lk),
                            vb_,
                            preferred_element_type=jnp.float32
                            ).reshape(b, h, lc, d)
        o = o * corr[..., None] + pv
        # rotate K/V to the next ring position
        perm = [(i, (i + 1) % n) for i in range(n)]
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (kb, vb, o, m_new, l), None

    (kb, vb, o, m, l), _ = lax.scan(body, (k, v, o, m, l), jnp.arange(n))
    out = (o / l[..., None]).astype(q.dtype)         # [B, H, Lc, D]
    return jnp.transpose(out, (0, 2, 1, 3))          # -> [B, Lc, H, D]


def ring_attention_zigzag(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          axis_name: str) -> jnp.ndarray:
    """Causal ring attention with ZIG-ZAG half-chunk balancing.

    Plain causal ring computes every rotation's full [Lc, Lc] score block
    and masks it: at rotation t the t devices holding fully-future K/V do
    pure throwaway work, so HALF of all block matmuls are wasted and the
    per-step critical path is set by the busiest device.  Zig-zag
    (the Llama-3 / ring-flash-attention assignment) splits the sequence
    into 2n half-chunks and gives device i halves (i, 2n-1-i); then at
    EVERY rotation EVERY device has exactly 2 of its 4 (q-half, kv-half)
    sub-blocks causally live (1 full + 2 diagonal at t=0) — balanced, and
    the dead sub-blocks are skipped with ``lax.cond`` so their matmuls
    never execute: ~2x less attention compute at the same exactness.

    Inputs/outputs are in the engine's CONTIGUOUS layout (device i holds
    ``[i*Lc, (i+1)*Lc)``, RoPE already applied with global positions);
    the zig-zag redistribution and its inverse are internal ppermutes.
    Requires an even per-device chunk length.
    """
    n = lax.axis_size(axis_name)
    b, lc, h, d = q.shape
    if lc % 2:
        raise ValueError(f"zig-zag ring needs an even per-device chunk "
                         f"length, got {lc}")
    from ..ops.attention import kv_group_size
    rep = kv_group_size(q, k)
    half = lc // 2
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    idx = lax.axis_index(axis_name)

    def t_of(hh):  # home device of global half-chunk hh under zig-zag
        return hh if hh < n else 2 * n - 1 - hh

    perm1 = [(j, t_of(2 * j)) for j in range(n)]        # even global halves
    perm2 = [(j, t_of(2 * j + 1)) for j in range(n)]    # odd global halves
    inv1 = [(t_of(2 * j), j) for j in range(n)]
    inv2 = [(t_of(2 * j + 1), j) for j in range(n)]
    even = (idx % 2 == 0)

    def to_zigzag(x):
        """[B, Lc, ...] contiguous -> (slotA, slotB) with global half ids
        (idx, 2n-1-idx)."""
        r1 = lax.ppermute(x[:, :half], axis_name, perm1)
        r2 = lax.ppermute(x[:, half:], axis_name, perm2)
        a = jnp.where(even, r1, r2)
        bslot = jnp.where(even, r2, r1)
        return a, bslot

    def from_zigzag(a, bslot):
        """(slotA, slotB) -> [B, Lc, ...] contiguous."""
        evn = jnp.where(even, a, bslot)   # this device's even global half
        odd = jnp.where(even, bslot, a)
        first = lax.ppermute(evn, axis_name, inv1)
        second = lax.ppermute(odd, axis_name, inv2)
        return jnp.concatenate([first, second], axis=1)

    qa, qb = to_zigzag(q)
    ka, kb_ = to_zigzag(k)
    va, vb_ = to_zigzag(v)
    if rep > 1:
        qa = qa.reshape(b, half, h // rep, rep, d)
        qb = qb.reshape(b, half, h // rep, rep, d)

    def update(qh, kh, vh, m, l, acc, gq, gk):
        """Online-softmax update of one (q-half, kv-half) sub-block with
        causal masking by global half ids; matmuls stay in model dtype."""
        if rep == 1:
            s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh,
                           preferred_element_type=jnp.float32) * scale
        else:
            s = jnp.einsum("bqgrd,bkgd->bgrqk", qh, kh,
                           preferred_element_type=jnp.float32) * scale
            s = s.reshape(b, h, half, half)
        qpos = gq * half + jax.lax.broadcasted_iota(jnp.int32,
                                                    (half, half), 0)
        kpos = gk * half + jax.lax.broadcasted_iota(jnp.int32,
                                                    (half, half), 1)
        s = jnp.where((kpos <= qpos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * corr + p.sum(axis=-1)
        if rep == 1:
            pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vh.dtype), vh,
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bgrqk,bkgd->bgrqd",
                            p.astype(vh.dtype).reshape(
                                b, h // rep, rep, half, half),
                            vh, preferred_element_type=jnp.float32
                            ).reshape(b, h, half, d)
        return m_new, l, acc * corr[..., None] + pv

    def maybe(qh, kh, vh, state, gq, gk):
        """Run ``update`` only when the sub-block is causally live —
        ``lax.cond`` with a device-varying predicate skips the dead
        matmuls entirely (both branches are collective-free)."""
        return lax.cond(
            gk <= gq,
            lambda s: update(qh, kh, vh, *s, gq, gk),
            lambda s: s,
            state)

    vma = tuple(sorted(set(jax.typeof(q).vma) | {axis_name}))
    vary = lambda x: lax.pcast(x, vma, to="varying")
    zero_state = lambda: (
        vary(jnp.full((b, h, half), -jnp.inf, jnp.float32)),
        vary(jnp.zeros((b, h, half), jnp.float32)),
        vary(jnp.zeros((b, h, half, d), jnp.float32)))
    ga, gb = idx, 2 * n - 1 - idx

    def body(carry, t):
        ka, kb_, va, vb_, sA, sB = carry
        src = (idx - t) % n
        gka, gkb = src, 2 * n - 1 - src
        for kh, vh, gk in ((ka, va, gka), (kb_, vb_, gkb)):
            sA = maybe(qa, kh, vh, sA, ga, gk)
            sB = maybe(qb, kh, vh, sB, gb, gk)
        perm = [(i, (i + 1) % n) for i in range(n)]
        ka, kb_, va, vb_ = (lax.ppermute(x, axis_name, perm)
                            for x in (ka, kb_, va, vb_))
        return (ka, kb_, va, vb_, sA, sB), None

    (ka, kb_, va, vb_, (mA, lA, accA), (mB, lB, accB)), _ = lax.scan(
        body, (ka, kb_, va, vb_, zero_state(), zero_state()),
        jnp.arange(n))
    outA = jnp.transpose((accA / lA[..., None]).astype(q.dtype),
                         (0, 2, 1, 3))                  # [B, half, H, D]
    outB = jnp.transpose((accB / lB[..., None]).astype(q.dtype),
                         (0, 2, 1, 3))
    return from_zigzag(outA, outB)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      axis_name: str, causal: bool = False) -> jnp.ndarray:
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

    Two ``lax.all_to_all``s trade the sequence sharding for a head sharding:
    each device gathers the FULL sequence for ``H/n`` of the heads, runs
    ordinary dense attention on them (causal masking applies directly —
    positions are global after the gather), and scatters back to sequence
    shards.  Exact (no online-softmax recurrence); needs ``H % n == 0``;
    moves 2x the activation bytes of ring attention but in two large dense
    collectives that XLA overlaps well on ICI.
    """
    n = lax.axis_size(axis_name)
    b, lc, h, d = q.shape
    kv = k.shape[2]
    if h % n or kv % n:
        raise ValueError(
            f"ulysses attention needs query heads ({h}) and kv heads ({kv}) "
            f"divisible by the seq-axis size ({n}); use ring attention "
            "otherwise")
    from ..ops.attention import dot_product_attention

    def to_heads(x):   # [B, Lc, H, D] -> [B, L, H/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    out = dot_product_attention(to_heads(q), to_heads(k), to_heads(v),
                                causal=causal)
    # [B, L, H/n, D] -> [B, Lc, H, D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)
