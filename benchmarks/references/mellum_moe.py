"""Plain float32 reference ``mellum_moe``: a decoder whose every block is
window-or-full attention and routed SwiGLU experts (configuration files
with ``"reference": "mellum_moe"``; the four functions ``lib/check.py`` and
``entries/train_global.py`` call are ``arch_of``, ``init_params``,
``train_steps`` and ``train_flops_per_token``).

Straightforward ``jax.numpy``: no kernels, no cache, no sort, no sharding,
nothing imported from the program.  The equations (ISSUE 26; every
assumption is in the configuration file's ``assumed``):

- block: ``x = x + Attn_t(RMSNorm(x))``, ``x = x + MoE(RMSNorm(x))``, ``t``
  the layer's entry in ``layer_types``; final RMSNorm, untied head over the
  rows held; mean cross-entropy over the positions with label >= 0;
- attention: 32 query heads and 4 key-value heads of width 128 (query head
  h reads key-value head h // 8), rotary on q and k (rotate-half), scores
  ``q k^T / sqrt(128)`` under an EXPLICIT mask (``j <= i``, and ``i - window
  < j`` on a sliding layer), computed a block of queries at a time so that
  8,192 positions fit beside the float32 state;
- rotary: ``inv_freq_n = theta^(-2n/d)``; the full layers blend each
  frequency with itself over ``factor`` by YaRN's ramp and scale cos and sin
  by ``attention_factor``;
- experts: softmax over ALL the router's outputs in float32, the top k,
  their weights over their sum; the sum over the chosen experts THAT ARE
  HELD, as a loop over the held experts with a mask (every held expert is
  applied to every token and weighted by 0 where it was not chosen).  What
  the experts held elsewhere would have added is left out.

Weights come from the seed alone, by flax's published rule
(``transformer_lm.py`` has the derivation; here the scanned unit is a
period of four layers, so a leaf's path is ``layers/layer_<i>/...`` and
its key is split off the root once a period).
``benchmarks/tests/test_reference.py`` holds this init to the program's.

``precision``: ``"float32"`` is the reference; ``"fp8"`` / ``"int8"`` round
both operands of every product per tensor with an absmax scale, float32
accumulation, straight-through gradients: the controls.
"""

from __future__ import annotations

import hashlib
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.lib import moe_flops

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
INIT_STD = 0.02
NEG_INF = -1e30
QUERY_BLOCK = 1024      # queries a block of the explicit-mask attention
LOSS_BLOCK = 1024       # positions a block of the head and its loss
KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


# ----------------------------------------------------------------------
# architecture, from the configuration file's published keys
# ----------------------------------------------------------------------

def arch_of(config: dict) -> dict:
    layers = config["num_hidden_layers"]
    first, count = config["experts_held"]
    if count != config["num_experts"]:
        raise ValueError("num_experts is the count held here and has to "
                         "agree with experts_held")
    return dict(
        family=config["family"], layers=layers,
        layer_types=tuple(KINDS[t] for t in config["layer_types"][:layers]),
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window"], vocab=config["vocab_size"],
        experts=config["router_width"], held=(first, count),
        top_k=config["num_experts_per_tok"],
        ffn=config["moe_intermediate_size"], eps=config["rms_norm_eps"],
        rope=tuple(sorted(
            (KINDS[kind], _rope_tuple(p))
            for kind, p in config["rope_parameters"].items())))


def _rope_tuple(p: dict) -> tuple:
    if p["rope_type"] == "default":
        return (float(p["rope_theta"]),)
    if p["rope_type"] != "yarn":
        raise ValueError(f"no reference for rope_type {p['rope_type']!r}")
    return (float(p["rope_theta"]), float(p["factor"]),
            int(p["original_max_position_embeddings"]),
            float(p["beta_fast"]), float(p["beta_slow"]),
            float(p["attention_factor"]))


def train_flops_per_token(config: dict, traffic: dict) -> float:
    return moe_flops.train_flops_per_token(arch_of(config),
                                           int(traffic["seq_len"]))


# ----------------------------------------------------------------------
# weights from the seed
# ----------------------------------------------------------------------

def _fold(key, *path):
    """flax's static fold-in: sha1 over the scope path and the draw counter,
    first four bytes, folded into the key."""
    m = hashlib.sha1()
    for x in path:
        if isinstance(x, str):
            m.update(x.encode("utf-8"))
        else:
            m.update(int(x).to_bytes((int(x).bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def _normal(key, shape, std=INIT_STD):
    return jax.random.normal(key, shape, jnp.float32) * std


# (scope under a layer, leaf, draw counter, shape from the arch): flax
# numbers a scope's draws from 1, and the scanned period's body is traced
# twice at init, so a scope's n-th draw carries n + (draws in the scope)
def _layer_leaves(a: dict) -> list:
    h, hd, f, held = a["hidden"], a["head_dim"], a["ffn"], a["held"][1]
    return [
        (("attn", "q"), "kernel", 2, (h, a["heads"], hd)),
        (("attn", "kv"), "kernel", 2, (h, 2, a["kv_heads"], hd)),
        (("attn", "out"), "kernel", 2, (a["heads"], hd, h)),
        (("moe", "gate"), "kernel", 2, (h, a["experts"])),
        (("moe",), "w1", 4, (held, h, f)),
        (("moe",), "w3", 5, (held, h, f)),
        (("moe",), "w2", 6, (held, f, h)),
    ]


def init_params(config: dict, seed: int) -> dict:
    """The model's parameters from ``seed``, as a nested dict with the
    program's leaf paths: the periods stacked on the leading axis of every
    leaf under ``layers``."""
    a = arch_of(config)
    period = _period_of(a["layer_types"])
    periods = a["layers"] // period
    root = jax.random.key(seed)
    keys = jax.random.split(root, periods)
    layers: dict = {}
    for i in range(period):
        layer: dict = {"rms1": {"scale": jnp.ones((periods, a["hidden"]))},
                       "rms2": {"scale": jnp.ones((periods, a["hidden"]))}}
        for scope, leaf, counter, shape in _layer_leaves(a):
            value = jax.vmap(lambda k: _normal(
                _fold(k, "layers", f"layer_{i}", *scope, counter), shape))(
                    keys)
            node = layer
            for s in scope:
                node = node.setdefault(s, {})
            node[leaf] = value
        layers[f"layer_{i}"] = layer
    return {
        "tok_emb": {"embedding": _normal(
            _fold(root, "tok_emb", 1), (a["vocab"], a["hidden"]),
            config["recipe"]["embed_init_std"])},
        "layers": layers,
        "rms_f": {"scale": jnp.ones((a["hidden"],))},
        "lm_head": {"kernel": _normal(_fold(root, "lm_head", 1),
                                      (a["hidden"], a["vocab"]))},
    }


def _period_of(kinds: tuple) -> int:
    """Length of the shortest prefix that, repeated, gives ``kinds``."""
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return p
    return len(kinds)


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------

def _fake_quant(x, precision: str):
    """Round ``x`` per tensor to the control's number format; identity
    gradient (the straight-through estimator)."""
    if precision == "float32":
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision == "fp8":
        s = 448.0 / amax                      # float8_e4m3fn's largest finite
        q = (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s
    elif precision == "int8":
        s = 127.0 / amax
        q = jnp.round(x * s) / s
    elif precision == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + lax.stop_gradient(q - x)


def _mm(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _fake_quant(a, precision),
                      _fake_quant(b, precision),
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope_table(head_dim: int, rope: tuple, length: int):
    """(cos, sin) [length, head_dim / 2] of one kind of layer."""
    n = np.arange(head_dim // 2, dtype=np.float64)
    inv = rope[0] ** (-2.0 * n / head_dim)
    scale = 1.0
    if len(rope) > 1:
        theta, factor, original, beta_fast, beta_slow, scale = rope
        d = lambda b: (head_dim * math.log(original / (2 * math.pi * b))
                       / (2 * math.log(theta)))
        low = max(math.floor(d(beta_fast)), 0)
        high = min(math.ceil(d(beta_slow)), head_dim - 1)
        ramp = np.clip((n - low) / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1 - ramp)
    ang = np.arange(length, dtype=np.float64)[:, None] * inv[None]
    return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
            jnp.asarray(np.sin(ang) * scale, jnp.float32))


def _rotate(x, cos, sin):
    """x [B, L, heads, d], rotate-half."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(x, p, a: dict, kind: str, precision: str):
    b, l, _ = x.shape
    q = _mm("bld,dhk->blhk", x, p["q"]["kernel"], precision)
    kv = _mm("bld,dthk->blthk", x, p["kv"]["kernel"], precision)
    cos, sin = rope_table(a["head_dim"], dict(a["rope"])[kind], l)
    q = _rotate(q, cos, sin)
    k, v = _rotate(kv[:, :, 0], cos, sin), kv[:, :, 1]
    rep = a["heads"] // a["kv_heads"]
    bq = QUERY_BLOCK if l % QUERY_BLOCK == 0 else l
    window = a["window"] if kind == "sliding" else None

    # a block of queries against every key under the explicit mask; each
    # block is recomputed on the way back, so one block's scores are live
    @jax.checkpoint
    def block(args):
        qb, first = args                              # [B, bq, heads, d]
        qg = qb.reshape(b, bq, a["kv_heads"], rep, a["head_dim"])
        s = _mm("bqgrk,bmgk->bgrqm", qg, k, precision) / math.sqrt(
            a["head_dim"])
        i = first + jnp.arange(bq)[:, None]
        j = jnp.arange(l)[None, :]
        keep = j <= i
        if window is not None:
            keep &= j > i - window
        w = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
        o = _mm("bgrqm,bmgk->bqgrk", w, v, precision)
        return o.reshape(b, bq, a["heads"], a["head_dim"])

    qs = q.reshape(b, l // bq, bq, a["heads"], a["head_dim"]).swapaxes(0, 1)
    o = lax.map(block, (qs, jnp.arange(l // bq) * bq))
    o = o.swapaxes(0, 1).reshape(b, l, a["heads"], a["head_dim"])
    return _mm("blhk,hkd->bld", o, p["out"]["kernel"], precision)


def _experts(x, p, a: dict, precision: str):
    b, l, h = x.shape
    toks = x.reshape(b * l, h)
    probs = jax.nn.softmax(
        jnp.einsum("nd,de->ne", toks, p["gate"]["kernel"],
                   precision=lax.Precision.HIGHEST), axis=-1)
    top, idx = lax.top_k(probs, a["top_k"])
    top = top / top.sum(-1, keepdims=True)
    first, count = a["held"]

    # every held expert on every token, weighted by 0 where not chosen
    @jax.checkpoint
    def one(acc, args):
        e, w1, w3, w2 = args
        weight = jnp.where(idx == e, top, 0.0).sum(-1)
        hidden = jax.nn.silu(_mm("nd,df->nf", toks, w1, precision)) \
            * _mm("nd,df->nf", toks, w3, precision)
        return acc + weight[:, None] * _mm("nf,fd->nd", hidden, w2,
                                           precision), None

    acc, _ = lax.scan(one, jnp.zeros_like(toks),
                      (first + jnp.arange(count), p["w1"], p["w3"], p["w2"]))
    return acc.reshape(b, l, h)


def hidden_fn(a: dict, params: dict, ids, precision: str = "float32"):
    """Token ids [B, L] -> the final norm's output [B, L, hidden]."""
    x = params["tok_emb"]["embedding"][ids]
    period = _period_of(a["layer_types"])

    def one_period(x, p):
        for i in range(period):
            # one layer at a time, recomputed on the way back
            @jax.checkpoint
            def layer(x, lp, kind=a["layer_types"][i]):
                x = x + _attention(
                    _rms_norm(x, lp["rms1"]["scale"], a["eps"]), lp["attn"],
                    a, kind, precision)
                return x + _experts(
                    _rms_norm(x, lp["rms2"]["scale"], a["eps"]), lp["moe"],
                    a, precision)
            x = layer(x, p[f"layer_{i}"])
        return x, None

    x, _ = lax.scan(one_period, x, params["layers"])
    return _rms_norm(x, params["rms_f"]["scale"], a["eps"])


def logits_fn(a: dict, params: dict, ids, precision: str = "float32"):
    """Token ids [B, L] -> logits [B, L, rows held] (tests, small sizes)."""
    return _mm("bld,dv->blv", hidden_fn(a, params, ids, precision),
               params["lm_head"]["kernel"], precision)


def loss_fn(a: dict, params: dict, ids, labels, precision: str = "float32",
            weights=None):
    """Mean cross-entropy over the positions whose label is >= 0, the head
    and its log-softmax a block of positions at a time.  ``weights`` ([B,
    L] 0/1) restricts the mean further: the planted half-batch fault."""
    x = hidden_fn(a, params, ids, precision)
    b, l, h = x.shape
    w = (labels >= 0).astype(jnp.float32)
    if weights is not None:
        w = w * weights
    blk = LOSS_BLOCK if l % LOSS_BLOCK == 0 else l

    @jax.checkpoint
    def block(args):
        xb, yb, wb = args
        logz = jax.nn.log_softmax(
            _mm("bld,dv->blv", xb, params["lm_head"]["kernel"], precision),
            axis=-1)
        ce = -jnp.take_along_axis(logz, jnp.maximum(yb, 0)[..., None],
                                  axis=-1)[..., 0]
        return (ce * wb).sum()

    cut = lambda t: t.reshape(b, l // blk, blk, *t.shape[2:]).swapaxes(0, 1)
    total = lax.map(block, (cut(x), cut(labels), cut(w))).sum()
    return total / jnp.maximum(w.sum(), 1.0)


def adam_step(params, grads, mu, nu, count, lr: float):
    """optax.scale_by_adam + ``p - lr * update``, written out."""
    count = count + 1
    mu = jax.tree_util.tree_map(
        lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    c1 = 1 - ADAM_B1 ** count
    c2 = 1 - ADAM_B2 ** count
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        params, mu, nu)
    return params, mu, nu, count


def _fault_weights(ids, half_batch: bool):
    """The half-batch fault's 0/1 weights: the second half of the rows, or
    of the positions where a step is one row."""
    if not half_batch:
        return None
    b, l = ids.shape
    if b > 1:
        return jnp.broadcast_to((jnp.arange(b) < b // 2)[:, None],
                                (b, l)).astype(jnp.float32)
    return jnp.broadcast_to((jnp.arange(l) < l // 2)[None],
                            (b, l)).astype(jnp.float32)


@partial(jax.jit, static_argnames=("arch_items", "precision", "lr",
                                   "half_batch"))
def _first_step(params, ids, labels, *, arch_items, precision, lr,
                half_batch):
    a = dict(arch_items)
    loss, g = jax.value_and_grad(
        lambda q: loss_fn(a, q, ids, labels, precision,
                          _fault_weights(ids, half_batch)))(params)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return loss, g, adam_step(params, g, zeros, zeros,
                              jnp.zeros((), jnp.float32), lr)


@partial(jax.jit, static_argnames=("arch_items", "precision", "lr",
                                   "half_batch"), donate_argnums=(0,))
def _later_step(carry, ids, labels, *, arch_items, precision, lr,
                half_batch):
    a = dict(arch_items)
    p, mu, nu, count = carry
    loss, g = jax.value_and_grad(
        lambda q: loss_fn(a, q, ids, labels, precision,
                          _fault_weights(ids, half_batch)))(p)
    return loss, adam_step(p, g, mu, nu, count, lr)


def train_steps(config: dict, params, ids, labels, *, lr: float,
                precision: str = "float32", half_batch: bool = False):
    """Drive the reference through ``ids.shape[0]`` optimizer steps.

    ``ids``, ``labels``: [steps, B, L].  Returns (losses [steps], the first
    step's gradient tree, the parameters after the last step).  A step is
    a program of its own and the first gradient waits on the host while
    the later steps run: at the published widths the state (parameters,
    Adam's two moments, a gradient) is 9.5 GB of the chip's 16, and what
    the caller still holds comes on top."""
    kw = dict(arch_items=tuple(sorted(arch_of(config).items())),
              precision=precision, lr=float(lr), half_batch=half_batch)
    ids, labels = jnp.asarray(ids), jnp.asarray(labels)
    loss, g1, carry = _first_step(params, ids[0], labels[0], **kw)
    losses = [loss]
    device = next(iter(jax.tree_util.tree_leaves(g1)[0].devices()))
    g1 = jax.device_get(g1)
    for s in range(1, ids.shape[0]):
        loss, carry = _later_step(carry, ids[s], labels[s], **kw)
        losses.append(loss)
    params_after = carry[0]
    del carry
    return jnp.stack(losses), jax.device_put(g1, device), params_after
