"""Plain float32 reference ``transformer_lm``: the two model families the
benchmark trains so far, GPT-2 and BERT.  A configuration file names its
reference (``"reference": "transformer_lm"``) and ``lib/check.py`` finds
the module by that name, so another family brings a module of its own with
the same four functions: ``arch_of``, ``init_params``, ``train_steps`` and
``train_flops_per_token``.

Straightforward ``jax.numpy``: no kernels, no cache, no sharding, nothing
imported from the program.  It follows the published descriptions (GPT-2:
pre-LN decoder, learned positions, tanh GELU, tied head; BERT: post-LN
encoder, learned positions, erf GELU, untied MLM head with transform +
LayerNorm) and the training recipe the configuration files state (masked
mean cross-entropy over positions with label >= 0, Adam b1 0.9 / b2 0.999 /
eps 1e-8 outside the root, constant learning rate).

Weights come from the seed alone.  The program draws its weights with flax's
``Module.init`` from ``jax.random.key(seed)``; ``init_params`` repeats that
derivation (sha1-of-path fold-ins, the scanned stack's ``split`` of the root
key) from the published rule in flax 0.12, so both sides start from the same
numbers without the reference taking an array from the program.
``benchmarks/tests/test_reference.py`` holds the two inits together.

Departures from the published models: dropout is absent (the program trains
without it); BERT has no token-type embedding and no pooler (the program's
``BertForMLM`` has neither); both noted in PERF.md.

``precision`` selects the arithmetic of every matrix product:

- ``"float32"``: operands float32, ``Precision.HIGHEST`` (the reference);
- ``"fp8"`` / ``"int8"``: operands rounded per tensor to float8_e4m3fn /
  int8 with an absmax scale before each product, accumulation in float32,
  straight-through gradients.  This is the *control* of "How `correct` is
  decided": the nearest precision below the bfloat16 the configurations
  state.  It has to come out as not correct.
"""

from __future__ import annotations

import hashlib
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.lib import flops

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
INIT_STD = 0.02
NEG_INF = -1e30


# ----------------------------------------------------------------------
# architecture, from a configuration file's published keys
# ----------------------------------------------------------------------

def arch_of(config: dict) -> dict:
    """The sizes the reference needs, from either family's published keys."""
    fam = config["family"]
    if fam == "gpt2":
        return dict(family=fam, layers=config["n_layer"],
                    hidden=config["n_embd"], heads=config["n_head"],
                    ffn=config["n_inner"], max_len=config["n_positions"],
                    vocab=config["vocab_size"],
                    ln_eps=config["layer_norm_epsilon"])
    if fam == "bert":
        return dict(family=fam, layers=config["num_hidden_layers"],
                    hidden=config["hidden_size"],
                    heads=config["num_attention_heads"],
                    ffn=config["intermediate_size"],
                    max_len=config["max_position_embeddings"],
                    vocab=config["vocab_size"],
                    ln_eps=config["layer_norm_eps"])
    raise ValueError(f"no plain reference for family {fam!r}")


def train_flops_per_token(config: dict, traffic: dict) -> float:
    """Operations one trained token REQUIRES, forward and backward
    (``lib/flops.py``): the loss needs every position's logits for a causal
    LM and the masked share's for MLM."""
    head_share = (float(traffic["mask_rate"])
                  if traffic["objective"] == "mlm" else 1.0)
    return flops.train_flops_per_token(
        arch_of(config), int(traffic["seq_len"]), head_share,
        causal=traffic["objective"] == "causal_lm")


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


def _normal(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * INIT_STD


def _stacked_normal(root, layers, path, counter, shape):
    """One leaf of the scanned layer stack: flax splits the ROOT key once
    per layer and keeps the path suffix; the stack's body is traced twice
    at init, so a scope's n-th draw carries counter n + (draws in scope)."""
    keys = jax.random.split(root, layers)
    return jax.vmap(lambda k: _normal(_fold(k, "layers", "layer", *path,
                                            counter), shape))(keys)


def init_params(config: dict, seed: int) -> dict:
    """The model's parameters from ``seed``, as a nested dict with the
    program's leaf paths (so leaves can be compared by name)."""
    a = arch_of(config)
    L, H, nh, F, V = a["layers"], a["hidden"], a["heads"], a["ffn"], a["vocab"]
    hd = H // nh
    root = jax.random.key(seed)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    zeros = lambda *s: jnp.zeros(s, jnp.float32)
    ln = lambda *lead: {"scale": ones(*lead, H), "bias": zeros(*lead, H)}
    gpt = a["family"] == "gpt2"
    ln_a, ln_b = ("ln1", "ln2") if gpt else ("ln_attn", "ln_ffn")
    layer = {
        ln_a: ln(L), ln_b: ln(L),
        "attn": {
            "qkv": {"kernel": _stacked_normal(root, L, ("attn", "qkv"), 3,
                                              (H, 3, nh, hd)),
                    "bias": zeros(L, 3, nh, hd)},
            "out": {"kernel": _stacked_normal(root, L, ("attn", "out"), 2,
                                              (nh, hd, H))},
            "out_bias": zeros(L, H)},
        "ffn_in": {"kernel": _stacked_normal(root, L, ("ffn_in",), 3, (H, F)),
                   "bias": zeros(L, F)},
        "ffn_out": {"kernel": _stacked_normal(root, L, ("ffn_out",), 2,
                                              (F, H))},
        "ffn_bias": zeros(L, H),
    }
    params = {
        "tok_emb": {"embedding": _normal(_fold(root, "tok_emb", 1), (V, H))},
        "pos_emb": {"embedding": _normal(_fold(root, "pos_emb", 1),
                                         (a["max_len"], H))},
        "layers": {"layer": layer},
    }
    if gpt:
        params["ln_f"] = ln()
    else:
        params["ln_emb"] = ln()
        params["mlm_dense"] = {
            "kernel": _normal(_fold(root, "mlm_dense", 1), (H, H)),
            "bias": zeros(H)}
        params["mlm_ln"] = ln()
        params["mlm_decoder"] = {
            "kernel": _normal(_fold(root, "mlm_decoder", 1), (H, V)),
            "bias": zeros(V)}
    return params


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------

def _fake_quant(x, precision: str):
    """Round ``x`` per tensor to the control's number format; identity
    gradient (the straight-through estimator lower-precision training
    uses)."""
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


def _layer_norm(x, p, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _attention(x, p, heads: int, causal: bool, precision: str):
    b, l, h = x.shape
    qkv = _mm("bld,dthk->blthk", x, p["qkv"]["kernel"], precision) \
        + p["qkv"]["bias"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = _mm("bqhk,bmhk->bhqm", q, k, precision) / jnp.sqrt(
        jnp.float32(h // heads))
    if causal:
        keep = jnp.arange(l)[None, :] <= jnp.arange(l)[:, None]
        s = jnp.where(keep, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqm,bmhk->bqhk", w, v, precision)
    return _mm("bqhk,hkd->bqd", o, p["out"]["kernel"], precision) \
        + p["out_bias"]


def _ffn(x, p, gelu_tanh: bool, precision: str):
    f = _mm("bld,df->blf", x, p["ffn_in"]["kernel"], precision) \
        + p["ffn_in"]["bias"]
    f = jax.nn.gelu(f, approximate=gelu_tanh)
    return _mm("blf,fd->bld", f, p["ffn_out"]["kernel"], precision) \
        + p["ffn_bias"]


def logits_fn(arch: dict, params: dict, ids, precision: str = "float32"):
    """Token ids [B, L] -> logits [B, L, vocab]."""
    gpt = arch["family"] == "gpt2"
    eps = arch["ln_eps"]
    x = params["tok_emb"]["embedding"][ids] \
        + params["pos_emb"]["embedding"][: ids.shape[1]][None]
    if not gpt:
        x = _layer_norm(x, params["ln_emb"], eps)

    # one layer at a time, recomputed on the way back, so the whole step
    # fits beside nothing else on a 16 GB chip
    @jax.checkpoint
    def block(x, p):
        if gpt:
            x = x + _attention(_layer_norm(x, p["ln1"], eps), p["attn"],
                               arch["heads"], True, precision)
            x = x + _ffn(_layer_norm(x, p["ln2"], eps), p, True, precision)
        else:
            x = _layer_norm(x + _attention(x, p["attn"], arch["heads"],
                                           False, precision),
                            p["ln_attn"], eps)
            x = _layer_norm(x + _ffn(x, p, False, precision),
                            p["ln_ffn"], eps)
        return x, None

    x, _ = lax.scan(block, x, params["layers"]["layer"])
    if gpt:
        x = _layer_norm(x, params["ln_f"], eps)
        return _mm("bld,vd->blv", x, params["tok_emb"]["embedding"],
                   precision)
    x = _mm("bld,de->ble", x, params["mlm_dense"]["kernel"], precision) \
        + params["mlm_dense"]["bias"]
    x = _layer_norm(jax.nn.gelu(x, approximate=False), params["mlm_ln"], eps)
    return _mm("bld,dv->blv", x, params["mlm_decoder"]["kernel"],
               precision) + params["mlm_decoder"]["bias"]


def loss_fn(arch: dict, params: dict, ids, labels,
            precision: str = "float32", rows=None):
    """Mean cross-entropy over the positions whose label is >= 0.
    ``rows`` (a [B] 0/1 vector) restricts the mean to some rows: only the
    planted half-batch fault uses it."""
    logits = logits_fn(arch, params, ids, precision)
    logz = jax.nn.log_softmax(logits, axis=-1)
    ce = -jnp.take_along_axis(logz, jnp.maximum(labels, 0)[..., None],
                              axis=-1)[..., 0]
    w = (labels >= 0).astype(jnp.float32)
    if rows is not None:
        w = w * rows[:, None]
    return (ce * w).sum() / jnp.maximum(w.sum(), 1.0)


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


@partial(jax.jit, static_argnames=("arch_items", "precision", "lr",
                                   "half_batch"))
def _train_steps(params, ids, labels, *, arch_items, precision, lr,
                 half_batch):
    arch = dict(arch_items)
    rows = None
    if half_batch:
        b = ids.shape[1]
        rows = (jnp.arange(b) < b // 2).astype(jnp.float32)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)

    def step(carry, batch):
        p, mu, nu, count = carry
        loss, g = jax.value_and_grad(
            lambda q: loss_fn(arch, q, batch[0], batch[1], precision,
                              rows))(p)
        p, mu, nu, count = adam_step(p, g, mu, nu, count, lr)
        return (p, mu, nu, count), (loss, g)

    carry = (params, zeros, zeros, jnp.zeros((), jnp.float32))
    # the first step apart, so that its gradient can be returned without
    # stacking every step's
    carry, (loss1, g1) = step(carry, (ids[0], labels[0]))

    def later_step(c, b):
        c, (loss, _) = step(c, b)
        return c, loss

    carry, losses = lax.scan(later_step, carry, (ids[1:], labels[1:]))
    return jnp.concatenate([loss1[None], losses]), g1, carry[0]


def train_steps(config: dict, params, ids, labels, *, lr: float,
                precision: str = "float32", half_batch: bool = False):
    """Drive the reference through ``ids.shape[0]`` optimizer steps.

    ``ids``, ``labels``: [steps, B, L].  Returns (losses [steps], the first
    step's gradient tree, the parameters after the last step)."""
    arch = arch_of(config)
    return _train_steps(params, jnp.asarray(ids), jnp.asarray(labels),
                        arch_items=tuple(sorted(arch.items())),
                        precision=precision, lr=float(lr),
                        half_batch=half_batch)
