"""Plain float32 reference ``mla_moe``: a decoder with latent attention
(MLA, no query bottleneck), leading dense layers, and sparse layers of
sigmoid-routed SwiGLU experts with a selection bias plus shared experts
(configuration files with ``"reference": "mla_moe"``, ``model_type``
``deepseek_v3``; the four functions ``lib/check.py`` and
``entries/train_global.py`` call are ``arch_of``, ``init_params``,
``train_steps`` and ``train_flops_per_token``).

Straightforward ``jax.numpy``: no kernels, no cache, no sort, no sharding,
nothing imported from the program.  From ``mellum_moe.py`` comes what is
letter for letter the same: Adam, the per-tensor fake quantisation of the
controls, RMSNorm, flax's key folding, the half-batch fault's weights.
The equations (ISSUE 30; every assumption is in the configuration file's
``assumed``):

- stack: ``x = x + MLA(RMSNorm(x))``, ``x = x + F_l(RMSNorm(x))``; ``F_l``
  is one dense SwiGLU ``W2(silu(W1 x) * W3 x)`` in the first
  ``first_k_dense_replace`` layers, after them ``Routed(x) + Shared(x)``,
  ``Shared`` the same SwiGLU at width ``n_shared_experts *
  moe_intermediate_size``; final RMSNorm, untied head over the rows held,
  mean cross-entropy over the positions with label >= 0;
- MLA (``q_lora_rank`` null): ``q = x Wq`` as heads of ``qk_nope + qk_rope``;
  ``(c, k_rope) = split(x Wkv_a)``; ``c = RMSNorm(c)``; ``c Wkv_b`` as heads
  of ``qk_nope + v``; rotary on each head's ``q_rope`` and on the ONE
  ``k_rope`` on INTERLEAVED PAIRS: ``(x_2n, x_2n+1) -> (x_2n cos - x_2n+1
  sin, x_2n sin + x_2n+1 cos)`` at ``pos * theta^(-2n / qk_rope)`` (the
  source permutes to halves and rotates those; q and k get the same
  permutation, so the scores are these); scores ``(q_nope . k_nope + q_rope
  . k_rope) / sqrt(qk_nope + qk_rope)`` with the rotary key broadcast over
  the heads in the open, under an EXPLICIT mask ``j <= i``, a block of
  queries at a time; softmax in float32; values ``v`` wide.  The expanded
  form, which trains; the absorbed form is a serving matter;
- router (``scoring_func`` sigmoid, ``topk_method`` noaux_tc, one group):
  ``s = sigmoid(x Wr)`` over ALL the router's outputs; the chosen are the
  ``k`` largest of ``s + b``; their weights are ``s`` WITHOUT ``b``, over
  ``(their sum + 1e-20)``, times ``routed_scaling_factor``; the sum over
  the chosen experts THAT ARE HELD, as a loop over the held experts with a
  mask.  What the experts held elsewhere would have added is left out.
  ``b`` enters the choice alone and has no gradient; nothing moves it.

Weights come from the seed alone, by flax's published rule
(``transformer_lm.py`` has the derivation): the scanned unit is one sparse
layer, ``layers/layer_0/...`` with the layers stacked on the leading axis
and the root key split once a layer; a leading dense layer is a module of
its own, ``lead_<i>/...``, traced once.
``benchmarks/tests/test_mla_cell.py`` holds this init to the program's.

``precision``: ``"float32"`` is the reference; ``"fp8"`` / ``"int8"`` are
the controls.  ``bias``: ``"selects"`` is the reference; ``"in_weights"``
(the weights are ``s + b``) and ``"dropped"`` (the choice is on ``s``) are
the planted faults of a misplaced bias.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.lib import mla_flops
from benchmarks.references.mellum_moe import (LOSS_BLOCK, NEG_INF,
                                              _fault_weights, _fold, _mm,
                                              _normal, _rms_norm, adam_step)

ROUTER_EPS = 1e-20
# queries a block of the explicit-mask attention: 32 heads x 256 x 8192
# float32 scores are 256 MB, and a block's backward holds four such (at
# 1,024 the gradient's program needs 5.4 GiB of temporaries beside 10.7 GiB
# of parameters, moments and gradient: it does not fit the chip)
QUERY_BLOCK = 256


# ----------------------------------------------------------------------
# architecture, from the configuration file's published keys
# ----------------------------------------------------------------------

def arch_of(config: dict) -> dict:
    first, count = config["experts_held"]
    if count != config["n_routed_experts"]:
        raise ValueError("n_routed_experts is the count held here and has "
                         "to agree with experts_held")
    for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("norm_topk_prob", True),
                      ("rope_interleave", True), ("moe_layer_freq", 1)):
        if config[key] != want:
            raise ValueError(f"no reference for {key} = {config[key]!r}")
    layers, lead = config["num_hidden_layers"], config["first_k_dense_replace"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    return dict(
        family=config["family"], layers=layers, lead=lead,
        moe_layers=layers - lead, hidden=config["hidden_size"],
        heads=config["num_attention_heads"], qk_nope=nope, qk_rope=rope,
        qk_dim=nope + rope, v_dim=config["v_head_dim"],
        latent=config["kv_lora_rank"], theta=float(config["rope_theta"]),
        vocab=config["vocab_size"], eps=config["rms_norm_eps"],
        dense_ffn=config["intermediate_size"],
        ffn=config["moe_intermediate_size"],
        shared_ffn=config["n_shared_experts"] * config["moe_intermediate_size"],
        experts=config["router_width"], held=(first, count),
        top_k=config["num_experts_per_tok"],
        scale=float(config["routed_scaling_factor"]))


def train_flops_per_token(config: dict, traffic: dict) -> float:
    return mla_flops.train_flops_per_token(arch_of(config),
                                           int(traffic["seq_len"]))


# ----------------------------------------------------------------------
# weights from the seed
# ----------------------------------------------------------------------

def _attention_leaves(a: dict) -> list:
    h, heads = a["hidden"], a["heads"]
    return [(("attn", "q"), (h, heads, a["qk_dim"])),
            (("attn", "kv_a"), (h, a["latent"] + a["qk_rope"])),
            (("attn", "kv_b"), (a["latent"], heads, a["qk_nope"] + a["v_dim"])),
            (("attn", "out"), (heads, a["v_dim"], h))]


def _swiglu_leaves(scope: str, a: dict, width: int) -> list:
    h = a["hidden"]
    return [((scope, "ffn_in"), (h, width)), ((scope, "ffn_up"), (h, width)),
            ((scope, "ffn_out"), (width, h))]


def _norms(a: dict, lead: tuple = ()) -> dict:
    ones = lambda n: jnp.ones(lead + (n,))
    return {"rms1": {"scale": ones(a["hidden"])},
            "rms2": {"scale": ones(a["hidden"])},
            "attn": {"kv_norm": {"scale": ones(a["latent"])}}}


def _set(tree: dict, path: tuple, value) -> None:
    for s in path[:-1]:
        tree = tree.setdefault(s, {})
    tree[path[-1]] = value


def init_params(config: dict, seed: int) -> dict:
    """The model's parameters from ``seed``, as a nested dict with the
    program's leaf paths.  A ``Dense`` kernel is its scope's only draw:
    counter 1 where the module is traced once (the leading layers, the
    embedding, the head), 2 inside the scanned layer, whose body is traced
    twice at init (a scope's n-th draw there carries n + the draws in the
    scope: the routed layer's own scope draws the bias, w1, w3, w2)."""
    a = arch_of(config)
    recipe = config["recipe"]
    root = jax.random.key(seed)
    out: dict = {
        "tok_emb": {"embedding": _normal(
            _fold(root, "tok_emb", 1), (a["vocab"], a["hidden"]),
            recipe["embed_init_std"])},
        "rms_f": {"scale": jnp.ones((a["hidden"],))},
        "lm_head": {"kernel": _normal(_fold(root, "lm_head", 1),
                                      (a["hidden"], a["vocab"]))}}
    for i in range(a["lead"]):
        layer = _norms(a)
        for scope, shape in (_attention_leaves(a)
                             + _swiglu_leaves("mlp", a, a["dense_ffn"])):
            _set(layer, scope + ("kernel",),
                 _normal(_fold(root, f"lead_{i}", *scope, 1), shape))
        out[f"lead_{i}"] = layer
    n, held, h, f = a["moe_layers"], a["held"][1], a["hidden"], a["ffn"]
    keys = jax.random.split(root, n)
    layer = _norms(a, (n,))
    draw = lambda scope, counter, shape, std=0.02: jax.vmap(
        lambda k: _normal(_fold(k, "layers", "layer_0", *scope, counter),
                          shape, std))(keys)
    for scope, shape in (_attention_leaves(a)
                         + _swiglu_leaves("shared", a, a["shared_ffn"])
                         + [(("moe", "gate"), (h, a["experts"]))]):
        _set(layer, scope + ("kernel",), draw(scope, 2, shape))
    layer["moe"].update(
        select_bias=draw(("moe",), 5, (a["experts"],),
                         recipe["router_bias_init_std"]),
        w1=draw(("moe",), 6, (held, h, f)), w3=draw(("moe",), 7, (held, h, f)),
        w2=draw(("moe",), 8, (held, f, h)))
    out["layers"] = {"layer_0": layer}
    return out


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------

def rotate_pairs(x, theta: float):
    """Rotary on interleaved pairs; x [B, L, ..., d], positions 0 .. L-1."""
    d, length = x.shape[-1], x.shape[1]
    inv = theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)
    ang = np.arange(length, dtype=np.float64)[:, None] * inv[None]
    shape = (1, length) + (1,) * (x.ndim - 3) + (d // 2,)
    cos = jnp.asarray(np.cos(ang), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(ang), jnp.float32).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def _attention(x, p, a: dict, precision: str):
    b, l, _ = x.shape
    nope, heads = a["qk_nope"], a["heads"]
    q = _mm("bld,dhk->blhk", x, p["q"]["kernel"], precision)
    ckv = _mm("bld,dk->blk", x, p["kv_a"]["kernel"], precision)
    c = _rms_norm(ckv[..., :a["latent"]], p["kv_norm"]["scale"], a["eps"])
    kv = _mm("blc,chk->blhk", c, p["kv_b"]["kernel"], precision)
    q_nope, q_rope = q[..., :nope], rotate_pairs(q[..., nope:], a["theta"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_rope = rotate_pairs(ckv[..., a["latent"]:], a["theta"])     # [B, L, r]
    bq = QUERY_BLOCK if l % QUERY_BLOCK == 0 else l

    # a block of queries against every key under the explicit mask; each
    # block is recomputed on the way back, so one block's scores are live
    @jax.checkpoint
    def block(args):
        qn, qr, first = args                          # [B, bq, heads, .]
        # every head's key ends in the same rotary key
        s = (_mm("bqhk,bmhk->bhqm", qn, k_nope, precision)
             + _mm("bqhr,bmr->bhqm", qr, k_rope, precision)) / math.sqrt(
                 a["qk_dim"])
        keep = jnp.arange(l)[None, :] <= first + jnp.arange(bq)[:, None]
        w = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
        return _mm("bhqm,bmhk->bqhk", w, v, precision)

    cut = lambda t: t.reshape(b, l // bq, bq, heads, -1).swapaxes(0, 1)
    o = lax.map(block, (cut(q_nope), cut(q_rope), jnp.arange(l // bq) * bq))
    o = o.swapaxes(0, 1).reshape(b, l, heads, a["v_dim"])
    return _mm("blhk,hkd->bld", o, p["out"]["kernel"], precision)


def _swiglu(x, p, precision: str):
    hidden = jax.nn.silu(_mm("...d,df->...f", x, p["ffn_in"]["kernel"],
                             precision)) \
        * _mm("...d,df->...f", x, p["ffn_up"]["kernel"], precision)
    return _mm("...f,fd->...d", hidden, p["ffn_out"]["kernel"], precision)


def route(toks, p, a: dict, bias: str = "selects"):
    """(weights [N, k], chosen experts [N, k]) of the sigmoid router."""
    s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", toks, p["gate"]["kernel"],
                                  precision=lax.Precision.HIGHEST))
    b = p["select_bias"]
    _, idx = lax.top_k(s if bias == "dropped" else s + b, a["top_k"])
    top = jnp.take_along_axis(s + b if bias == "in_weights" else s, idx, -1)
    return top / (top.sum(-1, keepdims=True) + ROUTER_EPS) * a["scale"], idx


def _experts(x, p, a: dict, precision: str, bias: str = "selects"):
    b, l, h = x.shape
    toks = x.reshape(b * l, h)
    top, idx = route(toks, p, a, bias)
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


def hidden_fn(a: dict, params: dict, ids, precision: str = "float32",
              bias: str = "selects"):
    """Token ids [B, L] -> the final norm's output [B, L, hidden]."""
    x = params["tok_emb"]["embedding"][ids]

    # one layer at a time, recomputed on the way back
    @partial(jax.checkpoint, static_argnums=(2,))
    def layer(x, lp, sparse):
        x = x + _attention(_rms_norm(x, lp["rms1"]["scale"], a["eps"]),
                           lp["attn"], a, precision)
        y = _rms_norm(x, lp["rms2"]["scale"], a["eps"])
        if not sparse:
            return x + _swiglu(y, lp["mlp"], precision)
        return x + _experts(y, lp["moe"], a, precision, bias) \
            + _swiglu(y, lp["shared"], precision)

    for i in range(a["lead"]):
        x = layer(x, params[f"lead_{i}"], False)
    x, _ = lax.scan(lambda x, lp: (layer(x, lp, True), None), x,
                    params["layers"]["layer_0"])
    return _rms_norm(x, params["rms_f"]["scale"], a["eps"])


def logits_fn(a: dict, params: dict, ids, precision: str = "float32"):
    """Token ids [B, L] -> logits [B, L, rows held] (tests, small sizes)."""
    return _mm("bld,dv->blv", hidden_fn(a, params, ids, precision),
               params["lm_head"]["kernel"], precision)


def loss_fn(a: dict, params: dict, ids, labels, precision: str = "float32",
            weights=None, bias: str = "selects"):
    """Mean cross-entropy over the positions whose label is >= 0, the head
    and its log-softmax a block of positions at a time.  ``weights`` ([B,
    L] 0/1) restricts the mean further: the planted half-batch fault."""
    x = hidden_fn(a, params, ids, precision, bias)
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


@partial(jax.jit, static_argnames=("arch_items", "precision", "half_batch",
                                   "bias"))
def _loss_and_grad(params, ids, labels, *, arch_items, precision, half_batch,
                   bias):
    return jax.value_and_grad(lambda q: loss_fn(
        dict(arch_items), q, ids, labels, precision,
        _fault_weights(ids, half_batch), bias))(params)


# Adam apart from the gradient's program, its operands given up to it: in
# one program with the gradient the compiler kept a second copy of
# parameters and moments among the temporaries (9.5 GiB where the gradient
# alone takes 3.1: my chip run, PR 30) and the step did not fit
_adam = partial(jax.jit, adam_step, static_argnames=("lr",))
_adam_first = _adam(donate_argnums=(1, 2, 3))      # the caller keeps p0
_adam_later = _adam(donate_argnums=(0, 2, 3))


def train_steps(config: dict, params, ids, labels, *, lr: float,
                precision: str = "float32", half_batch: bool = False,
                bias: str = "selects"):
    """Drive the reference through ``ids.shape[0]`` optimizer steps.

    ``ids``, ``labels``: [steps, B, L].  Returns (losses [steps], the first
    step's gradient tree, the parameters after the last step).  The first
    gradient waits on the host while the later steps run: at the published
    widths the state (parameters, Adam's two moments, a gradient) is 9.2 GB
    of the chip's 16, and the caller's own copy of the parameters comes on
    top."""
    kw = dict(arch_items=tuple(sorted(arch_of(config).items())),
              precision=precision, half_batch=half_batch, bias=bias)
    ids, labels = jnp.asarray(ids), jnp.asarray(labels)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu, count = zeros(), zeros(), jnp.zeros((), jnp.float32)
    losses, g1 = [], None
    for s in range(ids.shape[0]):
        loss, g = _loss_and_grad(params, ids[s], labels[s], **kw)
        losses.append(loss)
        if g1 is None:
            device = next(iter(jax.tree_util.tree_leaves(g)[0].devices()))
            g1 = jax.device_get(g)
        params, mu, nu, count = (_adam_later if s else _adam_first)(
            params, g, mu, nu, count, lr=float(lr))
    return jnp.stack(losses), jax.device_put(g1, device), params
