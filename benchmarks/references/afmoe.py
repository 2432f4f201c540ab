"""Plain float32 reference ``afmoe``: a decoder of gated grouped-query
attention (window layers with a rotary, full layers without), four norms a
block, a scaled embedding, leading dense layers, and sparse layers of
sigmoid-routed SwiGLU experts beside a shared one, whose selection bias a
rule moves after every optimizer step (configuration files with
``"reference": "afmoe"``, ``model_type`` ``afmoe``; the four functions
``lib/check.py`` and ``entries/train_global.py`` call are ``arch_of``,
``init_params``, ``train_steps`` and ``train_flops_per_token``).

Straightforward ``jax.numpy``: no kernels, no cache, no sort, no sharding,
nothing imported from the program.  From ``mellum_moe.py`` and
``mla_moe.py`` comes what is letter for letter the same: Adam, the
per-tensor fake quantisation of the controls, RMSNorm, the rotate-half
rotary and its table, flax's key folding, the half-batch fault's weights,
the SwiGLU, the sigmoid router and the loop over the held experts.  The
equations (ISSUE 32; every assumption is in the configuration file's
``assumed``; ``N`` is RMSNorm with a learned scale from 1):

- embedding: ``x = E[id] * sqrt(hidden)`` (``mup_enabled``);
- block: ``x = x + N2(Attn_t(N1(x)))``, ``x = x + N4(F_l(N3(x)))``: the
  second and fourth norm on the sublayer's OUTPUT, before the add.  ``F_l``
  is one dense SwiGLU ``W2(silu(W1 h) * W3 h)`` in the leading layers,
  after them ``Routed(h) + Shared(h)``, ``Shared`` the same SwiGLU at width
  ``num_shared_experts * moe_intermediate_size``; final N, untied head over
  the rows held, mean cross-entropy over the positions with label >= 0;
- ``Attn_t(h)``: ``q = h Wq`` as heads of ``head_dim``, ``k, v`` as
  key-value heads, ``g = h Wg`` as wide as q; ``q = Nq(q)``, ``k = Nk(k)``
  over each head's ``head_dim`` with ONE learned scale for q and one for
  k; ``t`` sliding: rotate-half rotary on q and k, query i sees keys ``i -
  window < j <= i``; ``t`` full: NO rotary, ``j <= i``; scores over
  ``sqrt(head_dim)`` under an EXPLICIT mask, a block of queries at a time,
  softmax in float32, query head h reads key-value head ``h // rep``; ``y =
  (o * sigmoid(g)) Wo``;
- router (``score_func`` sigmoid, ``route_norm``, one group): ``s =
  sigmoid(h Wr)`` over ALL the router's outputs; the chosen are the ``k``
  largest of ``s + b``; their weights are ``s`` WITHOUT ``b``, over
  ``(their sum + 1e-20)``, times ``route_scale``; the sum over the chosen
  experts THAT ARE HELD.  What the experts held elsewhere would have added
  is left out;
- the bias rule, once an optimizer step and sparse layer, after Adam's
  update: ``n_e`` the (token, choice) pairs of the step that chose expert
  e, over ALL experts; ``d_e = load_balance_coeff * sign(mean(n) - n_e)``;
  ``b_e += d_e - mean(d)``.  ``b`` has no gradient and no moment.

Weights come from the seed alone, by flax's published rule
(``transformer_lm.py`` has the derivation): the scanned unit is a period
of layers, ``layers/layer_<i>/...`` with the periods stacked on the leading
axis and the root key split once a period; a leading dense layer is a
module of its own, ``lead_<i>/...``, traced once.
``tests/test_trinity.py`` holds this init to the program's.

``precision``: ``"float32"`` is the reference; ``"fp8"`` / ``"int8"`` are
the controls.  ``fault``: ``None`` is the reference; each of ``FAULTS``
plants one fault of this architecture's own parts.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.lib import afmoe_flops
from benchmarks.references.mellum_moe import (KINDS, LOSS_BLOCK, NEG_INF,
                                              _fault_weights, _fold, _mm,
                                              _normal, _period_of, _rms_norm,
                                              _rotate, adam_step, rope_table)
from benchmarks.references.mla_moe import _experts, _set, _swiglu, route

FAULTS = ("gate_dropped", "rope_on_full", "qk_norm_dropped",
          "bias_in_weights", "bias_rule_off")
# queries a block of the explicit-mask attention: 32 heads x 256 x 8192
# float32 scores are 256 MB, and a block's backward holds four such
QUERY_BLOCK = 256


# ----------------------------------------------------------------------
# architecture, from the configuration file's published keys
# ----------------------------------------------------------------------

def arch_of(config: dict) -> dict:
    first, count = config["experts_held"]
    if count != config["num_experts"]:
        raise ValueError("num_experts is the count held here and has to "
                         "agree with experts_held")
    for key, want in (("rope_scaling", None), ("n_group", 1),
                      ("topk_group", 1), ("score_func", "sigmoid"),
                      ("route_norm", True), ("mup_enabled", True),
                      ("tie_word_embeddings", False)):
        if config[key] != want:
            raise ValueError(f"no reference for {key} = {config[key]!r}")
    # layer_types stays as published; layers_held says which of them are here
    held_layers = config["layers_held"]
    layers, lead = config["num_hidden_layers"], config["num_dense_layers"]
    if len(held_layers) != layers:
        raise ValueError("layers_held has to name num_hidden_layers layers")
    return dict(
        family=config["family"], layers=layers, lead=lead,
        moe_layers=layers - lead,
        layer_types=tuple(KINDS[config["layer_types"][i]]
                          for i in held_layers),
        hidden=config["hidden_size"], heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        window=config["sliding_window"], theta=float(config["rope_theta"]),
        vocab=config["vocab_size"], eps=config["rms_norm_eps"],
        dense_ffn=config["intermediate_size"],
        ffn=config["moe_intermediate_size"],
        shared_ffn=(config["num_shared_experts"]
                    * config["moe_intermediate_size"]),
        experts=config["router_width"], held=(first, count),
        top_k=config["num_experts_per_tok"],
        scale=float(config["route_scale"]),
        bias_step=float(config["load_balance_coeff"]),
        embed_scale=math.sqrt(config["hidden_size"]))


def train_flops_per_token(config: dict, traffic: dict) -> float:
    return afmoe_flops.train_flops_per_token(arch_of(config),
                                             int(traffic["seq_len"]))


# ----------------------------------------------------------------------
# weights from the seed
# ----------------------------------------------------------------------

def _attention_leaves(a: dict) -> list:
    h, heads, kv, hd = a["hidden"], a["heads"], a["kv_heads"], a["head_dim"]
    return [(("attn", "q"), (h, heads, hd)), (("attn", "kv"), (h, 2, kv, hd)),
            (("attn", "gate"), (h, heads, hd)),
            (("attn", "out"), (heads, hd, h))]


def _swiglu_leaves(scope: str, a: dict, width: int) -> list:
    h = a["hidden"]
    return [((scope, "ffn_in"), (h, width)), ((scope, "ffn_up"), (h, width)),
            ((scope, "ffn_out"), (width, h))]


def _norms(a: dict, lead: tuple = ()) -> dict:
    ones = lambda n: {"scale": jnp.ones(lead + (n,))}
    return dict({name: ones(a["hidden"]) for name in (
        "rms1", "rms1_post", "rms2", "rms2_post")},
        attn={"q_norm": ones(a["head_dim"]), "k_norm": ones(a["head_dim"])})


def init_params(config: dict, seed: int) -> dict:
    """The model's parameters from ``seed``, as a nested dict with the
    program's leaf paths.  A ``Dense`` kernel is its scope's only draw:
    counter 1 where the module is traced once (the leading layers, the
    embedding, the head), 2 inside the scanned period, whose body is traced
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
    period = _period_of(a["layer_types"][a["lead"]:])
    periods = a["moe_layers"] // period
    keys = jax.random.split(root, periods)
    held, h, f = a["held"][1], a["hidden"], a["ffn"]
    out["layers"] = {}
    for i in range(period):
        draw = lambda scope, counter, shape, std=0.02: jax.vmap(
            lambda k: _normal(_fold(k, "layers", f"layer_{i}", *scope,
                                    counter), shape, std))(keys)
        layer = _norms(a, (periods,))
        for scope, shape in (_attention_leaves(a)
                             + _swiglu_leaves("shared", a, a["shared_ffn"])
                             + [(("moe", "gate"), (h, a["experts"]))]):
            _set(layer, scope + ("kernel",), draw(scope, 2, shape))
        layer["moe"].update(
            select_bias=draw(("moe",), 5, (a["experts"],),
                             recipe["router_bias_init_std"]),
            w1=draw(("moe",), 6, (held, h, f)),
            w3=draw(("moe",), 7, (held, h, f)),
            w2=draw(("moe",), 8, (held, f, h)))
        out["layers"][f"layer_{i}"] = layer
    return out


# ----------------------------------------------------------------------
# arithmetic
# ----------------------------------------------------------------------

def _attention(x, p, a: dict, kind: str, precision: str, fault=None):
    b, l, _ = x.shape
    heads, kvh, hd = a["heads"], a["kv_heads"], a["head_dim"]
    q = _mm("bld,dhk->blhk", x, p["q"]["kernel"], precision)
    kv = _mm("bld,dthk->blthk", x, p["kv"]["kernel"], precision)
    g = _mm("bld,dhk->blhk", x, p["gate"]["kernel"], precision)
    k, v = kv[:, :, 0], kv[:, :, 1]
    if fault != "qk_norm_dropped":
        q = _rms_norm(q, p["q_norm"]["scale"], a["eps"])
        k = _rms_norm(k, p["k_norm"]["scale"], a["eps"])
    if kind == "sliding" or fault == "rope_on_full":
        cos, sin = rope_table(hd, (a["theta"],), l)
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    rep = heads // kvh
    bq = QUERY_BLOCK if l % QUERY_BLOCK == 0 else l
    window = a["window"] if kind == "sliding" else None

    # a block of queries against every key under the explicit mask; each
    # block is recomputed on the way back, so one block's scores are live
    @jax.checkpoint
    def block(args):
        qb, first = args                              # [B, bq, heads, d]
        s = _mm("bqgrk,bmgk->bgrqm", qb.reshape(b, bq, kvh, rep, hd), k,
                precision) / math.sqrt(hd)
        i = first + jnp.arange(bq)[:, None]
        j = jnp.arange(l)[None, :]
        keep = j <= i
        if window is not None:
            keep &= j > i - window
        w = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
        return _mm("bgrqm,bmgk->bqgrk", w, v, precision).reshape(
            b, bq, heads, hd)

    qs = q.reshape(b, l // bq, bq, heads, hd).swapaxes(0, 1)
    o = lax.map(block, (qs, jnp.arange(l // bq) * bq))
    o = o.swapaxes(0, 1).reshape(b, l, heads, hd)
    if fault != "gate_dropped":
        o = o * jax.nn.sigmoid(g)
    return _mm("blhk,hkd->bld", o, p["out"]["kernel"], precision)


def choice_counts(x, p, a: dict):
    """[experts]: the (token, choice) pairs that chose each expert."""
    _, idx = route(x.reshape(-1, x.shape[-1]), p, a)
    return (idx[..., None] == jnp.arange(a["experts"])).sum(
        (0, 1)).astype(jnp.float32)


def hidden_fn(a: dict, params: dict, ids, precision: str = "float32",
              fault=None):
    """Token ids [B, L] -> (the final norm's output [B, L, hidden], the
    sparse layers' choice counts ``{layer_<i>: [periods, experts]}``)."""
    x = params["tok_emb"]["embedding"][ids] * a["embed_scale"]
    norm = lambda y, p: _rms_norm(y, p["scale"], a["eps"])
    bias = "in_weights" if fault == "bias_in_weights" else "selects"

    # one layer at a time, recomputed on the way back
    @partial(jax.checkpoint, static_argnums=(2, 3))
    def layer(x, lp, kind, sparse):
        x = x + norm(_attention(norm(x, lp["rms1"]), lp["attn"], a, kind,
                                precision, fault), lp["rms1_post"])
        y = norm(x, lp["rms2"])
        if not sparse:
            return x + norm(_swiglu(y, lp["mlp"], precision),
                            lp["rms2_post"]), None
        f = (_experts(y, lp["moe"], a, precision, bias)
             + _swiglu(y, lp["shared"], precision))
        return (x + norm(f, lp["rms2_post"]),
                choice_counts(y, lp["moe"], a))

    kinds = a["layer_types"]
    for i in range(a["lead"]):
        x, _ = layer(x, params[f"lead_{i}"], kinds[i], False)
    period = _period_of(kinds[a["lead"]:])

    def one_period(x, p):
        counts = {}
        for i in range(period):
            x, counts[f"layer_{i}"] = layer(x, p[f"layer_{i}"],
                                            kinds[a["lead"] + i], True)
        return x, counts

    x, counts = lax.scan(one_period, x, params["layers"])
    return _rms_norm(x, params["rms_f"]["scale"], a["eps"]), counts


def logits_fn(a: dict, params: dict, ids, precision: str = "float32"):
    """Token ids [B, L] -> logits [B, L, rows held] (tests, small sizes)."""
    return _mm("bld,dv->blv", hidden_fn(a, params, ids, precision)[0],
               params["lm_head"]["kernel"], precision)


def loss_fn(a: dict, params: dict, ids, labels, precision: str = "float32",
            weights=None, fault=None):
    """(Mean cross-entropy over the positions whose label is >= 0, the
    choice counts): the head and its log-softmax a block of positions at a
    time.  ``weights`` ([B, L] 0/1) restricts the mean further: the planted
    half-batch fault."""
    x, counts = hidden_fn(a, params, ids, precision, fault)
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
    return total / jnp.maximum(w.sum(), 1.0), counts


def move_bias(params: dict, counts: dict, step: float) -> dict:
    """The bias rule on every sparse layer; ``counts`` as ``hidden_fn``
    gives them."""
    layers = dict(params["layers"])
    for name, n in counts.items():
        d = step * jnp.sign(n.mean(-1, keepdims=True) - n)
        moe = dict(layers[name]["moe"])
        moe["select_bias"] = moe["select_bias"] + d - d.mean(-1, keepdims=True)
        layers[name] = dict(layers[name], moe=moe)
    return dict(params, layers=layers)


@partial(jax.jit, static_argnames=("arch_items", "precision", "half_batch",
                                   "fault"))
def _loss_and_grad(params, ids, labels, *, arch_items, precision, half_batch,
                   fault):
    return jax.value_and_grad(lambda q: loss_fn(
        dict(arch_items), q, ids, labels, precision,
        _fault_weights(ids, half_batch), fault), has_aux=True)(params)


def _update(params, grads, mu, nu, count, counts, lr, bias_step):
    params, mu, nu, count = adam_step(params, grads, mu, nu, count, lr)
    return move_bias(params, counts, bias_step), mu, nu, count


# Adam and the rule apart from the gradient's program, its operands given
# up to it (``mla_moe.py`` has the reading: in one program the step did not
# fit the chip)
_update = partial(jax.jit, _update, static_argnames=("lr", "bias_step"))
_update_first = _update(donate_argnums=(1, 2, 3))      # the caller keeps p0
_update_later = _update(donate_argnums=(0, 2, 3))


def train_steps(config: dict, params, ids, labels, *, lr: float,
                precision: str = "float32", half_batch: bool = False,
                fault: str | None = None):
    """Drive the reference through ``ids.shape[0]`` optimizer steps.

    ``ids``, ``labels``: [steps, B, L].  Returns (losses [steps], the first
    step's gradient tree, the parameters after the last step).  The first
    gradient waits on the host while the later steps run: at the published
    widths the state (parameters, Adam's two moments, a gradient) is 8.1 GB
    of the chip's 16, and the caller's own copy of the parameters comes on
    top."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    a = arch_of(config)
    kw = dict(arch_items=tuple(sorted(a.items())), precision=precision,
              half_batch=half_batch, fault=fault)
    bias_step = 0.0 if fault == "bias_rule_off" else a["bias_step"]
    ids, labels = jnp.asarray(ids), jnp.asarray(labels)
    zeros = lambda: jax.tree_util.tree_map(jnp.zeros_like, params)
    mu, nu, count = zeros(), zeros(), jnp.zeros((), jnp.float32)
    losses, g1 = [], None
    for s in range(ids.shape[0]):
        (loss, counts), g = _loss_and_grad(params, ids[s], labels[s], **kw)
        losses.append(loss)
        if g1 is None:
            device = next(iter(jax.tree_util.tree_leaves(g)[0].devices()))
            g1 = jax.device_get(g)
        params, mu, nu, count = (_update_later if s else _update_first)(
            params, g, mu, nu, count, counts, lr=float(lr),
            bias_step=bias_step)
    return jnp.stack(losses), jax.device_put(g1, device), params
