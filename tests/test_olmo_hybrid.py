"""Olmo-Hybrid (ISSUE 37) on the CPU: the chunked gated-delta-rule walk
(``ops/delta_rule.py``: the Mosaic kernels in interpret mode and the
``lax.scan`` walk) against the token-by-token recurrence, outputs and the
five gradients, with beta above 1; ``GatedDeltaNet`` against the plain
reference's layer; the tiny preset's init, loss, gradient and three Adam
steps against ``benchmarks/references/olmo_hybrid.py``, and the same
through the engine's own round; five head shares against the uncut layer;
the record in the manifest and the serving refusal; and the two sparse
presets whose programs the shared record must leave as they were."""

import hashlib
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.entries import train_global as tg  # noqa: E402
from benchmarks.lib import compare, gdn_flops, traffic  # noqa: E402
from benchmarks.references import olmo_hybrid as reference  # noqa: E402
from benchmarks.run import load_spec  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu import train  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import (  # noqa: E402
    ARCHS, checkpoint_policy, get_model)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.arch import (  # noqa: E402
    DecoderArch, Linear)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.linear_attention import (  # noqa: E402
    GatedDeltaNet)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import delta_rule  # noqa: E402

TINY = os.path.join(ROOT, "benchmarks", "tests", "tiny_olmo")
SEED = 2147483659          # past 2**31, as the driver's seeds are
LR = 1e-5                  # the tiny fixture's recipe (its flags_why)


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(TINY, "benchmarks", "configs",
                           "olmo_hybrid_tiny.json")) as f:
        return json.load(f)


def _on_lax(monkeypatch):
    """The module's walk on ``lax.scan`` where the kernel is not what a
    test is about: the Pallas interpreter takes seconds a call."""
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import linear_attention
    real = delta_rule.gated_delta_rule
    monkeypatch.setattr(linear_attention, "gated_delta_rule",
                        lambda *a, **kw: real(*a, use_kernel=False, **kw))


@pytest.fixture
def lax_walk(monkeypatch):
    _on_lax(monkeypatch)


@pytest.fixture(scope="module")
def p0(config):
    """The reference's weights from the seed, drawn once (jitted: eager,
    every draw compiles on its own)."""
    return jax.jit(lambda: reference.init_params(config, SEED))()


@pytest.fixture(scope="module")
def batches():
    rng = np.random.default_rng(37)
    ids = rng.integers(0, 1000, (3, 2, 80)).astype(np.int32)
    labels = np.concatenate([ids[..., 1:], np.full((3, 2, 1), -1, np.int32)],
                            -1)
    return ids, labels


def _walk_inputs(l, h=2, dk=8, dv=12):
    ks = jax.random.split(jax.random.key(l), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, l, h, dk))) / dk ** 0.5
    k = unit(jax.random.normal(ks[1], (1, l, h, dk)))
    v = jax.random.normal(ks[2], (1, l, h, dv))
    log_alpha = -0.3 * jax.nn.softplus(jax.random.normal(ks[3], (1, l, h)))
    # beta in (1, 2) mostly: the transition's eigenvalue 1 - beta < 0
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (1, l, h)) + 1.0)
    w = jax.random.normal(ks[5], (1, l, h, dv))
    return (q, k, v, log_alpha, beta), w


class TestTheChunkedWalk:
    """float32 on both sides: what is left is the order of summation."""

    @pytest.mark.parametrize("l,use_kernel", [(64, False), (50, False),
                                              (50, True), (37, True)])
    def test_against_the_token_recurrence(self, l, use_kernel):
        args, w = _walk_inputs(l)
        assert float(args[-1].max()) > 1.5
        chunked = lambda *a: delta_rule.gated_delta_rule(
            *a, chunk=16, use_kernel=use_kernel)
        want = delta_rule.gated_delta_recurrence(*args)
        got = chunked(*args)
        assert got.shape == want.shape == (1, l, 2, 12)
        np.testing.assert_allclose(got, want, atol=2e-6 * float(
            jnp.abs(want).max()))
        loss = lambda f: lambda *a: (f(*a) * w).sum()
        gw = jax.grad(loss(delta_rule.gated_delta_recurrence),
                      range(5))(*args)
        gg = jax.grad(loss(chunked), range(5))(*args)
        for name, a, b in zip(("q", "k", "v", "log_alpha", "beta"), gg, gw):
            np.testing.assert_allclose(
                a, b, atol=2e-6 * float(jnp.abs(b).max()), err_msg=name)

    def test_the_walk_runs_once_a_step_under_every_policy(self):
        """Two layers in a scan, each rematerialised: the forward kernel
        runs once a layer under ``models.checkpoint_policy`` (its output and
        states are named and kept), twice under jax's own
        ``nothing_saveable``."""
        (q, k, v, log_alpha, beta), _ = _walk_inputs(32, dv=8)

        def count(policy):
            walk = jax.checkpoint(lambda c: delta_rule.gated_delta_rule(
                c, k, v, log_alpha, beta, chunk=16), policy=policy)
            loss = lambda c: jax.lax.scan(lambda c, _: (c + walk(c), None),
                                          c, None, length=2)[0].sum()
            text = str(jax.make_jaxpr(jax.grad(loss))(q))
            return (len(re.findall(r"name=gdn_fwd\b", text)),
                    len(re.findall(r"name=gdn_bwd\b", text)))

        assert count(checkpoint_policy("everything")) == (1, 1)
        assert count(jax.checkpoint_policies.nothing_saveable) == (2, 1)

    @staticmethod
    def _shapes(length, heads=6, dk=96, dv=192):
        s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
        return (s(1, length, heads, dk), s(1, length, heads, dk),
                s(1, length, heads, dv), s(1, length, heads),
                s(1, length, heads))

    @pytest.mark.parametrize("use_kernel", [True, False])
    def test_saved_state(self, use_kernel):
        # 3 chunks of 64 for 130 tokens, a [96, 192] float32 state each
        assert delta_rule.saved_state_bytes(
            *self._shapes(130), use_kernel=use_kernel) == 4 * 6 * 3 * 96 * 192
        # the cell: 56.6 MiB a linear layer, 162 MiB a step's three
        assert delta_rule.saved_state_bytes(
            *self._shapes(8192), use_kernel=use_kernel) * 3 / 2 ** 20 == 162.0

    def test_saved_state_is_read_off_the_residuals(self, monkeypatch):
        """A forward rule that left the backward its operands alone (a
        backward that recomputed the states) would read 0."""
        fwd = delta_rule._gdr_fwd
        monkeypatch.setattr(delta_rule, "_gdr_fwd", lambda *a: (
            fwd(*a)[0], fwd(*a)[1][:5]))
        delta_rule._residual_bytes.cache_clear()
        try:
            assert delta_rule.saved_state_bytes(*self._shapes(130),
                                                use_kernel=False) == 0
        finally:
            delta_rule._residual_bytes.cache_clear()


@pytest.mark.usefixtures("lax_walk")
class TestTheModule:
    def test_gated_deltanet_against_the_reference_layer(self):
        x = jax.random.normal(jax.random.key(1), (2, 40, 64))
        layer = GatedDeltaNet(4, 16, 32, 4, 1e-6)
        params = layer.init(jax.random.key(2), x)["params"]
        a = {"dk": 16, "eps": 1e-6}
        want = reference._linear(x, params, a, "float32")
        np.testing.assert_allclose(layer.apply({"params": params}, x), want,
                                   rtol=0, atol=2e-5 * float(
                                       jnp.abs(want).max()))
        # every parameter is used: a fault of each part moves the output
        for fault in ("beta_unscaled", "alpha_one", "conv_dropped",
                      "gate_dropped", "l2_dropped"):
            wrong = reference._linear(x, params, a, "float32", fault)
            assert float(jnp.abs(wrong - want).max()) > 1e-3 * float(
                jnp.abs(want).max()), fault

    def test_init_is_the_programs(self, p0):
        model = get_model("olmo_hybrid_tiny", num_classes=1000,
                          scan_layers=True, remat_policy="everything")
        prog = jax.jit(model.init)(jax.random.key(SEED),
                                   jnp.zeros((2, 64), jnp.int32))["params"]
        ref = p0
        assert jax.tree_util.tree_structure(compare._as_dict(prog)) == \
            jax.tree_util.tree_structure(ref)
        worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max()),
            compare._as_dict(prog), ref)))
        assert worst < 1e-6


def _program_steps(params, ids, labels, lr):
    """The program's model under the reference's recipe: value_and_grad of
    the masked mean cross-entropy and optax's Adam, as the engine's step
    does them."""
    import optax
    model = get_model("olmo_hybrid_tiny", num_classes=1000, scan_layers=True,
                      remat_policy="everything")
    tx = optax.adam(lr)

    def loss_fn(p, x, y):
        ce = train.softmax_cross_entropy(
            model.apply({"params": p}, x, train=True), jnp.maximum(y, 0))
        w = (y >= 0).astype(jnp.float32)
        return (ce * w).sum() / w.sum()

    @jax.jit
    def step(p, opt, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        u, opt = tx.update(g, opt, p)
        return loss, g, jax.tree_util.tree_map(jnp.add, p, u), opt

    opt, losses, g1 = tx.init(params), [], None
    for x, y in zip(ids, labels):
        loss, g, params, opt = step(params, opt, x, y)
        losses.append(float(loss))
        g1 = g if g1 is None else g1
    return np.asarray(losses), g1, params


class TestProgramAgainstReference:
    """Tolerances: float32 on both sides.  A loss to 2e-6 relative, a
    leaf's gradient norm to 1e-4 of the larger of its own and the median
    leaf's (the program's gradients read 2e-5 of the reference's element
    by element through eight post-normed layers), a leaf's three-step
    update norm to 5e-3 (Adam divides by the root of a small second
    moment)."""

    @pytest.fixture(scope="class")
    def both(self, config, batches, p0):
        ids, labels = batches
        with pytest.MonkeyPatch.context() as mp:
            _on_lax(mp)
            prog = _program_steps(p0, ids, labels, LR)
        ref = reference.train_steps(config, p0, ids, labels, lr=LR)
        return p0, prog, ref

    def test_loss_gradient_and_update(self, both):
        p0, (losses, g1, after), (ref_l, ref_g, ref_p) = both
        np.testing.assert_allclose(losses, np.asarray(ref_l), rtol=2e-6)
        assert compare.worst_gap(compare.block_norms(g1),
                                 compare.block_norms(ref_g))[0] < 1e-4
        assert compare.worst_gap(
            compare.block_norms(compare.tree_sub(after, p0)),
            compare.block_norms(compare.tree_sub(ref_p, p0)))[0] < 5e-3

    @pytest.mark.parametrize("fault", reference.FAULTS + ("fp8",))
    def test_a_fault_or_the_control_fails_the_tolerances(
            self, config, batches, both, fault):
        ids, labels = batches
        p0, _, (_, ref_g, _) = both
        kw = {"precision": "fp8"} if fault == "fp8" else {"fault": fault}
        _, g, _ = reference.train_steps(config, p0, ids[:1], labels[:1],
                                        lr=LR, **kw)
        gap = compare.worst_gap(compare.block_norms(g),
                                compare.block_norms(ref_g))[0]
        assert gap > 10 * 1e-4, (fault, gap)


class TestThroughTheEngine:
    """The tiny cell's own short call (``benchmarks/tests/tiny_olmo``,
    float32): the engine's round program of four steps with labels on the
    first 3, against the reference's three steps; and the counter."""

    @pytest.fixture(scope="class")
    def call(self, p0):
        spec = load_spec("olmo1", TINY)
        c, w = spec["config"], spec["workload"]
        t = w["traffic"]
        rows = traffic.generate(t, c, SEED, 1)
        x, y = rows["train"]
        rows["train"] = (x, traffic.keep_first_steps(y, 3, t, 1))
        three = tg.timed_call(tg.build_argv(c, w, SEED, 1), rows,
                              c["vocab_size"])[0]
        shape = (t["steps_per_round"], t["batch"], -1)
        ref = reference.train_steps(c, p0, x.reshape(shape)[:3],
                                    y.reshape(shape)[:3], lr=LR)
        return three, p0, ref, t

    def test_three_steps_are_the_references(self, call):
        three, p0, (ref_l, ref_g, ref_p), _ = call
        np.testing.assert_allclose(tg.step_losses(three, 1, 3)[0],
                                   np.asarray(ref_l), rtol=2e-6)
        got = compare._as_dict(three["variables"]["params"])
        assert compare.worst_gap(
            compare.block_norms(compare.tree_sub(got, p0)),
            compare.block_norms(compare.tree_sub(ref_p, p0)))[0] < 5e-3

    def test_the_rows_carry_the_saved_state(self, call):
        three, _, _, t = call
        # 96 tokens: two chunks of 64, four heads of a [16, 32] state
        assert three["round_timings"][0]["gdn_state_bytes"] == \
            t["batch"] * 4 * 2 * 16 * 32 * 4


@pytest.mark.usefixtures("lax_walk")
class TestTheShares:
    """Five chips split a layer's heads: each runs its heads and its slice
    of the output projection, and the layer's output is the sum of the
    five partial outputs (the exchange the cell leaves out)."""

    def test_five_linear_shares_add_up(self):
        x = jax.random.normal(jax.random.key(3), (1, 40, 64))
        whole = GatedDeltaNet(5, 16, 32, 4, 1e-6)
        p = whole.init(jax.random.key(4), x)["params"]
        head_axis = {"q": 1, "k": 1, "v": 1, "g": 1, "a": 1, "b": 1,
                     "out": 0, "q_conv": 1, "k_conv": 1, "v_conv": 1,
                     "A_log": 0, "dt_bias": 0}

        def share(i):
            cut = lambda a, ax: jax.lax.slice_in_dim(a, i, i + 1, axis=ax)
            out = {}
            for name, leaf in p.items():
                if name == "o_norm":
                    out[name] = leaf          # one scale shared by the heads
                elif isinstance(leaf, dict):
                    out[name] = {"kernel": cut(leaf["kernel"],
                                               head_axis[name])}
                else:
                    out[name] = cut(leaf, head_axis[name])
            return one({"params": out}, x)

        one = jax.jit(GatedDeltaNet(1, 16, 32, 4, 1e-6).apply)

        want = whole.apply({"params": p}, x)
        got = sum(share(i) for i in range(5))
        np.testing.assert_allclose(got, want, atol=1e-5 * float(
            jnp.abs(want).max()))

    def test_full_shares_need_the_whole_width_statistic(self):
        """The full layer's q / k norm is one statistic over all 30 heads'
        columns: five shares add up to the uncut layer when each is given
        that statistic, and not when each takes it over its own columns,
        which is what the chip does (the configuration's ``assumed``)."""
        a = {"heads": 5, "head_dim": 8, "eps": 1e-6}
        ks = jax.random.split(jax.random.key(5), 3)
        x = jax.random.normal(ks[0], (1, 24, 32))
        p = {"qkv": {"kernel": 0.2 * jax.random.normal(ks[1],
                                                       (32, 3, 5, 8))},
             "out": {"kernel": 0.2 * jax.random.normal(ks[2], (5, 8, 32))},
             "q_norm": {"scale": jnp.linspace(0.5, 1.5, 40).reshape(5, 8)},
             "k_norm": {"scale": jnp.linspace(1.5, 0.5, 40).reshape(5, 8)}}
        want = reference._attention(x, p, a, "float32")
        qkv = jnp.einsum("bld,dthk->blthk", x, p["qkv"]["kernel"])
        rms = lambda t: jnp.sqrt(jnp.mean(t * t, axis=(-2, -1),
                                          keepdims=True) + 1e-6)
        whole_q, whole_k = rms(qkv[:, :, 0]), rms(qkv[:, :, 1])

        def share(i, given):
            sl = lambda t, ax: jax.lax.slice_in_dim(t, i, i + 1, axis=ax)
            pi = {"qkv": {"kernel": sl(p["qkv"]["kernel"], 2)},
                  "out": {"kernel": sl(p["out"]["kernel"], 0)},
                  "q_norm": {"scale": sl(p["q_norm"]["scale"], 0)},
                  "k_norm": {"scale": sl(p["k_norm"]["scale"], 0)}}
            ai = dict(a, heads=1)
            if not given:
                return reference._attention(x, pi, ai, "float32")
            # the deployment's exchange: the statistic over all heads,
            # handed to the share in place of its own
            ratio = lambda whole, own: own / whole
            own_q, own_k = (rms(sl(qkv[:, :, j], 2)) for j in (0, 1))
            pi["q_norm"]["scale"] = pi["q_norm"]["scale"] * ratio(whole_q,
                                                                   own_q)
            pi["k_norm"]["scale"] = pi["k_norm"]["scale"] * ratio(whole_k,
                                                                   own_k)
            return reference._attention(x, pi, ai, "float32")

        given = sum(share(i, True) for i in range(5))
        np.testing.assert_allclose(given, want, atol=1e-5 * float(
            jnp.abs(want).max()))
        held = sum(share(i, False) for i in range(5))
        assert float(jnp.abs(held - want).max()) > 1e-3 * float(
            jnp.abs(want).max())


class TestArchitectureAsData:
    def test_the_record(self):
        a = ARCHS["olmo_hybrid_7b"]
        assert (a.hidden, a.heads, a.kv_heads, a.head_dim, a.dense_ffn,
                a.vocab, a.layers, a.experts) == (3840, 6, 6, 128, 11008,
                                                  12544, 4, 0)
        assert a.linear == Linear(6, 96, 192, 4)
        assert a.layer_types == ("linear",) * 3 + ("full",)
        assert a.rope_of("full") is None and a.rope_of("linear") is None
        assert (a.qk_norm, a.qk_norm_whole, a.post_norm_only,
                a.sandwich_norm) == (True, True, True, False)
        assert dict(a.published) == {"layers": 32, "heads": 30,
                                     "vocab": 100352}

    @pytest.mark.parametrize("name", ["olmo_hybrid_7b", "olmo_hybrid_tiny"])
    def test_manifest_round_trip(self, name):
        a = ARCHS[name]
        manifest = json.loads(json.dumps(a.as_manifest()))
        assert manifest["linear"]["value_dim"] == a.linear.value_dim
        assert DecoderArch.from_manifest(manifest) == a

    def test_published_config_agrees_with_the_program(self):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               "olmo_hybrid_7b.json")) as f:
            config = json.load(f)
        a = reference.arch_of(config)
        p = ARCHS["olmo_hybrid_7b"]
        assert (a["hidden"], a["heads"], a["head_dim"], a["lin_heads"],
                a["dk"], a["dv"], a["conv"], a["ffn"], a["vocab"],
                a["layers"], a["eps"], a["layer_types"]) == (
            p.hidden, p.heads, p.head_dim, p.linear.heads,
            p.linear.key_dim, p.linear.value_dim, p.linear.conv,
            p.dense_ffn, p.vocab, p.layers, p.norm_eps, p.layer_types)
        assert tuple(config["heads_held"]) == (0, p.heads)
        assert config["recipe"]["embed_init_std"] == p.embed_std
        published = config["published"]
        assert dict(p.published) == {
            "layers": published["num_hidden_layers"],
            "heads": published["num_attention_heads"],
            "vocab": published["vocab_size"]}
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            entry = [c for c in json.load(f)["configs"]
                     if c["name"] == "olmo_hybrid_7b"][0]
        assert sorted(entry["reduced"]) == sorted(published)
        assert len(config["layer_types"]) == 32, "kept as published"
        # 668.67 M parameters, as the issue's arithmetic has them
        model = get_model("olmo_hybrid_7b", num_classes=p.vocab,
                          scan_layers=True)
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 64), jnp.int32))["params"])
        assert sum(int(np.prod(s.shape)) for s in
                   jax.tree_util.tree_leaves(shapes)) == 668_669_796

    def test_required_operations(self):
        """6 x 620.4 M weights a token (a linear layer's projections 770
        columns a head: q, k 96, v, gate, out 192, beta's and alpha's one),
        causal attention of the full layer, 18 dk dv a token and head of
        each linear layer: 3.766 GFLOP, 81% of it the MLPs."""
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               "olmo_hybrid_7b.json")) as f:
            a = reference.arch_of(json.load(f))
        assert gdn_flops.linear_matmul_params(a) == 3840 * 6 * 770
        weights = (3 * (3840 * 6 * 770 + 3 * 3840 * 11008)
                   + 4 * 3840 * 768 + 3 * 3840 * 11008 + 3840 * 12544)
        assert gdn_flops.train_flops_per_token(a, 8192) == (
            6 * weights + 3 * 2 * 6 * 2 * 128 * 4096.5
            + 3 * 18 * 96 * 192 * 6)
        assert gdn_flops.train_flops_per_token(a, 8192) == pytest.approx(
            3.766e9, rel=1e-3)

    def test_serve_refuses_by_name(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve.engine import (
            model_from_metadata)
        with pytest.raises(ValueError, match="linear attention"):
            model_from_metadata({"model": "olmo_hybrid_tiny",
                                 "arch": ARCHS["olmo_hybrid_tiny"]
                                 .as_manifest()})


def _hlo_pin(name: str) -> str:
    """The lowered text of one training step's gradient, without source
    locations."""
    model = get_model(name, num_classes=1000, scan_layers=True,
                      remat_policy="everything")
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    mp = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((2, 64), jnp.int32))["params"])
    text = jax.jit(jax.grad(lambda p, i: model.apply(
        {"params": p}, i, train=True).sum())).lower(mp, ids).as_text(
            debug_info=False)
    assert not re.search(r"\bloc\(", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name,pin", [("trinity_tiny", "9bb8ec0286e5f2ef"),
                                      ("kanana2_tiny", "b4e37fcb57666706")])
def test_the_sparse_presets_lower_to_the_parents_programs(name, pin):
    """The shared record's new fields at their defaults change nothing: the
    step's lowered program is the parent's (bdccbd5) text for text, read
    again where the way back into the tokens became one kernel
    (``ops/moe_combine.py``), which changes every routed layer's
    program."""
    assert _hlo_pin(name) == pin
