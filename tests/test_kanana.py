"""kanana-2-30b-a3b-instruct-2601 (ISSUE 30) at the tiny preset on the CPU:
the program against the plain reference ``benchmarks/references/mla_moe.py``
on seeded weights, the eight expert shares against the uncut layer with the
shared experts counted once, a selection bias that moves choices and never
weights, the flash kernels at two widths, the rotary on interleaved pairs,
and the architecture record in the manifest."""

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

from benchmarks.lib import compare, mla_flops  # noqa: E402
from benchmarks.references import mla_moe as reference  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import (  # noqa: E402
    ARCHS, get_model, is_attention_model, remat_name_vocab)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.arch import DecoderArch  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.llama import SwiGLU  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.moe import RoutedExperts  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import (  # noqa: E402
    attend, dot_product_attention, rope)
from test_mellum import _program_steps  # noqa: E402

TINY = os.path.join(ROOT, "benchmarks", "tests", "tiny_mla", "benchmarks")
SEED = 2147483659          # past 2**31, as the driver's seeds are
ROUTER = ARCHS["kanana2_tiny"].router


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(TINY, "configs", "kanana2_tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def batches():
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 1000, (3, 2, 64)).astype(np.int32)
    labels = np.concatenate([ids[..., 1:], np.full((3, 2, 1), -1, np.int32)],
                            -1)
    return ids, labels


class TestProgramAgainstReference:
    """Tolerances: float32 on both sides, so what is left is the order of
    summation: a loss to 2e-6 relative, a leaf's gradient norm to 1e-5 of
    the larger of its own and the median leaf's, a leaf's three-step update
    norm to 1e-4 (Adam divides by the root of a small second moment).  The
    fp8 control misses the gradient limit by orders of magnitude."""

    def test_loss_gradient_and_update_by_leaf(self, config, batches):
        ids, labels = batches
        p0 = reference.init_params(config, SEED)
        ref_l, ref_g, ref_p = reference.train_steps(config, p0, ids, labels,
                                                    lr=1e-3)
        got_l, got_g, got_p = _program_steps(p0, ids, labels, 1e-3,
                                             "kanana2_tiny")
        np.testing.assert_allclose(got_l, np.asarray(ref_l), rtol=2e-6)
        ref_n = compare.block_norms(ref_g)
        # 2 sparse layers x 15 leaves, the dense layer's 10, embedding,
        # final norm, head
        assert len(ref_n) == 2 * 15 + 10 + 3, "a leaf a layer"
        assert compare.worst_gap(compare.block_norms(got_g), ref_n)[0] < 1e-5
        gap, where = compare.worst_gap(
            compare.block_norms(compare.tree_sub(got_p, p0)),
            compare.block_norms(compare.tree_sub(ref_p, p0)))
        assert gap < 1e-4, where
        # the bias has no gradient and Adam leaves it to the bit
        for tree in (got_g, ref_g):
            assert not np.asarray(
                tree["layers"]["layer_0"]["moe"]["select_bias"]).any()
        for tree in (got_p, ref_p):
            np.testing.assert_array_equal(
                tree["layers"]["layer_0"]["moe"]["select_bias"],
                p0["layers"]["layer_0"]["moe"]["select_bias"])

    @pytest.mark.parametrize("fault", ["fp8", "int8", "in_weights", "dropped"])
    def test_control_or_misplaced_bias_fails_the_tolerances(
            self, config, batches, fault):
        ids, labels = batches
        p0 = reference.init_params(config, SEED)
        _, ref_g, _ = reference.train_steps(config, p0, ids, labels, lr=1e-3)
        kw = ({"precision": fault} if fault in ("fp8", "int8")
              else {"bias": fault})
        _, ctl_g, _ = reference.train_steps(config, p0, ids, labels, lr=1e-3,
                                            **kw)
        gap = compare.worst_gap(compare.block_norms(ctl_g),
                                compare.block_norms(ref_g))[0]
        assert gap > 100 * 1e-5

    @pytest.mark.parametrize("remat", [None, "everything"])
    def test_init_is_the_programs(self, config, remat):
        model = get_model("kanana2_tiny", num_classes=1000, scan_layers=True,
                          remat_policy=remat)
        prog = jax.jit(model.init)(jax.random.key(SEED),
                                   jnp.zeros((2, 64), jnp.int32))["params"]
        worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), compare._as_dict(prog),
            reference.init_params(config, SEED))))
        assert worst < 1e-7


def _sparse_layer(config, held=None):
    """The first sparse layer's routed and shared parameters from the
    reference's seeded init, the routed ones cut to ``held = (first,
    count)``."""
    layer = jax.tree_util.tree_map(
        lambda a: a[0], reference.init_params(config, SEED)["layers"][
            "layer_0"])
    moe = dict(layer["moe"])
    if held:
        first, count = held
        moe.update({k: moe[k][first:first + count]
                    for k in ("w1", "w2", "w3")})
    return moe, layer["shared"]


def _routed(held=None):
    return RoutedExperts(8, 32, 2, experts_held=held, router=ROUTER)


class TestRoutedExperts:
    def test_the_shares_add_up(self, config):
        """Eight expert-parallel ranks, one of 8 experts each: every rank
        routes over all 8 (bias and all) and returns its own expert's part;
        the parts, with the shared experts' output counted ONCE, sum to the
        UNCUT reference's whole layer."""
        a = reference.arch_of(config)
        x = jax.random.normal(jax.random.key(1), (2, 64, 64))
        moe, shared = _sparse_layer(config)
        whole = (reference._experts(x, moe, a, "float32")
                 + reference._swiglu(x, shared, "float32"))
        parts = [_routed((first, 1)).apply(
            {"params": _sparse_layer(config, (first, 1))[0]}, x)
            for first in range(8)]
        assert all(float(jnp.abs(p).max()) > 0 for p in parts)
        once = SwiGLU(64).apply({"params": shared}, x)
        np.testing.assert_allclose(sum(parts) + once, whole, atol=2e-6)
        # and one rank alone is the reference given the same share
        share = reference._experts(
            x, _sparse_layer(config, (2, 1))[0], dict(a, held=(2, 1)),
            "float32")
        np.testing.assert_allclose(parts[2], share, atol=1e-6)

    def test_the_bias_moves_choices_and_not_weights(self, config):
        """With the bias large on expert 5 every token chooses it; its
        weight is still its sigmoid score over the chosen pair's scores
        (+ 1e-20), times 2.448; and the bias itself gets no gradient."""
        a = reference.arch_of(config)
        moe, _ = _sparse_layer(config)
        plain = dict(moe, select_bias=jnp.zeros(8))
        moe["select_bias"] = jnp.zeros(8).at[5].set(10.0)
        x = jax.random.normal(jax.random.key(2), (2, 64, 64))
        toks = x.reshape(-1, 64)
        w, idx = reference.route(toks, moe, a)
        w0, idx0 = reference.route(toks, plain, a)
        assert bool((idx[:, 0] == 5).all()), "every token chooses expert 5"
        assert 0.05 < float((idx0 == 5).any(-1).mean()) < 0.6
        # the second choice is the unbiased router's best other expert
        s = np.asarray(jax.nn.sigmoid(toks @ moe["gate"]["kernel"]))
        other = np.where(np.arange(8) == 5, -1.0, s).argmax(-1)
        np.testing.assert_array_equal(idx[:, 1], other)
        pair = np.stack([s[:, 5], s[np.arange(len(s)), other]], -1)
        np.testing.assert_allclose(
            w, pair / (pair.sum(-1, keepdims=True) + 1e-20) * 2.448,
            rtol=1e-6)
        # the program computes the same layer, and hands the bias no gradient
        out, mut = _routed().apply({"params": moe}, x, mutable=["counters"])
        np.testing.assert_allclose(
            out, reference._experts(x, moe, a, "float32"), atol=2e-6)
        load, = mut["counters"]["expert_load_max_over_mean"]
        assert float(load) == 4.0      # 128 of 256 rows on one of 8 experts
        g = jax.grad(lambda p: (_routed().apply({"params": p}, x) ** 2).sum())(
            moe)
        assert not np.asarray(g["select_bias"]).any()
        assert float(jnp.abs(g["gate"]["kernel"]).max()) > 0
        # a bias in the weights, or dropped, is another layer
        for fault in ("in_weights", "dropped"):
            bad = reference._experts(x, moe, a, "float32", fault)
            assert float(jnp.abs(bad - out).max()) > 1e-2

    def test_softmax_rule_is_the_parents_program(self):
        """``mellum2_12b_a2p5b``'s routed layer and the tiny preset's whole
        model trace to one program whatever the rule's defaults are spelt
        as: the jaxprs they traced to before the router's rule became data
        (0e9225a), read again by this code at ISSUE 33, whose row buffer
        changes every routed layer's program, and at ISSUE 35 (the whole
        model's text differs in the printed name of ``everything``'s
        policy alone; the routed layer's pin stands); read again where the
        way back into the tokens became one kernel
        (``ops/moe_combine.py``), which changes both programs."""
        layer = RoutedExperts(64, 896, 8, experts_held=(0, 16),
                              dtype=jnp.bfloat16)
        x = jax.ShapeDtypeStruct((1, 512, 2304), jnp.bfloat16)
        params = jax.eval_shape(lambda: layer.init(
            jax.random.key(0), jnp.zeros(x.shape, x.dtype))["params"])
        assert _jaxpr_pin(jax.grad(lambda p, x: layer.apply(
            {"params": p}, x).astype(jnp.float32).sum(), (0, 1)),
            params, x) == "8daac4e0b7c2d2b5"
        model = get_model("mellum2_tiny", num_classes=1000, scan_layers=True,
                          remat_policy="everything")
        ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
        mp = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((2, 64), jnp.int32))["params"])
        assert _jaxpr_pin(jax.grad(lambda p, i: model.apply(
            {"params": p}, i, train=True).sum()), mp, ids) == \
            "d01798a2fb5e83df"


def _jaxpr_pin(fn, *args) -> str:
    text = str(jax.make_jaxpr(fn)(*args))
    text = re.sub(r" at /[^\s\]]*", "", re.sub(r"/\S*\.py\S*", "", text))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


class TestTwoWidths:
    """The flash kernels with scores and values of different widths
    (interpret mode) against the dense attention, forward and the three
    gradients.  Tolerance: float32 operands, so the kernels' blockwise
    softmax differs from the dense one by the order of summation."""

    @pytest.mark.parametrize("l", [256, 2048])    # one block; 2 x 2 blocks
    @pytest.mark.parametrize("d_qk,d_v", [(192, 128), (24, 16)])
    def test_flash_against_dense(self, l, d_qk, d_v):
        before = set(pallas_ops._FALLBACK_LOGGED)
        ks = jax.random.split(jax.random.key(l + d_qk), 4)
        q = jax.random.normal(ks[0], (1, l, 2, d_qk))
        k = jax.random.normal(ks[1], (1, l, 2, d_qk))
        v = jax.random.normal(ks[2], (1, l, 2, d_v))
        w = jax.random.normal(ks[3], (1, l, 2, d_v))
        loss = lambda impl: lambda q, k, v: (attend(
            q, k, v, impl=impl, causal=True) * w).sum()
        out = attend(q, k, v, impl="flash", causal=True)
        assert out.shape == (1, l, 2, d_v)
        np.testing.assert_allclose(
            out, dot_product_attention(q, k, v, causal=True), atol=2e-5)
        got = jax.grad(loss("flash"), (0, 1, 2))(q, k, v)
        want = jax.grad(loss("dense"), (0, 1, 2))(q, k, v)
        for g, r in zip(got, want):
            assert g.shape == r.shape
            np.testing.assert_allclose(g, r, atol=1e-4)
        assert set(pallas_ops._FALLBACK_LOGGED) == before, "fell back"

    def test_the_scale_is_the_scores_width(self):
        q = jax.random.normal(jax.random.key(0), (1, 8, 2, 24))
        k = jax.random.normal(jax.random.key(1), (1, 8, 2, 24))
        v = jax.random.normal(jax.random.key(2), (1, 8, 2, 16))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(24.0)
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(dot_product_attention(q, k, v), want,
                                   atol=1e-6)

    @pytest.mark.parametrize("impl", ["ring", "all_to_all"])
    def test_sequence_parallel_attention_refuses(self, impl):
        q = jnp.zeros((1, 8, 2, 24))
        with pytest.raises(NotImplementedError, match="one width"):
            attend(q, q, jnp.zeros((1, 8, 2, 16)), impl=impl,
                   axis_name="seq")


class TestInterleavedRotary:
    THETA = 10000.0

    @pytest.mark.parametrize("pos", [0, 5, 8191])
    def test_pair_formula(self, pos):
        """Tolerance: the program's angles are float32 products, so at
        position 8191 an angle is off by up to 8191 x 2**-24 = 5e-4 rad
        from the float64 one here; 2e-4 of a unit-scale entry."""
        x = jax.random.normal(jax.random.key(pos), (1, 1, 3, 8))
        got = np.asarray(rope(x, jnp.asarray([pos]), self.THETA,
                              interleaved=True))[0, 0]
        for n in range(4):
            ang = pos * self.THETA ** (-2 * n / 8)
            a, b = np.asarray(x)[0, 0, :, 2 * n], np.asarray(x)[0, 0, :,
                                                                 2 * n + 1]
            np.testing.assert_allclose(
                got[:, 2 * n], a * np.cos(ang) - b * np.sin(ang), atol=2e-4)
            np.testing.assert_allclose(
                got[:, 2 * n + 1], a * np.sin(ang) + b * np.cos(ang),
                atol=2e-4)

    def test_scores_equal_the_sources_permuted_halves(self):
        """The source permutes each vector to (evens, odds) and rotates
        halves; q and k get the same permutation, so q . k is the same."""
        q = jax.random.normal(jax.random.key(0), (1, 16, 2, 8))
        k = jax.random.normal(jax.random.key(1), (1, 16, 1, 8))
        pos = jnp.arange(16)
        permute = lambda x: jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
        pairs = jnp.einsum("bqhd,bkd->bhqk",
                           rope(q, pos, self.THETA, interleaved=True),
                           rope(k, pos, self.THETA, interleaved=True)[:, :, 0])
        halves = jnp.einsum("bqhd,bkd->bhqk",
                            rope(permute(q), pos, self.THETA),
                            rope(permute(k), pos, self.THETA)[:, :, 0])
        np.testing.assert_allclose(pairs, halves, atol=1e-5)
        # and the reference's own rotation is the program's
        np.testing.assert_allclose(
            reference.rotate_pairs(q, self.THETA),
            rope(q, pos, self.THETA, interleaved=True), atol=1e-6)


class TestArchitectureAsData:
    def test_registry_and_predicates_read_the_record(self):
        assert is_attention_model("kanana2_30b_a3b")
        assert remat_name_vocab("kanana2_tiny")[-1] == "moe_dispatch"
        a = ARCHS["kanana2_30b_a3b"]
        assert (a.layers, a.lead_dense, a.periods, a.experts_held, a.vocab,
                a.shared_ffn) == (5, (1, 6144), 4, (0, 16), 16032, 1536)
        assert (a.latent.rank, a.latent.nope + a.latent.rope, a.latent.v,
                a.head_dim) == (512, 192, 128, 192)
        assert (a.router.score, a.router.select_bias, a.router.scale,
                a.router.eps) == ("sigmoid", True, 2.448, 1e-20)
        assert dict(a.published) == {"layers": 48, "experts": 128,
                                     "vocab": 128256}

    def test_a_manifest_from_before_the_new_fields_still_loads(self):
        """``mellum2_12b_a2p5b`` as PR 26 wrote it into MANIFEST.json: no
        ``latent``, ``lead_dense``, ``shared_ffn``, ``router`` key."""
        new = ("latent", "rope_interleaved", "lead_dense", "shared_ffn",
               "router")
        for name in ("mellum2_12b_a2p5b", "mellum2_tiny"):
            old = {k: v for k, v in json.loads(json.dumps(
                ARCHS[name].as_manifest())).items() if k not in new}
            assert DecoderArch.from_manifest(old) == ARCHS[name]

    def test_published_config_agrees_with_the_program(self):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               "kanana2_30b_a3b.json")) as f:
            config = json.load(f)
        a = reference.arch_of(config)
        p = ARCHS["kanana2_30b_a3b"]
        assert (a["hidden"], a["heads"], a["qk_nope"], a["qk_rope"],
                a["v_dim"], a["latent"], a["experts"], a["top_k"], a["ffn"],
                a["held"], a["vocab"], a["layers"], a["lead"],
                a["dense_ffn"], a["shared_ffn"], a["scale"], a["theta"],
                a["eps"]) == (
            p.hidden, p.heads, p.latent.nope, p.latent.rope, p.latent.v,
            p.latent.rank, p.experts, p.experts_per_token, p.expert_ffn,
            p.experts_held, p.vocab, p.layers, p.lead_dense[0],
            p.lead_dense[1], p.shared_ffn, p.router.scale,
            p.rope_of("full").theta, p.norm_eps)
        assert config["recipe"]["router_bias_init_std"] == p.router.bias_std
        assert config["recipe"]["embed_init_std"] == p.embed_std
        assert reference.ROUTER_EPS == p.router.eps
        # ISSUE 30's count: 1.53 GFLOP in weights, 1.26 in scores and values
        assert reference.train_flops_per_token(
            config, {"seq_len": 8192}) == pytest.approx(2.79e9, rel=0.003)
        assert mla_flops.attention_flops_per_token(a, 8192) == \
            5 * 3 * 2 * 32 * 320 * 4096.5


class TestThroughTheDriver:
    """``--model kanana2_tiny`` through ``main`` -> ``train_global`` -> the
    ``LocalSGDEngine`` round program, with a checkpoint."""

    @pytest.fixture(scope="class")
    def run(self, tmp_path_factory):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.main import train_main
        ckpt = str(tmp_path_factory.mktemp("ckpt"))
        before = set(pallas_ops._FALLBACK_LOGGED)
        results = train_main([
            "--device", "cpu", "--model", "kanana2_tiny", "--dataset",
            "synthetic_lm", "--epochs_global", "2", "--epochs_local", "1",
            "--limit_train_samples", "64", "--limit_eval_samples", "16",
            "--batch_size", "4", "--num_workers", "1", "--aggregation_by",
            "weights", "--attention_impl", "flash", "--remat_policy",
            "everything", "--compute_dtype", "float32", "--checkpoint_dir",
            ckpt, "--checkpoint_every", "1", "--compile_cache_dir", "",
            "--out_dir", str(tmp_path_factory.mktemp("plots"))])
        return results, ckpt, set(pallas_ops._FALLBACK_LOGGED) - before

    def test_trains_and_counts_every_pair(self, run):
        results, _, fell_back = run
        losses = results["global_train_losses"]
        assert len(losses) == 2 and np.isfinite(losses).all()
        assert losses[1] < losses[0]
        assert not fell_back, "flash attention fell back to dense"
        for row in results["round_timings"]:
            # 4 x 128 tokens x top-2, every expert held: nothing dropped;
            # the mean is over the two sparse layers (the dense one sows none)
            assert row["expert_rows"] == 4 * 128 * 2
            assert 1.0 <= row["expert_load_max_over_mean"] <= 8.0

    def test_manifest_rebuilds_the_model(self, run):
        results, ckpt, _ = run
        manifests = [os.path.join(d, f) for d, _, fs in os.walk(ckpt)
                     for f in fs if f == "MANIFEST.json"]
        assert manifests
        with open(sorted(manifests)[-1]) as f:
            meta = json.load(f)["metadata"]
        arch = DecoderArch.from_manifest(meta["arch"])
        assert arch == ARCHS["kanana2_tiny"] and meta["model"] == "kanana2_tiny"
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.decoder import DecoderLM
        model = DecoderLM(arch=arch, num_classes=meta["num_classes"])
        x = jnp.zeros((1, 128), jnp.int32)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), x)["params"])
        trained = results["variables"]["params"]
        assert jax.tree_util.tree_map(lambda a: a.shape, compare._as_dict(
            shapes)) == jax.tree_util.tree_map(
                lambda a: tuple(a.shape), compare._as_dict(trained))
        assert np.isfinite(np.asarray(model.apply(
            {"params": trained}, x))).all()

    def test_serve_refuses_by_the_mechanism(self, run):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve.api import serve_main
        _, ckpt, _ = run
        with pytest.raises(ValueError, match="latent attention"):
            serve_main(["--checkpoint_dir", ckpt, "--device", "cpu"])
