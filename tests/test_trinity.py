"""Trinity-Mini (ISSUE 32) at the tiny preset on the CPU: the program
against the plain reference ``benchmarks/references/afmoe.py`` on seeded
weights (loss, first gradient, three-step update and the MOVED selection
bias), each planted fault of the new parts, the four expert shares against
the uncut layer with the shared expert counted once, the rule that moves
the bias through the engine's own step (a step without a label, Adam's
moments, two workers), and the architecture record in the manifest."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.entries import train_global as tg  # noqa: E402
from benchmarks.lib import afmoe_flops, compare, traffic  # noqa: E402
from benchmarks.references import afmoe as reference  # noqa: E402
from benchmarks.run import load_spec  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu import train  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import (  # noqa: E402
    ARCHS, get_model, is_attention_model, remat_name_vocab)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.arch import (  # noqa: E402
    DecoderArch, Router)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.llama import SwiGLU  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.moe import (  # noqa: E402
    CHOICE_COUNTS, RoutedExperts)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops  # noqa: E402
from test_kanana import _jaxpr_pin  # noqa: E402

TINY = os.path.join(ROOT, "benchmarks", "tests", "tiny_afmoe")
SEED = 2147483659          # past 2**31, as the driver's seeds are
ARCH = ARCHS["trinity_tiny"]
NEW_FIELDS = ("qk_norm", "attn_gate", "sandwich_norm", "embed_scale")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(TINY, "benchmarks", "configs",
                           "trinity_tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def batches():
    # rows on which no step's count of any layer's expert sits on the mean
    # (32), found by trying seeds: see the first test
    rng = np.random.default_rng(48)
    ids = rng.integers(0, 1000, (3, 2, 64)).astype(np.int32)
    labels = np.concatenate([ids[..., 1:], np.full((3, 2, 1), -1, np.int32)],
                            -1)
    return ids, labels


def _biases(params) -> np.ndarray:
    """[layers of a period, periods, experts]"""
    layers = params["layers"]
    return np.stack([np.asarray(layers[f"layer_{i}"]["moe"]["select_bias"])
                     for i in range(len(layers))])


def _program_steps(params, ids, labels, lr):
    """The program's model under the reference's recipe: value_and_grad of
    the masked mean cross-entropy, optax's Adam, and after each update the
    step's own rule on the selection biases, from the counts the routed
    layers sowed.  Returns the reference's triple and the counts."""
    import optax
    model = get_model("trinity_tiny", num_classes=1000, scan_layers=True,
                      remat_policy="everything")
    tx = optax.scale_by_adam()

    def loss_fn(p, x, y):
        out, mut = model.apply({"params": p}, x, train=True,
                               mutable=["counters"])
        ce = train.softmax_cross_entropy(out, jnp.maximum(y, 0))
        w = (y >= 0).astype(jnp.float32)
        return (ce * w).sum() / w.sum(), train._mean_by_name(mut["counters"])

    @jax.jit
    def step(p, opt, x, y):
        (loss, counters), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, x, y)
        u, opt = tx.update(g, opt, p)
        p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, u)
        counts = counters[CHOICE_COUNTS]
        return loss, g, train.move_select_bias(
            p, counts, ARCH.router.bias_step), opt, counts

    opt, losses, g1, all_counts = tx.init(params), [], None, []
    for x, y in zip(ids, labels):
        loss, g, params, opt, counts = step(params, opt, x, y)
        losses.append(float(loss))
        all_counts.append(counts)
        g1 = g if g1 is None else g1
    return np.asarray(losses), g1, params, all_counts


class TestProgramAgainstReference:
    """Tolerances: float32 on both sides, so what is left is the order of
    summation: a loss to 2e-6 relative, a leaf's gradient norm to 1e-5 of
    the larger of its own and the median leaf's, a leaf's three-step update
    norm to 1e-4 (Adam divides by the root of a small second moment).  The
    bias moves by a sign, so it is the reference's to the ulp of the sum
    wherever the counts agree, and they are whole numbers in float32."""

    def test_loss_gradient_update_and_moved_bias(self, config, batches):
        ids, labels = batches
        p0 = reference.init_params(config, SEED)
        ref_l, ref_g, ref_p = reference.train_steps(config, p0, ids, labels,
                                                    lr=1e-3)
        got_l, got_g, got_p, counts = _program_steps(p0, ids, labels, 1e-3)
        np.testing.assert_allclose(got_l, np.asarray(ref_l), rtol=2e-6)
        ref_n = compare.block_norms(ref_g)
        # 8 sparse layers x 18 leaves, the dense layer's 13, embedding,
        # final norm, head
        assert len(ref_n) == 8 * 18 + 13 + 3, "a leaf a layer"
        assert compare.worst_gap(compare.block_norms(got_g), ref_n)[0] < 1e-5
        gap, where = compare.worst_gap(
            compare.block_norms(compare.tree_sub(got_p, p0)),
            compare.block_norms(compare.tree_sub(ref_p, p0)))
        assert gap < 1e-4, where
        # the bias has no gradient; the rule moved it, every expert of every
        # sparse layer as the reference's own rule did.  On these rows no
        # count of any step sits ON its layer's mean (32), so every sign is
        # +1 or -1 and none hangs on a pair that rounding could move
        for tree in (got_g, ref_g):
            assert not _biases(tree).any()
        for step_counts in counts:
            for n in step_counts.values():
                n = np.asarray(n)
                assert n.shape == (2, 8) and (n.sum(-1) == 2 * 64 * 2).all()
                assert (n != 32).all(), "pick another seed for the rows"
        moved = _biases(got_p) - _biases(p0)
        np.testing.assert_allclose(_biases(got_p), _biases(ref_p), rtol=0,
                                   atol=1e-9)
        # a step moves an entry by the step less the mean: under 0.002
        assert 0.0009 < np.abs(moved).max() < 3 * 0.002
        assert np.abs(moved.sum(-1)).max() < 1e-7, "the rule keeps the mean"

    @pytest.mark.parametrize("fault", ["fp8", "gate_dropped", "rope_on_full",
                                       "qk_norm_dropped", "bias_in_weights",
                                       "bias_rule_off"])
    def test_control_or_planted_fault_fails_the_tolerances(
            self, config, batches, fault):
        ids, labels = batches
        p0 = reference.init_params(config, SEED)
        _, ref_g, ref_p = reference.train_steps(config, p0, ids, labels,
                                                lr=1e-3)
        kw = {"precision": fault} if fault == "fp8" else {"fault": fault}
        _, ctl_g, ctl_p = reference.train_steps(config, p0, ids, labels,
                                                lr=1e-3, **kw)
        if fault == "bias_rule_off":
            # the first gradient cannot see a rule that acts after it: the
            # bias is what shows it, to the bit as drawn
            np.testing.assert_array_equal(_biases(ctl_p), _biases(p0))
            assert np.abs(_biases(ctl_p) - _biases(ref_p)).max() > 0.0009
            return
        gap = compare.worst_gap(compare.block_norms(ctl_g),
                                compare.block_norms(ref_g))[0]
        assert gap > 100 * 1e-5

    @pytest.mark.parametrize("remat", [None, "everything"])
    def test_init_is_the_programs(self, config, remat):
        model = get_model("trinity_tiny", num_classes=1000, scan_layers=True,
                          remat_policy=remat)
        prog = jax.jit(model.init)(jax.random.key(SEED),
                                   jnp.zeros((2, 64), jnp.int32))["params"]
        worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), compare._as_dict(prog),
            reference.init_params(config, SEED))))
        assert worst < 1e-7

    def test_the_other_models_trace_to_the_parents_programs(self):
        """``mellum2_tiny`` and ``kanana2_tiny`` with the new arguments at
        their defaults: the jaxprs of their gradients are the parent's
        (06ee999; read again by this code at ISSUE 33, whose row buffer
        changes every routed layer's program, and at ISSUE 35, where the
        text differs from the parent's in the printed name of the remat
        policy alone: ``save_only_these_names.<locals>.policy`` where it
        read ``nothing_saveable``, and no equation)."""
        for name, pin in (("mellum2_tiny", "617100ca8765e6b3"),
                          ("kanana2_tiny", "031067d7d8319fe4")):
            model = get_model(name, num_classes=1000, scan_layers=True,
                              remat_policy="everything")
            ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
            mp = jax.eval_shape(lambda: model.init(
                jax.random.key(0), jnp.zeros((2, 64), jnp.int32))["params"])
            assert _jaxpr_pin(jax.grad(lambda p, i: model.apply(
                {"params": p}, i, train=True).sum()), mp, ids) == pin, name


def _sparse_layer(config, held=None):
    """The first sparse layer's routed and shared parameters from the
    reference's seeded init, the routed ones cut to ``held``."""
    layer = jax.tree_util.tree_map(
        lambda a: a[0], reference.init_params(config, SEED)["layers"][
            "layer_0"])
    moe = dict(layer["moe"])
    if held:
        first, count = held
        moe.update({k: moe[k][first:first + count]
                    for k in ("w1", "w2", "w3")})
    return moe, layer["shared"]


class TestTheShares:
    def test_the_shares_add_up_and_count_alike(self, config):
        """Four expert-parallel ranks, two of 8 experts each: every rank
        routes over all 8 (bias and all) and returns its own experts' part;
        the parts, with the shared expert's output counted ONCE, sum to the
        UNCUT reference's whole layer; and every rank counts the same
        choices, because the counts are over all experts."""
        a = reference.arch_of(config)
        x = jax.random.normal(jax.random.key(1), (2, 64, 64))
        moe, shared = _sparse_layer(config)
        whole = (reference._experts(x, moe, a, "float32")
                 + reference._swiglu(x, shared, "float32"))
        parts, counts = [], []
        for first in range(0, 8, 2):
            out, mut = RoutedExperts(
                8, 32, 2, experts_held=(first, 2), router=ARCH.router).apply(
                    {"params": _sparse_layer(config, (first, 2))[0]}, x,
                    mutable=["counters"])
            parts.append(out)
            counts.append(np.asarray(mut["counters"][CHOICE_COUNTS][0]))
        assert all(float(jnp.abs(p).max()) > 0 for p in parts)
        once = SwiGLU(32).apply({"params": shared}, x)
        np.testing.assert_allclose(sum(parts) + once, whole, atol=2e-6)
        for n in counts:
            np.testing.assert_array_equal(n, counts[0])
        np.testing.assert_array_equal(
            counts[0], reference.choice_counts(x, moe, a))
        assert counts[0].sum() == 2 * 64 * 2

    def test_a_router_without_a_rule_sows_no_counts(self, config):
        moe, _ = _sparse_layer(config)
        x = jax.random.normal(jax.random.key(1), (2, 64, 64))
        still = Router("sigmoid", True, 2.826, 1e-20, 0.005)
        _, mut = RoutedExperts(8, 32, 2, router=still).apply(
            {"params": moe}, x, mutable=["counters"])
        assert CHOICE_COUNTS not in mut["counters"]


class TestTheRule:
    def test_by_hand(self):
        """One layer, four experts, counts 5, 1, 2, 4 (mean 3): d = 0.1 x
        (-1, +1, +1, -1), mean 0; counts 9, 1, 1, 1: d = 0.1 x (-1, 1, 1, 1),
        mean 0.05, so b += (-0.15, 0.05, 0.05, 0.05).  A stacked layer's
        rows move each by its own counts; a leaf that is no bias, and a bias
        without counts, stay."""
        params = {"a": {"moe": {"select_bias": jnp.zeros((2, 4)),
                                "w1": jnp.ones(3)}},
                  "b": {"moe": {"select_bias": jnp.ones(4)}}}
        counts = {("a", "moe"): jnp.asarray([[5., 1., 2., 4.],
                                             [9., 1., 1., 1.]])}
        out = train.move_select_bias(params, counts, 0.1)
        np.testing.assert_allclose(
            out["a"]["moe"]["select_bias"],
            [[-0.1, 0.1, 0.1, -0.1], [-0.15, 0.05, 0.05, 0.05]], atol=1e-7)
        np.testing.assert_array_equal(out["a"]["moe"]["w1"], 1.0)
        np.testing.assert_array_equal(out["b"]["moe"]["select_bias"], 1.0)


class TestThroughTheEngine:
    """The tiny cell's own call (``benchmarks/tests/tiny_afmoe``, float32):
    the engine's round program of four steps with labels on the first 3 of
    them, as the benchmark's short calls run it."""

    @pytest.fixture(scope="class")
    def call(self):
        spec = load_spec("afmoe1", TINY)
        c, w = spec["config"], spec["workload"]
        t = w["traffic"]
        rows = traffic.generate(t, c, SEED, 1)
        x, y = rows["train"]
        rows["train"] = (x, traffic.keep_first_steps(y, 3, t, 1))
        three = tg.timed_call(tg.build_argv(c, w, SEED, 1), rows,
                              c["vocab_size"])[0]
        p0 = reference.init_params(c, SEED)
        shape = (t["steps_per_round"], t["batch"], -1)
        ref = reference.train_steps(c, p0, x.reshape(shape)[:3],
                                    y.reshape(shape)[:3], lr=1e-3)
        return three, p0, ref

    def test_bias_after_the_round_is_the_references_after_three_steps(
            self, call):
        """Four steps ran and the fourth had no labelled position: the
        bias is where the reference's three steps left it, on every expert,
        so the fourth left it to the bit."""
        three, p0, (ref_l, _, ref_p) = call
        assert np.allclose(tg.step_losses(three, 1, 3)[0], np.asarray(ref_l),
                           rtol=2e-6)
        got = _biases(compare._as_dict(three["variables"]["params"]))
        np.testing.assert_allclose(got, _biases(ref_p), rtol=0, atol=1e-9)
        row = three["round_timings"][0]
        # the row key is the round's net movement, mean over layers and
        # experts
        assert row["select_bias_moved"] == pytest.approx(
            np.abs(got - _biases(p0)).mean(), rel=1e-3)
        assert row["expert_rows"] == 4 * 64 * 2
        assert CHOICE_COUNTS not in row, "the counts stay in the step"

    def test_adams_moments_of_the_bias_stay_zero(self, call):
        opt = call[0]["state"].opt_state
        for moment in (opt.mu, opt.nu):
            assert not _biases(compare._as_dict(moment)).any()
        assert float(jnp.abs(opt.mu["layers"]["layer_0"]["moe"]["gate"][
            "kernel"]).max()) > 0


class TestArchitectureAsData:
    def test_registry_and_predicates_read_the_record(self):
        assert is_attention_model("trinity_mini_26b_a3b")
        assert remat_name_vocab("trinity_tiny")[-1] == "moe_dispatch"
        a = ARCHS["trinity_mini_26b_a3b"]
        assert (a.layers, a.lead_dense, a.periods, a.experts_held, a.vocab,
                a.shared_ffn, a.window) == (5, (1, 6144), 1, (0, 8), 25024,
                                            1024, 2048)
        assert (a.qk_norm, a.attn_gate, a.sandwich_norm, a.embed_scale) == (
            True, True, True, 2048 ** 0.5)
        assert a.rope_of("sliding").theta == 10000.0
        assert a.rope_of("full") is None, "no rotary on the full layers"
        assert a.router == Router("sigmoid", True, 2.826, 1e-20, 0.005, 0.001)
        assert dict(a.published) == {"layers": 32, "dense_layers": 2,
                                     "experts": 128, "vocab": 200192}

    @pytest.mark.parametrize("name", sorted(ARCHS))
    def test_manifest_round_trip(self, name):
        """Through JSON and back, with the new fields; and a manifest from
        before them (no such keys, a router without ``bias_step``) loads
        with the defaults, which are what the older models have."""
        a = ARCHS[name]
        manifest = json.loads(json.dumps(a.as_manifest()))
        assert DecoderArch.from_manifest(manifest) == a
        old = {k: v for k, v in manifest.items() if k not in NEW_FIELDS}
        old["router"] = {k: v for k, v in old["router"].items()
                         if k != "bias_step"}
        if name.startswith("trinity"):
            assert DecoderArch.from_manifest(old) != a
        else:
            assert DecoderArch.from_manifest(old) == a

    def test_published_config_agrees_with_the_program(self):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               "trinity_mini_26b_a3b.json")) as f:
            config = json.load(f)
        a = reference.arch_of(config)
        p = ARCHS["trinity_mini_26b_a3b"]
        assert (a["hidden"], a["heads"], a["kv_heads"], a["head_dim"],
                a["window"], a["experts"], a["top_k"], a["ffn"], a["held"],
                a["vocab"], a["layers"], a["lead"], a["dense_ffn"],
                a["shared_ffn"], a["scale"], a["theta"], a["eps"],
                a["bias_step"], a["embed_scale"]) == (
            p.hidden, p.heads, p.kv_heads, p.head_dim, p.window, p.experts,
            p.experts_per_token, p.expert_ffn, p.experts_held, p.vocab,
            p.layers, p.lead_dense[0], p.lead_dense[1], p.shared_ffn,
            p.router.scale, p.rope_of("sliding").theta, p.norm_eps,
            p.router.bias_step, p.embed_scale)
        assert a["layer_types"] == (p.layer_types[0],) + p.layer_types
        assert config["recipe"]["router_bias_init_std"] == p.router.bias_std
        assert config["recipe"]["embed_init_std"] == p.embed_std
        assert dict(p.published) == {
            "layers": config["published"]["num_hidden_layers"],
            "dense_layers": config["published"]["num_dense_layers"],
            "experts": config["published"]["num_experts"],
            "vocab": config["published"]["vocab_size"]}
        # the catalog row's numbers, key by key, but for the four reduced
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            entry = [c for c in json.load(f)["configs"]
                     if c["name"] == "trinity_mini_26b_a3b"][0]
        assert sorted(entry["reduced"]) == sorted(config["published"])
        assert len(config["layer_types"]) == 32, "kept as published"
        # ISSUE 32's count: 1.585 GFLOP in weights, 0.554 in scores and values
        assert reference.train_flops_per_token(
            config, {"seq_len": 8192}) == pytest.approx(2.14e9, rel=0.003)
        assert afmoe_flops.attention_flops_per_token(a, 8192) == \
            3 * 2 * 32 * 256 * (4 * 1792.125 + 4096.5)


class TestThroughTheDriver:
    """``--model trinity_tiny`` through ``main`` -> ``train_global`` -> the
    ``LocalSGDEngine`` round program, with a checkpoint; then on two
    workers."""

    ARGV = ["--device", "cpu", "--model", "trinity_tiny", "--dataset",
            "synthetic_lm", "--epochs_local", "1", "--limit_train_samples",
            "64", "--limit_eval_samples", "16", "--batch_size", "4",
            "--aggregation_by", "weights", "--attention_impl", "flash",
            "--remat_policy", "everything", "--compute_dtype", "float32",
            "--compile_cache_dir", ""]

    @pytest.fixture(scope="class")
    def run(self, tmp_path_factory):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.main import train_main
        ckpt = str(tmp_path_factory.mktemp("ckpt"))
        before = set(pallas_ops._FALLBACK_LOGGED)
        results = train_main(self.ARGV + [
            "--epochs_global", "2", "--num_workers", "1", "--checkpoint_dir",
            ckpt, "--checkpoint_every", "1", "--out_dir",
            str(tmp_path_factory.mktemp("plots"))])
        return results, ckpt, set(pallas_ops._FALLBACK_LOGGED) - before

    def test_trains_counts_every_pair_and_moves_the_bias(self, run):
        results, _, fell_back = run
        losses = results["global_train_losses"]
        assert len(losses) == 2 and np.isfinite(losses).all()
        assert losses[1] < losses[0]
        assert not fell_back, "flash attention fell back to dense"
        for row in results["round_timings"]:
            # 4 x 128 tokens x top-2, every expert held: nothing dropped
            assert row["expert_rows"] == 4 * 128 * 2
            assert 1.0 <= row["expert_load_max_over_mean"] <= 8.0
            # 16 steps of 0.001 at most, and the rule is alive
            assert 0.0 < row["select_bias_moved"] <= 0.016 * (1 + 1e-6)

    def test_manifest_rebuilds_the_model(self, run):
        results, ckpt, _ = run
        manifests = [os.path.join(d, f) for d, _, fs in os.walk(ckpt)
                     for f in fs if f == "MANIFEST.json"]
        assert manifests
        with open(sorted(manifests)[-1]) as f:
            meta = json.load(f)["metadata"]
        assert all(k in meta["arch"] for k in NEW_FIELDS)
        assert meta["arch"]["router"]["bias_step"] == 0.001
        arch = DecoderArch.from_manifest(meta["arch"])
        assert arch == ARCH and meta["model"] == "trinity_tiny"
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
        with pytest.raises(ValueError, match="routed experts"):
            serve_main(["--checkpoint_dir", ckpt, "--device", "cpu"])

    def test_two_workers_average_the_bias_like_any_leaf(self,
                                                        tmp_path_factory):
        """``--aggregation_by weights``: the two workers walk their biases
        apart on their own rows, and the round's sync leaves both with one
        bias, as it leaves them with one router matrix."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.main import train_main
        results = train_main(self.ARGV + [
            "--epochs_global", "1", "--num_workers", "2", "--out_dir",
            str(tmp_path_factory.mktemp("plots"))])
        moe = results["state"].params["layers"]["layer_0"]["moe"]
        bias = np.asarray(moe["select_bias"])          # [workers, 2, 8]
        gate = np.asarray(moe["gate"]["kernel"])
        assert bias.shape == (2, 2, 8)
        np.testing.assert_array_equal(bias[0], bias[1])
        np.testing.assert_array_equal(gate[0], gate[1])
        assert results["round_timings"][0]["select_bias_moved"] > 0

    def test_accumulated_slices_count_as_one_step(self, tmp_path_factory):
        """``--grad_accum 2``: the two slices' counts add up to the step's,
        so one step moves the bias exactly as the unsliced step does (a
        token's choice does not depend on its batch)."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.main import train_main
        biases = []
        for accum in ("1", "2"):
            argv = [a if a != "64" else "4" for a in self.ARGV]
            results = train_main(argv + [
                "--epochs_global", "1", "--num_workers", "1", "--grad_accum",
                accum, "--out_dir", str(tmp_path_factory.mktemp("plots"))])
            assert results["round_timings"][0]["select_bias_moved"] > 0
            biases.append(_biases(compare._as_dict(
                results["variables"]["params"])))
        np.testing.assert_array_equal(*biases)
