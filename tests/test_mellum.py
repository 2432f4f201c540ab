"""Mellum2-12B-A2.5B (ISSUE 26) at the tiny preset on the CPU: the program
against the plain reference ``benchmarks/references/mellum_moe.py`` on
seeded weights, the expert shares against the uncut layer, no dropped
token at any imbalance, and the architecture record in the manifest."""

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

from benchmarks.lib import compare  # noqa: E402
from benchmarks.references import mellum_moe as reference  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import (  # noqa: E402
    ARCHS, get_model, is_attention_model, remat_name_vocab)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.arch import DecoderArch  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.moe import RoutedExperts  # noqa: E402

TINY = os.path.join(ROOT, "benchmarks", "tests", "tiny_routed", "benchmarks")
SEED = 2147483659          # past 2**31, as the driver's seeds are


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(TINY, "configs", "mellum2_tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def batches():
    rng = np.random.default_rng(7)
    ids = rng.integers(0, 1000, (3, 2, 64)).astype(np.int32)
    labels = np.concatenate([ids[..., 1:], np.full((3, 2, 1), -1, np.int32)],
                            -1)
    return ids, labels


def _program_steps(params, ids, labels, lr, name="mellum2_tiny"):
    """The program's model under the reference's recipe: value_and_grad of
    the masked mean cross-entropy, optax's Adam, three steps."""
    import optax
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.train import softmax_cross_entropy
    model = get_model(name, num_classes=1000, scan_layers=True,
                      remat_policy="everything")
    tx = optax.scale_by_adam()

    def loss_fn(p, x, y):
        ce = softmax_cross_entropy(model.apply({"params": p}, x, train=True),
                                   jnp.maximum(y, 0))
        w = (y >= 0).astype(jnp.float32)
        return (ce * w).sum() / w.sum()

    @jax.jit
    def step(p, opt, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        u, opt = tx.update(g, opt, p)
        return loss, g, jax.tree_util.tree_map(
            lambda a, b: a - lr * b, p, u), opt

    opt, losses, g1 = tx.init(params), [], None
    for x, y in zip(ids, labels):
        loss, g, params, opt = step(params, opt, x, y)
        losses.append(float(loss))
        g1 = g if g1 is None else g1
    return np.asarray(losses), g1, params


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
        got_l, got_g, got_p = _program_steps(p0, ids, labels, 1e-3)
        np.testing.assert_allclose(got_l, np.asarray(ref_l), rtol=2e-6)
        ref_n = compare.block_norms(ref_g)
        assert len(ref_n) == 8 * 9 + 3, "a leaf a layer, stacked by period"
        assert compare.worst_gap(compare.block_norms(got_g), ref_n)[0] < 1e-5
        gap, where = compare.worst_gap(
            compare.block_norms(compare.tree_sub(got_p, p0)),
            compare.block_norms(compare.tree_sub(ref_p, p0)))
        assert gap < 1e-4, where

    @pytest.mark.parametrize("precision", ["fp8", "int8"])
    def test_lower_precision_fails_the_tolerances(self, config, batches,
                                                  precision):
        ids, labels = batches
        p0 = reference.init_params(config, SEED)
        _, ref_g, _ = reference.train_steps(config, p0, ids, labels, lr=1e-3)
        ctl_l, ctl_g, _ = reference.train_steps(config, p0, ids, labels,
                                                lr=1e-3, precision=precision)
        gap = compare.worst_gap(compare.block_norms(ctl_g),
                                compare.block_norms(ref_g))[0]
        assert gap > 100 * 1e-5

    def test_init_is_the_programs(self, config):
        model = get_model("mellum2_tiny", num_classes=1000, scan_layers=True)
        prog = jax.jit(model.init)(jax.random.key(SEED),
                                   jnp.zeros((2, 64), jnp.int32))["params"]
        worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), compare._as_dict(prog),
            reference.init_params(config, SEED))))
        assert worst < 1e-7


def _layer_params(config, held=None):
    """One layer's expert parameters from the reference's seeded init, cut
    to the experts ``held = (first, count)``."""
    moe = jax.tree_util.tree_map(
        lambda a: a[0],
        reference.init_params(config, SEED)["layers"]["layer_0"]["moe"])
    if held:
        first, count = held
        moe = dict(moe, **{k: moe[k][first:first + count]
                           for k in ("w1", "w2", "w3")})
    return moe


class TestRoutedExperts:
    def test_the_shares_add_up(self, config):
        """Four expert-parallel ranks, 2 of 8 experts each: every rank
        routes over all 8 and returns its own experts' part; the parts sum
        to the UNCUT reference's whole layer."""
        a = reference.arch_of(config)
        x = jax.random.normal(jax.random.key(1), (2, 64, 64))
        whole = reference._experts(x, _layer_params(config), a, "float32")
        parts = [RoutedExperts(8, 32, 2, experts_held=(first, 2)).apply(
            {"params": _layer_params(config, (first, 2))}, x)
            for first in (0, 2, 4, 6)]
        assert all(float(jnp.abs(p).max()) > 0 for p in parts)
        np.testing.assert_allclose(sum(parts), whole, atol=1e-6)
        # and one rank alone is the reference given the same share
        share = reference._experts(
            x, _layer_params(config, (2, 2)), dict(a, held=(2, 2)), "float32")
        np.testing.assert_allclose(parts[1], share, atol=1e-6)

    @pytest.mark.parametrize("held", [None, (2, 2)])
    def test_no_token_dropped_when_one_expert_takes_every_token(self, config,
                                                                held):
        """A router that sends every token to expert 3 first (and to 5
        second): 128 rows on one expert of 8, sixteen times an even share,
        and the layer still computes every one of them."""
        a = reference.arch_of(config)
        params = _layer_params(config, held)
        params["gate"] = {"kernel": jnp.zeros((64, 8)).at[:, 3].set(1.0)
                          .at[:, 5].set(0.5)}
        x = jnp.abs(jax.random.normal(jax.random.key(2), (2, 64, 64)))
        layer = RoutedExperts(8, 32, 2, experts_held=held)
        out, mut = layer.apply({"params": params}, x, mutable=["counters"])
        ref = reference._experts(x, params, dict(a, held=held or (0, 8)),
                                 "float32")
        np.testing.assert_allclose(out, ref, atol=1e-6)
        rows, = mut["counters"]["expert_rows"]
        load, = mut["counters"]["expert_load_max_over_mean"]
        assert (float(rows), float(load)) == ((256.0, 4.0) if held is None
                                              else (128.0, 2.0))

    def test_gradients_reach_router_and_experts(self, config):
        a = reference.arch_of(config)
        params = _layer_params(config)
        x = jax.random.normal(jax.random.key(3), (2, 64, 64))
        prog = jax.grad(lambda p, x: (RoutedExperts(8, 32, 2).apply(
            {"params": p}, x) ** 2).sum(), (0, 1))(params, x)
        ref = jax.grad(lambda p, x: (reference._experts(
            x, p, a, "float32") ** 2).sum(), (0, 1))(params, x)
        for got, want in zip(jax.tree_util.tree_leaves(prog),
                             jax.tree_util.tree_leaves(ref)):
            assert float(jnp.abs(want).max()) > 0
            np.testing.assert_allclose(got, want, atol=1e-7, rtol=1e-4)


class TestArchitectureAsData:
    def test_registry_and_predicates_read_the_record(self):
        assert is_attention_model("mellum2_12b_a2p5b")
        assert remat_name_vocab("mellum2_tiny")[-1] == "moe_dispatch"
        a = ARCHS["mellum2_12b_a2p5b"]
        assert (a.layers, a.experts_held, a.vocab, a.head_dim * a.heads) == (
            4, (0, 16), 24576, 4096)
        assert dict(a.published) == {"layers": 28, "experts": 64,
                                     "vocab": 98304}

    def test_manifest_round_trip(self):
        for arch in ARCHS.values():
            again = DecoderArch.from_manifest(
                json.loads(json.dumps(arch.as_manifest())))
            assert again == arch

    def test_published_config_agrees_with_the_program(self):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               "mellum2_12b_a2p5b.json")) as f:
            a = reference.arch_of(json.load(f))
        p = ARCHS["mellum2_12b_a2p5b"]
        assert (a["hidden"], a["heads"], a["kv_heads"], a["head_dim"],
                a["window"], a["experts"], a["top_k"], a["ffn"], a["held"],
                a["vocab"], a["layers"], a["layer_types"]) == (
            p.hidden, p.heads, p.kv_heads, p.head_dim, p.window, p.experts,
            p.experts_per_token, p.expert_ffn, p.experts_held, p.vocab,
            p.layers, p.layer_types * p.periods)
        assert dict(a["rope"])["full"][1:] == p.rope_of("full").yarn
        assert reference.train_flops_per_token(
            json.load(open(os.path.join(ROOT, "benchmarks", "configs",
                                        "mellum2_12b_a2p5b.json"))),
            {"seq_len": 8192}) == pytest.approx(1.49e9, rel=0.01)


class TestThroughTheDriver:
    """``--model mellum2_tiny`` through ``main`` -> ``train_global`` -> the
    ``LocalSGDEngine`` round program, with a checkpoint."""

    @pytest.fixture(scope="class")
    def run(self, tmp_path_factory):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.main import train_main
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops
        ckpt = str(tmp_path_factory.mktemp("ckpt"))
        before = set(pallas_ops._FALLBACK_LOGGED)
        results = train_main([
            "--device", "cpu", "--model", "mellum2_tiny", "--dataset",
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
            # 4 x 128 tokens x top-2, every expert held: nothing dropped
            assert row["expert_rows"] == 4 * 128 * 2
            assert 1.0 <= row["expert_load_max_over_mean"] <= 8.0

    def test_one_worker_runs_no_probe(self, run):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu import probe
        durations, spb = probe.estimate_epoch_duration(
            None, None, None, world_size=1)
        assert durations.tolist() == [1.0]
        assert spb.tolist() == [probe.UNMEASURED]

    def test_manifest_rebuilds_the_model(self, run):
        results, ckpt, _ = run
        manifests = [os.path.join(d, f) for d, _, fs in os.walk(ckpt)
                     for f in fs if f == "MANIFEST.json"]
        assert manifests
        with open(sorted(manifests)[-1]) as f:
            meta = json.load(f)["metadata"]
        arch = DecoderArch.from_manifest(meta["arch"])
        assert arch == ARCHS["mellum2_tiny"] and meta["model"] == "mellum2_tiny"
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
