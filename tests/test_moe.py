"""Mixture-of-Experts FFN + expert parallelism (``models/moe.py``).

Correctness ladder: routing invariants (top-1, capacity, load-balance
loss); expert-sharded execution on a 4-device ``expert`` mesh vs the
dense twin (forward AND gradients); and end-to-end through the driver on
a (data=2, expert=2) mesh against the unsharded MoE data=2 run.
Beyond-reference capability (the reference is data-parallel only,
SURVEY.md 2.3).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.moe import (
    MoEFFN,
    ep_param_specs,
)


@pytest.fixture(scope="module")
def expert_mesh(devices):
    return Mesh(np.array(devices[:4]), ("expert",))


def _x(b=2, t=16, h=32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(b, t, h)), jnp.float32)


class TestMoEFFN:
    def test_output_shape_and_aux_loss(self):
        m = MoEFFN(num_experts=4, ffn_dim=64)
        x = _x()
        out, aux = m.init_with_output(jax.random.key(0), x,
                                      mutable=["params", "aux"])
        y, col = out, aux
        assert y.shape == x.shape
        lb = jax.tree_util.tree_leaves(col["aux"])[0]
        # Switch LB loss is E * sum(f_e * P_e) >= 1 with equality at
        # perfect balance; a random gate sits near 1
        assert 0.9 < float(lb) < 4.0

    def test_capacity_drops_overflow(self):
        """With capacity_factor tiny, most tokens drop -> output mostly 0
        (the caller's residual carries them)."""
        m = MoEFFN(num_experts=2, ffn_dim=16, capacity_factor=0.05)
        x = _x(b=1, t=64, h=8)
        variables = m.init(jax.random.key(0), x)
        y = m.apply(variables, x)
        # capacity = ceil(0.05 * 64 / 2) = 2 tokens per expert at most
        nonzero_rows = (np.abs(np.asarray(y[0])).sum(-1) > 1e-6).sum()
        assert nonzero_rows <= 4

    def test_sharded_matches_dense(self, expert_mesh):
        dense = MoEFFN(num_experts=4, ffn_dim=64)
        sharded_mod = MoEFFN(num_experts=4, ffn_dim=64,
                             expert_axis="expert", ep_size=4)
        x = _x(seed=1)
        params = dense.init(jax.random.key(1), x)["params"]
        specs = ep_param_specs({"moe": params}, axis="expert")["moe"]
        f = jax.jit(jax.shard_map(
            lambda p, x: sharded_mod.apply({"params": p}, x),
            mesh=expert_mesh, in_specs=(specs, P()), out_specs=P()))
        np.testing.assert_allclose(f(params, x),
                                   dense.apply({"params": params}, x),
                                   atol=1e-5)

    def test_sharded_grads_match_dense(self, expert_mesh):
        dense = MoEFFN(num_experts=4, ffn_dim=64)
        sharded_mod = MoEFFN(num_experts=4, ffn_dim=64,
                             expert_axis="expert", ep_size=4)
        x = _x(seed=2)
        params = dense.init(jax.random.key(2), x)["params"]
        specs = ep_param_specs({"moe": params}, axis="expert")["moe"]

        def loss(mod):
            def f(p, x):
                return (mod.apply({"params": p}, x) ** 2).sum()
            return f

        sh = jax.jit(jax.shard_map(loss(sharded_mod), mesh=expert_mesh,
                                   in_specs=(specs, P()), out_specs=P()))
        g = jax.grad(sh)(params, x)
        gr = jax.grad(loss(dense))(params, x)
        flat = jax.tree_util.tree_leaves_with_path(g)
        ref = dict(jax.tree_util.tree_leaves_with_path(gr))
        for path, leaf in flat:
            np.testing.assert_allclose(leaf, ref[path], atol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))


@pytest.mark.slow
class TestMoETensorParallel:
    """MoE x TP (VERDICT r3 'next' #4): per-expert Megatron sharding of
    the F dim over a 'model' mesh axis, routing replicated — the sharded
    module computes EXACTLY the unsharded MoE function."""

    @pytest.fixture(scope="class")
    def model_mesh(self, devices):
        return Mesh(np.array(devices[:4]), ("model",))

    def _specs(self, params):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.bert import tp_param_specs
        return tp_param_specs({"moe": params}, axis="model")["moe"]

    def test_tp_sharded_matches_dense(self, model_mesh):
        dense = MoEFFN(num_experts=4, ffn_dim=64)
        sharded_mod = MoEFFN(num_experts=4, ffn_dim=64,
                             model_axis="model", tp_size=4)
        x = _x(seed=3)
        params = dense.init(jax.random.key(3), x)["params"]
        specs = self._specs(params)
        f = jax.jit(jax.shard_map(
            lambda p, x: sharded_mod.apply({"params": p}, x),
            mesh=model_mesh, in_specs=(specs, P()), out_specs=P()))
        np.testing.assert_allclose(f(params, x),
                                   dense.apply({"params": params}, x),
                                   atol=1e-5)

    def test_tp_sharded_grads_match_dense(self, model_mesh):
        dense = MoEFFN(num_experts=4, ffn_dim=64)
        sharded_mod = MoEFFN(num_experts=4, ffn_dim=64,
                             model_axis="model", tp_size=4)
        x = _x(seed=4)
        params = dense.init(jax.random.key(4), x)["params"]
        specs = self._specs(params)

        def loss(mod):
            def f(p, x):
                return (mod.apply({"params": p}, x) ** 2).sum()
            return f

        sh = jax.jit(jax.shard_map(loss(sharded_mod), mesh=model_mesh,
                                   in_specs=(specs, P()), out_specs=P()))
        g = jax.grad(sh)(params, x)
        gr = jax.grad(loss(dense))(params, x)
        flat = jax.tree_util.tree_leaves_with_path(g)
        ref = dict(jax.tree_util.tree_leaves_with_path(gr))
        for path, leaf in flat:
            np.testing.assert_allclose(leaf, ref[path], atol=1e-4,
                                       err_msg=jax.tree_util.keystr(path))

    def _run(self, devices, mesh_axes):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        mesh = build_mesh(mesh_axes, devices)
        cfg = Config(model="bert_tiny", dataset="synthetic_mlm",
                     epochs_global=2, epochs_local=1, batch_size=8,
                     limit_train_samples=128, limit_eval_samples=32,
                     compute_dtype="float32", augment=False,
                     aggregation_by="weights", seed=7, num_experts=4)
        return train_global(cfg, mesh=mesh, progress=False)

    def test_driver_moe_tp_matches_unsharded(self, devices):
        base = self._run(devices[:2], {"data": 2})
        tp = self._run(devices[:4], {"data": 2, "model": 2})
        np.testing.assert_allclose(tp["global_train_losses"],
                                   base["global_train_losses"], rtol=2e-3)
        assert tp["global_train_losses"][-1] < tp["global_train_losses"][0]

    def test_driver_moe_tp_ep_matches_unsharded(self, devices):
        """3-D (data=2, model=2, expert=2): Megatron F dims over 'model'
        PLUS the expert overlay on the expert dim — still exactly the
        unsharded MoE function (routing replicated in both)."""
        base = self._run(devices[:2], {"data": 2})
        tpep = self._run(devices[:8], {"data": 2, "model": 2, "expert": 2})
        np.testing.assert_allclose(tpep["global_train_losses"],
                                   base["global_train_losses"], rtol=2e-3)
        res = tpep
        specs = [str(l.sharding.spec) for l in
                 jax.tree_util.tree_leaves(res["state"].params)]
        assert any("model" in s and "expert" in s for s in specs)


@pytest.mark.slow
class TestDriverExpertParallel:
    """MoE-BERT training expert-sharded over (data=2, expert=2) must match
    the unsharded MoE data=2 run."""

    def _run(self, devices, mesh_axes):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        mesh = build_mesh(mesh_axes, devices)
        cfg = Config(model="bert_tiny", dataset="synthetic_mlm",
                     epochs_global=2, epochs_local=1, batch_size=8,
                     limit_train_samples=128, limit_eval_samples=32,
                     compute_dtype="float32", augment=False,
                     aggregation_by="weights", seed=7, num_experts=4)
        return train_global(cfg, mesh=mesh, progress=False)

    def test_matches_unsharded_run(self, devices):
        base = self._run(devices[:2], {"data": 2})
        ep = self._run(devices[:4], {"data": 2, "expert": 2})
        np.testing.assert_allclose(ep["global_train_losses"],
                                   base["global_train_losses"], rtol=2e-3)
        assert ep["global_train_losses"][-1] < ep["global_train_losses"][0]

    def test_expert_axis_requires_experts(self, devices):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        mesh = build_mesh({"data": 2, "expert": 2}, devices[:4])
        cfg = Config(model="bert_tiny", dataset="synthetic_mlm",
                     limit_train_samples=64, limit_eval_samples=16,
                     augment=False)
        with pytest.raises(ValueError, match="expert"):
            train_global(cfg, mesh=mesh, progress=False)


@pytest.mark.slow
class TestMoEScanAndPipeline:
    """MoE x scan_layers (the sown aux lifts through ``nn.scan`` stacked)
    and MoE x pipeline parallelism (bubble-masked aux through the GPipe
    schedule, round-2 verdict item 7)."""

    def test_scanned_forward_matches_unrolled(self):
        """Same per-layer MoE params => identical logits for the two
        layouts (pattern of test_pp.TestScannedBert)."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
        loop = get_model("bert_tiny", num_classes=97, num_experts=4)
        scan = get_model("bert_tiny", num_classes=97, num_experts=4,
                         scan_layers=True)
        x = jnp.asarray(
            np.random.default_rng(0).integers(0, 97, (2, 16)), jnp.int32)
        pl_ = loop.init(jax.random.key(1), x, train=False)["params"]
        ps = {k: v for k, v in pl_.items() if not k.startswith("layer")}
        ps["layers"] = {"layer": jax.tree.map(
            lambda *ls: jnp.stack(ls), pl_["layer0"], pl_["layer1"])}
        np.testing.assert_allclose(
            scan.apply({"params": ps}, x, train=False),
            loop.apply({"params": pl_}, x, train=False), atol=1e-5)

    def test_scanned_aux_is_stacked_and_sums_match(self):
        """The scanned model's sown aux carries a leading layer axis and
        its total equals the unrolled model's per-layer scalar sum."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
        loop = get_model("bert_tiny", num_classes=97, num_experts=4)
        scan = get_model("bert_tiny", num_classes=97, num_experts=4,
                         scan_layers=True)
        x = jnp.asarray(
            np.random.default_rng(1).integers(0, 97, (2, 16)), jnp.int32)
        pl_ = loop.init(jax.random.key(2), x, train=False)["params"]
        ps = {k: v for k, v in pl_.items() if not k.startswith("layer")}
        ps["layers"] = {"layer": jax.tree.map(
            lambda *ls: jnp.stack(ls), pl_["layer0"], pl_["layer1"])}
        _, mut_s = scan.apply({"params": ps}, x, train=True,
                              mutable=["aux"])
        _, mut_l = loop.apply({"params": pl_}, x, train=True,
                              mutable=["aux"])
        leaves_s = jax.tree_util.tree_leaves(mut_s["aux"])
        assert any(l.ndim >= 1 and l.shape[0] == 2 for l in leaves_s)
        tot_s = sum(float(jnp.sum(l)) for l in leaves_s)
        tot_l = sum(float(jnp.sum(l))
                    for l in jax.tree_util.tree_leaves(mut_l["aux"]))
        np.testing.assert_allclose(tot_s, tot_l, rtol=1e-5)

    def _run(self, devices, mesh_axes, **kw):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        mesh = build_mesh(mesh_axes, devices)
        # generous capacity so no token drops either way: per-microbatch
        # routing then dispatches identically to full-batch routing and
        # only the aux-loss batching differs (microbatch mean vs full-
        # batch value), kept out of the trajectory with aux weight 0
        cfg = Config(model="bert_tiny", dataset="synthetic_mlm",
                     epochs_global=2, epochs_local=1, batch_size=8,
                     limit_train_samples=128, limit_eval_samples=32,
                     compute_dtype="float32", augment=False,
                     aggregation_by="weights", seed=7, num_experts=4,
                     expert_capacity_factor=2.0, moe_aux_weight=0.0, **kw)
        return train_global(cfg, mesh=mesh, progress=False)

    def test_driver_moe_pp_matches_unsharded(self, devices):
        base = self._run(devices[:2], {"data": 2})
        pp = self._run(devices[:4], {"data": 2, "pipe": 2})
        np.testing.assert_allclose(pp["global_train_losses"],
                                   base["global_train_losses"], rtol=2e-3)
        assert pp["global_train_losses"][-1] < pp["global_train_losses"][0]

    def test_driver_moe_pp_ep_trains(self, devices):
        """3-D: (data=2, pipe=2, expert=2) — stacked layer axis over
        'pipe', expert stacks over 'expert' (pp_ep_param_specs), with the
        default aux weight active."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        mesh = build_mesh({"data": 2, "pipe": 2, "expert": 2}, devices[:8])
        cfg = Config(model="bert_tiny", dataset="synthetic_mlm",
                     epochs_global=2, epochs_local=1, batch_size=8,
                     limit_train_samples=128, limit_eval_samples=32,
                     compute_dtype="float32", augment=False,
                     aggregation_by="weights", seed=7, num_experts=4)
        res = train_global(cfg, mesh=mesh, progress=False)
        assert np.isfinite(res["global_train_losses"]).all()
        assert res["global_train_losses"][-1] < res["global_train_losses"][0]
        specs = [str(l.sharding.spec) for l in
                 jax.tree_util.tree_leaves(res["state"].params)]
        assert any("pipe" in s and "expert" in s for s in specs)


@pytest.mark.slow
class TestDriverMoESequenceParallel:
    """MoE x SP (r5, guard lifted): each seq-parallel device routes its
    own chunk of every sequence — a declared semantics shift vs the
    unchunked run (per-chunk capacity), proven the same two-sided way as
    FSDP x MoE: the SP run itself must learn, and the EP-sharded triple
    composition must reproduce it EXACTLY (expert sharding touches no
    routing)."""

    def _run(self, devices, mesh_axes, **kw):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        mesh = build_mesh(mesh_axes, devices)
        cfg = Config(model="bert_tiny", dataset="synthetic_mlm",
                     epochs_global=2, epochs_local=1, batch_size=8,
                     limit_train_samples=128, limit_eval_samples=32,
                     compute_dtype="float32", augment=False,
                     aggregation_by="weights", seed=7, num_experts=4, **kw)
        return train_global(cfg, mesh=mesh, progress=False)

    @pytest.fixture(scope="class")
    def moe_sp_run(self, devices):
        return self._run(devices[:4], {"data": 2, "seq": 2},
                         sequence_parallel="ring")

    def test_moe_sp_runs_and_learns(self, moe_sp_run):
        losses = moe_sp_run["global_train_losses"]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]

    def test_moe_sp_ep_matches_moe_sp_twin(self, devices, moe_sp_run):
        ep = self._run(devices[:8], {"data": 2, "seq": 2, "expert": 2},
                       sequence_parallel="ring")
        np.testing.assert_allclose(ep["global_train_losses"],
                                   moe_sp_run["global_train_losses"],
                                   rtol=2e-3)


def _assert_params_close(res, ref, rtol=2e-3, atol=2e-4):
    """Final-parameter comparison between two driver runs with identical
    parameter structure (shared by the 1F1B MoE tests below)."""
    for a, b in zip(jax.tree_util.tree_leaves(res["state"].params),
                    jax.tree_util.tree_leaves(ref["state"].params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


@pytest.mark.slow
class TestDriverMoEOneF1B:
    """1F1B x MoE (r5, the final 1F1B exclusion lifted): the stage
    applies with mutable aux so the sown load-balance losses are
    captured, the schedule adds them to its loss carry per valid fwd
    slot, and the backward seeds the aux output's cotangent with the
    (scaled) aux weight — differentiated through the schedule.  GPipe
    under the same microbatching routes identically, so the 1F1B run
    must reproduce the GPipe moe x pp run."""

    def _run(self, devices, mesh_axes, **kw):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        mesh = build_mesh(mesh_axes, devices)
        cfg = Config(model="bert_tiny", dataset="synthetic_mlm",
                     epochs_global=2, epochs_local=1, batch_size=8,
                     limit_train_samples=128, limit_eval_samples=32,
                     compute_dtype="float32", augment=False,
                     aggregation_by="weights", seed=7, num_experts=4,
                     pp_microbatches=2, **kw)
        return train_global(cfg, mesh=mesh, progress=False)

    def test_1f1b_moe_matches_gpipe(self, devices):
        """Default aux weight ACTIVE: the trajectory only matches the
        GPipe twin if the aux loss is both captured and differentiated
        correctly through the schedule."""
        gpipe = self._run(devices[:4], {"data": 2, "pipe": 2})
        onef = self._run(devices[:4], {"data": 2, "pipe": 2},
                         pp_schedule="1f1b")
        np.testing.assert_allclose(onef["global_train_losses"],
                                   gpipe["global_train_losses"], rtol=2e-3)
        _assert_params_close(onef, gpipe)

    def test_1f1b_moe_ep_matches_gpipe_ep(self, devices):
        """The EP triple: expert stacks sharded over 'expert' behind the
        'pipe' layer dim, under the 1F1B schedule.  Params compared too
        (same structure): an EP-specific aux-cotangent bug below loss
        visibility would otherwise pass (code-review r5)."""
        gpipe = self._run(devices[:8], {"data": 2, "pipe": 2, "expert": 2})
        onef = self._run(devices[:8], {"data": 2, "pipe": 2, "expert": 2},
                         pp_schedule="1f1b")
        np.testing.assert_allclose(onef["global_train_losses"],
                                   gpipe["global_train_losses"], rtol=2e-3)
        _assert_params_close(onef, gpipe)

    def test_1f1b_moe_sp_matches_gpipe(self, devices):
        """The deepest composition in the framework: 1F1B x MoE x SP on
        a (data, pipe, seq) mesh — masked schedule slots (SP ring), aux
        capture + weight-valued cotangent (MoE), per-microbatch head
        loss, all at once.  GPipe with the identical chunking and
        microbatching computes the same function, so the trajectories
        must agree."""
        gpipe = self._run(devices[:8], {"data": 2, "pipe": 2, "seq": 2},
                          sequence_parallel="ring")
        onef = self._run(devices[:8], {"data": 2, "pipe": 2, "seq": 2},
                         sequence_parallel="ring", pp_schedule="1f1b")
        np.testing.assert_allclose(onef["global_train_losses"],
                                   gpipe["global_train_losses"], rtol=2e-3)
        # looser atol than the pure-MoE twins: under SP the 1F1B bwd
        # remats the ring attention (a different fp32 path than GPipe's
        # stored residuals) and Adam amplifies the noise, worst on
        # sparsely-updated embedding rows (see test_pp.py's 1f1b_sp
        # leaf-aware bounds)
        _assert_params_close(onef, gpipe, atol=5e-3)


# ----------------------------------------------------------------------
# The dispatch both routed layers share, on its row buffer (ISSUE 33)
# ----------------------------------------------------------------------

N_TOK, TOP_K, HID, FFN = 64, 2, 32, 48     # 128 pairs a step


def _routing(case: str, width: int, first: int, held: int, c: int):
    """``expert_idx`` [N_TOK, TOP_K] for a case, and the rows it puts on
    the ``held`` experts from ``first`` on."""
    rng = np.random.default_rng(5)
    away = np.array([e for e in range(width)
                     if not first <= e < first + held])
    if case == "seeded":
        idx = np.argsort(rng.normal(size=(N_TOK, width)), -1)[:, :TOP_K]
    elif case == "every_pair_held":
        idx = first + rng.integers(0, held, size=(N_TOK, TOP_K))
    else:
        rows = {"none_held": 0, "rows_fill_buffer": c,
                "rows_one_over": c + 1, "all_experts_held": 0}[case]
        idx = rng.choice(away, size=N_TOK * TOP_K) if len(away) else \
            rng.integers(0, width, size=N_TOK * TOP_K)
        here = rng.choice(N_TOK * TOP_K, size=rows, replace=False)
        idx[here] = first + rng.integers(0, held, size=rows)
        idx = idx.reshape(N_TOK, TOP_K)
    on_held = int(((idx >= first) & (idx < first + held)).sum())
    return jnp.asarray(idx, jnp.int32), on_held


def _per_token_loop(toks, idx, weights, first, params):
    """The plain layer: every token's every choice in turn, through its
    expert's three matrices if the expert is held, nothing shared with
    the program."""
    w1, w3, w2 = params
    out = []
    for t in range(toks.shape[0]):
        acc = jnp.zeros(toks.shape[1], jnp.float32)
        for j in range(idx.shape[1]):
            e = int(idx[t, j]) - first
            if 0 <= e < w1.shape[0]:
                h = jax.nn.silu(toks[t] @ w1[e]) * (toks[t] @ w3[e])
                acc = acc + weights[t, j] * (h @ w2[e])
        out.append(acc)
    return jnp.stack(out)


def _has_loop(jaxpr) -> bool:
    return any(eqn.primitive.name == "while" or any(
        _has_loop(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


class TestRowBuffer:
    """``routed_apply`` computes on a buffer of ``C`` rows that follows the
    held share of the router, and walks what does not fit: exact for every
    routing, against the plain per-token loop, values and the gradients
    with respect to the tokens, the weights and each expert matrix."""

    def _inputs(self, held):
        rng = np.random.default_rng(3)
        f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
        params = (0.2 * f32(held, HID, FFN), 0.2 * f32(held, HID, FFN),
                  0.2 * f32(held, FFN, HID))
        return (f32(N_TOK, HID), jnp.abs(f32(N_TOK, TOP_K)), params,
                f32(N_TOK, HID))

    @pytest.mark.parametrize("case,width,first,held", [
        ("seeded", 8, 3, 1),               # a held share of 1/8
        ("every_pair_held", 16, 4, 2),     # every chunk walked
        ("none_held", 16, 4, 2),
        ("rows_fill_buffer", 16, 4, 2),
        ("rows_one_over", 16, 4, 2),
        ("all_experts_held", 4, 0, 4),     # the worst case is the buffer
    ])
    def test_against_the_per_token_loop(self, assert_within_ulps, case,
                                        width, first, held):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import moe
        m_pad, c = moe.row_buffer(N_TOK * TOP_K, held, width)
        assert m_pad == N_TOK * TOP_K
        assert c == (m_pad if held == width else 24)
        idx, on_held = _routing(case, width, first, held, c)
        toks, weights, params, probe = self._inputs(held)

        def program(toks, weights, params):
            out, sizes = moe.routed_apply(
                toks, idx, weights, first, held,
                moe.Experts(moe._swiglu_experts, params, width))
            return (out * probe).sum(), (out, sizes)

        def plain(toks, weights, params):
            out = _per_token_loop(toks, idx, weights, first, params)
            return (out * probe).sum(), out

        grads, (out, sizes) = jax.jit(jax.grad(
            program, (0, 1, 2), has_aux=True))(toks, weights, params)
        want_grads, want = jax.grad(plain, (0, 1, 2), has_aux=True)(
            toks, weights, params)
        # no pair dropped, and the overflow walked as often as it has to be
        assert int(sizes.sum()) == on_held
        walked = int(moe.overflow_chunks(sizes, c))
        assert walked == {"seeded": 0, "every_pair_held": -(-m_pad // c) - 1,
                          "none_held": 0, "rows_fill_buffer": 0,
                          "rows_one_over": 1, "all_experts_held": 0}[case]
        assert_within_ulps(out, want, 8)
        for got, ref in zip(jax.tree_util.tree_leaves(grads),
                            jax.tree_util.tree_leaves(want_grads)):
            assert_within_ulps(got, ref, 16)
        jaxpr = jax.make_jaxpr(jax.grad(program, (0, 1, 2), has_aux=True))(
            toks, weights, params).jaxpr
        assert _has_loop(jaxpr) == (held != width)

    def test_the_same_step_twice_is_the_same_bits(self):
        """The way back into the tokens is a scatter-add, in a fixed order:
        one program run twice gives the same bits, overflow walked or not."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import moe
        toks, weights, params, probe = self._inputs(2)
        for case in ("seeded", "every_pair_held"):
            idx, _ = _routing(case, 16, 4, 2, 24)
            step = jax.jit(jax.value_and_grad(lambda t, w, p: (
                moe.routed_apply(t, idx, w, 4, 2, moe.Experts(
                    moe._swiglu_experts, p, 16))[0] * probe).sum(),
                (0, 1, 2)))
            for a, b in zip(
                    jax.tree_util.tree_leaves(step(toks, weights, params)),
                    jax.tree_util.tree_leaves(step(toks, weights, params))):
                np.testing.assert_array_equal(a, b)
