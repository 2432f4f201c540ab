"""Scenario lab (ISSUE 14): the vmap'd many-worker simulator.

The tentpole gate — fp32 N=8 simulated rounds BITWISE-identical to N=8
real-mesh rounds across all three topologies x equal/weighted, under
--sanitize with zero post-warmup retraces — plus:

- comms level: ``aggregate_sim`` (stacked math, no mesh) vs the dense
  reference path inside shard_map, unmasked bitwise + the participation
  mask vs the poison screen;
- engine level: a whole SimEngine round vs a whole LocalSGDEngine round
  on the 8-device mesh, weights AND gradients aggregation;
- driver level: sanitized e2e parity (tier-1 keeps one combo per
  topology; the full 6-combo matrix and the paper's 2x3 grid are
  slow-marked);
- the scenario surface: sampling/dropout/byzantine/lr-jitter semantics,
  and the guarantee that scenario knobs at their DEFAULTS never perturb
  the parity gate (all-ones masks select the unscreened arithmetic);
- scale: N >> device count in one jit on one chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
    comms,
    mesh as mesh_lib,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
from learning_deep_neural_network_in_distributed_computing_environment_tpu.sim import SimEngine
from learning_deep_neural_network_in_distributed_computing_environment_tpu.train import (
    LocalSGDEngine,
)

N = 8
TOPOS = ("allreduce", "ring", "double_ring")
HOWS = ("equal", "weighted")


def stacked_tree(n=N, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    shapes = {"a": (13, 7), "b": (257,), "c": (3,)}
    return {k: jnp.asarray(rng.normal(size=(n, *s)) * scale, jnp.float32)
            for k, s in shapes.items()}


def trees_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


def mesh1():
    return mesh_lib.build_mesh({"data": 1}, devices=jax.devices()[:1])


def base_kw(**kw):
    base = dict(model="mlp", dataset="mnist", epochs_global=2,
                epochs_local=1, batch_size=16, limit_train_samples=400,
                limit_eval_samples=100, compute_dtype="float32",
                augment=False, aggregation_by="weights", seed=1,
                compile_cache_dir="")
    base.update(kw)
    return base


def run_pair(mesh8, *, rounds=2, **kw):
    """(real N=8 on the 8-device mesh, simulated N=8 on one device) —
    identical config, deterministic probe/walls, sanitized."""
    kw = base_kw(epochs_global=rounds, sanitize=True, **kw)
    sims = np.full(N, 1.0)
    walls = lambda e: np.full(N, 0.1)
    real = train_global(Config(**kw), mesh=mesh8, progress=False,
                        simulated_durations=sims,
                        simulated_round_durations=walls)
    sim = train_global(Config(**kw, sim_workers=N), progress=False,
                       simulated_durations=sims,
                       simulated_round_durations=walls)
    return real, sim


# ---------------------------------------------------------------------
# comms level: aggregate_sim vs the flat-primitives reference path
# ---------------------------------------------------------------------
class TestAggregateSim:
    def _real(self, mesh8, tree, how, topo, poison=None):
        def pw(t, *rest):
            sq = jax.tree_util.tree_map(lambda a: a[0], t)
            if rest:
                out, _okf = comms.aggregate(sq, how=how, topology=topo,
                                            local_weight=0.3,
                                            poison=rest[0][0])
            else:
                out = comms.aggregate(sq, how=how, topology=topo,
                                      local_weight=0.3)
            return jax.tree_util.tree_map(lambda a: a[None], out)
        specs = (P("data"),) * (2 if poison is not None else 1)
        f = jax.jit(jax.shard_map(pw, mesh=mesh8, in_specs=specs,
                                  out_specs=P("data")))
        return f(tree, poison) if poison is not None else f(tree)

    def _sim(self, tree, how, topo):
        sim, res = jax.jit(functools.partial(
            comms.aggregate_sim, how=how, topology=topo,
            local_weight=0.3))(tree)
        assert res is None
        return sim

    @pytest.mark.parametrize("how,topo", [
        ("equal", "allreduce"), ("equal", "ring"), ("equal", "double_ring"),
        ("weighted", "allreduce")])
    def test_bitwise_vs_dense_reference(self, mesh8, topo, how):
        # the simulator's sync IS the dense path's arithmetic: stacked
        # fp32 blends bitwise == the shard_map collectives (rank-order
        # fold == psum, roll == ppermute) wherever the blend leaves the
        # compiler no contraction choice
        tree = stacked_tree(scale=100.0)
        assert_trees_equal(self._real(mesh8, tree, how, topo),
                           self._sim(tree, how, topo))

    @pytest.mark.parametrize("topo", ["ring", "double_ring"])
    def test_weighted_gossip_within_ulps_of_dense_reference(
            self, mesh8, assert_within_ulps, topo):
        # w*own + (1-w)*peer(s): two or three products and their sum,
        # which LLVM contracts into FMAs one way in the stacked program
        # and another in the shard_map one.  Each choice moves one
        # rounding of a term, and a term is at most the leaf's largest
        # input, so the two differ by an ulp or so of the LEAF'S largest
        # magnitude wherever the element lies (a blend that cancels to
        # -0.999 carries the error of its 1e2-sized terms: no
        # per-element rtol holds).  Read on jax 0.9.0's CPU backend over
        # six seeds at scale 1 and 100: at most 1.0 ulp of the leaf's
        # largest magnitude (3.05e-05 where it is 287), in a third of
        # the elements; 4 allowed.  Inside the real round programs the
        # same cells are BITWISE: TestEngineParity / TestDriverParity.
        tree = stacked_tree(scale=100.0)
        real = self._real(mesh8, tree, "weighted", topo)
        sim = self._sim(tree, "weighted", topo)
        for k in tree:
            assert_within_ulps(sim[k], real[k], ulps=4)

    def test_fold_matches_psum_and_roll_matches_ppermute(self, mesh8):
        # the two primitives the whole bitwise argument rests on
        x = stacked_tree()["a"]
        def pw(a):
            return (lax.psum(a[0], "data")[None],
                    lax.ppermute(a[0], "data",
                                 comms.ring_neighbors(N, 2))[None])
        f = jax.jit(jax.shard_map(pw, mesh=mesh8, in_specs=P("data"),
                                  out_specs=(P("data"), P("data"))))
        ps, perm = f(x)
        fold = jax.jit(comms.sim_fold)(x)
        np.testing.assert_array_equal(np.asarray(ps)[0], np.asarray(fold))
        np.testing.assert_array_equal(np.asarray(perm),
                                      np.asarray(jnp.roll(x, 2, axis=0)))

    @pytest.mark.parametrize("topo", TOPOS)
    @pytest.mark.parametrize("how", HOWS)
    def test_participation_mask_mirrors_poison_screen(self, mesh8, topo,
                                                      how):
        # the scenario masks reuse the dense poison path's renormalized
        # blends; fp32 values agree to <= 1 ulp (the select-heavy masked
        # programs fuse slightly differently across program shapes, so
        # this twin is semantic-exact, ulp-tight — the UNMASKED gate
        # above stays bitwise)
        tree = stacked_tree()
        ok = np.array([1, 1, 0, 1, 1, 1, 0, 1], np.float32)
        real = self._real(mesh8, tree, how, topo,
                          poison=jnp.asarray(ok < 1))
        sim, _ = jax.jit(functools.partial(
            comms.aggregate_sim, how=how, topology=topo,
            local_weight=0.3, ok=jnp.asarray(ok)))(tree)
        for k in tree:
            np.testing.assert_allclose(np.asarray(real[k]),
                                       np.asarray(sim[k]), rtol=2e-6,
                                       atol=1.3e-7)

    @pytest.mark.parametrize("topo", TOPOS)
    @pytest.mark.parametrize("how", HOWS)
    def test_all_ones_mask_selects_the_unscreened_values(self, topo,
                                                         how):
        # scenario knobs at their defaults compile NO mask machinery at
        # all (SimEngine.scenario_on) — the parity gate's program is the
        # unmasked one.  This case pins the adjacent property: an armed
        # scenario whose draw happens to be full participation selects
        # the unscreened VALUES via the all_ok construction — bitwise
        # for the equal blends (a pure select); the weighted blends are
        # ulp-tight (the masked program's extra branches give LLVM a
        # different FMA contraction context).
        tree = stacked_tree()
        f0 = jax.jit(functools.partial(comms.aggregate_sim, how=how,
                                       topology=topo, local_weight=0.3))
        f1 = jax.jit(functools.partial(comms.aggregate_sim, how=how,
                                       topology=topo, local_weight=0.3,
                                       ok=jnp.ones((N,))))
        a, b = f0(tree)[0], f1(tree)[0]
        if how == "equal":
            assert_trees_equal(a, b)
        else:
            for k in tree:
                np.testing.assert_allclose(np.asarray(a[k]),
                                           np.asarray(b[k]),
                                           rtol=2e-6, atol=1.3e-7)

    def test_mask_semantics_adoption_and_renormalization(self):
        # hand-checkable n=4 vector: worker 2 masked out
        x = jnp.asarray(np.array([[0.0], [4.0], [100.0], [8.0]],
                                 np.float32))
        ok = jnp.asarray(np.array([1, 1, 0, 1], np.float32))
        # allreduce equal: every row (incl. the masked) adopts the
        # survivors' mean (0+4+8)/3
        out, _ = comms.aggregate_sim({"p": x}, how="equal",
                                     topology="allreduce", ok=ok)
        np.testing.assert_allclose(np.asarray(out["p"]),
                                   np.full((4, 1), 4.0), rtol=1e-6)
        # ring equal: row 3's predecessor (2) is masked -> keeps own/1;
        # row 2 (masked) adopts its participating predecessor's payload
        out, _ = comms.aggregate_sim({"p": x}, how="equal",
                                     topology="ring", ok=ok)
        got = np.asarray(out["p"]).ravel()
        np.testing.assert_allclose(got[3], 8.0, rtol=1e-6)   # (8+0)/1? no: (8)/1
        np.testing.assert_allclose(got[2], 4.0, rtol=1e-6)   # adopts w1
        np.testing.assert_allclose(got[1], 2.0, rtol=1e-6)   # (4+0)/2

    def test_compressed_wire_ef_discriminates(self):
        # single-stage EF: the time-averaged consensus of repeated
        # syncs tracks the fp32 fixed point closer than plain bf16
        # (the gossip engine's EF argument, on the simulated wire)
        rng = np.random.default_rng(3)
        base = jnp.asarray(rng.normal(size=(N, 64)) * 1e-3, jnp.float32)
        tgt, _ = comms.aggregate_sim({"p": base}, how="equal",
                                     topology="allreduce")

        def run(ef):
            res = {"p": jnp.zeros_like(base)} if ef else None
            x = {"p": base}
            outs = []
            for _ in range(24):
                x, res = comms.aggregate_sim(
                    x, how="equal", topology="allreduce",
                    wire_dtype=jnp.bfloat16,
                    residual=res)
                if not ef:
                    res = None
                outs.append(np.asarray(x["p"]))
            return np.mean(outs[8:], axis=0)

        err_plain = np.abs(run(False) - np.asarray(tgt["p"])).mean()
        err_ef = np.abs(run(True) - np.asarray(tgt["p"])).mean()
        assert err_ef < err_plain / 2.0, (err_ef, err_plain)

    def test_sim_wire_bytes_accounting(self):
        tree = stacked_tree()
        shapes = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
                  for k, v in tree.items()}
        fp32 = comms.sim_wire_bytes(shapes, N, topology="allreduce")
        # fp32 == the dense accounting exactly
        assert fp32 == comms.sync_wire_bytes(shapes, N, mode="dense",
                                             topology="allreduce")
        assert comms.sim_wire_bytes(
            shapes, N, topology="allreduce",
            wire_dtype=jnp.bfloat16) == fp32 // 2
        assert comms.sim_wire_bytes(
            shapes, N, topology="allreduce",
            wire_dtype=jnp.int8) == fp32 // 4
        # double_ring sends every leaf twice per round
        assert comms.sim_wire_bytes(
            shapes, N, topology="double_ring") == 2 * fp32
        assert comms.sim_wire_bytes(shapes, 1, topology="ring") == 0


# ---------------------------------------------------------------------
# engine level: whole SimEngine rounds vs whole real-mesh rounds
# ---------------------------------------------------------------------
def make_packs(n=N, steps=4, b=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, steps, b, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, (n, steps, b)).astype(np.int32)
    m = np.ones((n, steps, b), np.float32)
    return x, y, m


def engine_pair(mesh8, **kw):
    cfg_kw = base_kw(**kw)
    cfg_kw.pop("epochs_global")
    model = get_model("mlp", num_classes=10, hidden=16)
    real = LocalSGDEngine(model, mesh8, Config(**cfg_kw))
    sim = SimEngine(model, mesh1(), Config(**cfg_kw, sim_workers=N))
    return real, sim


class TestEngineParity:
    @pytest.mark.parametrize("topo,how", [("allreduce", "weighted"),
                                          ("ring", "equal"),
                                          ("double_ring", "weighted")])
    def test_round_bitwise_weights_mode(self, mesh8, topo, how):
        real_e, sim_e = engine_pair(mesh8, topology=topo,
                                    aggregation_type=how, epochs_local=2)
        sample = np.zeros((8, 28, 28, 1), np.float32)
        rs = real_e.init_state(jax.random.key(0), sample)
        ss = sim_e.init_state(jax.random.key(0), sample)
        assert_trees_equal(jax.device_get(rs), jax.device_get(ss))
        tp, vp = make_packs(), make_packs(seed=1)
        for _ in range(2):
            rs, rmx = real_e.round(rs, tp, vp)
            ss, smx = sim_e.round(ss, tp, vp)
        assert_trees_equal(jax.device_get(rs.params),
                           jax.device_get(ss.params))
        assert_trees_equal(jax.device_get(rs.opt_state),
                           jax.device_get(ss.opt_state))
        np.testing.assert_array_equal(np.asarray(rs.rng),
                                      np.asarray(ss.rng))
        for k in rmx:
            np.testing.assert_array_equal(
                np.asarray(rmx[k]), np.asarray(smx[k]), err_msg=k)

    def test_round_bitwise_gradients_mode(self, mesh8):
        # reference default: collectives on the stale last-batch grads,
        # params untouched, only the aggregated norm observable
        real_e, sim_e = engine_pair(mesh8, aggregation_by="gradients")
        sample = np.zeros((8, 28, 28, 1), np.float32)
        rs = real_e.init_state(jax.random.key(0), sample)
        ss = sim_e.init_state(jax.random.key(0), sample)
        tp, vp = make_packs(), make_packs(seed=1)
        rs, rmx = real_e.round(rs, tp, vp)
        ss, smx = sim_e.round(ss, tp, vp)
        assert_trees_equal(jax.device_get(rs.params),
                           jax.device_get(ss.params))
        np.testing.assert_array_equal(np.asarray(rmx["agg_grad_norm"]),
                                      np.asarray(smx["agg_grad_norm"]))

    def test_sync_stats_schema_and_sim_accounting(self, mesh8):
        _, sim_e = engine_pair(mesh8)
        sample = np.zeros((8, 28, 28, 1), np.float32)
        ss = sim_e.init_state(jax.random.key(0), sample)
        ss, _ = sim_e.round(ss, make_packs(), make_packs(seed=1))
        stats = sim_e.last_sync_stats
        # identical schema to every real engine's row (ISSUE 16 added
        # sync_hidden_ms, zero-filled everywhere but staleness runs)
        assert set(stats) == {"sync_bytes", "sync_mode", "sync_ms",
                              "sync_hidden_ms",
                              "sync_bytes_ici", "sync_bytes_dcn"}
        assert stats["sync_hidden_ms"] == 0.0
        assert stats["sync_mode"] == "sim"
        assert stats["sync_bytes"] == comms.sim_wire_bytes(
            sim_e.params_template, N, topology="allreduce")
        # per-worker state bytes: each simulated worker owns 1/N of the
        # stacked rows even though all rows live on one chip
        bts = sim_e.state_resident_bytes(ss)
        total_params = sum(
            int(np.prod(np.shape(x))) * 4
            for x in jax.tree_util.tree_leaves(ss.params))
        assert bts["params"] == total_params // N


# ---------------------------------------------------------------------
# driver level: the sanitized e2e gate
# ---------------------------------------------------------------------
class TestDriverParity:
    # one combo per topology stays tier-1; the full 6-combo matrix is
    # the slow-marked case below (tier-1 wall hygiene, ISSUE 14)
    @pytest.mark.parametrize("topo,how", [("allreduce", "equal"),
                                          ("ring", "weighted"),
                                          ("double_ring", "equal")])
    def test_sim_bitwise_vs_real_mesh_sanitized(self, mesh8, topo, how):
        real, sim = run_pair(mesh8, topology=topo, aggregation_type=how)
        assert real["global_train_losses"] == sim["global_train_losses"]
        assert real["global_val_accuracies"] == \
            sim["global_val_accuracies"]
        assert real["all_epochs_losses"] == sim["all_epochs_losses"]
        assert_trees_equal(jax.device_get(real["state"].params),
                           jax.device_get(sim["state"].params))
        assert_trees_equal(real["variables"], sim["variables"])
        # zero post-warmup retraces on BOTH paths (--sanitize raised
        # otherwise; the rows record it)
        for res in (real, sim):
            assert res["sanitize"]["enabled"] is True
            assert res["sanitize"]["retrace_count"] == 0
            assert res["sanitize"]["donation_failures"] == 0

    @pytest.mark.slow
    @pytest.mark.parametrize("topo", TOPOS)
    @pytest.mark.parametrize("how", HOWS)
    def test_full_matrix_sim_bitwise_vs_real_mesh(self, mesh8, topo,
                                                  how):
        real, sim = run_pair(mesh8, topology=topo, aggregation_type=how)
        assert real["global_train_losses"] == sim["global_train_losses"]
        assert_trees_equal(jax.device_get(real["state"].params),
                           jax.device_get(sim["state"].params))

    def test_sim_telemetry_and_provenance(self, mesh8):
        _, sim = run_pair(mesh8)
        s = sim["sim"]
        assert s["workers"] == N and s["rounds"] == 2
        assert s["rounds_per_s"] is None or s["rounds_per_s"] > 0
        assert s["per_worker_sync_bytes"] > 0
        assert s["per_worker_state_bytes"]["params"] > 0
        assert s["scenario"] == {"sample_frac": 1.0, "dropout": 0.0,
                                 "byzantine": None, "lr_jitter": 0.0}
        assert "rounds_scenario" not in s   # nothing armed, no draws
        assert sim["sync_engine"]["mode"] == "sim"
        assert sim["sync_engine"]["levels"] == {"inner": "sim",
                                                "outer": None}
        # per-round rows keep the uniform telemetry schema
        for t in sim["round_timings"]:
            assert t["sync_mode"] == "sim"
            assert t["sync_bytes"] == s["per_worker_sync_bytes"]

    def test_more_workers_than_devices_one_chip(self):
        # the point of the lab: N=32 workers where the mesh caps at 8
        n = 32
        cfg = Config(**base_kw(), sim_workers=n)
        res = train_global(cfg, progress=False,
                           simulated_durations=np.full(n, 1.0),
                           simulated_round_durations=lambda e: np.full(
                               n, 0.1))
        assert res["sim"]["workers"] == n
        assert len(res["all_workers_losses"]) == n
        assert all(len(w) > 0 for w in res["all_workers_losses"])
        losses = res["global_train_losses"]
        assert losses[-1] < losses[0]
        # every worker-stacked state leaf carries the full simulated axis
        assert all(x.shape[0] == n for x in
                   jax.tree_util.tree_leaves(res["state"].params))

    @pytest.mark.slow
    def test_paper_matrix_2x3_sim_vs_real(self, mesh8):
        """The paper's full 2x3 grid (balanced/disbalanced x allreduce/
        ring/double_ring) at simulated N=8: per-topology consensus
        bitwise-matches the real-mesh twin, and the non-IID ordering the
        paper reports (skewed shards hurt accuracy) holds on the
        aggregate."""
        acc = {"balanced": [], "disbalanced": []}
        for mode in ("balanced", "disbalanced"):
            for topo in TOPOS:
                real, sim = run_pair(mesh8, rounds=3, topology=topo,
                                     data_mode=mode, fixed_ratio=0.8,
                                     epochs_local=2)
                assert real["global_train_losses"] == \
                    sim["global_train_losses"], (mode, topo)
                assert_trees_equal(
                    jax.device_get(real["state"].params),
                    jax.device_get(sim["state"].params))
                acc[mode].append(sim["global_val_accuracies"][-1])
        assert np.mean(acc["balanced"]) > np.mean(acc["disbalanced"]), acc


# ---------------------------------------------------------------------
# the scenario surface
# ---------------------------------------------------------------------
def sim_run(n=8, rounds=3, **kw):
    cfg = Config(**base_kw(epochs_global=rounds, **kw), sim_workers=n)
    return train_global(cfg, progress=False,
                        simulated_durations=np.full(n, 1.0),
                        simulated_round_durations=lambda e: np.full(
                            n, 0.1))


class TestScenarios:
    def test_sampling_draws_and_telemetry(self):
        res = sim_run(n=8, sim_sample_frac=0.5)
        draws = res["sim"]["rounds_scenario"]
        assert len(draws) == 3
        assert all(d["active"] == 4 for d in draws)  # ceil(0.5 * 8)
        assert res["sim"]["scenario"]["sample_frac"] == 0.5
        assert np.isfinite(res["global_train_losses"]).all()

    def test_sampling_is_seeded_deterministic(self):
        a = sim_run(n=8, sim_sample_frac=0.5)
        b = sim_run(n=8, sim_sample_frac=0.5)
        assert a["global_train_losses"] == b["global_train_losses"]
        assert a["sim"]["rounds_scenario"] == b["sim"]["rounds_scenario"]

    def test_dropout_freezes_the_dropped_worker(self):
        # dropout ~1 never drops EVERY worker (validated < 1), but a
        # high rate on a small grid exercises the freeze: a dropped
        # worker's whole round is a no-op — its lr_epoch clock must lag
        # the rounds it missed
        res = sim_run(n=4, rounds=4, sim_dropout=0.45)
        dropped_total = sum(d["dropped"]
                            for d in res["sim"]["rounds_scenario"])
        assert dropped_total > 0   # seeded: this config does drop
        clocks = np.asarray(res["state"].lr_epoch)
        full_clock = 4 * 1   # rounds x epochs_local
        assert clocks.min() < full_clock
        assert clocks.max() <= full_clock

    def test_sampled_out_worker_adopts_the_consensus(self):
        # allreduce x equal with sampling: after the sync EVERY
        # non-dropped worker holds the same consensus (sampled-out rows
        # adopt), so all params rows are identical each round
        res = sim_run(n=8, sim_sample_frac=0.5)
        p = jax.device_get(res["state"].params)
        for leaf in jax.tree_util.tree_leaves(p):
            assert np.all(leaf == leaf[:1]), "rows diverged"

    def test_byzantine_signflip_changes_consensus_and_hurts(self):
        clean = sim_run(n=8)
        byz = sim_run(n=8, sim_byzantine="signflip:3")
        assert clean["global_train_losses"] != byz["global_train_losses"]
        # three sign-flipped contributions out of eight slow convergence
        assert byz["global_train_losses"][-1] > \
            clean["global_train_losses"][-1]
        assert byz["sim"]["scenario"]["byzantine"] == "signflip:3"

    def test_byzantine_noise_is_seeded_and_bounded(self):
        a = sim_run(n=8, sim_byzantine="noise:2:0.01")
        b = sim_run(n=8, sim_byzantine="noise:2:0.01")
        assert a["global_train_losses"] == b["global_train_losses"]
        assert np.isfinite(a["global_train_losses"]).all()

    def test_lr_jitter_spreads_worker_trajectories(self):
        # gradients mode keeps params per-worker (no FedAvg overwrite),
        # so a per-worker LR spread must leave different rows
        flat = sim_run(n=4, aggregation_by="gradients")
        jit_ = sim_run(n=4, aggregation_by="gradients",
                       sim_lr_jitter=0.5)
        p = jax.device_get(jit_["state"].params)
        leaf = jax.tree_util.tree_leaves(p)[0]
        assert not np.all(leaf == leaf[:1]), "jitter had no effect"
        assert flat["global_train_losses"] != jit_["global_train_losses"]

    def test_defaults_compile_no_scenario_machinery(self, mesh8):
        # scenario_on is a compile-time arming: the default program has
        # no mask inputs at all (the parity gate's program)
        _, sim_e = engine_pair(mesh8)
        assert sim_e.scenario_on is False
        assert sim_e.lr_scale is None
        cfg = Config(**{**base_kw(), "epochs_global": 2},
                     sim_workers=N, sim_dropout=0.3)
        armed = SimEngine(get_model("mlp", num_classes=10, hidden=16),
                          mesh1(), cfg)
        assert armed.scenario_on is True

    def test_compressed_wire_runs_with_ef_state(self):
        res = sim_run(n=8, sync_dtype="bfloat16", sync_compression="ef",
                      topology="ring")
        assert res["sim"]["per_worker_state_bytes"]["ef_residual"] > 0
        assert res["sim"]["per_worker_sync_bytes"] == \
            res["sim"]["per_worker_state_bytes"]["params"] // 2
        assert np.isfinite(res["global_train_losses"]).all()


# ---------------------------------------------------------------------
# eager config validation (ISSUE 14 satellite)
# ---------------------------------------------------------------------
class TestSimConfigValidation:
    @pytest.mark.parametrize("kw,frag", [
        (dict(chaos="kill@1:w0"), "--chaos"),
        (dict(num_slices=2, topology="ring"), "--num_slices"),
        (dict(shard_redundancy="buddy"), "buddy"),
        (dict(opt_placement="sharded"), "--opt_placement"),
        (dict(param_residency="resident"), "resident"),
        (dict(sync_mode="sharded"), "--sync_mode"),
        (dict(stream_chunk_steps=4), "--stream_chunk_steps"),
        (dict(checkpoint_dir="/tmp/ck"), "--checkpoint_dir"),
        (dict(num_workers=4), "--num_workers"),
        (dict(mesh_shape="data=4,model=2"), "inner mesh axes"),
        (dict(sequence_parallel="ring"), "--sequence_parallel"),
    ])
    def test_real_mesh_only_features_rejected_eagerly(self, kw, frag):
        with pytest.raises(ValueError, match="sim_workers"):
            try:
                Config(**base_kw(), sim_workers=8, **kw)
            except ValueError as e:
                assert frag in str(e), (kw, str(e))
                raise

    @pytest.mark.parametrize("kw", [
        dict(sim_sample_frac=0.0), dict(sim_sample_frac=1.5),
        dict(sim_dropout=-0.1), dict(sim_dropout=1.0),
        dict(sim_lr_jitter=1.0), dict(sim_lr_jitter=-0.5),
    ])
    def test_scenario_ranges_checked(self, kw):
        with pytest.raises(ValueError):
            Config(**base_kw(), sim_workers=8, **kw)

    @pytest.mark.parametrize("spec", [
        "evil:2", "signflip", "signflip:0", "signflip:8",
        "signflip:2:0.5", "noise:2:-1", "noise:x",
    ])
    def test_byzantine_spec_validated(self, spec):
        with pytest.raises(ValueError):
            Config(**base_kw(), sim_workers=8, sim_byzantine=spec)

    def test_scenario_knobs_need_sim_workers(self):
        for kw in (dict(sim_dropout=0.5), dict(sim_sample_frac=0.5),
                   dict(sim_byzantine="signflip:2"),
                   dict(sim_lr_jitter=0.5)):
            with pytest.raises(ValueError, match="sim_workers"):
                Config(**base_kw(), **kw)

    def test_driver_rejects_snapshot_and_wide_mesh(self, mesh8):
        cfg = Config(**base_kw(), sim_workers=8)
        with pytest.raises(ValueError, match="ONE anchor device"):
            train_global(cfg, mesh=mesh8, progress=False)
        with pytest.raises(ValueError, match="elastic_snapshot"):
            train_global(cfg, elastic_snapshot=object(), progress=False)

    def test_valid_sim_config_accepted(self):
        cfg = Config(**base_kw(), sim_workers=256, sim_sample_frac=0.1,
                     sim_dropout=0.05, sim_byzantine="noise:8:0.5",
                     sim_lr_jitter=0.2)
        assert cfg.parse_sim_byzantine() == ("noise", 8, 0.5)
