"""Semi-synchronous rounds (ISSUE 16 tentpole).

Covers the staleness contract end to end: the delivery-blend helpers
against a numpy reference; K=0 structurally identical to the synchronous
engine (no staleness program is even built — the bitwise gate is the
absence of the code path, not delivery-time arithmetic); K=1 BITWISE
equal to its serial delayed-blend reference (same programs, same
delivery schedule, zero overlap — JAX_GRAFT_STALENESS_SERIAL) across
all three topologies incl. the EF-compressed wire; the delivery
schedule and end-of-run drain (round R's delta lands at the entry of
round R+K+1, everything pending folds at exit); per-round
``sync_hidden_ms`` telemetry + the ``results["async_rounds"]`` summary;
the sim lab's ``--sim_staleness`` convergence twin; and every eagerly
rejected K>0 combo failing fast in Config with its real reason.

Tier-1 keeps one e2e gate per axis (the allreduce K=1 bitwise gate, the
schedule/drain accounting, the sim twin's schema); the full topology x
EF x sanitized sweeps ride the slow marker.
"""

import os

import jax
import numpy as np
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
    comms,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

KW = dict(model="mlp", dataset="mnist", epochs_global=3, epochs_local=1,
          batch_size=16, limit_train_samples=256, limit_eval_samples=64,
          compute_dtype="float32", augment=False,
          aggregation_by="weights", proportionality="uniform", seed=0)


def run(mesh, k=0, serial=False, rounds=3, **extra):
    """One driver run; ``serial=True`` arms the scheduling-only serial
    reference (same programs, same delayed-delivery schedule, the sync
    wall fully exposed at dispatch)."""
    if serial:
        os.environ["JAX_GRAFT_STALENESS_SERIAL"] = "1"
    try:
        return train_global(
            Config(**{**KW, "epochs_global": rounds, **extra},
                   sync_staleness=k),
            mesh=mesh, progress=False)
    finally:
        os.environ.pop("JAX_GRAFT_STALENESS_SERIAL", None)


_CACHE: dict = {}


def run_cached(mesh, tag="", **kw):
    """Memoized ``run`` — tier-1 cases share trajectories (the mesh is
    the session-scoped mesh8, so the config tuple is the full key);
    ``tag`` forces a distinct run of an identical config (determinism
    checks need two real executions)."""
    key = (tag,) + tuple(sorted(kw.items()))
    if key not in _CACHE:
        _CACHE[key] = run(mesh, **kw)
    return _CACHE[key]


def params_bitwise(a, b):
    la = jax.tree_util.tree_leaves(a["state"].params)
    lb = jax.tree_util.tree_leaves(b["state"].params)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def trajectories_bitwise(a, b):
    return (a["global_train_losses"] == b["global_train_losses"]
            and a["global_val_accuracies"] == b["global_val_accuracies"]
            and params_bitwise(a, b))


# --------------------------------------------------------------------
# The delivery blend (comms unit, numpy reference)
# --------------------------------------------------------------------
class TestDeliveryBlend:
    def tree(self, seed, n=2):
        rng = np.random.default_rng(seed)
        return {"w": np.asarray(rng.normal(size=(n, 5, 3)), np.float32),
                "b": np.asarray(rng.normal(size=(n, 7)), np.float32)}

    def test_delta_is_blend_minus_base_exact(self):
        base, blend = self.tree(0), self.tree(1)
        d = comms.stale_delta(blend, base)
        for k in base:
            assert np.array_equal(np.asarray(d[k]), blend[k] - base[k])

    def test_deliver_folds_delta_additively(self):
        later, delta = self.tree(2), self.tree(3)
        out = comms.deliver_stale(later, delta)
        for k in later:
            assert np.array_equal(np.asarray(out[k]), later[k] + delta[k])

    def test_two_worker_equal_allreduce_delayed_schedule(self):
        # the K=1 schedule as plain numpy: each round trains (here: a
        # fixed per-worker increment), syncs to the 2-worker mean as a
        # DELTA, and folds round R's delta into round R+2's entry
        # params — the helpers driven through the same schedule must
        # agree bitwise with the hand-rolled arithmetic
        rng = np.random.default_rng(7)
        p0 = np.asarray(rng.normal(size=(2, 4)), np.float32)
        steps = [np.asarray(rng.normal(size=(2, 4)), np.float32)
                 for _ in range(3)]

        def schedule(delta_fn, deliver_fn):
            p, pending = p0.copy(), []
            for s in steps:
                if len(pending) > 1:
                    p = deliver_fn(p, pending.pop(0))
                t = p + s                              # the local phase
                blend = np.broadcast_to(
                    (t[0] + t[1]) / 2.0, t.shape)      # equal FedAvg
                pending.append(delta_fn(blend, t))
                p = t
            while pending:                             # the drain
                p = deliver_fn(p, pending.pop(0))
            return p

        ref = schedule(lambda b, t: b - t, lambda p, d: p + d)
        got = schedule(
            lambda b, t: np.asarray(comms.stale_delta(b, t)),
            lambda p, d: np.asarray(comms.deliver_stale(p, d)))
        assert np.array_equal(ref, got)


# --------------------------------------------------------------------
# K=0: the staleness machinery is structurally absent
# --------------------------------------------------------------------
class TestK0Structural:
    def test_k0_builds_no_staleness_programs(self, mesh8):
        res = run_cached(mesh8, tag="a", k=0)
        names = set(res["memory"]["programs"])
        assert not any(n.startswith(("deliver", "stale_sync"))
                       for n in names), names
        assert res["async_rounds"] == {"enabled": False}
        for t in res["round_timings"]:
            assert t["sync_hidden_ms"] == 0.0

    def test_k0_run_to_run_bitwise(self, mesh8):
        a = run_cached(mesh8, tag="a", k=0)
        b = run_cached(mesh8, tag="b", k=0)
        assert trajectories_bitwise(a, b)


# --------------------------------------------------------------------
# K=1: bitwise equal to the serial delayed-blend reference
# --------------------------------------------------------------------
class TestK1BitwiseGate:
    def test_allreduce_overlap_eq_serial(self, mesh8):
        ovl = run_cached(mesh8, k=1)
        ser = run_cached(mesh8, k=1, serial=True)
        assert trajectories_bitwise(ovl, ser)
        # the serial arm exposes the whole wall by construction
        assert ser["async_rounds"]["sync_hidden_ms_total"] == 0.0

    @pytest.mark.slow
    @pytest.mark.parametrize("topo", ["ring", "double_ring"])
    def test_gossip_topologies_overlap_eq_serial(self, mesh8, topo):
        ovl = run(mesh8, k=1, topology=topo)
        ser = run(mesh8, k=1, serial=True, topology=topo)
        assert trajectories_bitwise(ovl, ser)

    @pytest.mark.slow
    def test_ef_compressed_wire_composes(self, mesh8):
        ef = dict(topology="ring", sync_compression="ef",
                  sync_dtype="bfloat16")
        ovl = run(mesh8, k=1, **ef)
        ser = run(mesh8, k=1, serial=True, **ef)
        assert trajectories_bitwise(ovl, ser)
        # the engine-side residual chain is restored into the state at
        # the drain — the EF contract survives staleness
        assert ovl["state"].sync_residual is not None

    @pytest.mark.slow
    def test_k2_overlap_eq_serial(self, mesh8):
        ovl = run(mesh8, k=2, rounds=4)
        ser = run(mesh8, k=2, rounds=4, serial=True)
        assert trajectories_bitwise(ovl, ser)


# --------------------------------------------------------------------
# Schedule, drain, and telemetry
# --------------------------------------------------------------------
class TestScheduleAndTelemetry:
    def test_every_round_syncs_and_drains(self, mesh8):
        res = run_cached(mesh8, k=1)
        ar = res["async_rounds"]
        assert ar["enabled"] is True and ar["staleness"] == 1
        # every round dispatched one sync; all were delivered (in-loop
        # fences + the end-of-run drain)
        assert ar["delivered"] == 3
        assert ar["sync_ms_total"] >= ar["sync_hidden_ms_total"] >= 0.0
        rows = res["round_timings"]
        assert all("sync_hidden_ms" in t for t in rows)
        # rows 0..K zero-fill (no delivery has landed yet); row K+1
        # carries round 0's delivered walls
        assert rows[0]["sync_hidden_ms"] == 0.0
        assert rows[1]["sync_hidden_ms"] == 0.0

    def test_k_beyond_run_length_pure_drain(self, mesh8):
        # K=5 over 2 rounds: no in-loop delivery ever comes due — the
        # drain must fold both pending deltas into the final state
        res = run_cached(mesh8, k=5, rounds=2)
        assert res["async_rounds"]["delivered"] == 2
        ser = run_cached(mesh8, k=5, rounds=2, serial=True)
        assert trajectories_bitwise(res, ser)

    def test_staleness_programs_tracked(self, mesh8):
        res = run_cached(mesh8, k=1)
        names = set(res["memory"]["programs"])
        assert any(n.startswith("stale_sync") for n in names), names
        assert any(n.startswith("deliver") for n in names), names

    @pytest.mark.slow
    def test_sanitized_k1_all_zero_row(self, mesh8):
        res = run(mesh8, k=1, sanitize=True)
        assert res["sanitize"] == {
            "enabled": True, "transfer_guard_violations": 0,
            "retrace_count": 0, "recompile_count": 0,
            "donation_failures": 0}
        assert trajectories_bitwise(res, run(mesh8, k=1))


# --------------------------------------------------------------------
# The sim lab twin (--sim_staleness)
# --------------------------------------------------------------------
class TestSimStaleness:
    SKW = dict(KW, sim_workers=16)

    def sim_run(self, k, rounds=3, tag="", cached=True, **extra):
        key = ("sim", tag, k, rounds) + tuple(sorted(extra.items()))
        if not cached:
            _CACHE.pop(key, None)
        if key not in _CACHE:
            _CACHE[key] = train_global(
                Config(**{**self.SKW, "epochs_global": rounds, **extra},
                       sim_staleness=k), progress=False)
        return _CACHE[key]

    def test_k0_builds_no_deliver_program(self):
        res = self.sim_run(0)
        assert not any(n.startswith("sim_deliver")
                       for n in res["memory"]["programs"])
        assert res["sim"]["staleness"] == 0

    def test_k1_schema_and_drain(self):
        res = self.sim_run(1)
        assert res["sim"]["staleness"] == 1
        assert any(n.startswith("sim_deliver")
                   for n in res["memory"]["programs"])
        # the fused sim sync has no wall to hide — zero-filled column
        for t in res["round_timings"]:
            assert t["sync_hidden_ms"] == 0.0
        # real-engine staleness stays off (its knob is rejected here)
        assert res["async_rounds"] == {"enabled": False}

    def test_staleness_changes_the_trajectory(self):
        k0 = self.sim_run(0)
        k1 = self.sim_run(1)
        # a one-round-stale consensus is a DIFFERENT algorithm: the
        # curves must diverge after the first delivery (round K+1)
        assert (k0["global_train_losses"][:1]
                == k1["global_train_losses"][:1])
        assert k0["global_train_losses"] != k1["global_train_losses"]

    def test_k_runs_deterministic(self):
        a = self.sim_run(2, tag="a")
        b = self.sim_run(2, tag="b")
        assert a["global_train_losses"] == b["global_train_losses"]
        assert params_bitwise(a, b)

    @pytest.mark.slow
    def test_convergence_curves_across_matrix(self):
        # the paper's 2x3 matrix x K in {0,1,2}: every cell produces a
        # finite curve of the full run length
        for mode in ("balanced", "disbalanced"):
            for topo in ("allreduce", "ring", "double_ring"):
                for k in (0, 1, 2):
                    res = self.sim_run(k, cached=False,
                                       data_mode=mode, topology=topo)
                    accs = res["global_val_accuracies"]
                    assert len(accs) == 3
                    assert all(np.isfinite(a) for a in accs)

    @pytest.mark.slow
    def test_sanitized_sim_k1_all_zero_row(self):
        res = self.sim_run(1, sanitize=True)
        assert res["sanitize"]["transfer_guard_violations"] == 0
        assert res["sanitize"]["retrace_count"] == 0
        assert res["sanitize"]["recompile_count"] == 0


# --------------------------------------------------------------------
# Eager config validation: every rejected K>0 combo, with its reason
# --------------------------------------------------------------------
class TestConfigRejections:
    def test_negative_staleness(self):
        with pytest.raises(ValueError, match="sync_staleness must be"):
            Config(sync_staleness=-1)
        with pytest.raises(ValueError, match="sim_staleness must be"):
            Config(sim_staleness=-1)

    def test_sim_staleness_needs_sim_workers(self):
        with pytest.raises(ValueError, match="needs --sim_workers"):
            Config(sim_staleness=1)

    def test_sim_staleness_needs_weights_mode(self):
        with pytest.raises(ValueError, match="no between-round consensus"):
            Config(sim_staleness=1, sim_workers=8,
                   aggregation_by="gradients")

    def test_sync_staleness_rejects_sim_workers(self):
        with pytest.raises(ValueError, match="use --sim_staleness"):
            Config(sync_staleness=1, aggregation_by="weights",
                   sim_workers=8)

    def test_sync_staleness_needs_weights_mode(self):
        with pytest.raises(ValueError, match="nothing to deliver late"):
            Config(sync_staleness=1, aggregation_by="gradients")

    def test_rejects_chaos(self):
        with pytest.raises(ValueError, match="NO consensus is\\s+in flight"):
            Config(sync_staleness=1, aggregation_by="weights",
                   chaos="random")

    def test_rejects_hierarchical(self):
        with pytest.raises(ValueError, match="cannot pipeline"):
            Config(sync_staleness=1, aggregation_by="weights",
                   num_slices=2, topology="ring")

    def test_rejects_resident_params(self):
        with pytest.raises(ValueError, match="entry gather DEPEND"):
            Config(sync_staleness=1, aggregation_by="weights",
                   param_residency="resident")

    def test_rejects_buddy_redundancy(self):
        with pytest.raises(ValueError, match="nothing is uniquely held"):
            Config(sync_staleness=1, aggregation_by="weights",
                   shard_redundancy="buddy")

    def test_rejects_streamed_rounds(self):
        with pytest.raises(ValueError, match="already\\s+overlaps"):
            Config(sync_staleness=1, aggregation_by="weights",
                   stream_chunk_steps=2)

    def test_rejects_checkpointing(self):
        with pytest.raises(ValueError, match="in-flight\\s+consensus"):
            Config(sync_staleness=1, aggregation_by="weights",
                   checkpoint_dir="/tmp/x")
        with pytest.raises(ValueError, match="in-flight\\s+consensus"):
            Config(sync_staleness=1, aggregation_by="weights",
                   checkpoint_dir="/tmp/x", resume=True)

    def test_auto_residency_resolves_replicated(self):
        cfg = Config(sync_staleness=1, aggregation_by="weights")
        assert cfg.resolve_param_residency("cpu") == "replicated"
        assert cfg.resolve_param_residency("tpu") == "replicated"
