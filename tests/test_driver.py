"""End-to-end integration: train_global over the variant matrix on the
8-worker CPU mesh (SURVEY.md section 4 'Integration')."""

import numpy as np
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global


def cfg(**kw):
    base = dict(model="mlp", dataset="mnist", epochs_global=2, epochs_local=2,
                batch_size=16, limit_train_samples=800,
                limit_eval_samples=100, compute_dtype="float32",
                augment=False, aggregation_by="weights", seed=1)
    base.update(kw)
    return Config(**base)


def run(mesh8, **kw):
    return train_global(cfg(**kw), mesh=mesh8, progress=False)


class TestEndToEnd:
    def test_balanced_allreduce_learns(self, mesh8):
        res = run(mesh8)
        assert res["global_train_losses"][-1] < res["global_train_losses"][0]
        assert res["global_val_accuracies"][-1] > 50.0
        # reference metric structure shapes (trainer.py:192)
        assert len(res["global_train_losses"]) == 2
        assert len(res["all_epochs_losses"]) == 4  # epochs_global*epochs_local
        assert len(res["all_workers_losses"]) == 8
        assert all(len(w) > 0 for w in res["all_workers_losses"])
        assert len(res["worker_specific_train_losses"]) == 4
        assert len(res["global_epoch_accuracies"][0]) == 2

    @pytest.mark.parametrize("topology", ["ring", "double_ring"])
    def test_gossip_topologies_run(self, mesh8, topology):
        res = run(mesh8, topology=topology, aggregation_type="weighted")
        assert res["global_train_losses"][-1] < res["global_train_losses"][0]

    def test_disbalanced_mode(self, mesh8):
        res = run(mesh8, data_mode="disbalanced", fixed_ratio=0.6)
        assert np.isfinite(res["global_train_losses"]).all()

    def test_heterogeneous_durations_shift_shards(self, mesh8):
        # inverse proportionality: 4x-slower worker 0 gets ~4x less data
        sims = np.array([4.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0])
        res2 = train_global(cfg(proportionality="inverse"), mesh=mesh8,
                            simulated_durations=sims, progress=False)
        w0 = len(res2["all_workers_losses"][0])
        w1 = len(res2["all_workers_losses"][1])
        assert w0 < w1  # slower worker saw fewer batches

    def test_reference_direct_proportionality(self, mesh8):
        # reference-compat mode: slower worker gets MORE data (SURVEY.md 2.5.1)
        sims = np.array([4.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0])
        res = train_global(cfg(proportionality="direct"), mesh=mesh8,
                           simulated_durations=sims, progress=False)
        w0 = len(res["all_workers_losses"][0])
        w1 = len(res["all_workers_losses"][1])
        assert w0 > w1

    def test_time_limit_caps_steps(self, mesh8):
        # a tiny time budget caps every worker's steps per round
        sims = np.full(8, 8.0)  # 8s probe for 10 batches -> 0.8 s/batch
        res = train_global(
            cfg(time_limit=1.6), mesh=mesh8, simulated_durations=sims,
            # keep the measured per-epoch wall consistent with the probe
            # (0.8 s/batch x 2 capped steps) so the cap stays at 2
            simulated_round_durations=lambda e: np.full(8, 1.6),
            progress=False)
        # cap = 1.6/0.8 = 2 batches/worker/epoch -> per local epoch at most
        # 2*16=32 examples contribute
        for i in range(8):
            per_epoch = len(res["all_workers_losses"][i]) / 4  # 4 local epochs
            assert per_epoch <= 2

    def test_midrun_slowdown_shrinks_next_cap(self, mesh8):
        # VERDICT r1 'Next' #8: the straggler budget must react to MEASURED
        # round wall time, not just the initial probe.  Worker walls are
        # uniform in round 0; from round 1 on every worker reports a 100x
        # wall.  Under the overlapped pipeline's DELAYED EMA (round r+1 is
        # packed while round r still runs, so the freshest wall it can
        # consume is round r-1's) the reaction lands one round later:
        # round 3's cap shrinks from round 1's measured wall.
        sims = np.full(8, 8.0)  # probe: 0.8 s/batch -> cap 16.0/0.8 = 20

        def walls(epoch):
            base = np.full(8, 0.8)  # per-epoch wall -> spb stays ~0.8
            if epoch >= 1:
                base *= 100.0       # mid-run slowdown
            return base

        res = train_global(cfg(epochs_global=4, epochs_local=1,
                               time_limit=16.0),
                           mesh=mesh8, simulated_durations=sims,
                           simulated_round_durations=walls, progress=False)
        caps = res["step_caps"]
        assert len(caps) == 4
        # rounds 1-2 still see only the uniform round-0 wall
        assert caps[2][0] == caps[1][0], caps
        # round 3 consumed round 1's 100x wall through the delayed EMA
        assert caps[3][0] < caps[2][0], caps

    @pytest.mark.slow
    def test_bert_mlm_end_to_end(self, mesh8):
        # BASELINE ladder entry 5 (BERT MLM): token task with [B, L] labels
        # through pack_shard -> engine -> eval (VERDICT r1 missing #2).
        # slow tier (ISSUE 2 triage): the two bert driver e2e cases are the
        # longest tier-1 rounds (~50 s combined); bert coverage stays in
        # tier-1 via test_models_extra/test_pp unit+module tests
        res = run(mesh8, model="bert_tiny", dataset="synthetic_mlm",
                  epochs_global=2, epochs_local=1, batch_size=8,
                  limit_train_samples=256, limit_eval_samples=64, lr=1e-3)
        assert res["global_train_losses"][-1] < res["global_train_losses"][0]
        assert np.isfinite(res["global_train_losses"]).all()

    @pytest.mark.slow
    def test_bert_mlm_final_evaluation(self, mesh8):
        # the rank-0 evaluator must handle [B, L] token labels (masked
        # positions only) without crashing and produce finite P/R/F1.
        # slow tier (ISSUE 2 triage), see test_bert_mlm_end_to_end
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.eval import evaluate
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.train import rank0_variables
        res = run(mesh8, model="bert_tiny", dataset="synthetic_mlm",
                  epochs_global=1, epochs_local=1, batch_size=8,
                  limit_train_samples=128, limit_eval_samples=48)
        test = res["test"]
        loss, acc, preds, labels, metrics = evaluate(
            res["model"], rank0_variables(res["state"]),
            test.images, test.labels, batch_size=8, verbose=False)
        assert np.isfinite(loss) and 0.0 <= acc <= 100.0
        assert preds.shape == labels.shape
        assert all(np.isfinite(v) for v in metrics.values())


class TestCompileCacheTelemetry:
    def test_counter_counts_monitoring_events(self):
        # the persistent-cache hit/miss report rides jax's monitoring
        # events; count them directly so the plumbing is verified without
        # depending on backend cache support
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.xla_flags import (
            compile_cache_counts,
            install_cache_counter,
        )
        install_cache_counter()
        from jax._src import monitoring
        before = compile_cache_counts()
        monitoring.record_event("/jax/compilation_cache/cache_hits")
        monitoring.record_event("/jax/compilation_cache/cache_misses")
        monitoring.record_event("/jax/compilation_cache/cache_misses")
        after = compile_cache_counts()
        assert after["hits"] - before["hits"] == 1
        assert after["misses"] - before["misses"] == 2

    def test_train_global_reports_per_run_delta(self, mesh8):
        # enabled=False run: counters exist and the delta is zero
        res = train_global(cfg(epochs_global=1), mesh=mesh8, progress=False)
        assert res["compile_cache"]["enabled"] is False
        assert res["compile_cache"]["hits"] >= 0
        assert res["compile_cache"]["misses"] >= 0


def test_probe_commits_its_inputs_to_one_device(devices, monkeypatch):
    """Under dense sync the probe is handed worker 0's row of a replicated
    state, still laid over every chip.  A jit over several devices is a
    partitioned program, and a Mosaic kernel in it is refused at lowering
    ("cannot be automatically partitioned": ``chip_smoke.py`` on four
    chips, phase ``sync_twins``; interpret mode on the CPU cannot show
    it), so the probe, which times one device, puts its inputs there."""
    import flax.linen as nn
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from learning_deep_neural_network_in_distributed_computing_environment_tpu import probe

    class Model(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4)(x)

    model = Model()
    x = np.ones((2, 8), np.float32)
    variables = jax.device_put(
        model.init(jax.random.key(0), x),
        NamedSharding(Mesh(np.array(devices[:4]), ("data",)), P()))
    assert all(len(a.sharding.device_set) == 4
               for a in jax.tree.leaves(variables))
    seen, jit = [], jax.jit

    def spying_jit(fn):
        def call(*args):
            seen.append({d for a in jax.tree.leaves(args)
                         for d in a.sharding.device_set})
            return jit(fn)(*args)
        return call

    monkeypatch.setattr(probe.jax, "jit", spying_jit)
    assert probe.measure_step_time(model, variables, x, num_batches=2) > 0
    assert seen == [{jax.local_devices()[0]}] * 3
