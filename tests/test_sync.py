"""Sharded reduce-scatter round sync (ISSUE 2 tentpole).

Covers the numerics contract end to end: the fp32 sharded path is
BIT-IDENTICAL to the dense all-reduce across worker counts; uneven-bucket
padding round-trips exactly; the bf16-compressed path drifts within bf16
rounding per sync and, with error feedback, tracks the fp32 path over many
rounds where the uncompensated path stalls; the engine wires the mode
selection, residual state, and per-round telemetry; and the wire-byte
accounting puts sharded at 2(N-1)/N of dense.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
    comms,
    mesh as mesh_lib,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
from learning_deep_neural_network_in_distributed_computing_environment_tpu.train import LocalSGDEngine

N = 8

# uneven leaf sizes: none divisible by 8, so every bucket needs padding;
# TINY bucket target forces multiple buckets including a mid-tree boundary
SHAPES = {"a": (13, 7), "b": (257,), "c": (31, 5), "d": (3,)}
TINY_BUCKET = 1024  # bytes => 256 fp32 elements per bucket target


def stacked_tree(n=N, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(size=(n, *s)) * scale, jnp.float32)
            for k, s in SHAPES.items()}


def sub_mesh(k):
    return mesh_lib.build_mesh({"data": k}, devices=jax.devices()[:k])


class TestBucketPlan:
    def leaves(self):
        return [np.zeros(s, np.float32) for s in ((13, 7), (257,), (31, 5))]

    def test_padding_multiple_of_n_and_order_preserved(self):
        plan = comms.bucket_plan(self.leaves(), n=8, bucket_bytes=TINY_BUCKET)
        seen = []
        for b in plan:
            assert b.padded % 8 == 0
            filled = 0
            for (i, off, size) in b.items:
                assert off == filled  # contiguous, flatten order
                filled += size
                seen.append(i)
            assert b.padded >= filled
        assert seen == [0, 1, 2]  # every leaf exactly once, in order

    def test_tiny_bucket_target_splits_into_multiple_buckets(self):
        plan = comms.bucket_plan(self.leaves(), n=8, bucket_bytes=TINY_BUCKET)
        assert len(plan) >= 2
        one = comms.bucket_plan(self.leaves(), n=8, bucket_bytes=1 << 30)
        assert len(one) == 1

    def test_wire_bytes_accounting(self):
        tree = {k: jax.ShapeDtypeStruct(s, jnp.float32)
                for k, s in SHAPES.items()}
        total = sum(int(np.prod(s)) for s in SHAPES.values())
        assert comms.sync_wire_bytes(tree, N, mode="dense") == total * 4
        sharded = comms.sync_wire_bytes(tree, N, mode="sharded",
                                        wire_dtype=jnp.float32)
        padded = sum(b.padded for b in comms.bucket_plan(
            list(tree.values()), N, comms.DEFAULT_BUCKET_BYTES))
        assert sharded == 2 * (N - 1) * (padded // N) * 4
        # acceptance: sharded moves ~2(N-1)/N of dense bytes per bucket
        assert sharded / (total * 4) == pytest.approx(2 * (N - 1) / N,
                                                      rel=0.02)
        compressed = comms.sync_wire_bytes(tree, N, mode="sharded",
                                           wire_dtype=jnp.bfloat16)
        assert compressed * 2 == sharded
        assert comms.sync_wire_bytes(tree, 1, mode="sharded") == 0


class TestShardedBitIdentity:
    @pytest.mark.parametrize("k", [2, 4, 8])
    @pytest.mark.parametrize("how", ["equal", "weighted"])
    def test_fp32_sharded_bitwise_equals_dense(self, k, how):
        mesh = sub_mesh(k)
        tree = stacked_tree(n=k)
        dense = comms.make_host_sync(mesh, mode="dense", how=how,
                                     local_weight=0.3)(tree)[0]
        sharded = comms.make_host_sync(mesh, mode="sharded", how=how,
                                       local_weight=0.3,
                                       bucket_bytes=TINY_BUCKET)(tree)[0]
        for key in SHAPES:
            assert np.array_equal(np.asarray(dense[key]),
                                  np.asarray(sharded[key])), key

    def test_uneven_bucket_padding_roundtrips_exactly(self, mesh8):
        # all workers hold IDENTICAL small-integer-valued floats: the
        # cross-worker sum is exact (integers < 2^20 in fp32) and /8 is a
        # power-of-two scale, so the mean equals the input BITWISE — any
        # difference could only come from the pack/pad/unpack plumbing
        rng = np.random.default_rng(3)
        tree = {k: jnp.broadcast_to(
                    jnp.asarray(rng.integers(-1000, 1000, s), jnp.float32),
                    (N, *s))
                for k, s in SHAPES.items()}
        out = comms.make_host_sync(mesh8, mode="sharded",
                                   bucket_bytes=TINY_BUCKET)(tree)[0]
        for key in SHAPES:
            assert np.array_equal(np.asarray(tree[key]),
                                  np.asarray(out[key])), key


class TestCompressed:
    def test_single_sync_drift_is_bf16_bounded(self, mesh8):
        tree = stacked_tree(scale=1.0)
        dense = comms.make_host_sync(mesh8, mode="dense")(tree)[0]
        res = jax.tree_util.tree_map(jnp.zeros_like, tree)
        comp, new_res = comms.make_host_sync(
            mesh8, mode="sharded", wire_dtype=jnp.bfloat16)(tree, res)
        err = max(float(np.abs(np.asarray(comp[k], np.float32)
                               - np.asarray(dense[k], np.float32)).max())
                  for k in SHAPES)
        # two bf16 roundings (contribution + gathered mean) on O(1) values
        assert err < 0.05
        # the residual carries the fp32 rounding error of the own
        # contribution — nonzero for generic values
        assert any(float(np.abs(np.asarray(l)).max()) > 0
                   for l in jax.tree_util.tree_leaves(new_res))

    def test_error_feedback_tracks_fp32_where_plain_bf16_stalls(self, mesh8):
        # stall regime by construction: params ~100 sit on a bf16 grid of
        # ~0.5, per-round per-worker updates of 0.02..0.08 are far below
        # the half-quantum, so bf16(p + g) == bf16(p) and the uncompensated
        # compressed sync freezes the parameters while the fp32 reference
        # drifts ~15 quanta over 150 rounds.  Error feedback accumulates
        # the dropped sub-quantum mass in the fp32 residual until it
        # crosses a grid point, so the EF path tracks the drift.
        rng = np.random.default_rng(0)
        shape = (N, 512)
        row = (rng.uniform(64, 128, shape[1])
               * rng.choice([-1.0, 1.0], shape[1]))
        base = jnp.asarray(np.broadcast_to(row, shape), jnp.float32)
        step = jnp.asarray(rng.uniform(0.02, 0.08, shape), jnp.float32)
        dense = comms.make_host_sync(mesh8, mode="dense")
        comp = comms.make_host_sync(mesh8, mode="sharded",
                                    wire_dtype=jnp.bfloat16)
        rounds = 150
        p_ref = p_ef = p_raw = {"w": base}
        r_ef = {"w": jnp.zeros(shape, jnp.float32)}
        add = jax.jit(lambda t: {"w": t["w"] + step})
        for _ in range(rounds):
            # block each round: pipelined 8-thread collectives can starve
            # the XLA:CPU rendezvous (test_comms gossip note)
            p_ref = jax.block_until_ready(dense(add(p_ref))[0])
            p_ef, r_ef = jax.block_until_ready(comp(add(p_ef), r_ef))
            p_raw = jax.block_until_ready(comp(add(p_raw))[0])
        move = float(np.abs(np.asarray(p_ref["w"]) - np.asarray(base)).mean())
        err_ef = float(np.abs(np.asarray(p_ef["w"])
                              - np.asarray(p_ref["w"])).mean())
        err_raw = float(np.abs(np.asarray(p_raw["w"])
                               - np.asarray(p_ref["w"])).mean())
        assert move > 5.0  # the reference drifted many bf16 quanta
        assert err_ef < 0.15 * move, (err_ef, move)
        assert err_raw > 3 * err_ef, (err_raw, err_ef)


def small_cfg(**kw):
    base = dict(model="mlp", dataset="mnist", epochs_local=2, epochs_global=2,
                batch_size=8, compute_dtype="float32", augment=False,
                aggregation_by="weights")
    base.update(kw)
    return Config(**base)


def make_engine(mesh8, cfg):
    model = get_model("mlp", num_classes=10, hidden=16)
    return LocalSGDEngine(model, mesh8, cfg)


def make_packs(n=8, steps=4, b=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, steps, b, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, (n, steps, b)).astype(np.int32)
    m = np.ones((n, steps, b), np.float32)
    return x, y, m


class TestEngineSync:
    def _round_params(self, mesh8, cfg):
        engine = make_engine(mesh8, cfg)
        x, y, m = make_packs()
        state = engine.init_state(jax.random.key(0), x[0, 0])
        state, mx = engine.round(state, (x, y, m), (x, y, m))
        return state, mx, engine

    def test_weights_round_bitwise_identical_across_modes(self, mesh8):
        s_dense, mx_d, _ = self._round_params(
            mesh8, small_cfg(sync_mode="dense"))
        s_shard, mx_s, eng = self._round_params(
            mesh8, small_cfg(sync_mode="sharded", sync_bucket_mb=0.001))
        assert eng.sync_mode == "sharded"
        for a, b in zip(jax.tree_util.tree_leaves(s_dense.params),
                        jax.tree_util.tree_leaves(s_shard.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(mx_d["train_loss"]),
                              np.asarray(mx_s["train_loss"]))

    def test_gradients_norm_bitwise_identical_across_modes(self, mesh8):
        _, mx_d, _ = self._round_params(
            mesh8, small_cfg(aggregation_by="gradients", sync_mode="dense"))
        _, mx_s, _ = self._round_params(
            mesh8, small_cfg(aggregation_by="gradients",
                             sync_mode="sharded", sync_bucket_mb=0.001))
        assert np.array_equal(np.asarray(mx_d["agg_grad_norm"]),
                              np.asarray(mx_s["agg_grad_norm"]))
        assert float(np.asarray(mx_s["agg_grad_norm"]).ravel()[0]) > 0

    def test_compressed_round_carries_residual_and_stays_close(self, mesh8):
        cfg = small_cfg(sync_mode="sharded", sync_dtype="bfloat16",
                        sync_compression="ef")
        engine = make_engine(mesh8, cfg)
        assert engine.sync_ef
        x, y, m = make_packs()
        state = engine.init_state(jax.random.key(0), x[0, 0])
        assert state.sync_residual is not None
        state, _ = engine.round(state, (x, y, m), (x, y, m))
        res_mag = max(float(np.abs(np.asarray(l)).max())
                      for l in jax.tree_util.tree_leaves(state.sync_residual))
        assert 0 < res_mag < 0.01  # bf16-rounding scale, not garbage
        # FedAvg with a compressed wire still leaves replicas identical
        for leaf in jax.tree_util.tree_leaves(state.params):
            arr = np.asarray(leaf)
            assert np.array_equal(arr, np.broadcast_to(arr[:1], arr.shape))

    def test_sharded_ring_resolves_to_gossip_engine(self, mesh8):
        # the sharded-is-allreduce-only rejection is lifted (ISSUE 4):
        # --sync_mode sharded names the bucketed fast path, which for
        # gossip topologies is the per-bucket ppermute engine
        eng = make_engine(mesh8, small_cfg(sync_mode="sharded",
                                           topology="ring"))
        assert eng.sync_mode == "gossip"

    def test_auto_resolves_dense_on_cpu_sharded_for_bf16(self, mesh8):
        assert make_engine(mesh8, small_cfg()).sync_mode == "dense"
        eng = make_engine(mesh8, small_cfg(sync_dtype="bfloat16",
                                           sync_compression="ef"))
        assert eng.sync_mode == "sharded"


class TestConfigValidation:
    def test_bf16_dense_rejected(self):
        with pytest.raises(ValueError, match="sync_mode dense"):
            Config(sync_mode="dense", sync_dtype="bfloat16")

    def test_ef_requires_bf16(self):
        with pytest.raises(ValueError, match="bfloat16"):
            Config(sync_compression="ef")

    def test_bf16_ring_rides_the_gossip_engine(self):
        # a compressed-ring request used to fail fast so the flags could
        # not be silently ignored; since ISSUE 4 the bucketed gossip
        # engine honors them — auto must resolve onto it even on CPU
        cfg = Config(sync_dtype="bfloat16", sync_compression="ef",
                     topology="ring")
        assert cfg.resolve_sync_mode("cpu") == "gossip"


class TestDriverTelemetry:
    def test_round_timings_carry_sync_bytes_and_mode(self, mesh8):
        res = train_global(
            Config(model="mlp", dataset="mnist", epochs_global=2,
                   epochs_local=1, batch_size=16, limit_train_samples=256,
                   limit_eval_samples=64, compute_dtype="float32",
                   augment=False, aggregation_by="weights",
                   sync_mode="sharded"),
            mesh=mesh8, progress=False)
        assert len(res["round_timings"]) == 2
        for t in res["round_timings"]:
            assert t["sync_mode"] == "sharded"
            assert t["sync_bytes"] > 0
            # ISSUE 16 schema: every row carries sync_hidden_ms, and a
            # synchronous run zero-fills it (same convention as sync_ms)
            assert t["sync_hidden_ms"] == 0.0
        # run-artifact engine provenance (ISSUE 9 satellite): sync mode,
        # resolved optimizer placement, and measured per-worker resident
        # bytes for every state component
        se = res["sync_engine"]
        assert se["mode"] == "sharded"
        assert se["opt_placement"] == "sharded"   # auto follows the engine
        # ISSUE 11: weights x equal under the sharded engine auto-resolves
        # the scatter-resident params layout, and the state-bytes split
        # records it — the resident shard is EXACTLY 1/N of the transient
        # gathered peak (the padded full buffers the round-entry gather
        # materializes in compute scope)
        assert se["param_residency"] == "resident"
        pw = se["per_worker_state_bytes"]
        assert pw["params"] > 0 and pw["opt_state"] > 0
        assert pw["params"] * 8 == pw["params_gathered_peak"]
        assert pw["ef_residual"] == 0 and pw["round_opt"] == 0
        assert res["compile_cache"] == {"enabled": False, "hits": 0,
                                        "misses": 0}

    def test_streamed_rounds_measure_sync_wall(self, mesh8):
        res = train_global(
            Config(model="mlp", dataset="mnist", epochs_global=2,
                   epochs_local=1, batch_size=16, limit_train_samples=256,
                   limit_eval_samples=64, compute_dtype="float32",
                   augment=False, aggregation_by="weights",
                   sync_mode="sharded", stream_chunk_steps=2),
            mesh=mesh8, progress=False)
        for t in res["round_timings"]:
            assert t["sync_bytes"] > 0
            assert t["sync_ms"] >= 0.0  # the standalone sync program ran
            assert t["sync_hidden_ms"] == 0.0  # streamed rounds stay sync
        # the streamed path rides the resident layout too (enter program
        # + scatter-exit standalone sync); a replicated layout would
        # report a zero transient gather peak instead
        pw = res["sync_engine"]["per_worker_state_bytes"]
        assert res["sync_engine"]["param_residency"] == "resident"
        assert pw["params"] * 8 == pw["params_gathered_peak"]


class TestInt8Compressed:
    """int8 + per-bucket-scale second compression tier (ISSUE 3
    satellite): symmetric round-to-nearest on a max|x|/127 grid, the
    sender's fp32 scale riding a tiny all-gather next to the payload."""

    def test_single_sync_error_is_scale_bounded(self, mesh8):
        tree = stacked_tree(scale=1.0)
        dense = comms.make_host_sync(mesh8, mode="dense")(tree)[0]
        res = jax.tree_util.tree_map(jnp.zeros_like, tree)
        out, new_res = comms.make_host_sync(
            mesh8, mode="sharded", wire_dtype=jnp.int8,
            bucket_bytes=TINY_BUCKET)(tree, res)
        # per-element error <= one int8 step of each phase: contribution
        # steps are ~max|x|/127 per worker (averaged over N) plus the
        # gathered mean's own step — O(1) values quantize to ~0.03 steps
        err = max(float(np.abs(np.asarray(out[k], np.float32)
                               - np.asarray(dense[k], np.float32)).max())
                  for k in SHAPES)
        assert err < 0.1
        assert any(float(np.abs(np.asarray(l)).max()) > 0
                   for l in jax.tree_util.tree_leaves(new_res))

    def test_weighted_int8_close_to_dense(self, mesh8):
        tree = stacked_tree(scale=1.0)
        dense = comms.make_host_sync(mesh8, mode="dense", how="weighted",
                                     local_weight=0.3)(tree)[0]
        res = jax.tree_util.tree_map(jnp.zeros_like, tree)
        out, _ = comms.make_host_sync(
            mesh8, mode="sharded", how="weighted", local_weight=0.3,
            wire_dtype=jnp.int8, bucket_bytes=TINY_BUCKET)(tree, res)
        err = max(float(np.abs(np.asarray(out[k], np.float32)
                               - np.asarray(dense[k], np.float32)).max())
                  for k in SHAPES)
        assert err < 0.1

    def test_error_feedback_time_average_converges(self, mesh8):
        # error feedback makes the QUANTIZATION ERROR zero-mean over
        # rounds: re-syncing the same tree repeatedly, the time-average
        # of the compressed output approaches the exact dense mean far
        # beyond single-shot precision (the residual re-injects every
        # dropped sub-quantum until it crosses a grid point)
        tree = stacked_tree(scale=1.0)
        dense = comms.make_host_sync(mesh8, mode="dense")(tree)[0]
        sync = comms.make_host_sync(mesh8, mode="sharded",
                                    wire_dtype=jnp.int8,
                                    bucket_bytes=TINY_BUCKET)
        res = jax.tree_util.tree_map(jnp.zeros_like, tree)
        acc = None
        rounds = 24
        single = None
        for _ in range(rounds):
            out, res = jax.block_until_ready(sync(tree, res))
            if single is None:
                single = out
            acc = out if acc is None else jax.tree_util.tree_map(
                lambda a, b: a + b, acc, out)
        err_one = max(float(np.abs(np.asarray(single[k], np.float32)
                                   - np.asarray(dense[k], np.float32)).max())
                      for k in SHAPES)
        err_avg = max(float(np.abs(np.asarray(acc[k]) / rounds
                                   - np.asarray(dense[k])).max())
                      for k in SHAPES)
        assert err_avg < 0.25 * err_one, (err_avg, err_one)

    def test_wire_bytes_quarter_of_fp32(self):
        tree = {k: jax.ShapeDtypeStruct(s, jnp.float32)
                for k, s in SHAPES.items()}
        b32 = comms.sync_wire_bytes(tree, N, mode="sharded",
                                    wire_dtype=jnp.float32)
        b8 = comms.sync_wire_bytes(tree, N, mode="sharded",
                                   wire_dtype=jnp.int8)
        assert b8 == b32 // 4

    def test_engine_int8_round_carries_residual(self, mesh8):
        cfg = small_cfg(sync_mode="sharded", sync_dtype="int8",
                        sync_compression="ef")
        engine = make_engine(mesh8, cfg)
        assert engine.sync_ef
        assert engine.sync_wire_dtype == jnp.int8
        x, y, m = make_packs()
        state = engine.init_state(jax.random.key(0), x[0, 0])
        state, _ = engine.round(state, (x, y, m), (x, y, m))
        # FedAvg with a quantized wire still leaves replicas identical
        for leaf in jax.tree_util.tree_leaves(state.params):
            arr = np.asarray(leaf)
            assert np.array_equal(arr, np.broadcast_to(arr[:1], arr.shape))

    def test_int8_auto_resolves_sharded(self, mesh8):
        eng = make_engine(mesh8, small_cfg(sync_dtype="int8",
                                           sync_compression="ef"))
        assert eng.sync_mode == "sharded"

    def test_int8_dense_rejected(self):
        with pytest.raises(ValueError, match="sync_mode dense"):
            Config(sync_mode="dense", sync_dtype="int8")


class TestShardedSyncInnerAxes:
    """psum_scatter / all_to_all / all_gather over 'data' inside a mesh
    with inner TP/PP/EP axes are bit-identical to the dense twin under
    shard_map's varying-axes check (``check_vma=True``) — what lets the
    auto mode pick the bucketed engine on meshes with inner axes."""

    def _run(self, mesh_axes, spec_sharded, how="equal", wire=None):
        from jax import lax
        from jax.sharding import PartitionSpec as P
        mesh = mesh_lib.build_mesh(mesh_axes)
        n = mesh_axes["data"]
        rng = np.random.default_rng(0)
        tree = {"sharded": jnp.asarray(rng.normal(size=(n, 6, 8)),
                                       jnp.float32),
                "repl": jnp.asarray(rng.normal(size=(n, 33)), jnp.float32)}
        specs = {"sharded": spec_sharded, "repl": P("data")}
        inner = tuple(a for a in mesh_axes if a != "data")

        def cert(t):
            # the bucketed engine packs the replicated leaf beside the
            # inner-axis-sharded one, so its output is TYPED varying
            # over the inner axes; an identity pmean (the values are
            # equal) restores the invariance the out_spec claims
            return {"sharded": t["sharded"],
                    "repl": lax.pmean(t["repl"], inner)}

        def body(t):
            sq = jax.tree_util.tree_map(lambda a: a[0], t)
            out, _ = comms.sharded_sync(sq, how=how, local_weight=0.3,
                                        wire_dtype=wire,
                                        bucket_bytes=TINY_BUCKET)
            dense = comms.aggregate(sq, how=how, topology="allreduce",
                                    local_weight=0.3)
            ex = lambda tt: jax.tree_util.tree_map(lambda a: a[None], tt)
            return ex(cert(out)), ex(cert(dense))

        f = jax.shard_map(body, mesh=mesh, in_specs=(specs,),
                          out_specs=(specs, specs), check_vma=True)
        out, dense = jax.jit(f)(tree)
        return out, dense

    @pytest.mark.parametrize("how", ["equal", "weighted"])
    @pytest.mark.parametrize("axes,spec", [
        ({"data": 4, "model": 2}, ("data", None, "model")),
        ({"data": 2, "pipe": 2, "model": 2}, ("data", "pipe", "model")),
        ({"data": 4, "expert": 2}, ("data", "expert")),
    ], ids=["tp", "pp_tp", "ep"])
    def test_fp32_bitwise_under_inner_axes(self, axes, spec, how):
        from jax.sharding import PartitionSpec as P
        out, dense = self._run(axes, P(*spec), how=how)
        for k in ("sharded", "repl"):
            assert np.array_equal(np.asarray(out[k]),
                                  np.asarray(dense[k])), k

    @pytest.mark.slow
    def test_engine_auto_mode_no_longer_gates_on_inner_axes(self):
        # the lifted gate: auto still resolves dense on the CPU backend,
        # but an EXPLICIT sharded engine on a TP mesh must produce the
        # bitwise-dense round (the configuration the gate used to block)
        mesh = mesh_lib.build_mesh({"data": 4, "model": 2})
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.bert import (
            tp_param_specs,
        )
        outs = {}
        for mode in ("dense", "sharded"):
            cfg = Config(model="bert_tiny", dataset="synthetic_mlm",
                         batch_size=8, compute_dtype="float32",
                         augment=False, aggregation_by="weights",
                         epochs_local=1, sync_mode=mode,
                         sync_bucket_mb=0.001)
            model = get_model("bert_tiny", num_classes=30522,
                              scan_layers=True)
            tmodel = get_model("bert_tiny", num_classes=30522,
                               scan_layers=True, tp_size=2,
                               model_axis="model")
            eng = LocalSGDEngine(model, mesh, cfg, train_model=tmodel,
                                 param_specs_fn=tp_param_specs)
            rng = np.random.default_rng(0)
            x = rng.integers(0, 30522, (4, 2, 8, 16)).astype(np.int32)
            y = rng.integers(0, 30522, (4, 2, 8, 16)).astype(np.int32)
            m = np.ones((4, 2, 8), np.float32)
            state = eng.init_state(jax.random.key(0), x[0, 0])
            state, _ = eng.round(state, (x, y, m), (x, y, m))
            outs[mode] = jax.tree_util.tree_leaves(
                jax.device_get(state.params))
        for a, b in zip(outs["dense"], outs["sharded"]):
            assert np.array_equal(np.asarray(a), np.asarray(b))


class TestBuddyWireAccounting:
    """ISSUE 12 satellite: the buddy-redundancy hop's wire bytes ride
    ``sync_bytes`` — redundancy on must equal baseline + exactly one
    ppermute hop of the shard-resident rows in the wire dtype, per
    topology (gossip topologies keep every state worker-local, so
    redundancy is a no-op there and the accounting is unchanged)."""

    def _engine(self, topology, redundancy, **cfg_kw):
        cfg_kw.setdefault("aggregation_by", "weights")
        cfg = Config(model="mlp", batch_size=8, compute_dtype="float32",
                     augment=False, topology=topology,
                     sync_mode="sharded", shard_redundancy=redundancy,
                     **cfg_kw)
        eng = LocalSGDEngine(get_model("mlp", num_classes=10, hidden=8),
                             sub_mesh(4), cfg)
        state = eng.init_state(
            jax.random.key(0), np.zeros((8, 28, 28, 1), np.float32))
        eng._arm_sync_stats(state.params)
        return eng

    @pytest.mark.parametrize("topology", ["allreduce", "ring",
                                          "double_ring"])
    def test_redundancy_adds_exactly_one_hop(self, topology):
        on = self._engine(topology, "auto")
        off = self._engine(topology, "off")
        sb_on = on.last_sync_stats["sync_bytes"]
        sb_off = off.last_sync_stats["sync_bytes"]
        if topology == "allreduce":
            # weights x equal x sharded resolves resident -> buddy on
            assert on.buddy_on and not off.buddy_on
            expect = comms.buddy_wire_bytes(
                on.params_template, 4,
                bucket_bytes=on.sync_bucket_bytes)
            assert expect > 0
            assert sb_on == sb_off + expect, (sb_on, sb_off, expect)
        else:
            # gossip: nothing shard-resident, redundancy resolves off
            assert not on.buddy_on
            assert sb_on == sb_off

    def test_compressed_wire_hop_is_wire_dtype_sized(self):
        on = self._engine("allreduce", "auto", sync_dtype="bfloat16",
                          sync_compression="ef")
        off = self._engine("allreduce", "off", sync_dtype="bfloat16",
                           sync_compression="ef")
        # params row in bf16 (2 bytes) + the fp32 EF own-span (4 bytes)
        expect = comms.buddy_wire_bytes(
            on.params_template, 4, wire_dtype=jnp.bfloat16,
            bucket_bytes=on.sync_bucket_bytes, ef=True)
        assert on.last_sync_stats["sync_bytes"] == \
            off.last_sync_stats["sync_bytes"] + expect

    def test_tracker_hop_counts_two_fp32_rows(self):
        on = self._engine("allreduce", "auto",
                          aggregation_by="gradients")
        off = self._engine("allreduce", "off",
                           aggregation_by="gradients")
        assert on.round_opt_on and on.buddy_on
        expect = comms.buddy_wire_bytes(
            on.params_template, 4, params=False, tracker=True,
            bucket_bytes=on.sync_bucket_bytes)
        assert expect > 0
        assert on.last_sync_stats["sync_bytes"] == \
            off.last_sync_stats["sync_bytes"] + expect


class TestHierWireAccountingInEngine:
    """ISSUE 13 satellite: exact per-LEVEL byte accounting through the
    ENGINE's telemetry arming — outer (DCN) bytes are exactly
    ``hops x filled_bucket_row`` in the outer wire dtype (the gossip
    hop rides the 1/N_inner scatter shard, never the full tree), inner
    (ICI) bytes unchanged from the flat sharded engine at W workers.
    The comms-level exactness matrix lives in tests/test_hier_sync.py;
    flat engines report every byte as the ICI level with zero DCN."""

    def _engine(self, s, w, **cfg_kw):
        cfg_kw.setdefault("aggregation_by", "weights")
        cfg_kw.setdefault("topology", "ring" if s > 1 else "allreduce")
        cfg = Config(model="mlp", batch_size=8, compute_dtype="float32",
                     augment=False, num_slices=s, **cfg_kw)
        mesh = (mesh_lib.build_mesh({"slice": s, "data": w},
                                    devices=jax.devices()[:s * w])
                if s > 1 else sub_mesh(w))
        eng = LocalSGDEngine(get_model("mlp", num_classes=10, hidden=8),
                             mesh, cfg)
        state = eng.init_state(
            jax.random.key(0), np.zeros((8, 28, 28, 1), np.float32))
        eng._arm_sync_stats(state.params)
        return eng

    @pytest.mark.parametrize("topology,hops", [("ring", 1),
                                               ("double_ring", 2)])
    def test_dcn_bytes_exactly_hops_times_shard_row(self, topology, hops):
        eng = self._engine(2, 4, topology=topology)
        stats = eng.last_sync_stats
        plan = comms.bucket_plan(
            jax.tree_util.tree_leaves(eng.params_template), 4,
            eng.sync_bucket_bytes)
        expect_dcn = hops * sum((b.padded // 4) * 4 for b in plan)
        expect_ici = comms.sync_wire_bytes(
            eng.params_template, 4, mode="sharded",
            wire_dtype=jnp.float32, bucket_bytes=eng.sync_bucket_bytes)
        assert stats["sync_bytes_dcn"] == expect_dcn
        assert stats["sync_bytes_ici"] == expect_ici
        assert stats["sync_bytes"] == expect_ici + expect_dcn

    def test_compressed_outer_wire_quarters_dcn_only(self):
        fp = self._engine(2, 2, topology="ring")
        q = self._engine(2, 2, topology="ring", sync_dtype_outer="int8")
        assert q.last_sync_stats["sync_bytes_dcn"] * 4 == \
            fp.last_sync_stats["sync_bytes_dcn"]
        assert q.last_sync_stats["sync_bytes_ici"] == \
            fp.last_sync_stats["sync_bytes_ici"]

    def test_flat_engines_report_zero_dcn(self):
        for kw in (dict(sync_mode="sharded", topology="allreduce"),
                   dict(sync_mode="sharded", topology="ring"),
                   dict(sync_mode="dense", topology="allreduce")):
            eng = self._engine(1, 4, **kw)
            stats = eng.last_sync_stats
            assert stats["sync_bytes_dcn"] == 0
            assert stats["sync_bytes_ici"] == stats["sync_bytes"]
            assert set(stats) == {"sync_bytes", "sync_mode", "sync_ms",
                                  "sync_hidden_ms", "sync_bytes_ici",
                                  "sync_bytes_dcn"}
