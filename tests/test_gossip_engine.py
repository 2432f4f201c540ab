"""Bucketed compressed gossip engine (ISSUE 4 tentpole).

Covers the gossip twin of the sharded-sync contract: the fp32 bucketed
ring/double-ring round is BIT-IDENTICAL to the legacy dense per-leaf path
across worker counts and blend modes; the weighted blend reproduces the
reference's ``local_weight`` semantics through the bucketed path;
compressed gossip (bf16/int8 permuted payload, fp32 local blend) is
wire-rounding bounded per round and, with error feedback, contracts
repeated-round consensus to the dense fixed point where the uncompensated
path plateaus at the wire quantum; the engine resolves ``--sync_mode
sharded``/auto per topology onto the gossip program; and the per-round
telemetry schema is identical across all three topologies.
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

# same uneven leaf sizes as test_sync.py: multiple buckets at the tiny
# target, with a mid-tree bucket boundary
SHAPES = {"a": (13, 7), "b": (257,), "c": (31, 5), "d": (3,)}
TINY_BUCKET = 1024


def stacked_tree(n=N, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return {k: jnp.asarray(rng.normal(size=(n, *s)) * scale, jnp.float32)
            for k, s in SHAPES.items()}


def sub_mesh(k):
    return mesh_lib.build_mesh({"data": k}, devices=jax.devices()[:k])


class TestGossipBitIdentity:
    @pytest.mark.parametrize("k", [4, 8])
    @pytest.mark.parametrize("topology", ["ring", "double_ring"])
    def test_fp32_bucketed_bitwise_equals_dense(self, k, topology):
        mesh = sub_mesh(k)
        tree = stacked_tree(n=k)
        dense = comms.make_host_sync(mesh, mode="dense",
                                     topology=topology)(tree)[0]
        buck = comms.make_host_sync(mesh, mode="gossip", topology=topology,
                                    bucket_bytes=TINY_BUCKET)(tree)[0]
        for key in SHAPES:
            assert np.array_equal(np.asarray(dense[key]),
                                  np.asarray(buck[key])), key

    @pytest.mark.parametrize("topology,hops", [("ring", 1),
                                               ("double_ring", 2)])
    def test_bucketed_program_permutes_per_bucket_not_per_leaf(
            self, mesh8, topology, hops):
        # what "bucketed" means, read off the LOWERED programs: the dense
        # path issues one collective-permute a leaf a hop, the gossip
        # engine one a bucket a hop
        tree = stacked_tree()

        def count_permutes(fn):
            txt = jax.jit(lambda t: fn(t, None)).lower(tree).as_text()
            return (txt.count("collective_permute")
                    + txt.count("collective-permute"))

        leaves = [v[0] for v in tree.values()]
        dense = count_permutes(comms.make_host_sync(
            mesh8, mode="dense", topology=topology))
        assert dense == hops * len(leaves)
        for bucket_bytes in (TINY_BUCKET, comms.DEFAULT_BUCKET_BYTES):
            buckets = len(comms.bucket_plan(leaves, N, bucket_bytes))
            bucketed = count_permutes(comms.make_host_sync(
                mesh8, mode="gossip", topology=topology,
                bucket_bytes=bucket_bytes))
            assert bucketed == hops * buckets < dense


class TestWeightedBlend:
    """The Disbalanced variants' straggler weighting through the bucketed
    path: ``new = w*own + (1-w)*peer`` (peer mean for double-ring)."""

    @pytest.mark.parametrize("topology", ["ring", "double_ring"])
    def test_weighted_matches_dense_and_legacy_semantics(self, mesh8,
                                                         topology):
        w = 0.3
        tree = stacked_tree()
        dense = comms.make_host_sync(mesh8, mode="dense", topology=topology,
                                     how="weighted", local_weight=w)(tree)[0]
        buck = comms.make_host_sync(mesh8, mode="gossip", topology=topology,
                                    how="weighted", local_weight=w,
                                    bucket_bytes=TINY_BUCKET)(tree)[0]
        for key in SHAPES:
            a = np.asarray(tree[key], np.float64)
            r1 = np.roll(a, 1, axis=0)   # shift-1 predecessor's value
            if topology == "ring":
                expect = w * a + (1 - w) * r1
            else:
                r2 = np.roll(a, 2, axis=0)
                expect = w * a + ((1 - w) / 2) * (r1 + r2)
            # bucketed == dense bitwise; both == the reference's
            # local_weight blend to float rounding
            assert np.array_equal(np.asarray(dense[key]),
                                  np.asarray(buck[key])), key
            np.testing.assert_allclose(np.asarray(buck[key], np.float64),
                                       expect, rtol=1e-6, atol=1e-6)


class TestCompressedGossip:
    def test_single_round_error_is_wire_bounded(self, mesh8):
        tree = stacked_tree(scale=1.0)
        res0 = jax.tree_util.tree_map(jnp.zeros_like, tree)
        dense = comms.make_host_sync(mesh8, mode="dense",
                                     topology="ring")(tree)[0]
        for wdt, bound in ((jnp.bfloat16, 0.05), (jnp.int8, 0.1)):
            comp, new_res = comms.make_host_sync(
                mesh8, mode="gossip", topology="ring", wire_dtype=wdt,
                bucket_bytes=TINY_BUCKET)(tree, res0)
            # only the permuted neighbor term is compressed — one wire
            # rounding of an O(1) value per element
            err = max(float(np.abs(np.asarray(comp[k], np.float32)
                                   - np.asarray(dense[k], np.float32)).max())
                      for k in SHAPES)
            assert err < bound, (wdt, err)
            # the residual carries the own-transmission rounding error
            assert any(float(np.abs(np.asarray(l)).max()) > 0
                       for l in jax.tree_util.tree_leaves(new_res))

    @pytest.mark.parametrize("topology", ["ring", "double_ring"])
    def test_ef_consensus_contracts_to_dense_fixed_point(self, mesh8,
                                                         topology):
        # stall regime by construction: worker disagreement (~0.2) far
        # below the bf16 quantum at base magnitude ~100 (~0.5).  Plain
        # bf16 gossip rounds every transmission to the wire grid, so the
        # workers agree on GRID values — variance contracts, but the
        # consensus plateaus up to half a quantum off the dense fixed
        # point (the true fp32 mean) and stays there.  Error feedback
        # re-injects each round's rounding into the next transmission, so
        # the received values time-average to the true mean: the EF run's
        # time-averaged iterate lands several times closer (the EF-must-
        # win margin measured here is ~5x; asserted at 2x).
        rng = np.random.default_rng(1)
        base = rng.uniform(64, 128, 512) * rng.choice([-1.0, 1.0], 512)
        spread = rng.normal(size=(N, 512)) * 0.2
        x0 = jnp.asarray(base[None] + spread, jnp.float32)
        true_mean = np.asarray(x0).mean(0)
        var0 = float(((np.asarray(x0) - true_mean[None]) ** 2).mean())

        comp = comms.make_host_sync(mesh8, mode="gossip", topology=topology,
                                    wire_dtype=jnp.bfloat16)
        rounds, tail = 60, 20
        p_ef = p_raw = {"w": x0}
        r_ef = {"w": jnp.zeros((N, 512), jnp.float32)}
        ef_tail, raw_tail = [], []
        for t in range(rounds):
            # block each round: pipelined collectives can starve the
            # XLA:CPU rendezvous (test_comms gossip note)
            p_ef, r_ef = jax.block_until_ready(comp(p_ef, r_ef))
            p_raw = jax.block_until_ready(comp(p_raw)[0])
            if t >= rounds - tail:
                ef_tail.append(np.asarray(p_ef["w"]))
                raw_tail.append(np.asarray(p_raw["w"]))
        # consensus contraction: both compressed paths shrink the
        # cross-worker variance by well over 2x
        for tag, p in (("ef", p_ef), ("raw", p_raw)):
            a = np.asarray(p["w"])
            var = float(((a - a.mean(0)) ** 2).mean())
            assert var < 0.5 * var0, (topology, tag, var, var0)
        ef_dist = float(np.abs(np.mean(ef_tail, 0)
                               - true_mean[None]).mean())
        raw_dist = float(np.abs(np.mean(raw_tail, 0)
                                - true_mean[None]).mean())
        assert ef_dist < 0.5 * raw_dist, (topology, ef_dist, raw_dist)


class TestGossipWireBytes:
    def test_accounting_matches_hops_and_wire_dtype(self):
        tree = {k: jax.ShapeDtypeStruct(s, jnp.float32)
                for k, s in SHAPES.items()}
        total = sum(int(np.prod(s)) for s in SHAPES.values())
        for topo, hops in (("ring", 1), ("double_ring", 2)):
            dense = comms.sync_wire_bytes(tree, N, mode="dense",
                                          topology=topo)
            fp32 = comms.sync_wire_bytes(tree, N, mode="gossip",
                                         wire_dtype=jnp.float32,
                                         topology=topo)
            # bucketing changes the collective count, never the bytes:
            # each hop moves every element exactly once, unpadded
            assert dense == fp32 == hops * total * 4
            bf16 = comms.sync_wire_bytes(tree, N, mode="gossip",
                                         wire_dtype=jnp.bfloat16,
                                         topology=topo)
            int8 = comms.sync_wire_bytes(tree, N, mode="gossip",
                                         wire_dtype=jnp.int8,
                                         topology=topo)
            assert bf16 * 2 == fp32 and int8 * 4 == fp32
        assert comms.sync_wire_bytes(tree, 1, mode="gossip",
                                     topology="ring") == 0


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


class TestEngineGossip:
    def test_ring_round_bitwise_identical_and_telemetry_parity(self, mesh8):
        x, y, m = make_packs()

        def run(cfg):
            engine = make_engine(mesh8, cfg)
            state = engine.init_state(jax.random.key(0), x[0, 0])
            state, _ = engine.round(state, (x, y, m), (x, y, m))
            return engine, state

        eng_d, s_d = run(small_cfg(topology="ring", sync_mode="dense"))
        eng_g, s_g = run(small_cfg(topology="ring", sync_mode="sharded",
                                   sync_bucket_mb=0.001))
        assert eng_d.sync_mode == "dense"
        assert eng_g.sync_mode == "gossip"
        for a, b in zip(jax.tree_util.tree_leaves(s_d.params),
                        jax.tree_util.tree_leaves(s_g.params)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # telemetry schema parity (ISSUE 4 satellite): identical keys on
        # every engine, sync_ms zero-filled where no standalone sync
        # program ran (CPU fuses the sync into the round program).
        # ISSUE 13 widened the schema with the per-LEVEL split — flat
        # engines report every byte as the intra-slice (ICI) level —
        # and ISSUE 16 with sync_hidden_ms (zero-filled on synchronous
        # runs)
        keys = {"sync_bytes", "sync_mode", "sync_ms", "sync_hidden_ms",
                "sync_bytes_ici", "sync_bytes_dcn"}
        assert set(eng_d.last_sync_stats) == keys
        assert set(eng_g.last_sync_stats) == keys
        assert eng_g.last_sync_stats["sync_bytes"] > 0
        assert eng_g.last_sync_stats["sync_ms"] == 0.0
        assert eng_g.last_sync_stats["sync_bytes_ici"] == \
            eng_g.last_sync_stats["sync_bytes"]
        assert eng_g.last_sync_stats["sync_bytes_dcn"] == 0


class TestGossipConfigResolution:
    def test_sharded_ring_resolves_to_gossip_engine(self):
        # the old hard rejection is lifted (ISSUE 4): --sync_mode sharded
        # names the bucketed fast path, resolved per topology
        cfg = Config(sync_mode="sharded", topology="ring")
        assert cfg.resolve_sync_mode("cpu") == "gossip"
        assert cfg.resolve_sync_mode("tpu") == "gossip"
        assert Config(sync_mode="sharded").resolve_sync_mode("cpu") \
            == "sharded"

    def test_auto_resolves_per_topology_and_backend(self):
        for topo, fast in (("allreduce", "sharded"), ("ring", "gossip"),
                           ("double_ring", "gossip")):
            assert Config(topology=topo).resolve_sync_mode("cpu") == "dense"
            assert Config(topology=topo).resolve_sync_mode("tpu") == fast
            assert Config(topology=topo,
                          sync_dtype="bfloat16").resolve_sync_mode(
                              "cpu") == fast

    def test_compressed_gossip_flags_now_construct(self):
        # previously a hard ValueError; the engine now rides the
        # compressed wire for gossip topologies too
        cfg = Config(sync_dtype="int8", sync_compression="ef",
                     topology="double_ring", aggregation_by="weights")
        assert cfg.resolve_sync_mode("cpu") == "gossip"

    def test_dense_mode_still_rejects_compressed_wire(self):
        with pytest.raises(ValueError, match="sync_mode dense"):
            Config(sync_mode="dense", sync_dtype="bfloat16",
                   topology="ring")


class TestGossipDriverTelemetry:
    def test_ring_round_timings_schema_matches_allreduce(self, mesh8):
        res = train_global(
            Config(model="mlp", dataset="mnist", epochs_global=2,
                   epochs_local=1, batch_size=16, limit_train_samples=256,
                   limit_eval_samples=64, compute_dtype="float32",
                   augment=False, aggregation_by="weights",
                   topology="ring", sync_mode="sharded"),
            mesh=mesh8, progress=False)
        assert res["sync_engine"]["mode"] == "gossip"
        # gossip blends are worker-local; the optimizer-placement
        # resolution records that honestly (ISSUE 9)
        assert res["sync_engine"]["opt_placement"] == "local"
        assert len(res["round_timings"]) == 2
        for t in res["round_timings"]:
            # the exact keys the allreduce telemetry carries, whatever
            # the topology
            assert t["sync_mode"] == "gossip"
            assert t["sync_bytes"] > 0
            assert t["sync_ms"] >= 0.0
