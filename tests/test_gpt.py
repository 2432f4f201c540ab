"""Causal attention (dense/flash/ring/ulysses) + the GPT-2 family.

The reference has no sequence models (SURVEY.md 2.3); this is the
beyond-reference autoregressive ladder: causal masking in every attention
impl, the canonical GPT-2-small parameter count, and driver-level e2e
training under DP / TP / sequence parallelism.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


def _qkv(l=128, h=4, d=16, b=2, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(b, l, h, d)), jnp.float32)
                 for _ in range(3))


class TestCausalAttention:
    def test_dense_causal_equals_masked(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import dot_product_attention
        q, k, v = _qkv()
        d = dot_product_attention(q, k, v, causal=True)
        mask = jnp.asarray(np.tril(np.ones((128, 128), bool)))
        ref = dot_product_attention(q, k, v, mask=mask)
        np.testing.assert_allclose(d, ref, atol=1e-6)
        # position 0 attends only itself -> output == v[0]
        np.testing.assert_allclose(d[:, 0], v[:, 0], atol=1e-6)

    def test_flash_causal_forward_and_grad(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import (
            attend, dot_product_attention)
        q, k, v = _qkv()
        d = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(attend(q, k, v, impl="flash", causal=True),
                                   d, atol=1e-5)
        gf = jax.grad(lambda q: (attend(q, k, v, impl="flash",
                                        causal=True) ** 2).sum())(q)
        gd = jax.grad(lambda q: (dot_product_attention(
            q, k, v, causal=True) ** 2).sum())(q)
        np.testing.assert_allclose(gf, gd, atol=1e-4)

    @pytest.mark.parametrize("impl", ["ring", "all_to_all"])
    def test_seq_parallel_causal_matches_dense(self, impl, devices):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import (
            attend, dot_product_attention)
        q, k, v = _qkv()
        mesh = build_mesh({"seq": 4}, devices[:4])
        f = jax.jit(jax.shard_map(
            lambda q, k, v: attend(q, k, v, impl=impl, axis_name="seq",
                                   causal=True),
            mesh=mesh, in_specs=(P(None, "seq"),) * 3,
            out_specs=P(None, "seq")))
        np.testing.assert_allclose(
            f(q, k, v), dot_product_attention(q, k, v, causal=True),
            atol=1e-5)


@pytest.mark.slow


def _flash_vs_dense(q, k, v, causal, atol=1e-5, gtol=1e-4):
    """Flash forward and (dq, dk, dv) against dense attention, at the
    tolerances of ``test_flash_causal_forward_and_grad``."""
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import dot_product_attention
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.pallas_ops import flash_attention
    flash = lambda q, k, v: flash_attention(q, k, v, causal=causal)
    dense = lambda q, k, v: dot_product_attention(q, k, v, causal=causal)
    both = lambda fn: jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *b: (fn(*b) ** 2).sum(), argnums=(0, 1, 2))(*a)))
    (of, gf), (od, gd) = both(flash)(q, k, v), both(dense)(q, k, v)
    np.testing.assert_allclose(of, od, atol=atol)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, atol=gtol)


@pytest.fixture
def pallas_ops(monkeypatch):
    """The kernels' module with an empty tile registry."""
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops
    monkeypatch.setattr(pallas_ops, "TILE_COUNTS", {})
    return pallas_ops


class TestFlashCausalTiles:
    """The causal sub-tiling inside a flash block: the plan (pure Python),
    and the kernels (interpret mode) where it visits, skips and masks."""

    @pytest.mark.parametrize("bq,bk,tile,off,causal,visited,masked", [
        (1024, 1024, 256, 0, True, 10, 4),     # the benchmark's block
        (1024, 1024, 128, 0, True, 36, 8),
        (1024, 1024, 512, 0, True, 3, 2),
        (1024, 1024, 256, 0, False, 16, 0),    # not causal: all, unmasked
        (512, 1024, 256, 0, True, 3, 2),       # lq < lk, top-left aligned
        (1024, 512, 256, 0, True, 7, 2),
        (256, 256, 128, 256, True, 4, 0),      # a block below the diagonal
        (256, 256, 128, -256, True, 0, 0),     # a block above it
        (256, 384, 128, 128, True, 5, 2),      # bq != bk, shifted diagonal
    ])
    def test_tile_plan_counts(self, pallas_ops, bq, bk, tile, off, causal,
                              visited, masked):
        plan = pallas_ops.tile_plan(bq, bk, tile, tile, off, causal)
        assert len(plan) == bq // tile
        assert pallas_ops.tile_counts(plan) == (visited, masked)

    @pytest.mark.parametrize("bq,bk,tq,tk,off,window", [
        (1024, 1024, 256, 256, 0, None), (512, 512, 128, 128, 0, None),
        (256, 384, 128, 128, 128, None), (256, 384, 128, 128, -128, None),
        (512, 256, 256, 128, 0, None), (256, 256, 128, 128, 255, None),
        (1024, 1024, 256, 256, 1024, 1024),  # the block under the diagonal's
        (1024, 1024, 256, 256, 0, 1024),     # window = block: edge outside
        (512, 512, 128, 128, 0, 200),        # edge inside a sub-tile
        (512, 512, 128, 128, 0, 256),        # edge on a sub-tile boundary
        (512, 512, 128, 128, 0, 16),         # both edges in one sub-tile
        (256, 256, 128, 128, 256, 300),      # the edge alone, shifted
        (256, 384, 128, 128, 128, 130)])
    def test_block_groups_cover_the_triangle_once(self, pallas_ops, bq, bk,
                                                  tq, tk, off, window):
        """Every visible (query, key) pair lies in exactly one piece, no
        unmasked piece holds a hidden pair, and a masked piece's own
        offsets reproduce the block's mask."""
        r, c = np.arange(bq)[:, None] + off, np.arange(bk)[None]
        visible = c <= r
        if window is not None:
            visible &= c > r - window
        seen = np.zeros((bq, bk), int)
        for rows, pieces in pallas_ops._block_groups(bq, bk, tq, tk, off,
                                                     window):
            for cols, hi, lo in pieces:
                n, m = seen[rows, cols].shape
                own = np.ones((n, m), bool)
                if hi is not None:
                    own &= np.arange(m)[None] <= np.arange(n)[:, None] + hi
                if lo is not None:
                    own &= np.arange(m)[None] > np.arange(n)[:, None] + lo
                np.testing.assert_array_equal(own, visible[rows, cols])
                seen[rows, cols] += own
        np.testing.assert_array_equal(seen, visible.astype(int))

    @pytest.mark.parametrize("l,counts", [(256, (1, 1, 1)),
                                          (512, (3, 4, 2)),
                                          (1024, (10, 16, 4))])
    def test_causal_mha_matches_dense(self, pallas_ops, l, counts):
        """d = 64, one block a head: one sub-tile (L = 256), then more than
        one visited, at least one skipped and one masked.  The one backward
        kernel writes dq's rows straight out and carries dk and dv over the
        query sub-tiles as values."""
        _flash_vs_dense(*_qkv(l=l, h=2, d=64, b=1, seed=l), causal=True)
        assert pallas_ops.TILE_COUNTS == {(l, l, True, None): counts}

    @pytest.mark.parametrize("lq,lk,counts", [
        (512, 1024, (3, 8, 2)),      # keys past the last query: dk = dv = 0
        (1024, 512, (7, 8, 2))])
    def test_one_block_lq_differs_from_lk(self, pallas_ops, lq, lk, counts):
        q, _, _ = _qkv(l=lq, h=2, d=64, b=1, seed=lq)
        _, k, v = _qkv(l=lk, h=2, d=64, b=1, seed=lk + 1)
        _flash_vs_dense(q, k, v, causal=True)
        assert pallas_ops.TILE_COUNTS == {(lq, lk, True, None): counts}

    def test_not_causal_visits_every_tile_unmasked(self, pallas_ops):
        _flash_vs_dense(*_qkv(l=512, h=2, d=64, b=1, seed=5), causal=False)
        assert pallas_ops.TILE_COUNTS == {(512, 512, False, None): (4, 4, 0)}

    @pytest.mark.parametrize("lq,lk,counts,h,kv,d,dv", [
        (512, 512, (10, 16, 4), 2, 2, 64, 64),   # grid skip + tile skip
        (256, 512, (3, 8, 2), 2, 2, 64, 64),     # lq != lk: top-left aligned
        (768, 768, (21, 36, 6), 2, 2, 64, 64),   # three blocks a side
        # the backward kernel's dk / dv live across a whole K/V head: summed
        # over the group's members (rep = 2) in the same sweep
        (768, 768, (21, 36, 6), 4, 2, 64, 64),
        (256, 512, (3, 8, 2), 4, 2, 64, 64),     # ... key blocks no query has
        (512, 512, (10, 16, 4), 2, 2, 192, 128),  # two widths
        (768, 768, (21, 36, 6), 4, 1, 192, 128),  # ... and a group of four
        (512, 256, (7, 8, 2), 4, 2, 64, 64),      # queries past the last key
    ])
    def test_grid_skip_and_tile_skip_together(self, pallas_ops, monkeypatch,
                                              lq, lk, counts, h, kv, d, dv):
        monkeypatch.setattr(pallas_ops, "BQ", 256)
        monkeypatch.setattr(pallas_ops, "BK", 256)
        monkeypatch.setattr(pallas_ops, "TILE", 128)
        q, _, _ = _qkv(l=lq, h=h, d=d, b=1, seed=lq)
        _, k, _ = _qkv(l=lk, h=kv, d=d, b=1, seed=lk + 1)
        _, _, v = _qkv(l=lk, h=kv, d=dv, b=1, seed=lk + 1)
        _flash_vs_dense(q, k, v, causal=True)
        assert pallas_ops.TILE_COUNTS == {(lq, lk, True, None): counts}

    # (b, l, h, kv, d, dv, window) of the cells' calls
    CELL_CALLS = {"gpt2s_train_1k": (4, 1024, 12, 12, 64, 64, None),
                  "mellum2 full": (1, 8192, 32, 4, 128, 128, None),
                  "mellum2 sliding": (1, 8192, 32, 4, 128, 128, 1024),
                  "kanana2": (1, 8192, 32, 32, 192, 128, None)}

    @staticmethod
    def _grad_jaxpr(pallas_ops, monkeypatch, shape):
        monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
        b, l, h, kv, d, dv, window = shape
        loss = lambda q, k, v: pallas_ops.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32).sum()
        sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
        return jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
            sds(b, l, h, d), sds(b, l, kv, d), sds(b, l, kv, dv)).jaxpr

    @pytest.mark.parametrize("cell,grid", [
        ("gpt2s_train_1k", (4, 12, 1, 1, 1)), ("mellum2 full", (1, 4, 8, 8, 8)),
        ("mellum2 sliding", (1, 4, 8, 8, 2)), ("kanana2", (1, 32, 1, 8, 8))])
    def test_gradient_is_two_kernels_and_no_partial_sum(
            self, pallas_ops, monkeypatch, cell, grid):
        """``jax.grad`` of a flash call holds exactly two ``pallas_call``s,
        the forward and the one backward, and no reduction but the
        backward's ``delta = rowsum(do * o)``: dq, dk and dv leave the
        kernel whole (the fused backward that was closed wrote dq as f32
        partials a key block and summed them in XLA)."""
        shape = self.CELL_CALLS[cell]
        eqns = self._grad_jaxpr(pallas_ops, monkeypatch, shape).eqns
        calls = [e for e in eqns if e.primitive.name == "pallas_call"]
        assert [e.params["name"] for e in calls] == ["flash_fwd",
                                                     "flash_bwd"]
        assert tuple(calls[1].params["grid_mapping"].grid) == grid
        outs = [v.aval.shape for v in calls[1].outvars]
        b, l, h, kv, d, dv, _ = shape
        assert outs == [(b, h, l, d), (b, kv, l, d), (b, kv, l, dv)]
        after = eqns[eqns.index(calls[1]) + 1:]
        assert {e.primitive.name for e in after} <= {"transpose"}
        reductions = [e for e in eqns if e.primitive.name.startswith(
            ("reduce", "dot_general", "cumsum"))]
        assert [e.outvars[0].aval.shape for e in reductions[-1:]] == [
            (b, h, l)]                                  # delta, before

    @pytest.mark.parametrize("cell,held", [
        ("gpt2s_train_1k", 0),                       # one block: no state
        ("mellum2 full", 2 * 8192 * 128 * (4 + 2 * 2)),
        ("mellum2 sliding", 2 * 8192 * 128 * (4 + 2 * 2)),
        ("kanana2", 8192 * (256 + 128) * (4 + 2 * 2))])    # 192 lanes -> 256
    def test_backward_asks_for_the_vmem_its_shape_needs(
            self, pallas_ops, monkeypatch, cell, held):
        """Mosaic's default 16 MiB for the walk of one block pair and, for
        a call that holds state, the K/V head's two f32 sums and its two
        whole-sequence bf16 output blocks twice (double-buffered): by
        hand."""
        call = [e for e in self._grad_jaxpr(
            pallas_ops, monkeypatch, self.CELL_CALLS[cell]).eqns
                if e.primitive.name == "pallas_call"][1]
        params = call.params["compiler_params"]["mosaic_tpu"]
        assert params.vmem_limit_bytes == 16 * 2 ** 20 + held
        assert params.vmem_limit_bytes < 48 * 2 ** 20   # of a core's 128


class TestGPT:
    def test_gpt2_small_param_count_canonical(self):
        """Tied-head GPT-2 small == 124,439,808 params (the published
        count: wte 50257x768 + wpe 1024x768 + 12 blocks + ln_f)."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
        m = get_model("gpt2_small")
        vs = jax.eval_shape(
            lambda: m.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
        n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(vs["params"]))
        assert n == 124_439_808

    def test_gpt_tiny_forward_shape_and_causality(self):
        """Logits at position t must not depend on tokens after t."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
        m = get_model("gpt_tiny")
        x = jnp.asarray(np.random.default_rng(0).integers(2, 100, (2, 16)),
                        jnp.int32)
        v = jax.jit(lambda k: m.init(k, x))(jax.random.key(0))
        out = m.apply(v, x)
        assert out.shape == (2, 16, 50257)
        x2 = x.at[:, 8:].set(7)  # perturb the future
        out2 = m.apply(v, x2)
        np.testing.assert_allclose(out[:, :8], out2[:, :8], atol=1e-5)
        assert np.abs(np.asarray(out[:, 8:]) -
                      np.asarray(out2[:, 8:])).max() > 1e-3

    def test_synthetic_lm_labels_are_shifted_inputs(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.data import load_dataset
        train, test = load_dataset("synthetic_lm", seed=0,
                                   limit_train=32, limit_test=8)
        assert train.num_classes == 1000
        np.testing.assert_array_equal(train.labels[:, :-1],
                                      train.images[:, 1:])
        assert (train.labels[:, -1] == -1).all()

    def test_gpt_tiny_e2e_dp_loss_decreases(self, mesh8):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        cfg = Config(model="gpt_tiny", dataset="synthetic_lm",
                     epochs_global=2, epochs_local=1, batch_size=8,
                     limit_train_samples=256, limit_eval_samples=64,
                     compute_dtype="float32", augment=False,
                     aggregation_by="weights", seed=0)
        res = train_global(cfg, mesh=mesh8, progress=False)
        l = res["global_train_losses"]
        assert l[-1] < l[0], l

    @pytest.mark.parametrize("axes,extra", [
        ({"data": 2, "model": 2}, {}),
        ({"data": 2, "seq": 2}, {"sequence_parallel": "ring"}),
        ({"data": 2, "pipe": 2}, {}),
        ({"data": 2, "expert": 2}, {"num_experts": 4}),
    ], ids=["tensor", "seq_ring", "pipeline", "expert_moe"])
    def test_gpt_tiny_parallel_modes(self, axes, extra, devices):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        mesh = build_mesh(axes, devices[:4])
        cfg = Config(model="gpt_tiny", dataset="synthetic_lm",
                     epochs_global=1, epochs_local=1, batch_size=8,
                     limit_train_samples=128, limit_eval_samples=32,
                     compute_dtype="float32", augment=False,
                     aggregation_by="weights", seed=1, **extra)
        res = train_global(cfg, mesh=mesh, progress=False)
        assert np.isfinite(res["global_train_losses"]).all()

    def test_gpt_tp_vocab_parallel_tied_head_matches_dense(self, devices):
        """GPT x TP shards the TIED embedding table's vocab dim (r4):
        masked-psum lookup + local-slice logits must compute exactly the
        dense function — trajectories equal, table physically sharded."""
        import jax
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh

        def run(axes, devs):
            cfg = Config(model="gpt_tiny", dataset="synthetic_lm",
                         epochs_global=2, epochs_local=1, batch_size=8,
                         limit_train_samples=128, limit_eval_samples=32,
                         compute_dtype="float32", augment=False,
                         aggregation_by="weights", seed=5)
            return train_global(cfg, mesh=build_mesh(axes, devs),
                                progress=False)

        dense = run({"data": 2}, devices[:2])
        tp = run({"data": 2, "model": 2}, devices[:4])
        np.testing.assert_allclose(tp["global_train_losses"],
                                   dense["global_train_losses"], rtol=2e-3)
        emb = tp["state"].params["tok_emb"]["embedding"]
        assert "model" in str(emb.sharding.spec)
