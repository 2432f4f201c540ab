"""Grouped-query attention: K/V stay at the grouped head count end to end.

GQA's point is K/V bandwidth (and ring-traffic) savings, so ``attend`` and
every impl behind it consume [B, L, KV, D] K/V directly — these tests pin
each impl's grouped path to the reference semantics (repeat K/V to the full
head count, run MHA):

- dense grouped einsum == repeat-then-MHA (forward + grads, causal too);
- Pallas flash kernels (forward + blockwise backward) == grouped dense;
- ring attention (rep-x smaller rotating blocks) == grouped dense;
- Ulysses == grouped dense when kv_heads divide the seq axis, loud error
  otherwise.

No reference equivalent exists (the reference has no attention at all,
SURVEY.md section 2.3); GQA is part of the Llama family (models/llama.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import (
    attend,
    dot_product_attention,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.parallel.sp import (
    ring_attention,
    ulysses_attention,
)

H, KV, D = 4, 2, 16
REP = H // KV


def _qkv(b=2, l=64, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, l, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(b, l, KV, D)), dtype)
    v = jnp.asarray(rng.normal(size=(b, l, KV, D)), dtype)
    return q, k, v


def _expanded(q, k, v):
    """The semantics GQA must reproduce: repeat K/V to full heads, run MHA."""
    return q, jnp.repeat(k, REP, axis=2), jnp.repeat(v, REP, axis=2)


class TestDenseGrouped:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_expanded(self, causal):
        q, k, v = _qkv()
        out = dot_product_attention(q, k, v, causal=causal)
        ref = dot_product_attention(*_expanded(q, k, v), causal=causal)
        np.testing.assert_allclose(out, ref, atol=1e-6)

    def test_grads_match_expanded(self):
        q, k, v = _qkv(seed=1)
        g = jax.grad(lambda *a: (dot_product_attention(*a) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gref = jax.grad(
            lambda q, k, v: (dot_product_attention(
                *_expanded(q, k, v)) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gref):
            np.testing.assert_allclose(a, b, atol=1e-5)

    def test_indivisible_heads_rejected(self):
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, 8, 4, D)), jnp.float32)
        k = v = jnp.asarray(rng.normal(size=(1, 8, 3, D)), jnp.float32)
        with pytest.raises(ValueError, match="not divisible"):
            dot_product_attention(q, k, v)


@pytest.mark.slow
class TestFlashGrouped:
    """The Pallas kernels (interpret mode on CPU) with grouped K/V block
    specs and the group-folded dK/dV grid."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_dense(self, causal):
        q, k, v = _qkv(l=256)
        out = attend(q, k, v, impl="flash", causal=causal)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_dense(self, causal):
        q, k, v = _qkv(l=256, seed=2)
        loss = lambda impl: lambda q, k, v: (
            attend(q, k, v, impl=impl, causal=causal) ** 2).sum()
        g = jax.grad(loss("flash"), argnums=(0, 1, 2))(q, k, v)
        gref = jax.grad(loss("dense"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gref):
            np.testing.assert_allclose(a, b, atol=5e-4)


class TestFlashGroupedCausalTiles:
    """GQA (rep = 2) through the causal sub-tiling of a flash block: the
    dK/dV kernel folds the query group into its innermost grid dimension,
    so its tile geometry has to use ``it % ni``.  d = 64, tolerances of
    ``TestFlashGrouped``."""

    @staticmethod
    def _check(l, kv_l=None):
        rng = np.random.default_rng(l)
        q = jnp.asarray(rng.normal(size=(1, l, 2, 64)), jnp.float32)
        k, v = (jnp.asarray(rng.normal(size=(1, kv_l or l, 1, 64)),
                            jnp.float32) for _ in range(2))
        both = lambda impl: jax.jit(lambda *a: (
            attend(*a, impl=impl, causal=True),
            jax.grad(lambda *b: (attend(*b, impl=impl, causal=True)
                                 ** 2).sum(), argnums=(0, 1, 2))(*a)))
        (of, gf), (od, gd) = both("flash")(q, k, v), both("dense")(q, k, v)
        np.testing.assert_allclose(of, od, atol=2e-5)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(a, b, atol=5e-4)

    @pytest.mark.parametrize("l,counts", [(512, (3, 4, 2)),
                                          (1024, (10, 16, 4))])
    def test_one_block_a_head(self, monkeypatch, l, counts):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops
        monkeypatch.setattr(pallas_ops, "TILE_COUNTS", {})
        self._check(l)
        assert pallas_ops.TILE_COUNTS == {(l, l, True, None): counts}

    def test_grid_skip_and_tile_skip_together(self, monkeypatch):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops
        for name, value in (("BQ", 256), ("BK", 256), ("TILE", 128),
                            ("TILE_COUNTS", {})):
            monkeypatch.setattr(pallas_ops, name, value)
        self._check(512)
        assert pallas_ops.TILE_COUNTS == {(512, 512, True, None): (10, 16, 4)}


@pytest.fixture(scope="module")
def seq_mesh(devices):
    return Mesh(np.array(devices[:2]), ("seq",))


def _sharded(seq_mesh, fn):
    return jax.jit(jax.shard_map(
        fn, mesh=seq_mesh,
        in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq")))


@pytest.mark.slow
class TestRingGrouped:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_matches_dense(self, seq_mesh, causal):
        q, k, v = _qkv()
        out = _sharded(seq_mesh, lambda q, k, v: ring_attention(
            q, k, v, "seq", causal=causal))(q, k, v)
        ref = dot_product_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_grads_match_dense(self, seq_mesh):
        q, k, v = _qkv(seed=3)
        ring = _sharded(seq_mesh,
                        lambda q, k, v: ring_attention(q, k, v, "seq"))
        g = jax.grad(lambda *a: (ring(*a) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gref = jax.grad(lambda *a: (dot_product_attention(*a) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gref):
            np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.slow
class TestUlyssesGrouped:
    def test_forward_matches_dense(self, seq_mesh):
        # seq axis 2 divides both H=4 and KV=2
        q, k, v = _qkv()
        out = _sharded(seq_mesh, lambda q, k, v: ulysses_attention(
            q, k, v, "seq"))(q, k, v)
        ref = dot_product_attention(q, k, v)
        np.testing.assert_allclose(out, ref, atol=1e-5)

    def test_kv_not_divisible_rejected(self, devices):
        mesh = Mesh(np.array(devices[:4]), ("seq",))
        q, k, v = _qkv()   # KV=2 not divisible by seq=4
        f = jax.jit(jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "seq"), mesh=mesh,
            in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq")))
        with pytest.raises(ValueError, match="kv heads"):
            f(q, k, v)
