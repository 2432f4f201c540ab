"""Tests for the round-1-untested layer: ResNet-18/50, BERT, flash
attention (VERDICT r1 'Next' #5 — no source file with zero test references).

ResNet parameter counts are asserted against the canonical torchvision
values (resnet18 = 11,689,512; resnet50 = 25,557,032 at 1000 classes,
imagenet stem), pinning architectural parity for BASELINE ladder entries
3 and 4.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _init(model, shape, dtype=jnp.float32):
    return jax.jit(functools.partial(model.init, train=False))(
        jax.random.key(0), jnp.zeros(shape, dtype))


def _param_count(variables):
    return int(sum(np.prod(p.shape)
                   for p in jax.tree.leaves(variables["params"])))


class TestResNet:
    def test_resnet18_imagenet_param_count_matches_torchvision(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.resnet import ResNet18
        v = _init(ResNet18(num_classes=1000, stem="imagenet"), (1, 64, 64, 3))
        assert _param_count(v) == 11_689_512

    def test_resnet50_imagenet_param_count_matches_torchvision(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.resnet import ResNet50
        v = _init(ResNet50(num_classes=1000, stem="imagenet"), (1, 64, 64, 3))
        assert _param_count(v) == 25_557_032

    def test_resnet18_cifar_forward_shape_and_grads(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.resnet import ResNet18
        m = ResNet18(num_classes=10, stem="cifar")
        v = _init(m, (2, 32, 32, 3))
        assert _param_count(v) == 11_173_962

        @jax.jit
        def loss_fn(params):
            out, _ = m.apply({"params": params,
                              "batch_stats": v["batch_stats"]},
                             jnp.ones((2, 32, 32, 3)), train=True,
                             mutable=["batch_stats"])
            assert out.shape == (2, 10)
            return (out ** 2).mean()

        grads = jax.grad(loss_fn)(v["params"])
        assert all(np.isfinite(g).all() for g in jax.tree.leaves(grads))

    def test_resnet50_forward_shape(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.resnet import ResNet50
        m = ResNet50(num_classes=1000, stem="imagenet")
        v = _init(m, (1, 64, 64, 3))
        out = jax.jit(functools.partial(m.apply, train=False))(
            v, jnp.ones((1, 64, 64, 3)))
        assert out.shape == (1, 1000)
        # imagenet stem: 64x64 -> /4 stem -> /8 stages = 2x2 pre-pool
        assert np.isfinite(out).all()


class TestLeNet:
    def test_avg_pool_2x2_matches_nn_avg_pool(self):
        """The reshape-mean pooling (TPU-backend compile-hang workaround)
        must be numerically identical to flax's nn.avg_pool."""
        import flax.linen as nn
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.lenet import _avg_pool_2x2
        x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 28, 28, 6)),
                        jnp.float32)
        np.testing.assert_allclose(_avg_pool_2x2(x),
                                   nn.avg_pool(x, (2, 2), strides=(2, 2)),
                                   atol=1e-6)

    @pytest.mark.parametrize("padding,cin,cout", [("SAME", 1, 6),
                                                  ("VALID", 6, 16)])
    def test_im2col_conv_matches_nn_conv(self, padding, cin, cout):
        """The im2col patch-matmul conv (TPU compile-hang workaround +
        MXU-utilization win for tiny channel counts) must match nn.Conv
        exactly, parameter-for-parameter."""
        import flax.linen as nn
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.lenet import ConvIm2Col
        x = jnp.asarray(np.random.default_rng(1).normal(size=(4, 14, 14, cin)),
                        jnp.float32)
        m = ConvIm2Col(cout, (5, 5), padding=padding)
        v = m.init(jax.random.key(2), x)
        assert set(v["params"]) == {"kernel", "bias"}
        assert v["params"]["kernel"].shape == (5, 5, cin, cout)
        ref = nn.Conv(cout, (5, 5), padding=padding)
        out_ref = ref.apply(
            {"params": {"kernel": v["params"]["kernel"],
                        "bias": v["params"]["bias"]}}, x)
        np.testing.assert_allclose(m.apply(v, x), out_ref, atol=1e-5)

    def test_lenet5_param_count_forward_shape_and_grads(self):
        """LeNet-5 (SAME 5x5 stem on 28x28): 28->14->10->5 spatial,
        61,706 params (classic LeCun-98 count with the modern SAME stem)."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.lenet import LeNet5
        m = LeNet5(num_classes=10)
        v = _init(m, (2, 28, 28, 1))
        assert _param_count(v) == 61_706

        @jax.jit
        def loss_fn(params):
            out = m.apply({"params": params}, jnp.ones((2, 28, 28, 1)),
                          train=True)
            assert out.shape == (2, 10)
            return (out ** 2).mean()

        grads = jax.grad(loss_fn)(v["params"])
        assert all(np.isfinite(g).all() for g in jax.tree.leaves(grads))


class TestBert:
    def _tiny(self, **kw):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
        return get_model("bert_tiny", num_classes=1000, **kw)

    def test_forward_shape(self):
        m = self._tiny()
        ids = jnp.ones((2, 32), jnp.int32)
        v = _init(m, (2, 32), jnp.int32)
        out = jax.jit(functools.partial(m.apply, train=False))(v, ids)
        assert out.shape == (2, 32, 1000)

    def test_param_count_formula(self):
        # tok_emb V*H + pos_emb 512*H + ln_emb 2H
        # + per layer: qkv 3(H*H+H) + out H*H+H + 2 LN 4H + ffn H*F+F+F*H+H
        # + head: H*H+H + 2H + H*V+V
        V, H, F, L, P = 1000, 64, 128, 2, 512
        per_layer = 3 * (H * H + H) + H * H + H + 4 * H + H * F + F + F * H + H
        expect = (V * H + P * H + 2 * H + L * per_layer
                  + H * H + H + 2 * H + H * V + V)
        v = _init(self._tiny(), (2, 32), jnp.int32)
        assert _param_count(v) == expect

    def test_grads_finite(self):
        m = self._tiny()
        v = _init(m, (2, 32), jnp.int32)
        ids = jnp.ones((2, 32), jnp.int32)

        @jax.jit
        def loss_fn(params):
            out = m.apply({"params": params}, ids, train=True)
            return (out ** 2).mean()

        grads = jax.grad(loss_fn)(v["params"])
        assert all(np.isfinite(g).all() for g in jax.tree.leaves(grads))

    def test_bert_base_is_base_sized(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
        m = get_model("bert_base", num_classes=30522)
        assert (m.num_layers, m.hidden, m.num_heads, m.ffn_dim) == \
            (12, 768, 12, 3072)


@pytest.mark.slow
class TestFlashAttention:
    """Pallas flash kernel in interpret mode (CPU) vs the dense reference."""

    def _qkv(self, b=2, l=256, h=2, d=64, seed=0, dtype=jnp.float32):
        rng = np.random.default_rng(seed)
        return tuple(jnp.asarray(rng.normal(size=(b, l, h, d)), dtype)
                     for _ in range(3))

    def test_forward_matches_dense(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.pallas_ops import flash_attention
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import dot_product_attention
        q, k, v = self._qkv()
        np.testing.assert_allclose(flash_attention(q, k, v),
                                   dot_product_attention(q, k, v), atol=1e-5)

    def test_backward_matches_dense(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.pallas_ops import flash_attention
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import dot_product_attention
        q, k, v = self._qkv(seed=1)
        g = jax.grad(lambda *a: (flash_attention(*a) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        gref = jax.grad(lambda *a: (dot_product_attention(*a) ** 2).sum(),
                        argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, gref):
            np.testing.assert_allclose(a, b, atol=1e-4)

    def test_unaligned_shapes_fall_back_to_dense(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.pallas_ops import flash_attention
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import dot_product_attention
        q, k, v = self._qkv(l=100, seed=2)  # 100 % 128 != 0
        np.testing.assert_allclose(flash_attention(q, k, v),
                                   dot_product_attention(q, k, v), atol=1e-6)

    def test_attend_dispatch(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import attend
        q, k, v = self._qkv(l=128, seed=3)
        np.testing.assert_allclose(attend(q, k, v, impl="flash"),
                                   attend(q, k, v, impl="dense"), atol=1e-5)
        with pytest.raises(ValueError):
            attend(q, k, v, impl="nope")
        with pytest.raises(ValueError):
            attend(q, k, v, impl="ring")  # no axis_name

    def test_driver_attention_impl_flash(self, devices):
        """--attention_impl flash is plumbed through config -> driver ->
        engine.  On CPU the kernel falls back to dense inside shard_map
        (Pallas HLO-interpreter limitation), so this asserts the plumbing
        and exact numerical agreement; the kernel itself is covered by the
        unit tests above and compiles for real inside the TPU round
        program (the benchmark's cells ``gpt2s_train_1k`` and
        ``mellum2_train_8k``)."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
        mesh = build_mesh({"data": 2}, devices[:2])
        kw = dict(model="bert_tiny", dataset="synthetic_mlm",
                  epochs_global=1, epochs_local=1, batch_size=4,
                  limit_train_samples=32, limit_eval_samples=16,
                  compute_dtype="float32", augment=False,
                  aggregation_by="weights", seed=5)
        flash = train_global(Config(attention_impl="flash", **kw),
                             mesh=mesh, progress=False)
        dense = train_global(Config(**kw), mesh=mesh, progress=False)
        np.testing.assert_allclose(flash["global_train_losses"],
                                   dense["global_train_losses"], rtol=1e-4)
