"""The flash kernels compiled for the v5e by the TPU compiler installed in
the sandbox: no chip is attached and nothing runs, so this proves that
Mosaic lowers the kernels at the real widths (what interpret mode cannot
show: tile alignment of the sub-tile slices, VMEM use), never a result
and never a time.  The topology is described inside a fixture, and only
in this file: one process at a time may load the TPU's library."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib.trace import kernel_of, load_kernels  # noqa: E402
from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops  # noqa: E402


def _mosaic_calls(text: str) -> list:
    """The Mosaic calls of a compiled program's text, each under the name
    the benchmark's picker files give it, sorted."""
    return sorted(kernel_of(line.strip(), load_kernels())
                  for line in text.splitlines() if "tpu_custom_call" in line)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("b,lq,lk,h,kv,causal", [
    (4, 1024, 1024, 12, 12, True),     # gpt2s_train_1k: one block a head
    (1, 4096, 4096, 12, 12, True),     # grid skip + tile skip
    (1, 2048, 2048, 16, 4, True),      # GQA: the group's members a grid dim
    (2, 512, 1024, 4, 4, True),        # one block, keys past the last query
    (16, 512, 512, 12, 12, False),     # bert / vit: the untiled body
])
def test_flash_kernels_lower_for_v5e(one_chip, monkeypatch, b, lq, lk, h, kv,
                                     causal):
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_ops, "TILE_COUNTS", {})
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    loss = lambda q, k, v: pallas_ops._flash(q, k, v, causal).astype(
        jnp.float32).sum()
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        sds(b, lq, h, 64), sds(b, lk, kv, 64), sds(b, lk, kv, 64)).compile()
    # the forward and the one backward kernel
    assert compiled.as_text().count("tpu_custom_call") == 2
    (visited, total, masked), = pallas_ops.TILE_COUNTS.values()
    assert (visited < total, masked > 0) == (causal, causal)


@pytest.mark.parametrize("window,counts,grid", [
    (1024, (150, 1024, 60), (16, 15, 64)),
    (None, (528, 1024, 32), (64, 36, 64)),
    # trinity_mini_train_8k's sliding call (ISSUE 32): the same operands
    # under a window of two key blocks, the first call whose rows hold a
    # block wholly inside the band between a crossed one and the diagonal's
    (2048, (252, 1024, 56), (24, 21, 64))])
def test_mellum_attention_lowers_for_v5e(one_chip, monkeypatch, window,
                                         counts, grid):
    """mellum2_train_8k's two attention calls: L = 8192, 32 query and 4
    key-value heads of width 128, the window of 1024 or none; the first
    calls through the blocks that carry their softmax state, on a grid of
    the 2 blocks a row its window leaves.  Two Mosaic calls since ISSUE 31:
    the forward and the one backward kernel, which holds a K/V head's dk
    and dv sums for the whole sequence in VMEM (that it compiles at the
    published shape is the test of its VMEM budget).  The benchmark's
    picker names each compiled call from its text (operand count and
    output kind): the accepted ``kernels/flash_*.json`` read the backward
    (six operands, a tuple out) as ``flash_dkv`` and find no ``flash_dq``,
    so the three flash rooflines read ``None`` until a ``benchmark`` PR
    adds ``kernels/flash_bwd.json``; what that PR has to change fails here
    and not as a metric missing on the chip."""
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_ops, "TILE_COUNTS", {})
    monkeypatch.setattr(pallas_ops, "GRID_COUNTS", {})
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    loss = lambda q, k, v: pallas_ops._flash(q, k, v, True, window).astype(
        jnp.float32).sum()
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        sds(1, 8192, 32, 128), sds(1, 8192, 4, 128),
        sds(1, 8192, 4, 128)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    assert _mosaic_calls(text) == ["flash_dkv", "flash_fwd"]
    assert pallas_ops.TILE_COUNTS == {(8192, 8192, True, window): counts}
    assert pallas_ops.GRID_COUNTS == {(8192, 8192, True, window): grid}


def test_latent_attention_lowers_for_v5e(one_chip, monkeypatch):
    """kanana2_train_8k's attention call: L = 8192, 32 heads, scores 192
    wide (a lane tile and a half) and values 128, causal, the rotary key
    already copied to the heads: two Mosaic calls, which the accepted
    ``kernels/flash_*.json`` read as ``flash_fwd`` and ``flash_dkv`` (the
    one backward kernel: 24 MiB of a head's sums and output blocks beside
    its walk), on the full layer's walk."""
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    monkeypatch.setattr(pallas_ops, "TILE_COUNTS", {})
    monkeypatch.setattr(pallas_ops, "GRID_COUNTS", {})
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    loss = lambda q, k, v: pallas_ops._flash(q, k, v, True).astype(
        jnp.float32).sum()
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
        sds(1, 8192, 32, 192), sds(1, 8192, 32, 192),
        sds(1, 8192, 32, 128)).compile()
    assert _mosaic_calls(compiled.as_text()) == ["flash_dkv", "flash_fwd"]
    assert pallas_ops.TILE_COUNTS == {(8192, 8192, True, None):
                                      (528, 1024, 32)}
    assert pallas_ops.GRID_COUNTS == {(8192, 8192, True, None):
                                      (64, 36, 64)}


def test_grouped_expert_products_lower_for_v5e(one_chip, monkeypatch):
    """The routed layer's three products and their gradients at the
    published widths, over the worst-case 65,536 rows: 2 forward products
    that the gradient needs, 3 for the rows' gradient, 3 for the
    weights'."""
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.grouped_matmul import grouped_matmul
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    sds = lambda *s, d=jnp.bfloat16: jax.ShapeDtypeStruct(s, d,
                                                          sharding=one_chip)

    def ffn(x, w1, w3, w2, sizes):
        gate = grouped_matmul(x, w1, sizes)
        up = grouped_matmul(x, w3, sizes)
        return grouped_matmul(jax.nn.silu(gate) * up, w2, sizes).astype(
            jnp.float32).sum()

    compiled = jax.jit(jax.grad(ffn, (0, 1, 2, 3))).lower(
        sds(65536, 2304), sds(16, 2304, 896), sds(16, 2304, 896),
        sds(16, 896, 2304), sds(16, d=jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 8


def test_routed_layer_on_its_row_buffer_lowers_for_v5e(one_chip, monkeypatch):
    """trinity_mini_train_8k's routed layer (ISSUE 33), forward and
    backward under ``jax.checkpoint``: 8 of 128 experts held, 8 a token,
    8,192 tokens of 2,048.  Everything with 2,048 or 1,024 columns has the
    buffer's 5,120 rows: no array of the worst case's 65,536 rows has more
    than one column (the sort's int32 vectors do).  The grouped products,
    chunk 0's and the overflow loop's, are the Mosaic calls of four
    operands and one output that the accepted ``kernels/moe_gmm.json``
    picks."""
    import re
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import moe
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.models.arch import Router
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    assert moe.row_buffer(8192 * 8, 8, 128) == (65536, 5120)
    layer = moe.RoutedExperts(128, 1024, 8, experts_held=(0, 8),
                              dtype=jnp.bfloat16,
                              router=Router(score="sigmoid"))
    shaped = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=one_chip)
    x = shaped(jnp.zeros((1, 8192, 2048), jnp.bfloat16))
    params = jax.tree_util.tree_map(shaped, jax.eval_shape(
        lambda: layer.init(jax.random.key(0), jnp.zeros(
            x.shape, x.dtype))["params"]))
    block = jax.checkpoint(lambda p, x: layer.apply(
        {"params": p}, x, mutable=["counters"])[0])
    text = jax.jit(jax.grad(lambda p, x: block(p, x).astype(
        jnp.float32).sum(), (0, 1))).lower(params, x).compile().as_text()
    assert not re.findall(r"\w+\[65536,\d+\]", text)
    assert re.findall(r"bf16\[5120,2048\]", text)
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line]
    # chunk 0's 3 forward products, recomputed (the recomputed forward's
    # overflow loop is dead code: the backward recomputes those chunks
    # itself); the backward's 6 of chunk 0, and 3 + 6 in its loop
    assert len(calls) == 18
    assert {kernel_of(line, load_kernels()) for line in calls} == {"moe_gmm"}


def _scanned_attention_grad(one_chip, heads, kv, d, dv, window, policy):
    """The compiled gradient of two scanned layers of projections, one
    ``_flash`` call and the output projection at L = 8192 and a model width
    of 2048, each layer rematerialised under ``policy`` as the decoder's
    blocks are (``nn.remat`` inside ``nn.scan``, ``prevent_cse=False``): the
    scan keeps XLA from merging a recomputed call with the forward's."""
    hidden, layers = 2048, 2
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)

    def layer(x, w):
        wq, wk, wv, wo = w
        heads_of = lambda y, n, m: y.reshape(*y.shape[:2], n, m)
        o = pallas_ops._flash(heads_of(x @ wq, heads, d),
                              heads_of(x @ wk, kv, d),
                              heads_of(x @ wv, kv, dv), True, window)
        return x + o.reshape(*x.shape[:2], heads * dv) @ wo

    if policy is not None:
        layer = jax.checkpoint(layer, policy=policy, prevent_cse=False)

    def loss(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (layer(c, w), None), x, ws)
        return y.astype(jnp.float32).sum()

    return jax.jit(jax.grad(loss, (0, 1))).lower(
        sds(1, 8192, hidden),
        (sds(layers, hidden, heads * d), sds(layers, hidden, kv * d),
         sds(layers, hidden, kv * dv), sds(layers, heads * dv, hidden))
    ).compile()


# the three sparse cells' attention calls: grouped queries 32 / 4 of width
# 128 under mellum's window, trinity's and none, and kanana's latent call
SPARSE_CALLS = {"window1024": (32, 4, 128, 128, 1024),
                "window2048": (32, 4, 128, 128, 2048),
                "full": (32, 4, 128, 128, None),
                "latent": (32, 32, 192, 128, None)}


@pytest.mark.parametrize("call", SPARSE_CALLS)
@pytest.mark.parametrize("policy", ["none", "everything", "dots_saveable",
                                    "save_names:attn_out"])
def test_a_remat_policy_keeps_the_flash_residuals(one_chip, monkeypatch, call,
                                                policy):
    """ISSUE 35: what a flash call produced is a named residual that every
    policy of ``models.checkpoint_policy`` keeps, so a rematerialised
    layer's backward pass holds the one backward kernel and no second
    forward: two Mosaic calls in the compiled gradient, as with no
    ``jax.checkpoint`` at all."""
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import checkpoint_policy
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    compiled = _scanned_attention_grad(
        one_chip, *SPARSE_CALLS[call],
        None if policy == "none" else checkpoint_policy(policy))
    assert _mosaic_calls(compiled.as_text()) == ["flash_dkv", "flash_fwd"]


def test_a_policy_that_saves_nothing_would_run_it_twice(one_chip,
                                                        monkeypatch):
    """The control of the test above: under jax's own ``nothing_saveable``
    (what ``everything`` resolved to before ISSUE 35) the same program
    holds the forward kernel twice."""
    monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
    compiled = _scanned_attention_grad(
        one_chip, *SPARSE_CALLS["window1024"],
        jax.checkpoint_policies.nothing_saveable)
    assert _mosaic_calls(compiled.as_text()) == [
        "flash_dkv", "flash_fwd", "flash_fwd"]
