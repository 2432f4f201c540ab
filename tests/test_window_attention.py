"""Window and full attention mixed in the flash kernels (ISSUE 26): the
windowed kernels against ``dot_product_attention`` with the explicit mask
(interpret mode), the tile plan of the benchmark's L = 8192 / window 1024
call against a count by hand, and the YaRN frequencies against the
formula.  The grid that walks only the blocks a window leaves (ISSUE 27):
the same comparison where it shrinks, its step counts by hand, and the
two ``pallas_call`` grids (forward, the one backward: ISSUE 31) read out of
the jaxpr."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops
from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import (
    attend, causal_mask, dot_product_attention, rope, rope_frequencies)


@pytest.fixture
def small_blocks(monkeypatch):
    """256-wide grid blocks of 128-wide sub-tiles, so that a 512-position
    call has grid blocks to skip and sub-tiles to mask."""
    monkeypatch.setattr(pallas_ops, "BQ", 256)
    monkeypatch.setattr(pallas_ops, "BK", 256)
    monkeypatch.setattr(pallas_ops, "TILE", 128)
    monkeypatch.setattr(pallas_ops, "TILE_COUNTS", {})
    monkeypatch.setattr(pallas_ops, "GRID_COUNTS", {})
    return pallas_ops


def _qkv(l, h, kv, d, seed):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (1, l, h, d), jnp.float32),
            jax.random.normal(ks[1], (1, l, kv, d), jnp.float32),
            jax.random.normal(ks[2], (1, l, kv, d), jnp.float32))


def _assert_matches_dense(lq, lk, window):
    """GQA 8:1 at head width 128: forward, dq and the grouped dk / dv of
    the windowed kernels against the dense path with the explicit mask."""
    q, _, _ = _qkv(lq, 8, 1, 128, seed=window)
    _, k, v = _qkv(lk, 8, 1, 128, seed=window + (lq != lk))
    flash = lambda q, k, v: pallas_ops.flash_attention(
        q, k, v, causal=True, window=window)
    dense = lambda q, k, v: dot_product_attention(
        q, k, v, mask=causal_mask(lq, lk, window=window))
    both = lambda fn: jax.jit(lambda *a: (fn(*a), jax.grad(
        lambda *b: (fn(*b) ** 2).sum(), argnums=(0, 1, 2))(*a)))
    (of, gf), (od, gd) = both(flash)(q, k, v), both(dense)(q, k, v)
    np.testing.assert_allclose(of, od, atol=2e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(a, b, atol=2e-4)


class TestWindowedFlash:
    @pytest.mark.parametrize("l,window,counts", [
        (512, 200, (9, 16, 9)),      # edge inside a sub-tile
        (512, 256, (9, 16, 6)),      # edge on a sub-tile boundary
        (512, 300, (10, 16, 7)),     # edge across grid blocks
        (512, 16, (7, 16, 7)),       # both edges in the diagonal's sub-tile
        (768, 512, (20, 36, 8)),    # three blocks a side, one skipped
        # a window of TWO key blocks (ISSUE 32: the benchmark's window 2048
        # at 1024-wide blocks): rows 2 and 3 have all three kinds of block,
        # crossed by the window's edge, WHOLLY INSIDE the band, on the
        # diagonal.  By hand, sub-tile (a, b) is visited where 0 <= a - b <=
        # 4: 1 + 2 + 3 + 4 + 5 x 4 = 30 of 64; masked the 8 on the diagonal
        # and the 4 with a - b = 4, which the edge cuts
        (1024, 512, (30, 64, 12)),
        # the same with the edge inside a sub-tile: a - b <= 5, 33 visited;
        # the edge cuts the 4 sub-tiles at a - b = 4 and the 3 at 5
        (1024, 600, (33, 64, 15)),
    ])
    def test_forward_and_gradients_match_dense(self, small_blocks, l, window,
                                               counts):
        _assert_matches_dense(l, l, window)
        assert small_blocks.TILE_COUNTS == {(l, l, True, window): counts}

    @pytest.mark.parametrize("lq,lk,window,grid", [
        # 256-wide blocks.  L = 1024, window 256: a row needs its own block
        # and the one before, 2 of 4; row 0 has one, so 8 walked, 7 with work
        (1024, 1024, 256, (8, 7, 16)),
        # window 300 reaches a third block (300 - 1 > 256): rows 0..4 have
        # 1, 2, 3, 3, 3 blocks
        (1280, 1280, 300, (15, 12, 25)),
        # an edge inside a sub-tile: the blocks are window 256's
        (1024, 1024, 200, (8, 7, 16)),
        # one sub-tile of window: still the block before, for its last keys
        (1024, 1024, 128, (8, 7, 16)),
        # more keys than queries (top-left aligned): rows 0, 1 have 1, 2 of
        # the 4 key blocks; key blocks 2 and 3 have no query, and the
        # backward's dk / dv there are the zeros its sums start from
        (512, 1024, 256, (4, 3, 8)),
        # more queries than keys: rows 0..3 have key blocks 0, 0-1, 1-2, 2
        # (the last row's own block 3 is not there): 2 steps a row
        (1024, 768, 257, (8, 6, 12)),
        # a window of two key blocks: rows 0..4 have 1, 2, 3, 3, 3 blocks,
        # the middle one of three wholly inside the band
        (1280, 1280, 512, (15, 12, 25)),
    ])
    def test_shrunk_grid_matches_dense(self, small_blocks, lq, lk, window,
                                       grid):
        """Where the window leaves a row fewer blocks than the sequence
        has, the innermost grid dimension is the most any row needs: GQA
        8:1, forward, dq and the grouped dk / dv against the dense mask,
        and the walked / with-work grid steps a head by hand (one walk:
        the backward kernel's grid is the forward's with the group's
        members apart).  Every block holds random data, block 0's row and
        the last key block too: a step past a row's end clamps to them,
        and an unguarded one would add them a second time."""
        _assert_matches_dense(lq, lk, window)
        assert small_blocks.GRID_COUNTS == {(lq, lk, True, window): grid}

    def test_a_step_past_the_last_block_does_nothing(self, small_blocks):
        """The last query block's row where the keys end before the queries
        do: with 1024 queries, 768 keys and window 257, step (i = 3, jj = 1)
        names key block 3, which is not there; its offset 3 x 256 - 3 x 256
        = 0 is the diagonal's crossing offset, and the index map has
        clamped the step to key block 2.  The guard is ``live``: without
        it the backward adds block 2's share to dq's last 256 rows a second
        time and indexes its dk / dv sums past their end; the forward the
        same for its softmax state."""
        keys = small_blocks._key_walk(4, 3, 256, 256, True, 257)
        j, live = keys.block(jnp.int32(3), jnp.int32(1))
        assert (int(j), bool(live)) == (3, False)
        assert int(keys.loaded(jnp.int32(3), jnp.int32(1))) == 2
        assert 0 in small_blocks._crossing_offsets(4, 3, 256, 256, 257)
        q, _, _ = _qkv(1024, 8, 1, 128, seed=11)
        _, k, v = _qkv(768, 8, 1, 128, seed=12)
        grads = lambda fn: jax.jit(jax.grad(
            lambda *a: (fn(*a) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
        flash = grads(lambda q, k, v: pallas_ops.flash_attention(
            q, k, v, causal=True, window=257))
        dense = grads(lambda q, k, v: dot_product_attention(
            q, k, v, mask=causal_mask(1024, 768, window=257)))
        np.testing.assert_allclose(flash[0][:, -256:], dense[0][:, -256:],
                                   atol=2e-4)
        for a, b in zip(flash[1:], dense[1:]):
            np.testing.assert_allclose(a[:, -256:], b[:, -256:], atol=2e-4)

    def test_window_of_the_whole_sequence_is_the_causal_call(self,
                                                             small_blocks):
        q, k, v = _qkv(256, 2, 2, 64, seed=3)
        a = pallas_ops.flash_attention(q, k, v, causal=True, window=256)
        b = pallas_ops.flash_attention(q, k, v, causal=True)
        np.testing.assert_array_equal(a, b)

    def test_window_needs_causal(self):
        q, k, v = _qkv(128, 2, 2, 64, seed=4)
        with pytest.raises(ValueError, match="causal"):
            pallas_ops.flash_attention(q, k, v, window=16)
        with pytest.raises(NotImplementedError, match="window"):
            attend(q, k, v, impl="ring", axis_name="seq", causal=True,
                   window=16)

    def test_dense_window_mask_counts_the_position_itself(self):
        m = np.asarray(causal_mask(6, 6, window=2))
        assert m.sum(1).tolist() == [1, 2, 2, 2, 2, 2]
        assert m[3].tolist() == [False, False, True, True, False, False]

    def test_tile_counts_of_the_benchmark_call_by_hand(self, monkeypatch):
        """L = 8192, window 1024, 1024-wide blocks of 256-wide sub-tiles.
        By hand: 8 blocks on the diagonal, each 10 of 16 sub-tiles (4 on
        the diagonal masked); 7 blocks one under it, which the window's
        edge crosses from corner to corner: the 6 sub-tiles above the
        block's own diagonal whole, the 4 on it masked; everything further
        down is behind the window.  15 x 10 = 150 of 32 x 32 visited, 15 x
        4 = 60 masked."""
        monkeypatch.setattr(pallas_ops, "TILE_COUNTS", {})
        monkeypatch.setattr(pallas_ops, "GRID_COUNTS", {})
        pallas_ops._log_tiles(8192, 8192, 1024, 1024, True, 1024)
        assert pallas_ops.TILE_COUNTS == {
            (8192, 8192, True, 1024): (150, 1024, 60)}
        # the grid: a query block needs its own key block and the one
        # before, 2 steps a row for the sequence's 8; block 0's row has no
        # block before it, so 8 x 2 = 16 walked of 64 and 15 with work, by
        # the forward and by the one backward kernel alike, which pays 5
        # products a visited sub-tile (S, dP, dQ, dV, dK) where the two
        # kernels it replaced paid 3 + 4
        assert pallas_ops.GRID_COUNTS == {
            (8192, 8192, True, 1024): (16, 15, 64)}
        assert pallas_ops.tiles_line((8192, 8192, True, 1024)) == (
            "flash tiles L=8192 causal window 1024: visited 150/1024, "
            "masked 60; grid steps a head 16 of 64 walked, 15 with work, "
            "forward and backward (5 products a visited sub-tile backward, "
            "not 7)")
        pallas_ops._log_tiles(8192, 8192, 1024, 1024, True, None)
        assert pallas_ops.TILE_COUNTS[(8192, 8192, True, None)] == (
            528, 1024, 32)
        # no window: the last row needs every block, the triangle cannot be
        # made a rectangle: 64 walked, 8 x 9 / 2 = 36 with work
        assert pallas_ops.GRID_COUNTS[(8192, 8192, True, None)] == (
            64, 36, 64)
        assert "; grid steps a head 64 of 64 walked, 36 with work, " in (
            pallas_ops.tiles_line((8192, 8192, True, None)))
        # trinity_mini_train_8k's sliding call, window 2048 (ISSUE 32): 8
        # blocks on the diagonal, 10 sub-tiles each, 4 masked; 7 blocks one
        # under it WHOLLY INSIDE the band, 16 each, none masked; 6 blocks
        # two under it, which the window's edge crosses from corner to
        # corner, 10 each, 4 masked: 80 + 112 + 60 = 252 visited, 32 + 24 =
        # 56 masked.  A query block needs three key blocks, rows 0 and 1
        # have 1 and 2: 8 x 3 = 24 walked, 1 + 2 + 6 x 3 = 21 with work
        pallas_ops._log_tiles(8192, 8192, 1024, 1024, True, 2048)
        assert pallas_ops.TILE_COUNTS[(8192, 8192, True, 2048)] == (
            252, 1024, 56)
        assert pallas_ops.GRID_COUNTS[(8192, 8192, True, 2048)] == (
            24, 21, 64)


def _pallas_grids(jaxpr) -> dict:
    """``{kernel name: grid}`` of every ``pallas_call`` in a jaxpr, the
    nested ones too."""
    grids = {}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids[eqn.params["name"]] = tuple(
                eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids.update(_pallas_grids(sub))
    return grids


class TestGridOfTheBenchmarkCall:
    @pytest.mark.parametrize("window,grids", [
        (1024, {"flash_fwd": (1, 32, 8, 2), "flash_bwd": (1, 4, 8, 8, 2)}),
        (None, {"flash_fwd": (1, 32, 8, 8), "flash_bwd": (1, 4, 8, 8, 8)}),
        # trinity_mini_train_8k's sliding call: 3 key blocks a query block
        (2048, {"flash_fwd": (1, 32, 8, 3), "flash_bwd": (1, 4, 8, 8, 3)}),
    ])
    def test_grids_in_the_jaxpr(self, monkeypatch, window, grids):
        """mellum2_train_8k's two attention calls, (1, 8192, 32 / 4, 128):
        under the window of 1024 the innermost grid dimension is 2 key
        blocks a query block, with no window the whole 8; the backward
        walks them for each of a K/V head's 8 group members in turn."""
        monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
        loss = lambda q, k, v: pallas_ops.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32).sum()
        sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
        closed = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
            sds(1, 8192, 32, 128), sds(1, 8192, 4, 128),
            sds(1, 8192, 4, 128))
        assert _pallas_grids(closed.jaxpr) == grids


class TestPeriodAgainstDense:
    @pytest.mark.parametrize("name", ["mellum2_tiny", "trinity_tiny"])
    def test_a_period_of_window_and_full_layers(self, name):
        """A whole model of (sliding, sliding, sliding, full) periods with
        the flash kernels against the same parameters under dense attention
        with the explicit masks: ``mellum2_tiny`` has a rotary on both kinds
        of layer, ``trinity_tiny`` on the sliding layers and NONE on the
        full one (and q / k norms and a gate around the call).  Float32, so
        what is left is the kernels' order of summation over 8 or 9
        layers."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import get_model
        ids = jax.random.randint(jax.random.key(0), (2, 128), 0, 1000)
        dense, flash = (get_model(name, num_classes=1000, scan_layers=True,
                                  attention_impl=impl)
                        for impl in ("dense", "flash"))
        params = jax.jit(dense.init)(jax.random.key(1), ids)["params"]
        before = set(pallas_ops._FALLBACK_LOGGED)
        loss = lambda m: lambda p: (m.apply({"params": p}, ids) ** 2).mean()
        (lf, gf), (ld, gd) = (jax.jit(jax.value_and_grad(loss(m)))(params)
                              for m in (flash, dense))
        assert set(pallas_ops._FALLBACK_LOGGED) == before, "fell back"
        np.testing.assert_allclose(lf, ld, rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(gf),
                        jax.tree_util.tree_leaves(gd)):
            np.testing.assert_allclose(a, b, atol=1e-5 + 1e-3 * float(
                jnp.abs(b).max()))


class TestYarn:
    THETA, DIM = 500000.0, 128
    YARN = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)

    def _by_formula(self, n):
        inv = self.THETA ** (-2 * n / self.DIM)
        d = lambda b: (self.DIM * math.log(8192 / (2 * math.pi * b))
                       / (2 * math.log(self.THETA)))
        low, high = math.floor(d(32.0)), math.ceil(d(1.0))
        ramp = min(max((n - low) / (high - low), 0.0), 1.0)
        return inv / 16.0 * ramp + inv * (1 - ramp)

    @pytest.mark.parametrize("n", [0, 25, 63])
    def test_frequencies_against_the_formula(self, n):
        """One dimension that keeps its frequency, one on the ramp, one
        interpolated by the whole factor."""
        inv, scale = rope_frequencies(self.DIM, self.THETA, self.YARN)
        plain, one = rope_frequencies(self.DIM, self.THETA)
        np.testing.assert_allclose(inv[n], self._by_formula(n), rtol=1e-6)
        assert (scale, one) == (self.YARN[-1], 1.0)
        ratio = float(inv[n] / plain[n])
        assert {0: ratio == 1.0, 25: 1 / 16 < ratio < 1.0,
                63: abs(ratio - 1 / 16) < 1e-6}[n]

    def test_rope_scales_cos_and_sin(self):
        x = jnp.ones((1, 4, 1, self.DIM), jnp.float32)
        pos = jnp.zeros((4,), jnp.int32)
        np.testing.assert_allclose(rope(x, pos, self.THETA, self.YARN),
                                   x * self.YARN[-1], rtol=1e-6)


class TestOldCallsUnchanged:
    """The forward of the cells' calls lowers to the kernel it lowered to
    before the window came (ISSUE 26), before the grid followed the window
    (ISSUE 27), before the values' width came apart from the scores'
    (ISSUE 30) and before the backward became one kernel (ISSUE 31, which
    does not touch it): the jaxpr of the training forward (output and
    log-sum-exp), kernel body, grid and index maps included, source
    locations left out, hashed; the pins were read off ISSUE 31's parent
    (f071189) by the same code.  The gradient's pin, forward and the one
    backward kernel together, was read off ISSUE 31's own tree and again
    off ISSUE 35's, whose forward rule puts two ``name`` equations
    (``flash_out``, ``flash_lse``: identities that lower to nothing)
    behind the forward call and so renumbers what follows; the forward's
    pins stand.  A PR that changes these kernels on purpose reads new
    ones."""

    @pytest.mark.parametrize("shape,causal,window,forward,gradient", [
        ((4, 1024, 12, 12, 64), True, None,           # gpt2_small
         "8b639f80719e7aaa", "6e309e7576c32d3f"),
        ((16, 512, 12, 12, 64), False, None,          # bert_base
         "52ae05b93d47e435", "032472d6113ae8c5"),
        # mellum2_12b_a2p5b's full layer and its sliding one
        ((1, 8192, 32, 4, 128), True, None,
         "c9b2c56cb6f91c9d", "e00d69dfbc94223c"),
        ((1, 8192, 32, 4, 128), True, 1024,
         "7a8d6e41f4a86bef", "2ceee5eba89cb51e"),
    ])
    def test_jaxpr_hash(self, monkeypatch, shape, causal, window, forward,
                        gradient):
        import hashlib
        import re
        monkeypatch.setattr(pallas_ops, "_interpret", lambda: False)
        b, l, h, kv, d = shape
        sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
        args = sds(b, l, h, d), sds(b, l, kv, d), sds(b, l, kv, d)

        def pin(fn):
            text = str(jax.make_jaxpr(fn)(*args))
            text = re.sub(r"/\S*pallas_ops\.py\S*", "",
                          re.sub(r" at /[^\s\]]*", "", text))
            return hashlib.sha256(text.encode()).hexdigest()[:16]

        assert pin(lambda q, k, v: pallas_ops._flash_forward(
            q, k, v, causal, with_lse=True, window=window)) == forward
        loss = lambda q, k, v: pallas_ops.flash_attention(
            q, k, v, causal=causal, window=window).astype(jnp.float32).sum()
        assert pin(jax.grad(loss, (0, 1, 2))) == gradient
