"""``ops/moe_combine.py``, the routed layer's way back into the tokens, in
interpret mode on the CPU: the kernel against XLA's scatter-add
``acc.at[tok].add(where(valid, w * y))`` over the row buffers that
``models/moe.py::routed_apply`` sorts, for routings that put the runs
where the kernel's walk has its edges; the runs' bounds as a partition of
the rows; and the routed layer's gradient with the kernel against the
same layer with the scatter-add.  Never a time."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import moe
from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import moe_combine as mc

N, H = 256, 128                  # two token tiles of 128
# one trace a shape: a buffer's windows share it
combine = jax.jit(mc.moe_combine, static_argnames="n")


def _routing(case: str, rng):
    """``(expert_idx [N, k], router width, first, held)`` for a case."""
    def pick(width, k):
        return np.argsort(rng.random((N, width)), -1)[:, :k]
    if case == "balanced":                # 8 of 32 held, 4 a token
        return pick(32, 4), 32, 8, 8
    if case == "one_expert":              # every held pair on expert 5
        idx = 8 + pick(8, 2)
        idx[:, 0] = 5
        return idx, 16, 4, 4
    if case == "empty_group":             # held expert 2 gets no row
        idx = pick(16, 4)
        return np.where(idx == 2, 15, idx), 16, 0, 4
    if case == "all_k_held":              # every token's choices held
        return pick(4, 2), 16, 0, 4
    if case == "overflow":                # 512 held pairs, C = 80 rows
        return pick(2, 2) + 7, 16, 7, 2
    if case == "k1":
        return pick(8, 1), 8, 2, 4
    if case == "three_held":              # 3 slots of 64 rows: 4 in VMEM
        return pick(8, 2), 8, 0, 3
    raise ValueError(case)


def _buffers(idx, width, first, held):
    """routed_apply's sort, written out: per ``c``-row window the rows'
    tokens, weights' flat pairs and validity, and the runs' bounds."""
    n, k = idx.shape
    m = n * k
    m_pad, c = moe.row_buffer(m, held, width)
    local = idx.reshape(-1) - first
    local = np.where((local >= 0) & (local < held), local, held)
    key = np.pad(local, (0, m_pad - m), constant_values=held)
    order = np.argsort(key, kind="stable")
    past = -m_pad % c
    expert = np.pad(key[order], (0, past), constant_values=held)
    order = np.pad(order, (0, past), constant_values=m_pad)
    tok = np.minimum(order // k, n - 1)
    edges = mc.run_edges(jnp.asarray(local.reshape(n, k)), n, held)
    chunks = expert.size // c
    return (c, order.reshape(chunks, c), tok.reshape(chunks, c),
            (expert < held).reshape(chunks, c), expert, edges)


def _scatter(rows, tok, valid, weight, n):
    y = rows.astype(jnp.float32)
    if weight is not None:
        y = y * weight[:, None]
    return jnp.zeros((n, rows.shape[1]), jnp.float32).at[tok].add(
        jnp.where(valid[:, None], y, 0))


@pytest.mark.parametrize("weighted", [True, False])
@pytest.mark.parametrize("case", ["balanced", "one_expert", "empty_group",
                                  "all_k_held", "overflow", "k1",
                                  "three_held"])
def test_against_the_scatter_add(assert_within_ulps, case, weighted):
    """Every window of the buffer, float32 rows, the rows no group wrote
    holding NaN: the kernel's sums are the scatter-add's to a few ulps (a
    token's terms from several experts add in another order)."""
    rng = np.random.default_rng(41)
    idx, width, first, held = _routing(case, rng)
    c, order, tok, valid, _, edges = _buffers(idx, width, first, held)
    if case == "overflow":
        assert order.shape[0] >= 3 and valid[2].any()
    if case == "empty_group":
        assert int(jnp.diff(edges).reshape(held, -1)[2].sum()) == 0
    if case == "three_held":
        assert mc.plan(N, c, held, 4)[1:] == (64, 4)
    weights = rng.random(idx.size).astype(np.float32)
    got = want = 0
    for i in range(order.shape[0]):
        rows = rng.normal(size=(c, H)).astype(np.float32)
        rows[~valid[i]] = np.nan
        w = weights[np.minimum(order[i], idx.size - 1)] if weighted else None
        w = None if w is None else jnp.asarray(w)
        got = got + combine(jnp.asarray(rows), jnp.asarray(tok[i]),
                            jnp.clip(edges - i * c, 0, c), w, n=N)
        want = want + _scatter(jnp.asarray(rows), tok[i], valid[i], w, N)
    assert np.isfinite(np.asarray(got)).all()
    assert_within_ulps(got, want, 8)


def test_bfloat16_rows_take_the_weight_to_float32_rounding(
        assert_within_ulps):
    """bfloat16 rows and float32 weights: the weight enters the one-hot in
    three bfloat16 parts, so each term is ``w * y`` to float32 rounding."""
    rng = np.random.default_rng(7)
    idx, width, first, held = _routing("balanced", rng)
    c, order, tok, valid, _, edges = _buffers(idx, width, first, held)
    rows = jnp.asarray(rng.normal(size=(c, H)), jnp.bfloat16)
    rows = jnp.where(valid[0][:, None], rows, jnp.nan).astype(jnp.bfloat16)
    w = jnp.asarray(rng.random(c), jnp.float32)
    got = combine(rows, jnp.asarray(tok[0]), jnp.clip(edges, 0, c), w, n=N)
    assert_within_ulps(got, _scatter(rows, tok[0], valid[0], w, N), 8)


@pytest.mark.parametrize("seed", range(40))
def test_the_runs_partition_the_rows(seed):
    """For any routing the bounds counted from the choices are those of the
    sorted buffer: the runs are contiguous, in order, cover the rows of the
    held experts and nothing else, and each run's rows are its own
    expert's and lie in its own tile of tokens."""
    rng = np.random.default_rng(seed)
    width = int(rng.choice([8, 16, 64]))
    k = int(rng.integers(1, min(width, 8) + 1))
    held = int(rng.integers(1, width + 1))
    first = int(rng.integers(0, width - held + 1))
    idx = np.argsort(rng.random((N, width)), -1)[:, :k]
    *_, expert, edges = _buffers(idx, width, first, held)
    edges = np.asarray(edges)
    tt = mc.token_tile(N)
    tiles = N // tt
    assert edges[0] == 0 and (np.diff(edges) >= 0).all()
    assert edges[-1] == int(((idx >= first) & (idx < first + held)).sum())
    _, order, tok, *_ = _buffers(idx, width, first, held)
    tok = tok.reshape(-1)
    for run in range(held * tiles):
        rows = np.arange(edges[run], edges[run + 1])
        assert (expert[rows] == run // tiles).all()
        assert (tok[rows] // tt == run % tiles).all()


@pytest.mark.parametrize("case", ["balanced", "one_expert", "overflow"])
def test_every_live_row_is_summed_once(case):
    """Rows of ones: each token's sum counts its choices of a held expert,
    so a row the walk read twice, or not at all, shows."""
    rng = np.random.default_rng(3)
    idx, width, first, held = _routing(case, rng)
    c, _, tok, valid, _, edges = _buffers(idx, width, first, held)
    got = sum(combine(jnp.where(valid[i][:, None], 1.0, jnp.nan) *
                      jnp.ones((c, H)), jnp.asarray(tok[i]),
                      jnp.clip(edges - i * c, 0, c), n=N)
              for i in range(tok.shape[0]))
    held_choices = ((idx >= first) & (idx < first + held)).sum(1)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.repeat(held_choices[:, None], H, 1))


def test_routed_layer_gradient_against_the_scatter_form(monkeypatch,
                                                        assert_within_ulps):
    """The routed layer's value and gradients with respect to the tokens,
    the weights and every expert matrix, with the kernel and with XLA's
    scatter-add in its place, an overflow walked."""
    rng = np.random.default_rng(11)
    idx, width, first, held = _routing("overflow", rng)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    toks, weights, probe = f32(N, 32), jnp.abs(f32(*idx.shape)), f32(N, 32)
    params = (0.2 * f32(held, 32, 24), 0.2 * f32(held, 32, 24),
              0.2 * f32(held, 24, 32))

    def loss(toks, weights, params):
        out, *_ = moe.routed_apply(toks, jnp.asarray(idx), weights, first,
                                   held, moe.Experts(moe._swiglu_experts,
                                                     params, width))
        return (out * probe).sum()

    step = lambda: jax.value_and_grad(loss, (0, 1, 2))(toks, weights, params)
    got = step()

    def scatter_form(rows, tok, edges, weight=None, *, n):
        return _scatter(rows, tok, jnp.arange(rows.shape[0]) < edges[-1],
                        weight, n)
    monkeypatch.setattr(mc, "moe_combine", scatter_form)
    want = step()
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert_within_ulps(a, b, 16)
