"""The plain reference held against the program at a tiny size on the CPU:
the same weights from the same seed, and at float32 the same losses,
gradients and updates to rounding.  (On the chip the comparison is the one
``run.py`` makes at the published sizes; PERF.md.)"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.entries import train_global as tg
from benchmarks.lib import compare, traffic
from benchmarks.references import transformer_lm as reference

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny", "benchmarks")
SEED = 2147483659          # past 2**31, as the driver's seeds are


def load(config, cell):
    with open(os.path.join(TINY, "configs", f"{config}.json")) as f:
        c = json.load(f)
    with open(os.path.join(TINY, "workloads", f"{cell}.json")) as f:
        w = json.load(f)
    w["flags"] = w["flags"] + ["--compute_dtype", "float32"]
    return c, w


@pytest.mark.parametrize("config,cell", [("gpt_tiny", "lm1"),
                                         ("bert_tiny", "mlm1")])
def test_reference_follows_the_program_at_float32(config, cell):
    c, w = load(config, cell)
    t = w["traffic"]
    rows = traffic.generate(t, c, SEED, 1)
    x, y = rows["train"]

    def program(real_steps):
        r = dict(rows, train=(x, traffic.keep_first_steps(y, real_steps, t, 1)))
        res, *_ = tg.timed_call(tg.build_argv(c, w, SEED, 1), r,
                                c["vocab_size"])
        return res

    p0 = reference.init_params(c, SEED)
    # no labelled row: the round leaves the state as the seed made it
    untouched = compare._as_dict(program(0)["variables"]["params"])
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(jnp.asarray(a) - b).max()),
        untouched, p0)))
    assert worst < 1e-7, "the reference's weights are not the program's"

    xs = x.reshape(t["steps_per_round"], t["batch"], -1)[:3]
    ys = y.reshape(t["steps_per_round"], t["batch"], -1)[:3]
    losses, g1, p3 = reference.train_steps(c, p0, xs, ys, lr=1e-3)
    one, three = program(1), program(3)
    assert np.allclose(tg.step_losses(three, 1, 3)[0], np.asarray(losses),
                       rtol=2e-6)
    ref_g = compare.block_norms(g1)
    prog_g = compare.block_norms_by_worker(one["state"].opt_state.mu, 10.0)[0]
    assert compare.worst_gap(prog_g, ref_g)[0] < 1e-5
    dead = compare.dead_blocks(ref_g)
    assert any(".k[" in name for name in dead), \
        "a key's bias has no gradient under softmax"
    gap, _ = compare.worst_gap(
        compare.block_norms(compare.tree_sub(three["variables"]["params"], p0)),
        compare.block_norms(compare.tree_sub(p3, p0)), dead)
    assert gap < 1e-4


def test_lower_precision_moves_the_reference():
    """The control's arithmetic is really coarser: the same steps in fp8
    and in int8 leave the float32 ones by far more than rounding."""
    c, w = load("gpt_tiny", "lm1")
    t = w["traffic"]
    x, y = traffic.generate(t, c, SEED, 1)["train"]
    xs = x.reshape(t["steps_per_round"], t["batch"], -1)[:3]
    ys = y.reshape(t["steps_per_round"], t["batch"], -1)[:3]
    p0 = reference.init_params(c, SEED)
    _, g, _ = reference.train_steps(c, p0, xs, ys, lr=1e-3)
    ref = compare.block_norms(g)
    gaps = {}
    for precision in ("bfloat16", "int8", "fp8"):
        _, gq, _ = reference.train_steps(c, p0, xs, ys, lr=1e-3,
                                         precision=precision)
        gaps[precision] = compare.worst_gap(compare.block_norms(gq), ref)[0]
    assert gaps["bfloat16"] < gaps["int8"] < gaps["fp8"]
    assert gaps["fp8"] > 3 * gaps["bfloat16"]
