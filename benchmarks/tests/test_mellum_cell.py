"""The routed cell's files rehearsed on the CPU at the tiny preset
(``tiny_routed/``: two periods of the mellum block, 8 experts, top-2,
window 16): the reference's init against the program's through the
harness's own calls, a whole run's result line, each planted fault and the
lower-precision controls against the cell's limits, and the four new
per-layer readers on a synthetic context.  Nothing here is a time or a
rate of a device."""

import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import run as bench_run
from benchmarks.entries import train_global as tg
from benchmarks.layer_metrics import (expert_load_max_over_mean,
                                      expert_rows_per_step,
                                      moe_expert_roofline,
                                      window_flash_roofline)
from benchmarks.lib import check, compare, moe_flops, peaks, traffic
from benchmarks.references import mellum_moe as reference

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny_routed")
ROOT = os.path.dirname(os.path.dirname(HERE))
PKG = tg.PKG
SEED = 2147483659


def run_cell(trace="0"):
    return bench_run.run(["--workload", "routed1", "--seed", str(SEED),
                          "--seconds", "1", "--trace", trace],
                         require_tpu=False, root=TINY)


def test_reference_follows_the_program_at_float32():
    spec = bench_run.load_spec("routed1", TINY)
    c, w = spec["config"], spec["workload"]
    w["flags"] = w["flags"] + ["--compute_dtype", "float32"]
    t = w["traffic"]
    rows = traffic.generate(t, c, SEED, 1)
    x, y = rows["train"]

    def program(real_steps):
        r = dict(rows, train=(x, traffic.keep_first_steps(y, real_steps, t, 1)))
        return tg.timed_call(tg.build_argv(c, w, SEED, 1), r,
                             c["vocab_size"])[0]

    p0 = reference.init_params(c, SEED)
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
    prog_g = compare.block_norms_by_worker(one["state"].opt_state.mu, 10.0)[0]
    assert compare.worst_gap(prog_g, compare.block_norms(g1))[0] < 1e-5
    gap, _ = compare.worst_gap(
        compare.block_norms(compare.tree_sub(three["variables"]["params"], p0)),
        compare.block_norms(compare.tree_sub(p3, p0)))
    assert gap < 1e-4
    # the round's row carries what the routed layer counted: every pair of
    # 4 x 64 tokens x top-2 lands on one of the 8 held experts
    row = three["round_timings"][0]
    assert row["expert_rows"] == 4 * 64 * 2
    assert 1.0 <= row["expert_load_max_over_mean"] <= 8.0


def test_result_line(capsys):
    code, result = run_cell()
    assert code == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "hbm_peak_gib",
                                      "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "one_expert_left_out"])
def test_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    """Each fault planted in the PROGRAM makes a whole run come out
    ``correct: false``; the third is the routed layer's own: a held expert
    whose rows are dropped, as a capacity would drop them."""
    if fault == "state_unchanged":
        import optax
        monkeypatch.setattr(optax, "apply_updates", lambda p, u: p)
    elif fault == "half_batch":
        train = importlib.import_module(f"{PKG}.train")
        real = train.masked_weights

        def half(labels, batch_mask):
            w = real(labels, batch_mask)
            keep = (np.arange(w.shape[0]) < w.shape[0] // 2)
            return w * keep.reshape((-1,) + (1,) * (w.ndim - 1))
        monkeypatch.setattr(train, "masked_weights", half)
    else:
        moe = importlib.import_module(f"{PKG}.models.moe")
        real = moe.routed_apply

        def drop(toks, idx, weights, first, held, fn):
            return real(toks, idx, jnp.where(idx == 3, 0.0, weights), first,
                        held, fn)
        monkeypatch.setattr(moe, "routed_apply", drop)
    code, result = run_cell()
    assert code == 0
    assert result["correct"] is False
    assert [k for k, c in result["compared"].items() if not c["ok"]]


@pytest.mark.parametrize("precision", ["fp8", "int8"])
def test_control_is_not_correct(precision):
    spec = bench_run.load_spec("routed1", TINY)
    t = spec["workload"]["traffic"]
    x, y = traffic.generate(t, spec["config"], SEED, 1)["train"]
    dev = jax.devices()[:1]
    ref = check.reference_reading(spec["config"], t, x, y, SEED, 1, 3, dev)
    ctl = check.reference_reading(spec["config"], t, x, y, SEED, 1, 3, dev,
                                  precision=precision)
    values, _ = check.numbers(ctl, ref)
    limits = spec["workload"]["check"]["limits"]
    verdict = compare.judge(values, {k: limits[k] for k in values})
    assert not all(c["ok"] for c in verdict.values()), values


# ----------------------------------------------------------------------
# the new readers on a synthetic context
# ----------------------------------------------------------------------

def _ctx(**trace_kernels):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "mellum2_12b_a2p5b.json")) as f:
        arch = reference.arch_of(json.load(f))
    rows = [{"compute_ms": 1.0}] + [
        {"expert_rows": 16000.0 + 100 * r, "expert_load_max_over_mean": 1.5}
        for r in range(4)]
    return {"trace": {"kernels": trace_kernels},
            "results": {"round_timings": rows}, "rounds": 4, "workers": 1,
            "traffic": {"batch": 1, "seq_len": 8192, "steps_per_round": 8,
                        "val_steps": 1, "objective": "causal_lm"},
            "arch": arch, "peaks": peaks.peaks_of("TPU v5 lite")}


def test_counter_readers_take_the_windows_rounds():
    ctx = _ctx()
    assert expert_rows_per_step.read(ctx) == 16150.0
    assert expert_load_max_over_mean.read(ctx) == 1.5
    bare = dict(ctx, results={"round_timings": [{"compute_ms": 1.0}] * 3})
    assert expert_rows_per_step.read(bare) is None
    assert expert_load_max_over_mean.read(bare) is None
    assert moe_expert_roofline.read(bare) is None


def test_moe_expert_roofline_by_hand():
    """4 rounds x 4 layers x (9 x 8 + 3 x 1) = 1,200 products of 2 x 16,150
    x 2304 x 896 operations: 80.0 TFLOP, 0.406 s at 197 TFLOP/s (operations
    bind: the bytes take 0.30 s); in 0.812 s of kernel time that is 50%."""
    ctx = _ctx(moe_gmm={"seconds": 0.8124, "calls": 1600, "names": []})
    one = moe_flops.expert_product_cost(16150.0, ctx["arch"])
    assert one["flops"] == 2 * 16150 * 2304 * 896
    assert one["bytes"] == 2 * (16 * 2304 * 896 + 16150 * 3200)
    assert moe_expert_roofline.read(ctx) == pytest.approx(50.0, abs=0.05)
    assert moe_expert_roofline.read(_ctx()) is None


def test_window_flash_roofline_by_hand():
    """A sliding layer needs 960.06 keys a query on average at L = 8192 and
    window 1024, the full one 4096.5: (3 x 960.06 + 4096.5) x 12 x 4096 x
    8192 = 2.809 TFLOP a training step, x 4 x (8 + 1/3) steps = 93.6 TFLOP,
    0.475 s at peak; in 1.9 s of kernel time that is 25%."""
    assert moe_flops.visible_keys_mean(8192, 1024) == pytest.approx(960.0625)
    assert moe_flops.visible_keys_mean(8192, None) == 4096.5
    k = {name: {"seconds": 1.9012 / 3, "calls": 1, "names": []}
         for name in window_flash_roofline.KERNELS}
    assert window_flash_roofline.read(_ctx(**k)) == pytest.approx(25.0,
                                                                  abs=0.05)
    assert window_flash_roofline.read(_ctx(flash_fwd=k["flash_fwd"])) is None
    # a program that has no such arch (the parent's cells): nothing, no raise
    ctx = _ctx(**k)
    ctx["arch"] = {"heads": 12, "hidden": 768}
    assert window_flash_roofline.read(ctx) is None
