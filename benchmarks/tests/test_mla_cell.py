"""The latent-attention cell's files rehearsed on the CPU at the tiny preset
(``tiny_mla/``: one dense and two sparse layers, 4 heads with 24-wide
scores and 16-wide values, 8 experts, top-2, sigmoid router with a drawn
selection bias, shared experts): the reference's init against the
program's through the harness's own calls, a whole run's result line, each
planted fault and the lower-precision controls against the cell's limits,
``lib/mla_flops.py`` against a count by hand, and the four new per-layer
readers on a synthetic context.  Nothing here is a time or a rate of a
device."""

import importlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks import run as bench_run
from benchmarks.entries import train_global as tg
from benchmarks.layer_metrics import (mla_flash_roofline,
                                      routed_expert_roofline,
                                      routed_load_max_over_mean,
                                      routed_rows_per_step)
from benchmarks.lib import check, compare, mla_flops, peaks, traffic
from benchmarks.references import mla_moe as reference

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny_mla")
ROOT = os.path.dirname(os.path.dirname(HERE))
PKG = tg.PKG
SEED = 2147483659


def run_cell(trace="0"):
    return bench_run.run(["--workload", "mla1", "--seed", str(SEED),
                          "--seconds", "1", "--trace", trace],
                         require_tpu=False, root=TINY)


def test_reference_follows_the_program_at_float32():
    spec = bench_run.load_spec("mla1", TINY)
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
    # the selection bias is as the program drew it, to the bit, after 3
    # steps (the reference's own draw is an ulp off on 6 of 16: one fused
    # multiply against two)
    np.testing.assert_array_equal(
        three["variables"]["params"]["layers"]["layer_0"]["moe"][
            "select_bias"], untouched["layers"]["layer_0"]["moe"][
                "select_bias"])
    # the round's row carries what the two sparse layers counted: every
    # pair of 4 x 64 tokens x top-2 lands on one of the 8 held experts
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
                                   "bias_in_weights", "shared_left_out"])
def test_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    """Each fault planted in the PROGRAM makes a whole run come out
    ``correct: false``; the last two are this configuration's own: a
    selection bias that leaks into the weights, and a sparse layer without
    its shared experts."""
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
    elif fault == "bias_in_weights":
        moe = importlib.import_module(f"{PKG}.models.moe")
        real = moe.routed_apply

        # what a bias that leaks into the weights is: a constant of each
        # expert's own added to the weight it is mixed with
        def leak(toks, idx, weights, first, held, fn):
            return real(toks, idx, weights + 0.05 * jnp.cos(1.0 * idx),
                        first, held, fn)
        monkeypatch.setattr(moe, "routed_apply", leak)
    else:
        llama = importlib.import_module(f"{PKG}.models.llama")
        real = llama.SwiGLU.__call__
        monkeypatch.setattr(
            llama.SwiGLU, "__call__",
            lambda self, x: real(self, x) * (self.name != "shared"))
    code, result = run_cell()
    assert code == 0
    assert result["correct"] is False
    assert [k for k, c in result["compared"].items() if not c["ok"]]


@pytest.mark.parametrize("precision", ["fp8", "int8"])
def test_control_is_not_correct(precision):
    spec = bench_run.load_spec("mla1", TINY)
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
# required operations by hand, and the new readers on a synthetic context
# ----------------------------------------------------------------------

def _arch():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "kanana2_30b_a3b.json")) as f:
        return reference.arch_of(json.load(f))


def test_mla_flops_by_hand():
    """ISSUE 30's count.  Attention's four projections 2048 x 6144 + 2048 x
    576 + 512 x 8192 + 4096 x 2048 = 26,345,472 weights; the dense layer
    adds 3 x 2048 x 6144; a sparse layer the router's 2048 x 128, the
    shared experts' 3 x 2048 x 1536 and 6 x 16 / 128 of an expert's 3 x
    2048 x 768; the head 2048 x 16,032.  255,262,720 weights x 6 = 1.532
    GFLOP a token; scores and values 5 layers x 3 x 2 x 32 x (192 + 128) x
    4096.5 = 1.258 GFLOP at L = 8192."""
    a = _arch()
    assert mla_flops.attention_matmul_params(a) == 26345472
    assert mla_flops.layer_matmul_params(a, False) == 26345472 + 37748736
    assert mla_flops.layer_matmul_params(a, True) == (
        26345472 + 262144 + 9437184 + 0.75 * 4718592)
    assert mla_flops.train_flops_per_token(a, 8192) == (
        6 * 255262720 + 5 * 61440 * 4096.5)
    one = mla_flops.mla_flash_cost(1, 8192, a)
    assert one["flops"] == 8192 * 61440 * 4096.5          # 2.06 TFLOP
    assert one["bytes"] == 6 * 8192 * 32 * 2 * 320        # 1.0 GB


def _ctx(**trace_kernels):
    rows = [{"compute_ms": 1.0}] + [
        {"expert_rows": 6100.0 + 20 * r, "expert_load_max_over_mean": 1.25}
        for r in range(4)]
    return {"trace": {"kernels": trace_kernels},
            "results": {"round_timings": rows}, "rounds": 4, "workers": 1,
            "traffic": {"batch": 1, "seq_len": 8192, "steps_per_round": 8,
                        "val_steps": 1, "objective": "causal_lm"},
            "arch": _arch(), "peaks": peaks.peaks_of("TPU v5 lite")}


def test_counter_readers_take_the_windows_rounds():
    ctx = _ctx()
    assert routed_rows_per_step.read(ctx) == 6130.0
    assert routed_load_max_over_mean.read(ctx) == 1.25
    bare = dict(ctx, results={"round_timings": [{"compute_ms": 1.0}] * 3})
    assert routed_rows_per_step.read(bare) is None
    assert routed_load_max_over_mean.read(bare) is None
    assert routed_expert_roofline.read(bare) is None


def test_routed_expert_roofline_by_hand():
    """4 rounds x 4 sparse layers x (9 x 8 + 3 x 1) = 1,200 products of 2 x
    6,130 x 2048 x 768 operations: 23.14 TFLOP, 0.1175 s at 197 TFLOP/s;
    their bytes, 2 x (16 x 2048 x 768 + 6,130 x 2816) = 84.86 MB a product,
    101.8 GB, take 0.1243 s at 819 GB/s: at 383 rows a held expert the
    bytes bind.  In 0.4973 s of kernel time that is 25%."""
    ctx = _ctx(moe_gmm={"seconds": 0.49734, "calls": 1600, "names": []})
    assert routed_expert_roofline.read(ctx) == pytest.approx(25.0, abs=0.05)
    assert routed_expert_roofline.read(_ctx()) is None
    # a program whose arch has no such key (the parent's cells): nothing
    ctx["arch"] = {"hidden": 2304, "ffn": 896, "held": (0, 16), "layers": 4}
    assert routed_expert_roofline.read(ctx) is None


def test_mla_flash_roofline_by_hand():
    """5 layers x 4 rounds x (8 + 1/3) steps = 166.7 calls of 2.062 TFLOP:
    343.6 TFLOP, 1.744 s at peak (the bytes take 0.2 s); in 4.36 s of
    kernel time that is 40%."""
    k = {name: {"seconds": 4.3607 / 3, "calls": 1, "names": []}
         for name in mla_flash_roofline.KERNELS}
    assert mla_flash_roofline.read(_ctx(**k)) == pytest.approx(40.0, abs=0.05)
    assert mla_flash_roofline.read(_ctx(flash_fwd=k["flash_fwd"])) is None
    ctx = _ctx(**k)
    ctx["arch"] = {"heads": 12, "hidden": 768}
    assert mla_flash_roofline.read(ctx) is None
