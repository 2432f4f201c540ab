"""The AFMoE cell's files rehearsed on the CPU at the tiny preset
(``tiny_afmoe/``: one dense layer and two periods of sliding, sliding,
sliding, full; 4 / 2 heads of 32 with q / k norms and a gate, window 16,
rotary on the sliding layers alone, four norms a block, 8 experts, top-2,
a sigmoid router whose bias a rule moves, a shared expert; float32, the
fixture's ``flags_why`` says why): a whole run's result line, faults
planted in the PROGRAM and the lower-precision controls against the cell's
limits, ``lib/afmoe_flops.py`` against a count by hand, and the five new
per-layer readers on a synthetic context.  Nothing here is a time or a rate
of a device."""

import importlib
import json
import os

import numpy as np
import pytest

import jax

from benchmarks import run as bench_run
from benchmarks.entries import train_global as tg
from benchmarks.layer_metrics import (afmoe_expert_roofline,
                                      afmoe_load_max_over_mean,
                                      afmoe_rows_per_step,
                                      gated_flash_roofline,
                                      router_bias_moved)
from benchmarks.lib import (afmoe_flops, check, compare, flops, moe_flops,
                            peaks, traffic)
from benchmarks.references import afmoe as reference

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny_afmoe")
ROOT = os.path.dirname(os.path.dirname(HERE))
PKG = tg.PKG
SEED = 2147483659


def run_cell(trace="0"):
    return bench_run.run(["--workload", "afmoe1", "--seed", str(SEED),
                          "--seconds", "1", "--trace", trace],
                         require_tpu=False, root=TINY)


def test_result_line(capsys):
    code, result = run_cell()
    assert code == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "hbm_peak_gib",
                                      "setup_s"}
    assert result["compared"]["twin_loss_gap"]["value"] == 0.0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "gate_dropped", "rope_on_full"])
def test_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    """Each fault planted in the PROGRAM makes a whole run come out
    ``correct: false``; the last two are this configuration's own: an
    attention whose gate is always open, and a rotary on the full layers
    too.  (The reference's own planted faults, the dropped q / k norms and
    the misplaced or unmoved bias among them, are read against the
    tolerances in ``tests/test_trinity.py`` and on the chip by
    ``chip_controls_afmoe.py``.)"""
    import flax.linen as nn
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
    elif fault == "gate_dropped":
        real = nn.DenseGeneral.__call__
        # the gate's product stays, so the parameter is there; its sigmoid
        # reads 1 everywhere
        monkeypatch.setattr(
            nn.DenseGeneral, "__call__",
            lambda self, x: real(self, x) * 0 + 30.0 if self.name == "gate"
            else real(self, x))
    else:
        arch = importlib.import_module(f"{PKG}.models.arch")
        monkeypatch.setattr(arch.DecoderArch, "rope_of",
                            lambda self, kind: arch.Rope(10000.0))
    code, result = run_cell()
    assert code == 0
    assert result["correct"] is False
    assert [k for k, c in result["compared"].items() if not c["ok"]]


@pytest.mark.parametrize("precision", ["fp8", "int8"])
def test_control_is_not_correct(precision):
    spec = bench_run.load_spec("afmoe1", TINY)
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

def _arch():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "trinity_mini_26b_a3b.json")) as f:
        return reference.arch_of(json.load(f))


def test_afmoe_flops_by_hand():
    """ISSUE 32's count for ``trinity_mini_26b_a3b`` at L = 8192, each term
    by hand.  Attention's five projections: q, gate and out 3 x 2048 x 4096,
    k and v 2 x 2048 x 512 = 27,262,976 weights; the dense layer adds 3 x
    2048 x 6144; a sparse layer the router's 2048 x 128, the shared
    expert's 3 x 2048 x 1024 and 8 x 8 / 128 of an expert's 3 x 2048 x
    1024; the head 2048 x 25,024.  264,110,080 weights x 6 = 1.585 GFLOP a
    token.  Scores and values 3 x 2 x 32 x 256 = 49,152 operations a
    visible key and layer; a query sees 1,792.125 keys under the window of
    2048 (2048 x 2049 / 2 on the ramp, 6,144 x 2048 after it, over 8,192)
    and 4,096.5 on the full layer: 0.554 GFLOP.  2.138 in all."""
    a = _arch()
    assert a["layer_types"] == ("sliding",) * 4 + ("full",)
    assert afmoe_flops.attention_matmul_params(a) == 27_262_976
    assert afmoe_flops.layer_matmul_params(a, False) == \
        27_262_976 + 37_748_736
    assert afmoe_flops.layer_matmul_params(a, True) == (
        27_262_976 + 262_144 + 6_291_456 + 0.5 * 6_291_456)
    assert moe_flops.visible_keys_mean(8192, 2048) == 1792.125
    assert afmoe_flops.attention_flops_per_token(a, 8192) == \
        49_152 * (4 * 1792.125 + 4096.5)
    assert afmoe_flops.train_flops_per_token(a, 8192) == \
        6 * 264_110_080 + 553_697_280
    # the kernels' requirement a step: the tokens' operations, and twelve
    # tensors a layer of which six are grouped (4 of 32 heads)
    one = afmoe_flops.flash_cost(1, 8192, a)
    assert one["flops"] == 8192 * 553_697_280          # 4.54 TFLOP
    assert one["bytes"] == 5 * 6 * (8192 * 32 * 128 * 2 + 8192 * 4 * 128 * 2)
    assert flops.roofline_seconds(one, peaks.peaks_of("TPU v5 lite"))[1] \
        == "flops"


def _ctx(**trace_kernels):
    rows = [{"compute_ms": 1.0}] + [
        {"expert_rows": 4066.0 + 20 * r, "expert_load_max_over_mean": 1.25,
         "select_bias_moved": 0.007 - 0.001 * r} for r in range(4)]
    return {"trace": {"kernels": trace_kernels},
            "results": {"round_timings": rows}, "rounds": 4, "workers": 1,
            "traffic": {"batch": 1, "seq_len": 8192, "steps_per_round": 8,
                        "val_steps": 1, "objective": "causal_lm"},
            "arch": _arch(), "peaks": peaks.peaks_of("TPU v5 lite")}


def test_counter_readers_take_the_windows_rounds():
    ctx = _ctx()
    assert afmoe_rows_per_step.read(ctx) == 4096.0
    assert afmoe_load_max_over_mean.read(ctx) == 1.25
    assert router_bias_moved.read(ctx) == pytest.approx(0.0055)
    # a program that carries no such counters (the parent's): nothing, and
    # nothing raised
    bare = dict(ctx, results={"round_timings": [{"compute_ms": 1.0}] * 3})
    for reader in (afmoe_rows_per_step, afmoe_load_max_over_mean,
                   router_bias_moved, afmoe_expert_roofline):
        assert reader.read(bare) is None


def test_afmoe_expert_roofline_by_hand():
    """4 rounds x 4 sparse layers x (9 x 8 + 3 x 1) = 1,200 products of 2 x
    4,096 x 2048 x 1024 operations: 20.6 TFLOP, 0.1046 s at 197 TFLOP/s;
    their bytes, 2 x (8 x 2048 x 1024 + 4,096 x 3072) = 58.7 MB a product,
    take 0.0860 s at 819 GB/s: at 512 rows a held expert the operations
    bind, just.  In 0.3 s of kernel time that is 34.9%."""
    ctx = _ctx(moe_gmm={"seconds": 0.3, "calls": 1600, "names": []})
    assert afmoe_expert_roofline.read(ctx) == pytest.approx(34.883, abs=0.01)
    assert afmoe_expert_roofline.read(_ctx()) is None


def test_gated_flash_roofline_by_hand():
    """A step's two kernels over five layers need 8,192 x 553,697,280 =
    4.536 TFLOP (the bytes, 2.26 GB, take an eighth of the time); 4 rounds
    x (8 + 1/3) steps = 33.3 of them, 0.7675 s at peak; in 2 s of kernel
    time that is 38.4%.  The backward is read under the name the accepted
    picker gives it, ``flash_dkv``."""
    k = {name: {"seconds": 1.0, "calls": 1, "names": []}
         for name in gated_flash_roofline.KERNELS}
    assert gated_flash_roofline.KERNELS == ("flash_fwd", "flash_dkv")
    assert gated_flash_roofline.read(_ctx(**k)) == pytest.approx(38.375,
                                                                 abs=0.01)
    assert gated_flash_roofline.read(_ctx(flash_fwd=k["flash_fwd"])) is None
    # another configuration's architecture: not this reader's to read
    ctx = _ctx(**k)
    ctx["arch"] = {"family": "mellum", "layer_types": ("sliding", "full"),
                   "heads": 32, "kv_heads": 4, "head_dim": 128,
                   "window": 1024}
    assert gated_flash_roofline.read(ctx) is None
