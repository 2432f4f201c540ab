"""``flash_fwd_calls_per_bwd`` (ISSUE 35) on made-up contexts: the ratio
worked out by hand, nothing where a kernel is missing, and the benchmark's
entry.  Nothing here is a reading of a device."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.layer_metrics import flash_fwd_calls_per_bwd

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["gpt2s_train_1k", "gpt2s_round_4chip", "mellum2_train_8k",
         "kanana2_train_8k", "trinity_mini_train_8k"]


def ctx_of(**calls):
    return {"trace": {"kernels": {
        name: {"seconds": 0.001 * n, "calls": n}
        for name, n in calls.items()}}}


@pytest.mark.parametrize("fwd,bwd,expected", [
    # 4 rounds of 8 training steps and 1 validation step, 5 layers
    (4 * 5 * (2 * 8 + 1), 4 * 5 * 8, 2.125),   # every forward twice
    (4 * 5 * (8 + 1), 4 * 5 * 8, 1.125),       # the residuals are kept
    (4 * 12 * (16 + 4), 4 * 12 * 16, 1.25),    # gpt2: 16 steps, 4 val steps
    # what the sparse cells' traces held (PR 35): 4 validation steps a
    # round as packed, the scanned layers' forward twice and the leading
    # layer's once at the parent, every layer's once at the change
    (4 * (4 * 8 * 2 + 8 + 5 * 4), 4 * 5 * 8, 2.3),
    (4 * (5 * 8 + 5 * 4), 4 * 5 * 8, 1.5),
    (4 * (4 * 8 * 2 + 4 * 4), 4 * 4 * 8, 2.5),  # mellum: no leading layer
])
def test_reads_the_ratio_worked_out_by_hand(fwd, bwd, expected):
    assert flash_fwd_calls_per_bwd.read(
        ctx_of(flash_fwd=fwd, flash_dkv=bwd)) == expected


@pytest.mark.parametrize("calls", [
    {"flash_fwd": 36},                   # a window that never trained
    {"flash_dkv": 32},
    {"flash_fwd": 36, "flash_dkv": 0},
    {"moe_gmm": 10},                     # dense attention: bert
    {},
])
def test_nothing_where_a_kernel_is_missing(calls):
    assert flash_fwd_calls_per_bwd.read(ctx_of(**calls)) is None


def test_the_entry_lists_the_flash_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "flash_fwd_calls_per_bwd"]
    assert entry == {"name": "flash_fwd_calls_per_bwd", "unit": "ratio",
                     "better": "lower", "source": "device_trace",
                     "layer": "round program",
                     "moves": "train_tokens_per_s", "workloads": CELLS}
    for cell in [w["name"] for w in bench["workloads"]]:
        names = [m["name"] for m in bench_run.metrics_of_cell(
            bench, cell, "per_layer")]
        assert ("flash_fwd_calls_per_bwd" in names) == (cell in CELLS)
