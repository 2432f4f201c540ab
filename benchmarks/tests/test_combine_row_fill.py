"""``combine_row_fill`` rehearsed on the CPU: the counter in the
rounds' rows of the tiny routed cell, the reader on made-up contexts, the
kernel's place in the trace's picker (no picker file takes it), and the
benchmark's entry, found by its name.  Nothing here is a time or a rate of
a device."""

import json
import os

from benchmarks import run as bench_run
from benchmarks.entries import train_global as tg
from benchmarks.layer_metrics import combine_row_fill
from benchmarks.lib import trace, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny_routed")
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2147483671
CELLS = ["mellum2_train_8k", "kanana2_train_8k", "trinity_mini_train_8k"]


def test_rows_carry_the_counter():
    spec = bench_run.load_spec("routed1", TINY)
    c, w = spec["config"], spec["workload"]
    rows = traffic.generate(w["traffic"], c, SEED, 1)
    results = tg.timed_call(tg.build_argv(c, w, SEED, 2), rows,
                            c["vocab_size"])[0]
    fills = [r["combine_row_fill"] for r in results["round_timings"]]
    assert len(fills) == 2 and all(0.0 < f <= 100.0 for f in fills)
    assert combine_row_fill.read(
        {"results": {"round_timings": results["round_timings"]}}) == fills[1]


def test_reader_takes_the_windows_rounds():
    rows = [{"combine_row_fill": 90.0}] + [
        {"combine_row_fill": 40.0 + r} for r in range(4)]
    assert combine_row_fill.read(
        {"results": {"round_timings": rows}}) == 41.5
    # a program that sows no such counter (the parent's): nothing, and
    # nothing raised
    bare = {"results": {"round_timings": [{"compute_ms": 1.0}] * 3}}
    assert combine_row_fill.read(bare) is None


def test_no_picker_takes_the_kernel():
    """``moe_combine`` is a Mosaic call of five operands (two schedule
    arrays, the rows, their tokens and weights) and one output: no kernel
    file claims it, so the expert rooflines do not read it as a grouped
    product."""
    line = ('ROOT %moe_combine.1 = f32[8192,2304]{1,0:T(8,128)} custom-call('
            '%edges.1, %steps.2, %rows.1, %bitcast.4, %bitcast.5), '
            'custom_call_target="tpu_custom_call"')
    assert trace.kernel_of(line, trace.load_kernels()) is None
    gmm = line.replace(", %bitcast.5", "")
    assert trace.kernel_of(gmm, trace.load_kernels()) == "moe_gmm"


def test_the_entry_lists_the_sparse_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"]
              if m["name"] == "combine_row_fill"]
    assert entry == {"name": "combine_row_fill", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "experts", "moves": "train_tokens_per_s",
                     "workloads": CELLS}
    for cell in CELLS:
        assert "combine_row_fill" in [
            m["name"] for m in bench_run.metrics_of_cell(
                bench, cell, "per_layer")]
