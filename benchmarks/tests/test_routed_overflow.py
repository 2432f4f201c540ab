"""``routed_overflow_chunks`` (ISSUE 33) rehearsed on the CPU: the counter
in the rounds' rows of the tiny AFMoE cell (``tiny_afmoe/``: every expert
held, so the buffer is the worst case and nothing can overflow), the
reader on a synthetic context, and the benchmark's entry.  Nothing here is
a time or a rate of a device."""

import json
import os

from benchmarks import run as bench_run
from benchmarks.entries import train_global as tg
from benchmarks.layer_metrics import routed_overflow_chunks
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny_afmoe")
ROOT = os.path.dirname(os.path.dirname(HERE))
SEED = 2147483659
CELLS = ["mellum2_train_8k", "kanana2_train_8k", "trinity_mini_train_8k"]


def test_rows_carry_the_counter():
    spec = bench_run.load_spec("afmoe1", TINY)
    c, w = spec["config"], spec["workload"]
    rows = traffic.generate(w["traffic"], c, SEED, 1)
    results = tg.timed_call(tg.build_argv(c, w, SEED, 2), rows,
                            c["vocab_size"])[0]
    timings = results["round_timings"]
    assert [r["expert_overflow_chunks"] for r in timings] == [0.0] * 2
    assert routed_overflow_chunks.read(
        {"results": {"round_timings": timings}}) == 0.0


def test_reader_takes_the_windows_rounds():
    rows = [{"expert_overflow_chunks": 3.0}] + [
        {"expert_overflow_chunks": 0.25 * r} for r in range(4)]
    assert routed_overflow_chunks.read(
        {"results": {"round_timings": rows}}) == 0.375
    # a program that sows no such counter (the parent's): nothing, and
    # nothing raised
    bare = {"results": {"round_timings": [{"compute_ms": 1.0}] * 3}}
    assert routed_overflow_chunks.read(bare) is None


def test_the_entry_lists_the_sparse_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {"name": "routed_overflow_chunks", "unit": "chunks",
                     "better": "lower", "source": "program_counter",
                     "layer": "experts", "moves": "train_tokens_per_s",
                     "workloads": CELLS}
    for cell in CELLS:
        assert "routed_overflow_chunks" in [
            m["name"] for m in bench_run.metrics_of_cell(
                bench, cell, "per_layer")]
