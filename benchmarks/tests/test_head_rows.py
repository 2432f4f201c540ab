"""The MLM cell's row buffer rehearsed on the CPU at the tiny preset
(``tiny_mlm/``: 8 x 64 positions a step, so the buffer of 128 rows is
smaller than the step): a whole run's verdict with the head on the
labelled rows only, the short calls' steps without a label among them;
the counter in the rounds' rows at BERT's mask rate and at one that fills
the buffer three times a step; and the reader on a synthetic context.
Nothing here is a time or a rate of a device."""

import os

import pytest

from benchmarks import run as bench_run
from benchmarks.entries import train_global as tg
from benchmarks.layer_metrics import head_rows_per_step
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny_mlm")
SEED = 2147483659


@pytest.mark.parametrize("cell", ["mlm512", "mlm512_dense"])
def test_run_is_correct_with_the_row_buffer(cell):
    code, result = bench_run.run(
        ["--workload", cell, "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], require_tpu=False, root=TINY)
    assert code == 0
    assert result["correct"] is True and result["failed"] == 0
    assert result["compared"]["twin_loss_gap"]["value"] == 0.0


@pytest.mark.parametrize("cell,head_rows", [
    ("mlm512", 128.0),            # 77 +- 8 labels a step: one pass
    ("mlm512_dense", 384.0),      # 307 +- 11: three
])
def test_rows_carry_the_counter(cell, head_rows):
    spec = bench_run.load_spec(cell, TINY)
    c, w = spec["config"], spec["workload"]
    rows = traffic.generate(w["traffic"], c, SEED, 1)
    results = tg.timed_call(tg.build_argv(c, w, SEED, 2), rows,
                            c["vocab_size"])[0]
    assert [r["head_rows"] for r in results["round_timings"]] == [head_rows] * 2
    ctx = {"results": {"round_timings": results["round_timings"]}}
    assert head_rows_per_step.read(ctx) == head_rows


def test_reader_takes_the_windows_rounds():
    rows = [{"head_rows": 8192.0}] + [{"head_rows": 2048.0 + 256 * r}
                                      for r in range(4)]
    assert head_rows_per_step.read(
        {"results": {"round_timings": rows}}) == 2432.0
    bare = {"results": {"round_timings": [{"compute_ms": 1.0}] * 3}}
    assert head_rows_per_step.read(bare) is None
