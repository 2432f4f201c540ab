"""The traffic generator: the same seed gives the same rows, rows all
differ, labels follow the objective, and the labelled-prefix cut keeps each
worker's first batches only."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import traffic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cell(name):
    with open(os.path.join(ROOT, "workloads", f"{name}.json")) as f:
        w = json.load(f)
    return w["traffic"]


def config(name):
    with open(os.path.join(ROOT, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell_name,config_name,workers", [
    ("gpt2s_train_1k", "gpt2_small", 1), ("bert_train_512", "bert_base", 1),
    ("gpt2s_round_4chip", "gpt2_small", 4)])
def test_rows_from_the_seed(cell_name, config_name, workers):
    t, c = cell(cell_name), config(config_name)
    seed = 2**31 + 12345
    a = traffic.generate(t, c, seed, workers)
    b = traffic.generate(t, c, seed, workers)
    other = traffic.generate(t, c, seed + 1, workers)
    x, y = a["train"]
    n = workers * t["steps_per_round"] * t["batch"]
    assert x.shape == y.shape == (n, t["seq_len"])
    assert x.dtype == y.dtype == np.int32
    assert np.array_equal(x, b["train"][0]) and np.array_equal(y, b["train"][1])
    assert not np.array_equal(x, other["train"][0])
    assert len({row.tobytes() for row in x}) == n, "rows all differ"
    assert x.min() >= 0 and x.max() < c["vocab_size"]
    if t["objective"] == "causal_lm":
        assert np.array_equal(y[:, :-1], x[:, 1:]) and (y[:, -1] == -1).all()
    else:
        labelled = y >= 0
        assert 0.12 < labelled.mean() < 0.18
        assert (x[labelled] == c["special_tokens"]["mask"]).mean() > 0.7
    cut = traffic.keep_first_steps(y, 3, t, workers).reshape(
        workers, t["steps_per_round"], t["batch"], -1)
    assert np.array_equal(
        cut[:, :3], y.reshape(cut.shape)[:, :3]) and (cut[:, 3:] == -1).all()
