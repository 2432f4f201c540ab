"""How long a round's packs are: as many steps as the rows need, in buckets
of 1, 2, 4, 8, 12, ... (``driver._pack_steps``).  Every packed step is
computed, masked or not, so a pack of one step's rows packed into four
computed three forward passes over rows that do not exist; the bucket above
two bounds the round-program shapes a run compiles while repartition moves
the shard sizes.  The rows' ``pad_steps`` counts the steps that carry no
row."""

import json
import os

import numpy as np
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
    driver,
    train,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import (
    _pack_steps,
    train_global,
)

BATCH = 16
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("need, steps", [
    (1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (9, 12),
    (0, 4),                 # no rows at all: the parent's one bucket
])
def test_a_pack_is_as_long_as_its_rows_need(need, steps):
    assert _pack_steps(need) == steps


def cfg(**kw):
    base = dict(model="mlp", dataset="mnist", epochs_global=3,
                epochs_local=1, batch_size=BATCH, limit_train_samples=64,
                limit_eval_samples=16, compute_dtype="float32",
                augment=False, aggregation_by="weights", seed=1,
                num_workers=1)
    base.update(kw)
    return Config(**base)


def run(config, monkeypatch, val_steps=None):
    """One call with pinned walls; ``val_steps`` pads the staged
    validation pack by hand to that many steps (the added steps fully
    masked) on its way into the engine.  Returns the results and the
    (train steps, validation steps) of every staged pair of packs."""
    seen = []
    stage = train.LocalSGDEngine.stage_pack

    def spy(self, train_pack, val_pack):
        if val_steps is not None:
            pad = val_steps - val_pack[0].shape[1]
            val_pack = tuple(np.concatenate(
                [a, np.zeros((a.shape[0], pad) + a.shape[2:], a.dtype)], 1)
                for a in val_pack)
        seen.append((train_pack[0].shape[1], val_pack[0].shape[1],
                     val_pack[2].sum((1, 2))))
        return stage(self, train_pack, val_pack)

    monkeypatch.setattr(train.LocalSGDEngine, "stage_pack", spy)
    n = config.num_workers
    res = train_global(config, simulated_durations=[1.0] * n,
                       simulated_round_durations=lambda ep: [1.0] * n,
                       progress=False)
    monkeypatch.undo()
    return res, seen


def test_one_validation_step_is_packed_as_one(monkeypatch):
    """A validation shard of one step's rows is a pack of one step, and
    its losses are those of the same run with three masked steps added by
    hand: masked steps add exact zeros to the sums."""
    res, seen = run(cfg(), monkeypatch)
    assert [s[:2] for s in seen] == [(4, 1)] * 3
    assert [row["pad_steps"] for row in res["round_timings"]] == [0] * 3
    padded, seen4 = run(cfg(), monkeypatch, val_steps=4)
    assert [s[:2] for s in seen4] == [(4, 4)] * 3
    np.testing.assert_allclose(res["global_val_losses"],
                               padded["global_val_losses"], rtol=1e-6)
    np.testing.assert_allclose(res["global_train_losses"],
                               padded["global_train_losses"], rtol=1e-6)


def test_repartition_builds_one_program_a_bucket(monkeypatch):
    """Four workers whose shares a script moves round by round, so that
    the largest validation shard needs 1, 2, 3, 4, 3, 2, 1 steps: three
    buckets (1, 2, 4), three round programs, each built once, and
    ``pad_steps`` the steps beyond each worker's rows."""
    shares = [[.25] * 4, [.4, .2, .2, .2], [.7, .1, .1, .1],
              [.97, .01, .01, .01], [.7, .1, .1, .1], [.4, .2, .2, .2],
              [.25] * 4]
    script = iter(shares)
    monkeypatch.setattr(driver, "efficiency_ratios",
                        lambda durations, mode: np.asarray(next(script)))
    # a grace budget below one step caps every worker's training at one
    # step, so the train pack keeps one shape and the validation pack
    # alone moves
    config = cfg(epochs_global=len(shares), num_workers=4,
                 limit_train_samples=256, limit_eval_samples=64,
                 time_limit=1e-6)
    res, seen = run(config, monkeypatch)
    rows = res["round_timings"]
    need = [int(np.ceil(rows_.max() / BATCH)) for *_, rows_ in seen]
    assert need == [1, 2, 3, 4, 3, 2, 1]
    assert [s[1] for s in seen] == [1, 2, 4, 4, 4, 2, 1]
    assert {s[0] for s in seen} == {1}
    built = [name for row in rows for name in row["programs_built"]
             if name.startswith("round")]
    assert len(built) == len({_pack_steps(k) for k in need}) == 3
    assert [row["programs_built"] != [] for row in rows] == [
        True, True, True, False, False, False, False]
    pads = [int((steps * 4) - np.ceil(vr / BATCH).sum())
            for (_, steps, vr) in seen]
    assert [row["pad_steps"] for row in rows] == pads
    # the benchmark's reader: the mean over the window, rounds 1..N
    from benchmarks.layer_metrics import pad_steps_per_round
    assert pad_steps_per_round.read({"results": res}) == pytest.approx(
        np.mean(pads[1:]))
    assert pad_steps_per_round.read({"results": {"round_timings": [
        {"compute_ms": 1.0}] * 3}}) is None


def test_a_need_below_a_built_program_runs_in_it(monkeypatch):
    """Shards that fall below the packs of a program the engine has built
    run in that program: the validation need 2, 1, 1, 2 packs 2 steps
    each round, one round program in all, and a sanitized run (no trace
    and no compile after the warm-up) stays clean."""
    shares = [[.4, .2, .2, .2], [.25] * 4, [.25] * 4, [.4, .2, .2, .2]]
    script = iter(shares)
    monkeypatch.setattr(driver, "efficiency_ratios",
                        lambda durations, mode: np.asarray(next(script)))
    config = cfg(epochs_global=len(shares), num_workers=4,
                 limit_train_samples=256, limit_eval_samples=64,
                 time_limit=1e-6, sanitize=True)
    res, seen = run(config, monkeypatch)
    need = [int(np.ceil(rows_.max() / BATCH)) for *_, rows_ in seen]
    assert need == [2, 1, 1, 2]
    assert [s[:2] for s in seen] == [(1, 2)] * 4
    assert [row["programs_built"] for row in res["round_timings"]] == [
        ["round"], [], [], []]
    assert res["sanitize"]["retrace_count"] == 0
    assert res["sanitize"]["recompile_count"] == 0


def test_a_need_runs_in_no_longer_packs_than_a_granule_of_four(monkeypatch):
    """A built program runs a smaller need only where its packs are no
    longer than a granule of four steps gives that need: the train need
    falls 11 -> 8 and the round packs 8 steps, in a program of its own,
    where the 12-step program would compute 4 masked steps a worker."""
    shares = [[.35, .35, .15, .15], [.25] * 4]
    script = iter(shares)
    monkeypatch.setattr(driver, "efficiency_ratios",
                        lambda durations, mode: np.asarray(next(script)))
    config = cfg(epochs_global=len(shares), num_workers=4,
                 limit_train_samples=480, limit_eval_samples=64)
    res, seen = run(config, monkeypatch)
    assert [s[:2] for s in seen] == [(12, 4), (8, 2)]
    assert [row["programs_built"] for row in res["round_timings"]] == [
        ["round"], ["round#2"]]


def test_the_benchmark_reads_it_in_every_cell():
    """In ``per_layer`` (found by its name: later entries are appended
    after it), with no list of cells: every cell's rows carry the key."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == "pad_steps_per_round"]
    assert entry == {"name": "pad_steps_per_round", "unit": "steps",
                     "better": "lower", "source": "program_counter",
                     "layer": "orchestration", "moves": "train_tokens_per_s"}
