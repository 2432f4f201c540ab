"""A CPU rehearsal of the runner at a tiny size (four virtual devices for
the four-chip path).  It skips the harness's look for a chip and drives the
rest of a run: the result's keys, the window rebuilt from the driver's
round timings against the driver's own clock, the control, and each fault
a training cell can have, planted under the timed path.  Nothing here is a
time or a rate of a device."""

import importlib
import json
import os
import time

import numpy as np
import pytest

import jax

from benchmarks import run as bench_run
from benchmarks.entries import train_global as tg
from benchmarks.lib import check, compare, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "tiny")
PKG = tg.PKG
SEED = 2147483659


def run_cell(cell, seconds="1", trace="0"):
    return bench_run.run(["--workload", cell, "--seed", str(SEED),
                          "--seconds", seconds, "--trace", trace],
                         require_tpu=False, root=TINY)


@pytest.mark.parametrize("cell", ["lm1", "mlm1", "lm4"])
def test_result_line(cell, capsys):
    code, result = run_cell(cell)
    assert code == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(result))
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "hbm_peak_gib",
                                      "setup_s"}
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for c in result["compared"].values():
        assert set(c) == {"value", "limit", "ok"}


def test_no_chip_no_result(capsys):
    with pytest.raises(SystemExit):
        bench_run.run(["--workload", "lm1", "--seed", "1", "--seconds", "1",
                       "--trace", "0"], root=TINY)
    assert capsys.readouterr().out.strip() == ""


class _Clock:
    """The driver's clock, with every reading kept."""

    def __init__(self):
        self.stamps = []

    def perf_counter(self):
        t = time.perf_counter()
        self.stamps.append(t)
        return t

    def __getattr__(self, name):
        return getattr(time, name)


def _ready_times(stamps, timings):
    """Each round's ready time: the clock reading b for which an earlier
    reading a gives exactly the recorded compute_ms."""
    ready, after = [], -1.0
    for row in timings:
        found = None
        for j, b in enumerate(stamps):
            if b <= after:
                continue
            if any(round((b - a) * 1e3, 3) == row["compute_ms"]
                   for a in stamps[:j]):
                found = b
                break
        assert found is not None, "no clock reading matches compute_ms"
        ready.append(found)
        after = found
    return ready


@pytest.mark.parametrize("mode", ["overlap", "serial", "two_in_flight"])
def test_window_sum_is_the_round_span(mode, monkeypatch):
    """sum(compute_ms[1..n]) + positive gaps == ready[n] - ready[0] on the
    driver's own clock: overlapped, serial, and with two rounds in flight
    (the TPU's path, reached here by telling the driver it is not on a
    CPU, as the program's own tests do)."""
    driver = importlib.import_module(f"{PKG}.driver")
    with open(os.path.join(TINY, "benchmarks/configs/gpt_tiny.json")) as f:
        config = json.load(f)
    with open(os.path.join(TINY, "benchmarks/workloads/lm4.json")) as f:
        workload = json.load(f)
    if mode == "serial":
        workload["flags"] = workload["flags"] + ["--no_overlap_rounds"]
    if mode == "two_in_flight":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    clock = _Clock()
    monkeypatch.setattr(driver, "time", clock)
    rows = traffic.generate(workload["traffic"], config, SEED, 4)
    n = 5
    res, *_ = tg.timed_call(tg.build_argv(config, workload, SEED, 1 + n),
                            rows, config["vocab_size"])
    timings = res["round_timings"]
    if mode == "two_in_flight":
        assert "gap_ms" not in timings[1], "rounds were left in flight"
    ready = _ready_times(clock.stamps, timings)
    span = ready[n] - ready[0]
    assert tg.window_seconds(timings, n) == pytest.approx(span, abs=2e-5 * n)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_fault_under_the_timed_path_is_not_correct(fault, monkeypatch):
    """Each fault a training cell can have, planted in the PROGRAM, makes
    a whole run come out ``correct: false``."""
    train = importlib.import_module(f"{PKG}.train")
    if fault == "state_unchanged":
        import optax
        monkeypatch.setattr(optax, "apply_updates", lambda p, u: p)
    elif fault == "half_batch":
        real = train.masked_weights

        def half(labels, batch_mask):
            w = real(labels, batch_mask)
            keep = (np.arange(w.shape[0]) < w.shape[0] // 2)
            return w * keep.reshape((-1,) + (1,) * (w.ndim - 1))
        monkeypatch.setattr(train, "masked_weights", half)
    else:
        def no_sync(self, params, grads, residual, round_opt=None,
                    poison=None, outer_residual=None):
            import jax.numpy as jnp
            return (params, None, residual, round_opt, None, None,
                    jnp.zeros(()), outer_residual)
        monkeypatch.setattr(train.LocalSGDEngine, "_sync_body", no_sync)
    code, result = run_cell("lm4" if fault == "no_exchange" else "lm1")
    assert code == 0
    assert result["correct"] is False
    bad = [k for k, c in result["compared"].items() if not c["ok"]]
    assert bad, "a fault has to fail one of the numbers"


@pytest.mark.parametrize("precision", ["fp8", "int8"])
def test_control_is_not_correct(precision):
    """The reference in the program's place, one precision below the
    bfloat16 the configuration states, fails the cell's limits."""
    spec = bench_run.load_spec("lm1", TINY)
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
