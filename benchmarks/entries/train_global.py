"""Entry point ``train_global``: the program's training path, as the CLI
reaches it (``main.train_main``: ``config_from_args`` then
``driver.train_global``), with the benchmark's rows in place of the
program's toy ``synthetic_lm`` data (``datasets=`` is the driver's own
override).  Everything here is a call INTO the program; what the calls
return is read, never altered.
"""

from __future__ import annotations

import contextlib
import logging
import sys
import time

import numpy as np

PKG = "learning_deep_neural_network_in_distributed_computing_environment_tpu"


def build_argv(config: dict, workload: dict, seed: int, rounds: int,
               profile_dir: str | None = None) -> list[str]:
    """The CLI flags of one call.  ``workload["flags"]`` are the cell's own
    (attention, aggregation, ...: `chip_smoke.py`'s ``GPT_TRAIN`` minus the
    checkpoint); sizes come from the traffic parameters."""
    t = workload["traffic"]
    argv = ["--model", config["program_model"],
            "--dataset", {"causal_lm": "synthetic_lm",
                          "mlm": "synthetic_mlm"}[t["objective"]],
            "--batch_size", str(t["batch"]),
            "--epochs_local", "1", "--epochs_global", str(rounds),
            "--seed", str(seed)]
    argv += [str(x) for x in workload["flags"]]
    if profile_dir:
        argv += ["--profile_dir", profile_dir]
    return argv


def _dataset(x: np.ndarray, y: np.ndarray, vocab: int):
    from importlib import import_module
    Dataset = import_module(f"{PKG}.data.sources").Dataset
    return Dataset(images=x, labels=y, num_classes=vocab,
                   mean=np.zeros(1, np.float32), std=np.ones(1, np.float32))


def window_seconds(timings: list[dict], n: int) -> float:
    """Wall of rounds 1..n of one call, rebuilt from ``round_timings``.

    ``compute_ms[r]`` runs from max(dispatch of r, ready of r-1) to ready
    of r, and ``gap_ms[r-1]`` is dispatch of r minus ready of r-1 where the
    driver recorded it (it does not for a round it left in flight, whose
    successor it dispatches early).  So ready[n] - ready[0] is the sum of
    the computes plus the gaps that were positive."""
    ms = sum(timings[r]["compute_ms"] for r in range(1, n + 1))
    ms += sum(max(timings[r].get("gap_ms", 0.0), 0.0) for r in range(0, n))
    return ms / 1e3


def step_losses(results: dict, workers: int, steps: int) -> np.ndarray:
    """[workers, steps]: each worker's first ``steps`` training losses."""
    return np.asarray([results["all_workers_losses"][w][:steps]
                       for w in range(workers)], np.float64)


# ----------------------------------------------------------------------
# one run of a training cell
# ----------------------------------------------------------------------

class _RoundStamps:
    """Stands in for stdout during a call and notes when the program
    printed each round's first report line: its own progress output is
    the only per-round absolute time it gives (PERF.md, tracing list)."""

    def __init__(self, sink):
        self.sink = sink
        self.at: dict[int, float] = {}

    def write(self, text: str) -> int:
        if text.startswith("Rank "):
            try:
                rnd = int(text.split("Global Epoch ")[1].split(",")[0]) - 1
                self.at.setdefault(rnd, time.perf_counter())
            except (IndexError, ValueError):
                pass
        return self.sink.write(text)

    def flush(self) -> None:
        self.sink.flush()


class _CompileLog:
    """Times of every jaxpr trace and backend compile in this process."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.events: list[tuple[float, str]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.events.append((time.perf_counter(), event.split("/")[-1]))

    def between(self, t0: float, t1: float) -> list[str]:
        return [e for t, e in self.events if t0 < t < t1]


def timed_call(argv, rows, vocab):
    """``call`` with the round stamps taken."""
    from importlib import import_module
    config_from_args = import_module(f"{PKG}.config").config_from_args
    train_global = import_module(f"{PKG}.driver").train_global
    cfg = config_from_args(argv)
    logging.basicConfig(
        level=getattr(logging, cfg.log_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s: %(message)s")
    train = _dataset(*rows["train"], vocab)
    val = _dataset(*rows["val"], vocab)
    stamps = _RoundStamps(sys.stderr)
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(stamps):
        results = train_global(cfg, datasets=(train, val, val))
    return results, t0, time.perf_counter(), stamps.at


def _release(results: dict) -> None:
    """Drop everything of a call that holds device memory."""
    import gc
    results.clear()
    gc.collect()


def expose_state(config: dict, workload: dict, rows: dict, seed: int,
                 workers: int) -> dict:
    """Set-up's two short calls: one compiled round each, the window's own
    program and shapes, with labels on the first 1 / first ``check.steps``
    batches only, so that the state a call returns is the state after that
    many optimizer steps (a step with no labelled position leaves the
    state as it is).  Returns each call's losses, the block norms of the
    first gradient as the optimizer got it (Adam's first moment after one
    step is (1 - b1) * g), the parameters after ``check.steps`` steps, and
    a lone round's seconds to size the window with."""
    import jax
    from benchmarks.lib import compare, traffic

    t = workload["traffic"]
    vocab = int(config["vocab_size"])
    check_steps = int(workload["check"]["steps"])
    x, y = rows["train"]

    def short_call(real_steps: int):
        r = dict(rows, train=(x, traffic.keep_first_steps(
            y, real_steps, t, workers)))
        return timed_call(build_argv(config, workload, seed, 1), r, vocab)

    res, t0, t1, _ = short_call(1)
    out = {"losses_one": step_losses(res, workers, 1),
           "grad_norms": compare.block_norms_by_worker(
               res["state"].opt_state.mu, 1 / (1 - compare.ADAM_B1))}
    seconds = [t1 - t0]
    _release(res)

    res, t0, t1, _ = short_call(check_steps)
    # compute_ms of a call's round 0 runs from before the dispatch, which
    # loads the program, so the dispatch's own time comes off.  The device
    # starts inside the dispatch, so this reads low (PERF.md, section 2)
    row = res["round_timings"][0]
    out.update(
        losses_check=step_losses(res, workers, check_steps),
        params_after=jax.device_get(
            compare._as_dict(res["variables"]["params"])),
        lone_round_s=(row["compute_ms"] - row.get("stage_ms", 0.0)) / 1e3,
        sync_engine={k: res["sync_engine"][k] for k in
                     ("mode", "opt_placement", "param_residency")},
        seconds=seconds + [t1 - t0])
    _release(res)
    return out


def run_cell(spec: dict, seed: int, seconds: float, devices, *,
             trace_dir: str | None, t_process: float) -> dict:
    from benchmarks.lib import check, compare, peaks, traffic

    config, workload = spec["config"], spec["workload"]
    t = workload["traffic"]
    workers = len(devices)
    vocab = int(config["vocab_size"])
    steps = int(t["steps_per_round"])
    check_steps = int(workload["check"]["steps"])
    notes: dict = {}
    compiles = _CompileLog()

    rows = traffic.generate(t, config, seed, workers)
    x, y = rows["train"]
    notes["data_s"] = time.perf_counter() - t_process

    # --- set-up: the two short calls that expose the state ------------
    state = expose_state(config, workload, rows, seed, workers)
    loss_a, loss_b = state["losses_one"], state["losses_check"]
    grad_norms, params_after = state["grad_norms"], state["params_after"]
    round_s = state["lone_round_s"]
    notes.update(call_one_step_s=state["seconds"][0],
                 call_check_steps_s=state["seconds"][1],
                 sync_engine=state["sync_engine"])

    # --- the measured call: round 0 is set-up, rounds 1..n the window ---
    n = int(workload["trace_rounds"]) if trace_dir else max(
        1, int(seconds / max(round_s, 1e-6)))
    res, t0, t1, stamps = timed_call(
        build_argv(config, workload, seed, 1 + n, trace_dir), rows, vocab)
    timings = res["round_timings"]
    if len(timings) != 1 + n:
        return {"abort": f"asked for {1 + n} rounds, the driver recorded "
                         f"{len(timings)}"}
    window = window_seconds(timings, n)
    call_wall = t1 - t0
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    notes["memory_stats_device0"] = {
        k: int(v) for k, v in (devices[0].memory_stats() or {}).items()
        if isinstance(v, (int, float))}
    notes.update(rounds=n, window_s=window, call_wall_s=call_wall,
                 round_s_estimate=round_s,
                 compile_cache=dict(res["compile_cache"]),
                 compiled_memory={
                     name: [{k: r[k] for k in ("temp_bytes", "argument_bytes",
                                               "output_bytes", "alias_bytes")}
                            for r in rows_]
                     for name, rows_ in res["memory"]["programs"].items()})
    if window > call_wall:
        return {"abort": f"window {window:.3f} s rebuilt from round_timings "
                         f"exceeds the call's wall {call_wall:.3f} s"}
    if 0 in stamps and n in stamps:
        # the program's own report lines, stamped as they came: a second
        # reading of the window, and the only absolute times there are
        notes["window_by_report_lines_s"] = stamps[n] - stamps[0]
        inside = compiles.between(stamps[0], stamps[n])
        notes["compiles_in_window"] = len(inside)
        if inside:
            return {"abort": f"{len(inside)} trace/compile event(s) inside "
                             f"rounds 1..{n}: {sorted(set(inside))}"}
    loss_m = step_losses(res, workers, steps)
    all_losses = np.concatenate(
        [np.asarray(res["all_workers_losses"][w], np.float64)
         for w in range(workers)])
    sync_bytes = int(res["sync_engine"]["sync_bytes_ici"])
    layer_results = {
        "round_timings": [dict(r) for r in timings],
        "sync_bytes_ici": sync_bytes,
        "memory": dict(res["memory"]),
    }
    _release(res)
    tokens = n * workers * steps * int(t["batch"]) * int(t["seq_len"])
    end_to_end = {
        "train_tokens_per_s": tokens / window,
        "hbm_peak_gib": peak / 2**30,
        "setup_s": (t1 - t_process) - window,
    }

    # --- the comparison, once the window has closed ---------------------
    t_ref = time.perf_counter()
    # every worker's steps on chip 0, one after the other: 3 float32 steps
    # a worker are under a second, and one program compiles once
    ref = check.reference_reading(config, t, x, y, seed, workers,
                                  check_steps, devices[:1])
    got = {"losses": loss_m[:, :check_steps], "grad_norms": grad_norms,
           "update_norms": compare.block_norms(
               compare.tree_sub(params_after, ref["p0"]))}
    values, where = check.numbers(got, ref)
    values["twin_loss_gap"] = float(max(
        np.max(np.abs(loss_a - loss_m[:, :1])),
        np.max(np.abs(loss_b - loss_m[:, :check_steps]))))
    compared = compare.judge(values, workload["check"]["limits"])
    notes.update(reference_s=time.perf_counter() - t_ref, **where,
                 first_losses=loss_m[:, :check_steps].tolist(),
                 reference_losses=ref["losses"].tolist())
    failed = int(np.sum(~np.isfinite(all_losses)))
    out = {
        "correct": all(c["ok"] for c in compared.values()) and failed == 0,
        "attempted": int(all_losses.size), "failed": failed,
        "end_to_end": end_to_end, "memory_peak_bytes": peak,
        "compared": compared, "notes": notes,
    }
    if trace_dir:
        from benchmarks.lib import trace as trace_lib
        reference = check.reference_of(config)
        reduced = trace_lib.reduce_dir(trace_dir, rounds=1 + n)
        out["layer_context"] = {
            "trace": reduced, "results": layer_results, "rounds": n,
            "workers": workers, "traffic": t,
            "arch": reference.arch_of(config),
            "tokens_per_s": end_to_end["train_tokens_per_s"],
            "peaks": peaks.peaks_of(devices[0].device_kind),
            "flops_per_token": reference.train_flops_per_token(config, t),
        }
    return out
