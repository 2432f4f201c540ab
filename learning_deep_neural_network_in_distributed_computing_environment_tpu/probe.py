"""The heterogeneity timing probe.

Capability parity with ``estimate_epoch_duration``
(``Balanced All-Reduce/dataloader.py:119-153``): each worker times a fixed
number of forward+backward batches, durations are gathered across workers,
and shard-share ratios are derived from them.

TPU-native redesign:

- the timed computation is a *jitted* fwd+bwd (``outputs.sum().backward()``
  equivalent: grad of the summed logits w.r.t. params), compiled once and
  excluded from timing — the probe measures steady-state step time, not
  compilation;
- gradients never leak into training state (the reference leaves stale
  grads behind, SURVEY.md 2.5.7 — structurally impossible here since the
  probe is a pure function);
- durations are exchanged host-side with
  ``jax.experimental.multihost_utils.process_allgather`` between rounds,
  never inside a compiled program (SURVEY.md 7.3 host-side control flow).
  On a single process all mesh positions share one clock, so the gathered
  vector is uniform; heterogeneous fleets get real spread, and tests inject
  ``simulated_durations``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .spans import span
from .xla_flags import compile_cache_counts


# ----------------------------------------------------------------------
# Compiled-memory observability (ISSUE 15)
# ----------------------------------------------------------------------
# CPU cannot see HBM walls, so memory must be a MEASURED, asserted
# quantity on every compiled program: each engine wraps its cached jit
# programs in a TrackedProgram, which compiles ahead-of-time on first
# call (trace().lower().compile(), the three stages apart — the same one
# trace + one backend compile the jit path would pay; verified against
# the compile-event counter) and keeps the jax.stages.Compiled handle so
# ``memory_report`` can read XLA's ``memory_analysis()``
# (temp/argument/output/alias bytes) without ever re-lowering.  Calls
# after the first dispatch straight on the compiled executable —
# donation, shardings, and fp32 numerics are bitwise those of the jit
# path (tests/test_remat_memory.py pins this).  What the compiler counts
# for ONE program is not what the device's allocator holds for the
# process: ``memory_report`` carries the allocator's own readings beside
# it (``hbm``; ``spans.hbm`` takes them).


class TrackedProgram:
    """A cached engine program with its compiled executable retained.

    ``single-shape`` mode (default — the engines key their caches by
    input shape already): the first call AOT-compiles and every later
    call dispatches on that executable with zero per-call bookkeeping.
    ``multi_shape=True`` (the serve prefill program, one jit specialized
    per prompt bucket): executables are kept per input-shape key.

    A lower/compile failure propagates: a program the backend refuses
    (a Mosaic lowering error, an HBM overflow) must stop the run, not
    be retried through ``jit`` under an observability warning.  Only a
    multi-process run takes the plain jit call path (its memory row
    reports ``available: False``).

    ``_compile`` is the one site where a program is traced, lowered and
    compiled (or loaded from the compile cache).  It is a span under the
    owner's name for it (``build_span``; the round loop's engine says
    ``round.build``) with one child span a stage (``.trace``: the host's
    Python running the function into a jaxpr; ``.lower``: the jaxpr into
    StableHLO; ``.compile``: the backend's compile, or the persistent
    cache's load of an executable it already has) and appends ``(name,
    row)`` to ``built`` — the list an engine shares among its programs
    and the driver drains into the row of the round whose dispatch built
    them.  ``row`` holds ``build_ms`` and its parts ``build_trace_ms``,
    ``build_lower_ms``, ``build_compile_ms``, and what the persistent
    cache said during the compile stage, ``build_cache_hits`` /
    ``build_cache_misses`` (one hit and no miss: the stage was a load;
    both zero: no cache is armed, or the program is one the cache does
    not keep).
    """

    def __init__(self, name: str, fn, *, multi_shape: bool = False,
                 built: list | None = None,
                 build_span: str = "program.build"):
        self.name = name
        self._fn = fn
        self._built = built
        self._build_span = build_span
        self._multi = bool(multi_shape)
        self._multiprocess = jax.process_count() > 1
        self.compiled = None           # single-shape executable
        self._by_shape: dict = {}      # multi-shape: key -> executable

    @staticmethod
    def _shape_key(args):
        return tuple(
            (tuple(np.shape(l)), str(getattr(l, "dtype", type(l).__name__)))
            for l in jax.tree_util.tree_leaves(args))

    def _compile(self, args, kwargs):
        row: dict = {}
        stage, ids = self._build_span, {"program": self.name}
        with span(stage, row, "build_ms", **ids):
            with span(f"{stage}.trace", row, "build_trace_ms", **ids):
                traced = self._fn.trace(*args, **kwargs)
            with span(f"{stage}.lower", row, "build_lower_ms", **ids):
                lowered = traced.lower()
            cache0 = compile_cache_counts()
            with span(f"{stage}.compile", row, "build_compile_ms", **ids):
                comp = lowered.compile()
            cache1 = compile_cache_counts()
        row["build_cache_hits"] = cache1["hits"] - cache0["hits"]
        row["build_cache_misses"] = cache1["misses"] - cache0["misses"]
        if self._built is not None:
            self._built.append((self.name, row))
        return comp

    def __call__(self, *args, **kwargs):
        if self._multiprocess:
            return self._fn(*args, **kwargs)
        if self._multi:
            key = self._shape_key((args, kwargs))
            comp = self._by_shape.get(key)
            if comp is None:
                comp = self._by_shape[key] = self._compile(args, kwargs)
        else:
            comp = self.compiled
            if comp is None:
                comp = self.compiled = self._compile(args, kwargs)
        return comp(*args, **kwargs)

    def executables(self) -> list:
        if self.compiled is not None:
            return [self.compiled]
        return list(self._by_shape.values())

    def memory_rows(self) -> list[dict]:
        """One ``memory_analysis()`` row per compiled executable (the
        multi-shape prefill program has one per bucket)."""
        return [memory_analysis_row(c) for c in self.executables()]


def fold_builds(built: list) -> dict:
    """The row keys of a dispatch from the ``(name, row)`` pairs its
    programs' builds left in ``built``: each duration and each cache
    count summed over the programs, and their names.  No build: zeros
    and ``[]``, which is what every round but the first reads."""
    rows = [row for _, row in built]
    out: dict = {key: round(sum((r[key] for r in rows), 0.0), 3)
                 for key in ("build_ms", "build_trace_ms", "build_lower_ms",
                             "build_compile_ms")}
    for key in ("build_cache_hits", "build_cache_misses"):
        out[key] = sum(r[key] for r in rows)
    out["programs_built"] = [name for name, _ in built]
    return out


def memory_analysis_row(compiled) -> dict:
    """XLA's compiled-memory stats for one executable, as plain ints:
    ``temp_bytes`` (scratch + saved activations — the quantity the remat
    policy moves), ``argument_bytes`` / ``output_bytes`` (I/O buffers),
    ``alias_bytes`` (donated input bytes reused for outputs — subtracted
    from the true footprint since aliased pairs share one buffer), and
    ``generated_code_bytes``."""
    ma = compiled.memory_analysis()
    return {
        "temp_bytes": int(ma.temp_size_in_bytes),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }


def memory_report(programs: dict, *, state_bytes: dict | None = None,
                  n_workers: int = 1, sim: bool = False,
                  hbm: dict | None = None) -> dict:
    """The uniform ``results["memory"]`` row (ISSUE 15) — emitted on
    every run like ``sync_engine`` / ``sanitize``.

    Two views of the same wall, and the wall's own reading where the
    backend gives one (``hbm``: the allocator's statistics as the driver
    stamped them at every set-up phase and at the call's end; the key is
    absent where the backend reports none):

    - **compiled**: per-program ``memory_analysis()`` of every cached
      executable (``programs``: name -> TrackedProgram).  ``temp_bytes``
      is where a remat policy shows up — saved activations are XLA temp
      allocations, so ``none >= dots_saveable >= save_names:<set> >=
      everything`` is an asserted ordering (tests/test_remat_memory.py
      ``test_temp_bytes_monotone_down_the_ladder``), not a narrative.  A
      program with no compiled executable (a multi-process run's jit
      path) contributes no row and flips ``available`` off.
    - **analytic resident model**: ``per_worker_state_bytes`` (the
      ISSUE 9/11 accounting) extended with the stacked/fleet total
      (``state_bytes_total`` = workers x per-worker — on a simulated run
      that total is ONE chip's stacked residency, the ISSUE 14 N-ceiling
      quantity) and the worker peak (resident + the transient
      ``params_gathered_peak`` the round-entry gather materializes).
    """
    rows: dict[str, list[dict]] = {}
    missing: list[str] = []
    for name, tp in programs.items():
        r = tp.memory_rows() if hasattr(tp, "memory_rows") else []
        if r:
            rows[name] = r
        else:
            missing.append(name)
    temp_total = sum(r["temp_bytes"] for rs in rows.values() for r in rs)
    report: dict = {
        "available": bool(rows) and not missing,
        "programs": rows,
        "programs_unavailable": missing,
        "temp_bytes_total": temp_total,
        "workers": int(n_workers),
        "simulated": bool(sim),
    }
    if state_bytes is not None:
        peak = int(state_bytes.get("params_gathered_peak", 0))
        resident = sum(int(v) for k, v in state_bytes.items()
                       if k != "params_gathered_peak")
        report["per_worker_state_bytes"] = dict(state_bytes)
        report["per_worker_resident_bytes"] = resident
        # worker peak = steady resident state + the transient padded
        # gather buffers (zero on replicated layouts — no transient copy)
        report["per_worker_peak_bytes"] = resident + peak
        # fleet total on a real mesh; ONE-CHIP stacked total on a
        # simulated run (N x per-worker by construction — the measured
        # form of the sim-lab N-ceiling)
        report["state_bytes_total"] = resident * int(n_workers)
    if hbm:
        report["hbm"] = hbm
    return report


UNMEASURED = 1e-9   # sec/batch of a worker that no probe has timed


def measure_step_time(model, variables, sample_batch: np.ndarray,
                      num_batches: int = 10) -> float:
    """Seconds for ``num_batches`` jitted fwd+bwd executions (post-compile).

    ``model`` is the model that trains (the driver's ``host_model``).  The
    executions run one at a time and each hands back one scalar, the sum
    of its gradient, so the probe holds one gradient tree as a temporary
    and never ``num_batches`` of them as outputs beside the resident
    state."""

    def fwd_bwd(params, rest, x):
        def loss(p):
            out = model.apply({"params": p, **rest}, x, train=False)
            return out.sum()
        return sum(g.sum() for g in jax.tree_util.tree_leaves(
            jax.grad(loss)(params)))

    num_batches = max(num_batches, 1)
    params = variables["params"]
    rest = {k: v for k, v in variables.items() if k != "params"}
    # one-shot per probe: compiled once, the timed loop below reuses it
    # (compile excluded from timing by design); a probe runs once per
    # train_global with a run-specific model, so caching buys nothing
    # graftlint: disable=R2 -- intentional single probe compile per run
    fn = jax.jit(fwd_bwd)
    # the probe times ONE device.  A replicated state (dense sync) hands it
    # worker 0's row still laid over every chip, and a jit over several
    # devices is a partitioned program, which a Mosaic kernel refuses
    # ("cannot be automatically partitioned"): commit the inputs to this
    # process's first device
    params, rest, x = jax.device_put((params, rest, jnp.asarray(sample_batch)),
                                     jax.local_devices()[0])
    jax.block_until_ready(fn(params, rest, x))  # compile
    t0 = time.perf_counter()
    for _ in range(num_batches):
        jax.block_until_ready(fn(params, rest, x))
    return time.perf_counter() - t0


def gather_durations(local_duration: float, world_size: int,
                     simulated_durations=None) -> np.ndarray:
    """All processes' probe durations as a [world_size] vector (ref
    dataloader.py:139-147).  ``simulated_durations`` overrides for tests and
    for heterogeneity experiments on homogeneous hardware."""
    if simulated_durations is not None:
        d = np.asarray(simulated_durations, np.float64)
        if d.shape != (world_size,):
            raise ValueError(
                f"simulated_durations must have shape ({world_size},), "
                f"got {d.shape}")
        return d
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        gathered = multihost_utils.process_allgather(
            np.asarray([local_duration], np.float64))
        per_process = np.asarray(gathered).ravel()
        # jax.devices() orders devices contiguously by process (process 0's
        # local devices first), so each process's timing covers a contiguous
        # block of mesh positions.  Same strictness as the wall-time twin
        # (driver._measured_worker_walls): a non-divisible worker/process
        # count would silently mis-attribute durations, so refuse it.
        if world_size % per_process.size:
            raise ValueError(
                f"worker axis ({world_size}) not evenly divided by process "
                f"count ({per_process.size}); per-process probe-duration "
                "attribution would be wrong")
        return np.repeat(per_process, world_size // per_process.size)
    return np.full(world_size, local_duration, np.float64)


def joiner_sec_per_batch(survivor_spb: np.ndarray,
                         mode: str = "mean") -> float:
    """Probe-EMA seed for a worker JOINING mid-run (ISSUE 8).

    A joiner has no probe measurement and no wall history, so its
    sec/batch entry — which drives its step cap and shard share until
    measured walls blend in — is synthesized from the survivors' EMA:
    ``mean`` assumes fleet-typical hardware (default); ``max`` is the
    conservative choice (smallest initial shard/cap, so a slow joiner
    cannot straggle its first round); ``min`` the optimistic one.  The
    delayed-EMA feedback corrects whichever guess within two rounds."""
    spb = np.asarray(survivor_spb, np.float64)
    if spb.size == 0 or np.any(spb <= 0):
        raise ValueError(
            f"survivor sec/batch vector must be non-empty and positive, "
            f"got {survivor_spb!r}")
    if mode == "mean":
        return float(spb.mean())
    if mode == "max":
        return float(spb.max())
    if mode == "min":
        return float(spb.min())
    raise ValueError(f"unknown joiner_sec_per_batch mode {mode!r}")


def estimate_epoch_duration(model, variables, sample_batch: np.ndarray,
                            world_size: int, num_batches: int = 10,
                            simulated_durations=None):
    """Returns (durations [world_size], sec_per_batch [world_size]).

    One worker has nobody to be compared with: its ratio is 1 whatever the
    probe would read, so none runs.  Its duration is a unit one and its
    sec/batch ``UNMEASURED``, which caps no round; the measured round
    walls take the estimate over from there (``driver.consume_walls``)."""
    if simulated_durations is None and world_size == 1:
        return np.ones(1), np.full(1, UNMEASURED)
    if simulated_durations is None:
        local = measure_step_time(model, variables, sample_batch, num_batches)
    else:
        local = float(np.asarray(simulated_durations).ravel()[0])
    durations = gather_durations(local, world_size, simulated_durations)
    return durations, durations / max(num_batches, 1)
