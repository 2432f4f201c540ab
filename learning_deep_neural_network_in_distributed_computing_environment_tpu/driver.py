"""``train_global``: the orchestration loop.

Host-side control flow around the compiled round program, reproducing the
reference's global-epoch loop (``Balanced All-Reduce/trainer.py:11-192``):

1. timing probe -> shard-share ratios (``dataloader.py:119-153``);
2. proportional contiguous partition of train AND val sets
   (``dataloader.py:41-46``), with non-IID skew in disbalanced mode;
3. per global epoch: run the compiled round (epochs_local local epochs +
   per-epoch validation + the sync point), collect metrics;
4. straggler ``time_limit`` as a per-worker step cap (SURVEY.md 2.5.4
   redesign of the finish-flag protocol);
5. measure round duration, re-partition every worker's shard from
   (prev_fraction x own previous indices) + (next_fraction x global pool)
   (``trainer.py:179-188``, ``dataloader.py:77-117``).

Returns the reference's twelve metric structures under their original names
(``trainer.py:192``) plus the final state.
"""

from __future__ import annotations

import copy
import logging
import os
import sys
import time
from contextlib import contextmanager
from concurrent.futures import ThreadPoolExecutor
from typing import Any

import jax
import numpy as np

from . import chaos as chaos_lib
from . import elastic as elastic_lib
from . import probe as probe_lib
from .config import Config
from .data import (
    adaptive_partition,
    budget_from_time_limit,
    efficiency_ratios,
    fixed_classes_for_rank,
    load_dataset,
    PackBufferPool,
    pack_window,
    repartition,
    skew_repartition,
    step_budget,
    train_val_split,
    window_feed,
)
from . import checkpoint as ckpt_lib
from .mesh import (DATA_AXIS, EXPERT_AXIS, MODEL_AXIS, PIPE_AXIS,
                   SLICE_AXIS, build_mesh, initialize_distributed,
                   max_data_axis_size, resize_data_axis, world_size)
from .models import get_model, is_attention_model, is_token_model
from . import spans
from .spans import span
from .train import LocalSGDEngine, rank0_variables

log = logging.getLogger(__name__)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult if x else mult


def _pack_steps(need: int) -> int:
    """The bucket of a pack whose rows need ``need`` steps: 1 or 2
    exactly, 3 or more rounded up to a multiple of 4 (1, 2, 4, 8, 12,
    ...).  Every packed step is computed, masked or not, so a pack is as
    long as its rows need; the bucket above 2 bounds the round-program
    shapes (one compiled program each) that repartition can move a run
    through."""
    return need if 0 < need < 3 else _round_up(need, 4)


def _assemble_round_metrics(results: dict, mx: dict, worker_ids) -> None:
    """One round's mx arrays -> the reference metric lists.

    Vectorized rewrite of the reference's nested per-epoch/per-worker
    assembly loops (``trainer.py:49-171`` semantics): numpy boolean
    indexing replaces the per-element Python iteration, producing the
    SAME lists in the SAME order — row-major masking of [E, S] is the
    original epoch-major extend order per worker, of [N, S] the original
    worker-major order per epoch.  Runs on the metric worker thread in
    the overlapped pipeline, inline in serial mode.

    ``worker_ids`` maps mesh rows to LOGICAL worker ids (ISSUE 8): the
    per-worker ``all_workers_losses`` lists are keyed by logical id, so
    a worker's curve stays its own across elastic membership changes (a
    departed worker's list freezes, a joiner gets a fresh one).  A bare
    int keeps the pre-elastic call shape (ids 0..n-1)."""
    if isinstance(worker_ids, (int, np.integer)):
        worker_ids = list(range(int(worker_ids)))
    bl = np.asarray(mx["batch_losses"])          # [N, E, S]
    valid = np.asarray(mx["batch_mask"]) > 0
    epochs_local = bl.shape[1]
    for pos, wid in enumerate(worker_ids):
        results["all_workers_losses"][wid].extend(
            bl[pos][valid[pos]].tolist())
    for e in range(epochs_local):
        results["all_epochs_losses"].append(bl[:, e][valid[:, e]].tolist())
    results["global_epoch_losses"].append(
        bl.transpose(1, 0, 2)[valid.transpose(1, 0, 2)].tolist())
    results["global_epoch_accuracies"].append(
        np.asarray(mx["avg_acc"])[0].tolist())
    results["global_train_losses"].append(float(mx["global_train_loss"][0]))
    results["global_train_accuracies"].append(float(mx["global_train_acc"][0]))
    results["global_val_losses"].append(float(mx["global_val_loss"][0]))
    results["global_val_accuracies"].append(float(mx["global_val_acc"][0]))
    # rank-0 per-local-epoch curves (trainer.py:122-126)
    results["worker_specific_train_losses"].extend(
        np.asarray(mx["train_loss"])[0].tolist())
    results["worker_specific_train_accuracies"].extend(
        np.asarray(mx["train_acc"])[0].tolist())
    results["worker_specific_val_losses"].extend(
        np.asarray(mx["val_loss"])[0].tolist())
    results["worker_specific_val_accuracies"].extend(
        np.asarray(mx["val_acc"])[0].tolist())


def build_model_for(cfg: Config, num_classes: int, **extra):
    import jax.numpy as jnp
    dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
    if cfg.dtype != "float32":
        raise NotImplementedError(
            "param dtype other than float32 is not supported yet; use "
            "--compute_dtype for bfloat16 activations/matmuls")
    if cfg.model_width:
        if cfg.model != "enhanced_cnn":
            raise ValueError(
                f"--model_width applies to --model enhanced_cnn; got "
                f"{cfg.model}")
        extra["width"] = cfg.model_width
    return get_model(cfg.model, num_classes=num_classes, dtype=dtype, **extra)


def checkpoint_metadata(cfg: Config, num_classes: int,
                        scan_layers: bool,
                        param_residency: str | None = None,
                        params_template=None) -> dict:
    """The arch facts MANIFEST.json carries so ``serve`` (and future
    inspection tools) rebuild the trained model straight from a checkpoint
    directory instead of the user restating ``--model``/layer flags
    (ISSUE 7 satellite).  Keys consumed by
    ``serve.engine.model_from_metadata``.  ``opt_placement`` (ISSUE 9)
    records the RESOLVED round-optimizer placement the state was saved
    with — restore re-lays the sharded/replicated moment rows out for the
    restoring run's placement (``checkpoint.restore_checkpoint``).
    ``param_residency`` (ISSUE 11) likewise records whether the params
    were saved as the full replicated tree or as 1/N resident bucket
    shards, and ``sync_bucket_mb`` the bucket plan the shard layout is
    keyed to — restore re-lays the params out across residency modes in
    both directions.  Pass the ENGINE's resolved residency: the engine
    demotes resident under inner mesh axes / a 1-worker axis, and the
    manifest must describe the layout actually saved (serve keys its
    resident-checkpoint rejection off it) — the config resolution is
    only the mesh-blind fallback."""
    meta = {"model": cfg.model, "num_classes": int(num_classes),
            "scan_layers": bool(scan_layers),
            "compute_dtype": cfg.compute_dtype,
            "num_kv_heads": int(cfg.num_kv_heads),
            "num_experts": int(cfg.num_experts),
            "capacity_factor": float(cfg.expert_capacity_factor),
            "dataset": cfg.dataset,
            "opt_placement": cfg.resolve_opt_placement(
                jax.default_backend()),
            "param_residency": (param_residency
                                or cfg.resolve_param_residency(
                                    jax.default_backend())),
            "sync_bucket_mb": float(cfg.sync_bucket_mb),
            # slice topology (ISSUE 13): restore re-lays resident bucket
            # rows out across slice counts (checkpoint.py) and a
            # hierarchical state's per-slice consensus is refused where
            # a global one is required — the manifest must say which
            # world wrote it
            "num_slices": int(cfg.num_slices)}
    from .models import ARCHS
    if cfg.model in ARCHS:
        # the architecture as data (models/arch.py): what rebuilds the
        # model without this tree's registry (DecoderArch.from_manifest)
        meta["arch"] = ARCHS[cfg.model].as_manifest()
    if params_template is not None:
        # per-worker params leaf shapes (ISSUE 12 satellite): a
        # scatter-resident checkpoint's 1/N bucket rows carry no leaf
        # shapes of their own — recording the template here lets
        # TEMPLATE-FREE consumers (serve) unpack the consensus straight
        # from the shard rows instead of refusing resident checkpoints
        flat = jax.tree_util.tree_flatten_with_path(params_template)[0]
        meta["params_leaves"] = [
            [[str(getattr(k, "key", k)) for k in path],
             [int(d) for d in leaf.shape], str(np.dtype(leaf.dtype))]
            for path, leaf in flat]
    return meta


@contextmanager
def _round_guard(san: dict):
    """Transfer guard around one round's dispatch/wait (ISSUE 6).

    ``jax.transfer_guard("disallow")`` makes any IMPLICIT host<->device
    transfer inside the guarded region raise — un-staged jit arguments,
    bare-Python-scalar eager arithmetic on device arrays — while the
    round loop's EXPLICIT staging (``device_put``/``device_get``/
    ``jnp.asarray``) passes.  A violation is counted into
    ``san["transfer_guard_violations"]`` before the error propagates,
    so a crashed sanitized run still reports what tripped it.  No-op
    when the sanitizer is off."""
    if not san["enabled"]:
        yield
        return
    try:
        with jax.transfer_guard("disallow"):
            yield
    except Exception as e:  # noqa: BLE001 — classify, count, re-raise
        # only the guard's own errors count ("Disallowed host-to-device
        # transfer" / "Disallowed device-to-host transfer" / ...): an
        # unrelated engine failure whose message merely contains
        # "transfer" must not masquerade as a guard violation
        msg = str(e).lower()
        if "disallow" in msg and "transfer" in msg:
            san["transfer_guard_violations"] += 1
            log.error("sanitizer: implicit transfer in the round loop: %s",
                      e)
        raise


def _measured_worker_walls(wall: float, n: int) -> np.ndarray:
    """Map this round's measured wall time onto the worker axis.

    Single process: one lockstep SPMD wall clock covers every worker.
    Multi-host: each process measures its own wall and all hosts exchange
    them (the reference's per-rank epoch-duration all-reduce,
    ``Balanced All-Reduce/trainer.py:179-184``); each process's wall is
    attributed to its local span of the worker axis.
    """
    if jax.process_count() == 1:
        return np.full(n, wall, np.float64)
    from jax.experimental import multihost_utils
    walls = np.asarray(multihost_utils.process_allgather(
        np.asarray([wall], np.float64)), np.float64).reshape(-1)
    per = n // len(walls)
    if per * len(walls) != n:
        raise ValueError(
            f"worker axis ({n}) not evenly divided by process count "
            f"({len(walls)}); per-process wall attribution would be wrong")
    return np.repeat(walls, per)


def _last_peak_rise(hbm: dict, rows: list[dict]) -> str:
    """The tail of the ``set-up:`` line: the last of the call's stamps
    (entry, set-up's phases, the rounds' rows, in that order) at which
    the allocator's high mark stood above the mark before it, and by how
    much.  Nothing where no stamp carries a reading."""
    marks = ([("on entry", hbm["on_entry"])]
             + [(row["phase"], row) for row in hbm["phases"]]
             + [(f"round {r}", row) for r, row in enumerate(rows)])
    peaks = [(label, row["hbm_peak_bytes"]) for label, row in marks
             if "hbm_peak_bytes" in row]
    if not peaks:
        return ""
    tail = f"; HBM peak {peaks[-1][1] / 2**30:.3f} GiB"
    for (_, before), (label, after) in reversed(
            list(zip(peaks, peaks[1:]))):
        if after > before:
            return tail + (f", last raised at {label!r} by "
                           f"{(after - before) / 2**20:.1f} MiB")
    return tail + ", as on entry"


def train_global(cfg: Config, *, mesh=None, simulated_durations=None,
                 simulated_round_durations=None, datasets=None,
                 elastic_snapshot=None, progress: bool = True
                 ) -> dict[str, Any]:
    """Run the full experiment; returns the reference's metric structures.

    ``simulated_durations``: inject per-worker probe durations (tests /
    heterogeneity experiments on homogeneous hardware).
    ``simulated_round_durations``: callable ``epoch -> [N] seconds``
    overriding the measured round wall time per worker (tests of the
    mid-run straggler feedback).  Under ``--chaos`` the vector length
    must match the round's CURRENT membership size.
    ``datasets``: optional (train, val, test) ``Dataset`` triple override.
    ``elastic_snapshot``: a ``MembershipSnapshot`` (from a previous run's
    ``results["elastic"]["snapshots"]``) to start from — the fresh-run
    twin of the in-process membership transition, executing the identical
    staging path (the ISSUE 8 bitwise-trajectory gate).  Skips the probe
    and initial partition; membership events at rounds <= the snapshot's
    epoch are already baked into its roster and are not replayed.
    """
    if (cfg.serve_prefix_cache or cfg.serve_prefill_chunk
            or cfg.serve_draft_ckpt or cfg.serve_spec_tokens):
        # the other --serve_* knobs are inert engine defaults a training
        # run can carry harmlessly; these are behavior switches of
        # the serving fast path and mean nothing to training — reject
        # instead of silently ignoring them
        raise ValueError(
            "--serve_prefix_cache/--serve_prefill_chunk/"
            "--serve_draft_ckpt/--serve_spec_tokens configure the "
            "serving fast path and only apply under `main.py serve` — "
            "the training driver never runs the serve engine; drop the "
            "flags from this run")
    initialize_distributed()
    from .xla_flags import (compile_cache_counts, install_cache_counter,
                            setup_compile_cache)
    if cfg.compile_cache_dir:
        # persistent XLA compilation cache, at the one directory
        # xla_flags.compile_cache_dir() names: repeated runs on one
        # machine stop paying the round-program compiles
        setup_compile_cache()
    # hit/miss telemetry even when the cache is off (counts then stay
    # zero); the per-run delta lands in results
    install_cache_counter()
    cache_counts0 = compile_cache_counts()
    # --- runtime sanitizer (ISSUE 6) -----------------------------------
    # --sanitize / JAX_GRAFT_SANITIZE=1: transfer guard around every
    # round dispatch/wait, a zero-retrace budget for rounds after the
    # warmup one, and donated-buffer deletion asserts.  All counters are
    # zero on a clean run and land in results["sanitize"] either way.
    sanitize = cfg.sanitize or (
        os.environ.get("JAX_GRAFT_SANITIZE", "").strip().lower()
        not in ("", "0", "false", "off", "no"))
    san: dict[str, Any] = {"enabled": sanitize,
                           "transfer_guard_violations": 0,
                           "retrace_count": 0, "recompile_count": 0,
                           "donation_failures": 0}
    san_warmup: dict | None = None
    if sanitize:
        from .xla_flags import compile_event_counts, install_compile_counter
        install_compile_counter()
    # --- scenario lab (ISSUE 14) ---------------------------------------
    # --sim_workers N simulates the whole worker axis as one vmap'd jit
    # on a single chip (sim.SimEngine); the orchestration loop below is
    # the SAME — probe, partition, straggler EMA, sanitizer, telemetry
    # all run per simulated worker.  Real-mesh-only features were
    # rejected at config time (chaos, slices, buddy, streaming, inner
    # axes, checkpoints); the two driver-level inputs that bypass config
    # are rejected here.
    sim_on = cfg.sim_workers > 0
    if sim_on:
        if elastic_snapshot is not None:
            raise ValueError(
                "elastic_snapshot cannot combine with --sim_workers: "
                "membership snapshots describe the REAL worker axis "
                "(mesh rebuilds, row-edited device state) — simulated "
                "membership scenarios are --sim_sample_frac / "
                "--sim_dropout")
        if jax.process_count() > 1:
            raise NotImplementedError(
                "--sim_workers is single-process by construction: the "
                "simulated worker axis lives on one chip (that is the "
                "point) — run multi-process fleets on the real driver")
    # --- elastic membership + chaos harness (ISSUE 8) ------------------
    # The chaos schedule is pure data keyed by absolute round index; the
    # straggler policy (retry/timeout/backoff around the round sync) is
    # armed exactly when chaos is — a clean production run must never
    # declare a worker departed because a CI host hiccuped.
    schedule = chaos_lib.ChaosSchedule.from_config(cfg)
    if elastic_snapshot is not None and schedule is not None:
        # the snapshot IS the post-event state: membership events at
        # rounds <= its epoch are baked into its roster and must not
        # replay (wall perturbations stay — slow factors persist from
        # their event round on, exactly as the continued run feels them).
        # A crash AT the snapshot epoch is baked in too: the recovery
        # snapshot is built at the crashed round's boundary with the
        # worker already removed, and the fresh twin re-runs that round
        # on the post-crash roster.
        schedule = chaos_lib.ChaosSchedule(
            [e for e in schedule.events
             if e.kind not in ("kill", "join", "crash")
             or e.round > elastic_snapshot.epoch])
    # ISSUE 12 arming: the crash-rollback machinery (per-round fenced
    # host snapshot, serial round settlement) and the NaN integrity
    # screen (a compiled-in sync program input) exist exactly when the
    # schedule can exercise them — a clean run's round loop is untouched
    crash_armed = schedule is not None and schedule.has_kind("crash")
    nan_armed = schedule is not None and schedule.has_kind("nan")
    policy = (chaos_lib.StragglerPolicy(
        cfg.time_limit, cfg.chaos_grace, cfg.chaos_retries,
        cfg.chaos_backoff) if schedule is not None else None)
    elastic_on = schedule is not None or elastic_snapshot is not None
    if cfg.num_slices > 1 and elastic_snapshot is not None:
        raise ValueError(
            "elastic_snapshot cannot combine with --num_slices > 1 in "
            "v1: membership snapshots describe the flat worker axis "
            "(--chaos is likewise rejected at config time) — per-slice "
            "membership is the ROADMAP follow-on")
    if mesh is None:
        if sim_on:
            # ONE anchor device hosts the whole simulated worker grid —
            # the remaining devices are deliberately unused (the
            # capability being demonstrated: N no longer costs devices)
            mesh = build_mesh({DATA_AXIS: 1}, devices=jax.devices()[:1])
        else:
            axes = cfg.mesh_axes()
            if cfg.num_workers:
                axes[DATA_AXIS] = cfg.num_workers
            if elastic_snapshot is not None:
                axes[DATA_AXIS] = elastic_snapshot.n_workers
            mesh = build_mesh(axes)
    elif sim_on and world_size(mesh) != 1:
        raise ValueError(
            f"--sim_workers runs the whole worker grid on ONE anchor "
            f"device; got a {world_size(mesh)}-worker mesh — pass no "
            "mesh (the driver builds the 1-device anchor) or a "
            "1-device data mesh")
    elif (elastic_snapshot is not None
          and mesh.shape[DATA_AXIS] != elastic_snapshot.n_workers):
        # the caller's mesh predates the membership change; rebuild the
        # data axis exactly as the in-process transition does
        mesh = resize_data_axis(mesh, elastic_snapshot.n_workers)
    if int(mesh.shape.get(SLICE_AXIS, 1)) != cfg.num_slices:
        raise ValueError(
            f"mesh slice axis ({int(mesh.shape.get(SLICE_AXIS, 1))}) "
            f"does not match --num_slices {cfg.num_slices}: the "
            "hierarchical sync resolution is config-driven — build the "
            "mesh from cfg.mesh_axes() (or pass none and let the driver)")
    # TOTAL worker count — slices x workers-per-slice on a hierarchical
    # mesh (ISSUE 13); every partition, pack, metric row, and RNG stream
    # below is per total worker, exactly as before at 1 slice.  In
    # simulated mode (ISSUE 14) the worker axis is --sim_workers wide
    # regardless of the 1-device anchor mesh — every per-worker
    # structure below (partitions, packs, probe vector, metric rows,
    # RNG streams) is per SIMULATED worker.
    n = cfg.sim_workers if sim_on else world_size(mesh)
    if jax.process_count() > 1 and n % jax.process_count():
        # validate once at setup: probe-duration and wall-time attribution
        # both need whole worker-row blocks per process (probe.py,
        # _measured_worker_walls) — fail here, before any training, rather
        # than inside the probe mid-run (advisor r3)
        raise ValueError(
            f"worker axis ({n}) must be divisible by the process count "
            f"({jax.process_count()}): per-process probe/wall attribution "
            "maps whole worker-row blocks to whole processes")
    if elastic_on and jax.process_count() > 1:
        raise NotImplementedError(
            "elastic membership / --chaos drives the simulated N-worker "
            "single-process driver; multi-process membership changes need "
            "a coordinated mesh rebuild across hosts (ROADMAP follow-on)")
    rng = np.random.default_rng(cfg.seed)
    # logical worker roster: initial workers are 0..N-1, joiners take the
    # next free ids for the life of the run (never recycled)
    worker_ids = (list(elastic_snapshot.worker_ids)
                  if elastic_snapshot is not None else list(range(n)))
    # the run's ROUND-0 worker count: a fresh twin inherits the original
    # run's (its own starting roster is the post-change one) so random
    # wall-fault pinning below — and the snapshots it builds — agree
    n_round0 = (elastic_snapshot.n_round0
                if elastic_snapshot is not None
                and elastic_snapshot.n_round0 else n)
    if schedule is not None:
        # covers --num_workers 0 (mesh-derived axis): from_config could
        # only pin random wall-fault targets when num_workers was
        # explicit; here the round-0 roster is known (idempotent —
        # explicit-num_workers runs were pinned identically already)
        schedule.pin_wall_targets(range(n_round0))
    plan = elastic_lib.MembershipPlan(
        n, min_workers=cfg.elastic_min_workers,
        max_workers=max_data_axis_size(mesh), worker_ids=worker_ids,
        next_id=(elastic_snapshot.next_worker_id
                 if elastic_snapshot is not None else None))
    n_start = n
    pending_departs: list = []   # straggler-protocol departures awaiting
    #                              the next round boundary
    quarantine_strikes: dict[int, int] = {}   # consecutive quarantined
    #                              rounds per logical worker (ISSUE 12)
    el: dict[str, Any] = {"enabled": elastic_on, "events": [],
                          "rejected": [], "sync_retries": [],
                          "reshard_ms": [], "rounds_degraded": 0,
                          "snapshots": [],
                          # unplanned-failure telemetry (ISSUE 12)
                          "crashes": 0, "recoveries": 0,
                          "recovery_source": [], "recovery_ms": [],
                          "quarantined_rounds": 0}

    # stamps between set-up's phases, which follow one another from here
    # to the probe's end (results["setup_timings"]), and the allocator's
    # reading at each (results["memory"]["hbm"]; nothing on a backend
    # that keeps no statistics)
    t_setup = [time.perf_counter()]
    hbm: dict[str, Any] = {"on_entry": {}, "phases": []}

    def read_hbm(row: dict) -> dict | None:
        """``spans.hbm`` over this process's chips of the mesh as it is
        now (an elastic boundary rebuilds it)."""
        return spans.hbm(row, [d for d in mesh.devices.flat
                               if d.process_index == jax.process_index()])

    def setup_stamp(phase: str) -> None:
        # the reading first: its host time belongs to the phase it closes
        # (a run without a checkpoint still reads restore_s 0.000)
        row = {"phase": phase}
        if read_hbm(row):
            hbm["phases"].append(row)
        t_setup.append(time.perf_counter())

    entry_stats = read_hbm(hbm["on_entry"])
    if entry_stats:
        hbm["limit_bytes"] = int(entry_stats.get("bytes_limit", 0))

    # --- data ---------------------------------------------------------
    if datasets is None:
        full_train, test = load_dataset(
            cfg.dataset, cfg.data_dir, cfg.seed,
            cfg.limit_train_samples, cfg.limit_eval_samples)
        trainset, valset = train_val_split(full_train, 0.2, cfg.seed)
    else:
        trainset, valset, test = datasets
    num_classes = trainset.num_classes
    batch = cfg.batch_size
    setup_stamp("data")

    # --- model + engine -------------------------------------------------
    train_model = None
    param_specs_fn = None
    base_kw: dict[str, Any] = {}   # shared by the dense + train models
    train_kw: dict[str, Any] = {}
    pp = int(mesh.shape.get(PIPE_AXIS, 1))
    if cfg.pp_remat and pp <= 1:
        raise ValueError(
            f"--pp_remat applies under pipeline parallelism (a '{PIPE_AXIS}' "
            "mesh axis of size >= 2); without one the flag would silently "
            "do nothing — use --remat_policy with --layer_scan instead")
    # --- layer-scan compile engine (ISSUE 3) ---------------------------
    # Resolve --layer_scan: stack the repeated block parameters along a
    # leading layer axis and run them under lax.scan, so the block
    # traces/compiles once regardless of depth.  Pipeline parallelism
    # REQUIRES the stacked structure (the 'pipe' axis shards the layer
    # dim); auto turns it on for every homogeneous-block family.
    from .models import supports_layer_scan
    if cfg.layer_scan == "on" and not supports_layer_scan(cfg.model):
        raise ValueError(
            f"--layer_scan on applies to homogeneous-block models "
            f"(bert_*/gpt_*/llama_*/vit_*); got --model {cfg.model} "
            "(heterogeneous CNN/MLP layers cannot stack)")
    if cfg.layer_scan == "off" and pp > 1:
        raise ValueError(
            f"--layer_scan off cannot combine with a '{PIPE_AXIS}' mesh "
            "axis: pipeline parallelism shards the stacked layer axis "
            "(scan-over-layers IS the pipeline's parameter layout)")
    layer_scan_on = (pp > 1 or cfg.layer_scan == "on"
                     or (cfg.layer_scan == "auto"
                         and supports_layer_scan(cfg.model)))
    if layer_scan_on:
        base_kw.update(scan_layers=True)
    # --remat_policy (the old remat bool, now a named jax.checkpoint
    # policy); --pp_remat is its "everything" compat alias
    remat_policy = cfg.remat_policy
    if cfg.pp_remat and remat_policy == "none":
        remat_policy = "everything"
    if remat_policy != "none" and not layer_scan_on:
        raise ValueError(
            f"--remat_policy {remat_policy} applies to the scanned layer "
            "stack (--layer_scan on/auto with a homogeneous-block model, "
            "or pipeline parallelism); this config runs unrolled")
    if remat_policy != "none":
        train_kw.update(remat_policy=remat_policy)
    if cfg.grad_accum > 1 and not is_attention_model(cfg.model):
        raise ValueError(
            f"--grad_accum applies to attention models (bert_*/gpt_*/"
            f"vit_*/llama_* — no BatchNorm running stats to split across "
            f"microbatches); got --model {cfg.model}")
    if cfg.pp_schedule == "1f1b":
        if pp <= 1:
            raise ValueError(
                f"--pp_schedule 1f1b applies under pipeline parallelism "
                f"(a '{PIPE_AXIS}' mesh axis of size >= 2)")
        if not cfg.model.startswith(("bert", "gpt", "llama", "vit")):
            raise NotImplementedError(
                "--pp_schedule 1f1b supports bert_*/gpt_*/llama_*/vit_* "
                "(the per-microbatch head+loss runs inside the schedule)")
        # r5: 1F1B composes with TP (vocab-parallel head in the
        # schedule's head slot), SP (masked fwd/bwd slots), FSDP
        # (ZeRO-3 gather outside the schedule), MoE/EP (stage aux
        # captured via mutable apply and differentiated through the
        # schedule with a weight-valued cotangent), and every model
        # family incl. ViT (embed/stage/head mode decomposition).
        # 1F1B x SP (r5): the schedule runs its fwd/bwd slots in
        # GPipe-style MASKED mode under SP (train.py passes
        # masked_slots) — a ppermute inside a pipe-varying lax.cond
        # miscomputes (parallel/pp.py r5 note; psum is exact, ppermute
        # is not), so the ring collectives must execute unconditionally.
        # The head slot needs no collective at all (local numerator over
        # the pre-psum'd global denominator, as in the standard SP
        # path).  The unpinned-CPU fail-fast below covers the rendezvous
        # race for any SP x PP combination, 1f1b included.
        # 1F1B x FSDP (r5): the ZeRO-3 shards gather OUTSIDE the
        # custom-VJP schedule (train.py _onef1b_loss_and_metrics), so
        # the schedule runs on full params and the reduce-scatter is the
        # gather's transpose downstream of the schedule's full grads —
        # no guard needed.
    if pp > 1:
        # pipeline parallelism (GPipe schedule, parallel/pp.py): the
        # stacked layer axis shards over 'pipe'; the dense twin must use
        # the same stacked parameter structure
        if not is_attention_model(cfg.model):
            raise ValueError(
                f"a '{PIPE_AXIS}' mesh axis (pipeline parallelism) applies "
                f"to attention models (bert_*/gpt_*/vit_*/llama_*); got --model {cfg.model}")
        mb_count = cfg.pp_microbatches or pp
        if cfg.batch_size % mb_count:
            # fail fast here, not with an opaque trace-time reshape error
            # inside the schedule (code-review r4)
            raise ValueError(
                f"--batch_size {cfg.batch_size} must be divisible by the "
                f"{mb_count} pipeline microbatches (--pp_microbatches, "
                f"0 => the '{PIPE_AXIS}' axis size {pp})")
        if cfg.sequence_parallel != "none":
            # SP x PP is supported, but on an UNPINNED CPU backend the
            # concurrency-optimized thunk executor can deadlock on the
            # seq-pair psums racing the pipe ppermutes — fail fast with
            # instructions instead of a 40 s hang + SIGABRT
            from .xla_flags import (SEQUENTIAL_CPU_COLLECTIVES_FLAG,
                                    sequential_cpu_collectives_pinned)
            if (jax.default_backend() == "cpu"
                    and not sequential_cpu_collectives_pinned()):
                raise RuntimeError(
                    "sequence parallelism x pipeline parallelism on the "
                    "CPU backend needs the sequential collective "
                    "scheduler pinned BEFORE jax initializes: set "
                    f"XLA_FLAGS={SEQUENTIAL_CPU_COLLECTIVES_FLAG} (the "
                    "CLI --device cpu, tests/conftest.py, and "
                    "__graft_entry__.py do this automatically)")
        from functools import partial
        from .parallel.pp import pp_param_specs
        base_kw.update(scan_layers=True)
        train_kw.update(pipeline_axis=PIPE_AXIS, pp_size=pp,
                        num_microbatches=cfg.pp_microbatches)
        param_specs_fn = partial(pp_param_specs, axis=PIPE_AXIS)
    if cfg.num_kv_heads > 0:
        # grouped-query attention (models/llama.py; the Llama-2/3 recipe)
        if not cfg.model.startswith("llama"):
            raise ValueError(
                f"--num_kv_heads applies to llama_* models; got --model "
                f"{cfg.model}")
        base_kw.update(num_kv_heads=cfg.num_kv_heads)
    ep = int(mesh.shape.get(EXPERT_AXIS, 1))
    tp = int(mesh.shape.get(MODEL_AXIS, 1))
    if cfg.num_experts > 0:
        # MoE FFN (models/moe.py); with an 'expert' mesh axis the stacked
        # expert weights shard over it (expert parallelism)
        if not is_attention_model(cfg.model):
            raise ValueError(
                f"--num_experts applies to attention models (bert_*/gpt_*/vit_*/llama_*); "
                f"got --model {cfg.model}")
        # MoE x SP (r5): each seq-parallel device routes its own chunk of
        # every sequence with per-chunk capacity — the same declared
        # semantics shift as FSDP x MoE above, golden-tested the same
        # way (MoE x SP x EP == MoE x SP exactly; EP shards only the
        # expert stacks).  The engine averages the per-chunk aux losses
        # over every batch-partial axis (train.py) so the seq-axis grad
        # psum recovers full-batch aux scale.
        base_kw.update(num_experts=cfg.num_experts,
                       capacity_factor=cfg.expert_capacity_factor)
        if ep > 1:
            train_kw.update(expert_axis=EXPERT_AXIS, ep_size=ep)
            if tp == 1:
                from functools import partial
                from .models.moe import ep_param_specs, pp_ep_param_specs
                if pp > 1:
                    # MoE x PP x EP: the stacked layer axis shards over
                    # 'pipe' AND the expert stacks (dim 1 behind the layer
                    # dim) over 'expert'
                    param_specs_fn = partial(pp_ep_param_specs,
                                             pipe_axis=PIPE_AXIS,
                                             axis=EXPERT_AXIS)
                else:
                    param_specs_fn = partial(ep_param_specs,
                                             axis=EXPERT_AXIS)
            # tp > 1: the TP block below builds the moe-aware Megatron
            # specs and the expert overlay is applied after it
    elif ep > 1:
        raise ValueError(
            f"mesh has an '{EXPERT_AXIS}' axis but --num_experts is 0")
    model = build_model_for(cfg, num_classes, **base_kw)
    if tp > 1:
        # tensor parallelism (Megatron construction, parallel/tp.py):
        # attention heads + FFN hidden sharded over the 'model' axis; the
        # dense model (init/probe/final-eval) has the identical parameter
        # structure, physically sharded per tp_param_specs
        if not is_attention_model(cfg.model):
            raise ValueError(
                f"a '{MODEL_AXIS}' mesh axis (tensor parallelism) applies "
                f"to attention models (bert_*/gpt_*/vit_*/llama_*); got --model {cfg.model}")
        from functools import partial
        from .models.bert import pp_tp_param_specs, tp_param_specs
        train_kw.update(tp_size=tp, model_axis=MODEL_AXIS)
        # GPT's TIED head: sharding its embedding table's vocab dim makes
        # both the lookup (masked psum) and the decode (local logits
        # slice) vocab-parallel — models/gpt.py _embed
        tok = dict(shard_tok_emb=cfg.model.startswith("gpt"))
        if pp > 1:
            # 2-D composition: the stacked layer axis shards over 'pipe'
            # AND the inner Megatron dims over 'model' (the dense twin
            # keeps the same stacked structure via scan_layers)
            param_specs_fn = partial(pp_tp_param_specs,
                                     pipe_axis=PIPE_AXIS, axis=MODEL_AXIS,
                                     **tok)
        else:
            param_specs_fn = partial(tp_param_specs, axis=MODEL_AXIS,
                                     **tok)
        if ep > 1:
            # MoE x TP (x PP): the Megatron pattern covered the per-expert
            # F dims; the overlay shards the expert dim over 'expert'
            from .models.moe import with_expert_overlay
            param_specs_fn = with_expert_overlay(param_specs_fn,
                                                 axis=EXPERT_AXIS)
    from .mesh import FSDP_AXIS
    fsdp = int(mesh.shape.get(FSDP_AXIS, 1))
    if fsdp > 1:
        # ZeRO-3 / FSDP (parallel/fsdp.py): params + Adam moments sharded
        # over 'fsdp', each worker's batch split over it, params
        # all-gathered per step (gradients reduce-scattered by autodiff).
        # Works for every model family — the model code never sees shards —
        # and composes with tensor parallelism (2-D (fsdp, model) sharding:
        # ZeRO-3 claims a free dim of each TP-sharded leaf) and with
        # sequence parallelism (B over fsdp, L over seq).
        # MoE x FSDP (r5): the worker batch splits over 'fsdp', so each
        # slice routes its own tokens with per-slice capacity — the same
        # semantics shift as per-microbatch routing under GPipe, and like
        # that row it is golden-tested against the twin that SHARES the
        # slicing (fsdp x ep == fsdp x unsharded-MoE exactly; EP shards
        # only the expert stacks).  The aux-loss scaling is handled in the
        # engine: the per-slice sown losses are averaged over 'fsdp'
        # (train.py), so the gradient psum recovers full-batch scale
        # instead of multiplying it by the axis size.
        if cfg.batch_size % fsdp:
            raise ValueError(
                f"--batch_size {cfg.batch_size} must be divisible by the "
                f"'{FSDP_AXIS}' axis size {fsdp} (the batch splits over it)")
        if pp > 1 and (cfg.pp_microbatches or pp) > 1:
            mb = cfg.pp_microbatches or pp
            if (cfg.batch_size // fsdp) % mb:
                raise ValueError(
                    f"per-fsdp-slice batch {cfg.batch_size // fsdp} must "
                    f"be divisible by {mb} pipeline microbatches")
        from .parallel.fsdp import add_fsdp_axis, fsdp_param_specs
        if param_specs_fn is not None:
            # composition (TP and/or PP specs already chosen): extend with
            # fsdp sharding on a FREE dim of each large leaf — ZeRO-3
            # inside Megatron TP (2-D) and/or the GPipe stack (layer dim
            # stays on 'pipe', fsdp claims another dim)
            base_specs_fn = param_specs_fn

            def param_specs_fn(params):
                return add_fsdp_axis(base_specs_fn(params), params,
                                     axis=FSDP_AXIS, axis_size=fsdp)
        else:
            from functools import partial
            param_specs_fn = partial(fsdp_param_specs, axis=FSDP_AXIS,
                                     axis_size=fsdp)
    if cfg.grad_accum > 1:
        # the engine splits the per-DEVICE batch (after any fsdp split)
        # into grad_accum slices; each slice must still feed the GPipe
        # microbatch reshape when PP is on — fail fast here, not with an
        # opaque trace-time reshape error
        per_dev = cfg.batch_size // max(fsdp, 1)
        if per_dev % cfg.grad_accum:
            raise ValueError(
                f"per-device batch {per_dev} (batch_size {cfg.batch_size}"
                f"{f' / fsdp {fsdp}' if fsdp > 1 else ''}) must be "
                f"divisible by --grad_accum {cfg.grad_accum}")
        if pp > 1 and (per_dev // cfg.grad_accum) % (cfg.pp_microbatches
                                                     or pp):
            raise ValueError(
                f"per-accumulation-slice batch {per_dev // cfg.grad_accum} "
                f"must be divisible by {cfg.pp_microbatches or pp} "
                "pipeline microbatches")
    if cfg.sequence_parallel != "none":
        if cfg.attention_impl != "dense":
            raise ValueError(
                f"--attention_impl {cfg.attention_impl} cannot combine with "
                f"--sequence_parallel {cfg.sequence_parallel}: the round "
                "program's attention is the sequence-parallel kernel")
        from .mesh import SEQ_AXIS
        if SEQ_AXIS not in mesh.shape or mesh.shape[SEQ_AXIS] < 2:
            raise ValueError(
                f"--sequence_parallel {cfg.sequence_parallel} needs a "
                f"'{SEQ_AXIS}' mesh axis of size >= 2 (e.g. --mesh_shape "
                f"data=2,seq=4); got mesh {dict(mesh.shape)}")
        if not is_token_model(cfg.model):
            raise ValueError(
                "--sequence_parallel applies to token-sequence models "
                f"(bert_*/gpt_*/llama_*); got --model {cfg.model}")
        if (cfg.sequence_parallel == "ring_zigzag"
                and not cfg.model.startswith(("gpt", "llama"))):
            raise ValueError(
                "--sequence_parallel ring_zigzag balances CAUSAL masking "
                "work and applies to causal models (gpt_*/llama_*); "
                f"got --model {cfg.model} — use 'ring' for bidirectional "
                "attention")
        # the round program runs ring / all-to-all attention over the seq
        # axis; init/probe/final-eval keep the dense twin (same params)
        train_kw.update(attention_impl=cfg.sequence_parallel,
                        axis_name=SEQ_AXIS)
    elif cfg.attention_impl != "dense":
        if not is_attention_model(cfg.model):
            raise ValueError(
                "--attention_impl applies to attention models "
                f"(bert_*/gpt_*/vit_*/llama_*); got --model {cfg.model}")
        train_kw.update(attention_impl=cfg.attention_impl)
    if train_kw:
        train_model = build_model_for(cfg, num_classes, **base_kw, **train_kw)
    # the model a call outside the round program applies (the probe, the
    # caller's final evaluation): the one that trains, with its attention
    # implementation, window and remat policy, unless that one is bound to
    # mesh axes and runs only inside shard_map; then the dense twin
    host_model = (train_model if train_model is not None and set(train_kw)
                  <= {"attention_impl", "remat_policy"} else model)
    if cfg.sync_staleness > 0 and jax.default_backend() == "cpu":
        # semi-synchronous rounds keep a standalone sync program running
        # CONCURRENTLY with the next round program — on an unpinned
        # XLA:CPU backend the concurrency-optimized thunk executor can
        # join the two programs' collectives in different per-device
        # orders and deadlock (the SP x PP hazard, same mechanism) —
        # fail fast with instructions instead of a 40 s hang + SIGABRT
        from .xla_flags import (SEQUENTIAL_CPU_COLLECTIVES_FLAG,
                                sequential_cpu_collectives_pinned)
        if not sequential_cpu_collectives_pinned():
            raise RuntimeError(
                "--sync_staleness on the CPU backend needs the "
                "sequential collective scheduler pinned BEFORE jax "
                "initializes: set "
                f"XLA_FLAGS={SEQUENTIAL_CPU_COLLECTIVES_FLAG} (the CLI "
                "--device cpu, tests/conftest.py, and "
                "__graft_entry__.py do this automatically)")
    if sim_on:
        # param_specs_fn / nan_screen are real-mesh machinery (inner
        # axes and --chaos were both rejected at config time)
        from .sim import SimEngine
        engine = SimEngine(model, mesh, cfg, train_model=train_model)
    else:
        engine = LocalSGDEngine(model, mesh, cfg, train_model=train_model,
                                param_specs_fn=param_specs_fn,
                                nan_screen=nan_armed)
    # the engine resolution is per topology (Config.resolve_sync_mode):
    # bucketed reduce-scatter for allreduce, bucketed ppermute gossip for
    # ring/double_ring, legacy per-leaf dense otherwise — surfaced here
    # (and as results["sync_engine"]) so a run artifact states which sync
    # program produced it
    log.info("round-sync engine: %s (topology=%s, wire=%s/%s, "
             "num_slices=%d, param_residency=%s)",
             engine.sync_mode, cfg.topology, cfg.sync_dtype,
             cfg.sync_dtype_outer or cfg.sync_dtype, cfg.num_slices,
             engine.param_residency)
    sample = trainset.images[:batch]
    if elastic_snapshot is None:
        state = engine.init_state(jax.random.key(cfg.seed), sample)
    else:
        # fresh run from a membership snapshot: the IDENTICAL staging the
        # in-process continuation performs (elastic.py module docstring —
        # the shared path is what makes the bitwise gate mechanical).
        # The snapshot carries the per-worker params template a resident
        # state cannot self-describe (its bucket rows carry no leaf
        # shapes)
        if elastic_snapshot.params_template is not None:
            engine.params_template = elastic_snapshot.params_template
        state = engine.stage_state(elastic_snapshot.host_state)
    setup_stamp("engine")

    # --- checkpoint engine + resume (beyond-reference; off when no dir) --
    # Opening the engine sweeps stale mid-write leftovers (.tmp files,
    # unmanifested ckpt_<E>/ dirs) BEFORE the resume decision, so a crash
    # during the previous run's save can never be restored from.
    ckpt_engine = None
    if cfg.checkpoint_dir:
        ckpt_engine = ckpt_lib.CheckpointEngine(
            cfg.checkpoint_dir, keep=cfg.ckpt_keep,
            async_write=cfg.ckpt_async,
            metadata=checkpoint_metadata(
                cfg, num_classes, layer_scan_on,
                param_residency=engine.param_residency,
                params_template=engine.params_template))
    start_epoch = 0
    if ckpt_engine is not None and cfg.resume:
        if elastic_snapshot is not None:
            raise ValueError(
                "--resume and elastic_snapshot are mutually exclusive: a "
                "membership snapshot already fixes the starting state")
        latest = ckpt_engine.latest_checkpoint()
        if latest and schedule is not None:
            # checkpoint resume REPLAYS the deterministic chaos schedule
            # from the checkpoint epoch (the crash-during-reshard recovery
            # path: an event AT the resume boundary re-applies).  A
            # membership event at an EARLIER round means the checkpoint
            # was written on a post-change roster the restore template
            # cannot represent — refuse with the real reason BEFORE the
            # restore turns it into a shape-mismatch traceback.
            # epoch from the already-resolved latest path (ckpt_<E> /
            # ckpt_<E>.msgpack) — committed_epochs would re-read and
            # re-crc every shard of every kept epoch a third time
            resume_epoch = int(os.path.basename(latest)
                               .removesuffix(".msgpack")
                               .rsplit("_", 1)[1])
            past = [e.describe() for e in schedule.events
                    if e.kind in ("kill", "join", "crash")
                    and e.round < resume_epoch]
            if past:
                raise ValueError(
                    f"cannot resume at epoch {resume_epoch} across "
                    f"earlier membership events {past}: checkpoint resume "
                    "replays --chaos from the resume epoch, so membership "
                    "events must land at rounds >= it")
            # the schedule scan can't see STRAGGLER-protocol departures
            # (implicit kills that never appear in --chaos); the manifest
            # records the worker axis the checkpoint was written with, so
            # a departure-shrunk checkpoint is refused with the real
            # reason instead of restore's opaque shape mismatch
            axis = (ckpt_lib.manifest_worker_axis(latest)
                    if os.path.isdir(latest) else None)
            if axis is not None and axis != n:
                raise ValueError(
                    f"cannot resume: checkpoint {latest} was written "
                    f"with {axis} worker(s) but this run starts with "
                    f"{n} — a membership change (straggler departure "
                    "or kill/join) happened before it was saved; "
                    "restart fresh or resume a pre-change epoch")
        if latest:
            # buddy rows are derived state: restore strips them from
            # the template itself (checkpoint._strip_buddy — the one
            # place that owns the invariant); re-derive + restage after
            state, start_epoch = ckpt_lib.restore_checkpoint(
                latest, state, params_template=engine.params_template,
                bucket_bytes=engine.sync_bucket_bytes,
                num_slices=engine.n_slices)
            state = engine.refresh_buddy(state)
            log.info("resumed from %s at global epoch %d", latest, start_epoch)
    setup_stamp("restore")

    # --- probe -> ratios -> initial partition ---------------------------
    if elastic_snapshot is None:
        init_vars = engine.rank0_variables(state)
        durations, sec_per_batch = probe_lib.estimate_epoch_duration(
            host_model, init_vars, sample, n, cfg.probe_batches,
            simulated_durations)
        ratios = efficiency_ratios(durations, cfg.proportionality)
        log.info("probe durations %s -> ratios %s", durations, ratios)

        # the SAME recipe (and rng draw order: train before val, workers
        # in order) elastic.build_snapshot re-draws at a membership
        # boundary — one implementation, so the fresh-run-vs-snapshot
        # bitwise gate can never drift out from under an edit here
        fixed_classes = ([fixed_classes_for_rank(r, num_classes)
                          for r in range(n)]
                         if cfg.data_mode == "disbalanced" else None)
        train_parts = adaptive_partition(
            len(trainset), ratios, labels=trainset.labels,
            fixed_classes=fixed_classes, fixed_ratio=cfg.fixed_ratio,
            rng=rng)
        val_parts = adaptive_partition(
            len(valset), ratios, labels=valset.labels,
            fixed_classes=fixed_classes, fixed_ratio=cfg.fixed_ratio,
            rng=rng)
    else:
        # the snapshot carries the post-event heterogeneity EMA, the
        # re-drawn partitions, and the RNG stream position — no probe, no
        # initial partition, no extra draws (bitwise-gate requirement)
        start_epoch = int(elastic_snapshot.epoch)
        sec_per_batch = np.asarray(elastic_snapshot.sec_per_batch,
                                   np.float64).copy()
        train_parts = [np.asarray(p).copy()
                       for p in elastic_snapshot.train_parts]
        val_parts = [np.asarray(p).copy()
                     for p in elastic_snapshot.val_parts]
        fixed_classes = copy.deepcopy(elastic_snapshot.fixed_classes)
        rng.bit_generator.state = copy.deepcopy(elastic_snapshot.rng_state)
        log.info("continuing from membership snapshot: round %d, "
                 "workers %s", start_epoch, worker_ids)
    setup_stamp("probe")

    # --- reference metric structures (trainer.py:13-25) -----------------
    results: dict[str, Any] = {
        # keyed by LOGICAL worker id (== mesh row until the first elastic
        # membership change; a snapshot run's roster may have gaps)
        "all_workers_losses": [[] for _ in range(max(worker_ids) + 1)],
        "all_epochs_losses": [],
        "global_epoch_losses": [],
        "global_epoch_accuracies": [],
        "global_train_losses": [],
        "global_train_accuracies": [],
        "global_val_losses": [],
        "global_val_accuracies": [],
        "worker_specific_train_losses": [],
        "worker_specific_train_accuracies": [],
        "worker_specific_val_losses": [],
        "worker_specific_val_accuracies": [],
        # the sync/optimizer engine provenance of this run artifact
        # (ISSUE 9 satellite): which sync program ran, where the
        # round-boundary optimizer apply was placed, and each state
        # component's measured per-worker resident bytes — so the
        # sharded placement's N-fold round_opt drop is a recorded
        # number, not a claim ("mode" keeps the pre-ISSUE-9 string)
        "sync_engine": {
            "mode": engine.sync_mode,
            # per-LEVEL resolution (ISSUE 13): inner = the ICI engine,
            # outer = the DCN engine (None on flat runs) — plus the
            # static per-round wire-byte split, filled after the first
            # round arms the accounting (zeros when no round ran).
            # Simulated runs (ISSUE 14) report the one "sim" level: the
            # whole fabric is stacked math on one chip.
            "levels": ({"inner": "sim", "outer": None} if sim_on
                       else cfg.resolve_sync_levels(
                           jax.default_backend())),
            "num_slices": engine.n_slices,
            "sync_bytes_ici": 0,
            "sync_bytes_dcn": 0,
            "opt_placement": engine.opt_placement,
            # the ENGINE-resolved residency (ISSUE 11): the config
            # resolution plus the inner-axes / 1-worker demotions — what
            # the round programs actually ran with
            "param_residency": engine.param_residency,
            "per_worker_state_bytes": engine.state_resident_bytes(state),
        },
    }

    def _capped(parts, caps):
        sizes = [len(p) for p in parts]
        if caps is not None:
            sizes = [min(s, c * batch) for s, c in zip(sizes, caps)]
        idxs = [p if caps is None else p[:caps[i] * batch]
                for i, p in enumerate(parts)]
        return idxs, sizes

    # Double-buffered host staging for the packed path (ROADMAP overlap
    # follow-on (c)): pack_all gathers straight into a two-deep rotation of
    # reusable [N, S, B, ...] stacks via np.take(..., out=...) instead of
    # allocating fresh ones every round.  A buffer handed out for round r
    # returns at round r+2, after round r's host->device transfer is done.
    pack_pool = PackBufferPool()

    def pack_all(ds, idxs, kind: str, steps: int):
        xs = pack_pool.take((kind, "x"),
                            (n, steps, batch, *ds.images.shape[1:]),
                            ds.images.dtype)
        ys = pack_pool.take((kind, "y"),
                            (n, steps, batch, *ds.labels.shape[1:]),
                            ds.labels.dtype)
        ms = pack_pool.take((kind, "m"), (n, steps, batch), np.float32)
        for i, p in enumerate(idxs):
            pack_window(ds.images, ds.labels, p, batch, 0, steps,
                        out=(xs[i], ys[i], ms[i]))
        return xs, ys, ms

    def chunk_feed(ds, idxs, steps: int):
        """Streamed alternative to pack_all: a per-epoch iterator of
        fixed-shape [N, chunk, B, ...] windows (only one window is ever
        materialized on the host; VERDICT r1 'Next' #7)."""
        return window_feed(ds.images, ds.labels, idxs, batch,
                           cfg.stream_chunk_steps, steps)

    def pack_steps(tsizes, vsizes) -> tuple[int, int]:
        """Steps of a round's train and validation packs.  Streamed: each
        need rounded up to the chunk.  Packed: each need's bucket, unless
        that pair has no round program yet and a built one holds the rows
        in packs no longer than a granule of four steps gives (the one
        rule before the buckets): a new shape is a trace and a compile
        (none is allowed after a sanitized run's warm-up), so shards that
        repartition moves below a built program's packs run in it, and
        never in more masked steps than the granule left.  Of several,
        the fewest train steps: a train step costs more than a
        validation step."""
        need = (step_budget(tsizes, batch), step_budget(vsizes, batch))
        if streaming:
            return tuple(_round_up(k, cfg.stream_chunk_steps) for k in need)
        bucket = tuple(_pack_steps(k) for k in need)
        if bucket in engine.built_packs:
            return bucket
        return min((s for s in engine.built_packs
                    if all(k <= p <= _round_up(k, 4)
                           for k, p in zip(need, s))),
                   default=bucket)

    def empty_steps(sizes, steps: int) -> int:
        """Steps of a pack that carry no row, summed over the workers:
        forward passes (and, in a train pack, steps) the round program
        computes on masked rows alone."""
        return sum(steps - int(np.ceil(s / batch)) for s in sizes)

    # --- optional profiler trace (beyond-reference, SURVEY.md section 5) --
    profiling = False
    if cfg.profile_dir:
        # a requested trace that cannot start is an error, not a warning:
        # a run that was asked to be measured must not finish unmeasured
        jax.profiler.start_trace(cfg.profile_dir)
        profiling = True

    # --- the overlapped round pipeline ----------------------------------
    # Every round is dispatched asynchronously; the metric fetch + assembly
    # run on a worker thread and the next round's re-partition + packing
    # run on the main thread, all WHILE the device computes the current
    # round (cfg.overlap_rounds; serial mode runs the identical data flow
    # inline).  The one semantic consequence is made explicit: the
    # straggler-feedback EMA consumes MEASURED WALLS ONE ROUND DELAYED —
    # round r+1's partition must be packed while round r is still running,
    # so the freshest wall it can consume is round r-1's.  Serial mode
    # uses the same delayed consumption, making overlapped and serial runs
    # produce bit-identical results.
    results["step_caps"] = []
    results["shard_sizes"] = []      # per-round per-worker train-shard sizes
    # one row a round: the spans' durations and the two absolute stamps
    # (docs/ARCHITECTURE.md, "Spans of a round")
    results["round_timings"] = []
    setup_timings = results["setup_timings"] = {
        key: round(t1 - t0, 3) for key, t0, t1 in zip(
            ("data_s", "engine_s", "restore_s", "probe_s"),
            t_setup, t_setup[1:])}
    epoch_iter = range(start_epoch, cfg.epochs_global)
    pbar = None
    if progress and jax.process_index() == 0:
        try:  # the reference's global-epoch bar (trainer.py:27,174)
            from tqdm import tqdm
            pbar = tqdm(epoch_iter, desc="Global Epochs",
                        initial=start_epoch, total=cfg.epochs_global)
            epoch_iter = pbar
        except ImportError:
            pass
    # Multi-host: the metric fetch is a COLLECTIVE (process_allgather);
    # running it on a worker thread would interleave with the main
    # thread's collectives (walls exchange, the checkpoint commit
    # barrier, the next round itself) in different per-process orders —
    # a rendezvous hazard.  (The checkpoint engine keeps its own
    # collective on the main thread for the same reason: the background
    # writer only does local file I/O.)  Overlap therefore applies
    # single-process only; multi-host keeps the serial data flow
    # (identical results either way).
    # Unplanned-failure arming forces the SERIAL flow (ISSUE 12): a
    # crash verdict voids the whole round — its metrics must not have
    # been assembled by a worker thread before the verdict lands — and
    # the NaN quarantine escalation consumes each round's validity flags
    # before the next boundary.  Serial vs overlapped is result-identical
    # anyway; a chaos harness trades the gap for rollback simplicity.
    overlap = (cfg.overlap_rounds and jax.process_count() == 1
               and not (crash_armed or nan_armed))
    streaming = cfg.stream_chunk_steps > 0
    # ROADMAP overlap follow-on (a): the pre-dispatch state barrier exists
    # for the 1-core XLA:CPU collective rendezvous (a second in-flight
    # round program can starve it past its deadline -> SIGABRT).  On real
    # accelerators collectives execute in stream order, so the overlapped
    # pipeline may keep TWO rounds in flight: round r+1 is dispatched
    # before round r completes, and the host blocks only on round r-1's
    # completion marker (never the state itself — its buffers are donated
    # into the next round the moment it is dispatched).  Checkpoint rounds
    # and the final round still barrier (the save reads the state).
    # Sanitize mode forces the barrier path: the deep pipeline defers a
    # round's completion past its loop iteration, which would leave that
    # round's wait outside the transfer guard and its donated buffers
    # unchecked — the sanitizer's contract is every-round coverage, and
    # it is a debugging harness, so determinism beats overlap here.
    # Chaos runs also force the barrier path: a membership boundary must
    # find the previous round fully settled (its wall recorded, so the
    # straggler verdict and the EMA the snapshot captures are final)
    # before the state is snapshotted and the mesh rebuilt.
    # Staleness (ISSUE 16) keeps its own in-flight chain — up to K sync
    # programs under the round's compute, tracked engine-side — and its
    # handles carry no sync fence for the deep pipeline's deferred-round
    # marker bookkeeping to ride; the two overlap disciplines do not
    # compose in v1, and staleness is the stronger one (it hides the
    # sync wall, the deep pipeline's whole win on these meshes).
    deep_pipeline = (overlap and not streaming
                     and jax.default_backend() != "cpu"
                     and not sanitize and schedule is None
                     and cfg.sync_staleness == 0)

    def build_inputs(tidxs, vidxs, steps, row, ids):
        if streaming:
            return (chunk_feed(trainset, tidxs, steps[0]),
                    chunk_feed(valset, vidxs, steps[1]))
        # pack AND stage onto device at prep time: in the overlapped
        # pipeline this runs while the previous round computes, so the
        # host->device transfer rides under device time too (h2d_ms is
        # the host's time in device_put, not the copy's)
        with span("round.prep.pack", row, "pack_ms", **ids):
            packs = (pack_all(trainset, tidxs, "train", steps[0]),
                     pack_all(valset, vidxs, "val", steps[1]))
        with span("round.prep.h2d", row, "h2d_ms", **ids):
            return engine.stage_pack(*packs)

    def make_prep(tparts, vparts, row=None, **ids):
        """Caps + packed/staged inputs for the round about to run, from
        the CURRENT sec_per_batch estimate (straggler protocol: per-worker
        step cap from the probe-seeded, measured-wall-updated EMA and the
        time_limit grace budget).  ``row`` takes the pack and
        host->device spans."""
        caps = [budget_from_time_limit(
            int(np.ceil(len(p) / batch)), float(sec_per_batch[i]),
            cfg.time_limit) for i, p in enumerate(tparts)]
        steps_run = np.array([
            min(int(np.ceil(len(p) / batch)), caps[i])
            for i, p in enumerate(tparts)], np.float64)
        (tidxs, tsizes), (vidxs, vsizes) = (_capped(tparts, caps),
                                            _capped(vparts, None))
        steps = pack_steps(tsizes, vsizes)
        return dict(caps=caps, steps_run=steps_run,
                    sizes=[len(p) for p in tparts],
                    pad_steps=(empty_steps(tsizes, steps[0])
                               + empty_steps(vsizes, steps[1])),
                    inputs=build_inputs(tidxs, vidxs, steps, row, ids))

    walls_by_round: dict[int, tuple[np.ndarray, np.ndarray]] = {}
    next_wall_box = [start_epoch]  # next round whose wall the EMA consumes

    def consume_walls(upto: int):
        """Blend measured (wall, steps) feedback for rounds < ``upto``
        into the sec/batch EMA, exactly once each, in round order."""
        nonlocal sec_per_batch
        while next_wall_box[0] < upto and next_wall_box[0] in walls_by_round:
            ww, steps = walls_by_round.pop(next_wall_box[0])
            measured_spb = ww / np.maximum(steps, 1.0)
            sec_per_batch = 0.5 * sec_per_batch + 0.5 * measured_spb
            next_wall_box[0] += 1

    def prepare_next(cur_epoch: int, cur_steps_run: np.ndarray, row: dict):
        """Re-partition + pack round ``cur_epoch + 1``, as span
        ``round.prep`` (``prep_ms``) of round ``cur_epoch``'s row: the
        round this work runs beside.

        Runs while round ``cur_epoch`` may still be computing, so the
        straggler feedback (trainer.py:112-119, 179-188 semantics)
        consumes measured walls only through round ``cur_epoch - 1`` —
        the one-round-delayed EMA.  The per-worker round durations are
        modeled as (EMA sec/batch)_i x (steps run)_i of the CURRENT round
        (host-known at dispatch time): at equilibrium the products
        equalize, i.e. shard sizes settle inversely proportional to
        measured speed, one round later than the fully-serial reference."""
        with span("round.prep", row, "prep_ms", round=cur_epoch):
            with span("round.prep.partition", round=cur_epoch):
                _repartition(cur_epoch, cur_steps_run)
            return make_prep(train_parts, val_parts, row, round=cur_epoch)

    def _repartition(cur_epoch: int, cur_steps_run: np.ndarray):
        nonlocal train_parts, val_parts
        consume_walls(upto=cur_epoch)
        round_durations = sec_per_batch * np.maximum(cur_steps_run, 1.0)
        new_ratios = efficiency_ratios(round_durations, cfg.proportionality)
        replace = cfg.data_mode == "disbalanced"
        train_parts = [
            repartition(len(trainset), train_parts[i], new_ratios[i],
                        cfg.prev_fraction, cfg.next_fraction, rng,
                        replace=replace)
            for i in range(n)]
        val_parts = [
            repartition(len(valset), val_parts[i], new_ratios[i],
                        cfg.prev_fraction, cfg.next_fraction, rng,
                        replace=replace)
            for i in range(n)]
        if cfg.data_mode == "disbalanced":
            train_parts = [
                skew_repartition(trainset.labels, p, fixed_classes[i],
                                 cfg.fixed_ratio, rng)
                for i, p in enumerate(train_parts)]
            val_parts = [
                skew_repartition(valset.labels, p, fixed_classes[i],
                                 cfg.fixed_ratio, rng)
                for i, p in enumerate(val_parts)]

    def report_progress(mx, global_epoch: int, wall: float, wids):
        if not (progress and jax.process_index() == 0):
            return
        # the reference's per-rank per-local-epoch report lines
        # (trainer.py:109-110); all worker ranks share this process's
        # stdout, so every rank's lines appear here.  tqdm.write keeps
        # the live bar from garbling them.  In the overlapped pipeline
        # this runs on the metric worker thread (tqdm locks internally).
        say = pbar.write if pbar is not None else print
        epochs_local = np.asarray(mx["train_loss"]).shape[1]
        for r, wid in enumerate(wids):
            for e in range(epochs_local):
                say(f"Rank {wid}, Global Epoch {global_epoch + 1}, "
                    f"Local Epoch {e + 1}, "
                    f"Loss: {mx['train_loss'][r, e]}, "
                    f"Accuracy: {mx['train_acc'][r, e]}")
                say(f"Worker {wid}, Global Epoch {global_epoch + 1}, "
                    f"Validation Loss: {mx['val_loss'][r, e]:.4f}, "
                    f"Validation Accuracy: {mx['val_acc'][r, e]:.2f}%")
        if pbar is not None:  # trainer.py:174 postfix
            pbar.set_postfix(
                loss=results["global_train_losses"][-1],
                accuracy=results["global_train_accuracies"][-1],
                wall=f"{wall:.1f}s")
        else:
            print(f"Global Epoch {global_epoch + 1}/{cfg.epochs_global}: "
                  f"loss={results['global_train_losses'][-1]:.4f} "
                  f"acc={results['global_train_accuracies'][-1]:.2f}% "
                  f"val_loss={results['global_val_losses'][-1]:.4f} "
                  f"val_acc={results['global_val_accuracies'][-1]:.2f}% "
                  f"({wall:.1f}s)")

    def metrics_job(handle, global_epoch: int, t_dispatch: float,
                    timing: dict, wids):
        """Fetch + vectorized assembly of one round's metrics; the
        overlapped pipeline runs this on the worker thread while the next
        round computes (in that mode fetch_ms includes the tail of the
        round's own device time — it is hidden wall, not host gap).
        ``wids`` is the round's OWN membership roster, captured at
        dispatch: a membership change at the next boundary must not
        re-map this round's rows."""
        with span("round.fetch", timing, "fetch_ms", round=global_epoch):
            mx = engine.finish_metrics(handle)
        with span("round.assemble", timing, "assemble_ms",
                  round=global_epoch):
            _assemble_round_metrics(results, mx, wids)
        for name, per_step in (mx.get("counters") or {}).items():
            # what the model sowed (train.py, _loss_and_metrics), [N, E, S]:
            # the round's mean over workers, local epochs and steps
            timing[name] = float(np.mean(per_step))
        report_progress(mx, global_epoch, time.perf_counter() - t_dispatch,
                        wids)
        return mx

    executor = (ThreadPoolExecutor(max_workers=1,
                                   thread_name_prefix="round-metrics")
                if overlap else None)
    pending: list = []
    # no pack/stage when no rounds will run (e.g. resuming a finished run)
    prep = None
    setup_timings["first_prep_s"] = 0.0
    if start_epoch < cfg.epochs_global:
        with span("setup.first_prep", setup_timings, "first_prep_s"):
            prep = make_prep(train_parts, val_parts)
    setup_stamp("first_prep")
    t_ready = None
    # deep pipeline only: the round whose completion barrier was deferred
    inflight: list = []            # [(epoch, markers, t_disp, timing, steps)]
    # completion time of the previously settled round: with two rounds in
    # flight, a round's device time runs from max(its dispatch, the
    # previous round's completion) — measuring from dispatch alone would
    # double-count (the marker only completes after the previous round's
    # remaining compute), inflating the EMA and halving step caps
    t_done_prev: list = [None]

    def record_walls(ep: int, wall: float, steps_run, timing_: dict
                     ) -> list[int]:
        """Record one round's walls; returns the CRASHED logical ids
        (non-empty voids the round — the caller rolls back instead of
        recording anything, ISSUE 12)."""
        timing_["compute_ms"] = round(wall * 1e3, 3)
        # record the measured wall for DELAYED consumption: the EMA
        # blends it in when round ep + 2 is being prepared
        if simulated_round_durations is not None:
            worker_walls = np.asarray(
                simulated_round_durations(ep), np.float64)
            if worker_walls.shape != (n,):
                # ELASTIC runs only: a LOGICAL-id-indexed vector
                # (covering every id ever live) also works — a crash
                # re-runs its round on the shrunk roster, so one
                # epoch-keyed callable must serve two membership sizes
                # (tests index by stable logical ids).  Fixed-membership
                # runs keep the strict shape error: there a mis-sized
                # vector is a harness bug, not a roster mismatch.
                if (elastic_on and worker_walls.ndim == 1 and worker_ids
                        and len(worker_walls) > max(worker_ids)):
                    worker_walls = worker_walls[worker_ids]
                else:
                    raise ValueError(
                        f"simulated_round_durations({ep}) returned shape "
                        f"{worker_walls.shape}; round {ep}'s membership "
                        f"has {n} workers")
        else:
            # total steps this round = epochs_local x (train + val
            # steps); attribute the wall to train steps proportionally
            worker_walls = _measured_worker_walls(wall, n) / max(
                cfg.epochs_local, 1)
        if schedule is not None:
            # chaos slow/stall faults perturb ONLY this host-side
            # measured-wall vector (chaos.py) — device numerics are
            # untouched, which is what keeps chaos runs bit-deterministic
            worker_walls = schedule.perturb_walls(ep, worker_ids,
                                                  worker_walls)
        if policy is not None:
            # straggler protocol: overruns past the backoff-extended
            # deadline are tolerated as logged retries; one past the
            # retry budget and the worker departs at the next boundary,
            # its shard redistributed to the surviving quorum.  A missed
            # fence (non-finite wall) is the distinct CRASHED verdict:
            # the whole round is void — no wall recorded, no straggler
            # verdicts drawn from it — and the caller rolls back.
            departed, crashed, retries = policy.observe(worker_ids,
                                                        worker_walls)
            if crashed:
                return crashed
            if retries:
                el["sync_retries"].extend(retries)
                for r in retries:
                    log.warning("elastic: straggler retry %s", r)
            for wid in departed:
                log.warning(
                    "elastic: worker %d overran its straggler budget in "
                    "round %d (wall past time_limit + extended grace, "
                    "retries exhausted) — departing at the next round "
                    "boundary", wid, ep)
                pending_departs.append(chaos_lib.ChaosEvent(
                    kind="depart", round=ep + 1, worker=int(wid)))
        walls_by_round[ep] = (worker_walls, steps_run)
        return []

    def finish_inflight():
        """Deep pipeline: block on the deferred round's completion marker
        and record its wall.  Runs BEFORE the next prepare_next, so the
        delayed-EMA repartition consumes exactly the same wall set as the
        serial flow (walls through round r-1 when preparing round r+1)."""
        if not inflight:
            return
        ep, markers, t_disp_, timing_, steps_ = inflight.pop()
        with span("round.wait", timing_, "wait_ms", round=ep):
            jax.block_until_ready(markers)
        t_done = timing_["t_ready_s"] = time.perf_counter()
        read_hbm(timing_)
        start = t_disp_ if t_done_prev[0] is None \
            else max(t_disp_, t_done_prev[0])
        t_done_prev[0] = t_done
        record_walls(ep, t_done - start, steps_, timing_)

    # --- elastic membership transition (ISSUE 8 tentpole) ---------------
    def install_from_snapshot(snap) -> None:
        """Adopt a membership snapshot as the live run configuration.

        The mesh's data axis is rebuilt at the new worker count (inner
        TP/PP/SP/EP axes untouched), a fresh engine re-buckets the sync
        program and re-derives the gossip ring/double-ring ppermute
        neighbor tables from the new axis size (a departed worker can
        never strand the ring), and the row-edited host state restages
        through ``stage_state`` — the PR 5 cross-mesh reshard, in
        process.  The fresh-run twin (``elastic_snapshot=``) executes
        this identical configuration at setup."""
        nonlocal state, mesh, engine, n, worker_ids, sec_per_batch, \
            train_parts, val_parts, fixed_classes
        mesh = resize_data_axis(mesh, snap.n_workers)
        engine = LocalSGDEngine(model, mesh, cfg, train_model=train_model,
                                param_specs_fn=param_specs_fn,
                                nan_screen=nan_armed)
        if snap.params_template is not None:
            # resident bucket rows carry no leaf shapes; the new engine's
            # entry gather and host re-layouts need the per-worker
            # template before any round dispatch
            engine.params_template = snap.params_template
        state = engine.stage_state(snap.host_state)
        n = snap.n_workers
        worker_ids = list(snap.worker_ids)
        sec_per_batch = np.asarray(snap.sec_per_batch, np.float64).copy()
        train_parts = [np.asarray(p).copy() for p in snap.train_parts]
        val_parts = [np.asarray(p).copy() for p in snap.val_parts]
        fixed_classes = copy.deepcopy(snap.fixed_classes)
        rng.bit_generator.state = copy.deepcopy(snap.rng_state)
        for wid in worker_ids:   # joiners get fresh per-logical-id lists
            while len(results["all_workers_losses"]) <= wid:
                results["all_workers_losses"].append([])
        # the worker count changed, so every per-worker resident-bytes
        # figure (and the sharded round_opt / params_resident rows)
        # changed with it — as may the residency itself (a quorum of 1
        # demotes resident to replicated)
        results["sync_engine"]["param_residency"] = engine.param_residency
        results["sync_engine"]["per_worker_state_bytes"] = \
            engine.state_resident_bytes(state)

    def process_quarantine(rnd: int, okv: np.ndarray) -> None:
        """Turn one round's per-worker sync validity flags into
        quarantine strikes (ISSUE 12): a quarantined contribution is a
        logged strike; more than ``--chaos_retries`` CONSECUTIVE strikes
        escalate to a departure at the next boundary (the worker is
        producing garbage every round — remove it and redistribute its
        shard); a clean round resets the count."""
        for pos, wid in enumerate(worker_ids):
            if okv[pos] > 0:
                quarantine_strikes.pop(wid, None)
                continue
            k = quarantine_strikes.get(wid, 0) + 1
            quarantine_strikes[wid] = k
            el["quarantined_rounds"] += 1
            log.warning(
                "elastic: worker %d's round-%d sync contribution was "
                "quarantined (poisoned/non-finite) — blend renormalized "
                "over the survivors; strike %d (budget %d)",
                wid, rnd, k, cfg.chaos_retries)
            if k > cfg.chaos_retries:
                quarantine_strikes.pop(wid, None)
                log.warning(
                    "elastic: worker %d exhausted the quarantine strike "
                    "budget — departing at the next round boundary", wid)
                pending_departs.append(chaos_lib.ChaosEvent(
                    kind="depart", round=rnd + 1, worker=int(wid)))

    def recover_from_crash(rnd: int, crashed: list[int],
                           boundary_host) -> None:
        """Bounded rollback recovery (ISSUE 12 tentpole): round ``rnd``
        is VOID — worker(s) ``crashed`` missed its fence mid-round.
        Roll back to the boundary entering ``rnd`` entirely in memory
        (``boundary_host``, the fenced host snapshot pool), reconstruct
        the crashed workers' uniquely-held shard-resident spans from
        their ring buddies (double fault / redundancy off falls back to
        the newest committed checkpoint — the only path that pays
        restore I/O), remove them from the membership through the SAME
        plan -> build_snapshot -> install path a cooperative kill takes
        (which is what makes the fresh-twin bitwise gate mechanical),
        and rebuild the round's inputs; the caller then re-runs ``rnd``
        on the surviving quorum."""
        nonlocal state, prep, san_warmup
        t0 = time.perf_counter()
        el["crashes"] += len(crashed)
        log.warning(
            "elastic: worker(s) %s missed the round-%d fence (CRASHED "
            "mid-round, non-cooperative) — rolling back to the round "
            "boundary", crashed, rnd)
        if sanitize and san_warmup is not None:
            # close the steady-state retrace budget before the recovery
            # window (a sanctioned reshard window, like PR 8's): the new
            # mesh's round-program compile belongs to the recovery, but
            # anything traced during the steady rounds before it is
            # still a bug
            counts = compile_event_counts()
            d_tr = counts["traces"] - san_warmup["traces"]
            d_co = counts["compiles"] - san_warmup["compiles"]
            if d_tr or d_co:
                san["retrace_count"] += d_tr
                san["recompile_count"] += d_co
                raise RuntimeError(
                    f"sanitizer: retrace budget exceeded before the "
                    f"round-{rnd} crash recovery — post-warmup rounds "
                    f"added {d_tr} jaxpr trace(s) and {d_co} backend "
                    "compile(s)")
            san_warmup = None   # next completed round re-baselines
        # the rollback discards everything the voided round produced:
        # fold the walls recorded through rnd-1 into the EMA (the
        # snapshot must carry the final heterogeneity estimate, exactly
        # like a membership boundary), then clear the straggler /
        # quarantine ledgers — the fresh twin starts with empty ones
        consume_walls(upto=rnd)
        walls_by_round.clear()
        next_wall_box[0] = rnd
        if policy is not None:
            policy.reset()
        pending_departs.clear()
        quarantine_strikes.clear()
        positions = [worker_ids.index(c) for c in crashed]
        host_state = boundary_host
        uniquely_held = (engine.resident_on
                         or (engine.round_opt_on
                             and engine.opt_placement == "sharded"))
        opt_pl = engine.opt_placement if engine.round_opt_on else None
        try:
            host_state = elastic_lib.restore_crashed_rows(
                host_state, positions,
                params_template=engine.params_template,
                sync_bucket_bytes=engine.sync_bucket_bytes,
                round_opt_placement=opt_pl)
            source = "buddy" if uniquely_held else "snapshot"
        except ValueError as e:
            # double-fault ladder: worker AND buddy lost, or redundancy
            # off — the spans exist nowhere in memory.  Degrade to the
            # newest committed checkpoint, logged and counted.
            log.warning(
                "elastic: in-memory buddy recovery unavailable (%s) — "
                "degrading to the newest committed checkpoint", e)
            if ckpt_engine is None:
                raise RuntimeError(
                    f"crash of worker(s) {crashed} is unrecoverable: "
                    f"{e}; no --checkpoint_dir is configured to degrade "
                    "to") from e
            ckpt_engine.wait()
            latest = ckpt_engine.latest_checkpoint()
            if latest is None:
                raise RuntimeError(
                    f"crash of worker(s) {crashed} is unrecoverable: "
                    f"{e}; no committed checkpoint exists yet") from e
            restored, ck_epoch = ckpt_lib.restore_checkpoint(
                latest, state, params_template=engine.params_template,
                bucket_bytes=engine.sync_bucket_bytes)
            host_state = elastic_lib.host_state_snapshot(
                engine.checkpoint_fence(restored))
            source = "checkpoint"
            if ck_epoch < rnd:
                log.warning(
                    "elastic: checkpoint fallback rewound %d round(s) of "
                    "consensus progress (checkpoint epoch %d < crash "
                    "round %d) — the run continues at round %d on the "
                    "restored state", rnd - ck_epoch, ck_epoch, rnd, rnd)
        events = [chaos_lib.ChaosEvent(kind="crash", round=rnd,
                                       worker=int(c)) for c in crashed]
        change = plan.apply(events)
        if change.rejected or not change.applied:
            el["rejected"].extend(change.rejected)
            raise RuntimeError(
                f"crash of worker(s) {crashed} cannot be applied to the "
                f"membership {worker_ids} (quorum floor "
                f"{cfg.elastic_min_workers}): {change.rejected} — a "
                "crashed worker is gone regardless, so the run cannot "
                "continue")
        snap = elastic_lib.build_snapshot(
            epoch=rnd, change=change, old_state=host_state,
            sec_per_batch=sec_per_batch, seed=cfg.seed,
            num_classes=num_classes, trainset_len=len(trainset),
            valset_len=len(valset), proportionality=cfg.proportionality,
            data_mode=cfg.data_mode, fixed_ratio=cfg.fixed_ratio,
            rng=rng, trainset_labels=trainset.labels,
            valset_labels=valset.labels, next_worker_id=plan.next_id,
            n_round0=n_round0,
            round_opt_placement=opt_pl,
            sync_bucket_bytes=engine.sync_bucket_bytes,
            params_template=engine.params_template)
        el["snapshots"].append(elastic_lib.snapshot_copy(snap))
        install_from_snapshot(snap)
        el["events"].extend(change.applied)
        el["recoveries"] += 1
        el["recovery_source"].append(source)
        recovery_ms = round((time.perf_counter() - t0) * 1e3, 3)
        el["recovery_ms"].append(recovery_ms)
        log.info(
            "elastic: round %d crash recovery via %s -> %d worker(s) %s; "
            "stall %.1f ms (round re-runs on the surviving quorum)",
            rnd, source, n, worker_ids, recovery_ms)
        prep = make_prep(train_parts, val_parts)

    def membership_boundary(rnd: int) -> None:
        """Resolve + apply membership events at the boundary entering
        round ``rnd``: scripted/random chaos kill/join events plus any
        straggler-protocol departures observed last round.  On a change,
        capture the full post-event configuration as a
        ``MembershipSnapshot`` and install it in process — no restart."""
        nonlocal state, prep, san_warmup
        events = list(pending_departs)
        if schedule is not None:
            events += schedule.membership_events(rnd)
        if not events:
            return
        # settle EVERYTHING in flight first: the transition reads and
        # retires the whole device state, restructures the per-worker
        # metric lists the worker thread writes, and replaces the engine
        finish_inflight()
        while pending:
            pending.pop(0).result()
        change = plan.apply(
            events, resolve=(schedule.resolve_target
                             if schedule is not None else None))
        pending_departs.clear()
        if change.rejected:
            # graceful degradation: an event that would sink the roster
            # below the quorum floor or past device capacity is recorded
            # and skipped, never partially applied — the surviving quorum
            # keeps training
            el["rejected"].extend(change.rejected)
            for r in change.rejected:
                log.warning("elastic: membership event rejected: %s", r)
        if not change.changed:
            return
        if sanitize and san_warmup is not None:
            # close THIS steady-state segment's zero-retrace budget the
            # moment a change is committed, BEFORE any transition work:
            # checkpoint_fence and build_snapshot trace their own small
            # programs on first use, and those belong to the sanctioned
            # reshard window (like the new mesh's round-program compile
            # during the next round) — anything traced during the
            # steady-state rounds before this boundary is still a bug
            counts = compile_event_counts()
            d_tr = counts["traces"] - san_warmup["traces"]
            d_co = counts["compiles"] - san_warmup["compiles"]
            if d_tr or d_co:
                san["retrace_count"] += d_tr
                san["recompile_count"] += d_co
                raise RuntimeError(
                    f"sanitizer: retrace budget exceeded before the "
                    f"round-{rnd} membership change — post-warmup rounds "
                    f"added {d_tr} jaxpr trace(s) and {d_co} backend "
                    "compile(s)")
            san_warmup = None   # next completed round re-baselines
        t0 = time.perf_counter()
        # fold every recorded wall into the EMA now: the snapshot must
        # carry the fully-updated heterogeneity estimate, and the
        # continuation starts with an empty wall history — exactly like
        # a fresh run from the snapshot
        consume_walls(upto=rnd)
        walls_by_round.clear()
        next_wall_box[0] = rnd
        if policy is not None:
            # clear ALL retry state, not just the departed workers': the
            # snapshot carries no attempt counters, so the fresh-twin's
            # policy starts empty — resetting here keeps the continued
            # run's post-boundary straggler verdicts identical to the
            # twin's (a surviving mid-retry straggler gets its base
            # deadline back; the membership change re-arms every budget)
            policy.reset()
        state = engine.checkpoint_fence(state)
        snap = elastic_lib.build_snapshot(
            epoch=rnd, change=change, old_state=state,
            sec_per_batch=sec_per_batch, seed=cfg.seed,
            num_classes=num_classes, trainset_len=len(trainset),
            valset_len=len(valset), proportionality=cfg.proportionality,
            data_mode=cfg.data_mode, fixed_ratio=cfg.fixed_ratio,
            rng=rng, trainset_labels=trainset.labels,
            valset_labels=valset.labels, next_worker_id=plan.next_id,
            n_round0=n_round0,
            round_opt_placement=(engine.opt_placement
                                 if engine.round_opt_on else None),
            sync_bucket_bytes=engine.sync_bucket_bytes,
            params_template=engine.params_template)
        el["snapshots"].append(elastic_lib.snapshot_copy(snap))
        install_from_snapshot(snap)
        el["events"].extend(change.applied)
        reshard_ms = round((time.perf_counter() - t0) * 1e3, 3)
        el["reshard_ms"].append(reshard_ms)
        log.info("elastic: round %d boundary applied %s -> %d worker(s) "
                 "%s; reshard stall %.1f ms", rnd, change.applied, n,
                 worker_ids, reshard_ms)
        # the prep built for this round under the OLD membership is dead;
        # rebuild from the snapshot partitions (the fresh-run twin runs
        # the identical make_prep at its setup)
        prep = make_prep(train_parts, val_parts)

    try:
        for global_epoch in epoch_iter:
            # fail fast on metric-worker errors: a fetch/assembly failure
            # from an earlier round must abort the run within one round,
            # not after every remaining round has burned device time
            while pending and pending[0].done():
                pending.pop(0).result()
            if elastic_on:
                membership_boundary(global_epoch)
                if n < n_start:
                    el["rounds_degraded"] += 1
            # crash-recovery retry loop (ISSUE 12): a round whose fence a
            # worker misses is VOID — roll back to the boundary snapshot
            # taken right here and re-run the round on the surviving
            # quorum.  One iteration is the entire pre-ISSUE-12 body;
            # re-iteration only ever follows a crash verdict (each one
            # removes at least one worker, so the loop terminates).
            while True:
                boundary_host = None
                if crash_armed:
                    # the fenced host snapshot pool: the in-memory
                    # rollback target for a crash during THIS round (the
                    # PR 5/8 staging machinery — a copy-not-view host
                    # snapshot, no checkpoint I/O)
                    state = engine.checkpoint_fence(state)
                    boundary_host = elastic_lib.host_state_snapshot(state)
                results["step_caps"].append(list(prep["caps"]))
                results["shard_sizes"].append(list(prep["sizes"]))
                # zero-filled checkpoint walls (sync_ms convention: the
                # schema is identical every round; save rounds
                # overwrite).  The background writer fills ckpt_write_ms
                # when its write lands — always before results return
                # (ckpt_engine.wait in finally).  pad_steps: the packed
                # steps of this round's packs that carry no row.
                timing: dict[str, Any] = {"ckpt_snapshot_ms": 0.0,
                                          "ckpt_write_ms": 0.0,
                                          "pad_steps": prep["pad_steps"]}
                results["round_timings"].append(timing)
                t_disp = timing["t_dispatch_s"] = time.perf_counter()
                if t_ready is not None:
                    # host time the device sat idle between the previous
                    # round finishing and this round's dispatch — the
                    # round gap the overlap exists to close
                    results["round_timings"][-2]["gap_ms"] = round(
                        (t_disp - t_ready) * 1e3, 3)
                # stage_ms is the DISPATCH span: the packs were staged in
                # prepare_next, so it holds the poison flags' put, the
                # donation probe and engine.round_start, which on round 0
                # builds the programs (build_ms below)
                with span("round.dispatch", timing, "stage_ms",
                          round=global_epoch):
                    poison = None
                    if nan_armed:
                        # stage this round's per-worker poison flags
                        # (nan@R faults) — an EXPLICIT put,
                        # transfer-guard-safe
                        targets = schedule.nan_targets(global_epoch,
                                                       worker_ids)
                        poison = engine.stage_poison(np.array(
                            [wid in targets for wid in worker_ids],
                            np.bool_))
                    # sanitizer donation probe: the packed round program
                    # donates its whole TrainState input — hold the
                    # pre-dispatch buffer refs so the post-wait check can
                    # assert XLA actually deleted them (the streamed path
                    # donates only the inner chunk carry, with lr_epoch
                    # deliberately read eagerly, so it is exempt; the
                    # buddy rows are NOT a program input — round_start
                    # drops them and the sync program writes the fresh
                    # copy — so they are excluded from the donation
                    # contract)
                    donated_leaves = (
                        [l for l in jax.tree_util.tree_leaves(
                            state.replace(buddy=None))
                         if isinstance(l, jax.Array)]
                        if sanitize and not streaming else None)
                    with _round_guard(san):
                        if streaming:
                            state, handle = engine.round_streamed_start(
                                state, *prep["inputs"], poison=poison)
                        else:
                            state, handle = engine.round_start(
                                state, *prep["inputs"], poison=poison)
                # which dispatch built a program (trace + lower +
                # compile or cache load): round 0's as a rule, and any
                # later one is "this round recompiled"
                timing.update(probe_lib.fold_builds(engine.take_builds()))
                if engine.last_sync_stats:
                    # static per-round sync telemetry (bytes on the wire,
                    # mode); the measured sync_ms joins after round_wait
                    # when a standalone sync program ran
                    timing.update(engine.last_sync_stats)
                cur_steps_run = prep["steps_run"]
                if overlap:
                    pending.append(executor.submit(
                        metrics_job, handle, global_epoch, t_disp, timing,
                        list(worker_ids)))
                ckpt_due = bool(cfg.checkpoint_dir and cfg.checkpoint_every
                                and (global_epoch + 1)
                                % cfg.checkpoint_every == 0)
                last_round = global_epoch + 1 >= cfg.epochs_global
                defer = deep_pipeline and not ckpt_due and not last_round
                # settle the PREVIOUS deferred round first in either
                # case: its wall must be on record before prepare_next
                # runs, so the delayed-EMA repartition consumes the same
                # wall set as the serial flow
                finish_inflight()
                if defer:
                    # two rounds in flight: leave THIS round computing
                    markers = engine.round_markers(handle)
                    if markers[1] is not None:
                        # the host comes to this round's standalone sync
                        # late and never waits on it: no sync_ms, rather
                        # than a 0.0 that reads as a measured wall
                        timing.pop("sync_ms", None)
                    inflight.append((global_epoch, markers,
                                     t_disp, timing, cur_steps_run))
                    t_ready = None  # device not idle between rounds here
                if overlap and not last_round:
                    prep = prepare_next(global_epoch, cur_steps_run, timing)
                crashed: list[int] = []
                if not defer:
                    with span("round.wait", timing, "wait_ms",
                              round=global_epoch), _round_guard(san):
                        state = engine.round_wait(state, handle)
                    if engine.last_sync_stats:
                        timing.update(engine.last_sync_stats)
                    t_ready = timing["t_ready_s"] = time.perf_counter()
                    read_hbm(timing)
                    # the barrier round right after a deferred one also
                    # started computing only when its predecessor
                    # finished (same double-count hazard finish_inflight
                    # corrects)
                    start = t_disp if t_done_prev[0] is None \
                        else max(t_disp, t_done_prev[0])
                    t_done_prev[0] = t_ready
                    crashed = record_walls(global_epoch, t_ready - start,
                                           cur_steps_run, timing)
                    if donated_leaves is not None:
                        # donation hygiene at runtime (graftlint R4's
                        # dynamic twin): every leaf handed to the round
                        # program must be gone now — a surviving buffer
                        # means XLA declined the donation (sharding/
                        # layout mismatch) and the round silently ran at
                        # double state memory
                        fails = [i for i, l in enumerate(donated_leaves)
                                 if not l.is_deleted()]
                        if fails:
                            san["donation_failures"] += len(fails)
                            raise RuntimeError(
                                f"sanitizer: {len(fails)} of "
                                f"{len(donated_leaves)} donated "
                                "round-state buffers survived round "
                                f"{global_epoch} — donation was declined "
                                "(check in/out sharding match of the "
                                "round program)")
                if not crashed:
                    break
                if boundary_host is None:
                    # a non-finite wall without crash faults armed (a
                    # caller-injected inf/NaN simulated wall): the
                    # rollback snapshot pool is off, so recovery is
                    # impossible — fail with the real reason instead of
                    # an UnboundLocalError deep in the recovery path
                    raise RuntimeError(
                        f"worker(s) {crashed} reported a non-finite "
                        f"round-{global_epoch} wall but no crash fault "
                        "is armed (--chaos has no crash events), so no "
                        "rollback boundary snapshot exists — fix the "
                        "wall injection or script the crash")
                # the round is VOID: discard everything it appended (its
                # metrics were never assembled — crash arming forces the
                # serial flow, and metrics_job runs only after this
                # loop), restore the boundary, and re-run the round.
                # t_ready resets too: round R-1's gap_ms was written
                # correctly by this voided attempt's dispatch, and the
                # re-run must not overwrite it with the voided round's
                # compute + the recovery stall (reported in recovery_ms)
                results["step_caps"].pop()
                results["shard_sizes"].pop()
                results["round_timings"].pop()
                t_ready = None
                recover_from_crash(global_epoch, crashed, boundary_host)
            if not overlap:
                mx = metrics_job(handle, global_epoch, t_disp, timing,
                                 list(worker_ids))
                if nan_armed and mx is not None and "sync_ok" in mx:
                    process_quarantine(global_epoch,
                                       np.asarray(mx["sync_ok"]))
                if not last_round:
                    prep = prepare_next(global_epoch, cur_steps_run, timing)

            if ckpt_engine is not None and jax.process_count() > 1:
                # bound the multi-host deferred-commit window to ONE
                # round: the previous save's shard write overlapped this
                # round's compute; publish its manifest NOW instead of at
                # the next save, which could leave a fully-durable epoch
                # unmanifested (= unrestorable) for checkpoint_every
                # rounds.  Every process reaches this point every round
                # AFTER round_wait and the metric fetch, so the commit's
                # allgather matches across processes and stays strictly
                # serialized with the loop's other collectives.  No-op
                # when nothing is pending; single-process commits inside
                # the writer job and never defers.
                ckpt_engine.wait()
            if ckpt_due:
                # every process enters (the multi-host manifest commit is
                # collective) and writes ONLY its addressable shards — no
                # gather.  Checkpoint rounds never defer (ckpt_due excludes
                # them from the deep pipeline above), so the state is
                # materialized and the next round is NOT yet dispatched;
                # the engine fence + host snapshot then read the buffers
                # before donation can invalidate them, and the round loop
                # resumes while the background thread serializes + commits.
                # buddy rows (ISSUE 12) are derived state; the save
                # itself strips them (checkpoint._strip_buddy), so the
                # checkpoint layout is independent of the redundancy flag
                ckpt_engine.save(engine.checkpoint_fence(state),
                                 global_epoch + 1, timing=timing)
            if sanitize and san_warmup is None:
                # retrace budget (graftlint R2's dynamic twin): the first
                # round is the warmup — it legitimately traces+compiles
                # the round (and sync) programs.  Every LATER round must
                # add zero jaxpr traces and zero backend compiles; any
                # delta means per-round retracing (shape churn, a
                # rebuilt callable, value-varying static args) and is
                # asserted on after the loop.
                san_warmup = compile_event_counts()
    finally:
        try:
            if executor is not None:
                for fut in pending:
                    fut.result()   # propagate worker-thread failures loudly
                executor.shutdown(wait=True)
        finally:
            # runs even when a metric worker raised above.  Success path:
            # close() drains the in-flight write (failure re-raised
            # loudly; multi-host: the deferred commit barrier runs here,
            # on the main thread, on every process), records the final
            # ckpt_write_ms, and releases the writer thread.  Exception
            # path: abort() — same drain WITHOUT the commit collective,
            # which peers unwinding elsewhere might never match (a hang
            # would eat the real traceback).
            if ckpt_engine is not None:
                if sys.exc_info()[0] is None:
                    ckpt_engine.close()
                else:
                    ckpt_engine.abort()

    if pbar is not None:
        pbar.close()
    if profiling:
        jax.profiler.stop_trace()
    if results["round_timings"]:
        row0 = results["round_timings"][0]
        log.info("set-up: data %.3f s, engine %.3f s, restore %.3f s, "
                 "probe %.3f s, first prep %.3f s; the first round built "
                 "%s in %.1f ms (trace %.1f, lower %.1f, compile %.1f: %d "
                 "cache hit(s), %d miss(es)) of a %.1f ms dispatch%s",
                 *(setup_timings[k] for k in (
                     "data_s", "engine_s", "restore_s", "probe_s",
                     "first_prep_s")),
                 row0["programs_built"], *(row0[k] for k in (
                     "build_ms", "build_trace_ms", "build_lower_ms",
                     "build_compile_ms", "build_cache_hits",
                     "build_cache_misses", "stage_ms")),
                 _last_peak_rise(hbm, results["round_timings"]))

    # persistent-compile-cache effectiveness for THIS run (ROADMAP open
    # item): how many executable lookups the armed cache served vs compiled
    cache_counts = compile_cache_counts()
    results["compile_cache"] = {
        "enabled": bool(cfg.compile_cache_dir),
        "hits": cache_counts["hits"] - cache_counts0["hits"],
        "misses": cache_counts["misses"] - cache_counts0["misses"],
    }
    log.info("compile cache: %s, %d hits / %d misses this run",
             "on" if results["compile_cache"]["enabled"] else "off",
             results["compile_cache"]["hits"],
             results["compile_cache"]["misses"])

    # checkpoint-engine telemetry: total round-loop stall (the snapshot
    # walls) vs the hidden background write wall, bytes per host per save
    results["checkpoint"] = (ckpt_engine.summary()
                             if ckpt_engine is not None
                             else {"enabled": False})

    # per-level wire-byte telemetry (ISSUE 13): the engine computed the
    # split when the first round armed the accounting (zeros when no
    # round ran) — possibly a post-elastic engine, whose split reflects
    # the final membership like per_worker_state_bytes does
    ici_b, dcn_b = engine._sync_bytes_split
    results["sync_engine"]["sync_bytes_ici"] = ici_b
    results["sync_engine"]["sync_bytes_dcn"] = dcn_b

    # semi-synchronous drain (ISSUE 16): the round loop exits with up to
    # K consensus deltas still in flight — fold every one of them into
    # the params (oldest first, the same delivery blend the in-loop
    # fences use) and restore the engine-held EF residual into the state
    # BEFORE anything below reads it (the memory accounting's
    # state_resident_bytes, results["state"], rank0_variables).  The
    # drain walls land in engine.stale_log, not in round_timings — the
    # async_rounds summary below covers them.  The sim twin
    # (--sim_staleness) drains the same way, minus the wall accounting.
    if (getattr(engine, "staleness", 0) > 0
            or getattr(engine, "sim_staleness", 0) > 0):
        state = engine.drain_pending(state)

    # the allocator once more, every number it gives, now that the call's
    # last program has settled
    hbm["at_end"] = {}
    end_stats = read_hbm(hbm["at_end"])
    if end_stats:
        hbm["at_end"].update((k, int(v)) for k, v in end_stats.items()
                             if isinstance(v, (int, float)))

    # compiled-memory observability (ISSUE 15): recorded like
    # sync_engine / sanitize — every run artifact carries XLA's
    # memory_analysis of every cached executable this run compiled
    # (round / standalone sync / resident enter-gather / streamed chunk
    # programs / the sim vmap program) plus the analytic resident-state
    # model (per-worker bytes, the transient gathered peak, and the
    # stacked/fleet total — on a simulated run that total is ONE chip's
    # residency, the ISSUE 14 N-ceiling quantity).  Zero-round runs
    # emit the row with an empty program map — the schema is
    # unconditional.
    results["memory"] = probe_lib.memory_report(
        engine.memory_programs(),
        state_bytes=engine.state_resident_bytes(state),
        n_workers=n, sim=sim_on, hbm=hbm if end_stats else None)
    log.info(
        "compiled memory: %d program(s), %.2f MB temp total; per-worker "
        "resident state %.2f MB (+%.2f MB transient gather peak), "
        "%s total %.2f MB",
        len(results["memory"]["programs"]),
        results["memory"]["temp_bytes_total"] / 2**20,
        results["memory"]["per_worker_resident_bytes"] / 2**20,
        results["memory"]["per_worker_state_bytes"].get(
            "params_gathered_peak", 0) / 2**20,
        "one-chip stacked" if sim_on else "fleet",
        results["memory"]["state_bytes_total"] / 2**20)

    # sanitizer provenance (ISSUE 6): recorded like sync_engine — every
    # run artifact states whether it ran sanitized and what the harness
    # observed (all zeros on a clean run; enabled=False when off)
    if sanitize and san_warmup is not None:
        counts = compile_event_counts()
        san["retrace_count"] = counts["traces"] - san_warmup["traces"]
        san["recompile_count"] = (counts["compiles"]
                                  - san_warmup["compiles"])
    results["sanitize"] = san
    if sanitize and (san["retrace_count"] or san["recompile_count"]):
        raise RuntimeError(
            f"sanitizer: retrace budget exceeded — rounds after the "
            f"warmup added {san['retrace_count']} jaxpr trace(s) and "
            f"{san['recompile_count']} backend compile(s); a steady-state "
            "round loop must re-use its compiled programs (look for "
            "shape churn in the packed inputs, per-round jit "
            "construction, or value-varying static args)")
    if sanitize:
        # greppable clean-run provenance (any violation raised above);
        # verify.sh's smokes grep this spelling
        log.info("sanitizer clean: 0 transfer-guard violations, 0 "
                 "post-warmup retraces, 0 donation failures")

    # elastic-membership provenance (ISSUE 8): recorded like sync_engine/
    # sanitize — every run artifact states whether the elastic harness was
    # armed and what it did (events applied/rejected, straggler retries,
    # per-event reshard stalls, rounds run below the starting quorum).
    # "snapshots" carries a deep copy of every membership boundary's
    # post-event configuration: the fresh-run twin
    # (train_global(cfg, elastic_snapshot=snap)) starts from one to prove
    # the bitwise loss-trajectory gate.
    el["final_worker_ids"] = list(worker_ids)
    results["elastic"] = el
    if el["events"]:
        log.info("elastic: %d membership event(s), %d rejected, %d "
                 "straggler retries, reshard stalls %s ms, %d round(s) "
                 "degraded, final membership %s",
                 len(el["events"]), len(el["rejected"]),
                 len(el["sync_retries"]), el["reshard_ms"],
                 el["rounds_degraded"], el["final_worker_ids"])

    # scenario-lab provenance (ISSUE 14): recorded like sync_engine /
    # sanitize — a simulated run's artifact states the simulated scale,
    # measured rounds/s, per-worker bytes (state + what one worker's
    # sync would move on the simulated fabric), and the scenario draws
    if sim_on:
        results["sim"] = engine.sim_summary(results["round_timings"],
                                            state)
        log.info("scenario lab: %d simulated workers on one chip, "
                 "%s rounds/s, %d bytes/worker sync wire",
                 results["sim"]["workers"],
                 results["sim"]["rounds_per_s"],
                 results["sim"]["per_worker_sync_bytes"])

    # semi-synchronous provenance (ISSUE 16): recorded like sync_engine /
    # sanitize — every run artifact states whether rounds overlapped
    # their sync and how much of the consensus wall the overlap hid.
    # "delivered" counts every dispatched sync (in-loop fences plus the
    # drain above); hidden_fraction is the headline win — the fraction
    # of the total measured sync wall the round loop never waited on.
    if cfg.sync_staleness > 0:
        stale_log = list(getattr(engine, "stale_log", []))
        wall_total = sum(r["sync_ms"] for r in stale_log)
        hidden_total = sum(r["sync_hidden_ms"] for r in stale_log)
        results["async_rounds"] = {
            "enabled": True,
            "staleness": cfg.sync_staleness,
            "delivered": len(stale_log),
            "sync_ms_total": round(wall_total, 3),
            "sync_hidden_ms_total": round(hidden_total, 3),
            "hidden_fraction": (round(hidden_total / wall_total, 4)
                                if wall_total > 0 else 0.0),
        }
        log.info("async rounds: staleness %d, %d consensus delta(s) "
                 "delivered, %.1f ms sync wall, %.1f ms hidden under "
                 "compute (%.0f%%)",
                 cfg.sync_staleness, len(stale_log), wall_total,
                 hidden_total,
                 100.0 * results["async_rounds"]["hidden_fraction"])
    else:
        results["async_rounds"] = {"enabled": False}

    results["state"] = state
    # the rank-0 eval variables, residency-agnostic (ISSUE 11): a
    # scatter-resident final state cannot be sliced by generic consumers
    # (params is None; the bucket rows carry no leaf shapes), so the
    # driver — which holds the engine's params template — materializes
    # the consensus once here; main.py / eval consume this instead of
    # re-deriving it from the state
    results["variables"] = engine.rank0_variables(state)
    results["mesh"] = mesh
    results["model"] = host_model
    results["engine"] = engine
    results["test"] = test if datasets is None else datasets[2]
    return results
