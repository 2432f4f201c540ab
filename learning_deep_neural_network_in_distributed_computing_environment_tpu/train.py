"""The training engine: local-SGD rounds as one compiled SPMD program.

Reference semantics being reproduced (``Balanced All-Reduce/trainer.py``):

- two-level loop: ``epochs_global`` rounds x ``epochs_local`` local epochs
  (``trainer.py:29,46``); workers train independently within a round and
  synchronize ONCE per round (``:141-150``);
- the hot loop (``train_local_epoch``, ``:194-223``): per-batch
  zero_grad/forward/CE/backward/Adam step, per-batch loss capture, accuracy
  counting, ``scheduler.step()`` once per local epoch (StepLR semantics,
  ``main.py:54``, SURVEY.md 2.5.6);
- per-local-epoch validation on the worker's own val shard
  (``:105-107``; ``validator.py:3-23``);
- aggregation dispatch on (aggregation_by x aggregation_type x topology)
  (``:141-150``).  In **weights** mode the averaged parameters replace the
  local ones (FedAvg).  In **gradients** mode the reference averages the
  *stale last-batch* gradients and the next epoch's ``zero_grad()`` discards
  them before any optimizer step — the collectives run but weights are
  unaffected (SURVEY.md 3.2).  That observable behavior is kept: the
  aggregated-gradient global norm is reported in metrics, parameters are
  untouched;
- Adam moments stay local across rounds, BatchNorm statistics are never
  synchronized (SURVEY.md 7.3).

TPU-first design (not a translation):

- a whole round — ``epochs_local`` x ``steps`` train steps, per-epoch
  validation, metric reduction, and the sync point — is ONE ``jit`` of a
  ``shard_map`` over the mesh's ``data`` axis: zero host round-trips inside
  a round (the reference issues ~7 small collectives per local epoch from
  Python, ``trainer.py:50-119``);
- per-worker state (params, BN stats, Adam moments, RNG) is a pytree whose
  leaves carry a leading worker axis sharded over ``data`` — N independent
  replicas in SPMD clothing;
- unequal shard sizes become a fixed per-round step budget with per-example
  masks (SURVEY.md 7.3), which also hosts the straggler ``time_limit``
  capability (``data/partition.py:budget_from_time_limit``);
- per-batch ``.item()`` reads (``trainer.py:212-216``) become on-device
  metric buffers returned once per round.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from . import comms
from . import probe as probe_lib
from .config import Config
from .data.augment import augment_batch
from .mesh import DATA_AXIS, SLICE_AXIS
from .models.moe import CHOICE_COUNTS
from .spans import span

log = logging.getLogger(__name__)
PyTree = Any


class TrainState(struct.PyTreeNode):
    """Per-worker replica state; every leaf carries a leading worker axis."""

    params: PyTree
    batch_stats: PyTree
    opt_state: PyTree        # Adam moments — local to each worker, never synced
    lr_epoch: jnp.ndarray    # int32, local epochs completed (StepLR clock)
    rng: jnp.ndarray         # uint32[2] raw PRNG key per worker
    # fp32 error-feedback residuals for the bf16-compressed sharded sync
    # (None = compression off).  Params-shaped, carried across rounds like
    # the Adam moments: each round re-injects what bf16 wire rounding
    # dropped from this worker's previous contribution (comms.sharded_sync).
    sync_residual: PyTree = None
    # Round-optimizer Adam moments of the aggregated gradient (ISSUE 9;
    # gradients-aggregation mode under the sharded sync engine; None
    # otherwise).  The tracked quantity — the cross-worker MEAN gradient —
    # is worker-invariant, which is what makes this the one piece of
    # optimizer state the ZeRO-1 placement can shard: under
    # ``--opt_placement sharded`` each worker's row holds only the 1/N
    # bucket shard it owns ([N, padded/N] leaves — per-worker state and
    # update FLOPs at 1/N); under ``replicated`` every row is the full
    # vector ([N, padded] — the N-identical-copies baseline).  Layouts
    # interconvert exactly (comms.round_opt_relayout, checkpoint restore).
    round_opt: PyTree = None
    # Scatter-resident consensus params (ISSUE 11; weights x equal
    # aggregation under the bucketed sharded engine with
    # ``--param_residency resident``; None otherwise).  Between rounds
    # ``params`` is None and this dict — one ``[N, padded/N]`` array per
    # sync-engine bucket, row w = worker w's contiguous 1/N shard of the
    # packed consensus vector (exactly the scatter output the sync ends
    # at) — is the ONLY parameter state: per-worker param residency and
    # checkpoint payload are 1/N.  The round program all_gathers the
    # full tree just-in-time at round entry (comms.resident_gather), so
    # the gathered copy is transient compute-scope memory, never
    # resident state.  Layouts interconvert exactly
    # (comms.resident_from_tree / resident_to_tree / resident_relayout).
    params_resident: PyTree = None
    # Buddy-redundant resident shards (ISSUE 12; ``--shard_redundancy``
    # buddy/auto with something shard-resident; None otherwise).  One
    # dict per sync bucket whose row w holds worker (w-1) % N's
    # shard-resident spans — the resident params row ("params"), the
    # sharded round-opt moments ("mu"/"nu"), and under EF the owned
    # residual span ("res") — delivered by one extra ppermute fused onto
    # the sync program at scatter exit (comms.sharded_opt_sync).  Every
    # 1/N span therefore lives on exactly TWO workers, and an abrupt
    # mid-round worker loss is recoverable in memory from the buddy copy
    # (driver rollback recovery).  Derivable state: ring-rolled copies of
    # the rows above — STRIPPED from checkpoints and re-derived on
    # restore/reshard (comms.derive_buddy), and NOT an input of any
    # engine program (the round program is handed the state without it;
    # the sync program writes the fresh copy).
    buddy: PyTree = None
    # OUTER-level EF residual of the hierarchical sync (ISSUE 13;
    # ``--num_slices > 1`` with a compressed ``--sync_dtype_outer`` and
    # ``--sync_compression ef``; None otherwise).  One fp32
    # ``[N_total, padded // W]`` array per sync bucket: row (s*W + i)
    # carries worker (s, i)'s rounding error of its own DCN gossip
    # transmission (its 1/W span of slice s's mean), re-injected into
    # the next round's outer payload — the flat gossip engine's
    # single-stage EF, per level (comms.hierarchical_sync).  The INNER
    # level keeps its flat two-stage residual in ``sync_residual``.
    sync_residual_outer: PyTree = None


def _first_worker_row(x):
    """``x[0]`` of a worker-stacked leaf, multi-host-safe (no collective).

    A global array whose worker axis spans processes is not fully
    addressable, so ``x[0]`` would fail off process 0.  Every process
    instead assembles the first worker row it can address from ALL the
    addressable shards covering that row — under tensor parallelism one
    worker row is split over the ``model`` axis into several shards, and
    taking a single shard would return a fragment.  On process 0 (the
    consumer of post-training values: rank-0 final eval, ``main.py:61-62``)
    that is the true worker 0 whenever inner mesh axes are intra-host (the
    layout ``mesh.build_mesh`` documents); on other processes it is their
    first local worker — identical right after init (broadcast), which is
    the only place they consume it (probe)."""
    if not isinstance(x, jax.Array) or x.is_fully_addressable:
        if isinstance(x, jax.Array):
            # static slice instead of ``x[0]``: eager __getitem__ stages
            # its gather index host->device IMPLICITLY every call, which
            # the sanitizer's transfer guard rejects in the round loop
            # (and which is a needless blocking H2D on TPU); a static
            # slice bakes the index into the op instead
            return lax.squeeze(lax.slice_in_dim(x, 0, 1, axis=0), (0,))
        return x[0]
    start = min((s.index[0].start or 0) for s in x.addressable_shards)
    covering = [s for s in x.addressable_shards
                if (s.index[0].start or 0) == start]
    out = np.empty(x.shape[1:], dtype=x.dtype)
    for s in covering:
        out[tuple(s.index[1:])] = np.asarray(s.data)[0]
    return jnp.asarray(out)


def _host_fetch(tree):
    """Host copy of a device pytree, multi-host-safe: a worker-sharded
    global array spans non-addressable devices off its own processes,
    where a plain ``device_get`` raises — ``process_allgather``
    replicates the value to every host instead (the resident bucket
    rows are small: 1/N of the params per worker)."""
    if jax.process_count() == 1:
        return jax.device_get(tree)
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(tree, tiled=True)


def resident_consensus(state: "TrainState", params_template,
                       bucket_bytes: int | None = None,
                       n_inner: int | None = None) -> PyTree:
    """HOST per-worker consensus params of a scatter-resident state —
    the host twin of the round-entry gather (concatenating the shard
    rows is bit-exact data movement).  THE one reconstruction path:
    ``rank0_variables`` and ``LocalSGDEngine.materialize_params`` both
    route through it.

    ``n_inner`` (ISSUE 13): on a hierarchical state the rows stack S
    slices of W inner shards and each SLICE has its own consensus —
    the rank-0 consumer takes slice 0's (rows 0..W-1), matching the
    replicated path's worker-0-row convention."""
    if params_template is None:
        raise ValueError(
            "state carries scatter-resident params (params_resident): "
            "pass params_template/bucket_bytes or use "
            "LocalSGDEngine.rank0_variables / materialize_params")
    resident = _host_fetch(state.params_resident)
    if n_inner:
        resident = {k: np.asarray(v)[:n_inner]
                    for k, v in resident.items()}
    return comms.resident_to_tree(
        resident, params_template,
        bucket_bytes=bucket_bytes or comms.DEFAULT_BUCKET_BYTES)


def rank0_variables(state: "TrainState", *, params_template=None,
                    bucket_bytes: int | None = None,
                    n_inner: int | None = None) -> dict:
    """Worker-0 slice of a stacked TrainState as model.apply variables —
    the reference's rank-0 model for test evaluation (main.py:61-62).

    A scatter-resident state (ISSUE 11: ``params`` is None,
    ``params_resident`` holds the 1/N bucket shards) needs
    ``params_template`` (per-worker ShapeDtypeStructs) and the engine's
    ``bucket_bytes`` to reconstruct the consensus on host — the host
    twin of the round-entry gather, bit-exact (``engine.rank0_variables``
    passes them for you)."""
    if state.params is None:
        # the consensus IS every worker's value — no row slice needed
        # (hierarchical states: slice 0's consensus, via n_inner)
        variables = {"params": resident_consensus(
            state, params_template, bucket_bytes, n_inner)}
    else:
        variables = {"params": jax.tree_util.tree_map(_first_worker_row,
                                                      state.params)}
    if jax.tree_util.tree_leaves(state.batch_stats):
        variables["batch_stats"] = jax.tree_util.tree_map(
            _first_worker_row, state.batch_stats)
    return variables


def _mean_by_name(collection: dict) -> dict:
    """{name: scalar} of a sown collection: every value sown under one
    name (one a layer, stacked under a scan) averaged.  The routed layers'
    per-expert choice counts are no such scalar: they come out whole, as
    ``{CHOICE_COUNTS: {the layer's path: counts}}``, for the step's rule
    (``move_select_bias``), which takes them off again."""
    from flax.traverse_util import flatten_dict
    by_name: dict = {}
    counts: dict = {}
    for path, sown in flatten_dict(dict(collection)).items():
        if path[-1] == CHOICE_COUNTS:
            counts[path[:-1]], = sown
            continue
        by_name.setdefault(path[-1], []).extend(
            jnp.mean(v) for v in sown)
    out = {name: jnp.mean(jnp.stack(vs)) for name, vs in by_name.items()}
    return dict(out, **{CHOICE_COUNTS: counts}) if counts else out


def _select_biases(params: PyTree) -> list:
    """The routed layers' selection biases, in the tree's order."""
    return [leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(params)[0]
            if getattr(path[-1], "key", None) == "select_bias"]


def move_select_bias(params: PyTree, counts: dict, step: float) -> PyTree:
    """The rule that moves a router's selection bias, once an optimizer
    step (DeepSeek-V3's balancing without an auxiliary loss; ``step`` is
    ``arch.Router.bias_step``): with ``n_e`` the (token, choice) pairs of
    this step that chose expert e of a layer, ``d_e = step * sign(mean(n) -
    n_e)`` and ``b_e += d_e - mean(d)``: an expert that got more than its
    share is chosen a little less readily next step.  ``counts`` is
    ``{the layer's path: n}`` as the routed layers sowed it, over ALL the
    router's experts (a stacked layer's ``n`` carries the stack's axis, as
    its bias does).  No gradient and no Adam moment is involved."""
    def move(path, leaf):
        names = tuple(getattr(p, "key", str(p)) for p in path)
        if names[-1] != "select_bias" or names[:-1] not in counts:
            return leaf
        n = counts[names[:-1]]
        d = step * jnp.sign(n.mean(-1, keepdims=True) - n)
        return leaf + d - d.mean(-1, keepdims=True)
    return jax.tree_util.tree_map_with_path(move, params)


def steplr(lr0: float, gamma: float, step_size: int, epoch: jnp.ndarray):
    """torch.optim.lr_scheduler.StepLR equivalent, stepped per LOCAL epoch
    (ref trainer.py:218 + main.py:54; StepLR(step_size=25), default gamma
    0.1)."""
    return lr0 * gamma ** (epoch // step_size)


def softmax_cross_entropy_reference(logits: jnp.ndarray,
                                    labels: jnp.ndarray):
    """Per-example CE via ``log_softmax``, torch nn.CrossEntropyLoss
    semantics (main.py:52).  Kept as the numerics twin for
    ``softmax_cross_entropy`` (the production path below): under
    ``value_and_grad`` jax saves the f32 ``log_softmax`` output
    ([B, L, vocab] — 1.6 GB for GPT-2 at B=2, L=4096) as the autodiff
    residual, which is pure HBM traffic the fused path avoids."""
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logz, labels[..., None], axis=-1)[..., 0]


@jax.custom_vjp
def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray):
    """Per-example CE, torch nn.CrossEntropyLoss semantics (main.py:52).

    Large-vocab-aware custom VJP (VERDICT r3 'next' #2): the residuals are
    the (bf16) logits — which live anyway — plus the tiny [B, L]
    log-sum-exp, never the f32 [B, L, vocab] ``log_softmax`` output that
    plain autodiff saves.  The backward recomputes ``softmax = exp(logits
    - lse)`` as a fully fused elementwise chain, so no f32 vocab-sized
    array is ever materialized in HBM — the blockwise structure the
    roofline analysis asked for, achieved by letting XLA's fusion do the
    blocking instead of an explicit scan.  Forward values and gradients
    match ``softmax_cross_entropy_reference`` to float rounding
    (tests/test_train.py)."""
    return _ce_fwd(logits, labels)[0]


def _ce_fwd(logits, labels):
    # clamp ONCE here and store the clamped labels in the residual, so the
    # forward's take_along_axis and the backward's onehot agree for any
    # input (advisor r4: a negative label used to wrap in fwd but match
    # nothing in bwd).  Callers wanting ignore-index mask separately.
    labels = jnp.clip(labels, 0, logits.shape[-1] - 1)
    lf = logits.astype(jnp.float32)
    m = jnp.max(lf, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1, keepdims=True)) + m
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)
    return (lse - ll)[..., 0], (logits, labels, lse[..., 0])


def _ce_bwd(res, g):
    logits, labels, lse = res
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = labels[..., None] == jnp.arange(logits.shape[-1])
    d = (p - onehot) * g[..., None]
    return d.astype(logits.dtype), None


softmax_cross_entropy.defvjp(_ce_fwd, _ce_bwd)


def masked_weights(labels: jnp.ndarray, batch_mask: jnp.ndarray):
    """Per-position fp32 loss weights: the batch mask broadcast over the
    label dims, with ignore-index positions (label < 0, the standard
    convention) zeroed.  THE weight definition for every masked-mean
    loss in the engine — the token stats, the 1F1B schedule, and the
    grad-accumulation denominator must agree byte-for-byte or the
    numerator/denominator constructions silently stop matching."""
    w = batch_mask.reshape(
        batch_mask.shape + (1,) * (labels.ndim - batch_mask.ndim))
    return jnp.broadcast_to(w, labels.shape).astype(jnp.float32) * (labels >= 0)


def masked_token_stats(logits: jnp.ndarray, labels: jnp.ndarray,
                       batch_mask: jnp.ndarray):
    """(ce, weight, correct) for classification ([B] labels) and token
    tasks like MLM ([B, L] labels; positions with label < 0 are ignored,
    the standard ignore-index convention)."""
    labels_safe = jnp.maximum(labels, 0)
    ce = softmax_cross_entropy(logits, labels_safe)
    w = masked_weights(labels, batch_mask)
    correct = ((logits.argmax(-1) == labels) * w).sum()
    return ce, w, correct


def _masked_mean(values: jnp.ndarray, mask: jnp.ndarray):
    return (values * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _tree_where(pred, a: PyTree, b: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda x, y: jnp.where(pred, x, y), a, b)


def _zeros_like_varying(tree: PyTree, dtype=None, extra_axes=()) -> PyTree:
    """``zeros_like`` whose varying-axes type matches each source leaf.

    Scan carries under ``shard_map`` must type-match their body outputs
    (parallel/sp.py's accumulator note); a plain ``jnp.zeros_like`` is
    axis-invariant while fsdp-sharded gradient leaves vary over the fsdp
    axis.  ``dtype`` overrides the leaf dtype (the grad-accumulation
    carry widens to fp32).  ``extra_axes`` marks the zeros varying over
    ADDITIONAL axes beyond the source leaf's — the grad-accumulation
    carry holds PRE-reduction gradients, which vary over the
    batch-partial (seq/fsdp) axes that the params are invariant along."""
    def z(x):
        zz = jnp.zeros(x.shape, dtype or x.dtype)
        want = set(jax.typeof(x).vma) | set(extra_axes)
        missing = tuple(sorted(want - set(jax.typeof(zz).vma)))
        return lax.pcast(zz, missing, to="varying") if missing else zz
    return jax.tree_util.tree_map(z, tree)


class ChunkStager:
    """Bounded producer thread for the streamed round's input pipeline.

    Wraps a generator of host windows: the producer packs the next
    window(s) and stages them onto device (``stage_fn``) while the
    consumer's current chunk computes.  ``depth`` bounds the number of
    STAGED device-resident windows ahead of the consumer — ``depth=2`` is
    classic double buffering (one window computing, one staged on the
    alternate buffer, one being packed by the producer).  Generator /
    staging exceptions re-raise at the consumer's next pull.

    A consumer that bails mid-round must ``close()`` the stager (the
    round loop does, via try/except): close stops the producer and drains
    the queue so the staged device buffers are released instead of being
    pinned by a parked daemon thread for the rest of the process.
    """

    _DONE = object()

    def __init__(self, gen, stage_fn, depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, int(depth)))
        self._err: BaseException | None = None
        self._stop = threading.Event()
        self._t = threading.Thread(target=self._produce,
                                   args=(gen, stage_fn), daemon=True,
                                   name="chunk-stager")
        self._t.start()

    def _produce(self, gen, stage_fn):
        try:
            for item in gen:
                staged = stage_fn(item)
                while not self._stop.is_set():
                    try:
                        self._q.put(staged, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if self._stop.is_set():
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised at consumer
            self._err = e
        finally:
            # the sentinel uses the same stop-aware bounded put: block
            # while the consumer drains, give up only once close()d
            while not self._stop.is_set():
                try:
                    self._q.put(self._DONE, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def close(self):
        """Stop the producer and drop any staged-but-unconsumed windows
        (releases their device buffers).  Idempotent."""
        self._stop.set()
        # drain, let the producer observe the stop (its put attempts are
        # 0.1 s-bounded), then drain whatever its in-flight put landed
        for _ in range(2):
            while True:
                try:
                    self._q.get_nowait()
                except queue.Empty:
                    break
            self._t.join(timeout=1.0)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._DONE:
                if self._err is not None:
                    raise self._err
                return
            yield item


class LocalSGDEngine:
    """Builds and caches the jitted round program for one (model, mesh,
    config) triple."""

    def __init__(self, model, mesh, cfg: Config, train_model=None,
                 param_specs_fn=None, nan_screen: bool = False):
        self.model = model              # dense-attention model: init/probe/eval
        self.train_model = train_model or model  # round-program model (may use
        #                                 ring attention over the seq axis
        #                                 and/or tensor-parallel shards;
        #                                 identical parameter structure)
        self.mesh = mesh
        self.cfg = cfg
        # hierarchical two-level mesh (ISSUE 13): the worker grid is the
        # (slice, data) outer product — ``n_inner`` workers per slice on
        # the ICI-shaped data axis, ``n_slices`` slices on the DCN-shaped
        # outer axis, ``n_workers`` the TOTAL (its pre-ISSUE-13 meaning
        # at 1 slice: every metric array, pack, partition, and RNG
        # stream is per total worker).  At --num_slices 1 nothing below
        # changes: no slice axis exists and every spec/collective keeps
        # its flat form bit-for-bit.
        self.n_slices = int(mesh.shape.get(SLICE_AXIS, 1))
        self.slice_axis = SLICE_AXIS if self.n_slices > 1 else None
        self.n_inner = mesh.shape[DATA_AXIS]
        self.n_workers = self.n_inner * self.n_slices
        # the worker-stack leading axis: (slice, data) on a hierarchical
        # mesh (slice-major rows), plain data otherwise
        self._stack_axes = ((SLICE_AXIS, DATA_AXIS)
                            if self.slice_axis else (DATA_AXIS,))
        from .mesh import FSDP_AXIS, SEQ_AXIS
        self.seq_axis = (
            SEQ_AXIS if (cfg.sequence_parallel != "none"
                         and SEQ_AXIS in mesh.shape
                         and mesh.shape[SEQ_AXIS] > 1) else None)
        # ZeRO-3 / FSDP (parallel/fsdp.py): params + Adam moments sharded
        # over 'fsdp', batch split over it, params all-gathered per step
        self.fsdp_axis = (
            FSDP_AXIS if int(mesh.shape.get(FSDP_AXIS, 1)) > 1 else None)
        # pipeline parallelism: the MoE aux loss is stage-partial and gets
        # psum'd over 'pipe' to keep the loss pipe-invariant
        from .mesh import PIPE_AXIS
        self.pipe_axis = (
            PIPE_AXIS if int(mesh.shape.get(PIPE_AXIS, 1)) > 1 else None)
        # --pp_schedule 1f1b: the train step runs the manual 1F1B
        # schedule (parallel/pp.py onef1b_loss) instead of autodiff
        # through the GPipe scan; eval keeps the GPipe forward
        self.onef1b = (self.pipe_axis is not None
                       and getattr(cfg, "pp_schedule", "gpipe") == "1f1b")
        # tensor parallelism: params(single-replica) -> PartitionSpec tree
        # over the 'model' axis (e.g. models.bert.tp_param_specs)
        self.param_specs_fn = param_specs_fn
        # vocab-parallel head (Megatron): the train model outputs its LOCAL
        # vocab slice and the loss/accuracy use the sharded-vocab stats
        tm = self.train_model
        self.vp_axis = (getattr(tm, "model_axis", None)
                        if getattr(tm, "tp_size", 1) > 1
                        and getattr(tm, "vocab_parallel_head", False)
                        else None)
        self.param_specs = None      # set by init_state
        self._sspec = None           # full TrainState spec tree (TP only)
        # inner (non-worker) mesh axes of size > 1 (the slice axis is a
        # worker-grid axis, not a model axis)
        self._inner_axes = tuple(
            a for a in mesh.axis_names
            if a not in (DATA_AXIS, SLICE_AXIS) and int(mesh.shape[a]) > 1)
        # head and loss on the labelled rows only (ISSUE 29): the model
        # class says its task labels a minority of positions and its head
        # is position-wise; the engine takes it up where a worker is one
        # device (no vocab-parallel head, no 1F1B head slot, no seq / fsdp
        # batch split).  Label density is a runtime value and decides
        # nothing here: a step whose labels do not fit the row buffer
        # fills it again (``_labelled_row_sums``).
        self.labelled_rows_head = (
            bool(getattr(tm, "labelled_rows_head", ()))
            and not self._inner_axes)
        # the speed of the rule that moves a routed layer's selection bias
        # after every optimizer step (``move_select_bias``), from the
        # model's record; 0: the model has no such rule
        arch = getattr(tm, "arch", None)
        self.router_bias_step = arch.router.bias_step if arch else 0.0
        # Microbatch gradient accumulation (ISSUE 3): K > 1 scans the
        # step's batch in K slices with an fp32 gradient carry — bounded
        # activation memory, unchanged effective batch/optimizer/sync
        # cadence.  K == 1 takes the unmodified step path (bit-identical
        # to the pre-accumulation engine by construction).
        self.grad_accum = max(1, int(getattr(cfg, "grad_accum", 1)))
        # torch.optim.Adam defaults (betas 0.9/0.999, eps 1e-8); LR applied
        # outside so StepLR can drive it per local epoch.
        self.tx = optax.scale_by_adam(b1=0.9, b2=0.999, eps=1e-8)
        self._round_cache: dict[tuple, Callable] = {}
        # (train steps, validation steps) of every packed-round program
        # built: the pack lengths a round can take without a compile
        self.built_packs: set[tuple[int, int]] = set()
        # compiled-memory observability (ISSUE 15): every cached engine
        # program is wrapped in a probe.TrackedProgram (AOT lower +
        # compile on first call, executable handle retained), keyed by a
        # stable label — probe.memory_report walks this registry into
        # the uniform results["memory"] row
        self._programs: dict[str, probe_lib.TrackedProgram] = {}
        # (label, row of build durations) of every program built since
        # ``take_builds``
        self._built: list[tuple[str, dict]] = []
        self._spec = (P((SLICE_AXIS, DATA_AXIS)) if self.slice_axis
                      else P(DATA_AXIS))
        # --- round-sync engine selection (ISSUE 2 / ISSUE 13) ----------
        self.sync_mode = self._resolve_sync_mode()
        _wdt = {"bfloat16": jnp.bfloat16, "int8": jnp.int8}
        self.sync_wire_dtype = _wdt.get(cfg.sync_dtype, jnp.float32)
        # outer (DCN) gossip wire of the hierarchical sync — inherits the
        # inner choice when --sync_dtype_outer is unset (ISSUE 13)
        outer_name = (getattr(cfg, "sync_dtype_outer", "")
                      or cfg.sync_dtype)
        self.sync_wire_dtype_outer = _wdt.get(outer_name, jnp.float32)
        # error feedback needs per-worker residual state, which only the
        # weights (FedAvg) aggregation carries forward; in gradients mode
        # the aggregate is discarded after its norm, so compression error
        # has nothing to accumulate into.  The residual carries
        # per-topology: own+mean rounding for the sharded reduce-scatter,
        # own-transmission rounding for the gossip engines.  Hierarchical
        # runs arm EF PER LEVEL: the flat inner residual exactly when the
        # ICI wire is compressed, and the new OUTER residual
        # (TrainState.sync_residual_outer) exactly when the DCN wire is.
        _ef = (cfg.sync_compression == "ef"
               and cfg.aggregation_by == "weights")
        self.sync_ef = (_ef
                        and self.sync_mode in ("sharded", "gossip", "hier")
                        and cfg.sync_dtype in ("bfloat16", "int8"))
        self.sync_ef_outer = (_ef and self.sync_mode == "hier"
                              and outer_name in ("bfloat16", "int8"))
        self.sync_bucket_bytes = max(1, int(cfg.sync_bucket_mb * (1 << 20)))
        # --- shard-resident optimizer placement (ISSUE 9) ---------------
        # Where the round-boundary apply runs and where its state lives:
        # "sharded" = between psum_scatter and all_gather on the 1/N
        # shard; "replicated" = post-gather full-size (the A/B twin);
        # "local" = gossip topologies (worker-local blends, nothing
        # cross-replica-redundant to shard).  fp32 placements are
        # bitwise-identical (tests/test_opt_placement.py).
        self.opt_placement = cfg.resolve_opt_placement(
            jax.default_backend())
        # The round-optimizer Adam moment tracker (TrainState.round_opt)
        # follows the aggregated MEAN gradient — gradients-aggregation
        # mode only (in weights mode the aggregate replaces the params
        # and no boundary moments exist), and only under the bucketed
        # sharded engine (the tracker state is laid out by its bucket
        # plan).  Inner mesh axes (TP/PP/EP/FSDP/SP) shard the gradient
        # leaves themselves, which would make the bucket plan
        # per-device; the tracker stays off there (documented).
        # (hierarchical runs keep the tracker OFF — sync_mode "hier"
        # fails the check below by design: the aggregated mean is
        # per-SLICE under gossip mixing, not a single worker-invariant
        # global vector, so the flat tracker layout does not apply;
        # documented v1 demotion, docs/ARCHITECTURE.md)
        self.round_opt_on = (
            cfg.aggregation_by == "gradients"
            and self.sync_mode == "sharded"
            and self.opt_placement in ("replicated", "sharded")
            and not self._inner_axes)
        if self.sync_mode == "hier" and self.n_inner < 2:
            raise ValueError(
                f"--num_slices {self.n_slices} needs >= 2 workers per "
                f"slice (got a data axis of {self.n_inner}): the outer "
                "gossip hop rides the 1/W inner scatter shard — with "
                "W = 1 there is no inner level, run the flat gossip "
                "engine (--num_slices 1)")
        if (cfg.opt_placement == "sharded"
                and self.opt_placement == "local"):
            log.info(
                "opt_placement sharded requested on a %s topology: gossip "
                "blends are worker-local (no global reduce), resolved to "
                "'local' — see docs/ARCHITECTURE.md", cfg.topology)
        # --- scatter-resident consensus params (ISSUE 11) ---------------
        # Where the consensus parameter tree lives BETWEEN rounds:
        # "resident" keeps each worker's 1/N bucket shard (the sync's
        # scatter output) and the round program gathers just-in-time at
        # entry; "replicated" keeps the full tree per worker.  The
        # config resolution requires the sharded engine + weights x
        # equal aggregation (everything else is worker-local state —
        # docs/ARCHITECTURE.md); the engine additionally demotes under
        # inner mesh axes (TP/PP/EP/FSDP/SP shard the param leaves
        # themselves, which would make the bucket plan per-device —
        # the round_opt precedent) and on a 1-worker axis (nothing to
        # shard).  fp32 resident rounds are bitwise-identical to the
        # replicated twin (tests/test_param_residency.py).
        self.param_residency = cfg.resolve_param_residency(
            jax.default_backend())
        if (self.param_residency == "resident"
                and (self._inner_axes or self.n_inner < 2)):
            self.param_residency = "replicated"
            if cfg.param_residency == "resident":
                log.info(
                    "param_residency resident requested but %s: the "
                    "bucket plan must stay per-worker — resolved to "
                    "'replicated'",
                    "inner mesh axes shard the param leaves"
                    if self._inner_axes else "the worker axis is 1")
        elif (cfg.param_residency == "resident"
                and self.param_residency == "replicated"):
            log.info(
                "param_residency resident requested under %s/%s "
                "aggregation: the between-round params are worker-local "
                "state (the weighted own-term / unsynced gradients-mode "
                "params are per-worker by construction), resolved to "
                "'replicated' — see docs/ARCHITECTURE.md",
                cfg.aggregation_by, cfg.aggregation_type)
        self.resident_on = self.param_residency == "resident"
        # --- buddy-redundant resident shards (ISSUE 12) -----------------
        # The hop exists to protect state no other worker holds: the
        # scatter-resident params rows and/or the SHARDED round-opt
        # moment rows.  auto = on exactly when either resolves; an
        # explicit "buddy" with nothing shard-resident demotes with a
        # log (config rejected the eagerly-decidable cases).
        redundancy = getattr(cfg, "shard_redundancy", "auto")
        # hierarchical runs resolve buddy OFF (ISSUE 13 v1: the buddy
        # map is the flat worker-axis ring and crash recovery — its only
        # consumer — is rejected under slices; explicit buddy was
        # rejected eagerly in config)
        self.buddy_on = (
            redundancy != "off" and self.n_workers >= 2
            and self.n_slices == 1
            and (self.resident_on
                 or (self.round_opt_on
                     and self.opt_placement == "sharded")))
        if redundancy == "buddy" and not self.buddy_on:
            log.info(
                "shard_redundancy buddy requested but nothing resolves "
                "shard-resident (param_residency=%s, round_opt=%s, "
                "workers=%d): every span already lives on all workers — "
                "resolved to 'off'", self.param_residency,
                self.round_opt_on, self.n_workers)
        # --- NaN/Inf integrity screen (ISSUE 12) ------------------------
        # Armed by the driver exactly when the chaos schedule can poison
        # a contribution (nan@R:wI): the sync programs then take a
        # per-worker poison flag, screen every contribution sender-side,
        # renormalize the blend over the finite survivors, and emit
        # per-worker validity flags the driver turns into quarantine
        # strikes.  Clean rounds are bitwise-identical to the unscreened
        # program (comms), which is why this is a compile-time arming,
        # not an always-on input.
        self.nan_screen = bool(nan_screen)
        # per-worker params template (ShapeDtypeStructs, no worker
        # axis): set by init_state / stage_state, or installed from a
        # MembershipSnapshot — the resident layout's bucket plan, entry
        # gather, and host re-layouts all derive from it
        self.params_template = None
        # Packed-path sync placement: on XLA:CPU the sync stays FUSED in
        # the round program — dispatching a second collective program
        # while the round is in flight risks the 1-core rendezvous
        # starvation the driver's barrier exists for.  Elsewhere the sync
        # runs as its own donated program dispatched right behind the
        # round, which gives a measurable per-round collective wall and
        # the two-rounds-in-flight dispatch chain (driver deep pipeline).
        self.split_sync = jax.default_backend() != "cpu"
        # --- semi-synchronous rounds (ISSUE 16) -------------------------
        # K > 0: round R+1 dispatches off the PRE-sync params while sync
        # R runs concurrently; its consensus DELTA (comms.stale_delta) is
        # folded in at the entry of round R+K+1.  The window IS the
        # standalone sync program running under the next round's compute,
        # so the split is forced even on XLA:CPU (the driver fails fast
        # there unless the sequential collective scheduler is pinned —
        # xla_flags.py).  K = 0 leaves every path below untouched: the
        # bitwise gate is structural.
        self.staleness = max(0, int(getattr(cfg, "sync_staleness", 0)))
        if self.staleness > 0:
            self.split_sync = True
        # FIFO of in-flight stale sync records, oldest first (at most K
        # under any round's compute; drained by drain_pending)
        self._pending: list[dict] = []
        # per-delivery walls, in delivery order — the driver's
        # results["async_rounds"] summary reads this
        self.stale_log: list[dict] = []
        # under staleness the EF residual is threaded ENGINE-side from
        # sync program to sync program (state.sync_residual is stripped
        # to None so the round program neither donates nor retraces on
        # it); restored into the state at drain
        self._stale_residual = None
        self._delivered_stats: dict | None = None
        # gate knob: dispatch the SAME delayed-blend schedule but block
        # on every sync fence at dispatch — a scheduling-only change the
        # K=1 bitwise gate diffs against the overlapped run
        self.staleness_serial = bool(
            os.environ.get("JAX_GRAFT_STALENESS_SERIAL"))
        self.last_sync_stats: dict | None = None
        self._sync_bytes: int | None = None
        self._sync_bytes_split: tuple = (0, 0)   # (ici, dcn) per level

    # ------------------------------------------------------------------
    # Round-sync engine (ISSUE 2): dense vs sharded reduce-scatter
    # ------------------------------------------------------------------
    def _resolve_sync_mode(self) -> str:
        """Pick the round-sync implementation from config + backend.

        Delegates to ``Config.resolve_sync_mode`` (per-topology: the
        bucketed reduce-scatter engine for allreduce, the bucketed
        ppermute gossip engine for ring/double-ring, the legacy per-leaf
        dense path otherwise).  Inner (TP/PP/EP) mesh axes do not
        force the dense path: psum_scatter / all_to_all / all_gather /
        ppermute over 'data' are bit-identical to the dense twin
        (tests/test_sync.py::TestShardedSyncInnerAxes).
        """
        return self.cfg.resolve_sync_mode(jax.default_backend())

    def _sync_body(self, params, grads, residual, round_opt=None,
                   poison=None, outer_residual=None):
        """The once-per-round sync point, per worker (inside shard_map).

        Returns ``(params', resident', residual', round_opt', buddy',
        ok, agg_grad_norm, outer_residual')``.  Weights mode replaces params with the
        aggregate (FedAvg) — under the resident layout (ISSUE 11) the
        program ENDS at the scatter instead: ``params'`` is None and
        ``resident'`` carries the post-apply 1/N bucket shards, the
        between-round state the next round's entry gather consumes.
        Gradients mode runs the collectives on the stale last-batch
        grads and reports only their norm (reference semantics,
        SURVEY.md 3.2) — plus, when the round-optimizer tracker is armed
        (ISSUE 9), the shard-resident Adam moment update of the
        aggregated mean gradient.

        ``buddy'`` (ISSUE 12) is the ring-successor copy of this
        worker's shard-resident spans when ``buddy_on`` (None
        otherwise); ``ok`` is this worker's fp32 contribution-validity
        flag when the NaN screen is armed and ``poison`` given (None
        otherwise)."""
        cfg = self.cfg
        agg_grad_norm = jnp.zeros(())
        resident = None
        buddy = None
        ok = None
        screen = poison is not None
        fast = self.sync_mode in ("sharded", "gossip")
        if self.sync_mode == "hier":
            # hierarchical two-level sync (ISSUE 13): inner sharded
            # allreduce over the data axis x outer gossip over the
            # slice axis, one program; the NaN screen / buddy hop are
            # not composed (chaos is rejected under --num_slices > 1)
            if screen:
                raise ValueError(
                    "the hierarchical sync does not take a poison flag "
                    "(--chaos is rejected under --num_slices > 1)")
            if cfg.aggregation_by == "weights":
                first, residual, outer_residual = comms.hierarchical_sync(
                    params,
                    residual=residual if self.sync_ef else None,
                    outer_residual=(outer_residual if self.sync_ef_outer
                                    else None),
                    **self._hier_kwargs())
                if self.resident_on:
                    resident, params = first, None
                else:
                    params = first
            else:
                # gradients mode: the reference's aggregate-and-discard
                # semantics through the hierarchical program — the
                # collectives run, only the norm is reported
                agg, _r, _o = comms.hierarchical_sync(
                    grads, **self._hier_kwargs(residency="replicated"))
                agg_grad_norm = self._grad_global_norm(agg)
            return params, resident, residual, round_opt, buddy, ok, \
                agg_grad_norm, outer_residual
        if cfg.aggregation_by == "weights":
            if self.resident_on:
                rets = comms.sharded_opt_sync(
                    params, buddy=self.buddy_on,
                    poison=poison if screen else None,
                    **self._fast_kwargs(residual if self.sync_ef
                                        else None))
                resident, residual = rets[0], rets[1]
                idx = 3
                if self.buddy_on:
                    buddy = rets[idx]
                    idx += 1
                if screen:
                    ok = rets[idx]
                params = None
            elif fast:
                params, residual, ok = self._fast_sync(
                    params, residual if self.sync_ef else None,
                    poison=poison)
            else:
                params, ok = self._dense_sync(params, poison)
        else:
            if self.round_opt_on:
                rets = comms.sharded_opt_sync(
                    grads, tracker=round_opt, buddy=self.buddy_on,
                    poison=poison if screen else None,
                    **self._fast_kwargs())
                agg, round_opt = rets[0], rets[2]
                idx = 3
                if self.buddy_on:
                    buddy = rets[idx]
                    idx += 1
                if screen:
                    ok = rets[idx]
            elif fast:
                agg, _, ok = self._fast_sync(grads, None, poison=poison)
            else:
                agg, ok = self._dense_sync(grads, poison)
            agg_grad_norm = self._grad_global_norm(agg)
        return params, resident, residual, round_opt, buddy, ok, \
            agg_grad_norm, outer_residual

    def _hier_kwargs(self, residency: str | None = None) -> dict:
        """Shared kwargs of the hierarchical sync calls (ISSUE 13): the
        outer topology is ``--topology`` (ring / double_ring over the
        slice axis), the per-level wire dtypes, and the engine's
        resolved residency (overridable — gradients mode always runs
        replicated, its aggregate is discarded)."""
        cfg = self.cfg
        return dict(topology=cfg.topology, how=cfg.aggregation_type,
                    local_weight=cfg.local_weight,
                    wire_dtype=self.sync_wire_dtype,
                    outer_wire_dtype=self.sync_wire_dtype_outer,
                    bucket_bytes=self.sync_bucket_bytes,
                    residency=residency or self.param_residency)

    def _dense_sync(self, tree, poison):
        """Legacy dense per-leaf aggregate, screen-aware: returns
        ``(aggregated, ok_or_None)``."""
        cfg = self.cfg
        if poison is not None:
            return comms.aggregate(
                tree, how=cfg.aggregation_type, topology=cfg.topology,
                local_weight=cfg.local_weight, poison=poison)
        return comms.aggregate(
            tree, how=cfg.aggregation_type, topology=cfg.topology,
            local_weight=cfg.local_weight), None

    def _fast_kwargs(self, residual=None) -> dict:
        """Shared kwargs of the bucketed sharded engine calls, including
        the resolved optimizer placement (the dense twin and gossip never
        see a placement — their arithmetic is per-leaf replicated /
        worker-local by construction)."""
        cfg = self.cfg
        placement = ("replicated" if self.opt_placement == "replicated"
                     else "sharded")
        return dict(how=cfg.aggregation_type,
                    local_weight=cfg.local_weight,
                    wire_dtype=self.sync_wire_dtype, residual=residual,
                    bucket_bytes=self.sync_bucket_bytes,
                    opt_placement=placement,
                    residency=self.param_residency)

    def _fast_sync(self, tree, residual, poison=None):
        """Run the resolved bucketed fast engine on one pytree:
        the reduce-scatter program for ``sharded``, the ppermute gossip
        program for ``gossip`` — same kwargs, same
        ``(out, new_residual, ok_or_None)`` contract."""
        if self.sync_mode == "gossip":
            kw = self._fast_kwargs(residual)
            # gossip has no apply stage to place and no scatter whose
            # output could stay resident (worker-local blends)
            kw.pop("opt_placement")
            kw.pop("residency")
            rets = comms.gossip_sync(tree, topology=self.cfg.topology,
                                     poison=poison, **kw)
        else:
            rets = comms.sharded_opt_sync(tree, poison=poison,
                                          **self._fast_kwargs(residual))
        return rets[0], rets[1], (rets[-1] if poison is not None
                                  else None)

    def _arm_sync_stats(self, params_stacked) -> None:
        """Reset ``last_sync_stats`` for the round being dispatched: the
        static per-round wire bytes (from the bucket plan over per-worker
        logical shapes) + mode + a zero ``sync_ms``; ``round_wait``
        overwrites ``sync_ms`` with the measured wait on the sync when a
        standalone sync program ran.  The schema is identical across all
        three topologies and every engine (zero-filled where a
        measurement does not apply), so a reader of ``round_timings`` can
        key on the fields unconditionally."""
        if self._sync_bytes is None:
            # the per-worker template is authoritative once set (the
            # resident layout's stacked params are bucket rows, not
            # leaf shapes); the stacked fallback serves template-less
            # replicated callers
            shapes = self.params_template
            if shapes is None:
                shapes = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                    params_stacked)
            if self.sync_mode == "hier":
                # per-LEVEL accounting (ISSUE 13): the inner sharded
                # engine's bytes ride ICI, the outer gossip hop's ride
                # DCN — the hop moves each bucket's 1/W scatter shard
                # in the outer wire dtype (tests/test_sync.py asserts
                # both exactly)
                split = comms.hier_wire_bytes(
                    shapes, self.n_inner, topology=self.cfg.topology,
                    wire_dtype=self.sync_wire_dtype,
                    outer_wire_dtype=self.sync_wire_dtype_outer,
                    bucket_bytes=self.sync_bucket_bytes)
                self._sync_bytes_split = (split["ici"], split["dcn"])
                self._sync_bytes = split["ici"] + split["dcn"]
            else:
                wire = (self.sync_wire_dtype
                        if self.sync_mode in ("sharded", "gossip")
                        else jnp.float32)
                self._sync_bytes = comms.sync_wire_bytes(
                    shapes, self.n_workers, mode=self.sync_mode,
                    wire_dtype=wire, bucket_bytes=self.sync_bucket_bytes,
                    topology=self.cfg.topology)
                # flat engines: every wire byte is one level (intra-slice
                # — "ICI-shaped" in the two-level schema), zero DCN
                self._sync_bytes_split = (self._sync_bytes, 0)
            if self.buddy_on:
                # ISSUE 12: the buddy hop's wire bytes ride the same
                # accounting — one extra ppermute per bucket carrying
                # the shard-resident rows (tests/test_sync.py asserts
                # redundancy-on == baseline + buddy_wire_bytes exactly)
                self._sync_bytes += comms.buddy_wire_bytes(
                    shapes, self.n_workers, wire_dtype=wire,
                    bucket_bytes=self.sync_bucket_bytes,
                    params=self.resident_on,
                    tracker=(self.round_opt_on
                             and self.opt_placement == "sharded"),
                    ef=self.resident_on and self.sync_ef)
                # the buddy hop is intra-slice wire (buddy_on implies a
                # flat mesh): its bytes ride the ICI level of the split
                self._sync_bytes_split = (self._sync_bytes, 0)
        ici, dcn = self._sync_bytes_split
        self.last_sync_stats = {"sync_bytes": self._sync_bytes,
                                "sync_mode": self.sync_mode,
                                "sync_ms": 0.0,
                                # ISSUE 16: portion of the sync wall that
                                # ran hidden under the next round's
                                # compute — zero-filled on synchronous
                                # runs (same convention as sync_ms); under
                                # staleness, row R+K+1 carries sync R's
                                # DELIVERED walls (the round at whose
                                # fence the delta landed)
                                "sync_hidden_ms": 0.0,
                                # per-level split (ISSUE 13): identical
                                # schema on every engine — flat rounds
                                # report all bytes as the intra-slice
                                # (ICI) level and zero DCN, hierarchical
                                # rounds the true split
                                "sync_bytes_ici": ici,
                                "sync_bytes_dcn": dcn}

    def _track(self, key, fn, name: str):
        """Install a freshly-built engine program into the round cache
        wrapped for compiled-memory observability (ISSUE 15): the
        TrackedProgram AOT-compiles on first call — the same one trace +
        one backend compile the jit path would pay — and retains the
        ``jax.stages.Compiled`` handle so ``memory_report`` reads
        ``memory_analysis()`` without re-lowering.  ``key=None`` tracks
        without caching (the standalone sync's inner program lives
        inside its run closure)."""
        label, i = name, 2
        while label in self._programs:
            label, i = f"{name}#{i}", i + 1
        tp = probe_lib.TrackedProgram(label, fn, built=self._built,
                                      build_span="round.build")
        self._programs[label] = tp
        if key is not None:
            self._round_cache[key] = tp
        return tp

    def take_builds(self) -> list[tuple[str, dict]]:
        """``(label, row)`` of every program traced, lowered and compiled
        (or loaded from the compile cache) since the last call, ``row``
        as ``probe.TrackedProgram._compile`` fills it: the driver folds
        them into the row of the round whose dispatch built them
        (``build_ms`` and its parts, ``programs_built``)."""
        built, self._built[:] = list(self._built), []
        return built

    def memory_programs(self) -> dict:
        """Label -> TrackedProgram registry of every cached engine
        program compiled so far (round / sync / resident enter-gather /
        streamed chunk programs / the sim vmap program) — the input of
        ``probe.memory_report`` and the driver's ``results["memory"]``
        row."""
        return dict(self._programs)

    def state_resident_bytes(self, state: TrainState) -> dict:
        """Per-worker RESIDENT bytes of each ``TrainState`` component
        (ISSUE 9 satellite: the N-fold optimizer-state drop as a measured
        number, not a claim).  Every leaf carries a leading worker axis
        sharded over ``data``, so a worker's share of a leaf is
        ``nbytes / N`` — for the sharded round-optimizer layout that is
        1/N of the tracked vector, for the replicated layout the whole
        vector (N identical copies across the axis).

        ISSUE 11 split: under the resident params layout ``params``
        counts the 1/N bucket-shard rows (the only between-round
        parameter state) and ``params_gathered_peak`` the TRANSIENT
        padded full buffers the round-entry gather materializes in
        compute scope — exactly N x the resident shard, the measured
        form of the N-fold residency drop.  Replicated layouts report
        the full tree under ``params`` and a zero peak (no transient
        copy exists beyond the resident one)."""
        def per_worker(tree) -> int:
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                size = int(np.prod(np.shape(leaf), dtype=np.int64))
                itemsize = np.dtype(leaf.dtype).itemsize
                rows = max(1, int(np.shape(leaf)[0])) if np.ndim(leaf) \
                    else 1
                total += size * itemsize // rows
            return total
        gathered_peak = 0
        if state.params is None and state.params_resident is not None:
            # the gather's transient buffers are the PADDED bucket
            # vectors — each resident leaf [N, padded/N] regathers to
            # [padded], i.e. the leaf's own nbytes.  Hierarchical
            # layouts (ISSUE 13) stack S slices of W shard rows
            # ([S*W, padded/W]), and the entry gather runs over the
            # inner axis only — each worker's transient buffer is still
            # ONE padded vector (its slice's), i.e. nbytes / S
            gathered_peak = sum(
                int(np.prod(np.shape(leaf), dtype=np.int64))
                * np.dtype(leaf.dtype).itemsize
                for leaf in jax.tree_util.tree_leaves(
                    state.params_resident)) // max(1, self.n_slices)
        return {"params": (per_worker(state.params)
                           + per_worker(state.params_resident)),
                "params_gathered_peak": gathered_peak,
                "opt_state": per_worker(state.opt_state),
                "ef_residual": per_worker(state.sync_residual),
                # ISSUE 13: the outer (DCN) EF residual — 1/W of the
                # packed vector per worker, by construction
                "ef_residual_outer": per_worker(state.sync_residual_outer),
                "round_opt": per_worker(state.round_opt),
                # ISSUE 12: the buddy copy's per-worker cost — one extra
                # shard-row set, i.e. ~1/N of each protected component
                "buddy": per_worker(state.buddy),
                # ISSUE 15: the remaining TrainState rows, so the
                # component sum IS the state's exact device footprint
                # (results["memory"] asserts analytic == actual leaf
                # bytes; the sim lab's stacked total must account every
                # byte or the N-ceiling model silently undercounts)
                "batch_stats": per_worker(state.batch_stats),
                "bookkeeping": (per_worker(state.lr_epoch)
                                + per_worker(state.rng))}

    def _derive_buddy_host(self, state: TrainState):
        """Host-derive the buddy rows a state implies (ISSUE 12): a
        small fetch of the shard-resident layouts (each ~1/N of the
        params), ring-rolled by ``comms.derive_buddy``.  Off the hot
        path by construction — used at init/restore/restage only (the
        round loop's copies come from the fused sync hop)."""
        fetch = lambda t: (None if t is None else
                           jax.tree_util.tree_map(np.asarray,
                                                  _host_fetch(t)))
        return comms.derive_buddy(
            self.params_template, self.n_workers,
            bucket_bytes=self.sync_bucket_bytes,
            params_resident=fetch(state.params_resident),
            round_opt=(fetch(state.round_opt)
                       if self.round_opt_on
                       and self.opt_placement == "sharded" else None),
            residual=fetch(state.sync_residual)
            if self.resident_on and self.sync_ef else None,
            opt_placement=self.opt_placement)

    def refresh_buddy(self, state: TrainState) -> TrainState:
        """Return ``state`` with its buddy rows (re)derived and staged —
        the checkpoint-restore path's completion step (buddy rows are
        stripped from checkpoints; see TrainState.buddy)."""
        if not self.buddy_on:
            return state
        bud = self._derive_buddy_host(state)
        return state.replace(buddy=jax.tree_util.tree_map(
            lambda x: self._put(x, self._spec), bud))

    def stage_poison(self, flags: np.ndarray):
        """Stage a per-worker poison vector for the NaN-screened round
        (ISSUE 12): an EXPLICIT device_put (transfer-guard-safe in the
        sanitized round loop) of ``[N]`` bools sharded over the worker
        axis."""
        arr = np.asarray(flags, np.bool_).reshape(self.n_workers)
        return self._put(arr, self._spec)

    def materialize_params(self, state: TrainState) -> PyTree:
        """HOST per-worker consensus params of a possibly
        scatter-resident state (ISSUE 11): the host twin of the
        round-entry gather — ``resident_consensus`` with the engine's
        template/bucket context, so consumers (final eval, inspection)
        see exactly the tree the round program would have gathered.
        Replicated states return their worker-0 row (every row is the
        consensus after an equal-blend sync; the general per-worker
        case keeps using ``state.params`` directly)."""
        if state.params is not None:
            return jax.tree_util.tree_map(_first_worker_row, state.params)
        return resident_consensus(state, self.params_template,
                                  self.sync_bucket_bytes,
                                  self.n_inner if self.slice_axis
                                  else None)

    def rank0_variables(self, state: TrainState) -> dict:
        """``train.rank0_variables`` with the engine's residency context
        threaded through — works on replicated AND scatter-resident
        states (the driver's probe / final-eval surface).  Hierarchical
        states take slice 0's consensus (rows 0..W-1), the resident twin
        of the replicated worker-0-row convention."""
        return rank0_variables(state, params_template=self.params_template,
                               bucket_bytes=self.sync_bucket_bytes,
                               n_inner=(self.n_inner if self.slice_axis
                                        else None))

    # ------------------------------------------------------------------
    # Multi-host data movement
    # ------------------------------------------------------------------
    # The worker (data) axis is laid out process-major over hosts
    # (mesh.build_mesh), so every [N, ...] worker-stacked array maps whole
    # leading-row blocks to whole processes.  Single-process: plain
    # device_put / device_get.  Multi-host: feed with
    # make_array_from_process_local_data (each process contributes its own
    # row block) and fetch with process_allgather (replicates the small
    # metric arrays to every host) — the multihost twins of the
    # reference's scatter/gather (SURVEY.md 2.4).

    def _local_rows(self, a: np.ndarray):
        n, p = a.shape[0], jax.process_count()
        if n % p:
            raise ValueError(
                f"worker axis ({n}) not divisible by process count ({p})")
        per = n // p
        lo = jax.process_index() * per
        return a[lo:lo + per]

    def _put(self, a, spec):
        sharding = NamedSharding(self.mesh, spec)
        if jax.process_count() == 1:
            out = jax.device_put(jnp.asarray(a), sharding)
            if isinstance(a, np.ndarray):
                # host-numpy source (elastic/checkpoint restage, not the
                # init_state device path): materialize an XLA-owned
                # buffer before the round program can DONATE it — on
                # XLA:CPU the put can zero-copy alias
                # numpy-owned malloc memory (checkpoint._reshard_leaf
                # documents the resulting heap corruption)
                out = jax.block_until_ready(out).copy()
            return out
        a = np.asarray(a)
        return jax.make_array_from_process_local_data(
            sharding, self._local_rows(a), a.shape)

    def _fetch(self, tree):
        if jax.process_count() == 1:
            return jax.device_get(tree)
        from jax.experimental import multihost_utils
        # tiled=True: global (non-fully-addressable) arrays come back as
        # their full global value on every host, no extra stacking axis
        return multihost_utils.process_allgather(tree, tiled=True)

    # ------------------------------------------------------------------
    # State init
    # ------------------------------------------------------------------
    def init_state(self, rng: jax.Array, sample_input: np.ndarray) -> TrainState:
        """Initialize one replica and broadcast it to all workers — the
        reference's Xavier init + rank-0 ``state_dict`` broadcast, which
        includes BN buffers (``main.py:33-46``)."""
        n = self.n_workers

        def _init(key):
            variables = self.model.init(key, jnp.asarray(sample_input),
                                        train=False)
            params = variables["params"]
            batch_stats = variables.get("batch_stats", {})
            opt_state = self.tx.init(params)
            return params, batch_stats, opt_state

        # one-shot per engine: init runs exactly once per train_global
        # graftlint: disable=R2 -- single Xavier-init trace, not a loop
        params, batch_stats, opt_state = jax.jit(_init)(rng)
        self.params_template = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        if self.param_specs_fn is not None and self.param_specs is None:
            # derive TP/PP/EP specs from the per-worker template while it
            # is in hand: stage_state's lazy fallback would otherwise pull
            # the whole n-stacked device tree to host just to read row 0
            self.param_specs = self.param_specs_fn(params)

        def tile(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (n, *x.shape)), tree)

        # resident residency (ISSUE 11): the broadcast init IS a
        # consensus (identical on every worker), so the between-round
        # layout starts scatter-resident from round 0 — every round
        # program then has the one shape (resident in, resident out) and
        # the sanitizer's zero-retrace budget holds from the warmup on
        # hierarchical meshes (ISSUE 13): the bucket tiling is per INNER
        # shard (padded // W) while the rows stack all S x W workers —
        # the broadcast-init consensus is every slice's consensus, so
        # the one shard set tiles across the slice groups
        resident = (comms.resident_from_tree(
            jax.device_get(params), self.n_inner,
            bucket_bytes=self.sync_bucket_bytes, n_rows=n)
            if self.resident_on else None)
        sync_residual = (jax.tree_util.tree_map(
            lambda x: jnp.zeros((n, *x.shape), jnp.float32), params)
            if self.sync_ef else None)
        sync_residual_outer = (comms.hier_outer_residual_init(
            params, self.n_inner, n,
            bucket_bytes=self.sync_bucket_bytes)
            if self.sync_ef_outer else None)
        round_opt = (comms.round_opt_init(
            params, n, placement=self.opt_placement,
            bucket_bytes=self.sync_bucket_bytes)
            if self.round_opt_on else None)
        state = TrainState(
            params=None if self.resident_on else tile(params),
            params_resident=resident,
            batch_stats=tile(batch_stats),
            opt_state=tile(opt_state),
            lr_epoch=jnp.zeros((n,), jnp.int32),
            rng=jax.vmap(lambda i: jax.random.key_data(
                jax.random.fold_in(jax.random.key(self.cfg.seed), i)))(
                    jnp.arange(n)),
            sync_residual=sync_residual,
            sync_residual_outer=sync_residual_outer,
            round_opt=round_opt,
            # ISSUE 12: the buddy copy exists from round 0 on (derivable
            # on host — ring-rolled rows of the layouts above), so every
            # round program has the one output structure and the
            # sanitizer's zero-retrace budget holds from the warmup
            buddy=(comms.derive_buddy(
                self.params_template, n,
                bucket_bytes=self.sync_bucket_bytes,
                params_resident=resident, round_opt=round_opt,
                residual=sync_residual,
                opt_placement=self.opt_placement)
                if self.buddy_on else None),
        )
        return self.stage_state(state)

    def stage_state(self, state: TrainState) -> TrainState:
        """Stage a worker-stacked ``TrainState`` (host numpy or device
        arrays) onto this engine's mesh with the engine's shardings.

        This is the PR 5 restore path promoted to an engine surface
        (ISSUE 8): ``init_state`` routes its freshly-tiled state through
        it, and the elastic membership layer hands it the row-edited
        HOST snapshot of the previous mesh's state — the in-process
        cross-mesh reshard.  Under TP/PP/EP the param specs are derived
        lazily from the state's own (squeezed) parameter structure, so a
        snapshot-restored engine never needs an ``init_state`` call."""
        if (self.resident_on and state.params_resident is None) or (
                not self.resident_on and state.params_resident is not None):
            raise ValueError(
                f"stage_state: state params residency does not match the "
                f"engine's ({self.param_residency!r}) — re-lay the host "
                "state out first (comms.resident_from_tree / "
                "resident_to_tree, or checkpoint.restore_checkpoint's "
                "cross-residency path)")
        if self.params_template is None and state.params is not None:
            self.params_template = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(tuple(np.shape(x)[1:]),
                                               np.dtype(x.dtype)),
                state.params)
        if self.buddy_on and state.buddy is None:
            # ISSUE 12: buddy rows are derivable (ring-rolled resident
            # rows) and deliberately absent from checkpoints and
            # redundancy-off snapshots — rebuild them here so every
            # restage lands a complete state whatever its source
            state = state.replace(buddy=self._derive_buddy_host(state))
        elif not self.buddy_on and state.buddy is not None:
            # a redundancy-on snapshot restaged into a redundancy-off
            # engine just drops the copy (it is derived state)
            state = state.replace(buddy=None)
        if self.param_specs_fn is not None:
            if self.param_specs is None:
                p0 = jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[0], state.params)
                self.param_specs = self.param_specs_fn(p0)
            if self._sspec is None:
                self._sspec = self._build_state_specs(state)
            return jax.tree_util.tree_map(
                lambda x, s: self._put(x, s), state, self._sspec)
        return jax.tree_util.tree_map(
            lambda x: self._put(x, self._spec), state)

    def _build_state_specs(self, state: TrainState):
        """Full-structure PartitionSpec tree for a worker-stacked
        TrainState under tensor parallelism: every leaf is sharded over
        ``data`` on the worker axis, and param-shaped leaves (params and
        the Adam moments that mirror them) additionally over ``model`` per
        ``self.param_specs``."""
        pfull = jax.tree_util.tree_map(
            lambda s: P(DATA_AXIS, *s), self.param_specs)
        dspec = lambda t: jax.tree_util.tree_map(lambda _: self._spec, t)

        def opt_specs(opt_state):
            # optax states are pytrees of namedtuples; map by structure:
            # any sub-tree with the params' treedef (the Adam moments)
            # gets the param specs, everything else is data-only
            pdef = jax.tree_util.tree_structure(state.params)
            def rec(node):
                try:
                    if jax.tree_util.tree_structure(node) == pdef:
                        return pfull
                except Exception:
                    pass
                if isinstance(node, tuple) and hasattr(node, "_fields"):
                    return type(node)(*(rec(c) for c in node))
                if isinstance(node, (list, tuple)):
                    return type(node)(rec(c) for c in node)
                if isinstance(node, dict):
                    return {k: rec(v) for k, v in node.items()}
                return self._spec
            return rec(opt_state)

        return TrainState(
            params=pfull, batch_stats=dspec(state.batch_stats),
            opt_state=opt_specs(state.opt_state),
            lr_epoch=self._spec, rng=self._spec,
            sync_residual=pfull if self.sync_ef else None,
            round_opt=dspec(state.round_opt))

    # ------------------------------------------------------------------
    # The round program
    # ------------------------------------------------------------------
    def _grad_global_norm(self, grads):
        """Global L2 norm of a gradient pytree whose leaves may be
        physically sharded over inner mesh axes (TP/PP/EP param specs):
        sharded leaves' sum-of-squares are psum'ed over their axes, so the
        result is invariant along every mesh axis (required for the
        P(data)-only metrics out_spec) and equals the true global norm."""
        if self.param_specs is None:
            return optax.global_norm(grads)
        # group local sum-of-squares by the leaf's sharded-axis set, then
        # ONE psum per group (not per leaf — keeps the collective count
        # independent of model depth)
        groups: dict[tuple, list] = {}
        for g, spec in zip(jax.tree_util.tree_leaves(grads),
                           jax.tree_util.tree_leaves(
                               self.param_specs,
                               is_leaf=lambda x: isinstance(x, P))):
            axes = tuple(dict.fromkeys(
                a for part in spec if part
                for a in (part if isinstance(part, tuple) else (part,))))
            groups.setdefault(axes, []).append(
                jnp.sum(jnp.square(g.astype(jnp.float32))))
        total = jnp.zeros(())
        for axes, sumsqs in groups.items():
            ss = sum(sumsqs)
            total = total + (lax.psum(ss, axes) if axes else ss)
        return jnp.sqrt(total)

    def _part_axes(self) -> tuple:
        """Mesh axes along which this device's batch is PARTIAL: the seq
        axis (one chunk of every sequence) and/or the fsdp axis (a slice
        of the worker's batch).  Loss denominators and metric sums psum
        over all of them."""
        return tuple(a for a in (self.seq_axis, self.fsdp_axis) if a)

    def _token_stats(self, out, yb, mb):
        if self.vp_axis is not None:
            from .parallel.tp import vocab_parallel_token_stats
            return vocab_parallel_token_stats(out, yb, mb, self.vp_axis)
        return masked_token_stats(out, yb, mb)

    def _labelled_row_sums(self, params, x, yb, mb):
        """``(sum(ce * w), correct, sum(w), head_rows)`` of a model that
        carries ``labelled_rows_head``, from its encoder output ``x``
        [B, L, H]: head, cross-entropy and argmax run on the labelled rows
        (label >= 0 in a real batch row), gathered labelled-first into a
        buffer of ``K`` rows, and on nothing else.  ``K`` is static: a
        quarter of the ``N = B * L`` positions, rounded up to a multiple
        of 128 (BERT's data pipeline fixes ``max_predictions_per_seq`` the
        same way); the filler rows are unlabelled positions at weight 0.

        Exact for every batch: the buffer is the body of a loop that runs
        as often as there are labelled rows to fill it, once as a rule
        (MLM labels 15%), ``ceil(count / K)`` times where more are
        labelled, so nothing of [N, vocab] ever exists.  ``head_rows`` is
        the rows the head ran on, ``K`` a pass."""
        n = yb.size
        k = min(n, -(-n // 512) * 128)
        rows = x.reshape(n, x.shape[-1])
        labels = yb.reshape(n)
        w = masked_weights(yb, mb).reshape(n)
        total = w.sum()
        # the functions below close over the model, never over ``self``:
        # the custom derivative's thunks live as long as the traced round
        # program, and an engine reachable from its own program's jaxpr
        # outlives the call that built it, compiled program and all
        model = self.train_model
        head_params = {name: params[name]
                       for name in model.labelled_rows_head}

        def sums(head_params, rows, labels, w):
            logits = model.apply({"params": head_params}, rows, mode="head")
            ce, w, correct = masked_token_stats(logits, labels, w)
            return (ce * w).sum(), correct

        if k == n:
            return (*sums(head_params, rows, labels, w), total,
                    jnp.float32(n))
        passes = jnp.maximum(jnp.ceil(total / k), 1.0).astype(jnp.int32)
        order = jnp.argsort(w == 0, stable=True)

        def buffer(head_params, rows, c):
            # ranks c*k .. c*k + k - 1 of ``order``; the last buffer is
            # moved back to end at n, and the ranks it then shares with
            # the buffer before it weigh 0
            start = jnp.minimum(c * k, n - k)
            idx = lax.dynamic_slice(order, (start,), (k,))

            def take(a):
                return a.at[idx].get(mode="promise_in_bounds",
                                     unique_indices=True)

            fresh = start + jnp.arange(k) >= c * k
            return sums(head_params, take(rows), take(labels),
                        take(w) * fresh)

        def walk(one):
            """``one(c)`` summed over the passes.  One loop body serves
            the single pass and the rare further ones alike: a ``cond``
            around a second copy of the head was 16 MB more program text
            a step on the v5e, which the device holds in HBM."""
            return lax.fori_loop(
                0, passes, lambda c, acc: jax.tree_util.tree_map(
                    jnp.add, acc, one(c)),
                _zeros_like_varying(jax.eval_shape(one, jnp.int32(0))))

        # A loop of a dynamic trip count has no reverse-mode derivative,
        # so the derivative is stated by hand: a pass's value and gradient
        # are taken together inside the loop, and the backward pass scales
        # the summed gradient.
        @jax.custom_vjp
        def head(head_params, rows):
            return walk(lambda c: buffer(head_params, rows, c))

        def head_fwd(head_params, rows):
            return walk(lambda c: jax.value_and_grad(
                buffer, argnums=(0, 1), has_aux=True)(head_params, rows, c))

        def head_bwd(grads, cotangent):
            return jax.tree_util.tree_map(
                lambda g: (cotangent[0] * g).astype(g.dtype), grads)

        head.defvjp(head_fwd, head_bwd)
        num, correct = head(head_params, rows)
        return num, correct, total, (k * passes).astype(jnp.float32)

    def _onef1b_loss_and_metrics(self, params, batch_stats, xb, yb, mb,
                                 denom=None, aux_div=1.0):
        """1F1B train-step loss: embeddings and the per-microbatch head +
        CE run through ``parallel.pp.onef1b_loss`` (the fwd+bwd schedule
        as a custom-VJP function), so an outer ``value_and_grad`` over
        ``params`` composes: stage grads come from the schedule, while
        embedding grads flow through the returned input cotangent (tied
        heads — GPT's tok_emb — get both contributions summed by the
        chain rule automatically).  The masked-mean loss stays exact
        because its denominator is data-derived and computed up front.

        ``denom``/``aux_div``: external full-step denominator + aux-loss
        divisor from the gradient-accumulation wrapper (this call then
        sees ONE microbatch slice and returns its numerator share —
        ``_accum_value_and_grad``)."""
        from .parallel.pp import onef1b_loss
        tm = self.train_model
        mnum = tm.num_microbatches or tm.pp_size
        b = xb.shape[0]
        if self.fsdp_axis:
            # 1F1B x FSDP (r5): ZeRO-3 shards gather to full params HERE,
            # OUTSIDE the custom-VJP schedule — the schedule then runs on
            # full params with no fsdp collectives inside any tick, and
            # the gradient reduce-scatter is the gather's transpose in
            # the OUTER vjp, downstream of onef1b_loss's returned full
            # grads.  The fsdp axis splits the worker batch, so the
            # masked-mean denominator psums over it (grads then sum to
            # the full-batch gradient exactly, as in the standard path).
            from .parallel.fsdp import gather_params
            params = gather_params(params, self.param_specs,
                                   self.fsdp_axis)
        emb = tm.apply({"params": params}, xb, train=True, mode="embed")
        ys = yb.reshape(mnum, b // mnum, *yb.shape[1:])
        mbs = mb.reshape(mnum, b // mnum, *mb.shape[1:])
        w = masked_weights(yb, mb)
        ws = w.reshape(mnum, b // mnum, *w.shape[1:])
        external_denom = denom is not None
        if not external_denom:
            denom = w.sum()
        part = self._part_axes()
        if part and not external_denom:
            # the batch is PARTIAL on this device (fsdp slice of the
            # worker batch and/or one seq chunk of every sequence): the
            # masked-mean denominator is global, while each loss_fn
            # below returns its LOCAL numerator over it — the 1F1B twin
            # of the standard path's construction, so the cross-device
            # gradient reduction (train_step's psum over seq /
            # reduce-scatter over fsdp) sums to grad(global loss) with
            # NO collective inside the schedule's head slot.
            denom = lax.psum(denom, part)
            # ORDER this mask-only psum BEFORE the schedule's pipe
            # ppermutes on every device: it is otherwise DAG-independent
            # of them, and intersecting-group collectives entered in
            # different per-device orders deadlock the unpinned XLA:CPU
            # rendezvous (the same race the standard path barriers at
            # its metrics psum; free on TPU)
            emb = lax.optimization_barrier((emb, denom))[0]
        xs = emb.reshape(mnum, b // mnum, *emb.shape[1:])
        denom = jnp.maximum(denom, 1.0)  # data-derived: known pre-schedule
        stage_params = params["layers"]
        head_params = {k: v for k, v in params.items() if k != "layers"}
        has_moe = getattr(tm, "num_experts", 0) > 0
        aux_w = None
        if has_moe:
            # 1F1B x MoE (r5): the stage applies with mutable aux so the
            # sown load-balance losses are captured (a plain apply would
            # silently drop them); each microbatch contributes 1/m of
            # the full-batch aux scale, further averaged over any
            # batch-partial axes exactly as the standard path does
            # aux_div: the accumulation wrapper averages the per-slice
            # aux losses over its K microbatches too
            aux_w = self.cfg.moe_aux_weight / mnum / aux_div
            for ax in part:
                aux_w = aux_w / self.mesh.shape[ax]

        def stage_fn(sp, x):
            if has_moe:
                y, mut = tm.apply({"params": {"layers": sp}}, x,
                                  train=True, mode="stage",
                                  mutable=["aux"])
                a = sum(jnp.sum(l) for l in
                        jax.tree_util.tree_leaves(mut["aux"]))
                return y, a.astype(jnp.float32)
            return tm.apply({"params": {"layers": sp}}, x, train=True,
                            mode="stage")

        def loss_fn(hp, y, i):
            logits = tm.apply({"params": hp}, y, train=True, mode="head")
            if self.vp_axis is not None:
                # 1F1B x TP (r5): the head emitted its LOCAL vocab slice;
                # the Megatron vocab-parallel CE psums over 'model' inside
                # the schedule — legal because the schedule's cond
                # predicates are uniform across each model-group
                # (parallel/pp.py tick)
                from .parallel.tp import vocab_parallel_token_stats
                ce, w_i, correct_i = vocab_parallel_token_stats(
                    logits, ys[i], mbs[i], self.vp_axis)
                return (ce * w_i).sum() / denom, (correct_i, w_i.sum())
            ce = softmax_cross_entropy(logits, jnp.maximum(ys[i], 0))
            w_i = ws[i]
            loss_i = (ce * w_i).sum() / denom
            correct_i = ((logits.argmax(-1) == ys[i]) * w_i).sum()
            return loss_i, (correct_i, w_i.sum())

        loss, (correct, total) = onef1b_loss(
            stage_fn, loss_fn, stage_params, head_params, xs,
            axis_name=self.pipe_axis, num_micro=mnum,
            # ring/Ulysses attention puts ppermutes/all-to-alls inside
            # the slots; a ppermute under a pipe-varying cond predicate
            # miscomputes (parallel/pp.py r5 note), so SP runs the
            # schedule with GPipe-style masked slots instead of skips
            masked_slots=self.seq_axis is not None,
            stage_aux_weight=aux_w)
        if part:
            # schedule aux counted this device's batch slice / seq chunk
            correct = lax.psum(correct, part)
            total = lax.psum(total, part)
        return loss, (batch_stats, correct, total, {})

    def _loss_and_metrics(self, params, batch_stats, xb, yb, mb,
                          denom=None, aux_div=1.0):
        """Step loss + aux metrics.  ``denom`` (full-step masked-weight
        sum, already psum'd over batch-partial axes and floored at 1) and
        ``aux_div`` come from the gradient-accumulation wrapper: this
        call then sees ONE microbatch slice and returns its numerator
        over the shared denominator, so the K slice losses/grads SUM to
        the full-batch step's (``_accum_value_and_grad``).

        The aux's last element is the step's ``counters``: what the model
        sowed into that collection (``models.moe.RoutedExperts``: rows on
        held experts, fullest expert over the mean), each name averaged
        over the layers that sowed it; ``{}`` for a model that sows
        none."""
        if self.onef1b:
            return self._onef1b_loss_and_metrics(params, batch_stats,
                                                 xb, yb, mb, denom=denom,
                                                 aux_div=aux_div)
        if self.fsdp_axis:
            # ZeRO-3: shards -> full params just-in-time; grad of this
            # all_gather is reduce-scatter, so each device's gradient tree
            # comes back already sharded (parallel/fsdp.py)
            from .parallel.fsdp import gather_params
            params = gather_params(params, self.param_specs, self.fsdp_axis)
        out, mut = self.train_model.apply(
            {"params": params, "batch_stats": batch_stats}, xb, train=True,
            mutable=["batch_stats", "aux", "counters"],
            **({"mode": "encode"} if self.labelled_rows_head else {}))
        counters = _mean_by_name(mut.get("counters", {}))
        if self.labelled_rows_head:
            # ``out`` is the encoder's output; one device a worker, so no
            # batch-partial axis: the masked mean, or the accumulation
            # slice's numerator over the external denominator
            num, correct, total, counters["head_rows"] = \
                self._labelled_row_sums(params, out, yb, mb)
            loss = num / (jnp.maximum(total, 1.0) if denom is None
                          else denom)
        else:
            ce, w, correct = self._token_stats(out, yb, mb)
            part_axes = self._part_axes()
            if denom is not None:
                # accumulation microbatch: local numerator over the external
                # full-step denominator; correct/total stay slice-local sums
                # psum'd over batch-partial axes exactly as below, so the
                # wrapper's running sums match the full-batch step's values
                if part_axes:
                    w = lax.optimization_barrier((w, ce))[0]
                loss = (ce * w).sum() / denom
                total = w.sum()
                if part_axes:
                    correct = lax.psum(correct, part_axes)
                    total = lax.psum(total, part_axes)
            elif part_axes:
                # ORDER the mask-only psums below after the model's own
                # collectives: ``w`` derives from the batch mask alone, so its
                # psums are otherwise DAG-independent of the forward pass and
                # the XLA:CPU thunk executor may start them concurrently with
                # the model's ppermutes on different devices — intersecting-
                # group collectives entered in different per-device orders
                # deadlock the CPU collective rendezvous (reproduced by
                # SP x PP stress runs; 40 s timeout then SIGABRT).  Routing
                # ``w`` through a barrier with ``ce`` (which depends on the
                # model output) serializes them; free on TPU.
                w = lax.optimization_barrier((w, ce))[0]
                # the batch is partial on this device: under seq parallelism it
                # holds one chunk of every sequence, under FSDP a slice of the
                # worker's batch (composable — psum over both).  The loss is
                # the GLOBAL masked mean; returning the local numerator over
                # the global denominator makes the cross-device gradient
                # reduction (psum over seq / reduce-scatter over fsdp) equal
                # grad(global loss).
                denom = jnp.maximum(lax.psum(w.sum(), part_axes), 1.0)
                loss = (ce * w).sum() / denom
                correct = lax.psum(correct, part_axes)
                total = lax.psum(w.sum(), part_axes)
            else:
                loss = _masked_mean(ce, w)
                total = w.sum()
        # MoE load-balance auxiliary losses sown by models/moe.py.  Leaves
        # may be stacked: [n_local] under scan_layers, [steps, n_local]
        # under the GPipe schedule (bubble steps sown as exact zeros and
        # valid steps pre-scaled by 1/M — parallel/pp.py), so each leaf is
        # summed fully.  Under pipeline parallelism the sum is per-stage
        # partial; psum over 'pipe' restores the pipe-invariant loss the
        # replicated-gradient construction relies on.
        aux = jax.tree_util.tree_leaves(mut.get("aux", {}))
        if aux:
            a = sum(jnp.sum(x) for x in aux)
            if self.pipe_axis is not None:
                a = lax.psum(a, self.pipe_axis)
            part_aux = self._part_axes()
            if part_aux:
                # each fsdp slice / seq chunk routed its own tokens and
                # sowed its own load-balance loss; average so the cross-
                # device gradient reduction recovers full-batch aux scale
                # rather than multiplying it by the axis sizes (r5
                # FSDP x MoE, MoE x SP)
                denom_aux = 1.0
                for ax in part_aux:
                    denom_aux = denom_aux * lax.axis_size(ax)
                a = a / denom_aux
            # aux_div: the accumulation wrapper averages the K per-slice
            # aux losses (per-slice routing/capacity — the same declared
            # semantics shift as per-microbatch routing under GPipe)
            loss = loss + self.cfg.moe_aux_weight * a / aux_div
        new_bs = mut.get("batch_stats", batch_stats)
        if self.fsdp_axis and jax.tree_util.tree_leaves(new_bs):
            # BatchNorm under FSDP: each device normalized its sub-batch
            # with its own statistics (standard DP BatchNorm); the running
            # stats are averaged so the stored tree stays replicated along
            # the fsdp axis
            new_bs = lax.pmean(new_bs, self.fsdp_axis)
        return loss, (new_bs, correct, total, counters)

    def _accum_value_and_grad(self, params, batch_stats, xb, yb, mb):
        """Microbatch gradient accumulation (ISSUE 3): split the step's
        batch into ``grad_accum`` slices and ``lax.scan`` them with an
        fp32 gradient carry (donated in place by XLA's loop buffer
        reuse), so peak activation memory is that of ONE slice.

        Exactness: the full-step masked-weight denominator is computed up
        front (psum'd over batch-partial axes like the standard path), so
        each slice returns its loss NUMERATOR over the shared denominator
        and its gradient — both of which SUM over slices to the
        full-batch step's values, up to fp32 summation order.  Returns
        the same ``((loss, (batch_stats, correct, total)), grads)``
        contract as the K=1 ``value_and_grad`` call."""
        k = self.grad_accum
        b = xb.shape[0]
        xs = xb.reshape(k, b // k, *xb.shape[1:])
        ys = yb.reshape(k, b // k, *yb.shape[1:])
        ms = mb.reshape(k, b // k, *mb.shape[1:])
        denom = masked_weights(yb, mb).sum()
        part = self._part_axes()
        if part:
            denom = lax.psum(denom, part)
            # ORDER this mask-only psum before the model collectives of
            # every slice (same XLA:CPU rendezvous hazard the standard
            # path barriers at its metrics psum; free on TPU)
            xs = lax.optimization_barrier((xs, denom))[0]
        denom = jnp.maximum(denom, 1.0)

        def micro(g, inp):
            x_k, y_k, m_k = inp
            (loss_k, (_bs, c_k, t_k, n_k)), g_k = jax.value_and_grad(
                self._loss_and_metrics, has_aux=True)(
                    params, batch_stats, x_k, y_k, m_k,
                    denom=denom, aux_div=float(k))
            g = jax.tree_util.tree_map(
                lambda a, d: a + d.astype(jnp.float32), g, g_k)
            # the scalars ride as stacked scan OUTPUTS — ys have no
            # carry type-matching constraint on either runtime — and
            # sum after the loop
            return g, (loss_k, c_k, t_k, n_k)

        zeros = _zeros_like_varying(params, dtype=jnp.float32,
                                    extra_axes=part)
        grads, (losses, corrects, totals, counters) = lax.scan(
            micro, zeros, (xs, ys, ms))
        loss, correct, total = losses.sum(), corrects.sum(), totals.sum()
        counters = jax.tree_util.tree_map(lambda c: c.mean(0), counters)
        if CHOICE_COUNTS in counters:      # counts add up over the slices
            counters[CHOICE_COUNTS] = jax.tree_util.tree_map(
                lambda c: c * k, counters[CHOICE_COUNTS])
        # batch_stats pass through unchanged: accumulation is gated to
        # models without BatchNorm (driver validates), so the tree is
        # empty and the step's _tree_where keeps it as-is
        return (loss, (batch_stats, correct, total, counters)), grads

    def _make_step_fns(self, augment: bool):
        """The shared per-batch bodies: one SGD step and one eval step.
        Used by both the whole-round program and the streamed chunk
        programs, so their numerics are identical by construction."""

        def train_step(carry, inp):
            params, batch_stats, opt_state, rng, lr = carry[:5]
            xb, yb, mb = inp
            rng, k = jax.random.split(jax.random.wrap_key_data(rng))
            rng = jax.random.key_data(rng)
            if augment:
                if self.fsdp_axis:
                    # the per-worker key is replicated along fsdp while the
                    # batch is split over it: decorrelate so each device's
                    # slice gets independent per-image draws
                    k = jax.random.fold_in(
                        k, lax.axis_index(self.fsdp_axis))
                xb = augment_batch(k, xb)
            if self.grad_accum > 1:
                (loss, (new_bs, correct, total, counters)), grads = \
                    self._accum_value_and_grad(params, batch_stats,
                                               xb, yb, mb)
            else:
                (loss, (new_bs, correct, total, counters)), grads = \
                    jax.value_and_grad(
                        self._loss_and_metrics, has_aux=True)(
                            params, batch_stats, xb, yb, mb)
            if self.seq_axis:
                # combine per-chunk grad contributions; params (and the
                # Adam update below) stay replicated along seq
                grads = lax.psum(grads, self.seq_axis)
            if self.fsdp_axis:
                # sharded leaves' grads arrived reduce-scattered (all_gather
                # transpose); replicated leaves still need their per-device
                # partials summed
                from .parallel.fsdp import reduce_replicated_grads
                grads = reduce_replicated_grads(grads, self.param_specs,
                                                self.fsdp_axis)
            if self._part_axes():
                # loss metric: global mean = sum of per-device local
                # numerators over the shared psum'd denominator
                loss = lax.psum(loss, self._part_axes())
            updates, new_opt = self.tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(
                params, jax.tree_util.tree_map(lambda u: -lr * u, updates))
            # the one change to a parameter that is no optimizer's: the
            # routers' selection biases follow this step's loads (masked
            # below like every other change of the state)
            counts = counters.pop(CHOICE_COUNTS, None)
            if counts:
                new_params = move_select_bias(new_params, counts,
                                              self.router_bias_step)
            # fully-masked (padding) steps leave everything untouched —
            # including the carried last-real-batch grads, so gradients
            # mode aggregates each worker's stale last REAL gradient
            # (reference semantics) rather than a padding step's zeros
            do = total > 0
            params = _tree_where(do, new_params, params)
            batch_stats = _tree_where(do, new_bs, batch_stats)
            opt_state = _tree_where(do, new_opt, opt_state)
            grads = _tree_where(do, grads, carry[5])
            return ((params, batch_stats, opt_state, rng, lr, grads),
                    (loss, correct, total, counters))

        def eval_step(carry, inp):
            # NOTE: under FSDP the carry must hold FULL params — callers
            # gather once before the scan (params are loop-invariant during
            # eval; a per-batch all_gather would be pure waste)
            params, batch_stats = carry
            xb, yb, mb = inp
            if self.labelled_rows_head:
                enc = self.train_model.apply(
                    {"params": params, "batch_stats": batch_stats}, xb,
                    train=False, mode="encode")
                num, correct, total, _ = self._labelled_row_sums(
                    params, enc, yb, mb)
                return carry, (num, correct, total)
            out = self.train_model.apply(
                {"params": params, "batch_stats": batch_stats}, xb,
                train=False)
            ce, w, correct = self._token_stats(out, yb, mb)
            sums = ((ce * w).sum(), correct, w.sum())
            if self._part_axes():
                sums = lax.psum(sums, self._part_axes())
            return carry, sums

        return train_step, eval_step

    def _make_local_round(self, augment: bool):
        """Builder for the LOCAL phase of one worker's round —
        ``epochs_local`` x (train scan + per-epoch validation scan) with
        the StepLR clock — containing NO cross-worker collectives.

        ONE definition serves two executions (ISSUE 14): the real round
        program runs it per worker inside ``shard_map`` (``_build_round``
        adds the avg_acc/global-metric pmeans and the sync point around
        it), and the many-worker simulator (sim.py) ``jax.vmap``s it over
        the stacked worker axis — hundreds of simulated workers in one
        jit on one chip.  Keeping the body collective-free is what makes
        the one definition serve both, and the N=8 simulated-vs-real
        bitwise gate mechanical.

        ``lr_scale`` (sim scenario surface: per-worker LR jitter)
        multiplies the StepLR output when given; ``None`` leaves the real
        path's arithmetic byte-for-byte untouched.

        Returns ``local_round(params0, batch_stats0, opt_state0,
        lr_epoch0, rng0, x, y, m, xv, yv, mv, lr_scale=None) ->
        ((params, batch_stats, opt_state, lr_epoch, rng, last_grads),
        per_epoch)`` with ``per_epoch`` the [E]-stacked dict
        (batch_losses/batch_mask/train_loss/train_acc/val_loss/val_acc —
        the cross-worker ``avg_acc`` is the caller's to add)."""
        cfg = self.cfg
        epochs_local = cfg.epochs_local
        train_step, eval_step = self._make_step_fns(augment)

        def local_round(params0, batch_stats0, opt_state0, lr_epoch0,
                        rng0, x, y, m, xv, yv, mv, lr_scale=None):
            zero_grads = _zeros_like_varying(params0)

            def local_epoch(carry, _):
                params, batch_stats, opt_state, lr_epoch, rng, _ = carry
                lr = steplr(cfg.lr, cfg.lr_gamma, cfg.lr_step_size,
                            lr_epoch)
                if lr_scale is not None:
                    lr = lr * lr_scale
                (params, batch_stats, opt_state, rng, _, last_grads), \
                    (losses, corrects, totals, counters) = lax.scan(
                        train_step,
                        (params, batch_stats, opt_state, rng, lr,
                         zero_grads),
                        (x, y, m))
                if self.router_bias_step:
                    # how far this local epoch's steps moved the selection
                    # biases, net: mean |after - before| over layers and
                    # experts, as a row key beside the per-step counters
                    moved = jnp.mean(jnp.stack([
                        jnp.abs(after - before).mean() for before, after
                        in zip(_select_biases(carry[0]),
                               _select_biases(params))]))
                    counters = dict(counters, select_bias_moved=(
                        jnp.broadcast_to(moved, losses.shape)))
                # reference per-epoch scalars: loss = mean over real batches
                # (trainer.py:220), accuracy = 100*correct/total (:221)
                real_step = (totals > 0).astype(jnp.float32)
                train_loss = _masked_mean(losses, real_step)
                train_acc = 100.0 * corrects.sum() / jnp.maximum(
                    totals.sum(), 1.0)
                # validation on the worker's own val shard every local epoch
                # (trainer.py:105-107); FSDP: one gather for the whole scan
                eval_params = params
                if self.fsdp_axis:
                    from .parallel.fsdp import gather_params
                    eval_params = gather_params(
                        params, self.param_specs, self.fsdp_axis)
                _, (vls, vcs, vts) = lax.scan(
                    eval_step, (eval_params, batch_stats), (xv, yv, mv))
                val_loss = vls.sum() / jnp.maximum(vts.sum(), 1.0)
                val_acc = 100.0 * vcs.sum() / jnp.maximum(vts.sum(), 1.0)
                lr_epoch = lr_epoch + 1
                per_epoch = dict(
                    batch_losses=losses, batch_mask=real_step,
                    train_loss=train_loss, train_acc=train_acc,
                    val_loss=val_loss, val_acc=val_acc,
                    # per training step, [S] each; {} where none is sown
                    counters=counters)
                return ((params, batch_stats, opt_state, lr_epoch, rng,
                         last_grads), per_epoch)

            carry0 = (params0, batch_stats0, opt_state0, lr_epoch0, rng0,
                      zero_grads)
            return lax.scan(local_epoch, carry0, None, length=epochs_local)

        return local_round

    def _build_round(self, shapes_key):
        cfg = self.cfg
        augment = cfg.augment and len(shapes_key[0]) == 5  # [S,B,H,W,C]
        local_round = self._make_local_round(augment)

        # the fused (CPU) sync point screens contributions when the NaN
        # screen is armed: the round program then takes the per-worker
        # poison flag and emits per-worker validity; under split_sync
        # the standalone sync program carries both instead
        fused_screen = self.nan_screen and not self.split_sync

        def per_worker(state: TrainState, x, y, m, xv, yv, mv,
                       poison=None):
            """One worker's round.  x:[S,B,...] y,m:[S,B]; val likewise."""
            if self.resident_on:
                # ISSUE 11 round-entry gather: the between-round state is
                # the 1/N bucket shard of the consensus; the full tree is
                # reconstructed HERE, inside the donated round program, so
                # the gathered copy is transient compute-scope memory —
                # bit-for-bit the tree the replicated twin carried (the
                # gather moves the exact bytes the sync-exit gather used
                # to)
                params0 = comms.resident_gather(
                    state.params_resident, self.params_template,
                    bucket_bytes=self.sync_bucket_bytes)
            else:
                params0 = state.params
            (params, batch_stats, opt_state, lr_epoch, rng, last_grads), \
                per_epoch = local_round(
                    params0, state.batch_stats, state.opt_state,
                    state.lr_epoch, state.rng, x, y, m, xv, yv, mv)
            # cross-worker mean accuracy per local epoch (trainer.py:50-53)
            # — over the WHOLE worker grid: (slice, data) on a hierarchical
            # mesh (ISSUE 13).  Elementwise over the [E]-stacked outputs,
            # i.e. the same per-epoch pmeans the scan used to carry,
            # hoisted out so the local phase stays collective-free (shared
            # with the vmap'd simulator, ISSUE 14).
            per_epoch = dict(per_epoch, avg_acc=lax.pmean(
                per_epoch["train_acc"], self._stack_axes))

            # --- the sync point (trainer.py:141-150) -----------------------
            # On CPU the sync engine (dense per-leaf, the sharded
            # reduce-scatter, or the bucketed gossip — _sync_body) runs
            # fused HERE; under
            # split_sync the round program stops pre-sync and round_start
            # dispatches the standalone donated sync program right behind
            # it (measured collective wall, two-rounds-in-flight chain).
            agg_grad_norm = jnp.zeros(())
            residual = state.sync_residual
            outer_residual = state.sync_residual_outer
            round_opt = state.round_opt
            resident = None
            new_buddy = None
            sync_ok = None
            if not self.split_sync:
                params, resident, residual, round_opt, new_buddy, \
                    sync_ok, agg_grad_norm, outer_residual = \
                    self._sync_body(
                        params, last_grads, residual, round_opt,
                        poison=poison, outer_residual=outer_residual)

            # cross-worker global-epoch metric means (trainer.py:152-162)
            metrics = dict(
                per_epoch,
                agg_grad_norm=agg_grad_norm,
                global_train_loss=lax.pmean(
                    per_epoch["train_loss"].mean(), self._stack_axes),
                global_train_acc=lax.pmean(
                    per_epoch["train_acc"].mean(), self._stack_axes),
                global_val_loss=lax.pmean(
                    per_epoch["val_loss"].mean(), self._stack_axes),
                global_val_acc=lax.pmean(
                    per_epoch["val_acc"].mean(), self._stack_axes),
            )
            if sync_ok is not None:
                metrics = dict(metrics, sync_ok=sync_ok)
            new_state = TrainState(params=params, params_resident=resident,
                                   batch_stats=batch_stats,
                                   opt_state=opt_state, lr_epoch=lr_epoch,
                                   rng=rng, sync_residual=residual,
                                   round_opt=round_opt, buddy=new_buddy,
                                   sync_residual_outer=outer_residual)
            if emit_grads:
                # split_sync x gradients mode: the standalone sync program
                # aggregates the stale last-batch grads, so the round
                # program must surface them
                return new_state, last_grads, metrics
            return new_state, metrics

        def stacked(state, x, y, m, xv, yv, mv, *rest):
            squeeze = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
            expand = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
            poi = squeeze(rest[0]) if rest else None
            outs = per_worker(
                squeeze(state), *map(lambda a: a[0], (x, y, m, xv, yv, mv)),
                poison=poi)
            return tuple(map(expand, outs))

        # the compiled module is ``jit_<name>``: what the device trace
        # calls this program
        stacked.__name__ = "localsgd_round"
        sspec = self._sspec if self._sspec is not None else self._spec
        pspec = self._sspec.params if self._sspec is not None else self._spec
        emit_grads = self.split_sync and cfg.aggregation_by == "gradients"
        in_specs = (sspec,) + self._pack_specs(shapes_key) * 2
        if fused_screen:
            in_specs = in_specs + (self._spec,)
        out_specs = ((sspec, pspec, self._spec) if emit_grads
                     else (sspec, self._spec))
        fn = jax.shard_map(
            stacked, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs)
        return jax.jit(fn, donate_argnums=(0,))

    def _pack_specs(self, shapes_key=None):
        """(x, y, m) PartitionSpecs for one pack.  Token tasks under
        sequence parallelism shard the sequence dim of x [N,S,B,L] and y
        [N,S,B,L] over the seq axis; under FSDP the batch dim (index 2) of
        all three shards over the fsdp axis (an inner data axis); the two
        compose (B over fsdp, L over seq)."""
        bdim = self.fsdp_axis  # None or the axis name
        if self.seq_axis:
            tok = P(DATA_AXIS, None, bdim, self.seq_axis)
            return (tok, tok, P(DATA_AXIS, None, bdim))
        if bdim:
            return (P(DATA_AXIS, None, bdim),) * 3
        return (self._spec,) * 3

    def _inner_specs(self):
        """Spec for the streamed-round inner carry
        (params, batch_stats, opt_state, rng, grads)."""
        if self._sspec is None:
            return self._spec
        return (self._sspec.params, self._sspec.batch_stats,
                self._sspec.opt_state, self._spec, self._sspec.params)

    def stage_pack(self, train_pack, val_pack):
        """Stage numpy round packs onto device ahead of dispatch.

        The overlapped driver calls this from its prepare step while the
        PREVIOUS round is still computing, so the host->device transfer
        of round r+1's inputs rides under round r's device time;
        ``round_start`` accepts the staged arrays as-is."""
        xs, ys, ms = self._pack_specs()
        put = self._put
        stage = lambda p: (put(p[0], xs), put(p[1], ys), put(p[2], ms))
        return stage(train_pack), stage(val_pack)

    def round_start(self, state: TrainState, train_pack, val_pack,
                    poison=None):
        """Stage (if not already staged) + dispatch one global epoch
        WITHOUT blocking on it.

        Packs are numpy stacks (x [N,S,B,...], y [N,S,B], m [N,S,B]) or
        the device triples ``stage_pack`` returns.  Returns
        ``(new_state, handle)``: ``new_state`` is the
        asynchronously-computing round output (the input ``state``'s
        buffers are DONATED to the round program — the caller must not
        touch them again), and ``handle`` feeds ``finish_metrics`` (from
        any thread) to obtain the round's host metric arrays.  Callers
        must ``round_wait`` before dispatching the next round — at most
        one round program in flight (1-core CPU hosts deadlock on
        pipelined collective rendezvous).

        ``poison`` (ISSUE 12, NaN-screened engines only): the staged
        [N]-bool per-worker poison vector (``stage_poison``); defaults
        to all-clear.  The previous round's buddy rows are NOT an input
        of any program — they are dropped here (the sync writes the
        fresh copy) so the whole remaining state donates cleanly."""
        if not isinstance(train_pack[0], jax.Array):
            train_pack, val_pack = self.stage_pack(train_pack, val_pack)
        if state.buddy is not None:
            # previous round's buddy rows: derived state, not a program
            # input — the sync below writes the fresh copy (the old
            # buffers free when the caller rebinds its state)
            state = state.replace(buddy=None)
        x, y, m = train_pack
        xv, yv, mv = val_pack
        key = (tuple(x.shape[1:]), tuple(xv.shape[1:]))
        if key not in self._round_cache:
            log.info("compiling round program for shapes %s", key)
            self._track(key, self._build_round(key), "round")
            self.built_packs.add((x.shape[1], xv.shape[1]))
        if self.nan_screen and poison is None:
            poison = self.stage_poison(np.zeros(self.n_workers, np.bool_))
        extra = ((poison,) if self.nan_screen and not self.split_sync
                 else ())
        if self.staleness > 0:
            # semi-synchronous entry (ISSUE 16): fold every DUE stale
            # consensus delta into the params this round is about to
            # train, then dispatch the round off them — the still-young
            # syncs keep running under its compute
            state = self._stale_enter(state)
        outs = self._round_cache[key](state, x, y, m, xv, yv, mv, *extra)
        new_state, metrics = outs[0], outs[-1]
        self._arm_sync_stats(new_state.params)
        sync_norm = fence = sync_ok = None
        if self.staleness > 0:
            # dispatch this round's sync as a stale record (primary NOT
            # donated — the next round's program donates those buffers;
            # the delta is delivered K rounds later) and surface the
            # walls of whatever delivery just landed in THIS row
            self._stale_dispatch(new_state, metrics)
            if self._delivered_stats is not None:
                self.last_sync_stats.update(self._delivered_stats)
                self._delivered_stats = None
            return new_state, ("packed", metrics, None, None, None)
        if self.split_sync:
            # the sync program consumes the round's outputs, so its
            # dispatch chains behind the still-running round program; the
            # handle carries both markers so that ``round_wait`` can time
            # the wait on the sync apart
            if "sync" not in self._round_cache:
                self._round_cache["sync"] = self._build_sync()
            sync = self._round_cache["sync"]
            if self.cfg.aggregation_by == "weights":
                args = [new_state.params]
                if self.sync_ef:
                    args.append(new_state.sync_residual)
                if self.sync_ef_outer:
                    # ISSUE 13: the outer (DCN) EF rows ride the
                    # standalone program as their own donated input
                    args.append(new_state.sync_residual_outer)
                d = sync(*args, poison=poison)
                residual = d.get("residual", new_state.sync_residual)
                outer_res = d.get("outer_residual",
                                  new_state.sync_residual_outer)
                if self.resident_on:
                    # the sync ended at the scatter: the resident bucket
                    # shards replace the (donated) full params as the
                    # between-round state
                    new_state = new_state.replace(
                        params=None, params_resident=d["out"],
                        sync_residual=residual,
                        sync_residual_outer=outer_res,
                        buddy=d.get("buddy"))
                else:
                    new_state = new_state.replace(
                        params=d["out"], sync_residual=residual,
                        sync_residual_outer=outer_res)
                fence = d["fence"]
            else:
                if self.round_opt_on:
                    d = sync(outs[1], new_state.round_opt, poison=poison)
                    new_state = new_state.replace(
                        round_opt=d["tracker"], buddy=d.get("buddy"))
                else:
                    d = sync(outs[1], poison=poison)
                sync_norm = d["out"]
                fence = sync_norm
            sync_ok = d.get("ok")
        return new_state, ("packed", metrics, sync_norm, fence, sync_ok)

    def round_markers(self, handle) -> tuple:
        """``(round marker, sync fence)`` of a dispatched round: two
        small, never-donated device arrays.  The first materializes when
        the round program has run, the second (None when the sync ran
        fused, or under staleness) when the standalone sync program
        behind it has.  The deep-pipeline driver keeps them with the
        round it leaves in flight and blocks on them instead of the
        state, whose buffers the NEXT round's dispatch already donated.
        A streamed round has no round marker: its per-epoch barrier has
        materialized everything before the sync."""
        if handle[0] == "packed":
            _, metrics, _sync_norm, fence, _ok = handle
            return metrics["train_loss"], fence
        return None, handle[-1]

    def round_wait(self, new_state: TrainState, handle) -> TrainState:
        """Block until a dispatched round's state is materialized — the
        barrier that keeps at most one round program in flight.

        When a standalone sync program ran (split_sync / streamed rounds),
        also measures the host's wait on it into ``last_sync_stats``:
        block on the round program's marker first, then time the block on
        the sync's fence (span ``round.sync``).  The caller is here before
        the round program ends, so that is the sync program's execution
        plus its dispatch overhead.  A round the driver leaves in flight
        is not settled here and gets no ``sync_ms``: the host comes to
        it late and would time two blocks that return at once."""
        marker, fence = self.round_markers(handle)
        if marker is not None:
            jax.block_until_ready(marker)
        if fence is not None:
            with span("round.sync", self.last_sync_stats, "sync_ms"):
                jax.block_until_ready(fence)
        return jax.block_until_ready(new_state)

    # ------------------------------------------------------------------
    # Semi-synchronous rounds (ISSUE 16): the staleness state machine
    # ------------------------------------------------------------------
    # round_start under K > 0 runs three phases:
    #   1. _stale_enter  — deliver every DUE consensus delta (oldest
    #      first, while more than K are pending) into the params the
    #      round is about to train;
    #   2. dispatch the (donated) round program off the delivered params;
    #   3. _stale_dispatch — dispatch this round's sync program on the
    #      round's trained output WITHOUT donating it (the next round's
    #      program donates those buffers; the PJRT runtime orders its
    #      write after the sync's read because the sync dispatched
    #      first), record the in-flight {delta, fence} pair.
    # The schedule: round R's delta lands at the entry of round R+K+1,
    # so at most K sync programs run under any round's compute, and a
    # K=1 run is one round stale everywhere.  The host never blocks on
    # a sync fence except at delivery — the exposed remainder of the
    # wall — which is how sync_hidden_ms is measured.

    def _stale_enter(self, state: TrainState) -> TrainState:
        """Entry-of-round staleness work: move the EF residual
        engine-side (first round only — the round program must neither
        donate nor retrace on it) and deliver every due delta."""
        if self.sync_ef and state.sync_residual is not None:
            self._stale_residual = state.sync_residual
            state = state.replace(sync_residual=None)
        while len(self._pending) > self.staleness:
            state = self._deliver_oldest(state)
        return state

    def _deliver_oldest(self, state: TrainState) -> TrainState:
        """Fold the oldest in-flight consensus delta into the current
        params (comms.deliver_stale, both inputs donated) and measure
        the delivery accounting: ``exposed_ms`` is the host block on the
        delta (zero when the sync finished under compute), ``hidden_ms``
        the remainder of the sync wall the overlap absorbed."""
        rec = self._pending.pop(0)
        t0 = time.perf_counter()
        jax.block_until_ready(rec["delta"])
        exposed_ms = (time.perf_counter() - t0) * 1e3
        rec["thread"].join()
        wall_ms = rec["wall_ms"]
        # serial gate mode blocked the whole wall at dispatch: nothing
        # was hidden, whatever the delivery-time arithmetic says
        hidden_ms = (0.0 if self.staleness_serial
                     else max(0.0, wall_ms - exposed_ms))
        params = self._round_cache["deliver"](state.params, rec["delta"])
        self._delivered_stats = {"sync_ms": round(wall_ms, 3),
                                 "sync_hidden_ms": round(hidden_ms, 3)}
        self.stale_log.append({"sync_ms": round(wall_ms, 3),
                               "sync_hidden_ms": round(hidden_ms, 3),
                               "exposed_ms": round(exposed_ms, 3)})
        return state.replace(params=params)

    def _stale_dispatch(self, new_state: TrainState, metrics) -> None:
        """Dispatch the staleness sync program on a round's trained
        params and enqueue its in-flight record.  A watcher thread times
        the sync's own execution wall (block the round marker, then the
        fence — the same two-block probe the synchronous engine uses),
        so the wall is measurable even though the dispatch thread never
        waits for it."""
        if "stale_sync" not in self._round_cache:
            self._round_cache["stale_sync"] = self._build_stale_sync()
            # AOT-compile the delivery program NOW (round 0 = inside
            # every warmup window): the first delivery runs at round
            # K+1's entry, where a fresh trace would bust the
            # sanitizer's zero-post-warmup-retrace budget
            # only the params donate: the delta has no same-shaped
            # second output to alias into (it frees when the host
            # drops the pending record)
            tp = self._track("deliver",
                             jax.jit(comms.deliver_stale,
                                     donate_argnums=(0,)),
                             "deliver")
            spec = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                new_state.params)
            tp.compiled = tp._compile((spec, spec), {})
        args = [new_state.params]
        if self.sync_ef:
            # the EF residual chains sync-to-sync engine-side: sync R
            # consumes (donates) sync R-1's residual output — the data
            # dependency serializes the SYNC chain, never the rounds
            args.append(self._stale_residual)
        d = self._round_cache["stale_sync"](*args)
        if self.sync_ef:
            self._stale_residual = d["residual"]
        rec = {"delta": d["delta"], "fence": d["fence"], "wall_ms": 0.0}
        marker = metrics["train_loss"]
        fence = d["fence"]

        def _watch():
            jax.block_until_ready(marker)
            t0 = time.perf_counter()
            jax.block_until_ready(fence)
            rec["wall_ms"] = (time.perf_counter() - t0) * 1e3

        t = threading.Thread(target=_watch, daemon=True,
                             name="stale-sync-watch")
        t.start()
        rec["thread"] = t
        self._pending.append(rec)
        if self.staleness_serial:
            # the K-bitwise gate's serial reference: same programs, same
            # delayed-delivery schedule, zero overlap
            jax.block_until_ready(fence)

    def drain_pending(self, state: TrainState) -> TrainState:
        """End-of-run fence: deliver every still-in-flight consensus
        delta (oldest first) and restore the engine-side EF residual
        into the state, so the trained result reflects every dispatched
        sync.  No-op when staleness is off or nothing is pending."""
        while self._pending:
            state = self._deliver_oldest(state)
        if self._stale_residual is not None:
            state = state.replace(sync_residual=self._stale_residual)
            self._stale_residual = None
        return jax.block_until_ready(state) if self.staleness else state

    def _build_stale_sync(self):
        """The staleness variant of the standalone sync program (ISSUE
        16).  Three contract changes against ``_build_sync``:

        * the primary input (the freshly trained params) is NOT donated —
          the next round's round program donates those buffers, and the
          runtime orders that write after this program's read because
          the sync dispatched first; the host side never re-reads them
          (the graftlint R4 contract);
        * the output is the consensus DELTA ``blend(T) - T``
          (comms.stale_delta) instead of the blend itself — additive, so
          it folds into whatever params exist at delivery without
          touching T again;
        * only the weights (FedAvg) x replicated-residency x unscreened
          shape exists: config rejected every other combo eagerly, so
          there is no resident / buddy / tracker / poison plumbing."""

        def _fence(tree):
            f = jnp.sum(jax.tree_util.tree_leaves(tree)[0]).astype(
                jnp.float32)
            return lax.psum(f, self._inner_axes) if self._inner_axes else f

        pspec = self._sspec.params if self._sspec is not None else self._spec
        takes_residual = self.sync_ef

        def per_worker(*args):
            primary = args[0]
            residual = args[1] if takes_residual else None
            p, _res, r, _t, _bud, _ok, _n, _o = self._sync_body(
                primary, None, residual)
            delta = comms.stale_delta(p, primary)
            d = {"delta": delta, "fence": _fence(delta)}
            if takes_residual:
                d["residual"] = r
            return d

        in_specs = [pspec]
        donate: tuple = ()
        if takes_residual:
            in_specs.append(pspec)
            donate = (1,)
        out_specs: dict = {"delta": pspec, "fence": self._spec}
        if takes_residual:
            out_specs["residual"] = pspec
        prog = self._track(None,
                           self._wrap_stacked(per_worker, in_specs,
                                              out_specs=out_specs,
                                              donate=donate,
                                              name="stale_sync"),
                           "stale_sync")

        def run(*args):
            return dict(prog(*args))

        return run

    def checkpoint_fence(self, state: TrainState) -> TrainState:
        """Barrier a checkpoint snapshot needs before reading ``state``.

        Every engine program DONATES its state input (the round program,
        the standalone sync program, the chunk programs), so a snapshot
        taken while any of them is still in flight would copy bytes the
        next dispatch is free to overwrite.  Blocking here pins the
        invariant to the save path itself instead of relying on which
        driver pipeline mode (serial / overlapped / deep) happened to
        have barriered already; on an already-materialized state it
        costs nothing.  The checkpoint engine's device->host shard copy
        (``checkpoint.snapshot_addressable``) runs right behind this
        fence — together they are the host-staging snapshot pool the
        ROADMAP's offloaded-remat item waits on."""
        return jax.block_until_ready(state)

    def finish_metrics(self, handle) -> dict:
        """Fetch + assemble a dispatched round's host metrics.

        Blocks until the round's metric buffers are computed; safe to call
        from a worker thread while the NEXT round is already running —
        the overlapped driver pipeline does exactly that."""
        if handle[0] == "packed":
            _, metrics, sync_norm, _fence, sync_ok = handle
            mx = self._fetch(metrics)
            if sync_norm is not None:
                # split_sync x gradients mode: the norm came from the
                # standalone sync program, not the round program
                mx["agg_grad_norm"] = self._fetch(sync_norm)
            if sync_ok is not None:
                # split_sync x NaN screen: validity came from the
                # standalone sync program
                mx["sync_ok"] = self._fetch(sync_ok)
            return mx
        _, per_epoch, agg_grad_norm, sync_ok, _fence = handle
        mx = self._assemble_streamed(per_epoch, agg_grad_norm)
        if sync_ok is not None:
            mx["sync_ok"] = self._fetch(sync_ok)
        return mx

    def round(self, state: TrainState, train_pack, val_pack):
        """Serial convenience wrapper: dispatch, block, fetch."""
        new_state, handle = self.round_start(state, train_pack, val_pack)
        new_state = self.round_wait(new_state, handle)
        return new_state, self.finish_metrics(handle)

    # ------------------------------------------------------------------
    # Streamed rounds: per-chunk host->device feeding (ImageNet scale)
    # ------------------------------------------------------------------
    # The whole-round program holds the full epoch in device memory — fine
    # for CIFAR, impossible for ImageNet (8 workers x real epoch ~ hundreds
    # of GB).  The streamed path runs the SAME step bodies
    # (``_make_step_fns``) chunk by chunk: the host feeds fixed-shape
    # [N, C, B, ...] windows, dispatch is async (chunk k+1 transfers while
    # chunk k executes — double buffering for free), and only O(metrics)
    # bytes ever return to the host.

    def _wrap_stacked(self, per_worker, in_specs, out_specs=None,
                      donate=False, *, name: str):
        """shard_map a per-worker fn over the worker-stacked leading axis.
        The compiled module is ``jit_localsgd_<name>``: the program's
        label, which is what the device trace then calls it."""

        def stacked(*args):
            sq = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
            ex = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
            unstacked = [a if s == P() else sq(a)
                         for a, s in zip(args, in_specs)]
            return ex(per_worker(*unstacked))

        stacked.__name__ = f"localsgd_{name}"
        fn = jax.shard_map(stacked, mesh=self.mesh,
                           in_specs=tuple(in_specs),
                           out_specs=out_specs or self._spec)
        if donate is True:
            donate = (0,)
        return jax.jit(fn, donate_argnums=donate or ())

    def _build_chunk_train(self, shapes_key):
        augment = self.cfg.augment and len(shapes_key) == 5  # [C,B,H,W,Ch]
        train_step, _ = self._make_step_fns(augment)

        def per_worker(inner, lr, x, y, m):
            params, batch_stats, opt_state, rng, grads = inner
            carry = (params, batch_stats, opt_state, rng, lr, grads)
            carry, ys = lax.scan(train_step, carry, (x, y, m))
            params, batch_stats, opt_state, rng, _, grads = carry
            # (loss, correct, total): the streamed round carries no
            # counters
            return (params, batch_stats, opt_state, rng, grads), ys[:3]

        xs, ys_, ms = self._pack_specs()
        inner = self._inner_specs()
        return self._wrap_stacked(
            per_worker, [inner, P(), xs, ys_, ms],
            out_specs=(inner, self._spec), donate=True, name="chunk_train")

    def _build_chunk_eval(self, shapes_key):
        _, eval_step = self._make_step_fns(False)

        def per_worker(params, batch_stats, x, y, m):
            if self.fsdp_axis:
                from .parallel.fsdp import gather_params
                params = gather_params(params, self.param_specs,
                                       self.fsdp_axis)
            _, sums = lax.scan(eval_step, (params, batch_stats), (x, y, m))
            return sums  # (ce_sum, correct, w_sum), each [C]

        xs, ys_, ms = self._pack_specs()
        pspec = self._sspec.params if self._sspec is not None else self._spec
        bspec = self._sspec.batch_stats if self._sspec is not None \
            else self._spec
        return self._wrap_stacked(
            per_worker, [pspec, bspec, xs, ys_, ms],
            out_specs=self._spec, name="chunk_eval")

    def _build_sync(self):
        """The standalone donated sync program (streamed rounds on every
        backend; packed rounds under split_sync).  One compiled shard_map
        program runs the whole sync engine — bucketed reduce-scatter /
        scale-on-shard / all-gather, bucketed ppermute gossip, or the
        dense twin — with the inputs donated so the once-per-round
        parameter sync updates in place.

        Returns a callable ``run(primary[, residual_or_tracker],
        poison=None)`` producing a DICT: ``out`` (synced params /
        resident shards / agg norm), plus ``residual`` / ``tracker`` /
        ``buddy`` (ISSUE 12 ring-successor copies) / ``ok`` (ISSUE 12
        per-worker validity) as armed, and ``fence`` — a tiny
        never-donated per-worker scalar marker that ``round_wait`` times
        the sync on and the deep-pipeline driver blocks on (in gradients
        mode ``out`` IS the fence)."""
        cfg = self.cfg

        def _fence(tree):
            f = jnp.sum(jax.tree_util.tree_leaves(tree)[0]).astype(
                jnp.float32)
            # a TP/PP/EP-sharded leaf sums to a shard-varying value; make
            # the fence invariant along inner axes so the P(data) out-spec
            # holds (its VALUE is irrelevant — only its completion is)
            return lax.psum(f, self._inner_axes) if self._inner_axes else f

        pspec = self._sspec.params if self._sspec is not None else self._spec
        weights = cfg.aggregation_by == "weights"
        takes_residual = weights and self.sync_ef
        # ISSUE 13: the outer (DCN) EF residual is its own donated input
        # of the hierarchical standalone sync
        takes_outer = weights and self.sync_ef_outer
        takes_tracker = (not weights) and self.round_opt_on
        screen = self.nan_screen

        def per_worker(*args):
            idx = 0
            primary = args[idx]
            idx += 1
            residual = outer_res = tracker = poi = None
            if takes_residual:
                residual = args[idx]
                idx += 1
            if takes_outer:
                outer_res = args[idx]
                idx += 1
            if takes_tracker:
                tracker = args[idx]
                idx += 1
            if screen:
                poi = args[idx]
            if weights:
                p, res, r, _t, bud, ok, _, oret = self._sync_body(
                    primary, None, residual, poison=poi,
                    outer_residual=outer_res)
                out = res if self.resident_on else p
                d = {"out": out, "fence": _fence(out)}
                if takes_residual:
                    d["residual"] = r
                if takes_outer:
                    d["outer_residual"] = oret
            else:
                _p, _res, _r, trk, bud, ok, norm, _o = self._sync_body(
                    None, primary, None, tracker, poison=poi)
                d = {"out": norm}
                if takes_tracker:
                    d["tracker"] = trk
            if bud is not None:
                d["buddy"] = bud
            if ok is not None:
                d["ok"] = ok
            return d

        in_specs = [pspec]
        donate = [0]
        if takes_residual:
            in_specs.append(pspec)
            donate.append(len(in_specs) - 1)
        if takes_outer:
            in_specs.append(self._spec)
            donate.append(len(in_specs) - 1)
        if takes_tracker:
            in_specs.append(self._spec)
            donate.append(len(in_specs) - 1)
        if screen:
            in_specs.append(self._spec)   # [N] poison flags, not donated
        out_specs: dict = {"out": (self._spec if (self.resident_on
                                                  or not weights)
                                   else pspec)}
        if weights:
            out_specs["fence"] = self._spec
        if takes_residual:
            out_specs["residual"] = pspec
        if takes_outer:
            out_specs["outer_residual"] = self._spec
        if takes_tracker:
            out_specs["tracker"] = self._spec
        if self.buddy_on:
            out_specs["buddy"] = self._spec
        if screen:
            out_specs["ok"] = self._spec
        prog = self._track(None,
                           self._wrap_stacked(per_worker, in_specs,
                                              out_specs=out_specs,
                                              donate=tuple(donate),
                                              name="sync"),
                           "sync")

        def run(*args, poison=None):
            if screen:
                if poison is None:
                    poison = self.stage_poison(
                        np.zeros(self.n_workers, np.bool_))
                args = args + (poison,)
            d = dict(prog(*args))
            if not weights:
                d["fence"] = d["out"]
            return d

        return run

    def _staged_chunks(self, gen):
        """Iterator of device-staged (x, y, m) chunk triples.

        With ``cfg.stream_prefetch > 0`` a bounded producer thread
        (``ChunkStager``) packs + stages up to that many windows ahead
        onto alternating device buffers while the current chunk computes;
        0 stages synchronously (the serial twin)."""
        xs_spec, ys_spec, ms_spec = self._pack_specs()
        put = self._put

        def stage(chunk):
            x, y, m = chunk
            return put(x, xs_spec), put(y, ys_spec), put(m, ms_spec)

        if self.cfg.stream_prefetch > 0:
            return ChunkStager(gen, stage, depth=self.cfg.stream_prefetch)
        return map(stage, gen)

    def round_streamed_start(self, state: TrainState, train_chunks,
                             val_chunks, poison=None):
        """Dispatch one streamed global epoch; metric fetch is deferred.

        ``train_chunks(epoch)`` / ``val_chunks(epoch)`` return an iterator
        of fixed-shape numpy (x [N,C,B,...], y [N,C,B,...], m [N,C,B])
        chunks for that local epoch.  Returns ``(new_state, handle)``
        exactly like ``round_start``: the chunk programs and the sync are
        dispatched (with a per-local-epoch in-flight barrier), but the
        O(metrics) device->host fetch + numpy assembly are deferred to
        ``finish_metrics`` so the driver can run them on a worker thread
        while the next round computes.
        """
        cfg = self.cfg
        if state.buddy is not None:
            # previous round's buddy rows: derived state, not a program
            # input — the standalone sync writes the fresh copy below
            state = state.replace(buddy=None)
        # Fresh-grads program, built ONCE per engine (a per-call
        # ``jax.jit(lambda ...)`` here was a graftlint R2 true positive:
        # every round paid a fresh retrace+compile).  out_shardings pins
        # the zeros to the params' shardings — zeros depend on no input,
        # so GSPMD propagation has nothing to anchor on and an
        # unconstrained program hands back UNSHARDED leaves, which the
        # chunk program then silently reshards device-to-device every
        # round (the sanitizer's transfer guard caught exactly that).
        params0 = state.params
        if self.resident_on:
            # ISSUE 11: the streamed chunk programs consume full params,
            # so a cached donated ENTER program re-gathers them from the
            # resident bucket shards at round start — the full tree then
            # lives only for the duration of the round (the standalone
            # sync at round end re-scatters it and the chunk programs'
            # donation frees the working copy)
            if "enter" not in self._round_cache:
                self._track("enter", comms.make_resident_gather(
                    self.mesh, self.params_template,
                    bucket_bytes=self.sync_bucket_bytes, donate=True),
                    "resident_enter")
            params0 = self._round_cache["enter"](state.params_resident)
        if "zeros" not in self._round_cache:
            self._track("zeros", jax.jit(
                lambda p: jax.tree_util.tree_map(jnp.zeros_like, p),
                out_shardings=jax.tree_util.tree_map(
                    lambda x: x.sharding, params0)), "stream_zeros")
        zeros_like = self._round_cache["zeros"]

        inner = (params0, state.batch_stats, state.opt_state, state.rng,
                 zeros_like(params0))
        epoch0 = int(jax.device_get(_first_worker_row(state.lr_epoch)))

        per_epoch = []  # (train_chunk_ys, val_chunk_sums) device arrays
        for e in range(cfg.epochs_local):
            # staged via an EXPLICIT device_put: jnp.asarray of a host
            # PYTHON/numpy scalar is an implicit transfer
            # (convert_element_type on the scalar) that the sanitizer's
            # guard rejects in the round loop — a 0-d ndarray takes the
            # explicit path on both branches.  Multi-host keeps the
            # uncommitted asarray.
            lr_np = np.asarray(
                steplr(cfg.lr, cfg.lr_gamma, cfg.lr_step_size, epoch0 + e),
                np.float32)
            lr = (jax.device_put(lr_np, NamedSharding(self.mesh, P()))
                  if jax.process_count() == 1 else jnp.asarray(lr_np))
            # fresh zero grads each epoch: the round program resets the
            # last-grad carry per local epoch (scan init), match it
            if e > 0:
                inner = inner[:4] + (zeros_like(inner[0]),)
            t_ys = []
            feed = self._staged_chunks(train_chunks(e))
            try:
                for (x, y, m) in feed:
                    key = ("ct", tuple(x.shape[1:]))
                    if key not in self._round_cache:
                        log.info("compiling chunk-train program for %s", key)
                        self._track(key, self._build_chunk_train(
                            tuple(x.shape[1:])), "chunk_train")
                    inner, ys = self._round_cache[key](inner, lr, x, y, m)
                    t_ys.append(ys)
                v_sums = []
                feed = self._staged_chunks(val_chunks(e))
                for (x, y, m) in feed:
                    key = ("ce", tuple(x.shape[1:]))
                    if key not in self._round_cache:
                        log.info("compiling chunk-eval program for %s", key)
                        self._track(key, self._build_chunk_eval(
                            tuple(x.shape[1:])), "chunk_eval")
                    v_sums.append(self._round_cache[key](
                        inner[0], inner[1], x, y, m))
            except BaseException:
                # consumer bailed mid-round (e.g. a compile error): stop
                # the producer and release its staged device buffers
                if isinstance(feed, ChunkStager):
                    feed.close()
                raise
            # one fetch barrier per epoch keeps at most one epoch's worth of
            # dispatch in flight (see the 1-core-CPU rendezvous note above)
            jax.block_until_ready(inner[0])
            per_epoch.append((t_ys, v_sums))

        params, batch_stats, opt_state, rng, last_grads = inner
        if "sync" not in self._round_cache:
            self._round_cache["sync"] = self._build_sync()
        sync = self._round_cache["sync"]
        self._arm_sync_stats(params)
        residual = state.sync_residual
        outer_res = state.sync_residual_outer
        round_opt = state.round_opt
        resident = None
        new_buddy = None
        sync_ok = None
        if cfg.aggregation_by == "weights":
            args = [params]
            if self.sync_ef:
                args.append(residual)
            if self.sync_ef_outer:
                args.append(outer_res)
            d = sync(*args, poison=poison)
            synced, fence = d["out"], d["fence"]
            residual = d.get("residual", residual)
            outer_res = d.get("outer_residual", outer_res)
            new_buddy = d.get("buddy")
            sync_ok = d.get("ok")
            if self.resident_on:
                # the sync ended at the scatter: only the bucket shards
                # survive the round (the donated full params are gone)
                resident, params = synced, None
            else:
                params = synced
            # weights mode reports a zero norm; keep it a sharded device
            # array so the multi-host metric fetch (process_allgather)
            # sees the same global [N] layout as the gradients mode
            agg_grad_norm = self._put(
                np.zeros((self.n_workers,), np.float32), self._spec)
        else:
            if self.round_opt_on:
                d = sync(last_grads, round_opt, poison=poison)
                round_opt = d["tracker"]
            else:
                d = sync(last_grads, poison=poison)
            agg_grad_norm = d["out"]
            new_buddy = d.get("buddy")
            sync_ok = d.get("ok")
            fence = agg_grad_norm
        # everything before the sync is already materialized (the
        # per-epoch barrier above), so ``round_wait``'s block on the
        # fence, which rides the handle, times the sync program's
        # collectives alone

        # the epoch bump runs as a tiny cached program: eager arithmetic
        # with a Python/numpy scalar is an IMPLICIT host->device transfer
        # every round — the sanitizer's transfer guard (ISSUE 6) rejects
        # it, and on TPU it is a needless blocking H2D in the round loop.
        # Inside jit the addend is a trace-time constant instead.
        if "bump_epoch" not in self._round_cache:
            self._track("bump_epoch", jax.jit(
                lambda e: e + jnp.asarray(cfg.epochs_local, e.dtype)),
                "bump_epoch")
        new_state = TrainState(
            params=params, params_resident=resident,
            batch_stats=batch_stats, opt_state=opt_state,
            lr_epoch=self._round_cache["bump_epoch"](state.lr_epoch),
            rng=rng, sync_residual=residual, round_opt=round_opt,
            buddy=new_buddy, sync_residual_outer=outer_res)
        return new_state, ("streamed", per_epoch, agg_grad_norm, sync_ok,
                           fence)

    def _assemble_streamed(self, per_epoch, agg_grad_norm) -> dict:
        """Fetch + assemble a streamed round's metrics into the same mx
        structure ``round`` returns (thread-safe; blocks on the fetches)."""
        E = self.cfg.epochs_local
        n = self.n_workers
        losses, corrects, totals, vls, vcs, vws = ([] for _ in range(6))
        for t_ys, v_sums in per_epoch:
            l, c, t = zip(*(self._fetch(ys) for ys in t_ys))
            losses.append(np.concatenate(l, 1))     # [N, S]
            corrects.append(np.concatenate(c, 1))
            totals.append(np.concatenate(t, 1))
            vl, vc, vw = zip(*(self._fetch(s) for s in v_sums))
            vls.append(np.concatenate(vl, 1).sum(1))  # [N]
            vcs.append(np.concatenate(vc, 1).sum(1))
            vws.append(np.concatenate(vw, 1).sum(1))
        losses = np.stack(losses, 1)                 # [N, E, S]
        totals = np.stack(totals, 1)
        corrects = np.stack(corrects, 1)
        real = (totals > 0).astype(np.float32)
        train_loss = (losses * real).sum(-1) / np.maximum(real.sum(-1), 1.0)
        train_acc = 100.0 * corrects.sum(-1) / np.maximum(totals.sum(-1), 1.0)
        vw_arr = np.maximum(np.stack(vws, 1), 1.0)   # [N, E]
        val_loss = np.stack(vls, 1) / vw_arr
        val_acc = 100.0 * np.stack(vcs, 1) / vw_arr
        tile = lambda v: np.broadcast_to(np.asarray(v, np.float32), (n,))
        return dict(
            batch_losses=losses, batch_mask=real,
            train_loss=train_loss, train_acc=train_acc,
            val_loss=val_loss, val_acc=val_acc,
            avg_acc=np.broadcast_to(train_acc.mean(0), (n, E)),
            agg_grad_norm=self._fetch(agg_grad_norm),
            global_train_loss=tile(train_loss.mean()),
            global_train_acc=tile(train_acc.mean()),
            global_val_loss=tile(val_loss.mean()),
            global_val_acc=tile(val_acc.mean()),
        )

    def round_streamed(self, state: TrainState, train_chunks, val_chunks):
        """Serial convenience wrapper around the streamed round: dispatch,
        block, fetch.  Numerics match the whole-round program exactly
        (same step bodies, same RNG stream)."""
        new_state, handle = self.round_streamed_start(
            state, train_chunks, val_chunks)
        new_state = self.round_wait(new_state, handle)
        return new_state, self.finish_metrics(handle)
