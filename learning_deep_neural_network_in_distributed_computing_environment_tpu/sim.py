"""Scenario lab: the vmap'd many-worker simulator (ISSUE 14).

Every distributed scenario on the real driver costs one mesh slot per
worker, capping studies at N = device count (8 virtual CPU devices in the
test harness).  ``SimEngine`` removes the cap by making N a BATCH
dimension instead of a process count: the entire local-SGD round —
per-worker data slices from the existing non-IID injector, per-worker RNG
streams, per-worker SGD/Adam state stacked on a leading ``[N, ...]`` axis
exactly like the layer-scan stack — runs under one ``jax.vmap``-ed,
donated jit on a single chip, and the once-per-round sync point runs as
pure stacked math (``comms.aggregate_sim``, the flat-primitives reference
path's twin).  Hundreds of simulated workers compile ONE per-worker
program, the round-loop analogue of the weight-update-sharding economics
in arXiv 2004.13336 / the single-program pjit stacks of arXiv 2204.06514.

Correctness contract (the tentpole gate, tests/test_sim.py): fp32 N=8
simulated rounds are BITWISE-identical to N=8 real-mesh rounds across all
three topologies x equal/weighted, under ``--sanitize`` with zero
post-warmup retraces.  Three facts make the gate mechanical:

1. the per-worker local phase is ONE definition
   (``LocalSGDEngine._make_local_round`` — collective-free), executed per
   device under shard_map on the real path and vmapped here; XLA batches
   every op without changing its per-element arithmetic;
2. XLA's all-reduce accumulates participants in rank order, and
   ``comms.sim_fold`` reproduces exactly that sequential fold over the
   stacked axis (a reassociating ``jnp.sum`` does not match);
3. ppermute's receive-from-predecessor is ``jnp.roll`` on the stacked
   axis — pure data movement.

Scenario surface (the generative part — none of these exist on the real
path, which is why the lab exists):

- ``--sim_sample_frac``: per-round client sampling — sampled-out workers
  skip the round locally but adopt the consensus;
- ``--sim_dropout``: per-round seeded worker dropout — a dropped worker's
  round is a complete no-op (no train, no contribute, no adopt);
- ``--sim_byzantine``: sign-flip/noise adversaries corrupting their sync
  contribution;
- ``--sim_lr_jitter``: a fixed per-worker LR spread.

Participation masks ride ``aggregate_sim``'s ``ok`` screen (the dense
poison path's arithmetic, so blends renormalize over survivors exactly
like a quarantined contribution).  Scenario knobs at their defaults
never perturb the parity gate: an unarmed scenario compiles NONE of the
mask machinery (``scenario_on`` is a compile-time arming), so the gate's
program is the plain vmap + stacked blends.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax

from . import comms
from .config import Config
from .train import LocalSGDEngine, TrainState

log = logging.getLogger(__name__)

# built under jit: eager zeros_like materializes scalar constants
# host-side — a transfer the sanitizer's guard (correctly) disallows
# inside the round loop; cached at module level so the callable is
# constructed once (graftlint R2)
_zeros_like_tree = jax.jit(
    lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))


def _row_where(mask_rows: jnp.ndarray, a, b):
    """Per-worker row select on worker-stacked pytrees: ``mask_rows`` is
    [N] (bool/0-1); row i of the result is a's where mask, else b's."""
    def sel(x, y):
        m = mask_rows.reshape(mask_rows.shape[0],
                              *([1] * (x.ndim - 1))) > 0
        return jnp.where(m, x, y)
    return jax.tree_util.tree_map(sel, a, b)


class SimEngine(LocalSGDEngine):
    """``LocalSGDEngine`` with the worker axis SIMULATED on one chip.

    The engine keeps the whole driver-facing contract — ``init_state`` /
    ``stage_pack`` / ``round_start`` / ``round_wait`` /
    ``finish_metrics`` / ``rank0_variables`` / ``state_resident_bytes``
    — so ``driver.train_global`` runs the identical orchestration loop
    (probe, partition, straggler EMA, sanitizer, telemetry) around it;
    only the mesh is gone.  ``mesh`` must be a 1-device anchor mesh (the
    driver builds it); ``cfg.sim_workers`` is the simulated N.
    """

    def __init__(self, model, mesh, cfg: Config, train_model=None):
        if cfg.sim_workers < 1:
            raise ValueError(
                f"SimEngine needs --sim_workers >= 1, got "
                f"{cfg.sim_workers}")
        super().__init__(model, mesh, cfg, train_model=train_model)
        if self.n_slices != 1 or self._inner_axes:
            raise ValueError(
                "SimEngine runs on a 1-device anchor mesh (config "
                "rejects slices/inner axes eagerly); got mesh "
                f"{dict(mesh.shape)}")
        # the worker axis is simulated: every [N, ...] stack lives on
        # the one anchor device, N = cfg.sim_workers (the base __init__
        # read the mesh's 1-wide data axis)
        self.n_workers = int(cfg.sim_workers)
        self.n_inner = self.n_workers
        self.sync_mode = "sim"
        # the simulated sync is fused stacked math inside the round
        # program on every backend — there is no standalone collective
        # program to split out (or to place/shard: the dense-semantics
        # twin is literally replicated arithmetic)
        self.split_sync = False
        self.opt_placement = "replicated"
        self.param_residency = "replicated"
        self.resident_on = False
        self.round_opt_on = False
        self.buddy_on = False
        # the row buffer's loop has a trip count a worker; under ``vmap``
        # every worker would run the fullest one's
        self.labelled_rows_head = False
        # error feedback for the SIMULATED compressed wire (the gossip
        # engine's single-stage model, comms.aggregate_sim): armed on
        # weights aggregation exactly like the real engines
        self.sync_ef = (cfg.sync_compression == "ef"
                        and cfg.aggregation_by == "weights"
                        and cfg.sync_dtype in ("bfloat16", "int8"))
        self.sync_ef_outer = False
        # --- scenario surface -----------------------------------------
        byz = cfg.parse_sim_byzantine()
        self.byz_kind, self.byz_count, self.byz_scale = (
            byz if byz is not None else (None, 0, 0.0))
        # an armed scenario compiles the mask/adversary machinery into
        # the round program (extra [N] inputs, row selects); the default
        # run compiles NONE of it — the parity gate's program is the
        # plain vmap + stacked blends
        self.scenario_on = (cfg.sim_sample_frac < 1.0
                            or cfg.sim_dropout > 0.0
                            or self.byz_count > 0)
        # per-round draws (participation, dropout, adversary noise) come
        # from a dedicated host generator so they are deterministic in
        # --seed and independent of the data pipeline's stream
        self._scen_rng = np.random.default_rng(
            np.random.SeedSequence([cfg.seed, 0x51AB]))
        # per-worker LR jitter: a fixed seeded spread baked into the
        # round program as a constant (no input, no retrace)
        if cfg.sim_lr_jitter > 0.0:
            u = np.random.default_rng(
                np.random.SeedSequence([cfg.seed, 0x17E9])).uniform(
                    -1.0, 1.0, self.n_workers)
            self.lr_scale = (1.0 + cfg.sim_lr_jitter * u).astype(
                np.float32)
        else:
            self.lr_scale = None
        # per-round scenario telemetry, assembled into results["sim"]
        self.rounds_scenario: list[dict] = []
        # --- semi-synchronous twin (ISSUE 16) --------------------------
        # --sim_staleness K models the REAL engine's delayed-delivery
        # schedule (train.py's staleness state machine) as pure stacked
        # math, so staleness-vs-convergence is characterized across the
        # paper's 2x3 matrix on one chip before any hardware is rented.
        # The round program always takes a delta_in input (a cached
        # zeros tree during the first K+1 warmup rounds — one program,
        # no retrace) and emits delta_out = delivered - trained while
        # params stay at the TRAINED value; the host-side deque below
        # applies the real schedule: round R's delta folds in at the
        # entry of round R+K+1, drain at exit.  The base engine's
        # overlap machinery stays off (self.staleness == 0): the sim
        # sync is fused math with no wall to hide — this arm is the
        # CONVERGENCE twin, not the wall-clock one.
        self.sim_staleness = max(0, int(cfg.sim_staleness))
        self._sim_pending: list = []
        self._sim_zeros = None

    # ------------------------------------------------------------------
    # Telemetry
    # ------------------------------------------------------------------
    def _arm_sync_stats(self, params_stacked) -> None:
        """Per-round sync telemetry, sim accounting: ``sync_bytes`` is
        what ONE simulated worker's sync WOULD move on the simulated
        fabric (``comms.sim_wire_bytes`` — the dense per-leaf model in
        the wire dtype), zero measured wall (the stacked math is fused
        into the round program).  Schema identical to every real
        engine's row."""
        if self._sync_bytes is None:
            shapes = self.params_template
            if shapes is None:
                shapes = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                    params_stacked)
            wire = (self.sync_wire_dtype
                    if self.cfg.sync_dtype in ("bfloat16", "int8")
                    else None)
            self._sync_bytes = comms.sim_wire_bytes(
                shapes, self.n_workers, topology=self.cfg.topology,
                wire_dtype=wire)
            self._sync_bytes_split = (self._sync_bytes, 0)
        ici, dcn = self._sync_bytes_split
        self.last_sync_stats = {"sync_bytes": self._sync_bytes,
                                "sync_mode": self.sync_mode,
                                "sync_ms": 0.0,
                                # schema twin of the real rows (ISSUE
                                # 16): the fused sim sync has no wall to
                                # hide, so the column is always zero
                                "sync_hidden_ms": 0.0,
                                "sync_bytes_ici": ici,
                                "sync_bytes_dcn": dcn}

    # ------------------------------------------------------------------
    # Scenario draws
    # ------------------------------------------------------------------
    def _draw_scenario(self):
        """One round's seeded scenario draw: ``(active f32 [N], dropped
        bool [N], noise_key uint32 [2])`` host arrays.  active = sampled
        AND not dropped (the contribution/training mask); dropped rows
        additionally skip consensus adoption."""
        cfg = self.cfg
        n = self.n_workers
        part = np.ones(n, np.bool_)
        if cfg.sim_sample_frac < 1.0:
            k = max(1, int(np.ceil(cfg.sim_sample_frac * n)))
            part = np.zeros(n, np.bool_)
            part[self._scen_rng.choice(n, size=k, replace=False)] = True
        dropped = np.zeros(n, np.bool_)
        if cfg.sim_dropout > 0.0:
            dropped = self._scen_rng.random(n) < cfg.sim_dropout
        active = part & ~dropped
        key = np.zeros(2, np.uint32)
        if self.byz_kind == "noise":
            key = self._scen_rng.integers(0, 2 ** 32, size=2,
                                          dtype=np.uint32)
        return active.astype(np.float32), dropped, key

    def _byz_mask(self) -> np.ndarray:
        """The LAST ``byz_count`` worker ids are the adversaries —
        static for the run (config validated count < N)."""
        return (np.arange(self.n_workers)
                >= self.n_workers - self.byz_count)

    # ------------------------------------------------------------------
    # The simulated round program
    # ------------------------------------------------------------------
    def _build_round(self, shapes_key):
        cfg = self.cfg
        n = self.n_workers
        augment = cfg.augment and len(shapes_key[0]) == 5  # [S,B,H,W,C]
        local_round = self._make_local_round(augment)
        weights_mode = cfg.aggregation_by == "weights"
        scenario = self.scenario_on
        stale = self.sim_staleness > 0
        byz_rows = (jnp.asarray(self._byz_mask()) if self.byz_count
                    else None)
        lr_scale = (jnp.asarray(self.lr_scale)
                    if self.lr_scale is not None else None)
        wire = (self.sync_wire_dtype
                if cfg.sync_dtype in ("bfloat16", "int8") else None)

        def bcast(v):
            """A cross-worker reduced value broadcast back to [N, ...]
            rows — the stacked twin of a pmean'd out_spec row."""
            return jnp.broadcast_to(v, (n, *np.shape(v)))

        def mean_rows(v):
            # lax.pmean accumulates in rank order then divides by the
            # axis size; sim_fold reproduces the accumulation bitwise
            return comms.sim_fold(v) / n

        def corrupt(contrib, entry, noise_key):
            """Byzantine adversaries' transmitted payloads (the last
            ``byz_count`` rows): sign-flip sends the round's update
            NEGATED (weights mode: 2*entry - trained = entry - update;
            gradients mode: -grad); noise adds a fresh seeded N(0,1)
            draw scaled by ``byz_scale``."""
            if byz_rows is None:
                return contrib
            if self.byz_kind == "signflip":
                if weights_mode:
                    flipped = jax.tree_util.tree_map(
                        lambda e, t: 2.0 * e - t, entry, contrib)
                else:
                    flipped = jax.tree_util.tree_map(
                        lambda t: -t, contrib)
                return _row_where(byz_rows, flipped, contrib)
            key = jax.random.wrap_key_data(noise_key)
            leaves, treedef = jax.tree_util.tree_flatten(contrib)
            noisy = [
                x + self.byz_scale * jax.random.normal(
                    jax.random.fold_in(key, i), x.shape, jnp.float32)
                for i, x in enumerate(leaves)]
            return _row_where(
                byz_rows, jax.tree_util.tree_unflatten(treedef, noisy),
                contrib)

        def sim_round(state: TrainState, x, y, m, xv, yv, mv, *scen):
            if stale:
                # deliver the due (possibly zero) stale consensus delta
                # into the params this round trains off — the twin of
                # the real engine's _stale_enter fold
                scen, delta_in = scen[:-1], scen[-1]
                state = state.replace(params=comms.deliver_stale(
                    state.params, delta_in))
            entry = (state.params, state.batch_stats, state.opt_state,
                     state.lr_epoch, state.rng)
            args = entry + (x, y, m, xv, yv, mv)
            if lr_scale is not None:
                args = args + (lr_scale,)
            (params, batch_stats, opt_state, lr_epoch, rng,
             last_grads), per_epoch = jax.vmap(local_round)(*args)
            active = dropped = noise_key = None
            if scenario:
                active, dropped, noise_key = scen
                # sampled-out / dropped rows FREEZE locally: the whole
                # local phase is discarded for them (no training, no
                # clock advance, no RNG consumption)
                params = _row_where(active, params, entry[0])
                batch_stats = _row_where(active, batch_stats, entry[1])
                opt_state = _row_where(active, opt_state, entry[2])
                lr_epoch = _row_where(active, lr_epoch, entry[3])
                rng = _row_where(active, rng, entry[4])
            # cross-worker metric twins: the same values the real
            # path's pmeans produce, as stacked folds ([N, E] -> [E]
            # mean -> broadcast) — bitwise by the sim_fold argument
            per_epoch = dict(per_epoch,
                             avg_acc=bcast(mean_rows(
                                 per_epoch["train_acc"])))
            # --- the sync point: pure stacked math ---------------------
            agg_grad_norm = jnp.zeros((n,))
            residual = state.sync_residual
            delta_out = None
            agg_kw = dict(how=cfg.aggregation_type,
                          topology=cfg.topology,
                          local_weight=cfg.local_weight,
                          ok=active, wire_dtype=wire)
            if weights_mode:
                contrib = (params if not scenario
                           else corrupt(params, entry[0], noise_key))
                blended, residual = comms.aggregate_sim(
                    contrib, residual=(residual if self.sync_ef
                                       else None), **agg_kw)
                if residual is None:
                    residual = state.sync_residual
                # dropped rows miss the consensus too; everyone else
                # (incl. sampled-out and adversarial rows) adopts
                delivered = (_row_where(dropped, params, blended)
                             if scenario else blended)
                if stale:
                    # ISSUE 16: emit the consensus displacement instead
                    # of adopting it — params stay at the trained value
                    # and the host delivers delta_out K+1 rounds later
                    # (a dropped row's delta is exactly zero)
                    delta_out = comms.stale_delta(delivered, params)
                else:
                    params = delivered
            else:
                contrib = (last_grads if not scenario
                           else corrupt(last_grads, None, noise_key))
                agg, _ = comms.aggregate_sim(contrib, **agg_kw)
                # reference semantics: the aggregate is discarded after
                # its norm (params untouched — SURVEY.md 3.2)
                agg_grad_norm = jax.vmap(optax.global_norm)(agg)
            metrics = dict(
                per_epoch,
                agg_grad_norm=agg_grad_norm,
                global_train_loss=bcast(mean_rows(
                    per_epoch["train_loss"].mean(axis=1))),
                global_train_acc=bcast(mean_rows(
                    per_epoch["train_acc"].mean(axis=1))),
                global_val_loss=bcast(mean_rows(
                    per_epoch["val_loss"].mean(axis=1))),
                global_val_acc=bcast(mean_rows(
                    per_epoch["val_acc"].mean(axis=1))),
            )
            new_state = TrainState(params=params,
                                   batch_stats=batch_stats,
                                   opt_state=opt_state,
                                   lr_epoch=lr_epoch, rng=rng,
                                   sync_residual=residual)
            if stale:
                return new_state, metrics, delta_out
            return new_state, metrics

        # delta_in (the last positional under staleness) is NOT donated:
        # the warmup rounds reuse one cached zeros tree
        return jax.jit(sim_round, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # Dispatch (the driver-facing round contract)
    # ------------------------------------------------------------------
    def round_start(self, state: TrainState, train_pack, val_pack,
                    poison=None):
        """Dispatch one simulated global epoch without blocking —
        ``round_start``'s contract with the simulated worker axis.
        ``poison`` is the real chaos harness's input and never arrives
        here (config rejects --chaos x --sim_workers)."""
        if poison is not None:
            raise ValueError(
                "the simulated engine takes no poison flags (--chaos is "
                "rejected with --sim_workers; use --sim_dropout / "
                "--sim_byzantine)")
        if not isinstance(train_pack[0], jax.Array):
            train_pack, val_pack = self.stage_pack(train_pack, val_pack)
        x, y, m = train_pack
        xv, yv, mv = val_pack
        key = (tuple(x.shape[1:]), tuple(xv.shape[1:]))
        if key not in self._round_cache:
            log.info("compiling simulated round program for %d workers, "
                     "shapes %s", self.n_workers, key)
            # tracked like every engine program (ISSUE 15): the one
            # vmap'd round executable's memory_analysis is what the
            # sim-lab N-ceiling measurement reads on a real chip
            self._track(key, self._build_round(key), "sim_round")
            if self.sim_staleness > 0 and \
                    "sim_deliver" not in self._round_cache:
                # the drain's delivery fold, AOT-compiled NOW (round 0 =
                # inside the sanitizer's warmup window — its first call
                # runs after the loop, where a fresh compile would bust
                # the zero-post-warmup-retrace budget)
                tp = self._track("sim_deliver",
                                 jax.jit(comms.deliver_stale,
                                         donate_argnums=(0,)),
                                 "sim_deliver")
                spec = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(
                        a.shape, a.dtype, sharding=a.sharding),
                    state.params)
                tp.compiled = tp._compile((spec, spec), {})
        extra = ()
        if self.scenario_on:
            active, dropped, noise_key = self._draw_scenario()
            self.rounds_scenario.append(
                {"active": int(active.sum()),
                 "dropped": int(dropped.sum()),
                 "byzantine": int(self.byz_count)})
            # explicit stages (transfer-guard-safe, like stage_poison)
            extra = (self._put(active, self._spec),
                     self._put(dropped, self._spec),
                     jax.device_put(noise_key))
        if self.sim_staleness > 0:
            # the real engine's delivery schedule, host-side: round R's
            # delta folds in at the entry of round R+K+1 (one delta is
            # appended per round, so at most one comes due here); the
            # first K+1 rounds deliver a cached zeros tree so ONE
            # program serves every round
            if self._sim_zeros is None:
                self._sim_zeros = _zeros_like_tree(state.params)
            delta_in = (self._sim_pending.pop(0)
                        if len(self._sim_pending) > self.sim_staleness
                        else self._sim_zeros)
            extra = extra + (delta_in,)
            new_state, metrics, delta_out = self._round_cache[key](
                state, x, y, m, xv, yv, mv, *extra)
            self._sim_pending.append(delta_out)
        else:
            new_state, metrics = self._round_cache[key](
                state, x, y, m, xv, yv, mv, *extra)
        self._arm_sync_stats(new_state.params)
        return new_state, ("packed", metrics, None, None, None)

    def drain_pending(self, state: TrainState) -> TrainState:
        """End-of-run fence (ISSUE 16 sim twin): fold every still-pending
        consensus delta (oldest first) so the final state reflects every
        simulated sync — the same drain contract as the real engine."""
        while self._sim_pending:
            delta = self._sim_pending.pop(0)
            params = self._round_cache["sim_deliver"](state.params, delta)
            state = state.replace(params=params)
        return (jax.block_until_ready(state) if self.sim_staleness
                else state)

    def round_streamed_start(self, state, train_chunks, val_chunks,
                             poison=None):
        raise NotImplementedError(
            "streamed rounds are a real-mesh feature "
            "(--stream_chunk_steps is rejected with --sim_workers)")

    def sim_summary(self, round_timings: list[dict],
                    state: TrainState) -> dict:
        """``results["sim"]`` (ISSUE 14 telemetry): the simulated scale,
        measured throughput, per-worker bytes (state residency + what
        one worker's sync would move on the simulated fabric), and the
        scenario provenance."""
        cfg = self.cfg
        comp = [t.get("compute_ms", 0.0) for t in round_timings]
        total_ms = float(sum(comp))
        out = {
            "workers": self.n_workers,
            "rounds": len(comp),
            "rounds_per_s": (round(1e3 * len(comp) / total_ms, 3)
                             if total_ms > 0 else None),
            "round_ms": [round(c, 3) for c in comp],
            "per_worker_state_bytes": self.state_resident_bytes(state),
            "per_worker_sync_bytes": int(self._sync_bytes or 0),
            # ISSUE 16: the delayed-delivery twin's K (0 = synchronous)
            "staleness": self.sim_staleness,
            "scenario": {
                "sample_frac": cfg.sim_sample_frac,
                "dropout": cfg.sim_dropout,
                "byzantine": cfg.sim_byzantine or None,
                "lr_jitter": cfg.sim_lr_jitter,
            },
        }
        if self.rounds_scenario:
            out["rounds_scenario"] = list(self.rounds_scenario)
        return out
