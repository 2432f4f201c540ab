"""Configuration: one dataclass + CLI covering the reference's whole flag
matrix.

The reference duplicates its argparse block per variant directory
(``Balanced All-Reduce/main.py:83-96``; ``Disbalanced All-Reduce/main.py:101``
adds ``--fixed_ratio``); the 2x3 variant matrix itself is "configured" by
directory choice.  Here topology (allreduce | ring | double_ring) and data
mode (balanced | disbalanced) are flags, collapsing six directories into one
framework.  Every reference flag name and default is preserved for parity.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any

from .xla_flags import compile_cache_dir


@dataclasses.dataclass
class Config:
    """Full run configuration.

    Parity flags (names + defaults match the reference CLI,
    ``Balanced All-Reduce/main.py:83-96``):
    """

    # --- reference-parity flags -------------------------------------------
    backend: str = "jax"          # ref: gloo|nccl (torch.dist) / implicit MPI.
    #                               Accepted values gloo|nccl|mpi are compat
    #                               no-ops: the backend is always XLA.
    epochs_local: int = 5
    epochs_global: int = 20
    batch_size: int = 64
    lr: float = 1e-3
    time_limit: float = 60.0      # straggler grace budget, seconds
    prev_fraction: float = 0.5    # re-partition: fraction from own prev shard
    next_fraction: float = 0.5    # re-partition: fraction from global pool
    aggregation_type: str = "equal"      # equal | weighted
    aggregation_by: str = "gradients"    # gradients | weights (ref default)
    local_weight: float = 0.5     # own-value weight in 'weighted' aggregation
    fixed_ratio: float = 0.5      # disbalanced: share of shard pinned to the
    #                               worker's two fixed classes
    #                               (Disbalanced All-Reduce/main.py:101)

    # --- variant selectors (directories in the reference, flags here) ------
    topology: str = "allreduce"   # allreduce | ring | double_ring
    data_mode: str = "balanced"   # balanced | disbalanced

    # --- framework-level knobs (new, TPU-first) ----------------------------
    model: str = "enhanced_cnn"   # enhanced_cnn | mlp | lenet5 | resnet18 |
    #                               resnet50 | bert_base | gpt2_small (+ tiny
    #                               test variants)
    dataset: str = "cifar10"      # cifar10 | mnist | imagenet |
    #                               synthetic_mlm | synthetic_lm
    num_workers: int = 0          # 0 => use all devices on the mesh data axis
    seed: int = 0
    dtype: str = "float32"        # param dtype
    compute_dtype: str = "bfloat16"  # activation/matmul dtype on TPU
    optimizer: str = "adam"       # ref: Adam (main.py:53)
    lr_step_size: int = 25        # StepLR(step_size=25) per LOCAL epoch
    lr_gamma: float = 0.1         # torch StepLR default gamma
    # Heterogeneity-proportional shard sizing.  The reference gives SLOWER
    # workers MORE data (shard size ~ measured duration,
    # Balanced All-Reduce/dataloader.py:149-151 — defect SURVEY.md 2.5.1).
    # 'inverse' is the sensible default; 'direct' reproduces the reference.
    proportionality: str = "inverse"   # inverse | direct | uniform
    probe_batches: int = 10       # timing-probe batches (dataloader.py:39)
    data_dir: str = "data"        # real CIFAR-10 binaries if present
    out_dir: str = "Graphs"       # plot output dir (ref: Graphs/*.png)
    checkpoint_dir: str = ""      # empty => checkpointing off
    checkpoint_every: int = 0     # global epochs between checkpoints
    # Async checkpoint engine (ISSUE 5): True = the round loop pays only
    # the device->host snapshot and a background thread serializes,
    # checksums, fsyncs, and manifest-commits the per-process shards;
    # False = the identical sharded write path runs inline (debugging;
    # the blocking twin of tests/test_checkpoint.py).  Either way the
    # save is gather-free and atomic (an epoch without its MANIFEST.json
    # is never restored from).
    ckpt_async: bool = True
    ckpt_keep: int = 3            # committed checkpoints retained by prune
    resume: bool = False
    profile_dir: str = ""         # empty => no jax.profiler traces
    log_level: str = "info"
    limit_train_samples: int = 0  # 0 => full dataset (tests use small values)
    limit_eval_samples: int = 0
    augment: bool = True          # AutoAugment-equivalent on-device policy

    # --- multi-axis mesh (beyond-reference parallelism) --------------------
    mesh_shape: str = "data=-1"   # e.g. "data=8", "data=4,model=2",
    #                               "data=2,model=2,pipe=2"
    sequence_parallel: str = "none"  # none | ring | ring_zigzag (causal
    #                                  models only) | all_to_all
    attention_impl: str = "dense"    # dense | flash (Pallas kernel; bert)
    model_width: int = 0             # EnhancedCNN channel base override
    #                                  (0 = reference width 64; smaller
    #                                  widths let the canonical epoch
    #                                  structure run on CPU-only hosts)
    pp_microbatches: int = 0         # GPipe microbatches (0 => pipe size)
    pp_schedule: str = "gpipe"       # gpipe | 1f1b (parallel/pp.py): 1f1b
    #                                  interleaves one backward per
    #                                  forward, capping in-flight
    #                                  residuals at O(stages) not O(M)
    pp_remat: bool = False           # [compat alias] rematerialize each
    #                                  layer under PP — equivalent to
    #                                  --remat_policy everything (kept so
    #                                  existing launch scripts work)
    # --- layer-scan compile engine (ISSUE 3) -------------------------------
    # layer_scan: stack each homogeneous transformer block's parameters
    # along a leading layer axis and run the stack under lax.scan — the
    # block traces/compiles ONCE instead of num_layers times, so compile
    # wall and HLO size stop growing with depth.  "auto" = on for the
    # homogeneous-block families (bert_*/gpt_*/llama_*/vit_*), off for
    # CNN/MLP models (heterogeneous blocks cannot stack); "on" requires a
    # homogeneous-block model; "off" keeps the unrolled twin (pipeline
    # parallelism still forces the stacked structure — the 'pipe' axis
    # shards the layer dim).
    layer_scan: str = "auto"         # auto | on | off
    # remat_policy: named jax.checkpoint policy for the scanned layer
    # stack (replaces the old remat bool).  "none" saves every
    # intermediate (fastest, most HBM); "dots_saveable" saves matmul
    # outputs and recomputes elementwise chains; "everything"
    # rematerializes the whole block from its boundary activations (the
    # GPipe-paper recipe, max memory saving at ~1/3 extra forward
    # compute).  Applies to the scanned stack (layer_scan on / PP).
    # ISSUE 15 named-activation tiers: "save_names:<a,b>" keeps exactly
    # the checkpoint_name-annotated activations in the set on device
    # (jax save_only_these_names), "offload_names:<a,b>" additionally
    # offloads them to pinned host memory between forward and backward
    # (save_and_offload_only_these_names; demoted to the same-set
    # save_names with a logged reason on backends without a
    # pinned_host memory space).  Names are
    # validated EAGERLY against the model family's emitted vocabulary
    # (models.remat_name_vocab: attn_out / mlp_out / block_out /
    # moe_dispatch) — a typo'd name would otherwise silently degrade
    # the policy to save-nothing.  All policies are bitwise-identical
    # in fp32 (remat moves residency, never math).  What every policy
    # keeps besides: ``models.checkpoint_policy``'s comment.
    remat_policy: str = "none"       # none | dots_saveable | everything
    #                                  | save_names:<set>
    #                                  | offload_names:<set>
    # grad_accum: split each train step's batch into K microbatches and
    # scan them with a donated fp32 gradient carry — per-device activation
    # memory is bounded by B/K while the effective batch, the optimizer
    # step count, and the round-sync cadence are unchanged.  Matches the
    # full-batch step within fp32 summation tolerance (exact at K=1:
    # the K=1 path is the unmodified step).
    grad_accum: int = 1
    num_experts: int = 0             # >0 => MoE FFN in bert/gpt layers
    num_kv_heads: int = 0            # >0 => GQA (llama_* models)
    expert_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01     # load-balance aux loss coefficient
    # Streamed input pipeline: >0 = feed the round in chunks of this many
    # steps (host window + async double-buffered transfer) instead of
    # materializing the whole epoch — required at ImageNet scale.
    stream_chunk_steps: int = 0
    # Streamed-path producer thread: how many packed windows may be staged
    # on device ahead of the consumer (2 = double buffering); 0 packs and
    # stages synchronously.
    stream_prefetch: int = 2
    # Overlapped round pipeline: dispatch round r, then fetch/assemble its
    # metrics on a worker thread and re-partition + pack round r+1 on the
    # host while the device computes — the between-round host gap hides
    # behind device time.  The straggler EMA consumes measured walls one
    # round delayed in BOTH modes, so overlapped and serial runs produce
    # identical results (False = fully serial: debugging, and the twin
    # tests/test_overlap.py compares against).
    overlap_rounds: bool = True
    # Semi-synchronous rounds (ISSUE 16): K > 0 dispatches round R+1's
    # local phase immediately off the PRE-sync params while round R's
    # standalone sync program runs concurrently on device; the sync's
    # output is carried as a consensus DELTA (blend - pre-sync params)
    # and folded into the freshly trained params at the entry of round
    # R+K+1 — at most K sync programs are in flight under any round's
    # compute.  K = 0 is today's fully synchronous engine, bitwise.
    # Weights (FedAvg) aggregation only; the v1 combos that cannot
    # compose (chaos faults, elastic membership, multi-slice DCN,
    # scatter-resident params, buddy redundancy, streamed rounds,
    # checkpointing) are rejected eagerly below with the real reasons.
    sync_staleness: int = 0
    # Persistent XLA compilation cache: "" = off, anything else = on.
    # WHERE it lives is not set here — xla_flags.compile_cache_dir() is
    # the one rule ($JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache).  The CLI defaults it on; library/test
    # callers opt in.
    compile_cache_dir: str = ""
    # --- round-sync engine (bucketed collectives) --------------------------
    # sync_mode: how the once-per-round parameter/gradient aggregation
    # runs.  "sharded" selects the bucketed fast engine for the CONFIGURED
    # topology: flatten-and-bucket -> psum_scatter -> scale the 1/N shard
    # -> all_gather for allreduce, and flatten-and-bucket -> per-bucket
    # ppermute hops -> local fp32 blend for ring/double-ring gossip (both
    # bit-identical to dense in fp32).  "dense" = the legacy per-leaf
    # pmean/psum/ppermute path; "auto" = the fast engine on TPU (and
    # whenever compression is requested), dense otherwise.  The resolution
    # is per topology — see ``resolve_sync_mode``.
    sync_mode: str = "auto"          # auto | dense | sharded
    # Wire dtype of the bucketed sync collectives (allreduce AND gossip).
    # bfloat16 halves the bytes on the wire; int8 quarters them
    # (per-bucket fp32 scale, symmetric round-to-nearest — the second
    # compression tier); fp32 keeps the bit-identical-to-dense guarantee.
    sync_dtype: str = "float32"      # float32 | bfloat16 | int8
    # Compression error handling for compressed sync_dtype: "ef" carries
    # fp32 error-feedback residuals in the train state (weights mode), so
    # quantization error accumulates in the residual, not the parameters.
    sync_compression: str = "none"   # none | ef
    # Sharded-sync bucket size (MiB of fp32 parameters per collective).
    sync_bucket_mb: float = 4.0
    # --- hierarchical two-level sync (ISSUE 13) ----------------------------
    # num_slices: outer slice count of the two-level worker grid.  1 (the
    # default) is the flat world — every code path is EXACTLY the
    # pre-ISSUE-13 engine (no slice mesh axis is ever built).  S > 1
    # composes the two sync engines the paper's topology matrix keeps
    # separate: each slice's W workers all-reduce over the ICI-shaped
    # ``data`` axis via the bucketed psum_scatter/all_gather engine
    # (inner level), and the S slice consensuses gossip over the
    # DCN-shaped ``slice`` axis via per-bucket ppermute hops (outer
    # level, --topology ring | double_ring) — one donated shard_map
    # program over the nested axes, with the outer hop riding each
    # worker's 1/W scatter shard so DCN wire bytes are bucket/W per hop.
    # v1 composition limits (rejected eagerly, documented in
    # docs/ARCHITECTURE.md): outer allreduce (that is the flat S*W
    # engine — use --num_slices 1), a dense inner level, inner model
    # axes (TP/PP/SP/EP/FSDP), elastic membership / --chaos faults, and
    # explicit buddy redundancy.
    num_slices: int = 1
    # Wire dtype of the OUTER (DCN) gossip hops; "" inherits --sync_dtype.
    # The production shape compresses the slow inter-slice wire (int8 +
    # EF) while the fast ICI level stays fp32 — exactly the per-level
    # resolution the DCN/ICI split exists for.
    sync_dtype_outer: str = ""
    # --- shard-resident optimizer placement (ISSUE 9) ----------------------
    # opt_placement: where the round-boundary optimizer transform (the
    # FedAvg blend + EF bookkeeping, and in gradients mode the round-level
    # Adam moment tracker of the aggregated gradient) runs and where its
    # state lives — the ZeRO-1 cross-replica weight-update scheme
    # ("Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
    # Training", PAPERS.md).  "sharded" runs the apply between psum_scatter
    # and all_gather on each worker's 1/N bucket shard and stores the
    # round-optimizer moments sharded over the worker axis (per-worker
    # state and apply FLOPs drop N-fold; only post-update weights ride the
    # all_gather home); "replicated" is the post-gather full-size twin
    # (every worker applies the whole update); "auto" = sharded whenever
    # the bucketed sharded sync engine is active.  Ring/double-ring
    # gossip resolves to "local": gossip blends are worker-specific by
    # construction (no global reduce), so there is no
    # cross-replica-redundant apply to shard — see docs/ARCHITECTURE.md.
    # In fp32 the two placements are bit-identical
    # (tests/test_opt_placement.py).
    opt_placement: str = "auto"      # auto | replicated | sharded
    # --- scatter-resident consensus params (ISSUE 11) ----------------------
    # param_residency: where the consensus parameter tree LIVES between
    # rounds — the round-loop twin of per-step ZeRO-3 (parallel/fsdp.py),
    # built on the ISSUE 9 scatter -> APPLY -> gather decomposition.
    # "resident" keeps each worker's 1/N bucket shard of the consensus
    # (the psum_scatter output, post-apply) as the ONLY between-round
    # parameter state: the trailing all_gather of the sync moves to the
    # NEXT round's entry, inside the donated round program, so the
    # gathered full tree is transient compute-scope memory — per-worker
    # parameter residency and checkpoint payload drop N-fold.
    # "replicated" is the full-tree-per-worker twin (the pre-ISSUE-11
    # layout); "auto" = resident whenever the bucketed sharded sync
    # engine is active AND the between-round params are a shared
    # consensus at all — weights (FedAvg) aggregation with the "equal"
    # blend.  Everything else resolves to replicated for the ISSUE 9
    # reasons: gossip blends and the weighted blend's own-term are
    # worker-specific by construction, and gradients-mode params are
    # never synced — worker-local state has no cross-replica-redundant
    # consensus to shard (docs/ARCHITECTURE.md).  In fp32 (and through
    # the compressed wire's decode) resident trajectories are BITWISE
    # identical to the replicated twin: the entry gather moves exactly
    # the bytes the exit gather used to (tests/test_param_residency.py).
    param_residency: str = "auto"    # auto | replicated | resident
    # --- buddy-redundant resident shards (ISSUE 12) -------------------------
    # shard_redundancy: whether the sync program keeps a second live copy
    # of every SHARD-RESIDENT 1/N state span (scatter-resident params,
    # the sharded round-optimizer rows, the EF residual's consensus
    # span).  "buddy" fuses one extra per-bucket ppermute hop onto the
    # donated sync program at scatter exit: every worker also receives
    # its ring-PREDECESSOR's resident rows (comms.ring_neighbors is the
    # buddy map), so each span lives on exactly two workers and an
    # abrupt mid-round worker loss is recoverable entirely in memory —
    # the crashed worker's spans are reconstructed from its buddy at the
    # rollback boundary, no checkpoint-restore I/O on the recovery path.
    # "auto" = buddy whenever any state actually resolves shard-resident
    # (otherwise nothing is uniquely held and redundancy is a no-op);
    # "off" disables the hop — a crash then degrades to the newest
    # committed checkpoint (the double-fault ladder, logged + counted).
    # The extra hop is pure data movement: the no-redundancy program's
    # outputs are bitwise-unchanged, and the hop's wire bytes are
    # accounted into sync_bytes (tests/test_sync.py).
    shard_redundancy: str = "auto"   # auto | buddy | off
    # --- runtime sanitizer (ISSUE 6) ---------------------------------------
    # sanitize: arm the round-loop correctness harness — the driver wraps
    # every round dispatch/wait in jax.transfer_guard("disallow") (any
    # IMPLICIT host<->device transfer in the hot path raises), enforces a
    # zero-retrace budget after the warmup round (rounds 2..K must add no
    # jaxpr traces or backend compiles), and asserts the donated round
    # state's buffers were actually deleted by each engine call (missed
    # donation silently doubles peak memory).  A clean run records all
    # zeros in results["sanitize"].  Also armed by JAX_GRAFT_SANITIZE=1.
    sanitize: bool = False
    # --- elastic membership + chaos harness (ISSUE 8) ----------------------
    # chaos: fault-injection plan for the simulated N-worker CPU driver.
    # Scripted spec — comma-separated `kind@round[:wID][xF][+S][*K]`
    # events (kill/join/slow/stall, rounds are 0-based global epochs,
    # membership changes land at the boundary ENTERING that round) — or
    # the literal "random" (chaos_seed/chaos_events draw the schedule up
    # front, so checkpoint resume replays it identically).  "" = off.
    chaos: str = ""
    chaos_seed: int = 0           # random-mode schedule seed
    chaos_events: int = 4         # random-mode event count
    # Random-mode kind selection (ISSUE 12 satellite): the kinds a
    # `--chaos random` schedule may draw.  Defaults to the PR 8
    # cooperative/timing faults; the unplanned-failure kinds
    # (crash/nan) are opt-in — e.g. --chaos_kinds kill,join,crash,nan —
    # so a random schedule never silently starts exercising the
    # rollback-recovery machinery.  Scripted specs are unaffected.
    chaos_kinds: str = "kill,join,slow,stall"
    # Straggler departure protocol (retry/timeout/backoff around the
    # round sync): a worker whose measured round wall exceeds
    # time_limit + chaos_grace*(1 + chaos_backoff*attempt) has overrun;
    # up to chaos_retries CONSECUTIVE overruns are tolerated as logged
    # retries with the backoff-extended deadline, one more and the
    # worker is treated as DEPARTED — its state row dropped and its
    # shard redistributed at the next round boundary.
    chaos_grace: float = 5.0
    chaos_retries: int = 1
    chaos_backoff: float = 0.5
    # Quorum floor: membership events that would leave fewer live
    # workers are rejected (logged + counted), never partially applied —
    # the run degrades gracefully to the surviving quorum instead.
    elastic_min_workers: int = 1
    # --- serving engine (ISSUE 7: `main.py serve`) -------------------------
    # Continuous-batching inference off a sharded checkpoint: the model
    # self-configures from the checkpoint's MANIFEST metadata
    # (--checkpoint_dir points at the run's checkpoint root or one
    # committed ckpt_<E> dir); these knobs shape the two compiled
    # programs (per-bucket prefill + one fixed-batch decode step) and the
    # paged KV cache behind them.
    serve_max_batch: int = 4      # decode slots (the fixed decode shape)
    serve_page_size: int = 16     # tokens per KV-cache page
    serve_max_pages: int = 64     # page-pool size (page 0 = trash page)
    serve_prompt_buckets: str = "16,64"  # prefill program lengths, csv
    serve_eos_id: int = -1        # sampling this id evicts (-1 = off)
    serve_max_new_tokens: int = 16  # per-request generation budget
    serve_temperature: float = 0.0  # 0 = greedy
    serve_requests: int = 8       # synthetic requests when no prompt given
    serve_prompt: str = ""        # fixed prompt (csv token ids) for all
    #                               requests; "" = per-request synthetic
    # Per-request wall-clock timeout (seconds; 0 = off): an admitted
    # sequence still decoding past this budget is EVICTED (reason
    # "timeout", counted in results["serve"]["timed_out"]) so a stuck
    # request can never pin decode slots and cache pages forever.
    serve_request_timeout: float = 0.0
    # --- serving fast path (ISSUE 17) --------------------------------------
    # Paged prefix cache: pages become content-addressed (a page's key is
    # the rolling hash of the token prefix it closes) with a refcounted
    # hash -> physical-page index.  Admission walks the prompt's
    # page-aligned prefix and maps every cached page straight into the
    # new sequence's page table BY REFERENCE (never copied — the
    # cache-offset causal mask makes shared pages position-safe),
    # prefilling only the cold tail, so N requests sharing a system
    # prompt pay its KV once.  Eviction moves from the free-list head to
    # refcount-0 LRU; results["serve"] gains page_reuse_ratio +
    # prefill_tokens_saved.
    serve_prefix_cache: bool = False
    # Chunked prefill: > 0 replaces the per-bucket monolithic prefill
    # with ONE fixed-shape [1, C] chunk program interleaved into the
    # decode loop — a long cold prompt advances C tokens per scheduler
    # tick instead of stalling every running stream, and the compiled
    # prefill set shrinks from one-per-bucket to exactly one.  Must be a
    # positive multiple of --serve_page_size (chunk boundaries must land
    # on page boundaries); 0 = the monolithic per-bucket path.
    serve_prefill_chunk: int = 0
    # Speculative decoding (ISSUE 18): a small DRAFT model (its own
    # sharded checkpoint, loaded through the same manifest path) runs k
    # fixed-shape greedy decode steps per scheduler tick through its own
    # paged KV pool; the target scores all k+1 positions in ONE [B, k+1]
    # verify program with the accept/reject fused on, committing the
    # longest accepted prefix + one bonus token.  Greedy speculative
    # output is BITWISE the non-speculative twin's — the speedup is
    # provably free.  Both flags or neither; greedy only
    # (--serve_temperature 0).
    serve_draft_ckpt: str = ""    # draft checkpoint dir ("" = off)
    serve_spec_tokens: int = 0    # draft tokens per verify (k); 0 = off
    # --- scenario lab: vmap'd many-worker simulator (ISSUE 14) -------------
    # sim_workers: > 0 runs the ENTIRE local-SGD round for that many
    # workers as one vmap'd, donated jit on a SINGLE chip — per-worker
    # data slices, RNG streams and SGD/Adam state stacked on a leading
    # [N, ...] axis (exactly the layer-scan stacking trick, applied to
    # the worker axis), the sync point as pure stacked math
    # (comms.aggregate_sim, the flat-primitives reference path's twin —
    # fp32 N=8 simulated is BITWISE N=8 real-mesh rounds).  N becomes a
    # batch dimension instead of a process count, so hundreds of workers
    # fit where the real mesh caps at the device count.  0 = off (the
    # real-mesh driver).  Real-mesh-only features are rejected eagerly
    # below: elastic/chaos process semantics, buddy redundancy,
    # multi-slice DCN, inner model axes, streamed rounds, checkpoints
    # (v1), an explicit real-mesh worker count.
    sim_workers: int = 0
    # Client sampling: each round draws ceil(frac * N) participants
    # (seeded by --seed, deterministic).  Sampled-out workers skip the
    # round's local training and contribution but ADOPT the consensus
    # their topology delivers (allreduce: the survivors' mean; gossip:
    # a participating predecessor's payload) — FedAvg client sampling.
    sim_sample_frac: float = 1.0     # (0, 1]; 1 = everyone, every round
    # Worker dropout: each round each worker independently vanishes with
    # this probability (seeded) — it neither trains, contributes, NOR
    # adopts (the whole round is a no-op for it; unlike a sampled-out
    # worker it misses the consensus too).
    sim_dropout: float = 0.0         # [0, 1)
    # Byzantine adversaries: "kind:count[:scale]" — the LAST `count`
    # worker ids corrupt their sync contribution every round.  Kinds:
    # "signflip" (weights mode: 2*entry - trained, i.e. the round's
    # update sign-flipped; gradients mode: -grad) and "noise" (payload +
    # scale * N(0,1), fresh seeded draw per round; scale defaults 1.0).
    # Their LOCAL state stays honest — they adopt blends like everyone —
    # so the knob isolates the poisoned-contribution effect.  "" = off.
    sim_byzantine: str = ""
    # Per-worker learning-rate jitter: worker i trains with
    # lr * (1 + jitter * u_i), u_i a seeded uniform[-1, 1) draw fixed
    # for the run — heterogeneous-tuning scenarios.  0 = off (the real
    # path's arithmetic, byte-for-byte).
    sim_lr_jitter: float = 0.0       # [0, 1)
    # Simulated staleness (ISSUE 16): the scenario lab's twin of
    # --sync_staleness — each round's consensus delta is queued and
    # folded in K rounds late, so staleness-vs-convergence is
    # characterized across the 2x3 balanced/disbalanced x topology
    # matrix on one chip before any hardware is rented.  Requires
    # --sim_workers; 0 = synchronous (the unmodified lab, bitwise).
    sim_staleness: int = 0

    def __post_init__(self) -> None:
        _choices("backend", self.backend, ("jax", "gloo", "nccl", "mpi"))
        _choices("aggregation_type", self.aggregation_type, ("equal", "weighted"))
        _choices("aggregation_by", self.aggregation_by, ("gradients", "weights"))
        _choices("topology", self.topology, ("allreduce", "ring", "double_ring"))
        _choices("data_mode", self.data_mode, ("balanced", "disbalanced"))
        _choices("proportionality", self.proportionality, ("inverse", "direct", "uniform"))
        _choices("attention_impl", self.attention_impl, ("dense", "flash"))
        _choices("layer_scan", self.layer_scan, ("auto", "on", "off"))
        self.parse_remat_policy()   # validates spelling + names eagerly
        _choices("sync_mode", self.sync_mode, ("auto", "dense", "sharded"))
        _choices("sync_dtype", self.sync_dtype,
                 ("float32", "bfloat16", "int8"))
        _choices("sync_compression", self.sync_compression, ("none", "ef"))
        _choices("opt_placement", self.opt_placement,
                 ("auto", "replicated", "sharded"))
        _choices("param_residency", self.param_residency,
                 ("auto", "replicated", "resident"))
        _choices("shard_redundancy", self.shard_redundancy,
                 ("auto", "buddy", "off"))
        if self.grad_accum < 1:
            raise ValueError(
                f"grad_accum must be >= 1, got {self.grad_accum}")
        if self.grad_accum > 1 and self.batch_size % self.grad_accum:
            raise ValueError(
                f"--batch_size {self.batch_size} must be divisible by "
                f"--grad_accum {self.grad_accum} (microbatch split)")
        compressed_wire = self.sync_dtype in ("bfloat16", "int8")
        if compressed_wire and self.sync_mode == "dense":
            raise ValueError(
                f"--sync_dtype {self.sync_dtype} is the bucketed engines' "
                "compressed wire format; it cannot combine with "
                "--sync_mode dense")
        if self.opt_placement == "sharded" and self.sync_mode == "dense":
            raise ValueError(
                "--opt_placement sharded runs the optimizer apply between "
                "psum_scatter and all_gather — a bucketed-sync-engine "
                "stage; it cannot combine with --sync_mode dense")
        if self.opt_placement == "replicated" and compressed_wire:
            raise ValueError(
                f"--opt_placement replicated cannot combine with "
                f"--sync_dtype {self.sync_dtype}: a compressed wire "
                "quantizes the gathered mean, which forces the "
                "scale-then-encode apply onto the 1/N shard (the sharded "
                "placement) — a post-gather replicated apply would gather "
                "the uncompressed fp32 sum instead")
        if (self.param_residency == "resident"
                and self.topology != "allreduce" and self.num_slices == 1):
            # hierarchical runs (num_slices > 1) are exempt: there the
            # ring/double_ring topology names the OUTER slice level and
            # the between-round state is each slice's consensus — a
            # worker-invariant-within-slice tree whose 1/W scatter shard
            # CAN stay resident (resolve_param_residency)
            raise ValueError(
                f"--param_residency resident cannot combine with "
                f"--topology {self.topology}: gossip blends are "
                "worker-local by construction — every worker's post-round "
                "params are a different function of its own value, so "
                "there is no cross-replica-redundant consensus tree to "
                "keep scatter-resident (the same argument that resolves "
                "--opt_placement to 'local' there)")
        if self.param_residency == "resident" and self.sync_mode == "dense":
            raise ValueError(
                "--param_residency resident keeps the psum_scatter "
                "output as the between-round parameter state — a bucketed-"
                "sync-engine stage; it cannot combine with "
                "--sync_mode dense (no scatter whose output could stay "
                "resident)")
        if (self.param_residency == "resident"
                and self.opt_placement == "replicated"):
            raise ValueError(
                "--param_residency resident stores the SHARD-side apply "
                "output (the scaled 1/N scatter shard) as the resident "
                "state; --opt_placement replicated applies post-gather "
                "full-size and leaves no per-shard apply output to keep "
                "resident")
        if self.shard_redundancy == "buddy" and self.num_slices == 1 and (
                self.topology != "allreduce" or self.sync_mode == "dense"):
            raise ValueError(
                "--shard_redundancy buddy protects SHARD-RESIDENT state "
                "(scatter-resident params / sharded round-optimizer "
                "rows), which only the bucketed sharded allreduce engine "
                f"produces; --topology {self.topology} / --sync_mode "
                f"{self.sync_mode} keeps every state worker-local or "
                "replicated — nothing is uniquely held, so there is "
                "nothing for a buddy to back up (auto resolves this to "
                "off)")
        # --- hierarchical two-level sync (ISSUE 13): eager v1 limits ----
        _choices("sync_dtype_outer", self.sync_dtype_outer,
                 ("", "float32", "bfloat16", "int8"))
        if self.num_slices < 1:
            raise ValueError(
                f"num_slices must be >= 1, got {self.num_slices}")
        if self.sync_dtype_outer and self.num_slices == 1:
            raise ValueError(
                "--sync_dtype_outer sets the OUTER (DCN) gossip wire of "
                "the hierarchical sync; it requires --num_slices >= 2 "
                "(a flat run has no outer level)")
        outer_compressed = (self.sync_dtype_outer or self.sync_dtype) in (
            "bfloat16", "int8")
        if self.num_slices > 1:
            if self.topology == "allreduce":
                raise ValueError(
                    "--num_slices > 1 syncs the outer slice level with "
                    "the ppermute GOSSIP engine (--topology ring | "
                    "double_ring); an allreduce outer level is just the "
                    "flat sharded allreduce over all S*W workers — run "
                    "it as --num_slices 1")
            if self.sync_mode == "dense":
                raise ValueError(
                    "--num_slices > 1 runs the bucketed sharded "
                    "psum_scatter/all_gather engine on the inner (ICI) "
                    "level — the outer gossip hop rides its 1/W scatter "
                    "shard; a dense inner level has no shard for the "
                    "hop to ride (--sync_mode dense rejected)")
            if self.chaos:
                raise ValueError(
                    "--chaos cannot combine with --num_slices > 1 in "
                    "v1: elastic membership and the crash/NaN fault "
                    "machinery operate on the flat worker axis (mesh "
                    "resize, ring buddy map, quorum floor are all "
                    "single-level) — per-slice membership is the "
                    "ROADMAP follow-on")
            if self.shard_redundancy == "buddy":
                raise ValueError(
                    "--shard_redundancy buddy cannot combine with "
                    "--num_slices > 1 in v1: the buddy map is the flat "
                    "worker-axis ring, and crash recovery (its consumer) "
                    "is rejected under slices anyway (auto resolves to "
                    "off)")
            if self.opt_placement == "replicated":
                raise ValueError(
                    "--opt_placement replicated cannot combine with "
                    "--num_slices > 1: the outer gossip hop rides the "
                    "1/W scatter shard, so the apply (inner mean scale, "
                    "gossip blend, wire encode) necessarily runs "
                    "shard-side — there is no post-gather full-size "
                    "apply stage in the hierarchical program")
        if self.sync_compression == "ef" and not (compressed_wire
                                                  or outer_compressed):
            raise ValueError(
                "--sync_compression ef compensates compressed-wire "
                "rounding; it requires a compressed --sync_dtype (or, "
                "hierarchically, --sync_dtype_outer) of bfloat16 or int8")
        if self.checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every must be >= 0, got {self.checkpoint_every}")
        if self.checkpoint_every > 0 and not self.checkpoint_dir:
            raise ValueError(
                "--checkpoint_every needs --checkpoint_dir (nowhere to "
                "write the shards)")
        if self.resume and not self.checkpoint_dir:
            raise ValueError(
                "--resume needs --checkpoint_dir (nowhere to restore from)")
        if self.ckpt_keep < 1:
            raise ValueError(
                f"ckpt_keep must be >= 1, got {self.ckpt_keep}")
        if self.sync_bucket_mb <= 0:
            raise ValueError(
                f"sync_bucket_mb must be positive, got {self.sync_bucket_mb}")
        if self.serve_max_batch < 1 or self.serve_page_size < 1:
            raise ValueError(
                f"serve_max_batch ({self.serve_max_batch}) and "
                f"serve_page_size ({self.serve_page_size}) must be >= 1")
        if self.serve_max_pages < 2:
            raise ValueError(
                f"serve_max_pages must be >= 2 (page 0 is the reserved "
                f"trash page), got {self.serve_max_pages}")
        if self.serve_max_new_tokens < 1 or self.serve_requests < 1:
            raise ValueError(
                "serve_max_new_tokens and serve_requests must be >= 1, "
                f"got {self.serve_max_new_tokens}/{self.serve_requests}")
        if self.serve_temperature < 0.0:
            raise ValueError(
                f"serve_temperature must be >= 0 (0 = greedy), got "
                f"{self.serve_temperature}")
        if self.serve_request_timeout < 0.0:
            raise ValueError(
                f"serve_request_timeout must be >= 0 (0 = off), got "
                f"{self.serve_request_timeout}")
        if self.serve_prefill_chunk < 0 or (
                self.serve_prefill_chunk
                and self.serve_prefill_chunk % self.serve_page_size):
            raise ValueError(
                f"--serve_prefill_chunk must be a positive multiple of "
                f"--serve_page_size ({self.serve_page_size}) — chunk "
                f"boundaries must land on page boundaries so every chunk "
                f"writes whole pages (and the prefix cache can key them) "
                f"— got {self.serve_prefill_chunk}; 0 disables chunking")
        # speculative decoding (ISSUE 18): every v1 limit rejected
        # eagerly with its real reason, never three ticks into a run
        if bool(self.serve_draft_ckpt) != bool(self.serve_spec_tokens):
            raise ValueError(
                "--serve_draft_ckpt and --serve_spec_tokens arm "
                "speculative decoding TOGETHER (the draft proposes, k "
                "sizes the verify program) — one without the other is "
                f"inert; got draft_ckpt={self.serve_draft_ckpt!r}, "
                f"spec_tokens={self.serve_spec_tokens}")
        if self.serve_spec_tokens < 0:
            raise ValueError(
                f"--serve_spec_tokens must be >= 1 (0 disables), got "
                f"{self.serve_spec_tokens}")
        if self.serve_draft_ckpt and self.serve_temperature > 0.0:
            raise ValueError(
                f"--serve_temperature {self.serve_temperature} with "
                "--serve_draft_ckpt: v1 speculative acceptance is greedy "
                "argmax equality against the verify logits — temperature "
                "sampling needs the stochastic rejection-sampling rule "
                "(accept with prob min(1, p_target/p_draft)) that is not "
                "implemented; serve greedy or drop the draft")
        buckets = self.parse_prompt_buckets()   # validates the csv eagerly
        if self.serve_prefix_cache:
            # the serve engine sizes sequences at max_seq = largest
            # bucket + serve_max_new_tokens (+ spec_tokens of verify
            # overshoot); if ONE such sequence can pin the whole pool
            # there is never a refcount-0 page to retain, so the cache
            # could only ever thrash — reject eagerly
            longest = (buckets[-1] + self.serve_max_new_tokens
                       + self.serve_spec_tokens)
            seq_pages = -(-longest // self.serve_page_size)
            if seq_pages >= self.serve_max_pages - 1:
                raise ValueError(
                    f"--serve_prefix_cache needs page-pool headroom "
                    f"beyond one max-length sequence: a {longest}-token "
                    f"sequence (largest bucket {buckets[-1]} + "
                    f"serve_max_new_tokens {self.serve_max_new_tokens}"
                    + (f" + serve_spec_tokens {self.serve_spec_tokens}"
                       if self.serve_spec_tokens else "") + ") "
                    f"pins {seq_pages} of the {self.serve_max_pages - 1} "
                    f"usable pages (page 0 is the trash page), so no "
                    f"page could ever stay cached — raise "
                    f"--serve_max_pages past {seq_pages + 1}")
        if self.chaos and self.chaos.strip().lower() != "random":
            # eager spec validation, like parse_prompt_buckets: a typo'd
            # --chaos fails at argparse time, not at round boundary 3
            from .chaos import parse_chaos_spec
            parse_chaos_spec(self.chaos)
        self.parse_chaos_kinds()   # validates the csv eagerly
        if self.chaos_events < 0 or self.chaos_retries < 0:
            raise ValueError(
                f"chaos_events ({self.chaos_events}) and chaos_retries "
                f"({self.chaos_retries}) must be >= 0")
        if self.chaos_grace < 0.0 or self.chaos_backoff < 0.0:
            raise ValueError(
                f"chaos_grace ({self.chaos_grace}) and chaos_backoff "
                f"({self.chaos_backoff}) must be >= 0")
        if self.elastic_min_workers < 1:
            raise ValueError(
                f"elastic_min_workers must be >= 1, got "
                f"{self.elastic_min_workers}")
        if not 0.0 <= self.local_weight <= 1.0:
            raise ValueError(f"local_weight must be in [0,1], got {self.local_weight}")
        if not 0.0 <= self.fixed_ratio <= 1.0:
            raise ValueError(f"fixed_ratio must be in [0,1], got {self.fixed_ratio}")
        # --- scenario lab (ISSUE 14): eager validation -------------------
        if self.sim_workers < 0:
            raise ValueError(
                f"sim_workers must be >= 0 (0 = real-mesh driver), got "
                f"{self.sim_workers}")
        if not 0.0 < self.sim_sample_frac <= 1.0:
            raise ValueError(
                f"--sim_sample_frac must be in (0, 1] (each round samples "
                f"ceil(frac * N) >= 1 participants), got "
                f"{self.sim_sample_frac}")
        if not 0.0 <= self.sim_dropout < 1.0:
            raise ValueError(
                f"--sim_dropout must be in [0, 1) (1.0 would drop every "
                f"worker every round — no round could ever commit), got "
                f"{self.sim_dropout}")
        if not 0.0 <= self.sim_lr_jitter < 1.0:
            raise ValueError(
                f"--sim_lr_jitter must be in [0, 1): worker i trains at "
                f"lr * (1 + jitter * u_i) with u_i in [-1, 1), and jitter "
                f">= 1 could drive a learning rate to zero or negative; "
                f"got {self.sim_lr_jitter}")
        self.parse_sim_byzantine()   # validates the spec eagerly
        if self.sim_workers == 0:
            for flag, dflt, name in (
                    (self.sim_sample_frac, 1.0, "--sim_sample_frac"),
                    (self.sim_dropout, 0.0, "--sim_dropout"),
                    (self.sim_byzantine, "", "--sim_byzantine"),
                    (self.sim_lr_jitter, 0.0, "--sim_lr_jitter")):
                if flag != dflt:
                    raise ValueError(
                        f"{name} is a simulated-scenario knob; it needs "
                        "--sim_workers N (the real-mesh driver has no "
                        "per-round participation/adversary machinery)")
        else:
            if self.chaos:
                raise ValueError(
                    "--chaos cannot combine with --sim_workers: the chaos "
                    "harness injects faults into the REAL driver's "
                    "process semantics (measured walls, membership "
                    "boundaries, mesh rebuilds) which the vmap'd "
                    "simulator replaces with stacked math — use "
                    "--sim_dropout / --sim_byzantine for simulated "
                    "failure scenarios")
            if self.num_slices > 1:
                raise ValueError(
                    "--num_slices > 1 cannot combine with --sim_workers: "
                    "the hierarchical sync models a real multi-slice DCN "
                    "fabric (nested mesh axes, per-level wires) — the "
                    "simulator's fabric is stacked math on one chip; "
                    "simulate the flat topologies instead")
            if self.shard_redundancy == "buddy":
                raise ValueError(
                    "--shard_redundancy buddy cannot combine with "
                    "--sim_workers: buddy redundancy protects REAL "
                    "shard-resident state against a real worker's crash "
                    "— every simulated worker's rows already live on the "
                    "one chip (nothing is uniquely held; auto resolves "
                    "to off)")
            if self.opt_placement == "sharded":
                raise ValueError(
                    "--opt_placement sharded cannot combine with "
                    "--sim_workers: the shard-resident apply is a stage "
                    "of the real bucketed sync engine (psum_scatter/"
                    "all_gather over a real worker axis) — the simulated "
                    "sync is the dense-semantics stacked twin "
                    "(comms.aggregate_sim), which has no scatter phase "
                    "to place an apply between")
            if self.param_residency == "resident":
                raise ValueError(
                    "--param_residency resident cannot combine with "
                    "--sim_workers: scatter-resident params ARE the real "
                    "engine's 1/N scatter output kept between rounds — "
                    "the simulated worker axis lives on one chip, where "
                    "every row is already resident (nothing to gather)")
            if self.sync_mode == "sharded":
                raise ValueError(
                    "--sync_mode sharded cannot combine with "
                    "--sim_workers: the bucketed sharded engine runs "
                    "real collectives over a real mesh axis — the "
                    "simulated sync is comms.aggregate_sim, the stacked "
                    "twin of the dense reference path (fp32 sharded is "
                    "bitwise dense anyway, so nothing is lost)")
            if self.stream_chunk_steps > 0:
                raise ValueError(
                    "--stream_chunk_steps cannot combine with "
                    "--sim_workers in v1: the streamed round feeds "
                    "per-chunk shard_map programs over the real worker "
                    "axis — the simulator runs the whole-round vmap'd "
                    "program (its pack already scales as one [N, S, B] "
                    "stack on one chip)")
            if self.checkpoint_dir or self.resume:
                raise ValueError(
                    "--checkpoint_dir/--resume cannot combine with "
                    "--sim_workers in v1: the sharded checkpoint "
                    "engine's layouts and manifest worker-axis "
                    "bookkeeping describe the real mesh — simulated "
                    "runs are cheap to replay from seed (ROADMAP names "
                    "sim checkpointing as the follow-on)")
            if self.num_workers:
                raise ValueError(
                    f"--num_workers {self.num_workers} sizes the REAL "
                    "mesh data axis; with --sim_workers the worker axis "
                    "is simulated on one chip — drop --num_workers (the "
                    "simulated count is --sim_workers)")
            inner = [a for a, s in self._mesh_shape_axes().items()
                     if a != "data" and (s > 1 or s <= 0)]
            if inner:
                raise ValueError(
                    f"--sim_workers cannot combine with inner mesh axes "
                    f"{inner} (--mesh_shape {self.mesh_shape!r}): "
                    "TP/PP/SP/EP/FSDP shard the parameter leaves over "
                    "REAL devices inside each worker — the simulator "
                    "stacks whole per-worker states on one chip "
                    "(hierarchy inside a simulated worker is the "
                    "ROADMAP follow-on)")
            if self.sequence_parallel != "none":
                raise ValueError(
                    "--sequence_parallel cannot combine with "
                    "--sim_workers: the ring/zigzag attention kernels "
                    "run over a real 'seq' mesh axis (see the inner-"
                    "mesh-axes rejection)")
        # --- semi-synchronous rounds (ISSUE 16): eager v1 limits ---------
        if self.sync_staleness < 0:
            raise ValueError(
                f"sync_staleness must be >= 0 (0 = fully synchronous), "
                f"got {self.sync_staleness}")
        if self.sim_staleness < 0:
            raise ValueError(
                f"sim_staleness must be >= 0 (0 = the synchronous lab), "
                f"got {self.sim_staleness}")
        if self.sim_staleness > 0:
            if self.sim_workers == 0:
                raise ValueError(
                    "--sim_staleness is a simulated-scenario knob; it "
                    "needs --sim_workers N (the real engine's knob is "
                    "--sync_staleness)")
            if self.aggregation_by != "weights":
                raise ValueError(
                    "--sim_staleness requires --aggregation_by weights: "
                    "in gradients mode every worker applies its own "
                    "optimizer to the aggregate inside the round — there "
                    "is no between-round consensus blend whose delivery "
                    "could be deferred")
        if self.sync_staleness > 0:
            if self.sim_workers > 0:
                raise ValueError(
                    "--sync_staleness cannot combine with --sim_workers: "
                    "the real engine's staleness overlaps a REAL "
                    "standalone sync program under the next round's "
                    "device compute — the lab's sync is stacked math "
                    "inside the one round program (use --sim_staleness "
                    "for the simulated delivery-delay twin)")
            if self.aggregation_by != "weights":
                raise ValueError(
                    "--sync_staleness requires --aggregation_by weights "
                    "(FedAvg): the deferred delivery folds a consensus "
                    "DELTA into later params, which needs a consensus "
                    "blend to exist — in gradients mode the aggregate "
                    "feeds each worker's optimizer step inside the round "
                    "and there is nothing to deliver late")
            if self.chaos:
                raise ValueError(
                    "--chaos cannot combine with --sync_staleness in v1: "
                    "crash rollback and elastic membership both rebuild "
                    "state at a round boundary assuming NO consensus is "
                    "in flight — a pending stale delta would be computed "
                    "against a pre-crash (or pre-reshard) worker axis and "
                    "silently corrupt the restored params (per-fault "
                    "drain is the ROADMAP follow-on)")
            if self.num_slices > 1:
                raise ValueError(
                    "--num_slices > 1 cannot combine with "
                    "--sync_staleness in v1: the hierarchical sync "
                    "threads a DCN outer-EF residual through consecutive "
                    "sync programs — under staleness sync R+1 dispatches "
                    "before sync R's residual exists, so the two-level "
                    "chain cannot pipeline without restructuring the "
                    "outer hop (the ROADMAP follow-on)")
            if self.param_residency == "resident":
                raise ValueError(
                    "--param_residency resident cannot combine with "
                    "--sync_staleness: resident keeps the sync's scatter "
                    "output as the between-round state, which makes round "
                    "R+1's entry gather DEPEND on sync R finishing — the "
                    "exact serialization staleness exists to remove "
                    "(auto resolves to replicated)")
            if self.shard_redundancy == "buddy":
                raise ValueError(
                    "--shard_redundancy buddy cannot combine with "
                    "--sync_staleness: the buddy hop rides the sync "
                    "program to snapshot shard-resident state, and "
                    "staleness resolves param residency to replicated — "
                    "nothing is uniquely held, so there is nothing to "
                    "back up (its consumer, crash recovery, is rejected "
                    "under staleness anyway)")
            if self.stream_chunk_steps > 0:
                raise ValueError(
                    "--stream_chunk_steps cannot combine with "
                    "--sync_staleness in v1: the streamed round already "
                    "overlaps its standalone sync under the next round's "
                    "first chunks via the producer thread — composing a "
                    "second staleness window over the chunked dispatch "
                    "is the ROADMAP follow-on")
            if self.checkpoint_dir or self.resume:
                raise ValueError(
                    "--checkpoint_dir/--resume cannot combine with "
                    "--sync_staleness in v1: a snapshot taken between "
                    "fences would capture params WITHOUT the K in-flight "
                    "consensus deltas, so the restored trajectory would "
                    "silently diverge from the run that wrote it "
                    "(drain-before-snapshot is the ROADMAP follow-on)")

    # Convenience ----------------------------------------------------------
    def replace(self, **kw: Any) -> "Config":
        return dataclasses.replace(self, **kw)

    def resolve_sync_mode(self, backend: str) -> str:
        """Resolve ``--sync_mode`` per topology into the engine actually
        run: ``dense`` | ``sharded`` | ``gossip`` | ``hier``.

        ``sharded`` names the bucketed fast engine, whatever the
        topology: the reduce-scatter/all-gather program for allreduce,
        the per-bucket ppermute gossip program for ring/double-ring
        (ISSUE 4 lifted the old sharded-is-allreduce-only rejection into
        this resolution).  ``auto`` picks the fast engine on TPU — where
        bucketed collectives ride the ICI ring — and whenever a
        compressed wire is requested (compression is a bucketed-engine
        feature); the XLA:CPU test backend keeps the dense twin, which
        is bit-identical in fp32 anyway.

        ``--num_slices > 1`` resolves to ``hier`` unconditionally
        (ISSUE 13): the hierarchical program IS the composition of the
        two fast engines — sharded allreduce on the inner (ICI) level,
        ppermute gossip on the outer (DCN) level — so there is no dense
        or per-level-auto variant to fall back to (the unsupported
        level pairs were rejected eagerly at construction; see
        ``resolve_sync_levels`` for the per-level breakdown)."""
        if self.num_slices > 1:
            return "hier"
        fast = "sharded" if self.topology == "allreduce" else "gossip"
        if self.sync_mode == "sharded":
            return fast
        if self.sync_mode == "dense":
            return "dense"
        if self.sync_dtype in ("bfloat16", "int8"):
            return fast
        if self.opt_placement == "sharded":
            # the shard-resident apply is a stage of the bucketed engine;
            # requesting it selects the fast path like a compressed wire
            # does (explicit --sync_mode dense was rejected up front)
            return fast
        if self.param_residency == "resident":
            # scatter-resident params ARE a bucketed-engine state layout
            # (the resident shard is the scatter output); requesting them
            # selects the fast path the same way (ISSUE 11)
            return fast
        return fast if backend == "tpu" else "dense"

    def resolve_sync_levels(self, backend: str) -> dict:
        """Per-LEVEL engine resolution (ISSUE 13): ``{"inner": ...,
        "outer": ...}``.

        Flat runs report their single resolved engine as the inner
        level with ``outer=None``.  Hierarchical runs are always
        ``inner="sharded"`` (the bucketed psum_scatter/all_gather
        engine over the ICI-shaped ``data`` axis) x ``outer="gossip"``
        (per-bucket ppermute hops over the DCN-shaped ``slice`` axis,
        ``--topology`` picking ring vs double_ring) — every other pair
        (gossip-outer x dense-inner, allreduce-outer, ...) was rejected
        eagerly at Config construction, so this resolution can never
        surprise at round time."""
        if self.num_slices == 1:
            return {"inner": self.resolve_sync_mode(backend),
                    "outer": None}
        return {"inner": "sharded", "outer": "gossip"}

    def resolve_sync_wire_dtypes(self) -> tuple[str, str]:
        """``(inner, outer)`` wire dtype names: ``--sync_dtype`` for the
        inner (ICI) collectives, ``--sync_dtype_outer`` for the outer
        (DCN) gossip hops, inheriting the inner choice when unset."""
        return (self.sync_dtype, self.sync_dtype_outer or self.sync_dtype)

    def resolve_opt_placement(self, backend: str) -> str:
        """Resolve ``--opt_placement`` into the placement actually run:
        ``replicated`` | ``sharded`` | ``local``.

        Gossip topologies (ring / double_ring) resolve to ``local``
        regardless of the flag: every gossip blend output is
        worker-specific by construction (each worker mixes its OWN value
        with its predecessors' — there is no global reduce whose result
        could be computed once and shared), so the blend arithmetic and
        the EF residual are already worker-resident and nothing
        cross-replica-redundant exists to shard (docs/ARCHITECTURE.md
        documents what stays replicated and why).  For allreduce,
        ``auto`` picks ``sharded`` exactly when the bucketed sharded
        sync engine is active (compressed wire always is), mirroring the
        sync-mode resolution; the dense per-leaf path has no
        scatter/gather phases to place an apply between and reports
        ``replicated`` (which its arithmetic literally is)."""
        mode = self.resolve_sync_mode(backend)
        if mode == "hier":
            # the hierarchical apply (inner mean scale, outer gossip
            # blend, wire encode) necessarily runs on the 1/W scatter
            # shard — the outer hop rides it; explicit replicated was
            # rejected eagerly at construction
            return "sharded"
        if mode == "gossip" or self.topology != "allreduce":
            return "local"
        if self.opt_placement in ("replicated", "sharded"):
            return self.opt_placement
        return "sharded" if mode == "sharded" else "replicated"

    def resolve_param_residency(self, backend: str) -> str:
        """Resolve ``--param_residency`` into the layout actually run:
        ``replicated`` | ``resident`` (ISSUE 11).

        ``resident`` — each worker's between-round parameter state is its
        1/N bucket shard of the consensus tree (the sync's psum_scatter
        output, post-apply), gathered just-in-time at round entry —
        requires three things at once:

        1. the bucketed SHARDED sync engine (the scatter whose output
           stays resident; gossip topologies and the dense per-leaf path
           have none — explicit resident there is rejected eagerly,
           ``auto`` resolves to replicated);
        2. weights (FedAvg) aggregation — in gradients mode the
           aggregate is discarded and every worker's params evolve
           independently from round 1 on: worker-local state, nothing
           cross-replica-redundant to shard (the exact argument that
           resolves ``--opt_placement`` to "local" on gossip);
        3. the ``equal`` blend — the weighted blend's output is
           ``w*own + (1-w)*(total-own)/(n-1)``, a different function of
           each worker's own full value: the own-term is irreducibly
           per-worker (the PR 9 ARCHITECTURE.md section documents why),
           so the whole post-blend tree IS per-worker state and resolves
           to replicated.

        ``auto`` picks resident exactly when all three hold; an explicit
        ``resident`` under weighted/gradients resolves to replicated with
        an engine log line, mirroring ``--opt_placement sharded`` on a
        gossip topology.

        Hierarchical runs (ISSUE 13) qualify like the flat sharded
        engine: the between-round state is each SLICE's consensus —
        worker-invariant within the slice under weights x equal — and
        the sync still ends at the inner scatter, so each worker keeps
        its 1/W bucket shard of its own slice's consensus (exactly
        1/N_inner between rounds, the ISSUE 13 composition contract)."""
        if self.sync_staleness > 0:
            # resident would make round R+1's entry gather depend on
            # sync R finishing — the serialization staleness removes
            # (explicit resident x staleness is rejected eagerly)
            return "replicated"
        if self.resolve_sync_mode(backend) not in ("sharded", "hier"):
            return "replicated"
        if self.resolve_opt_placement(backend) != "sharded":
            # the resident state IS the shard-side apply output; an
            # explicitly replicated (post-gather) apply leaves none
            # (explicit resident x replicated is rejected eagerly)
            return "replicated"
        if self.aggregation_by != "weights":
            return "replicated"
        if self.aggregation_type != "equal":
            return "replicated"
        if self.param_residency == "replicated":
            return "replicated"
        return "resident"

    def parse_remat_policy(self) -> tuple[str, tuple[str, ...]]:
        """``--remat_policy`` as ``(kind, names)`` — eagerly validated
        (ISSUE 15): the base spellings pass through; the named tiers
        (``save_names:<a,b>`` / ``offload_names:<a,b>``) additionally
        check every name against the model FAMILY's emitted
        ``checkpoint_name`` vocabulary (``models.remat_name_vocab``), so
        a typo'd activation name fails at argparse time with the real
        vocabulary in the message instead of silently degrading the
        policy to save-nothing.  The "named policy without a scanned
        stack" case keeps the existing driver rejection (the resolution
        needs the mesh's pipe axis, which config cannot see)."""
        from .models import remat_name_vocab, split_remat_policy
        kind, names = split_remat_policy(self.remat_policy)
        if not names:
            return kind, names
        vocab = remat_name_vocab(self.model, self.num_experts)
        if not vocab:
            raise ValueError(
                f"--remat_policy {kind}:... selects checkpoint_name-"
                f"annotated activations of the scanned transformer "
                f"block; --model {self.model} has no scanned block path "
                "(bert_*/gpt_*/llama_*/vit_* do)")
        unknown = [n for n in names if n not in vocab]
        if unknown:
            moe = (f" (num_experts={self.num_experts})"
                   if self.num_experts else "")
            raise ValueError(
                f"--remat_policy {kind}: unknown activation name(s) "
                f"{unknown} — the {self.model} family{moe} emits exactly "
                f"{sorted(vocab)} (a name outside the vocabulary would "
                "silently degrade the policy to save-nothing)")
        return kind, names

    def parse_chaos_kinds(self) -> tuple[str, ...]:
        """``--chaos_kinds`` as a validated kind tuple (ISSUE 12
        satellite): the kinds a ``--chaos random`` schedule may draw.
        Order-preserving, duplicates collapsed; every entry must be a
        known ``chaos.KINDS`` member so a typo'd selection fails at
        argparse time, not mid-run."""
        from .chaos import KINDS
        out: list[str] = []
        for part in self.chaos_kinds.split(","):
            part = part.strip()
            if not part:
                continue
            if part not in KINDS:
                raise ValueError(
                    f"unknown chaos kind {part!r} in --chaos_kinds "
                    f"{self.chaos_kinds!r}: expected a subset of {KINDS}")
            if part not in out:
                out.append(part)
        if not out:
            raise ValueError(
                f"--chaos_kinds {self.chaos_kinds!r} selects no event "
                "kinds — a random schedule needs at least one")
        return tuple(out)

    SIM_BYZANTINE_KINDS = ("signflip", "noise")

    def parse_sim_byzantine(self) -> tuple[str, int, float] | None:
        """``--sim_byzantine`` as ``(kind, count, scale)`` or None.

        Spec: ``kind:count[:scale]`` with kind in
        ``SIM_BYZANTINE_KINDS``, count >= 1 adversarial workers (the
        LAST count worker ids), scale the noise stddev (noise kind only;
        default 1.0).  Validated eagerly like parse_chaos_kinds — a
        typo'd adversary spec fails at argparse time, not mid-sweep."""
        spec = self.sim_byzantine.strip()
        if not spec:
            return None
        parts = spec.split(":")
        if len(parts) not in (2, 3):
            raise ValueError(
                f"--sim_byzantine must be 'kind:count[:scale]', got "
                f"{self.sim_byzantine!r}")
        kind = parts[0].strip()
        if kind not in self.SIM_BYZANTINE_KINDS:
            raise ValueError(
                f"unknown --sim_byzantine kind {kind!r}: expected one of "
                f"{self.SIM_BYZANTINE_KINDS}")
        try:
            count = int(parts[1])
        except ValueError:
            raise ValueError(
                f"--sim_byzantine count must be an integer, got "
                f"{parts[1]!r} in {self.sim_byzantine!r}") from None
        if count < 1:
            raise ValueError(
                f"--sim_byzantine count must be >= 1, got {count}")
        if self.sim_workers and count >= self.sim_workers:
            raise ValueError(
                f"--sim_byzantine count {count} must leave at least one "
                f"honest worker (--sim_workers {self.sim_workers})")
        scale = 1.0
        if len(parts) == 3:
            if kind != "noise":
                raise ValueError(
                    f"--sim_byzantine scale applies to the 'noise' kind "
                    f"(the injected stddev); {kind!r} takes none — got "
                    f"{self.sim_byzantine!r}")
            try:
                scale = float(parts[2])
            except ValueError:
                raise ValueError(
                    f"--sim_byzantine scale must be a float, got "
                    f"{parts[2]!r} in {self.sim_byzantine!r}") from None
            if scale <= 0:
                raise ValueError(
                    f"--sim_byzantine noise scale must be > 0, got "
                    f"{scale}")
        return (kind, count, scale)

    def _mesh_shape_axes(self) -> dict[str, int]:
        """Raw ``--mesh_shape`` parse (no slice-axis logic) — the sim
        validation reads it before ``mesh_axes``'s hierarchical checks."""
        axes: dict[str, int] = {}
        for part in self.mesh_shape.split(","):
            part = part.strip()
            if not part:
                continue
            name, _, size = part.partition("=")
            axes[name.strip()] = int(size) if size else -1
        return axes

    def parse_prompt_buckets(self) -> tuple[int, ...]:
        """``--serve_prompt_buckets`` as ascending unique lengths."""
        out = []
        for part in self.serve_prompt_buckets.split(","):
            part = part.strip()
            if not part:
                continue
            try:
                out.append(int(part))
            except ValueError:
                raise ValueError(
                    f"serve_prompt_buckets must be comma-separated "
                    f"integers, got {self.serve_prompt_buckets!r}") from None
        if not out or min(out) < 1:
            raise ValueError(
                f"serve_prompt_buckets needs at least one positive "
                f"length, got {self.serve_prompt_buckets!r}")
        return tuple(sorted(set(out)))

    def mesh_axes(self) -> dict[str, int]:
        """Parse ``mesh_shape`` into an ordered {axis: size} dict.

        A size of -1 means "all remaining devices" (resolved in mesh.py).
        ``--num_slices > 1`` (ISSUE 13) prepends the ``slice`` outer
        axis — it LEADS the mesh so multi-host layouts map whole slices
        to whole host groups (only the outer gossip hop crosses DCN).
        The slice axis comes from ``--num_slices`` only; naming it in
        ``--mesh_shape`` is rejected, as are inner model axes under
        slices (the v1 composition limit: hierarchical sync x
        TP/PP/SP/EP/FSDP needs per-device bucket plans — follow-on).
        """
        axes = self._mesh_shape_axes()
        if "slice" in axes:
            raise ValueError(
                "the 'slice' mesh axis is driven by --num_slices, not "
                f"--mesh_shape (got --mesh_shape {self.mesh_shape!r})")
        if "data" not in axes:
            axes = {"data": -1, **axes}
        if self.num_slices > 1:
            inner = [a for a, s in axes.items()
                     if a != "data" and (s > 1 or s <= 0)]
            if inner:
                raise ValueError(
                    f"--num_slices {self.num_slices} cannot combine with "
                    f"inner mesh axes {inner} in v1: the hierarchical "
                    "sync's bucket plan is per-worker, and TP/PP/SP/EP/"
                    "FSDP shard the parameter leaves themselves "
                    "(docs/ARCHITECTURE.md documents the demotion)")
            axes = {"slice": self.num_slices, **axes}
        return axes


def _choices(name: str, value: str, allowed: tuple[str, ...]) -> None:
    if value not in allowed:
        raise ValueError(f"{name} must be one of {allowed}, got {value!r}")


def build_argparser() -> argparse.ArgumentParser:
    """CLI with every reference flag (same names, same defaults) plus the
    framework selectors.  Reference flags: Balanced All-Reduce/main.py:83-96,
    Disbalanced All-Reduce/main.py:94,101."""
    d = Config()
    p = argparse.ArgumentParser(
        description="TPU-native local-SGD distributed training framework")
    # Reference-parity flags (incl. the reference's dead flags, accepted as
    # documented no-ops so existing launch scripts keep working).
    p.add_argument("--local-rank", type=int, dest="local_rank", default=None,
                   help="[compat no-op] torch.distributed.launch artifact")
    p.add_argument("--backend", type=str, default=d.backend,
                   choices=["jax", "gloo", "nccl", "mpi"],
                   help="[compat] backend is always XLA; gloo/nccl/mpi accepted as no-ops")
    p.add_argument("--epochs_local", type=int, default=d.epochs_local)
    p.add_argument("--epochs_global", type=int, default=d.epochs_global)
    p.add_argument("--batch_size", type=int, default=d.batch_size)
    p.add_argument("--lr", type=float, default=d.lr)
    p.add_argument("--time_limit", type=float, default=d.time_limit,
                   help="straggler grace budget in seconds")
    p.add_argument("--prev_fraction", type=float, default=d.prev_fraction)
    p.add_argument("--next_fraction", type=float, default=d.next_fraction)
    p.add_argument("--aggregation_type", type=str, default=d.aggregation_type,
                   choices=["equal", "weighted"])
    p.add_argument("--aggregation_by", type=str, default=d.aggregation_by,
                   choices=["gradients", "weights"])
    p.add_argument("--local_weight", type=float, default=d.local_weight)
    p.add_argument("--fixed_ratio", type=float, default=d.fixed_ratio)
    p.add_argument("--gpu_weight", type=float, default=None,
                   help="[compat no-op] dead reference flag "
                        "(Disbalanced All-Reduce/main.py:94)")
    p.add_argument("--dist-url", type=str, dest="dist_url", default=None,
                   help="[compat no-op] dead reference flag "
                        "(Balanced Double-Ring/main.py:80)")
    # Variant selectors
    p.add_argument("--topology", type=str, default=d.topology,
                   choices=["allreduce", "ring", "double_ring"])
    p.add_argument("--data_mode", type=str, default=d.data_mode,
                   choices=["balanced", "disbalanced"])
    # Framework knobs
    p.add_argument("--model", type=str, default=d.model)
    p.add_argument("--dataset", type=str, default=d.dataset)
    p.add_argument("--num_workers", type=int, default=d.num_workers,
                   help="data-axis worker count (0 = all devices); under "
                        "--num_slices > 1 this is workers PER SLICE (the "
                        "inner ICI level) — the total is slices x this")
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--device", type=str, default=None,
                   help="tpu|cpu — force a JAX platform (default: auto)")
    p.add_argument("--dtype", type=str, default=d.dtype)
    p.add_argument("--compute_dtype", type=str, default=d.compute_dtype)
    p.add_argument("--proportionality", type=str, default=d.proportionality,
                   choices=["inverse", "direct", "uniform"])
    p.add_argument("--probe_batches", type=int, default=d.probe_batches)
    p.add_argument("--data_dir", type=str, default=d.data_dir)
    p.add_argument("--out_dir", type=str, default=d.out_dir)
    p.add_argument("--checkpoint_dir", type=str, default=d.checkpoint_dir)
    p.add_argument("--checkpoint_every", type=int, default=d.checkpoint_every)
    p.add_argument("--ckpt_async", choices=["on", "off"],
                   default="on" if d.ckpt_async else "off",
                   help="off-critical-path checkpointing: the round loop "
                        "pays only the device->host snapshot; a background "
                        "thread writes + manifest-commits the per-process "
                        "shards (off = identical write path, inline)")
    p.add_argument("--ckpt_keep", type=int, default=d.ckpt_keep,
                   help="committed checkpoints retained by the "
                        "every-process prune")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--profile_dir", type=str, default=d.profile_dir)
    p.add_argument("--limit_train_samples", type=int, default=d.limit_train_samples)
    p.add_argument("--limit_eval_samples", type=int, default=d.limit_eval_samples)
    p.add_argument("--no_augment", action="store_true")
    p.add_argument("--mesh_shape", type=str, default=d.mesh_shape)
    p.add_argument("--sequence_parallel", type=str, default=d.sequence_parallel,
                   choices=["none", "ring", "ring_zigzag", "all_to_all"])
    p.add_argument("--attention_impl", type=str, default=d.attention_impl,
                   choices=["dense", "flash"],
                   help="attention kernel for bert models (flash = Pallas)")
    p.add_argument("--model_width", type=int, default=d.model_width,
                   help="EnhancedCNN channel base override (0 = the "
                        "reference's 64)")
    p.add_argument("--pp_microbatches", type=int, default=d.pp_microbatches,
                   help="GPipe microbatches when the mesh has a pipe axis "
                        "(0 = pipe size)")
    p.add_argument("--pp_schedule", default=d.pp_schedule,
                   choices=["gpipe", "1f1b"],
                   help="pipeline schedule: gpipe (autodiff through the "
                        "schedule) or 1f1b (interleaved backward, "
                        "O(stages) residual memory)")
    p.add_argument("--pp_remat", action="store_true",
                   default=d.pp_remat,
                   help="[compat alias] rematerialize each layer under "
                        "pipeline parallelism — same as --remat_policy "
                        "everything")
    p.add_argument("--layer_scan", type=str, default=d.layer_scan,
                   choices=["auto", "on", "off"],
                   help="run homogeneous transformer blocks as a stacked "
                        "lax.scan (compile once per block, not per layer); "
                        "auto = on for bert_*/gpt_*/llama_*/vit_*")
    p.add_argument("--remat_policy", type=str, default=d.remat_policy,
                   help="jax.checkpoint policy for the scanned layer "
                        "stack: none | dots_saveable (save matmul "
                        "outputs) | everything (rematerialize whole "
                        "blocks, the GPipe-paper memory recipe) | "
                        "save_names:<a,b> (keep exactly the named "
                        "activations on device; vocabulary attn_out/"
                        "mlp_out/block_out/moe_dispatch) | "
                        "offload_names:<a,b> (additionally offload the "
                        "set to pinned host memory; demoted to the "
                        "same-set save_names on backends without a "
                        "host memory space).  A policy chooses among "
                        "XLA's activations; a kernel call whose outputs "
                        "are O(L d) and whose cost is O(L^2 d) is never "
                        "rematerialised: where an attention call "
                        "reaches the flash kernel, every policy also "
                        "keeps its output and log-sum-exp (2 B x heads "
                        "x dv + 512 B a position and layer)")
    p.add_argument("--grad_accum", type=int, default=d.grad_accum,
                   help="microbatch gradient accumulation factor: scan K "
                        "microbatches per step with a donated fp32 grad "
                        "carry (bounded activation memory, unchanged "
                        "effective batch and sync cadence)")
    p.add_argument("--num_kv_heads", type=int, default=d.num_kv_heads,
                   help="grouped-query attention kv-head count "
                        "(llama_* models; 0 = multi-head)")
    p.add_argument("--num_experts", type=int, default=d.num_experts,
                   help="MoE experts per bert/gpt layer (0 = dense FFN); "
                        "shard with an 'expert' mesh axis")
    p.add_argument("--expert_capacity_factor", type=float,
                   default=d.expert_capacity_factor)
    p.add_argument("--moe_aux_weight", type=float, default=d.moe_aux_weight)
    p.add_argument("--stream_chunk_steps", type=int, default=d.stream_chunk_steps,
                   help="stream the round in chunks of this many steps "
                        "(0 = materialize the whole epoch)")
    p.add_argument("--stream_prefetch", type=int, default=d.stream_prefetch,
                   help="streamed-path producer depth: windows staged on "
                        "device ahead of compute (2 = double buffering, "
                        "0 = synchronous)")
    p.add_argument("--no_overlap_rounds", action="store_true",
                   help="disable the overlapped round pipeline (serial "
                        "fetch/assemble/re-partition between rounds; same "
                        "results, larger device gap)")
    p.add_argument("--sync_staleness", type=int, default=d.sync_staleness,
                   help="semi-synchronous rounds: dispatch the next "
                        "round's local phase off the pre-sync params "
                        "while the standalone sync runs concurrently, "
                        "folding each consensus delta in K rounds late "
                        "(at most K syncs in flight; 0 = fully "
                        "synchronous, bitwise today's engine; weights "
                        "aggregation only)")
    p.add_argument("--compile_cache_dir", type=str,
                   default=compile_cache_dir(),
                   help="'' turns the persistent XLA compilation cache "
                        "off.  The directory is not chosen here (a cache "
                        "that moves never hits): it is "
                        "$JAX_COMPILATION_CACHE_DIR when set, else "
                        "<checkout>/.jax_cache; any other value is "
                        "rejected")
    p.add_argument("--sync_mode", type=str, default=d.sync_mode,
                   choices=["auto", "dense", "sharded"],
                   help="round-sync engine, resolved per topology: "
                        "sharded = the bucketed fast path (reduce-"
                        "scatter/all-gather for allreduce, per-bucket "
                        "ppermute gossip for ring/double_ring; both "
                        "bit-identical to dense in fp32), auto = the "
                        "fast path on TPU, dense otherwise")
    p.add_argument("--sync_dtype", type=str, default=d.sync_dtype,
                   choices=["float32", "bfloat16", "int8"],
                   help="wire dtype of the bucketed sync collectives, "
                        "allreduce and gossip alike (bfloat16 halves "
                        "bytes on the wire; int8 + per-bucket scale "
                        "quarters them)")
    p.add_argument("--sync_compression", type=str,
                   default=d.sync_compression, choices=["none", "ef"],
                   help="ef = carry fp32 error-feedback residuals in train "
                        "state so compressed wire rounding does not "
                        "accumulate into the parameters (weights "
                        "aggregation)")
    p.add_argument("--sync_bucket_mb", type=float, default=d.sync_bucket_mb,
                   help="sharded-sync bucket size in MiB per collective")
    p.add_argument("--num_slices", type=int, default=d.num_slices,
                   help="hierarchical two-level sync: outer slice count "
                        "of the (slice, worker) grid — each slice's "
                        "workers all-reduce over ICI (bucketed "
                        "psum_scatter/all_gather) and the slice "
                        "consensuses gossip over DCN (--topology ring | "
                        "double_ring, per-bucket ppermute on the 1/W "
                        "scatter shard); 1 = the flat engine")
    p.add_argument("--sync_dtype_outer", type=str,
                   default=d.sync_dtype_outer,
                   choices=["", "float32", "bfloat16", "int8"],
                   help="wire dtype of the OUTER (DCN) gossip hops "
                        "(hierarchical runs; '' inherits --sync_dtype — "
                        "the production shape compresses the slow "
                        "inter-slice wire while ICI stays fp32)")
    p.add_argument("--opt_placement", type=str, default=d.opt_placement,
                   choices=["auto", "replicated", "sharded"],
                   help="round-boundary optimizer placement (ZeRO-1 "
                        "cross-replica weight update): sharded runs the "
                        "apply between psum_scatter and all_gather on the "
                        "1/N shard and stores round-optimizer moments "
                        "sharded over the worker axis; replicated is the "
                        "post-gather full-size twin; auto = sharded when "
                        "the bucketed sync engine is active (gossip "
                        "topologies are worker-local either way)")
    p.add_argument("--param_residency", type=str, default=d.param_residency,
                   choices=["auto", "replicated", "resident"],
                   help="between-round consensus-params layout "
                        "(round-loop FSDP): resident keeps each worker's "
                        "1/N bucket shard of the consensus (the sync's "
                        "scatter output) and all_gathers just-in-time at "
                        "round entry — per-worker param residency and "
                        "checkpoint payload drop N-fold; replicated is "
                        "the full-tree twin; auto = resident whenever the "
                        "bucketed sharded engine syncs weights with the "
                        "equal blend (gossip/weighted/gradients states "
                        "are worker-local and stay replicated)")
    p.add_argument("--shard_redundancy", type=str,
                   default=d.shard_redundancy,
                   choices=["auto", "buddy", "off"],
                   help="buddy-redundant resident shards (unplanned-"
                        "failure domain): buddy fuses one extra "
                        "per-bucket ppermute onto the sync program at "
                        "scatter exit so every 1/N resident span also "
                        "lives on its ring successor — a mid-round "
                        "worker crash recovers in memory from the buddy "
                        "copy instead of a checkpoint restore; auto = "
                        "buddy whenever any state resolves "
                        "shard-resident; off = crash recovery degrades "
                        "to the newest committed checkpoint")
    p.add_argument("--serve_max_batch", type=int, default=d.serve_max_batch,
                   help="serve: concurrent decode slots (the one fixed "
                        "shape the decode-step program compiles at)")
    p.add_argument("--serve_page_size", type=int, default=d.serve_page_size,
                   help="serve: tokens per KV-cache page")
    p.add_argument("--serve_max_pages", type=int, default=d.serve_max_pages,
                   help="serve: KV-cache page-pool size (page 0 is the "
                        "reserved trash page)")
    p.add_argument("--serve_prompt_buckets", type=str,
                   default=d.serve_prompt_buckets,
                   help="serve: comma-separated prefill prompt-length "
                        "buckets; one prefill program compiles per bucket")
    p.add_argument("--serve_eos_id", type=int, default=d.serve_eos_id,
                   help="serve: sampling this token id finishes a "
                        "request (-1 = generate to max_new_tokens)")
    p.add_argument("--serve_max_new_tokens", type=int,
                   default=d.serve_max_new_tokens,
                   help="serve: per-request generation budget")
    p.add_argument("--serve_temperature", type=float,
                   default=d.serve_temperature,
                   help="serve: sampling temperature (0 = greedy)")
    p.add_argument("--serve_requests", type=int, default=d.serve_requests,
                   help="serve: synthetic request count when no "
                        "--serve_prompt is given")
    p.add_argument("--serve_prompt", type=str, default=d.serve_prompt,
                   help="serve: fixed prompt as comma-separated token ids "
                        "(every request decodes it; '' = synthetic "
                        "per-request prompts)")
    p.add_argument("--serve_request_timeout", type=float,
                   default=d.serve_request_timeout,
                   help="serve: per-request wall-clock budget in seconds "
                        "— a sequence still decoding past it is evicted "
                        "(reason 'timeout') instead of pinning its slot "
                        "and pages forever (0 = off)")
    p.add_argument("--serve_prefix_cache", action="store_true",
                   default=d.serve_prefix_cache,
                   help="serve: content-address the KV pages (rolling "
                        "hash of the prefix each page closes) and map "
                        "cached pages into new sequences by reference — "
                        "shared prompt prefixes prefill once; eviction "
                        "becomes refcount-0 LRU")
    p.add_argument("--serve_prefill_chunk", type=int,
                   default=d.serve_prefill_chunk,
                   help="serve: prefill in fixed [1, C] chunks "
                        "interleaved with decode steps instead of one "
                        "monolithic per-bucket program (positive "
                        "multiple of --serve_page_size; 0 = monolithic)")
    p.add_argument("--serve_draft_ckpt", type=str,
                   default=d.serve_draft_ckpt,
                   help="serve: sharded checkpoint dir of a small DRAFT "
                        "model for speculative decoding — k greedy "
                        "draft steps per tick through a second paged KV "
                        "pool, one fused [B, k+1] target verify; greedy "
                        "output stays bitwise the non-speculative "
                        "twin's (needs --serve_spec_tokens)")
    p.add_argument("--serve_spec_tokens", type=int,
                   default=d.serve_spec_tokens,
                   help="serve: draft tokens per verify step (k >= 1; "
                        "0 = no speculation; needs --serve_draft_ckpt)")
    # --- chaos / elastic membership group (ISSUE 8) ------------------------
    p.add_argument("--chaos", type=str, default=d.chaos,
                   help="fault-injection plan: comma-separated "
                        "kind@round[:wID][xF][+S][*K] events (kill/join/"
                        "slow/stall/crash/nan) or 'random' (seeded "
                        "schedule); membership changes apply at round "
                        "boundaries via the elastic reshard, crashes "
                        "mid-round via the rollback recovery — no "
                        "process restart")
    p.add_argument("--chaos_seed", type=int, default=d.chaos_seed,
                   help="seed for --chaos random's up-front event draw")
    p.add_argument("--chaos_events", type=int, default=d.chaos_events,
                   help="event count for --chaos random")
    p.add_argument("--chaos_kinds", type=str, default=d.chaos_kinds,
                   help="event kinds --chaos random may draw (csv; "
                        "crash/nan are opt-in — the default keeps the "
                        "cooperative kill/join/slow/stall faults)")
    p.add_argument("--chaos_grace", type=float, default=d.chaos_grace,
                   help="seconds past --time_limit before a round wall "
                        "counts as a straggler overrun")
    p.add_argument("--chaos_retries", type=int, default=d.chaos_retries,
                   help="consecutive straggler overruns tolerated (each "
                        "a logged retry with a backoff-extended "
                        "deadline) before the worker is treated as "
                        "departed and its shard redistributed")
    p.add_argument("--chaos_backoff", type=float, default=d.chaos_backoff,
                   help="per-retry grace extension factor: attempt k's "
                        "deadline is time_limit + grace*(1 + backoff*k)")
    p.add_argument("--elastic_min_workers", type=int,
                   default=d.elastic_min_workers,
                   help="quorum floor: membership events that would drop "
                        "below this many live workers are rejected")
    p.add_argument("--sanitize", action="store_true", default=d.sanitize,
                   help="arm the round-loop sanitizer: transfer guard "
                        "around dispatch/wait (implicit transfers raise), "
                        "zero-retrace budget after the warmup round, and "
                        "donated-buffer deletion asserts (also via "
                        "JAX_GRAFT_SANITIZE=1)")
    # --- scenario lab group (ISSUE 14) -------------------------------------
    p.add_argument("--sim_workers", type=int, default=d.sim_workers,
                   help="simulate this many local-SGD workers as one "
                        "vmap'd jit on a SINGLE chip (per-worker state/"
                        "data/RNG stacked on a leading axis; sync = "
                        "stacked math, fp32 bitwise vs the real mesh at "
                        "equal N); 0 = the real-mesh driver")
    p.add_argument("--sim_sample_frac", type=float,
                   default=d.sim_sample_frac,
                   help="scenario: per-round client sampling — each "
                        "round ceil(frac*N) seeded-drawn workers train "
                        "and contribute; the rest skip the round but "
                        "adopt the consensus (FedAvg sampling)")
    p.add_argument("--sim_dropout", type=float, default=d.sim_dropout,
                   help="scenario: per-round worker dropout probability "
                        "— a dropped worker neither trains, contributes, "
                        "nor adopts (the whole round is a no-op for it)")
    p.add_argument("--sim_byzantine", type=str, default=d.sim_byzantine,
                   help="scenario: adversarial workers, "
                        "'kind:count[:scale]' — the last count ids "
                        "corrupt their sync contribution every round "
                        "(signflip = the round's update sign-flipped; "
                        "noise = payload + scale*N(0,1), seeded)")
    p.add_argument("--sim_lr_jitter", type=float, default=d.sim_lr_jitter,
                   help="scenario: per-worker LR spread — worker i "
                        "trains at lr*(1 + jitter*u_i), u_i a seeded "
                        "uniform[-1,1) draw fixed for the run")
    p.add_argument("--sim_staleness", type=int, default=d.sim_staleness,
                   help="scenario: deliver each round's consensus delta "
                        "K rounds late — the lab twin of "
                        "--sync_staleness for staleness-vs-convergence "
                        "curves (0 = synchronous)")
    return p


def config_from_args(argv: list[str] | None = None) -> Config:
    args = build_argparser().parse_args(argv)
    import os
    if args.device:
        # explicit CLI choice overrides any inherited JAX_PLATFORMS (the
        # env var for a jax not yet imported, jax.config for one that is)
        os.environ["JAX_PLATFORMS"] = args.device
        if args.device == "cpu":
            # CPU thunk executor collective-deadlock workaround (see
            # xla_flags.py); only effective before backend init
            from .xla_flags import ensure_sequential_cpu_collectives
            ensure_sequential_cpu_collectives()
        import jax
        jax.config.update("jax_platforms", args.device)
    field_names = {f.name for f in dataclasses.fields(Config)}
    kw = {k: v for k, v in vars(args).items() if k in field_names}
    kw["augment"] = not args.no_augment
    kw["overlap_rounds"] = not args.no_overlap_rounds
    kw["ckpt_async"] = args.ckpt_async == "on"
    if args.compile_cache_dir not in ("", compile_cache_dir()):
        raise ValueError(
            f"--compile_cache_dir {args.compile_cache_dir!r}: the flag "
            "only turns the cache off (''); its directory is "
            f"{compile_cache_dir()!r} ($JAX_COMPILATION_CACHE_DIR when "
            "set, else <checkout>/.jax_cache)")
    return Config(**kw)
