"""Driver surface for the serving engine: ``main.py serve`` / ``run_serve``.

Self-configures the model from the checkpoint manifest metadata (the
ISSUE 7 checkpoint satellite): the user points at ``--checkpoint_dir``
and the ``--serve_*`` group; restating ``--model`` is optional and
cross-checked (mismatch is a hard error, not a silent override).

``--sanitize`` arms the serving twin of the round-loop retrace budget:
after a warmup has compiled the workload's programs (every prefill
bucket — all configured buckets under ``--serve_prefix_cache``, since a
partial hit prefills its tail at a smaller bucket — or the single
``[1, C]`` chunk program under ``--serve_prefill_chunk``, plus the
decode step), the measured run must add ZERO jaxpr traces / backend
compiles — the continuous-batching loop re-dispatches fixed programs,
nothing else.
"""

from __future__ import annotations

import json
import logging
import os
import sys
from typing import Any, Optional

import numpy as np

log = logging.getLogger(__name__)


def build_requests(cfg, vocab: int) -> list:
    """Requests from the CLI surface: ``--serve_prompt`` (comma-separated
    token ids, replicated ``--serve_requests`` times) or per-request
    synthetic prompts drawn from the served vocabulary."""
    from .scheduler import Request
    n = max(1, int(cfg.serve_requests))
    rng = np.random.default_rng(cfg.seed)
    out = []
    for i in range(n):
        if cfg.serve_prompt:
            ids = [int(t) for t in cfg.serve_prompt.split(",") if t.strip()]
        else:
            lo = min(4, cfg.parse_prompt_buckets()[0])
            plen = int(rng.integers(lo, cfg.parse_prompt_buckets()[0] + 1))
            ids = rng.integers(0, vocab, plen).tolist()
        out.append(Request(rid=i, prompt=ids,
                           max_new_tokens=cfg.serve_max_new_tokens,
                           temperature=cfg.serve_temperature))
    return out


def run_serve(cfg, requests: Optional[list] = None, *,
              model_flag_given: Optional[bool] = None) -> dict[str, Any]:
    """Load the checkpoint onto the serving mesh and serve ``requests``
    (built from the config when None).  Returns ``{"serve": telemetry,
    "completions": [...], "engine": ServeEngine}``.

    ``model_flag_given`` — whether the user EXPLICITLY passed ``--model``
    (``serve_main`` inspects argv; library callers default to "given iff
    not the dataclass default").  Explicit + metadata mismatch is a hard
    error; explicit + a metadata-less (pre-metadata) checkpoint is the
    supported fallback — the arch rebuilds from the registry name with
    num_classes recovered from the manifest leaf shapes."""
    import jax

    from .. import checkpoint as ckpt_lib
    from .engine import ServeEngine, manifest_num_classes
    from .scheduler import ContinuousBatchingScheduler

    if not cfg.checkpoint_dir:
        raise ValueError("serve needs --checkpoint_dir (the sharded "
                         "checkpoint to load)")
    if cfg.compile_cache_dir:
        from ..xla_flags import setup_compile_cache
        setup_compile_cache()
    path = cfg.checkpoint_dir
    if not os.path.isfile(os.path.join(path, ckpt_lib.MANIFEST)):
        resolved = ckpt_lib.latest_checkpoint(path)
        if resolved is None:
            raise FileNotFoundError(
                f"no committed checkpoint under {cfg.checkpoint_dir}")
        path = resolved
    meta = ckpt_lib.manifest_metadata(path) or {}
    if model_flag_given is None:
        # compare against the dataclass default, not a hardcoded name —
        # one source of truth if the Config default ever changes
        import dataclasses
        default_model = next(f.default
                             for f in dataclasses.fields(type(cfg))
                             if f.name == "model")
        model_flag_given = cfg.model != default_model
    if model_flag_given and meta.get("model") and cfg.model != meta["model"]:
        raise ValueError(
            f"--model {cfg.model} does not match the checkpoint's "
            f"recorded model {meta['model']!r} ({path}); drop --model — "
            "serve self-configures from the manifest metadata")
    model = None
    if not meta:
        if not model_flag_given:
            raise ValueError(
                f"checkpoint {path} carries no serve metadata (saved by "
                "a pre-metadata engine?) — restate --model gpt_*/llama_* "
                "to serve it")
        ncls = manifest_num_classes(path)
        if ncls is None:
            raise ValueError(
                f"checkpoint {path} has no tok_emb params leaf — not an "
                "autoregressive-family checkpoint, nothing to serve")
        from ..models import get_model
        kw: dict[str, Any] = dict(num_classes=ncls, scan_layers=True)
        if cfg.num_kv_heads:
            kw["num_kv_heads"] = cfg.num_kv_heads
        if cfg.num_experts:
            kw["num_experts"] = cfg.num_experts
            kw["capacity_factor"] = cfg.expert_capacity_factor
        model = get_model(cfg.model, **kw)
        log.info("serve: no manifest metadata; rebuilt %s (vocab %d from "
                 "manifest leaf shapes)", cfg.model, ncls)
    buckets = cfg.parse_prompt_buckets()
    # identical geometry for both engines of a speculative pair (the
    # pairing check enforces it): one page-table schedule, one filled
    # offset, joint admission.  max_seq grows by k — the verify program
    # writes up to position C + k
    engine_kw = dict(
        max_batch=cfg.serve_max_batch, page_size=cfg.serve_page_size,
        max_pages=cfg.serve_max_pages, prompt_buckets=buckets,
        max_seq=(buckets[-1] + cfg.serve_max_new_tokens
                 + cfg.serve_spec_tokens),
        seed=cfg.seed, prefix_cache=cfg.serve_prefix_cache,
        prefill_chunk=cfg.serve_prefill_chunk)
    draft = None
    if cfg.serve_draft_ckpt:
        # the draft self-configures from ITS manifest metadata (there is
        # only one --model flag, and it belongs to the target); every
        # pairing rejection — vocab mismatch, MoE draft — fires inside
        # the ServeEngine constructor below, before any request runs
        draft = ServeEngine.from_checkpoint(cfg.serve_draft_ckpt,
                                            **engine_kw)
    engine = ServeEngine.from_checkpoint(
        path, model=model, draft=draft,
        spec_tokens=cfg.serve_spec_tokens, **engine_kw)
    if requests is None:
        requests = build_requests(cfg, engine.spec.vocab)

    sanitize = cfg.sanitize or (
        os.environ.get("JAX_GRAFT_SANITIZE", "").strip().lower()
        not in ("", "0", "false", "off", "no"))
    warmup_counts = None
    if sanitize:
        from ..utils.batching import pick_bucket
        from ..xla_flags import (compile_event_counts,
                                 install_compile_counter)
        from .scheduler import Request
        install_compile_counter()
        mnt = min(2, cfg.serve_max_new_tokens)
        if engine.prefill_chunk:
            # chunked prefill: ONE [1, C] chunk program covers every
            # prompt length — a single longest-prompt request (>= 2
            # chunks when possible) compiles it + the decode step
            r0 = max(requests, key=lambda r: len(r.prompt),
                     default=None)
            warm = ([Request(rid=10_000_000, prompt=r0.prompt,
                             max_new_tokens=mnt,
                             temperature=r0.temperature)]
                    if r0 is not None else [])
        else:
            # warmup: ONE request per distinct prefill bucket
            # compiles every program the workload uses (+ the shared
            # decode step) off the measured run — warming all N
            # requests would scale startup with N for no extra
            # compile coverage.  With the prefix cache on, a
            # measured request can HIT pages and prefill only its
            # tail at a SMALLER bucket than its full length picks —
            # cover every configured bucket, not just the full-
            # length ones, so a partial hit can never retrace.
            per_bucket = {}
            for r in requests:
                per_bucket.setdefault(
                    pick_bucket(len(r.prompt), engine.prompt_buckets),
                    r)
            warm = [Request(rid=10_000_000 + i, prompt=r.prompt,
                            max_new_tokens=min(2, r.max_new_tokens),
                            temperature=r.temperature)
                    for i, r in enumerate(per_bucket.values())]
            if engine.prefix_cache:
                rng = np.random.default_rng(cfg.seed)
                warm += [
                    Request(rid=11_000_000 + i,
                            prompt=rng.integers(
                                0, engine.spec.vocab, b).tolist(),
                            max_new_tokens=mnt,
                            temperature=cfg.serve_temperature)
                    for i, b in enumerate(engine.prompt_buckets)
                    if b not in per_bucket]
        if warm:
            ContinuousBatchingScheduler(
                engine, eos_id=cfg.serve_eos_id).run(warm)
        warmup_counts = compile_event_counts()

    sched = ContinuousBatchingScheduler(
        engine, eos_id=cfg.serve_eos_id,
        request_timeout=cfg.serve_request_timeout)
    telemetry = sched.run(requests)
    completions = telemetry.pop("completions")
    # compiled-memory observability (ISSUE 15): the serve twin of the
    # driver's results["memory"] — memory_analysis of the decode-step
    # executable + every compiled prefill bucket (no analytic resident
    # model: serve state is the params + the byte-exact page accounting
    # the scheduler already reports)
    from ..probe import memory_report
    telemetry["memory"] = memory_report(engine.memory_programs())
    telemetry["retrace_count"] = 0
    telemetry["recompile_count"] = 0
    telemetry["sanitized"] = bool(sanitize)
    if sanitize:
        counts = compile_event_counts()
        telemetry["retrace_count"] = (counts["traces"]
                                      - warmup_counts["traces"])
        telemetry["recompile_count"] = (counts["compiles"]
                                        - warmup_counts["compiles"])
        if telemetry["retrace_count"] or telemetry["recompile_count"]:
            raise RuntimeError(
                f"serve sanitizer: the steady-state decode run added "
                f"{telemetry['retrace_count']} trace(s) / "
                f"{telemetry['recompile_count']} compile(s) past the "
                "warmup — the loop must re-dispatch only the prefill-"
                "bucket and decode-step programs")
        log.info("serve sanitizer clean: 0 post-warmup retraces across "
                 "%d decode steps", telemetry["decode_steps"])
    return {"serve": telemetry, "completions": completions,
            "engine": engine}


def serve_main(argv=None) -> int:
    """``python -m ...main serve`` entry: serve off a checkpoint, print
    one JSON telemetry line plus per-request decoded ids."""
    from ..config import config_from_args
    args = sys.argv[1:] if argv is None else list(argv)
    cfg = config_from_args(args)
    logging.basicConfig(
        level=getattr(logging, cfg.log_level.upper(), logging.INFO),
        format="%(asctime)s %(name)s %(levelname)s: %(message)s")
    # explicit --model (even restating the dataclass default) engages the
    # mismatch check / metadata-less fallback; absent means self-configure
    given = any(a == "--model" or a.startswith("--model=") for a in args)
    results = run_serve(cfg, model_flag_given=given)
    for c in results["completions"]:
        print(f"request {c.rid}: prompt_len={c.prompt_len} "
              f"reason={c.reason} tokens={','.join(map(str, c.tokens))}")
    print("SERVE " + json.dumps(results["serve"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(serve_main())
