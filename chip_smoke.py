"""chip_smoke.py — the quickest proof that the system starts on the chip.

One process drives the normal path once, through the entry points a user
calls, on every chip it finds (one v5e chip or the four-chip host: the
same file), and exits non-zero if any phase fails:

1. ``kernels``  ``ops.attention.attend(impl="flash", causal=True)`` forward
   and ``jax.grad`` at bf16, head_dim 64, for [1, 4096, 12, 64] (MHA) and
   [1, 2048, 16, 64] with 4 KV heads (GQA), against
   ``dot_product_attention`` at a written tolerance: through the CLI the
   kernels only ever see one 128 block.
2. ``train_gpt``  ``main.train_main``: gpt2_small at its published widths
   (12 layers, hidden 768, 12 heads; vocabulary 1000 and length 128 come
   from ``synthetic_lm``), two global rounds, flash attention, default
   dtypes, a checkpoint per round.  The compiled round program must hold
   the Mosaic custom call and no flash->dense fallback may have fired.
3. ``serve_gpt``  ``main.main(["serve", ...])`` off that checkpoint: eight
   requests of different prompt lengths; the served greedy ids must equal
   the full-forward argmax continuation from the restored params in the
   same dtype (``greedy_gate``).
4. ``train_cnn``  the README's first line: enhanced_cnn at reference width
   64 on CIFAR-shape data with the default flags, through the rank-0
   evaluation and the six plots.
5. with more than one chip: the allreduce run above must have resolved
   ``mode: sharded`` / ``param_residency: resident`` with every state leaf
   sharded one row per chip; after one round its consensus must equal a
   ``--sync_mode dense`` twin's to fp32 rounding, with identical rows on
   every worker of the twin; over two rounds the loss trajectories must
   agree to bf16 tolerance; one ``--topology ring`` round must resolve
   ``mode: gossip``.

The walls it prints include compilation and are SMOKE walls, never
benchmark numbers.  Weights are random from a seed and the data is
synthetic (the tree ships no CIFAR binaries).  Plots go to
``chiprun_out/chip_smoke/`` inside the checkout; the checkpoints (GBs of
optimizer state) to ``.scratch_chip_smoke/`` there, removed on success.
The last stdout line is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")
WORK = os.path.join(REPO, ".scratch_chip_smoke")

# flash vs dense at bf16: both round probabilities and outputs to 8
# significant bits, so elementwise agreement is a few bf16 ulps of the
# tensor's scale
KERNEL_TOL = 2.0 ** -5
# a served token may differ from the reference argmax only where the
# reference itself is a near-tie: margin <= this many ulps of the compute
# dtype at the row's largest logit (paged decode and the full forward are
# different XLA programs; each rounds to the compute dtype per op)
GREEDY_TIE_ULPS = 4.0
# sharded (bucketed) vs dense sync twins.  After ONE round only the sync
# differs (fp32 means of the same values in another order); over two
# rounds bf16 local compute runs on top of that last-bit difference
SYNC_RTOL = 1e-5
TWIN_RTOL = 1e-2

GPT_TRAIN = [
    "--model", "gpt2_small", "--dataset", "synthetic_lm",
    "--attention_impl", "flash", "--aggregation_by", "weights",
    "--epochs_global", "2", "--epochs_local", "1", "--batch_size", "8",
    "--limit_train_samples", "640", "--limit_eval_samples", "64",
    "--seed", "0",
]
CNN_TRAIN = [
    "--epochs_global", "2", "--epochs_local", "1",
    "--limit_train_samples", "2560", "--limit_eval_samples", "512",
]
PLOTS = (
    "loss_distribution_by_worker.png", "loss_distribution_per_epoch.png",
    "loss_distribution_per_epoch_global.png",
    "accuracy_distribution_per_epoch_global.png", "training_metrics.png",
    "training_metrics_0.png",
)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


@contextlib.contextmanager
def phase(name: str, walls: dict):
    """Time one phase (compile included) and report its persistent-cache
    traffic.  An exception ends the script: no phase failure is survived."""
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.xla_flags import (
        compile_cache_counts,
    )
    c0, t0 = compile_cache_counts(), time.perf_counter()
    say(f"--- phase {name} ---")
    yield
    wall, c1 = time.perf_counter() - t0, compile_cache_counts()
    walls[name] = round(wall, 1)
    say(f"phase {name}: ok, smoke wall {wall:.1f} s (compile included); "
        f"persistent cache {c1['hits'] - c0['hits']} hits / "
        f"{c1['misses'] - c0['misses']} misses")


# ----------------------------------------------------------------------
# 1. kernels
# ----------------------------------------------------------------------

def check_flash_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import (
        pallas_ops,
    )
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops.attention import (
        attend,
        causal_mask,
        dot_product_attention,
    )

    def loss(fn):
        return lambda q, k, v, w: (fn(q, k, v).astype(jnp.float32) * w).sum()

    def dense_in_blocks(window, bq=1024):
        """Dense attention under the explicit mask, a block of queries at a
        time and recomputed on the way back: at L = 8192 one layer's whole
        score matrix is 8.6 GB."""
        def fn(q, k, v):
            b, l, h, d = q.shape
            block = jax.checkpoint(lambda a: dot_product_attention(
                a[0], k, v, mask=causal_mask(bq, l, q_offset=a[1],
                                             window=window)))
            qs = q.reshape(b, l // bq, bq, h, d).swapaxes(0, 1)
            out = jax.lax.map(block, (qs, jnp.arange(l // bq) * bq))
            return out.swapaxes(0, 1).reshape(b, l, h, v.shape[-1])
        return fn

    # d: the scores' width, or (the scores', the values')
    for name, (b, l, h, kv, d, window) in {
            "mha L=1024 (gpt2s_train_1k, the benchmark's)":
                (4, 1024, 12, 12, 64, None),
            "mha L=4096 (gpt2_4k_flash)": (1, 4096, 12, 12, 64, None),
            "gqa L=2048 16/4 (llama_gqa4)": (1, 2048, 16, 4, 64, None),
            "gqa L=8192 32/4 d=128 window 1024 (mellum2_train_8k's)":
                (1, 8192, 32, 4, 128, 1024),
            "gqa L=8192 32/4 d=128 window 2048 (trinity_mini_train_8k's)":
                (1, 8192, 32, 4, 128, 2048),
            "mla L=8192 32 heads, scores 192 values 128 (kanana2_train_8k's)":
                (1, 8192, 32, 32, (192, 128), None)}.items():
        d, dv = d if isinstance(d, tuple) else (d, d)
        flash = lambda q, k, v: attend(q, k, v, impl="flash", causal=True,
                                       window=window)
        dense = (dense_in_blocks(window) if l > 4096 else
                 lambda q, k, v: dot_product_attention(q, k, v, causal=True))
        keys = jax.random.split(jax.random.key(l), 4)
        q = jax.random.normal(keys[0], (b, l, h, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, l, kv, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, l, kv, dv), jnp.bfloat16)
        w = jax.random.normal(keys[3], (b, l, h, dv), jnp.float32)
        run = lambda fn: jax.jit(
            lambda *a: (fn(*a[:3]), jax.grad(loss(fn), (0, 1, 2))(*a)))
        lowered = run(flash).lower(q, k, v, w)
        if "tpu_custom_call" not in lowered.as_text():
            raise AssertionError(f"{name}: no Mosaic custom call lowered")
        out_f, grads_f = lowered.compile()(q, k, v, w)
        out_d, grads_d = run(dense)(q, k, v, w)
        for what, a, r in (("out", out_f, out_d),
                           *zip(("dq", "dk", "dv"), grads_f, grads_d)):
            a = np.asarray(a, np.float32)
            r = np.asarray(r, np.float32)
            if a.shape != r.shape or not np.isfinite(a).all():
                raise AssertionError(f"{name} {what}: shape/finite")
            err = float(np.abs(a - r).max() / max(np.abs(r).max(), 1e-6))
            say(f"flash {name} {what}: max|flash-dense|/max|dense| = "
                f"{err:.2e} (tolerance {KERNEL_TOL:.2e})")
            if err > KERNEL_TOL:
                raise AssertionError(f"{name} {what}: {err} > {KERNEL_TOL}")
    if pallas_ops._FALLBACK_LOGGED:
        raise AssertionError(
            f"flash fell back to dense: {pallas_ops._FALLBACK_LOGGED}")
    say_flash_tiles()
    # a window of one key block leaves a row 2 blocks, one of two 3 (a
    # crossed one, one wholly inside the band, the diagonal's)
    for window, want in ((1024, (16, 15, 64)), (2048, (24, 21, 64))):
        grid = pallas_ops.GRID_COUNTS[(8192, 8192, True, window)]
        if grid != want:
            raise AssertionError(f"the window {window} call's grid does not "
                                 f"follow its window: {grid}")


def say_flash_tiles() -> None:
    """The kernels' tile registry: a causal shape of more than one sub-tile
    must skip some and mask some, any other shape must visit them all
    unmasked; every block that holds work is a grid step of the one walk
    that the forward and the backward kernel share."""
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import (
        pallas_ops,
    )
    for key, (visited, total, masked) in sorted(
            pallas_ops.TILE_COUNTS.items(), key=str):
        causal = key[2]
        say(pallas_ops.tiles_line(key))
        walked, work, blocks = pallas_ops.GRID_COUNTS[key]
        if ((visited < total) != (causal and total > 1)
                or (masked > 0) != causal
                or not 0 < work <= min(walked, blocks)):
            raise AssertionError(f"wrong for this shape: "
                                 f"{pallas_ops.tiles_line(key)}")


# ----------------------------------------------------------------------
# 2./4./5. training runs
# ----------------------------------------------------------------------

def check_training(results: dict, n_dev: int, *, flash: bool) -> None:
    """What every training phase must show: finite, falling losses on all
    chips, compiled programs retained, and — with flash — the Mosaic call
    inside the compiled round program with no dense fallback."""
    import numpy as np

    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import (
        pallas_ops,
    )
    losses = results["global_train_losses"]
    say(f"sync_engine: {json.dumps(results['sync_engine'])}")
    say(f"global train losses {losses}; val acc "
        f"{results['global_val_accuracies']}")
    if not (len(losses) == 2 and np.isfinite(losses).all()
            and np.isfinite(results["global_val_losses"]).all()):
        raise AssertionError(f"losses not finite: {losses}")
    if not losses[1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if results["mesh"].devices.size != n_dev:
        raise AssertionError(
            f"mesh has {results['mesh'].devices.size} devices, jax has "
            f"{n_dev}")
    if not results["memory"]["available"]:
        raise AssertionError(f"a program has no executable: "
                             f"{results['memory']['programs_unavailable']}")
    say(f"compiled programs: {sorted(results['memory']['programs'])}, "
        f"{results['memory']['temp_bytes_total'] / 2**20:.0f} MiB temp; "
        f"compile cache {json.dumps(results['compile_cache'])}")
    say_hbm_and_build(results)
    if flash:
        text = results["engine"].memory_programs()["round"].compiled.as_text()
        n_calls = text.count("tpu_custom_call")
        say(f"round program: {n_calls} Mosaic custom-call mention(s)")
        if not n_calls:
            raise AssertionError("no Mosaic custom call in the compiled "
                                 "round program: flash did not run")
        if pallas_ops._FALLBACK_LOGGED:
            raise AssertionError(
                f"flash fell back to dense: {pallas_ops._FALLBACK_LOGGED}")
        say_flash_tiles()


def say_hbm_and_build(results: dict) -> None:
    """The allocator's reading at each set-up phase and each round of the
    call (the fullest chip; MiB in use / high mark), and round 0's build
    in its three stages.  On a TPU every stamp must be there, the high
    mark must never fall and the stages must add up to the build."""
    import jax
    mib = lambda b: f"{b / 2**20:.1f}"
    rows = results["round_timings"]
    hbm = results["memory"].get("hbm")
    if hbm is None:
        if jax.devices()[0].platform == "tpu":
            raise AssertionError("the program read no allocator statistics")
        say("hbm: this backend's allocator keeps no statistics")
    else:
        stamps = ([("on entry", hbm["on_entry"])]
                  + [(p["phase"], p) for p in hbm["phases"]]
                  + [(f"round {r}", row) for r, row in enumerate(rows)]
                  + [("at end", hbm["at_end"])])
        say("hbm MiB in use / peak (limit " + mib(hbm["limit_bytes"]) + "): "
            + ", ".join(f"{label} {mib(s['hbm_in_use_bytes'])} / "
                        f"{mib(s['hbm_peak_bytes'])}" for label, s in stamps)
            + f"; largest allocation "
              f"{mib(hbm['at_end'].get('largest_alloc_size', 0))}")
        peaks = [s["hbm_peak_bytes"] for _, s in stamps]
        # entry, set-up's five phases, a row a round, the end
        if len(stamps) != 7 + len(rows) or peaks != sorted(peaks):
            raise AssertionError(f"allocator stamps missing or the high "
                                 f"mark fell: {stamps}")
    row0 = rows[0]
    parts = [row0[k] for k in ("build_trace_ms", "build_lower_ms",
                               "build_compile_ms")]
    say(f"round 0 built {row0['programs_built']} in {row0['build_ms']:.1f} "
        "ms: trace {:.1f}, lower {:.1f}, compile-or-load {:.1f} ".format(
            *parts)
        + f"(cache hits {row0['build_cache_hits']}, misses "
          f"{row0['build_cache_misses']})")
    if not 0.95 * row0["build_ms"] <= sum(parts) <= row0["build_ms"] + 0.01:
        raise AssertionError(f"the build's stages do not add up: {row0}")


def state_leaves(state) -> list:
    import jax
    return [(jax.tree_util.keystr(p), x) for p, x in
            jax.tree_util.tree_flatten_with_path(state)[0]
            if isinstance(x, jax.Array)]


def check_sharded_run(results: dict, n_dev: int) -> None:
    """Several chips, the default allreduce engine: bucketed sync with
    shard-side apply and scatter-resident params, and every state leaf laid
    out one worker row per chip (nothing piled on device 0)."""
    se = results["sync_engine"]
    want = {"mode": "sharded", "opt_placement": "sharded",
            "param_residency": "resident"}
    got = {k: se[k] for k in want}
    if got != want:
        raise AssertionError(f"sync engine resolved {got}, expected {want}")
    state = results["state"]
    if state.params is not None or not state.params_resident:
        raise AssertionError("resident run kept a replicated params tree")
    for name, x in state_leaves(state):
        devs = {s.device for s in x.addressable_shards}
        rows = {s.data.shape[0] for s in x.addressable_shards}
        if len(devs) != n_dev or rows != {1} or x.shape[0] != n_dev:
            raise AssertionError(
                f"state leaf {name} {x.shape}: {len(devs)} device(s), "
                f"shard rows {rows}; expected one row on each of {n_dev}")
    say(f"state: {len(state_leaves(state))} leaves, each one row per chip "
        f"on {n_dev} distinct devices")


def check_consensus_after_one_round(sharded: dict, dense: dict) -> None:
    """One round, bucketed engine against the per-leaf dense one on the
    same chips.  The local phase is the same program on the same data, so
    the only difference is the sync itself (reduce-scatter + all-gather
    against all-reduce): the consensus must agree to fp32 rounding, and
    the dense layout, which keeps a full copy per worker, must hold
    identical rows on every chip after the equal-weights allreduce."""
    import jax
    import numpy as np
    for run, mode in ((sharded, "sharded"), (dense, "dense")):
        if run["sync_engine"]["mode"] != mode:
            raise AssertionError(f"{mode} arm resolved "
                                 f"{run['sync_engine']['mode']}")
    if sharded["global_train_losses"] != dense["global_train_losses"]:
        raise AssertionError(
            "pre-sync losses differ between the twins: "
            f"{sharded['global_train_losses']} vs "
            f"{dense['global_train_losses']}")
    spread = 0.0
    for _, x in state_leaves(dense["state"].params):
        rows = np.asarray(x)
        spread = max(spread, float(np.abs(rows - rows[:1]).max()))
    say(f"dense arm: max |row_i - row_0| over param leaves = {spread}")
    if spread != 0.0:
        raise AssertionError("workers disagree after the allreduce round")
    worst, exact = 0.0, True
    for a, b in zip(jax.tree.leaves(sharded["variables"]["params"]),
                    jax.tree.leaves(dense["variables"]["params"])):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        exact &= bool((a == b).all())
        worst = max(worst, float(np.abs(a - b).max()
                                 / max(np.abs(b).max(), 1e-30)))
    say(f"consensus after one round, sharded vs dense: max |diff| / "
        f"max|leaf| = {worst:.2e} (tolerance {SYNC_RTOL:.0e}"
        f"{', bitwise' if exact else ', NOT bitwise'})")
    if worst > SYNC_RTOL:
        raise AssertionError(f"consensus params differ ({worst})")


def check_two_round_trajectory(primary: dict, twin: dict) -> None:
    """Two rounds: the second round trains in bf16 from consensus params
    that may differ in the last fp32 bit, so the trajectories agree to
    bf16 tolerance, not bitwise."""
    import numpy as np
    if twin["sync_engine"]["mode"] != "dense":
        raise AssertionError(f"twin resolved {twin['sync_engine']['mode']}")
    for key in ("global_train_losses", "global_val_losses"):
        a, b = np.asarray(primary[key]), np.asarray(twin[key])
        rel = float(np.abs(a - b).max() / np.abs(b).max())
        say(f"{key}: sharded {a.tolist()} dense {b.tolist()} "
            f"max rel diff {rel:.2e} (tolerance {TWIN_RTOL:.0e}"
            f"{', bitwise' if rel == 0 else ''})")
        if rel > TWIN_RTOL:
            raise AssertionError(f"{key}: sharded != dense twin ({rel})")


# ----------------------------------------------------------------------
# 3. serve
# ----------------------------------------------------------------------

def greedy_gate(model, params, prompts, completions, dtype,
                pad_to: int = 64) -> dict:
    """The PR 7 gate in the compute dtype: every served token must be the
    full-forward argmax given the same prefix (prompt + tokens served so
    far) — or tie with it within ``GREEDY_TIE_ULPS`` ulps of ``dtype`` at
    the row's largest logit, counted and reported.  One full forward per
    request (the model is causal, so right-padding to ``pad_to`` changes
    no earlier position)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    fwd = jax.jit(lambda p, ids: model.apply({"params": p}, ids,
                                             train=False))
    ulp = float(jnp.finfo(dtype).eps)
    exact = ties = 0
    worst = 0.0
    for prompt, served in zip(prompts, completions):
        seq = list(prompt) + list(served)
        if not served or len(seq) > pad_to:
            raise AssertionError(f"bad completion: {len(prompt)} + "
                                 f"{len(served)} tokens (pad {pad_to})")
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :len(seq)] = seq
        logits = np.asarray(fwd(params, ids)[0], np.float32)
        if not np.isfinite(logits[:len(seq)]).all():
            raise AssertionError("reference logits not finite")
        for j, tok in enumerate(served):
            row = logits[len(prompt) + j - 1]
            best = int(row.argmax())
            if tok == best:
                exact += 1
                continue
            margin = float(row[best] - row[tok])
            tol = GREEDY_TIE_ULPS * ulp * float(np.abs(row).max())
            worst = max(worst, margin / tol)
            if margin > tol:
                raise AssertionError(
                    f"served token {tok} at +{j} of a {len(prompt)}-token "
                    f"prompt is not the reference argmax {best}: margin "
                    f"{margin:.4g} > tie tolerance {tol:.4g}")
            ties += 1
    return {"tokens": exact + ties, "exact": exact, "near_ties": ties,
            "worst_tie_vs_tol": round(worst, 3)}


def serve_and_gate(ckpt_dir: str) -> None:
    import jax.numpy as jnp

    from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
        checkpoint as ckpt_lib,
        main,
    )
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import (
        config_from_args,
    )
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve import (
        api,
        engine,
    )
    argv = ["--checkpoint_dir", ckpt_dir]
    captured = io.StringIO()
    with contextlib.redirect_stdout(captured):
        rc = main.main(["serve", *argv])
    lines = captured.getvalue().splitlines()
    print("\n".join(l if len(l) < 400 else l[:400] + " ..."
                    for l in lines), flush=True)
    if rc != 0:
        raise AssertionError(f"main serve returned {rc}")
    tele = json.loads(next(l for l in lines
                           if l.startswith("SERVE ")).split(" ", 1)[1])
    served = {}
    for l in lines:
        if l.startswith("request "):
            rid = int(l.split()[1].rstrip(":"))
            served[rid] = [int(t) for t in
                           l.rsplit("tokens=", 1)[1].split(",") if t]
    # the prompts `main serve` drew (seeded), the params it restored, and
    # the model its manifest names — rebuilt here, independent of the
    # serving engine
    path = ckpt_lib.latest_checkpoint(ckpt_dir)
    meta = ckpt_lib.manifest_metadata(path)
    requests = api.build_requests(config_from_args(argv),
                                  int(meta["num_classes"]))
    lens = [len(r.prompt) for r in requests]
    say(f"served {len(served)} requests, prompt lengths {lens}, "
        f"{tele['tokens_generated']} tokens in {tele['decode_steps']} "
        f"decode steps; pages leaked {tele['pages']['leaked']}; one chip "
        "serves (ServeEngine restores onto the default device)")
    if len(served) != len(requests) or len(set(lens)) < 4:
        raise AssertionError("need every request answered and >= 4 "
                             f"different prompt lengths, got {lens}")
    if tele["pages"]["leaked"] or not tele["memory"]["available"]:
        raise AssertionError(f"serve telemetry: {tele['pages']}, "
                             f"{tele['memory']['programs_unavailable']}")
    if meta.get("compute_dtype") != "bfloat16":
        raise AssertionError(f"checkpoint dtype {meta.get('compute_dtype')}: "
                             "the smoke serves the default compute dtype")
    model = engine.model_from_metadata(meta)
    params = (engine.load_params_resident(path, meta)
              if meta.get("param_residency") == "resident"
              else engine.load_params_row0(path))
    report = greedy_gate(model, params, [r.prompt for r in requests],
                         [served[r.rid] for r in requests], jnp.bfloat16)
    say(f"greedy gate ({meta['compute_dtype']}, {meta['param_residency']} "
        f"checkpoint): {json.dumps(report)}")
    if report["exact"] < 0.9 * report["tokens"]:
        raise AssertionError(f"too few exact argmax matches: {report}")


# ----------------------------------------------------------------------
# main
# ----------------------------------------------------------------------

def main() -> int:
    pinned = os.environ.get("JAX_PLATFORMS", "")
    if pinned and pinned.split(",")[0].strip() != "tpu":
        print(f"chip_smoke: JAX_PLATFORMS={pinned!r} — this script proves "
              "the TPU path and will not run on another platform",
              file=sys.stderr)
        return 2
    # tpu first in the list BEFORE the first jax import (the chip host
    # exports "tpu,cpu" itself): a libtpu that cannot start then raises,
    # where the unset default quietly hands back the CPU
    os.environ["JAX_PLATFORMS"] = pinned or "tpu"
    sys.path.insert(0, REPO)
    t_start = time.perf_counter()

    import importlib.metadata as md
    import logging

    import jax
    import numpy as np

    from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
        main as cli,
    )
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import (
        config_from_args,
    )
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import (
        train_global,
    )
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.xla_flags import (
        compile_cache_counts,
        setup_compile_cache,
    )

    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    if dev["platform"] != "tpu":
        raise AssertionError(f"jax came up on {dev['platform']!r}, not tpu")
    n_dev = dev["count"]
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s: %(message)s")
    say(f"platform {dev['platform']} / device_kind {dev['kind']} / "
        f"device count {n_dev}")
    say(f"jax {jax.__version__}, jaxlib {md.version('jaxlib')}, "
        f"libtpu {md.version('libtpu')}")
    say("data source: synthetic (synthetic_lm; CIFAR-shape synthetic — the "
        "tree ships no CIFAR binaries)")
    say(f"persistent compile cache: {setup_compile_cache()} "
        f"({'from' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'no'}"
        " JAX_COMPILATION_CACHE_DIR)")

    for d in (OUT, WORK):
        shutil.rmtree(d, ignore_errors=True)
        os.makedirs(d)
    walls: dict = {}
    ckpt_dir = os.path.join(WORK, "gpt_ckpt")

    with phase("kernels", walls):
        check_flash_kernels()

    with phase("train_gpt", walls):
        gpt = cli.train_main([
            *GPT_TRAIN, "--checkpoint_dir", ckpt_dir,
            "--checkpoint_every", "1",
            "--out_dir", os.path.join(OUT, "gpt_plots")])
        check_training(gpt, n_dev, flash=True)
        say(f"checkpoint: {json.dumps(gpt['checkpoint'])}")
        if gpt["checkpoint"].get("saves") != 2:
            raise AssertionError("expected one committed save per round")
        if n_dev > 1:
            check_sharded_run(gpt, n_dev)

    with phase("serve_gpt", walls):
        serve_and_gate(ckpt_dir)

    if n_dev > 1:
        run = lambda *extra: train_global(
            config_from_args([*GPT_TRAIN, *extra]))
        with phase("sync_twins", walls):
            one_sharded = run("--epochs_global", "1")
            one_dense = run("--epochs_global", "1", "--sync_mode", "dense")
            check_consensus_after_one_round(one_sharded, one_dense)
            del one_sharded, one_dense
            twin = run("--sync_mode", "dense")
            check_two_round_trajectory(gpt, twin)
            del twin
        with phase("ring_round", walls):
            ring = run("--topology", "ring", "--epochs_global", "1")
            say(f"sync_engine: {json.dumps(ring['sync_engine'])}; loss "
                f"{ring['global_train_losses']}")
            if ring["sync_engine"]["mode"] != "gossip":
                raise AssertionError(
                    f"ring resolved {ring['sync_engine']['mode']}")
            if not np.isfinite(ring["global_train_losses"]).all():
                raise AssertionError("ring loss is not finite")
            del ring
    del gpt

    with phase("train_cnn", walls):
        plots = os.path.join(OUT, "cnn_plots")
        cnn = cli.train_main([*CNN_TRAIN, "--out_dir", plots])
        check_training(cnn, n_dev, flash=False)
        missing = [p for p in PLOTS
                   if not os.path.isfile(os.path.join(plots, p))]
        if missing:
            raise AssertionError(f"plots not written: {missing}")
        del cnn

    shutil.rmtree(WORK)
    counts = compile_cache_counts()
    say(f"all phases ok; smoke walls (s, compile included): "
        f"{json.dumps(walls)}; total {time.perf_counter() - t_start:.1f} s; "
        f"persistent cache {counts['hits']} hits / {counts['misses']} "
        "misses this process")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
