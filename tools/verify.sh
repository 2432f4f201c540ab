#!/usr/bin/env bash
# Repo verification on the CPU: the lint gate (graftlint, ruff), the
# tier-1 test command (ROADMAP.md, verbatim semantics), then CLI smokes
# of the program through `main` on virtual CPU devices: checkpoint
# kill-mid-write -> resume, sanitize, async rounds, hierarchical sync,
# chaos, crash, sim, serve (greedy and speculative), optimizer
# placement, parameter residency and the memory tier.  Nothing here is a
# measurement: the benchmark is BENCHMARK.json + benchmarks/ on the chip
# (PERF.md), the chip proof `python chip_smoke.py`.
#
# Usage:  tools/verify.sh
set -u
cd "$(dirname "$0")/.."

# Lint gate (ISSUE 6): graftlint's JAX-hazard rules + ruff's generic
# Python rules run BEFORE pytest — a non-baselined finding fails the
# build without paying a single compile.
echo "== graftlint (JAX-hazard static analysis) =="
python -m tools.graftlint
lrc=$?
if [ "$lrc" -ne 0 ]; then
  echo "graftlint FAILED (rc=$lrc) — fix, suppress with a justified"
  echo "  '# graftlint: disable=R<n> -- reason', or baseline via"
  echo "  'python -m tools.graftlint --write-baseline'"
  exit "$lrc"
fi

echo "== ruff (generic Python lint, pinned config in pyproject.toml) =="
if command -v ruff >/dev/null 2>&1; then
  ruff check . || { echo "ruff FAILED"; exit 1; }
elif python -c "import ruff" >/dev/null 2>&1; then
  python -m ruff check . || { echo "ruff FAILED"; exit 1; }
else
  echo "ruff not installed in this environment — skipped (the pinned"
  echo "  F/E9/B config in pyproject.toml gates wherever ruff exists)"
fi

echo "== tier-1 tests (ROADMAP.md) =="
set -o pipefail
rm -f /tmp/_t1.log
t1_start=$SECONDS
timeout -k 10 870 env JAX_PLATFORMS=cpu \
  python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
# wall-time visibility: the tier-1 budget is 870 s — regressions toward it
# should be seen long before timeout -k kills the run
t1_wall=$((SECONDS - t1_start))
echo "TIER1_WALL_S=${t1_wall} (budget 870)"
if [ "$t1_wall" -gt 652 ]; then
  echo "WARNING: tier-1 wall ${t1_wall}s exceeds 75% of the 870s budget —"
  echo "         move heavy cases to the 'slow' marker before the"
  echo "         suite starts timing out"
fi
if [ "$rc" -ne 0 ]; then
  echo "tier-1 FAILED (rc=$rc)"
  exit "$rc"
fi

# Checkpoint kill-mid-write -> resume smoke (ISSUE 5 satellite): phase A
# trains 2 rounds with per-round commits, then starts a THIRD save and is
# killed (os._exit via the JAX_GRAFT_CKPT_TEST_CRASH hook) after the shard
# write but before the manifest commit — exactly what a mid-write SIGKILL
# leaves on disk.  Phase B must (a) sweep the unmanifested debris at
# engine open, (b) resolve latest to the newest COMMITTED epoch, (c)
# restore it BITWISE identical to phase A's post-round-2 state, and (d)
# resume the run from there.
echo "== checkpoint kill-mid-write -> resume smoke =="
CKPT_SMOKE_DIR=$(mktemp -d)
trap 'rm -rf "$CKPT_SMOKE_DIR"' EXIT
JAX_PLATFORMS=cpu python - "$CKPT_SMOKE_DIR" <<'EOF'
import os, sys
import numpy as np
from learning_deep_neural_network_in_distributed_computing_environment_tpu import checkpoint as C
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

d = sys.argv[1]
kw = dict(model="mlp", dataset="mnist", epochs_local=1, batch_size=16,
          limit_train_samples=256, limit_eval_samples=64,
          compute_dtype="float32", augment=False, aggregation_by="weights",
          checkpoint_dir=d, checkpoint_every=1, seed=7)
res = train_global(Config(epochs_global=2, **kw), progress=False)
pieces, meta = C.snapshot_addressable(res["state"])
full = {k: C._merge_pieces(k, pl, tuple(meta[k]["shape"]), pl[0][1].dtype)
        for k, pl in pieces.items()}
np.savez(os.path.join(d, "expect.npz"), **full)
# the mid-write kill: shard lands, manifest never does
os.environ["JAX_GRAFT_CKPT_TEST_CRASH"] = "before_manifest"
eng = C.CheckpointEngine(d, async_write=True)
eng.save(res["state"], 99)
eng.wait()           # the writer thread os._exit(42)s before this returns
os._exit(1)          # unreachable: the crash hook must have fired
EOF
rc=$?
if [ "$rc" -ne 42 ]; then
  echo "ckpt kill-mid-write phase A FAILED (rc=$rc, expected 42)"
  exit 1
fi
JAX_PLATFORMS=cpu python - "$CKPT_SMOKE_DIR" <<'EOF'
import os, sys
import numpy as np
from learning_deep_neural_network_in_distributed_computing_environment_tpu import checkpoint as C
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

d = sys.argv[1]
eng = C.CheckpointEngine(d)       # open -> sweep the mid-write debris
names = {n for root, _ds, fs in os.walk(d)
         for n in fs + [os.path.basename(root)]}
assert not any(".tmp." in n for n in names), names
assert not os.path.isdir(os.path.join(d, "ckpt_99")), "debris survived sweep"
latest = eng.latest_checkpoint()
assert latest and latest.endswith("ckpt_2"), latest
got, ep = C.host_tree(latest)
assert ep == 2
exp = np.load(os.path.join(d, "expect.npz"))
for k in exp.files:
    assert np.array_equal(exp[k], got[k]), f"leaf {k} not bit-identical"
kw = dict(model="mlp", dataset="mnist", epochs_local=1, batch_size=16,
          limit_train_samples=256, limit_eval_samples=64,
          compute_dtype="float32", augment=False, aggregation_by="weights",
          checkpoint_dir=d, checkpoint_every=1, seed=7)
res = train_global(Config(epochs_global=3, resume=True, **kw),
                   progress=False)
assert len(res["global_train_losses"]) == 1   # only round 3 ran
assert C.committed_epochs(d)[-1] == 3
print("ckpt kill-mid-write smoke OK")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "ckpt kill-mid-write phase B FAILED (rc=$rc)"
  exit "$rc"
fi

# Runtime sanitizer smoke (ISSUE 6), CLI edition: a 2-round --sanitize
# run through the real `python -m ...main` entry — the round loop
# executes inside jax.transfer_guard("disallow"), the retrace budget
# asserts rounds after the warmup add ZERO jaxpr traces / backend
# compiles, and donated round-state buffers are checked deleted.  Any
# violation raises (non-zero exit); a clean run logs the provenance
# line asserted below.  (tests/test_sanitize.py already covers the
# library path + the all-zero results["sanitize"] row — this smoke
# covers the --sanitize flag, config plumbing, and main() instead.)
echo "== sanitize smoke (CLI --sanitize, 2-round CPU driver) =="
SAN_DIR=$(mktemp -d)
SAN_OUT="$SAN_DIR/out.log"
if ! JAX_PLATFORMS=cpu python -m \
    learning_deep_neural_network_in_distributed_computing_environment_tpu.main \
    --sanitize --device cpu --model mlp --dataset mnist \
    --epochs_global 2 --epochs_local 1 --batch_size 16 \
    --limit_train_samples 512 --limit_eval_samples 64 \
    --compute_dtype float32 --no_augment --aggregation_by weights \
    --seed 7 --out_dir "$SAN_DIR/graphs" \
    >"$SAN_OUT" 2>&1; then
  echo "sanitize smoke FAILED:"; tail -40 "$SAN_OUT"
  rm -rf "$SAN_DIR"; exit 1
fi
if ! grep -q "sanitizer clean" "$SAN_OUT"; then
  echo "sanitize smoke: run exited 0 but no 'sanitizer clean' provenance"
  echo "line was logged — the --sanitize flag did not arm the harness:"
  tail -40 "$SAN_OUT"; rm -rf "$SAN_DIR"; exit 1
fi
rm -rf "$SAN_DIR"
echo "sanitize smoke OK"

# Semi-synchronous sanitized driver smoke (ISSUE 16 satellite): a
# 2-worker --sync_staleness 1 CPU driver run under --sanitize — the
# overlapped dispatch, the non-donated stale-sync read, the AOT
# pre-compiled delivery fold, and the end-of-run drain all execute
# inside the transfer guard with ZERO post-warmup retraces and zero
# donation failures (the all-zero sanitizer row behind the greppable
# "sanitizer clean" provenance line).  --device cpu pins the sequential
# collective scheduler the staleness engine requires on this backend.
echo "== async sanitize smoke (CLI --sync_staleness 1 --sanitize, 2-worker CPU driver) =="
ASAN_DIR=$(mktemp -d)
ASAN_OUT="$ASAN_DIR/out.log"
if ! XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    JAX_PLATFORMS=cpu python -m \
    learning_deep_neural_network_in_distributed_computing_environment_tpu.main \
    --sanitize --device cpu --sync_staleness 1 --model mlp \
    --dataset mnist --epochs_global 3 --epochs_local 1 --batch_size 16 \
    --limit_train_samples 512 --limit_eval_samples 64 \
    --compute_dtype float32 --no_augment --aggregation_by weights \
    --seed 7 --out_dir "$ASAN_DIR/graphs" \
    >"$ASAN_OUT" 2>&1; then
  echo "async sanitize smoke FAILED:"; tail -40 "$ASAN_OUT"
  rm -rf "$ASAN_DIR"; exit 1
fi
if ! grep -q "sanitizer clean" "$ASAN_OUT"; then
  echo "async sanitize smoke: run exited 0 but no 'sanitizer clean'"
  echo "provenance line — the staleness path tripped the harness:"
  tail -40 "$ASAN_OUT"; rm -rf "$ASAN_DIR"; exit 1
fi
if ! grep -q "async rounds: staleness 1" "$ASAN_OUT"; then
  echo "async sanitize smoke: no 'async rounds' summary line — the"
  echo "staleness engine did not arm:"
  tail -40 "$ASAN_OUT"; rm -rf "$ASAN_DIR"; exit 1
fi
rm -rf "$ASAN_DIR"
echo "async sanitize smoke OK"

# Hierarchical two-level sync smoke (ISSUE 13): a sanitized 2-slice x
# 2-worker CPU driver run — the CLI flags resolve the hier engine, the
# nested (slice, data) round + sync programs run under the transfer
# guard with ZERO post-warmup retraces (the all-zero sanitizer row),
# and the per-level telemetry's DCN/ICI byte split matches the exact
# accounting (the outer gossip hop rides the 1/W scatter shard).
echo "== hierarchical smoke (sanitized 2-slice x 2-worker CPU driver) =="
if ! XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    JAX_PLATFORMS=cpu python - <<'EOF'
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import config_from_args
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
from learning_deep_neural_network_in_distributed_computing_environment_tpu import comms

import jax.numpy as jnp

# through the CLI parser: the --num_slices / --sync_dtype_outer flag
# plumbing is part of what this smoke pins
cfg = config_from_args([
    "--device", "cpu", "--sanitize", "--model", "mlp",
    "--dataset", "mnist", "--topology", "ring", "--num_slices", "2",
    "--num_workers", "2", "--epochs_global", "2", "--epochs_local", "1",
    "--batch_size", "16", "--limit_train_samples", "256",
    "--limit_eval_samples", "64", "--compute_dtype", "float32",
    "--no_augment", "--aggregation_by", "weights", "--seed", "7",
    "--compile_cache_dir", ""])
res = train_global(cfg, progress=False)
san = res["sanitize"]
assert san == {"enabled": True, "transfer_guard_violations": 0,
               "retrace_count": 0, "recompile_count": 0,
               "donation_failures": 0}, san
se = res["sync_engine"]
assert se["mode"] == "hier" and se["num_slices"] == 2, se
assert se["levels"] == {"inner": "sharded", "outer": "gossip"}, se
rt = res["round_timings"][1]
assert rt["sync_bytes_ici"] == se["sync_bytes_ici"] > 0
assert rt["sync_bytes_dcn"] == se["sync_bytes_dcn"] > 0
# exact byte ratio at 2 workers/slice, fp32 both levels: the inner
# sharded engine moves 2(W-1)/W x padded = padded bytes per worker and
# the ring hop rides the padded/W = padded/2 shard — DCN = ICI / 2
assert rt["sync_bytes_ici"] == 2 * rt["sync_bytes_dcn"], rt
print("hier smoke: sanitizer all-zero, DCN/ICI byte ratio exact",
      {"ici": rt["sync_bytes_ici"], "dcn": rt["sync_bytes_dcn"]})
EOF
then
  echo "hierarchical smoke FAILED"; exit 1
fi
echo "hierarchical smoke OK"

# Chaos/elastic smoke (ISSUE 8): a 2-round sanitized CPU driver run on 4
# simulated workers with one scripted kill AND one join at the round-1
# boundary — the membership change resizes the mesh, re-buckets the sync
# engine, and restages the row-edited state in process.  Gate: rc 0, the
# elastic provenance line shows 2 applied events, and the all-zero
# sanitizer row SURVIVES the reshard ("sanitizer clean" — the new round
# program's recompile is the one sanctioned exception; anything else
# raises and fails the run).
echo "== chaos smoke (CLI --chaos kill+join, sanitized 2-round driver) =="
CHAOS_DIR=$(mktemp -d)
CHAOS_OUT="$CHAOS_DIR/out.log"
if ! XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    JAX_PLATFORMS=cpu python -m \
    learning_deep_neural_network_in_distributed_computing_environment_tpu.main \
    --sanitize --chaos "kill@1:w1,join@1" --device cpu \
    --model mlp --dataset mnist --num_workers 4 \
    --epochs_global 2 --epochs_local 1 --batch_size 16 \
    --limit_train_samples 512 --limit_eval_samples 64 \
    --compute_dtype float32 --no_augment --aggregation_by weights \
    --seed 7 --out_dir "$CHAOS_DIR/graphs" \
    >"$CHAOS_OUT" 2>&1; then
  echo "chaos smoke FAILED:"; tail -40 "$CHAOS_OUT"
  rm -rf "$CHAOS_DIR"; exit 1
fi
if ! grep -q "elastic: 2 membership event(s)" "$CHAOS_OUT"; then
  echo "chaos smoke: run exited 0 but the kill+join membership events"
  echo "were not applied (no elastic provenance line):"
  tail -40 "$CHAOS_OUT"; rm -rf "$CHAOS_DIR"; exit 1
fi
if ! grep -q "sanitizer clean" "$CHAOS_OUT"; then
  echo "chaos smoke: membership change applied but the all-zero"
  echo "sanitizer row did not survive the reshard:"
  tail -40 "$CHAOS_OUT"; rm -rf "$CHAOS_DIR"; exit 1
fi
rm -rf "$CHAOS_DIR"
echo "chaos smoke OK"

# Crash-recovery smoke (ISSUE 12): a sanitized 2-worker CLI run takes a
# NON-COOPERATIVE mid-round worker loss (crash@2:w1 — a missed round
# fence, not a boundary kill) and must (a) exit 0 with the rollback
# recovery sourced from the BUDDY copy (zero checkpoint reads: no
# --checkpoint_dir even exists), (b) keep the all-zero sanitizer row
# after the recovery window's re-baseline, and (c) — checked through the
# library below — replay the post-crash tail bitwise (fp32) from the
# captured recovery snapshot.
echo "== crash smoke (CLI crash@2:w1, sanitized 2-worker driver) =="
CRASH_DIR=$(mktemp -d)
CRASH_OUT="$CRASH_DIR/out.log"
if ! XLA_FLAGS="--xla_force_host_platform_device_count=2" \
    JAX_PLATFORMS=cpu python -m \
    learning_deep_neural_network_in_distributed_computing_environment_tpu.main \
    --sanitize --chaos "crash@2:w1" --device cpu \
    --model mlp --dataset mnist --num_workers 2 \
    --epochs_global 3 --epochs_local 1 --batch_size 16 \
    --limit_train_samples 512 --limit_eval_samples 64 \
    --compute_dtype float32 --no_augment --aggregation_by weights \
    --sync_mode sharded --seed 7 --out_dir "$CRASH_DIR/graphs" \
    >"$CRASH_OUT" 2>&1; then
  echo "crash smoke FAILED:"; tail -40 "$CRASH_OUT"
  rm -rf "$CRASH_DIR"; exit 1
fi
if ! grep -q "crash recovery via buddy" "$CRASH_OUT"; then
  echo "crash smoke: run exited 0 but the rollback recovery did not"
  echo "source the buddy copy (no 'crash recovery via buddy' line):"
  tail -40 "$CRASH_OUT"; rm -rf "$CRASH_DIR"; exit 1
fi
if ! grep -q "sanitizer clean" "$CRASH_OUT"; then
  echo "crash smoke: recovery applied but the all-zero sanitizer row"
  echo "did not survive the rollback re-baseline:"
  tail -40 "$CRASH_OUT"; rm -rf "$CRASH_DIR"; exit 1
fi
rm -rf "$CRASH_DIR"
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
  JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

kw = dict(model="mlp", dataset="mnist", epochs_global=4, epochs_local=1,
          batch_size=16, limit_train_samples=400, limit_eval_samples=100,
          compute_dtype="float32", augment=False, seed=1, num_workers=4,
          aggregation_by="weights", sync_mode="sharded", sanitize=True,
          chaos="crash@2:w1")
probe = np.array([1.0, 1.5, 1.0, 2.0])
walls = lambda e: np.ones(4)
full = train_global(Config(**kw), progress=False,
                    simulated_durations=probe,
                    simulated_round_durations=walls)
el = full["elastic"]
assert el["recovery_source"] == ["buddy"], el["recovery_source"]
assert el["crashes"] == 1 and el["recoveries"] == 1
assert full["sync_engine"]["param_residency"] == "resident"
assert full["sanitize"]["retrace_count"] == 0
assert full["sanitize"]["transfer_guard_violations"] == 0
fresh = train_global(Config(**kw), progress=False,
                     simulated_durations=probe,
                     simulated_round_durations=walls,
                     elastic_snapshot=el["snapshots"][0])
for k in ("global_train_losses", "global_val_losses", "step_caps",
          "shard_sizes"):
    assert full[k][2:] == fresh[k], f"results[{k!r}] diverged"
print("crash smoke OK: buddy recovery, bitwise tail from the recovery"
      " snapshot")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "crash bitwise-tail smoke FAILED (rc=$rc)"
  exit "$rc"
fi

# Scenario-lab smoke (ISSUE 14), CLI edition: a sanitized 2-round
# simulated driver run through config_from_args — the --sim_* flag
# plumbing resolves the SimEngine, the vmap'd round + stacked sync run
# under the transfer guard with ZERO post-warmup retraces (the all-zero
# sanitizer row), the donated stacked state passes the deletion asserts,
# and the run artifact carries the sim provenance (mode "sim",
# per-worker wire accounting).
echo "== sim smoke (sanitized 16-worker simulated CPU driver) =="
if ! JAX_PLATFORMS=cpu python - <<'EOF'
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import config_from_args
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

cfg = config_from_args([
    "--device", "cpu", "--sanitize", "--model", "mlp",
    "--dataset", "mnist", "--sim_workers", "16", "--topology", "ring",
    "--epochs_global", "2", "--epochs_local", "1", "--batch_size", "16",
    "--limit_train_samples", "256", "--limit_eval_samples", "64",
    "--compute_dtype", "float32", "--no_augment",
    "--aggregation_by", "weights", "--seed", "7",
    "--compile_cache_dir", ""])
res = train_global(cfg, progress=False)
san = res["sanitize"]
assert san == {"enabled": True, "transfer_guard_violations": 0,
               "retrace_count": 0, "recompile_count": 0,
               "donation_failures": 0}, san
s = res["sim"]
assert s["workers"] == 16 and s["rounds"] == 2, s
assert s["per_worker_sync_bytes"] > 0
assert res["sync_engine"]["mode"] == "sim"
assert len(res["all_workers_losses"]) == 16
print("sim smoke: sanitizer all-zero on the 16-worker vmap'd driver,",
      s["per_worker_sync_bytes"], "wire bytes/worker")
EOF
then
  echo "sim CLI smoke FAILED"; exit 1
fi
echo "sim CLI smoke OK"

# Serving smoke (ISSUE 7): train 2 rounds of gpt_tiny with per-round
# checkpoints at the DEFAULT compute dtype (bfloat16 — what the chip
# serves in), then `main.py serve` decodes a fixed prompt GREEDILY off
# the committed checkpoint through the real CLI (model self-configured
# from MANIFEST metadata, params streamed worker-0-row to device) under
# --sanitize (zero post-warmup retraces across the decode run).  The
# decoded ids must pass chip_smoke.greedy_gate (the full-forward argmax
# given the same prefix, in the same dtype), and a second serve run must
# reproduce them byte-for-byte.
echo "== serve smoke (train -> checkpoint -> CLI serve, greedy) =="
SERVE_DIR=$(mktemp -d)
JAX_PLATFORMS=cpu python - "$SERVE_DIR" <<'EOF'
import sys
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

cfg = Config(model="gpt_tiny", dataset="synthetic_lm", epochs_global=2,
             epochs_local=1, batch_size=8, limit_train_samples=64,
             limit_eval_samples=16, augment=False,
             aggregation_by="weights", checkpoint_dir=sys.argv[1],
             checkpoint_every=1, seed=3)
assert cfg.compute_dtype == "bfloat16"
train_global(cfg, progress=False)
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "serve smoke train phase FAILED (rc=$rc)"; rm -rf "$SERVE_DIR"; exit 1
fi
serve_once() {
  JAX_PLATFORMS=cpu python -m \
    learning_deep_neural_network_in_distributed_computing_environment_tpu.main \
    serve --device cpu --checkpoint_dir "$SERVE_DIR" \
    --serve_prompt 5,9,3,7,2 --serve_max_new_tokens 4 --serve_requests 2 \
    --serve_max_batch 2 --serve_page_size 8 --serve_max_pages 16 \
    --serve_prompt_buckets 8 --sanitize 2>/dev/null
}
SERVE_OUT1=$(serve_once) || { echo "serve smoke CLI run 1 FAILED"; rm -rf "$SERVE_DIR"; exit 1; }
SERVE_OUT2=$(serve_once) || { echo "serve smoke CLI run 2 FAILED"; rm -rf "$SERVE_DIR"; exit 1; }
JAX_PLATFORMS=cpu python - "$SERVE_DIR" <<EOF
import json, sys
import jax.numpy as jnp
import chip_smoke
from learning_deep_neural_network_in_distributed_computing_environment_tpu import checkpoint as ckpt_lib
from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve import engine

path = ckpt_lib.latest_checkpoint(sys.argv[1])
meta = ckpt_lib.manifest_metadata(path)
assert meta["compute_dtype"] == "bfloat16", meta
model = engine.model_from_metadata(meta)
params = engine.load_params_row0(path)
prompt = [5, 9, 3, 7, 2]
outs = []
for out in ('''$SERVE_OUT1''', '''$SERVE_OUT2'''):
    lines = out.strip().splitlines()
    toks = [[int(t) for t in l.rsplit("tokens=", 1)[1].split(",")]
            for l in lines if "tokens=" in l]
    assert len(toks) == 2 and toks[0] == toks[1], toks
    report = chip_smoke.greedy_gate(model, params, [prompt] * 2, toks,
                                    jnp.bfloat16, pad_to=16)
    assert report["tokens"] == 8, report
    outs.append(toks)
    tele = json.loads(next(l for l in lines
                           if l.startswith("SERVE ")).split(" ", 1)[1])
    assert tele["sanitized"] is True
    assert tele["retrace_count"] == 0 and tele["recompile_count"] == 0
    assert tele["pages"]["leaked"] == 0
assert outs[0] == outs[1], outs
print("serve smoke OK (bfloat16): greedy ids pass the full-forward gate,"
      " twice identical, 0 post-warmup retraces")
EOF
rc=$?
rm -rf "$SERVE_DIR"
if [ "$rc" -ne 0 ]; then
  echo "serve smoke assertions FAILED (rc=$rc)"
  exit "$rc"
fi

# Speculative-decoding smoke (ISSUE 18): train a gpt_tiny DRAFT and a
# gpt_small TARGET (different arch, different seed — real disagreement),
# then serve the target through the real CLI twice: plain, and with
# --serve_draft_ckpt/--serve_spec_tokens 4.  The speculative run must
# emit byte-identical greedy ids (the draft only changes WHEN tokens
# appear, never WHICH), accept at least one proposal, stay sanitized
# (zero post-warmup retraces across draft + verify programs), and leak
# zero pages from EITHER pool.
echo "== speculative serve smoke (draft+target ckpts -> CLI, bitwise) =="
SPEC_DIR=$(mktemp -d)
JAX_PLATFORMS=cpu python - "$SPEC_DIR" <<'EOF'
import sys
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

d = sys.argv[1]
kw = dict(dataset="synthetic_lm", epochs_global=1, epochs_local=1,
          batch_size=8, limit_train_samples=32, limit_eval_samples=16,
          compute_dtype="float32", augment=False,
          aggregation_by="weights", checkpoint_every=1)
train_global(Config(model="gpt_tiny", seed=11,
                    checkpoint_dir=f"{d}/draft", **kw), progress=False)
train_global(Config(model="gpt_small", seed=3,
                    checkpoint_dir=f"{d}/target", **kw), progress=False)
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "speculative smoke train phase FAILED (rc=$rc)"; rm -rf "$SPEC_DIR"; exit 1
fi
spec_serve() {
  JAX_PLATFORMS=cpu python -m \
    learning_deep_neural_network_in_distributed_computing_environment_tpu.main \
    serve --device cpu --checkpoint_dir "$SPEC_DIR/target" \
    --serve_prompt 5,9,3,7,2 --serve_max_new_tokens 6 --serve_requests 2 \
    --serve_max_batch 2 --serve_page_size 8 --serve_max_pages 16 \
    --serve_prompt_buckets 8 --sanitize "$@" 2>/dev/null
}
SPEC_PLAIN=$(spec_serve) || { echo "speculative smoke twin run FAILED"; rm -rf "$SPEC_DIR"; exit 1; }
SPEC_OUT=$(spec_serve --serve_draft_ckpt "$SPEC_DIR/draft" \
  --serve_spec_tokens 4) || { echo "speculative smoke spec run FAILED"; rm -rf "$SPEC_DIR"; exit 1; }
rm -rf "$SPEC_DIR"
python - <<EOF
import json
def parse(out):
    lines = out.strip().splitlines()
    toks = [l.rsplit("tokens=", 1)[1] for l in lines if "tokens=" in l]
    tele = json.loads(next(l for l in lines
                           if l.startswith("SERVE ")).split(" ", 1)[1])
    return toks, tele
plain_toks, plain = parse('''$SPEC_PLAIN''')
spec_toks, spec = parse('''$SPEC_OUT''')
assert spec_toks == plain_toks, (spec_toks, plain_toks)
assert spec["sanitized"] is True
assert spec["retrace_count"] == 0 and spec["recompile_count"] == 0
assert spec["spec"]["verify_steps"] > 0, spec["spec"]
assert spec["spec"]["acceptance_rate"] > 0, spec["spec"]
assert spec["pages"]["leaked"] == 0
assert spec["pages"]["draft_leaked"] == 0
assert plain["spec"] == {"acceptance_rate": 0.0, "draft_steps": 0,
                         "verify_steps": 0,
                         "target_steps_per_token": 0.0}, plain["spec"]
print("speculative smoke OK: CLI spec ids == twin, acceptance",
      spec["spec"]["acceptance_rate"], "with 0 post-warmup retraces")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "speculative smoke assertions FAILED (rc=$rc)"
  exit "$rc"
fi

# Shard-resident optimizer smoke (ISSUE 9): a 2-worker CPU run of the
# SAME sanitized weights-mode config under --opt_placement replicated vs
# sharded — the round-boundary apply moves from the post-gather
# full-size twin onto the 1/N psum_scatter shard, and the final params
# must be BITWISE identical (the fp32 placement gate, through the real
# driver).  A third gradients-mode run checks the round-optimizer
# moments actually land sharded: per-worker round_opt bytes at exactly
# 1/2 of the replicated layout on the 2-worker mesh.
echo "== opt-placement smoke (2-worker sharded vs replicated, sanitized) =="
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
  JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import jax
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

kw = dict(model="mlp", dataset="mnist", epochs_global=2, epochs_local=1,
          batch_size=16, limit_train_samples=256, limit_eval_samples=64,
          compute_dtype="float32", augment=False, seed=7, num_workers=2,
          sync_mode="sharded", sanitize=True)
runs = {}
for pl in ("replicated", "sharded"):
    # param_residency pinned replicated: this smoke gates the ISSUE 9
    # apply PLACEMENT on the full params tree (the sharded run would
    # otherwise auto-resolve the ISSUE 11 resident layout, whose state
    # carries no params leaves — the residency smoke below owns that axis)
    res = train_global(Config(aggregation_by="weights", opt_placement=pl,
                              param_residency="replicated",
                              **kw), progress=False)
    assert res["sync_engine"]["opt_placement"] == pl, res["sync_engine"]
    assert res["sanitize"]["retrace_count"] == 0
    assert res["sanitize"]["transfer_guard_violations"] == 0
    runs[pl] = jax.device_get(res["state"].params)
leaves = {pl: jax.tree_util.tree_leaves(runs[pl]) for pl in runs}
assert leaves["replicated"] and \
    len(leaves["replicated"]) == len(leaves["sharded"])
for a, b in zip(leaves["replicated"], leaves["sharded"]):
    assert np.array_equal(np.asarray(a), np.asarray(b)), \
        "sharded apply diverged from the replicated twin"
byt = {}
for pl in ("replicated", "sharded"):
    res = train_global(Config(aggregation_by="gradients", opt_placement=pl,
                              **kw), progress=False)
    byt[pl] = res["sync_engine"]["per_worker_state_bytes"]["round_opt"]
    assert byt[pl] > 0, res["sync_engine"]
assert byt["replicated"] == 2 * byt["sharded"], byt
print("opt-placement smoke OK: fp32 sharded apply bitwise == replicated,"
      f" per-worker round_opt bytes {byt['sharded']} vs"
      f" {byt['replicated']} (1/2)")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "opt-placement smoke FAILED (rc=$rc)"
  exit "$rc"
fi

# Param-residency smoke (ISSUE 11): the SAME sanitized weights-mode
# config under --param_residency replicated vs resident — between rounds
# the resident run holds only each worker's 1/N bucket shard of the
# consensus (entry gather inside the donated round program, sync ends at
# the scatter), and the trajectories plus final consensus params must be
# BITWISE identical through the real driver with ZERO post-warmup
# retraces.  Also asserts the recorded state-bytes split: resident shard
# exactly 1/N of the transient gathered peak.
echo "== param-residency smoke (2-worker resident vs replicated, sanitized) =="
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
  JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import jax
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

kw = dict(model="mlp", dataset="mnist", epochs_global=2, epochs_local=1,
          batch_size=16, limit_train_samples=256, limit_eval_samples=64,
          compute_dtype="float32", augment=False, seed=7, num_workers=2,
          aggregation_by="weights", sync_mode="sharded", sanitize=True)
runs = {}
for pr in ("replicated", "resident"):
    res = train_global(Config(param_residency=pr, **kw), progress=False)
    assert res["sync_engine"]["param_residency"] == pr, res["sync_engine"]
    assert res["sanitize"]["retrace_count"] == 0
    assert res["sanitize"]["transfer_guard_violations"] == 0
    runs[pr] = res
assert runs["resident"]["state"].params is None
assert runs["resident"]["state"].params_resident is not None
for k in ("global_train_losses", "global_val_losses"):
    assert runs["resident"][k] == runs["replicated"][k], k
a = jax.tree_util.tree_leaves(runs["resident"]["variables"]["params"])
b = jax.tree_util.tree_leaves(runs["replicated"]["variables"]["params"])
assert a and len(a) == len(b)
for x, y in zip(a, b):
    assert np.array_equal(np.asarray(x), np.asarray(y)), \
        "resident consensus diverged from the replicated twin"
pw = runs["resident"]["sync_engine"]["per_worker_state_bytes"]
assert pw["params"] * 2 == pw["params_gathered_peak"], pw
pww = runs["replicated"]["sync_engine"]["per_worker_state_bytes"]
assert pww["params_gathered_peak"] == 0
print("param-residency smoke OK: resident rounds bitwise == replicated,"
      f" per-worker resident params {pw['params']} vs transient peak"
      f" {pw['params_gathered_peak']} (1/2)")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "param-residency smoke FAILED (rc=$rc)"
  exit "$rc"
fi

# Memory-tier driver smoke (ISSUE 15): a sanitized 2-worker CPU run of
# a SCANNED family under --remat_policy save_names:attn_out vs the
# "none" twin — the named policy resolves through the real config/
# driver/engine plumbing, the fp32 trajectory and final params are
# BITWISE the baseline's (remat never changes math), zero post-warmup
# retraces, and every run emits a populated results["memory"] row
# (compiled temp/argument bytes per cached executable + the exact
# resident-state accounting).
echo "== memory-tier smoke (2-worker save_names vs none, sanitized) =="
XLA_FLAGS="--xla_force_host_platform_device_count=2" \
  JAX_PLATFORMS=cpu python - <<'EOF'
import numpy as np
import jax
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

kw = dict(model="gpt_tiny", dataset="synthetic_lm", epochs_global=2,
          epochs_local=1, batch_size=4, limit_train_samples=64,
          limit_eval_samples=16, compute_dtype="float32", augment=False,
          seed=7, num_workers=2, aggregation_by="weights", sanitize=True)
runs = {}
for pol in ("none", "save_names:attn_out"):
    res = train_global(Config(remat_policy=pol, **kw), progress=False)
    assert res["sanitize"]["retrace_count"] == 0, res["sanitize"]
    m = res["memory"]
    assert m["available"] is True, m
    assert m["programs"]["round"][0]["temp_bytes"] > 0, m
    assert m["state_bytes_total"] == 2 * m["per_worker_resident_bytes"]
    runs[pol] = res
base, named = runs["none"], runs["save_names:attn_out"]
assert base["global_train_losses"] == named["global_train_losses"]
a = jax.tree_util.tree_leaves(base["variables"]["params"])
b = jax.tree_util.tree_leaves(named["variables"]["params"])
assert a and len(a) == len(b)
for x, y in zip(a, b):
    assert np.array_equal(np.asarray(x), np.asarray(y)), \
        "save_names trajectory diverged from the none twin"
tn = base["memory"]["programs"]["round"][0]["temp_bytes"]
ts = named["memory"]["programs"]["round"][0]["temp_bytes"]
assert ts <= tn, (ts, tn)
print("memory-tier smoke OK: save_names bitwise == none; round temp "
      f"bytes {ts} <= {tn}; memory row populated on both runs")
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
  echo "memory-tier smoke FAILED (rc=$rc)"
  exit "$rc"
fi

echo "verify OK"
