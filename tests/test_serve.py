"""Serving engine (ISSUE 7): paged-decode equivalence + continuous
batching + direct-to-device checkpoint restore.

Correctness gates, tier-1 style:

- paged prefill logits are BITWISE equal to the full-sequence forward
  (identical op order over the same cached keys), incremental decode
  matches at fp32 tolerance with argmax equality — gpt, llama, GQA, MoE;
- batched continuous decoding emits the identical token stream a
  single-sequence decode would, per slot, greedy AND temperature
  (sampling keys derive from (seed, rid, position) only);
- the decode loop re-dispatches exactly the prefill-bucket + decode-step
  programs: a post-warmup run adds ZERO jaxpr traces / backend compiles
  across a >= 32-step decode;
- evicted sequences' pages recycle (literally the next ids handed out),
  admission under full occupancy blocks instead of failing, EOS and
  max-token stops finish with the right reason;
- ``from_checkpoint`` restores a training-mesh sharded checkpoint onto
  the serving mesh (worker-0 row, leaf-streamed) and manifest metadata
  self-configures the model.
"""

import dataclasses
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from learning_deep_neural_network_in_distributed_computing_environment_tpu import (  # noqa: E402
    checkpoint as ckpt_lib,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import (  # noqa: E402
    Config,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import (  # noqa: E402
    decode as D,
    get_model,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve import (  # noqa: E402
    ContinuousBatchingScheduler,
    PageAllocator,
    Request,
    ServeEngine,
    page_prefix_keys,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.utils.batching import (  # noqa: E402
    pad_to_batches,
    pad_to_bucket,
    pick_bucket,
)

VOCAB = 97
PROMPT = [5, 9, 3, 7, 2, 11, 4, 1]

FAMILIES = {
    "gpt": ("gpt_tiny", {}),
    "llama": ("llama_tiny", {}),
    "llama_gqa": ("llama_tiny", {"num_kv_heads": 2}),
    "gpt_moe": ("gpt_tiny", {"num_experts": 2, "capacity_factor": 2.0}),
}


@pytest.fixture(scope="module")
def served(request):
    """(model, variables) per family, built once per module."""
    cache = {}

    def build(fam):
        if fam not in cache:
            name, kw = FAMILIES[fam]
            m = get_model(name, num_classes=VOCAB, scan_layers=True, **kw)
            v = m.init(jax.random.key(0),
                       np.asarray(PROMPT, np.int32)[None])
            cache[fam] = (m, v)
        return cache[fam]

    return build


def _engine(model, variables, **kw):
    base = dict(max_batch=3, page_size=4, max_pages=32,
                prompt_buckets=(8, 16), max_seq=24, seed=0)
    base.update(kw)
    return ServeEngine(model, variables["params"], **base)


# ----------------------------------------------------------------------
# Paged-vs-dense logit equivalence
# ----------------------------------------------------------------------

class TestPagedEquivalence:
    @pytest.mark.parametrize("fam", list(FAMILIES))
    def test_prefill_and_decode_match_full_forward(
            self, served, assert_within_ulps, fam):
        model, v = served(fam)
        toks = np.asarray(PROMPT, np.int32)[None]
        full = np.asarray(model.apply(v, toks, train=False))
        spec = D.spec_from_model(model)
        table = jnp.asarray(np.array([[1, 2, 3, 4]], np.int32))
        # whole-prompt prefill: the same op order over the same keys,
        # but another program (paged gather/scatter around the
        # attention), so XLA fuses it differently: read at most 4 ulps
        # of the largest logit (2.4e-07 at 0.74; gpt and gpt_moe, the
        # llama families equal), 16 allowed = 9.5e-07, under the 5e-6
        # of the incremental decode below
        kc, vc = D.init_paged_cache(spec, 8, 4)
        lg, kc, vc = D.forward_paged(
            spec, v["params"], jnp.asarray(toks), jnp.zeros(1, jnp.int32),
            jnp.array([8], jnp.int32), table, kc, vc)
        assert_within_ulps(lg, full, ulps=16)
        np.testing.assert_array_equal(np.asarray(lg).argmax(-1),
                                      full.argmax(-1))
        # prefill 4 + decode 4 single tokens: fp32 tolerance + argmax
        kc, vc = D.init_paged_cache(spec, 8, 4)
        lg4, kc, vc = D.forward_paged(
            spec, v["params"], jnp.asarray(toks[:, :4]),
            jnp.zeros(1, jnp.int32), jnp.array([4], jnp.int32), table,
            kc, vc)
        outs = [np.asarray(lg4)]
        for i in range(4, 8):
            lgi, kc, vc = D.forward_paged(
                spec, v["params"], jnp.asarray(toks[:, i:i + 1]),
                jnp.array([i], jnp.int32), jnp.array([1], jnp.int32),
                table, kc, vc)
            outs.append(np.asarray(lgi))
        inc = np.concatenate(outs, axis=1)
        np.testing.assert_allclose(inc, full, rtol=0, atol=5e-6)
        np.testing.assert_array_equal(inc.argmax(-1), full.argmax(-1))

    def test_spec_rejects_non_autoregressive_and_unscanned(self):
        bert = get_model("bert_tiny", num_classes=VOCAB, scan_layers=True)
        with pytest.raises(ValueError, match="no decode path"):
            D.spec_from_model(bert)
        unrolled = get_model("gpt_tiny", num_classes=VOCAB)
        with pytest.raises(ValueError, match="scan_layers"):
            D.spec_from_model(unrolled)


# ----------------------------------------------------------------------
# Continuous batching == single-sequence decode, per slot
# ----------------------------------------------------------------------

class TestBatchedVsSingle:
    @pytest.mark.parametrize("fam", ["gpt", "llama"])
    def test_token_streams_identical(self, served, fam):
        model, v = served(fam)
        rng = np.random.default_rng(7)
        # mixed greedy + temperature, ragged lengths, more requests than
        # slots so admissions interleave with running decodes
        reqs = [Request(rid=i,
                        prompt=rng.integers(1, VOCAB, 4 + i).tolist(),
                        max_new_tokens=5,
                        temperature=0.0 if i % 2 == 0 else 0.8)
                for i in range(5)]
        batched = ContinuousBatchingScheduler(
            _engine(model, v), eos_id=-1).run(reqs)
        assert batched["admitted"] == batched["evicted"] == 5
        by_rid = {c.rid: c.tokens for c in batched["completions"]}
        # ONE reused engine for all single runs: each run decodes over
        # recycled pages still holding the previous run's stale KV — the
        # cache-offset mask must make that invisible
        single_eng = _engine(model, v)
        for r in reqs:
            single = ContinuousBatchingScheduler(
                single_eng, eos_id=-1, max_active=1).run(
                    [Request(rid=r.rid, prompt=r.prompt, max_new_tokens=5,
                             temperature=r.temperature)])
            assert single["completions"][0].tokens == by_rid[r.rid], (
                f"rid {r.rid} (temp {r.temperature}) diverged between "
                "batched and single-sequence decode")


# ----------------------------------------------------------------------
# Page pool: recycle, occupancy accounting, admission backpressure
# ----------------------------------------------------------------------

class TestPages:
    def test_allocator_recycles_freed_pages_first(self):
        a = PageAllocator(8)        # pages 1..7
        first = a.alloc(3)
        assert first == [1, 2, 3] and a.in_use == 3
        a.free(first)
        assert a.alloc(3) == [1, 2, 3]   # literally the recycled ids
        assert a.alloc(99) is None       # over-ask leaves state intact
        assert a.in_use == 3 and a.peak_in_use == 3

    def test_allocator_guards(self):
        with pytest.raises(ValueError, match="trash page"):
            PageAllocator(1)
        a = PageAllocator(4)
        got = a.alloc(2)
        a.free(got)
        with pytest.raises(ValueError, match="double free"):
            a.free(got)
        with pytest.raises(ValueError, match="invalid page"):
            a.free([0])

    def test_scheduler_recycles_and_never_leaks(self, served):
        model, v = served("gpt")
        eng = _engine(model, v, max_batch=2, max_pages=8)
        # 2 pages/request (4 prompt + 4 new @ page_size 4); 7 free pages
        # hold 3 concurrent => the 4th request rides recycled pages
        reqs = [Request(rid=i, prompt=PROMPT[:4], max_new_tokens=4)
                for i in range(4)]
        out = ContinuousBatchingScheduler(eng, eos_id=-1).run(reqs)
        assert out["evicted"] == 4
        assert out["pages"]["leaked"] == 0
        assert out["pages"]["peak_in_use"] <= 4   # 2 slots x 2 pages
        assert out["pages"]["page_bytes"] == eng.page_bytes()
        assert eng.allocator.free_pages == 7      # all returned

    def test_admission_blocks_under_full_occupancy(self, served):
        model, v = served("gpt")
        # pool of 3 usable pages; each request needs 2 => strictly one
        # in flight, the rest wait (blocked counted, nothing fails)
        eng = _engine(model, v, max_batch=2, max_pages=4, max_seq=8,
                      prompt_buckets=(4,))
        reqs = [Request(rid=i, prompt=PROMPT[:4], max_new_tokens=4)
                for i in range(3)]
        sched = ContinuousBatchingScheduler(eng, eos_id=-1)
        out = sched.run(reqs)
        assert out["admission_blocked"] > 0
        assert out["evicted"] == 3 and out["pages"]["leaked"] == 0

    def test_oversized_request_fails_at_submit(self, served):
        model, v = served("gpt")
        eng = _engine(model, v)
        sched = ContinuousBatchingScheduler(eng)
        with pytest.raises(ValueError, match="exceeds the largest"):
            sched.run([Request(rid=0, prompt=[1] * 17, max_new_tokens=2)])
        with pytest.raises(ValueError, match="max_seq"):
            sched.run([Request(rid=0, prompt=PROMPT, max_new_tokens=100)])
        # out-of-vocab ids would silently clamp/wrap inside the gather —
        # must fail at submit instead of decoding confidently wrong
        with pytest.raises(ValueError, match="prompt ids"):
            sched.run([Request(rid=0, prompt=[1, VOCAB], max_new_tokens=2)])
        with pytest.raises(ValueError, match="prompt ids"):
            sched.run([Request(rid=0, prompt=[-3, 1], max_new_tokens=2)])


# ----------------------------------------------------------------------
# Stop conditions
# ----------------------------------------------------------------------

class TestStops:
    def test_max_token_budget_stop(self, served):
        model, v = served("gpt")
        out = ContinuousBatchingScheduler(
            _engine(model, v), eos_id=-1).run(
                [Request(rid=0, prompt=PROMPT, max_new_tokens=3)])
        c = out["completions"][0]
        assert c.reason == "length" and len(c.tokens) == 3

    def test_eos_stop(self, served):
        model, v = served("gpt")
        # learn the greedy continuation, then declare its second token
        # the EOS id — the rerun must stop there with reason "eos"
        probe = ContinuousBatchingScheduler(
            _engine(model, v), eos_id=-1).run(
                [Request(rid=0, prompt=PROMPT, max_new_tokens=4)])
        stream = probe["completions"][0].tokens
        eos = stream[1]
        out = ContinuousBatchingScheduler(
            _engine(model, v), eos_id=eos).run(
                [Request(rid=0, prompt=PROMPT, max_new_tokens=4)])
        c = out["completions"][0]
        stop = stream.index(eos)
        assert c.reason == "eos" and c.tokens == stream[:stop + 1]

    def test_request_timeout_evicts_stuck_sequence(self, served):
        # ISSUE 8 satellite: a sequence decoding past its wall-clock
        # budget is evicted (reason "timeout", counted in timed_out)
        # instead of pinning its slot + pages forever — and the freed
        # capacity admits the queue behind it (max_batch=1 forces the
        # second request to ride the eviction)
        model, v = served("gpt")
        eng = _engine(model, v, max_batch=1)
        out = ContinuousBatchingScheduler(
            eng, eos_id=-1, request_timeout=1e-6).run(
                [Request(rid=0, prompt=PROMPT[:4], max_new_tokens=8),
                 Request(rid=1, prompt=PROMPT[:4], max_new_tokens=8)])
        assert out["timed_out"] == 2 and out["evicted"] == 2
        for rid in (0, 1):
            c = out["completions"][rid]
            assert c.reason == "timeout"
            assert len(c.tokens) < 8     # cut off before its budget
        assert out["pages"]["leaked"] == 0
        assert eng.allocator.in_use == 0  # everything freed on eviction

    def test_request_timeout_off_by_default(self, served):
        model, v = served("gpt")
        out = ContinuousBatchingScheduler(
            _engine(model, v), eos_id=-1).run(
                [Request(rid=0, prompt=PROMPT[:4], max_new_tokens=3)])
        assert out["timed_out"] == 0
        assert out["completions"][0].reason == "length"


# ----------------------------------------------------------------------
# Two compiled programs: zero retraces after warmup
# ----------------------------------------------------------------------

class TestCompilePrograms:
    def test_zero_retraces_across_long_decode(self, served):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.xla_flags import (
            compile_event_counts,
            install_compile_counter,
        )
        model, v = served("gpt")
        eng = _engine(model, v, max_seq=48)
        install_compile_counter()
        # warmup: compile the one bucket this workload uses + the decode
        # step (2-token generation exercises both programs)
        ContinuousBatchingScheduler(eng, eos_id=-1).run(
            [Request(rid=100, prompt=PROMPT, max_new_tokens=2)])
        before = compile_event_counts()
        # steady state: >= 32 decode steps, fresh rids/lengths/pages —
        # the loop must re-dispatch the SAME two programs only
        out = ContinuousBatchingScheduler(eng, eos_id=-1).run(
            [Request(rid=i, prompt=PROMPT[:4 + i], max_new_tokens=36)
             for i in range(2)])
        after = compile_event_counts()
        assert out["decode_steps"] >= 32
        assert after["traces"] == before["traces"], "steady-state retrace"
        assert after["compiles"] == before["compiles"], "steady-state compile"


# ----------------------------------------------------------------------
# Checkpoint restore onto the serving mesh + manifest metadata
# ----------------------------------------------------------------------

def _worker_stacked_state(params, n):
    """A TrainState-shaped tree with every leaf worker-stacked, as the
    training checkpoints store it (worker row 0 = the served params)."""
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.train import (
        TrainState,
    )
    stack = jax.tree.map(
        lambda x: np.broadcast_to(np.asarray(x), (n, *np.shape(x))).copy(),
        params)
    # rows beyond worker 0 perturbed: restore must take row 0, not a mean
    stack = jax.tree.map(
        lambda x: np.concatenate([x[:1], x[1:] + 1.0], axis=0), stack)
    return TrainState(params=stack, batch_stats={}, opt_state={},
                      lr_epoch=np.zeros(n, np.int32),
                      rng=np.zeros((n, 2), np.uint32))


class TestCheckpointRestore:
    def test_row0_restore_across_meshes(self, served, tmp_path):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import (
            build_mesh,
        )
        model, v = served("gpt")
        n = 2
        train_mesh = build_mesh({"data": n}, devices=jax.devices()[:n])
        sharding = NamedSharding(train_mesh, P("data"))
        state = jax.tree.map(
            lambda x: jax.device_put(x, sharding),
            _worker_stacked_state(v["params"], n))
        meta = {"model": "gpt_tiny", "num_classes": VOCAB,
                "scan_layers": True, "compute_dtype": "float32",
                "num_kv_heads": 0, "num_experts": 0}
        ckpt_lib.save_checkpoint(str(tmp_path), state, 1, metadata=meta)
        # serving mesh is a DIFFERENT, single-device mesh
        serve_mesh = build_mesh({"data": 1}, devices=jax.devices()[:1])
        eng = ServeEngine.from_checkpoint(
            str(tmp_path), mesh=serve_mesh, max_batch=2, page_size=4,
            max_pages=16, prompt_buckets=(8,), max_seq=12)
        for a, b in zip(jax.tree.leaves(eng.params),
                        jax.tree.leaves(v["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        out = ContinuousBatchingScheduler(eng, eos_id=-1).run(
            [Request(rid=0, prompt=PROMPT, max_new_tokens=3)])
        # greedy decode off the restored params == full-forward argmax
        ids = list(PROMPT)
        for _ in range(3):
            lg = model.apply(v, np.asarray(ids, np.int32)[None],
                             train=False)
            ids.append(int(np.asarray(lg)[0, -1].argmax()))
        assert out["completions"][0].tokens == ids[len(PROMPT):]

    def test_resident_checkpoint_serves_from_bucket_rows(self, served,
                                                         tmp_path):
        """ISSUE 12 satellite: a scatter-resident checkpoint (params
        stored as 1/N bucket shard rows, no ``.params`` leaves) serves —
        the consensus unpacks template-free from the manifest metadata's
        ``params_leaves`` (PR 11 left a hard refusal here), bitwise the
        source params; a resident checkpoint WITHOUT the template keeps
        a clear refusal."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
            comms,
        )
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.train import (
            TrainState,
        )
        model, v = served("gpt")
        n = 2
        resident = comms.resident_from_tree(
            jax.tree.map(np.asarray, v["params"]), n)
        state = TrainState(params=None, params_resident=resident,
                           batch_stats={}, opt_state={},
                           lr_epoch=np.zeros(n, np.int32),
                           rng=np.zeros((n, 2), np.uint32))
        flat = jax.tree_util.tree_flatten_with_path(v["params"])[0]
        meta = {"model": "gpt_tiny", "num_classes": VOCAB,
                "scan_layers": True, "compute_dtype": "float32",
                "num_kv_heads": 0, "num_experts": 0,
                "param_residency": "resident", "sync_bucket_mb": 4.0,
                "params_leaves": [
                    [[str(getattr(k, "key", k)) for k in path],
                     [int(d) for d in np.shape(leaf)],
                     str(np.asarray(leaf).dtype)]
                    for path, leaf in flat]}
        ckpt_lib.save_checkpoint(str(tmp_path), state, 1, metadata=meta)
        eng = ServeEngine.from_checkpoint(
            str(tmp_path), max_batch=2, page_size=4, max_pages=16,
            prompt_buckets=(8,), max_seq=12)
        for a, b in zip(jax.tree.leaves(eng.params),
                        jax.tree.leaves(v["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        out = ContinuousBatchingScheduler(eng, eos_id=-1).run(
            [Request(rid=0, prompt=PROMPT, max_new_tokens=3)])
        ids = list(PROMPT)
        for _ in range(3):
            lg = model.apply(v, np.asarray(ids, np.int32)[None],
                             train=False)
            ids.append(int(np.asarray(lg)[0, -1].argmax()))
        assert out["completions"][0].tokens == ids[len(PROMPT):]
        # a pre-ISSUE-12 resident checkpoint (no params_leaves) still
        # refuses with instructions instead of crashing
        legacy = dict(meta)
        legacy.pop("params_leaves")
        old = tmp_path / "legacy"
        ckpt_lib.save_checkpoint(str(old), state, 1, metadata=legacy)
        with pytest.raises(ValueError, match="params_leaves"):
            ServeEngine.from_checkpoint(str(old))

    def test_manifest_metadata_roundtrip_and_absence(self, served,
                                                     tmp_path):
        model, v = served("gpt")
        state = _worker_stacked_state(v["params"], 1)
        meta = {"model": "gpt_tiny", "num_classes": VOCAB,
                "scan_layers": True}
        ckpt_lib.save_checkpoint(str(tmp_path), state, 3, metadata=meta)
        # epoch dir and checkpoint root both resolve
        assert ckpt_lib.manifest_metadata(
            str(tmp_path / "ckpt_3")) == meta
        assert ckpt_lib.manifest_metadata(str(tmp_path)) == meta
        # a metadata-less save reads back {} (pre-metadata engines)
        bare = tmp_path / "bare"
        ckpt_lib.save_checkpoint(str(bare), state, 1)
        assert ckpt_lib.manifest_metadata(str(bare)) == {}
        assert ckpt_lib.manifest_metadata(str(tmp_path / "nope")) == {}
        with pytest.raises(ValueError, match="no serve metadata"):
            ServeEngine.from_checkpoint(str(bare))
        # metadata-less + an EXPLICIT --model: the CLI fallback rebuilds
        # the arch with num_classes recovered from the manifest leaves
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve.api import (
            run_serve,
        )
        cfg = Config(model="gpt_tiny", checkpoint_dir=str(bare),
                     serve_prompt="5,9,3", serve_requests=1,
                     serve_max_new_tokens=2, serve_max_batch=2,
                     serve_page_size=8, serve_max_pages=16,
                     serve_prompt_buckets="8")
        with pytest.raises(ValueError, match="no serve metadata"):
            run_serve(cfg, model_flag_given=False)
        out = run_serve(cfg, model_flag_given=True)
        assert out["engine"].spec.vocab == VOCAB
        assert len(out["completions"][0].tokens) == 2

    def test_model_from_metadata_guards(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve.engine import (
            model_from_metadata,
        )
        with pytest.raises(ValueError, match="autoregressive"):
            model_from_metadata({"model": "bert_tiny",
                                 "scan_layers": True, "num_classes": 10})
        with pytest.raises(ValueError, match="layer_scan"):
            model_from_metadata({"model": "gpt_tiny",
                                 "scan_layers": False, "num_classes": 10})
        m = model_from_metadata({"model": "llama_tiny",
                                 "scan_layers": True, "num_classes": VOCAB,
                                 "num_kv_heads": 2})
        assert type(m).__name__ == "LlamaForCausalLM"
        assert m.num_kv_heads == 2 and m.scan_layers


# ----------------------------------------------------------------------
# Batching helpers (the eval/serve shared padding satellite)
# ----------------------------------------------------------------------

class TestBatchingHelpers:
    def test_pad_to_batches_masks_tail(self):
        x = np.arange(10, dtype=np.float32)[:, None]
        y = np.arange(10, dtype=np.int32)
        xs, ys, m = pad_to_batches(x, y, 4)
        assert xs.shape == (3, 4, 1) and m.shape == (3, 4)
        assert m.sum() == 10 and m[2].tolist() == [1.0, 1.0, 0.0, 0.0]
        # padding repeats the final real example (in-domain values)
        assert ys[2].tolist() == [8, 9, 9, 9]
        with pytest.raises(ValueError):
            pad_to_batches(x[:0], y[:0], 4)

    def test_pick_and_pad_bucket(self):
        assert pick_bucket(5, (8, 16)) == 8
        assert pick_bucket(8, (8, 16)) == 8
        assert pick_bucket(9, (8, 16)) == 16
        with pytest.raises(ValueError, match="largest bucket"):
            pick_bucket(17, (8, 16))
        padded = pad_to_bucket(np.array([3, 1, 4]), 8)
        assert padded.tolist() == [3, 1, 4, 0, 0, 0, 0, 0]
        with pytest.raises(ValueError):
            pad_to_bucket(np.array([1] * 9), 8)


# ----------------------------------------------------------------------
# End-to-end: train -> checkpoint -> serve (the full driver path)
# ----------------------------------------------------------------------

@pytest.mark.slow
class TestServeEndToEnd:
    def test_train_checkpoint_serve_greedy_matches_argmax(self, tmp_path):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import (
            train_global,
        )
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve.api import (
            run_serve,
        )
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.train import (
            rank0_variables,
        )
        cfg = Config(model="gpt_tiny", dataset="synthetic_lm",
                     epochs_global=1, epochs_local=1, batch_size=8,
                     limit_train_samples=64, limit_eval_samples=16,
                     compute_dtype="float32", augment=False,
                     aggregation_by="weights", checkpoint_dir=str(tmp_path),
                     checkpoint_every=1, seed=3)
        res = train_global(cfg, progress=False)
        out = run_serve(cfg.replace(
            serve_prompt="5,9,3,7,2", serve_requests=2,
            serve_max_new_tokens=4, serve_max_batch=2, serve_page_size=8,
            serve_max_pages=16, serve_prompt_buckets="8"))
        v = rank0_variables(res["state"])
        ids = [5, 9, 3, 7, 2]
        for _ in range(4):
            lg = res["model"].apply(v, np.asarray(ids, np.int32)[None],
                                    train=False)
            ids.append(int(np.asarray(lg)[0, -1].argmax()))
        for c in out["completions"]:
            assert c.tokens == ids[5:]
        tele = out["serve"]
        assert tele["tokens_generated"] == 8
        assert tele["pages"]["leaked"] == 0


# ----------------------------------------------------------------------
# PR 17: paged prefix cache — content keys + refcounted allocator
# ----------------------------------------------------------------------

class TestPrefixKeys:
    def test_rolling_hash_keys_whole_prefix(self):
        keys = page_prefix_keys(PROMPT, 4)
        assert len(keys) == 2            # two FULL pages of 4
        assert page_prefix_keys(PROMPT[:7], 4) == keys[:1]  # partial page
        # unshared pages: same page-1 tokens but a different page 0 must
        # change BOTH keys — the hash rolls over the whole prefix, not
        # the page in isolation (position safety of the shared pages)
        other = [1, 1, 1, 1] + PROMPT[4:]
        assert page_prefix_keys(other, 4)[0] != keys[0]
        assert page_prefix_keys(other, 4)[1] != keys[1]
        # shared prefix, divergent tail: first key equal, second differs
        fork = PROMPT[:4] + [2, 2, 2, 2]
        assert page_prefix_keys(fork, 4)[0] == keys[0]
        assert page_prefix_keys(fork, 4)[1] != keys[1]

    def test_refcount_lifecycle(self):
        a = PageAllocator(8)
        p0, p1 = a.alloc(2)
        a.register(b"k0", p0)
        a.claim(p0)                      # a second sequence shares p0
        assert a.refcount(p0) == 2 and a.in_use == 2
        a.free([p0, p1])                 # first owner exits
        assert a.refcount(p0) == 1       # still referenced — not cached
        assert a.cached_pages == 0 and a.in_use == 1
        a.free([p0])                     # last reference drops
        assert a.in_use == 0 and a.cached_pages == 1
        assert a.lookup([b"k0"]) == [p0]         # retained, KV intact
        with pytest.raises(ValueError, match="double free"):
            a.free([p0])                 # cached != free: still guarded
        a.claim(p0)                      # resurrect off the LRU
        assert a.cached_pages == 0 and a.refcount(p0) == 1
        with pytest.raises(ValueError, match="no live reference"):
            a.register(b"kX", p1)        # p1 went back to the free list
        with pytest.raises(ValueError, match="neither"):
            a.claim(7)                   # never allocated
        # identity the telemetry gates on, at every state above
        assert a.in_use + a.cached_pages + a.free_pages == 7

    def test_lru_eviction_oldest_first_and_first_writer_wins(self):
        a = PageAllocator(5)             # pages 1..4
        pages = a.alloc(3)               # [1, 2, 3]
        for i, p in enumerate(pages):
            a.register(bytes([i]), p)
        a.free(pages)                    # all three park on the LRU
        assert a.cached_pages == 3 and a.free_pages == 1
        # a 3-page ask: free list first (page 4), then evict the two
        # OLDEST cached pages — their keys die, the newest survives
        got = a.alloc(3)
        assert got == [4, 1, 2] and a.cache_evictions == 2
        assert a.lookup([bytes(), bytes([0])]) == []
        assert a.lookup([bytes([2])]) == [3]
        # first writer wins: key 2 is taken, and got[0] can carry only
        # one key ever
        assert a.register(bytes([2]), got[0]) is False
        assert a.register(bytes([9]), got[0]) is True
        assert a.register(bytes([10]), got[0]) is False

    def test_lookup_stops_at_first_miss(self):
        a = PageAllocator(8)
        pages = a.alloc(3)
        a.register(b"a", pages[0])
        a.register(b"c", pages[2])
        # consecutive-run semantics: a hole at key 1 hides page 2 even
        # though its key is indexed (its CONTENT depends on pages 0-1)
        assert a.lookup([b"a", b"b", b"c"]) == [pages[0]]


class _AuditAllocator(PageAllocator):
    """PageAllocator that re-checks the sharing invariants on every
    operation: a page is never handed out while referenced, refcounts
    mirror the claim/free history exactly, and the occupancy identity
    ``in_use + cached + free == usable`` never breaks."""

    def __init__(self, max_pages):
        super().__init__(max_pages)
        self.shadow: dict = {}
        self.ops = 0

    def _check(self):
        self.ops += 1
        live = {p for p, r in self.shadow.items() if r > 0}
        assert len(live) == self.in_use, "in_use drifted from refcounts"
        for p, r in self.shadow.items():
            assert self.refcount(p) == r, f"page {p} refcount drifted"
        assert (self.in_use + self.cached_pages + self.free_pages
                == self.max_pages - 1), "occupancy identity broke"

    def alloc(self, count):
        got = super().alloc(count)
        if got is not None:
            for p in got:
                assert self.shadow.get(p, 0) == 0, (
                    f"page {p} recycled while referenced")
                self.shadow[p] = 1
        self._check()
        return got

    def free(self, pages):
        super().free(pages)              # double-free raises in the base
        for p in pages:
            self.shadow[p] -= 1
        self._check()

    def claim(self, page):
        super().claim(page)
        self.shadow[page] = self.shadow.get(page, 0) + 1
        self._check()


class TestPrefixCache:
    @pytest.mark.parametrize("fam", ["gpt", "llama", "llama_gqa"])
    def test_hit_decode_trajectory_bitwise_vs_cold_twin(self, served, fam):
        model, v = served(fam)
        reqs = [Request(rid=i, prompt=PROMPT, max_new_tokens=6,
                        temperature=0.0 if i == 0 else 0.8)
                for i in range(2)]
        # the cold twin: same engine config, cache OFF
        cold = ContinuousBatchingScheduler(_engine(model, v)).run(
            [Request(**dataclasses.asdict(r)) for r in reqs])
        eng = _engine(model, v, prefix_cache=True)
        sched = ContinuousBatchingScheduler(eng)
        warm = sched.run(reqs)
        for cc, cw in zip(cold["completions"], warm["completions"]):
            assert cw.tokens == cc.tokens, (
                f"rid {cw.rid}: prefix-hit trajectory diverged from the "
                "cold twin")
        # rid 0 was cold (2 prompt pages, 0 hits), rid 1 hit the one
        # shareable page ((plen-1)//page_size caps the reuse at 1)
        assert warm["page_reuse_ratio"] == pytest.approx(1 / 4)
        assert warm["prefill_tokens_saved"] == 4
        assert warm["pages"]["leaked"] == 0
        assert warm["pages"]["cached_pages"] > 0
        assert cold["page_reuse_ratio"] == 0.0

    def test_shared_system_prompt_reuse_ratio(self, served):
        model, v = served("gpt")
        rng = np.random.default_rng(11)
        sys_prefix = rng.integers(1, VOCAB, 8).tolist()
        reqs = [Request(rid=i,
                        prompt=sys_prefix + rng.integers(
                            1, VOCAB, 4).tolist(),
                        max_new_tokens=4)
                for i in range(4)]
        eng = _engine(model, v, prefix_cache=True)
        out = ContinuousBatchingScheduler(eng).run(
            [Request(**dataclasses.asdict(r)) for r in reqs])
        # 12-token prompts: 3 prompt pages each, the 2 sys-prefix pages
        # shareable; request 0 pays them cold, 1..3 hit both
        assert out["page_reuse_ratio"] == pytest.approx(6 / 12)
        assert out["prefill_tokens_saved"] == 3 * 8
        assert out["pages"]["leaked"] == 0
        # every stream still equals its solo cold run (one plain engine,
        # reused: streams are batch- and cache-independent by design)
        plain = _engine(model, v)
        for r in reqs:
            solo = ContinuousBatchingScheduler(plain, max_active=1).run(
                [Request(rid=r.rid, prompt=r.prompt, max_new_tokens=4)])
            got = next(c for c in out["completions"] if c.rid == r.rid)
            assert got.tokens == solo["completions"][0].tokens

    def test_page_never_recycled_while_referenced_property(self, served):
        """Property-style sweep of the refcount invariants: shared
        prefixes + a pool tight enough to force LRU evictions and
        admission backpressure, plus timeout and EOS evictions — every
        allocator operation re-audited (never recycled while referenced,
        never double-freed, occupancy identity byte-exact)."""
        model, v = served("gpt")
        rng = np.random.default_rng(23)
        sys_prefix = rng.integers(1, VOCAB, 8).tolist()

        def mk(rid, tail, new=4):
            return Request(rid=rid,
                           prompt=sys_prefix + rng.integers(
                               1, VOCAB, tail).tolist(),
                           max_new_tokens=new)

        eng = _engine(model, v, prefix_cache=True, prefill_chunk=4,
                      max_pages=14)
        eng.allocator = _AuditAllocator(14)
        sched = ContinuousBatchingScheduler(eng)
        out = sched.run([mk(i, 1 + (i % 5)) for i in range(8)])
        assert out["page_reuse_ratio"] > 0
        assert out["pages"]["peak_bytes"] == (
            out["pages"]["peak_in_use"] * eng.page_bytes())
        # timeout evictions (possibly mid-prefill) release cleanly too
        out2 = ContinuousBatchingScheduler(
            eng, request_timeout=1e-6).run(
                [mk(100 + i, 3, new=8) for i in range(4)])
        assert out2["timed_out"] == 4
        # EOS on the very first token exercises the admission-time finish
        eos = out["completions"][0].tokens[0]
        ContinuousBatchingScheduler(eng, eos_id=eos).run(
            [mk(200 + i, 1 + (i % 5)) for i in range(4)])
        assert eng.allocator.in_use == 0, "references leaked"
        assert eng.allocator.ops > 50
        assert out["pages"]["leaked"] == 0 and out2["pages"]["leaked"] == 0

    def test_zero_retraces_with_prefix_hits(self, served):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.xla_flags import (
            compile_event_counts,
            install_compile_counter,
        )
        model, v = served("gpt")
        eng = _engine(model, v, prefix_cache=True, max_seq=48)
        install_compile_counter()
        rng = np.random.default_rng(5)
        long_prompt = rng.integers(1, VOCAB, 16).tolist()
        # warmup covers both buckets AND the hit path (rerunning PROMPT
        # prefills only its tail, at a smaller effective length)
        sched = ContinuousBatchingScheduler(eng)
        sched.run([Request(rid=100, prompt=PROMPT, max_new_tokens=2),
                   Request(rid=101, prompt=long_prompt, max_new_tokens=2)])
        ContinuousBatchingScheduler(eng).run(
            [Request(rid=102, prompt=PROMPT, max_new_tokens=2)])
        before = compile_event_counts()
        # steady state: full hits, partial hits, and cold prompts
        out = ContinuousBatchingScheduler(eng).run(
            [Request(rid=0, prompt=PROMPT, max_new_tokens=8),
             Request(rid=1, prompt=PROMPT[:4] + [13, 17, 19, 23, 29, 31,
                                                 37, 41],
                     max_new_tokens=8),
             Request(rid=2, prompt=rng.integers(1, VOCAB, 16).tolist(),
                     max_new_tokens=8)])
        after = compile_event_counts()
        assert out["page_reuse_ratio"] > 0
        assert after["traces"] == before["traces"], "hit-path retrace"
        assert after["compiles"] == before["compiles"], "hit-path compile"

    def test_engine_headroom_guard(self, served):
        model, v = served("gpt")
        # max_seq 24 @ page_size 4 = 6 pages/sequence; 7 pages in the
        # pool leave 6 usable — one sequence pins everything, nothing
        # could ever stay cached
        with pytest.raises(ValueError, match="headroom"):
            _engine(model, v, prefix_cache=True, max_pages=7)
        _engine(model, v, prefix_cache=True, max_pages=8)   # fits


# ----------------------------------------------------------------------
# PR 17: chunked prefill — one [1, C] program interleaved into decode
# ----------------------------------------------------------------------

class TestChunkedPrefill:
    @pytest.mark.parametrize("fam", ["gpt", "llama", "llama_gqa"])
    @pytest.mark.parametrize("chunk", [4, 8])
    def test_logits_and_cache_match_monolithic(
            self, served, assert_within_ulps, fam, chunk):
        model, v = served(fam)
        prompt = np.asarray(PROMPT + [6, 2, 8, 3], np.int32)   # 12 tokens
        kw = dict(prompt_buckets=(16,), max_seq=16)
        em = _engine(model, v, **kw)
        ec = _engine(model, v, prefill_chunk=chunk, **kw)
        row_m = em.table_row(em.allocator.alloc(em.pages_for(16)))
        row_c = ec.table_row(ec.allocator.alloc(ec.pages_for(16)))
        tok_m, lg_m = em.prefill(prompt, row_m, 0.0, 7)
        tok_c = lg_c = None
        for s in range(0, len(prompt), chunk):
            tok_c, lg_c = ec.prefill_chunk_step(
                prompt[s:s + chunk], s, row_c, 0.0, 7)
        # the emitted token is exact.  Logits and the sequence's written
        # pages are the monolithic prefill's to float32 rounding: a
        # chunk attends over [chunk, span] scores where the monolithic
        # program has [bucket, bucket], two program shapes.  Read: gpt
        # and llama equal; llama_gqa at most 4 ulps of the largest logit
        # (1.2e-07 at 0.49) and 1.75 of the largest cache entry
        # (1.0e-07 at 0.65); 16 allowed.  (Page 0 is the trash page:
        # bucket padding scribbles there, chunk-aligned spans don't, and
        # decode never reads it.)
        assert tok_c == tok_m
        assert_within_ulps(lg_c, lg_m, ulps=16)
        assert_within_ulps(np.asarray(ec.kcache)[:, 1:5],
                           np.asarray(em.kcache)[:, 1:5], ulps=16)
        assert_within_ulps(np.asarray(ec.vcache)[:, 1:5],
                           np.asarray(em.vcache)[:, 1:5], ulps=16)
        assert ec.compiled_buckets == []   # no bucket ever specialized

    def test_streams_identical_and_chunk_counts(self, served):
        model, v = served("gpt")
        rng = np.random.default_rng(3)
        reqs = [Request(rid=i,
                        prompt=rng.integers(1, VOCAB, 5 + 3 * i).tolist(),
                        max_new_tokens=5,
                        temperature=0.0 if i % 2 == 0 else 0.7)
                for i in range(4)]                 # lengths 5, 8, 11, 14
        mono = ContinuousBatchingScheduler(_engine(model, v)).run(
            [Request(**dataclasses.asdict(r)) for r in reqs])
        chk = ContinuousBatchingScheduler(
            _engine(model, v, prefill_chunk=4)).run(reqs)
        assert ([c.tokens for c in chk["completions"]]
                == [c.tokens for c in mono["completions"]])
        # ceil(plen / 4) chunks per prompt: 2 + 2 + 3 + 4
        assert chk["prefill_chunks"] == 11
        assert mono["prefill_chunks"] == 0
        assert chk["prefill_buckets"] == []
        assert chk["pages"]["leaked"] == 0

    def test_chunks_interleave_with_running_decode(self, served):
        model, v = served("gpt")
        eng = _engine(model, v, prefill_chunk=4)
        calls = []
        orig_chunk, orig_decode = eng.prefill_chunk_step, eng.decode
        eng.prefill_chunk_step = (
            lambda *a, **k: (calls.append("chunk"),
                             orig_chunk(*a, **k))[1])
        eng.decode = (
            lambda *a, **k: (calls.append("decode"),
                             orig_decode(*a, **k))[1])
        out = ContinuousBatchingScheduler(eng).run(
            [Request(rid=0, prompt=PROMPT[:4], max_new_tokens=12),
             Request(rid=1, prompt=PROMPT * 2, max_new_tokens=2)])
        # the 16-token prompt prefills one chunk per scheduler tick WHILE
        # rid 0 keeps decoding: some decode call lands strictly between
        # two chunk calls instead of the monolithic stall
        first, last = calls.index("chunk"), len(calls) - 1 - calls[
            ::-1].index("chunk")
        assert "decode" in calls[first:last], (
            f"prefill was not interleaved with decode: {calls}")
        assert out["pages"]["leaked"] == 0
        # the short stream is unperturbed by the long prefill riding along
        solo = ContinuousBatchingScheduler(
            _engine(model, v, prefill_chunk=4)).run(
                [Request(rid=0, prompt=PROMPT[:4], max_new_tokens=12)])
        assert (next(c for c in out["completions"] if c.rid == 0).tokens
                == solo["completions"][0].tokens)

    def test_prompt_beyond_largest_bucket_admits(self, served):
        model, v = served("gpt")
        long_prompt = (PROMPT * 3)[:18]            # 18 > largest bucket 16
        with pytest.raises(ValueError, match="exceeds the largest"):
            ContinuousBatchingScheduler(_engine(model, v)).run(
                [Request(rid=0, prompt=long_prompt, max_new_tokens=2)])
        out = ContinuousBatchingScheduler(
            _engine(model, v, prefill_chunk=4)).run(
                [Request(rid=0, prompt=long_prompt, max_new_tokens=2)])
        c = out["completions"][0]
        assert c.reason == "length" and len(c.tokens) == 2
        assert out["prefill_chunks"] == 5          # ceil(18 / 4)

    def test_zero_retraces_chunked(self, served):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.xla_flags import (
            compile_event_counts,
            install_compile_counter,
        )
        model, v = served("gpt")
        eng = _engine(model, v, prefill_chunk=4, max_seq=48)
        install_compile_counter()
        # ONE warm request (2 chunks) compiles the chunk program + decode
        ContinuousBatchingScheduler(eng).run(
            [Request(rid=100, prompt=PROMPT, max_new_tokens=2)])
        before = compile_event_counts()
        rng = np.random.default_rng(9)
        out = ContinuousBatchingScheduler(eng).run(
            [Request(rid=i, prompt=rng.integers(
                1, VOCAB, 3 + 5 * i).tolist(), max_new_tokens=35)
             for i in range(3)])                   # lengths 3, 8, 13
        after = compile_event_counts()
        assert out["decode_steps"] >= 32
        assert after["traces"] == before["traces"], (
            "chunked steady-state retrace — the [1, C] program must "
            "cover every prompt length")
        assert after["compiles"] == before["compiles"]

    def test_engine_rejects_non_page_multiple_chunk(self, served):
        model, v = served("gpt")
        with pytest.raises(ValueError, match="multiple of page_size"):
            _engine(model, v, prefill_chunk=3)
        with pytest.raises(ValueError, match="multiple of page_size"):
            _engine(model, v, prefill_chunk=-4)


# ----------------------------------------------------------------------
# PR 17 satellites: latency split + eager config validation
# ----------------------------------------------------------------------

class TestLatencyTelemetry:
    def test_ttft_split_from_decode_gaps(self, served):
        model, v = served("gpt")
        out = ContinuousBatchingScheduler(_engine(model, v)).run(
            [Request(rid=0, prompt=PROMPT, max_new_tokens=5)])
        c = out["completions"][0]
        assert c.ttft_s is not None and c.ttft_s > 0
        # the first token's wall (prefill included) is NOT a decode gap
        assert len(c.decode_latencies_s) == len(c.tokens) - 1
        for key in ("p50", "p99", "mean"):
            assert out["ttft_ms"][key] > 0
            assert out["latency_ms"][key] > 0

    def test_zero_filled_schema_on_empty_run(self, served):
        model, v = served("gpt")
        out = ContinuousBatchingScheduler(_engine(model, v)).run([])
        zero = {"p50": 0.0, "p99": 0.0, "mean": 0.0}
        assert out["latency_ms"] == zero
        assert out["ttft_ms"] == zero
        assert out["page_reuse_ratio"] == 0.0
        assert out["prefill_tokens_saved"] == 0
        assert out["prefill_chunks"] == 0
        assert out["tokens_per_s"] == 0.0


class TestServeFastPathConfig:
    def test_chunk_must_be_positive_page_multiple(self):
        with pytest.raises(ValueError, match="positive multiple"):
            Config(serve_prefill_chunk=5)
        with pytest.raises(ValueError, match="positive multiple"):
            Config(serve_prefill_chunk=-16)
        with pytest.raises(ValueError, match="positive multiple"):
            Config(serve_prefill_chunk=24, serve_page_size=16)
        assert Config(serve_prefill_chunk=32).serve_prefill_chunk == 32
        assert Config(serve_prefill_chunk=24,
                      serve_page_size=8).serve_prefill_chunk == 24

    def test_prefix_cache_needs_pool_headroom(self):
        # default buckets 16,64 + 16 new tokens = 80-token sequences =
        # 5 pages @ page_size 16: a 6-page pool (5 usable) is pinned
        # whole by one sequence — rejected with the real reason
        with pytest.raises(ValueError, match="headroom"):
            Config(serve_prefix_cache=True, serve_max_pages=6)
        cfg = Config(serve_prefix_cache=True, serve_max_pages=7)
        assert cfg.serve_prefix_cache

    def test_fast_path_flags_rejected_outside_serve_mode(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import (
            train_global,
        )
        for kw in (dict(serve_prefix_cache=True),
                   dict(serve_prefill_chunk=16),
                   dict(serve_draft_ckpt="/tmp/x", serve_spec_tokens=4)):
            with pytest.raises(ValueError, match="serving fast path"):
                train_global(Config(**kw))

    def test_spec_flags_required_together(self):
        with pytest.raises(ValueError, match="TOGETHER"):
            Config(serve_draft_ckpt="/tmp/x")
        with pytest.raises(ValueError, match="TOGETHER"):
            Config(serve_spec_tokens=4)
        cfg = Config(serve_draft_ckpt="/tmp/x", serve_spec_tokens=4)
        assert cfg.serve_spec_tokens == 4

    def test_spec_rejects_temperature(self):
        # eager v1 rejection with the real reason: greedy argmax
        # acceptance only — the stochastic rejection-sampling rule is
        # not implemented
        with pytest.raises(ValueError, match="rejection-sampling"):
            Config(serve_draft_ckpt="/tmp/x", serve_spec_tokens=4,
                   serve_temperature=0.8)

    def test_spec_prefix_cache_headroom_counts_spec_tokens(self):
        # the verify program overshoots k positions past max_new, so the
        # headroom math must include them: 7 pages pass without spec
        # (80-token sequences = 5 pages) but 16 spec tokens push a
        # sequence to 96 tokens = 6 pages == the 6 usable — rejected
        Config(serve_prefix_cache=True, serve_max_pages=7)
        with pytest.raises(ValueError, match="serve_spec_tokens"):
            Config(serve_prefix_cache=True, serve_max_pages=7,
                   serve_draft_ckpt="/tmp/x", serve_spec_tokens=16)


# ----------------------------------------------------------------------
# ISSUE 18: speculative decoding — draft pool + fused verify
# ----------------------------------------------------------------------

def _spec_pair(model, tv, draft_model, dv, k, **kw):
    """(target engine paired with a draft, twin plain engine) sharing
    one geometry."""
    draft = _engine(draft_model, dv, **kw)
    eng = ServeEngine(model, tv["params"], draft=draft, spec_tokens=k,
                      **{**dict(max_batch=3, page_size=4, max_pages=32,
                                prompt_buckets=(8, 16), max_seq=24,
                                seed=0), **kw})
    return eng


class TestSpeculativeAccept:
    """Device accept math vs a plain-python reference."""

    def _ref(self, logits, draft):
        b, k = draft.shape
        tgt = logits.argmax(-1)
        out_e = np.full((b, k), -1, np.int32)
        out_a = np.zeros(b, np.int32)
        for i in range(b):
            n = 0
            while n < k and draft[i, n] == tgt[i, n]:
                n += 1
            acc = min(n, k - 1)
            out_a[i] = acc
            out_e[i, :acc] = draft[i, :acc]
            out_e[i, acc] = tgt[i, acc]
        return out_e, out_a

    @pytest.mark.parametrize("k", [1, 2, 4])
    def test_matches_reference(self, k):
        rng = np.random.default_rng(5)
        logits = rng.standard_normal((6, k + 1, 13)).astype(np.float32)
        draft = rng.integers(0, 13, (6, k)).astype(np.int32)
        # row 0: force full acceptance to exercise the k-1 cap; row 1:
        # force total rejection (first draft wrong)
        full = logits[0].argmax(-1)
        draft[0] = full[:k]
        draft[1, 0] = (logits[1, 0].argmax() + 1) % 13
        emitted, acc = D.speculative_accept(jnp.asarray(logits),
                                            jnp.asarray(draft))
        ref_e, ref_a = self._ref(logits, draft)
        np.testing.assert_array_equal(np.asarray(acc), ref_a)
        np.testing.assert_array_equal(np.asarray(emitted), ref_e)
        assert int(acc[0]) == k - 1          # cap engaged
        assert int(acc[1]) == 0              # burst collapses to bonus

    def test_cap_emits_identical_stream(self):
        # when every draft matches, the bonus token t_{k-1} IS d_k: the
        # capped burst d_1..d_{k-1}, t_{k-1} equals d_1..d_k — capping
        # costs nothing, ever
        rng = np.random.default_rng(7)
        logits = rng.standard_normal((2, 5, 11)).astype(np.float32)
        tgt = logits.argmax(-1)
        draft = tgt[:, :4].astype(np.int32)
        emitted, acc = D.speculative_accept(jnp.asarray(logits),
                                            jnp.asarray(draft))
        np.testing.assert_array_equal(np.asarray(emitted), draft)


class TestSpeculative:
    # tier-1 keeps the trickiest cell (GQA at the full k=4 burst); the
    # rest of the 3x2 matrix runs in the slow tier on the 1-core CI
    # host — gpt k=2 bitwise coverage also rides tier-1 through the
    # batched-vs-single and zero-retrace tests below
    @pytest.mark.parametrize("fam,k", [
        ("llama_gqa", 4),
        pytest.param("gpt", 2, marks=pytest.mark.slow),
        pytest.param("llama", 2, marks=pytest.mark.slow),
        pytest.param("llama_gqa", 2, marks=pytest.mark.slow),
        pytest.param("gpt", 4, marks=pytest.mark.slow),
        pytest.param("llama", 4, marks=pytest.mark.slow),
    ])
    def test_bitwise_vs_nonspeculative_twin(self, served, fam, k):
        """THE gate: greedy speculative output is bitwise the twin's —
        the draft (same family, independently initialized, so real
        disagreement) only ever changes WHEN tokens appear, never WHICH."""
        model, v = served(fam)
        name, mkw = FAMILIES[fam]
        draft_model = get_model(name, num_classes=VOCAB, scan_layers=True,
                                **mkw)
        dv = draft_model.init(jax.random.key(99),
                              np.asarray(PROMPT, np.int32)[None])
        reqs = lambda: [Request(rid=i, prompt=PROMPT[:4 + 2 * i],  # noqa: E731
                                max_new_tokens=6) for i in range(3)]
        twin = ContinuousBatchingScheduler(
            _engine(model, v), eos_id=-1).run(reqs())
        eng = _spec_pair(model, v, draft_model, dv, k)
        out = ContinuousBatchingScheduler(eng, eos_id=-1).run(reqs())
        assert ([c.tokens for c in out["completions"]]
                == [c.tokens for c in twin["completions"]]), (
            f"{fam} k={k}: speculative stream diverged from the twin")
        assert out["spec"]["verify_steps"] > 0
        assert out["spec"]["draft_steps"] == k * out["spec"]["verify_steps"]
        assert out["pages"]["leaked"] == 0
        assert out["pages"]["draft_leaked"] == 0

    def test_composes_with_prefix_cache_and_chunked(self, served):
        """All three fast-path features at once — warm prefix hits +
        chunked prefill + speculation — still bitwise, in both pools."""
        model, v = served("gpt")
        draft_model = get_model("gpt_tiny", num_classes=VOCAB,
                                scan_layers=True)
        dv = draft_model.init(jax.random.key(99),
                              np.asarray(PROMPT, np.int32)[None])
        kw = dict(max_pages=48, prefix_cache=True, prefill_chunk=4)
        reqs = lambda: [Request(rid=i, prompt=PROMPT, max_new_tokens=6)  # noqa: E731
                        for i in range(2)]
        twin = ContinuousBatchingScheduler(
            _engine(model, v), eos_id=-1).run(reqs())
        base = [c.tokens for c in twin["completions"]]
        eng = _spec_pair(model, v, draft_model, dv, 4, **kw)
        cold = ContinuousBatchingScheduler(eng, eos_id=-1).run(reqs())
        warm = ContinuousBatchingScheduler(eng, eos_id=-1).run(reqs())
        assert [c.tokens for c in cold["completions"]] == base
        assert [c.tokens for c in warm["completions"]] == base
        assert warm["page_reuse_ratio"] > 0    # the hits really happened
        assert warm["prefill_chunks"] > 0
        assert warm["pages"]["leaked"] == 0
        assert warm["pages"]["draft_leaked"] == 0

    def test_batched_vs_single_speculative(self, served):
        """PR 7 gate extended: a slot's ACCEPTED tokens are independent
        of its batch neighbors (greedy end-to-end, and the verify's
        per-row masking keeps inactive rows out of every gather)."""
        model, v = served("gpt")
        draft_model = get_model("gpt_tiny", num_classes=VOCAB,
                                scan_layers=True)
        dv = draft_model.init(jax.random.key(99),
                              np.asarray(PROMPT, np.int32)[None])
        reqs = [Request(rid=i, prompt=PROMPT[:3 + i], max_new_tokens=5)
                for i in range(3)]
        eng = _spec_pair(model, v, draft_model, dv, 2)
        batched = ContinuousBatchingScheduler(eng, eos_id=-1).run(reqs)
        by_rid = {c.rid: c.tokens for c in batched["completions"]}
        # the same engine pair serves the single-slot runs: engines are
        # stateless between scheduler runs, and reusing the compiled
        # programs keeps this in the tier-1 budget on a 1-core host
        for r in reqs:
            single = ContinuousBatchingScheduler(
                eng, eos_id=-1, max_active=1).run(
                    [Request(rid=r.rid, prompt=r.prompt,
                             max_new_tokens=5)])
            assert single["completions"][0].tokens == by_rid[r.rid], (
                f"rid {r.rid} diverged between batched and single "
                "speculative decode")

    def test_self_similar_deterministic_acceptance(self, served):
        """Draft sharing the target's params accepts every proposal:
        acceptance pins at (k-1)/k (the cap) and target steps per
        emitted token at 1/k: counts, so the same on every backend."""
        model, v = served("gpt")
        k = 4
        draft = _engine(model, v, max_seq=32)
        eng = ServeEngine(model, v["params"], draft=draft, spec_tokens=k,
                          max_batch=3, page_size=4, max_pages=32,
                          prompt_buckets=(8, 16), max_seq=32, seed=0)
        # 17 = 1 prefill token + 16 speculative = exactly 4 full bursts
        out = ContinuousBatchingScheduler(eng, eos_id=-1).run(
            [Request(rid=i, prompt=PROMPT, max_new_tokens=17)
             for i in range(2)])
        assert out["spec"]["acceptance_rate"] == (k - 1) / k
        assert out["spec"]["target_steps_per_token"] == 1 / k
        twin = ContinuousBatchingScheduler(
            _engine(model, v, max_seq=32), eos_id=-1).run(
            [Request(rid=i, prompt=PROMPT, max_new_tokens=17)
             for i in range(2)])
        assert ([c.tokens for c in out["completions"]]
                == [c.tokens for c in twin["completions"]])

    def test_eos_truncates_burst_like_twin(self, served):
        """An eos landing mid-burst must cut the stream exactly where
        the twin stops — committed one token at a time, the tail of the
        burst is discarded."""
        model, v = served("gpt")
        probe = ContinuousBatchingScheduler(
            _engine(model, v), eos_id=-1).run(
                [Request(rid=0, prompt=PROMPT, max_new_tokens=6)])
        stream = probe["completions"][0].tokens
        eos = stream[2]     # third token: lands mid-burst at k=4
        draft = _engine(model, v)
        eng = ServeEngine(model, v["params"], draft=draft, spec_tokens=4,
                          max_batch=3, page_size=4, max_pages=32,
                          prompt_buckets=(8, 16), max_seq=24, seed=0)
        out = ContinuousBatchingScheduler(eng, eos_id=eos).run(
            [Request(rid=0, prompt=PROMPT, max_new_tokens=6)])
        c = out["completions"][0]
        stop = stream.index(eos)
        assert c.reason == "eos" and c.tokens == stream[:stop + 1]
        assert out["pages"]["leaked"] == 0
        assert out["pages"]["draft_leaked"] == 0

    def test_zero_retraces_speculative(self, served):
        """Steady state re-dispatches exactly the compiled pair set
        (draft decode + fused verify on the hot loop, prefill on the
        admission path) — fresh rids/lengths/pages add ZERO traces."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.xla_flags import (
            compile_event_counts,
            install_compile_counter,
        )
        model, v = served("gpt")
        draft_model = get_model("gpt_tiny", num_classes=VOCAB,
                                scan_layers=True)
        dv = draft_model.init(jax.random.key(99),
                              np.asarray(PROMPT, np.int32)[None])
        eng = _spec_pair(model, v, draft_model, dv, 2, max_seq=48,
                         max_pages=64)
        install_compile_counter()
        ContinuousBatchingScheduler(eng, eos_id=-1).run(
            [Request(rid=100, prompt=PROMPT, max_new_tokens=2)])
        before = compile_event_counts()
        out = ContinuousBatchingScheduler(eng, eos_id=-1).run(
            [Request(rid=i, prompt=PROMPT[:4 + i], max_new_tokens=32)
             for i in range(2)])
        after = compile_event_counts()
        assert out["spec"]["verify_steps"] >= 16
        assert after["traces"] == before["traces"], "speculative retrace"
        assert after["compiles"] == before["compiles"]

    def test_spec_telemetry_zero_filled_without_draft(self, served):
        model, v = served("gpt")
        out = ContinuousBatchingScheduler(_engine(model, v)).run(
            [Request(rid=0, prompt=PROMPT[:4], max_new_tokens=3)])
        assert out["spec"] == {"acceptance_rate": 0.0, "draft_steps": 0,
                               "verify_steps": 0,
                               "target_steps_per_token": 0.0}
        assert out["pages"]["draft_peak_in_use"] == 0
        assert out["pages"]["draft_leaked"] == 0

    def test_pairing_rejections(self, served):
        model, v = served("gpt")
        draft_model = get_model("gpt_tiny", num_classes=VOCAB,
                                scan_layers=True)
        dv = draft_model.init(jax.random.key(99),
                              np.asarray(PROMPT, np.int32)[None])
        # one flag without the other is inert — rejected
        with pytest.raises(ValueError, match="BOTH"):
            _engine(model, v, draft=_engine(draft_model, dv))
        with pytest.raises(ValueError, match="BOTH"):
            _engine(model, v, spec_tokens=4)
        # vocab mismatch: ids from different id spaces
        other = get_model("gpt_tiny", num_classes=VOCAB + 1,
                          scan_layers=True)
        ov = other.init(jax.random.key(1),
                        np.asarray(PROMPT, np.int32)[None])
        with pytest.raises(ValueError, match="vocabulary mismatch"):
            _engine(model, v, draft=_engine(other, ov), spec_tokens=2)
        # MoE draft: densely-evaluated experts cost MORE than the dense
        # twin at decode — a draft exists to be cheap
        moe, mv = served("gpt_moe")
        with pytest.raises(ValueError, match="MoE draft"):
            _engine(model, v, draft=_engine(moe, mv), spec_tokens=2)
        # geometry mismatch: the pools must stay position-paired
        with pytest.raises(ValueError, match="geometry"):
            _engine(model, v,
                    draft=_engine(draft_model, dv, page_size=8),
                    spec_tokens=2)
        # per-request temperature rejected at submit in spec mode
        eng = _spec_pair(model, v, draft_model, dv, 2)
        with pytest.raises(ValueError, match="temperature"):
            ContinuousBatchingScheduler(eng).run(
                [Request(rid=0, prompt=PROMPT[:4], max_new_tokens=2,
                         temperature=0.7)])


class TestSpeculativePages:
    def test_paired_admit_rolls_back_both_pools(self):
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve.cache import (
            paired_admit,
        )
        tgt, dra = PageAllocator(8), PageAllocator(8)
        # plain success: both pools advance together
        got = paired_admit(tgt, dra, [], [], 3)
        assert got is not None and tgt.in_use == dra.in_use == 3
        # draft pool exhausted -> target's claim + alloc fully unwound
        dra2 = PageAllocator(4)                  # 3 usable
        pin = dra2.alloc(2)
        assert paired_admit(tgt, dra2, [], [], 3) is None
        assert tgt.in_use == 3                   # back to entry state
        assert dra2.in_use == 2
        dra2.free(pin)
        # target pool exhausted -> nothing touched in the draft pool
        tgt2 = PageAllocator(4)
        tgt2.alloc(3)
        assert paired_admit(tgt2, dra, [], [], 3) is None
        assert dra.in_use == 3
        # unequal hit runs break the one-shared-offset contract
        with pytest.raises(ValueError, match="equal length"):
            paired_admit(tgt, dra, [1], [], 2)

    def test_dual_pool_joint_occupancy_audit(self, served):
        """PR 17 shadow-refcount property test extended to the pool
        PAIR: speculation + prefix cache + chunked prefill over tight
        twin pools, every allocator operation re-audited in BOTH, and
        the pools' joint occupancy mirroring through accept/rollback
        cycles, LRU eviction, backpressure and timeout eviction."""
        model, v = served("gpt")
        rng = np.random.default_rng(41)
        sys_prefix = rng.integers(1, VOCAB, 8).tolist()

        def mk(rid, tail, new=6):
            return Request(rid=rid,
                           prompt=sys_prefix + rng.integers(
                               1, VOCAB, tail).tolist(),
                           max_new_tokens=new)

        kw = dict(prefix_cache=True, prefill_chunk=4, max_pages=18,
                  max_seq=28)
        draft = _engine(model, v, **kw)
        draft.allocator = _AuditAllocator(18)
        eng = ServeEngine(model, v["params"], draft=draft, spec_tokens=2,
                          max_batch=3, page_size=4, prompt_buckets=(8, 16),
                          seed=0, **kw)
        eng.allocator = _AuditAllocator(18)
        out = ContinuousBatchingScheduler(eng).run(
            [mk(i, 1 + (i % 5)) for i in range(8)])
        assert out["page_reuse_ratio"] > 0
        assert out["spec"]["verify_steps"] > 0
        # the joint invariant: admission is all-or-nothing across the
        # pair, so the two pools' referenced-page counts track each
        # other exactly at every quiescent point
        assert eng.allocator.in_use == draft.allocator.in_use == 0
        assert eng.allocator.ops > 20 and draft.allocator.ops > 20
        # timeout eviction releases BOTH pools' spans
        out2 = ContinuousBatchingScheduler(
            eng, request_timeout=1e-6).run(
                [mk(100 + i, 3, new=8) for i in range(4)])
        assert out2["timed_out"] == 4
        assert eng.allocator.in_use == draft.allocator.in_use == 0
        assert out2["pages"]["leaked"] == 0
        assert out2["pages"]["draft_leaked"] == 0
