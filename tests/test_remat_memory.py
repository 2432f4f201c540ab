"""Memory-tier engine tests (ISSUE 15).

Three surfaces:

1. **Named activations** — every scanned family's block emits the
   ``checkpoint_name`` labels in ``models.REMAT_NAMES`` (visible in the
   jaxpr), and the ``save_names:``/``offload_names:`` policy spellings
   resolve/validate/demote correctly;
2. **Bitwise gate** — remat policy NEVER changes math: fp32 training
   trajectories are bitwise-identical across ALL policies at engine
   level (tier-1) and through the sanitized driver (slow-marked, the
   tier-1 wall hygiene rule for new e2e cases);
3. **Compiled-memory observability** — ``memory_analysis`` temp bytes
   order monotonically down the policy ladder, ``TrackedProgram``
   retains executables without double-compiling, and the uniform
   ``results["memory"]`` row is emitted on every run with exact
   resident-state accounting.
"""

from __future__ import annotations

import functools as ft
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
    models as models_lib,
    probe,
    train as train_lib,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global
from learning_deep_neural_network_in_distributed_computing_environment_tpu.mesh import build_mesh
from learning_deep_neural_network_in_distributed_computing_environment_tpu.models import (
    REMAT_NAMES,
    get_model,
    remat_name_vocab,
)

VOCAB, B, L_SEQ = 97, 4, 16
L_FLASH = 128   # the shortest sequence the flash kernel tiles

ALL_POLICIES = ("none", "dots_saveable", "save_names:attn_out",
                "save_names:attn_out,block_out", "offload_names:attn_out",
                "everything")


def _token_fixture(seed=0, seq=L_SEQ):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, VOCAB, (B, seq)), jnp.int32)
    y = jnp.asarray(rng.integers(0, VOCAB, (B, seq)), jnp.int32)
    return x, y


def _grad_jaxpr(model, x):
    def loss(p):
        out = model.apply({"params": p}, x, train=True)
        if isinstance(out, tuple):
            out = out[0]
        return jnp.sum(out.astype(jnp.float32))
    params = jax.eval_shape(
        lambda k: model.init(k, x, train=False), jax.random.key(0))
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), params)["params"]
    return str(jax.make_jaxpr(jax.grad(loss))(params))


class TestNamedActivations:
    """The vocabulary contract: names present in the jaxpr for every
    scanned family, exactly as ``remat_name_vocab`` promises."""

    @pytest.mark.parametrize("name,shape,extra", [
        ("bert_tiny", (L_SEQ,), {}),
        ("gpt_tiny", (L_SEQ,), {}),
        ("llama_tiny", (L_SEQ,), {}),
        ("vit_tiny", (32, 32, 3), {}),
        ("gpt_tiny", (L_SEQ,), {"num_experts": 2}),
    ])
    def test_names_present_in_jaxpr(self, name, shape, extra):
        kw = dict(num_classes=VOCAB, scan_layers=True, **extra)
        if len(shape) == 1:
            if not name.startswith("llama"):   # RoPE: no position table
                kw["max_len"] = L_SEQ
            x = jnp.zeros((B, *shape), jnp.int32)
        else:
            kw.pop("num_classes")
            kw["num_classes"] = 10
            x = jnp.zeros((B, *shape), jnp.float32)
        model = get_model(name, **kw)
        jpr = _grad_jaxpr(model, x)
        # the name primitive prints as ``name[name=<label>]`` — pjit's
        # unrelated ``pjit[name=...]`` params must not match
        emitted = set(re.findall(r"name\[name=(\w+)\]", jpr))
        vocab = set(remat_name_vocab(name, extra.get("num_experts", 0)))
        assert vocab <= emitted, (name, vocab - emitted)
        # and nothing outside the closed vocabulary (the R6 contract)
        assert emitted <= set(REMAT_NAMES), emitted - set(REMAT_NAMES)

    @pytest.mark.parametrize("name", ["gpt_tiny", "llama_tiny",
                                      "mellum2_tiny", "kanana2_tiny"])
    def test_flash_emits_the_kernel_residuals(self, name):
        """ISSUE 35: where the attention call reaches the flash kernel its
        output and log-sum-exp carry names (interpret mode here), and with
        dense attention no such name exists."""
        kw = dict(num_classes=models_lib.MODEL_INPUT_SPECS[name][1],
                  scan_layers=True)
        if name.startswith("gpt"):
            kw["max_len"] = L_FLASH
        x = jnp.zeros((2, L_FLASH), jnp.int32)
        emitted = {impl: set(re.findall(r"name\[name=(\w+)\]", _grad_jaxpr(
            get_model(name, attention_impl=impl, **kw), x)))
            for impl in ("flash", "dense")}
        assert set(remat_name_vocab(name)) <= emitted["flash"]
        assert emitted["flash"] <= set(REMAT_NAMES)
        assert emitted["flash"] - emitted["dense"] == {"flash_out",
                                                       "flash_lse"}
        assert emitted["dense"] == set(remat_name_vocab(name))

    def test_vocab_registry(self):
        assert REMAT_NAMES == ("attn_out", "mlp_out", "block_out",
                               "moe_dispatch", "flash_out", "flash_lse")
        assert models_lib.KERNEL_RESIDUALS == REMAT_NAMES[-2:]
        assert remat_name_vocab("gpt_tiny") == (
            "attn_out", "mlp_out", "block_out")
        assert remat_name_vocab("llama_tiny", 4)[-1] == "moe_dispatch"
        assert remat_name_vocab("mlp") == ()
        assert remat_name_vocab("enhanced_cnn", 2) == ()
        # every policy keeps the kernel's residuals: nothing to select
        assert not set(models_lib.KERNEL_RESIDUALS) & set(
            remat_name_vocab("llama_tiny", 4))


class TestPolicyResolution:
    def test_split_spellings(self):
        assert models_lib.split_remat_policy("none") == ("none", ())
        assert models_lib.split_remat_policy("save_names:a,b,a") == (
            "save_names", ("a", "b"))
        with pytest.raises(ValueError, match="at least one"):
            models_lib.split_remat_policy("offload_names:")
        with pytest.raises(ValueError, match="must start with"):
            models_lib.split_remat_policy("keep_names:a")
        with pytest.raises(ValueError, match="must be one of"):
            models_lib.split_remat_policy("sometimes")

    def test_config_validates_names_eagerly(self):
        # valid spellings construct
        Config(model="gpt_tiny", remat_policy="save_names:attn_out")
        Config(model="gpt_tiny", num_experts=2,
               remat_policy="offload_names:moe_dispatch")
        # unknown name: the error lists the family's emitted vocabulary
        with pytest.raises(ValueError,
                           match=r"attn_typo.*attn_out.*block_out"):
            Config(model="gpt_tiny", remat_policy="save_names:attn_typo")
        # moe_dispatch without experts is not emitted
        with pytest.raises(ValueError, match="moe_dispatch"):
            Config(model="gpt_tiny",
                   remat_policy="save_names:moe_dispatch")
        # non-attention family has no scanned block path at all
        with pytest.raises(ValueError, match="no scanned block"):
            Config(model="mlp", remat_policy="save_names:attn_out")

    @pytest.mark.parametrize("impl", ["flash", "dense"])
    def test_config_offers_no_kernel_residual_to_select(self, impl):
        """Every policy keeps them (ISSUE 35), so a set that named one
        would change nothing: the message lists what can be chosen."""
        with pytest.raises(ValueError, match=r"flash_out.*attn_out"):
            Config(model="gpt_tiny", attention_impl=impl,
                   remat_policy="save_names:flash_out")

    def test_named_policy_without_layer_scan_keeps_rejection(self):
        cfg = Config(model="gpt_tiny", dataset="synthetic_lm",
                     layer_scan="off",
                     remat_policy="save_names:attn_out",
                     epochs_global=1, epochs_local=1, batch_size=4,
                     limit_train_samples=16, limit_eval_samples=8,
                     compute_dtype="float32", augment=False)
        with pytest.raises(ValueError, match="scanned layer"):
            train_global(cfg, progress=False)

    def test_save_names_policy_resolves(self):
        pol = models_lib.checkpoint_policy("save_names:attn_out,mlp_out")
        assert callable(pol)

    def test_offload_demotes_with_logged_reason(self, caplog):
        if models_lib.host_offload_supported():
            pytest.skip("backend has pinned_host — no demotion here")
        names = ("block_out", "mlp_out")   # unique set => fresh log
        models_lib._OFFLOAD_DEMOTIONS_LOGGED.discard(names)
        with caplog.at_level(logging.INFO):
            pol = models_lib.checkpoint_policy("offload_names:block_out,mlp_out")
        assert callable(pol)
        assert any("demoted to save_names" in r.message
                   and "pinned_host" in r.message
                   for r in caplog.records), caplog.text

    def test_base_spellings_unchanged(self):
        for name in ("dots_saveable", "everything"):
            models_lib.checkpoint_policy(name)
        with pytest.raises(ValueError):
            models_lib.checkpoint_policy("none")

    @pytest.mark.parametrize("policy", [
        "everything", "dots_saveable", "save_names:attn_out",
        "save_names:attn_out,block_out", "offload_names:attn_out"])
    def test_every_policy_keeps_what_a_kernel_produced(self, policy):
        """One rule over every spelling (ISSUE 35): the recomputation of a
        rematerialised flash block holds no ``flash_fwd`` call, because
        the call's two named outputs are saved."""
        from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops
        q = jnp.zeros((1, L_FLASH, 2, 64), jnp.float32)

        def block(w):
            o = pallas_ops._flash(q * w, q * w, q * w, True)
            return jnp.sum(jnp.tanh(o) * w)

        def kernels(policy):
            f = block if policy is None else jax.checkpoint(
                block, policy=policy)
            return re.findall(r"name=(flash_fwd|flash_bwd)", str(
                jax.make_jaxpr(jax.grad(f))(jnp.float32(1.0))))

        assert kernels(None) == ["flash_fwd", "flash_bwd"]
        assert kernels(models_lib.checkpoint_policy(policy)) == [
            "flash_fwd", "flash_bwd"]
        # the control: jax's save-nothing policy runs the forward twice
        assert kernels(jax.checkpoint_policies.nothing_saveable) == [
            "flash_fwd", "flash_fwd", "flash_bwd"]


def _make_step(policy, depth=2, attention_impl="dense", seq=L_SEQ):
    model = get_model("gpt_tiny", num_classes=VOCAB, num_layers=depth,
                      max_len=seq, scan_layers=True,
                      attention_impl=attention_impl,
                      remat_policy=None if policy == "none" else policy)
    x, y = _token_fixture(seq=seq)
    tx = optax.adam(1e-3)

    def loss_fn(p):
        out = model.apply({"params": p}, x, train=True)
        return train_lib.softmax_cross_entropy(out, y).mean()

    @ft.partial(jax.jit, donate_argnums=0)
    def step(state):
        params, opt_state = state
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), new_opt), loss

    def init():
        params = jax.jit(
            lambda k: model.init(k, x, train=False))(
                jax.random.key(3))["params"]
        return (params, jax.jit(tx.init)(params))

    return step, init


@pytest.fixture(scope="module")
def flash_baseline():
    step, init = _make_step("none", attention_impl="flash", seq=L_FLASH)
    state, loss = step(init())
    return (jax.tree_util.tree_leaves(jax.device_get(state[0])),
            np.asarray(loss).copy())


class TestBitwiseAcrossPolicies:
    """The tentpole gate at engine level: remat policy never changes
    math — 3 fp32 Adam steps land bit-identical params and losses on
    every policy arm, including the demoted offload arm."""

    def test_fp32_trajectory_bitwise_all_policies(self):
        finals = {}
        for policy in ALL_POLICIES:
            step, init = _make_step(policy)
            state = init()
            losses = []
            for _ in range(3):
                state, loss = step(state)
                losses.append(np.asarray(loss).copy())
            finals[policy] = (jax.tree_util.tree_leaves(
                jax.device_get(state[0])), losses)
        base_leaves, base_losses = finals["none"]
        for policy, (leaves, losses) in finals.items():
            assert all(np.array_equal(a, b)
                       for a, b in zip(base_leaves, leaves)), policy
            assert all(np.array_equal(a, b)
                       for a, b in zip(base_losses, losses)), policy


    @pytest.mark.parametrize("policy", [p for p in ALL_POLICIES
                                        if p != "none"] + [
        "save_names:block_out", "offload_names:mlp_out,block_out"])
    def test_flash_block_bitwise_the_no_policy_step(self, policy,
                                                    flash_baseline):
        """ISSUE 35: with the flash kernel in the block (interpret mode)
        the saved output and log-sum-exp are the values a second run of
        the kernel would have produced: one Adam step's loss and
        parameters are the no-policy step's bit for bit."""
        step, init = _make_step(policy, attention_impl="flash", seq=L_FLASH)
        state, loss = step(init())
        base_leaves, base_loss = flash_baseline
        assert np.array_equal(np.asarray(loss), base_loss)
        assert all(np.array_equal(a, b) for a, b in zip(
            base_leaves, jax.tree_util.tree_leaves(jax.device_get(state[0]))))


# sanitized driver-level matrix: new e2e driver cases ride the slow tier
# up front (ROADMAP tier-1 wall hygiene)
@pytest.mark.slow
class TestDriverBitwiseSanitized:
    DRIVER_KW = dict(
        model="gpt_tiny", dataset="synthetic_lm", epochs_global=2,
        epochs_local=1, batch_size=4, limit_train_samples=64,
        limit_eval_samples=16, compute_dtype="float32", augment=False,
        aggregation_by="weights", sanitize=True, seed=11)

    def _run(self, policy):
        mesh = build_mesh({"data": 2}, devices=jax.devices()[:2])
        res = train_global(Config(remat_policy=policy, **self.DRIVER_KW),
                           mesh=mesh, progress=False)
        leaves = jax.tree_util.tree_leaves(
            jax.device_get(res["variables"]["params"]))
        return res, leaves

    def test_sanitized_driver_bitwise_across_policies(self):
        base, base_leaves = self._run("none")
        assert base["sanitize"]["retrace_count"] == 0
        for policy in ("dots_saveable", "save_names:attn_out",
                       "offload_names:attn_out,mlp_out", "everything"):
            res, leaves = self._run(policy)
            assert res["sanitize"] == base["sanitize"], policy
            assert res["global_train_losses"] == \
                base["global_train_losses"], policy
            assert all(np.array_equal(a, b)
                       for a, b in zip(base_leaves, leaves)), policy
            assert res["memory"]["available"] is True


class TestMemoryAnalysisOrdering:
    def test_temp_bytes_monotone_down_the_ladder(self):
        temps = {}
        for policy in ("none", "dots_saveable", "save_names:attn_out",
                       "everything"):
            step, init = _make_step(policy, depth=4)
            comp = step.lower(init()).compile()
            temps[policy] = int(comp.memory_analysis().temp_size_in_bytes)
        assert temps["none"] >= temps["dots_saveable"] \
            >= temps["save_names:attn_out"] >= temps["everything"]
        assert temps["none"] > temps["everything"]

    def test_dense_attention_everything_is_still_save_nothing(self,
                                                              monkeypatch):
        """No attention call reaches the kernel here, so nothing carries
        a kernel residual's name and ``everything`` compiles to the bytes
        of jax's own ``nothing_saveable`` (ISSUE 35)."""
        def temp(policy):
            step, init = _make_step(policy, depth=4)
            return int(step.lower(init()).compile()
                       .memory_analysis().temp_size_in_bytes)
        ours = temp("everything")
        monkeypatch.setattr(
            models_lib, "checkpoint_policy",
            lambda name: jax.checkpoint_policies.nothing_saveable)
        assert temp("everything") == ours

    def test_offload_arm_matches_save_arm_bytes(self):
        # demoted offload is the SAME executable residency-wise
        if models_lib.host_offload_supported():
            pytest.skip("backend has pinned_host — bytes may differ")
        vals = []
        for policy in ("save_names:attn_out", "offload_names:attn_out"):
            step, init = _make_step(policy, depth=4)
            comp = step.lower(init()).compile()
            vals.append(int(comp.memory_analysis().temp_size_in_bytes))
        assert vals[0] == vals[1]


class TestTrackedProgram:
    def test_single_shape_compiles_once_and_tracks(self):
        calls = []
        inner = jax.jit(lambda a: a * 2)
        orig_trace = inner.trace

        def counting_trace(*a, **k):
            calls.append(1)
            return orig_trace(*a, **k)
        inner.trace = counting_trace
        tp = probe.TrackedProgram("p", inner)
        x = jnp.arange(4.0)
        assert np.array_equal(np.asarray(tp(x)), np.asarray(x) * 2)
        tp(x)
        tp(x)
        assert len(calls) == 1          # one AOT trace+lower+compile total
        rows = tp.memory_rows()
        assert len(rows) == 1
        for key in ("temp_bytes", "argument_bytes", "output_bytes",
                    "alias_bytes"):
            assert isinstance(rows[0][key], int)

    def test_multi_shape_keeps_one_executable_per_shape(self):
        tp = probe.TrackedProgram("p", jax.jit(lambda a: a.sum()),
                                  multi_shape=True)
        tp(jnp.ones(3))
        tp(jnp.ones(5))
        tp(jnp.ones(3))
        assert len(tp.executables()) == 2
        assert len(tp.memory_rows()) == 2

    def test_compile_failure_propagates(self):
        """A program the backend refuses must stop the run — no retry
        through plain jit behind an observability warning."""
        def refuses(a):
            raise RuntimeError("compiler refused")
        tp = probe.TrackedProgram("p", jax.jit(refuses))
        with pytest.raises(RuntimeError, match="compiler refused"):
            tp(jnp.ones(3))
        assert tp.memory_rows() == []

    def test_memory_report_schema(self):
        tp = probe.TrackedProgram("round", jax.jit(lambda a: a + 1))
        tp(jnp.ones(3))
        uncompiled = probe.TrackedProgram("broken", jax.jit(lambda a: a))
        rep = probe.memory_report(
            {"round": tp, "broken": uncompiled},
            state_bytes={"params": 100, "opt_state": 200,
                         "params_gathered_peak": 800},
            n_workers=8)
        assert rep["available"] is False     # one program missing
        assert rep["programs_unavailable"] == ["broken"]
        assert rep["per_worker_resident_bytes"] == 300
        assert rep["per_worker_peak_bytes"] == 1100
        assert rep["state_bytes_total"] == 2400
        assert rep["temp_bytes_total"] == sum(
            r["temp_bytes"] for r in rep["programs"]["round"])


class TestMemoryRowOnEveryRun:
    """results["memory"] is emitted unconditionally, like sync_engine /
    sanitize — including on unarmed (no remat, no sanitize) runs."""

    KW = dict(model="mlp", dataset="mnist", epochs_local=1, batch_size=16,
              limit_train_samples=128, limit_eval_samples=32,
              compute_dtype="float32", augment=False,
              aggregation_by="weights", seed=5)

    def test_unarmed_run_emits_schema(self):
        mesh = build_mesh({"data": 2}, devices=jax.devices()[:2])
        res = train_global(Config(epochs_global=1, **self.KW),
                           mesh=mesh, progress=False)
        m = res["memory"]
        assert m["available"] is True
        assert m["simulated"] is False and m["workers"] == 2
        assert list(m["programs"]) == ["round"]
        row = m["programs"]["round"][0]
        assert row["temp_bytes"] > 0 and row["argument_bytes"] > 0
        pw = m["per_worker_state_bytes"]
        assert set(pw) >= {"params", "opt_state", "params_gathered_peak",
                           "batch_stats", "bookkeeping"}
        assert m["per_worker_resident_bytes"] == sum(
            v for k, v in pw.items() if k != "params_gathered_peak")
        assert m["state_bytes_total"] == 2 * m["per_worker_resident_bytes"]

    def test_zero_round_run_still_emits(self, tmp_path):
        # resuming a finished run dispatches nothing — the row must
        # still be there (empty program map, analytic model populated)
        kw = dict(self.KW, checkpoint_dir=str(tmp_path),
                  checkpoint_every=1)
        mesh = build_mesh({"data": 2}, devices=jax.devices()[:2])
        train_global(Config(epochs_global=1, **kw), mesh=mesh,
                     progress=False)
        res = train_global(Config(epochs_global=1, resume=True, **kw),
                           mesh=mesh, progress=False)
        m = res["memory"]
        assert m["programs"] == {} and m["available"] is False
        assert m["per_worker_resident_bytes"] > 0

    def test_exact_accounting_vs_actual_state_bytes(self):
        mesh = build_mesh({"data": 2}, devices=jax.devices()[:2])
        res = train_global(Config(epochs_global=1, **self.KW),
                           mesh=mesh, progress=False)
        actual = sum(l.nbytes
                     for l in jax.tree_util.tree_leaves(res["state"])
                     if hasattr(l, "nbytes"))
        assert res["memory"]["state_bytes_total"] == actual

    def test_sim_run_stacked_total_is_n_times_per_worker(self):
        res = train_global(Config(epochs_global=1, sim_workers=8,
                                  **self.KW), progress=False)
        m = res["memory"]
        assert m["simulated"] is True and m["workers"] == 8
        assert list(m["programs"]) == ["sim_round"]
        assert m["state_bytes_total"] == 8 * m["per_worker_resident_bytes"]
        actual = sum(l.nbytes
                     for l in jax.tree_util.tree_leaves(res["state"])
                     if hasattr(l, "nbytes"))
        assert m["state_bytes_total"] == actual


@pytest.mark.slow
class TestMemoryRowResidentAndStreamed:
    """Driver e2e coverage of the resident / streamed program maps
    (slow tier: new e2e driver cases up front)."""

    KW = dict(model="mlp", dataset="mnist", epochs_global=2,
              epochs_local=1, batch_size=16, limit_train_samples=256,
              limit_eval_samples=64, compute_dtype="float32",
              augment=False, aggregation_by="weights", seed=5)

    def test_resident_run_reports_gathered_peak(self, mesh8):
        res = train_global(Config(sync_mode="sharded",
                                  param_residency="resident", **self.KW),
                           mesh=mesh8, progress=False)
        m = res["memory"]
        pw = m["per_worker_state_bytes"]
        # the acceptance identity: resident params are EXACTLY 1/N of
        # the transient gathered peak
        assert pw["params"] * 8 == pw["params_gathered_peak"]
        assert m["per_worker_peak_bytes"] == \
            m["per_worker_resident_bytes"] + pw["params_gathered_peak"]
        assert m["available"] is True

    def test_streamed_resident_run_tracks_all_programs(self, mesh8):
        res = train_global(Config(sync_mode="sharded",
                                  param_residency="resident",
                                  stream_chunk_steps=2, **self.KW),
                           mesh=mesh8, progress=False)
        labels = set(res["memory"]["programs"])
        assert {"sync", "resident_enter", "stream_zeros", "chunk_train",
                "chunk_eval", "bump_epoch"} <= labels
        assert res["memory"]["available"] is True
