"""Runtime sanitizer (ISSUE 6): clean-run provenance, transfer-guard
violation counting, the retrace-budget counter, and env-var arming."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import (
    _round_guard,
    train_global,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.xla_flags import (
    compile_event_counts,
    install_compile_counter,
)

CLEAN = {"enabled": True, "transfer_guard_violations": 0,
         "retrace_count": 0, "recompile_count": 0, "donation_failures": 0}


def cfg(**kw):
    base = dict(model="mlp", dataset="mnist", epochs_global=2,
                epochs_local=1, batch_size=16, limit_train_samples=512,
                limit_eval_samples=64, compute_dtype="float32",
                augment=False, aggregation_by="weights", seed=3)
    base.update(kw)
    return Config(**base)


class TestDriverSanitize:
    def test_clean_packed_run_records_zeros(self, mesh8):
        res = train_global(cfg(sanitize=True), mesh=mesh8, progress=False)
        assert res["sanitize"] == CLEAN
        # sanitize mode changes no numerics: same run unsanitized matches
        ref = train_global(cfg(), mesh=mesh8, progress=False)
        assert res["global_train_losses"] == ref["global_train_losses"]

    def test_clean_streamed_run_records_zeros(self, mesh8):
        # the streamed path is where this PR's three runtime hazards
        # lived (per-round jit rebuild, unsharded-zeros d2d reshard,
        # implicit scalar H2Ds) — keep it under the harness so a
        # regression of any of them trips the guard or retrace budget
        res = train_global(cfg(sanitize=True, stream_chunk_steps=4),
                           mesh=mesh8, progress=False)
        assert res["sanitize"] == CLEAN

    def test_unsanitized_run_records_disabled(self, mesh8):
        res = train_global(cfg(), mesh=mesh8, progress=False)
        assert res["sanitize"]["enabled"] is False
        assert res["sanitize"]["transfer_guard_violations"] == 0

    def test_env_var_arms_the_sanitizer(self, mesh8, monkeypatch):
        monkeypatch.setenv("JAX_GRAFT_SANITIZE", "1")
        res = train_global(cfg(epochs_global=1), mesh=mesh8,
                           progress=False)
        assert res["sanitize"]["enabled"] is True

    @pytest.mark.parametrize("value", ["0", "false"])
    def test_falsy_env_var_means_off(self, mesh8, monkeypatch, value):
        monkeypatch.setenv("JAX_GRAFT_SANITIZE", value)
        res = train_global(cfg(epochs_global=1), mesh=mesh8,
                           progress=False)
        assert res["sanitize"]["enabled"] is False


class TestRoundGuard:
    def test_implicit_transfer_counted_and_reraised(self):
        san = {"enabled": True, "transfer_guard_violations": 0}
        x = jnp.ones((4,))
        with pytest.raises(Exception, match="[Dd]isallow"):
            with _round_guard(san):
                _ = x + 1.0  # bare Python scalar: implicit H2D
        assert san["transfer_guard_violations"] == 1

    def test_explicit_staging_passes(self):
        san = {"enabled": True, "transfer_guard_violations": 0}
        with _round_guard(san):
            a = jax.device_put(np.ones(3, np.float32))
            _ = jax.device_get(a)
        assert san["transfer_guard_violations"] == 0

    def test_disabled_guard_is_a_no_op(self):
        san = {"enabled": False, "transfer_guard_violations": 0}
        x = jnp.ones((4,))
        with _round_guard(san):
            _ = x + 1.0  # allowed: guard off
        assert san["transfer_guard_violations"] == 0


class TestCompileCounter:
    def test_fresh_jit_counts_trace_and_compile(self):
        install_compile_counter()
        before = compile_event_counts()
        f = jax.jit(lambda a: a * 3 + 1)
        jax.block_until_ready(f(jnp.arange(7.0)))
        mid = compile_event_counts()
        assert mid["traces"] > before["traces"]
        assert mid["compiles"] > before["compiles"]
        # cached second call adds neither — the retrace-budget signal
        jax.block_until_ready(f(jnp.arange(7.0)))
        after = compile_event_counts()
        assert after == mid
