"""What the v5e bring-up (PR 21) fixed, as cheap CPU cases: serving a
checkpoint trained at the DEFAULT compute dtype, the one compile-cache
rule, and the two entry points that must fail instead of quietly moving
to another platform."""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
    xla_flags,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import (
    Config,
    config_from_args,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_serve_checkpoint_trained_at_default_dtype(tmp_path):
    """``main`` -> ``main serve`` at ``compute_dtype="bfloat16"`` (the
    Config default, what the chip serves in): the paged block used to
    multiply the bf16 residual by the f32-stored kernels and die in the
    first prefill on a float32 scan carry.  Served greedy ids must be the
    full-forward argmax in the same dtype (``chip_smoke.greedy_gate``)."""
    import chip_smoke
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import (
        train_global,
    )
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.serve.api import (
        build_requests,
        run_serve,
    )
    cfg = Config(model="gpt_tiny", dataset="synthetic_lm", epochs_global=1,
                 epochs_local=1, batch_size=8, limit_train_samples=64,
                 limit_eval_samples=16, augment=False,
                 aggregation_by="weights", checkpoint_dir=str(tmp_path),
                 checkpoint_every=1, seed=3, num_workers=2)
    assert cfg.compute_dtype == "bfloat16"
    res = train_global(cfg, progress=False)
    out = run_serve(cfg.replace(
        serve_requests=4, serve_max_new_tokens=6, serve_max_batch=2,
        serve_page_size=8, serve_max_pages=16, serve_prompt_buckets="16"))
    done = sorted(out["completions"], key=lambda c: c.rid)
    assert [c.reason for c in done] == ["length"] * 4
    assert out["engine"].kcache.dtype == jnp.bfloat16
    prompts = [r.prompt for r in build_requests(
        cfg.replace(serve_requests=4, serve_prompt_buckets="16"),
        out["engine"].spec.vocab)]
    report = chip_smoke.greedy_gate(
        res["model"], res["variables"]["params"], prompts,
        [c.tokens for c in done], jnp.bfloat16, pad_to=32)
    assert report["tokens"] == 24
    assert report["exact"] >= 22, report


class TestCompileCacheRule:
    def test_default_is_absolute_under_the_checkout_and_stable(
            self, monkeypatch, tmp_path):
        monkeypatch.delenv(xla_flags.CACHE_DIR_ENV, raising=False)
        first = xla_flags.compile_cache_dir()
        assert first == os.path.join(REPO, ".jax_cache")
        assert os.path.isabs(first)
        monkeypatch.chdir(tmp_path)
        assert xla_flags.compile_cache_dir() == first
        # the CLI default is that directory, not a cwd-relative name
        assert config_from_args([]).compile_cache_dir == first

    def test_env_var_wins_and_nothing_sets_the_dir(self, monkeypatch,
                                                   tmp_path):
        env_dir = str(tmp_path / "from_env")
        monkeypatch.setenv(xla_flags.CACHE_DIR_ENV, env_dir)
        assert xla_flags.compile_cache_dir() == env_dir
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append((k, v)))
        # jax read the variable at import (here: emulated), so it stands
        monkeypatch.setattr(type(jax.config), "jax_compilation_cache_dir",
                            env_dir, raising=False)
        assert xla_flags.setup_compile_cache() == env_dir
        assert updates == []

    def test_env_var_set_after_import_is_an_error(self, monkeypatch,
                                                  tmp_path):
        monkeypatch.setenv(xla_flags.CACHE_DIR_ENV, str(tmp_path / "late"))
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: pytest.fail("must not set"))
        with pytest.raises(RuntimeError, match="after jax was imported"):
            xla_flags.setup_compile_cache()

    def test_default_is_the_only_value_ever_set(self, monkeypatch):
        monkeypatch.delenv(xla_flags.CACHE_DIR_ENV, raising=False)
        updates = []
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: updates.append((k, v)))
        assert xla_flags.setup_compile_cache() \
            == xla_flags.DEFAULT_COMPILE_CACHE_DIR
        assert updates == [("jax_compilation_cache_dir",
                            xla_flags.DEFAULT_COMPILE_CACHE_DIR)]

    def test_flag_can_switch_off_but_not_redirect(self, monkeypatch,
                                                  tmp_path):
        monkeypatch.delenv(xla_flags.CACHE_DIR_ENV, raising=False)
        assert config_from_args(
            ["--compile_cache_dir", ""]).compile_cache_dir == ""
        with pytest.raises(ValueError, match="only turns the cache off"):
            config_from_args(["--compile_cache_dir", str(tmp_path)])
        monkeypatch.setenv(xla_flags.CACHE_DIR_ENV, str(tmp_path / "env"))
        with pytest.raises(ValueError, match="only turns the cache off"):
            config_from_args(["--compile_cache_dir", str(tmp_path)])


def test_chip_smoke_refuses_cpu_at_once():
    """Run with ``JAX_PLATFORMS=cpu`` the chip smoke exits non-zero before
    it imports jax, printing no result line."""
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=60)
    assert proc.returncode != 0
    assert time.perf_counter() - t0 < 10
    assert proc.stdout == ""
    assert "will not run on another platform" in proc.stderr


def test_dryrun_multichip_does_not_switch_platform(monkeypatch):
    """Fewer chips than asked on a non-CPU platform is an error — the old
    path cleared the backends and carried on under CPU, reporting "ok"
    for a device it never touched."""
    import __graft_entry__ as entry

    class OneChip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [OneChip()])
    platforms_before = jax.config.jax_platforms
    with pytest.raises(RuntimeError, match="needs 8 devices"):
        entry.dryrun_multichip(8)
    assert jax.config.jax_platforms == platforms_before
