"""Test harness: force an 8-device virtual CPU mesh.

This is the reference-impossible trick that replaces its (absent) test
strategy: every mesh/psum/ppermute path and all 12 DP sync modes run as
ordinary pytest cases on one host (SURVEY.md section 4).  Pallas kernels
run in interpret mode here; the chip path is ``chip_smoke.py``.
"""

import os

# XLA flags are read at first backend initialization; set before any
# jax.devices() call.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# deadlock workaround for the CPU thunk executor (see the helper's docs)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.xla_flags import (  # noqa: E402
    ensure_sequential_cpu_collectives,
)

ensure_sequential_cpu_collectives()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

# --- quick tier ----------------------------------------------------------
# ``pytest -m quick`` selects ONE representative case per subsystem,
# <= ~5 minutes total on the virtual CPU mesh — the pre-commit smoke run
# (the full suite stays the round gate; round-2 verdict weak #8).  Entries
# are nodeid prefixes, so a bare file selects its whole (cheap) module.
QUICK_PREFIXES = (
    "tests/test_model.py::test_param_count_matches_reference",
    "tests/test_comms.py::TestAllReduce::test_equal_is_global_mean",
    "tests/test_comms.py::TestRing::test_equal_blends_with_predecessor",
    "tests/test_comms.py::TestDoubleRing::test_equal_three_way_average",
    "tests/test_partition.py",          # pure-numpy partition math
    "tests/test_train.py::TestStepLR",
    "tests/test_train.py::TestCrossEntropy",
    "tests/test_train.py::TestEngine::test_round_learns_and_lr_epoch_advances",
    "tests/test_eval_viz.py::TestPRF",
    "tests/test_eval_viz.py::TestViz::test_all_six_files_written",
    "tests/test_checkpoint.py::test_save_restore_roundtrip",
    "tests/test_gqa.py::TestDenseGrouped",
    "tests/test_gpt.py::TestCausalAttention::test_dense_causal_equals_masked",
    "tests/test_sp.py::TestRingAttention::test_forward_matches_dense",
    "tests/test_pp.py::TestGpipeSchedule::test_forward_matches_sequential",
    "tests/test_tp.py::TestTPModule::test_forward_matches_dense",
    "tests/test_fsdp.py::TestSpecsAndGather::test_large_leaves_shard_small_replicate",
    "tests/test_moe.py::TestMoEFFN::test_output_shape_and_aux_loss",
    "tests/test_streaming.py::TestPackWindow",
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: one fast case per subsystem (pre-commit smoke "
        "tier; the full suite remains the round gate)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        nodeid = item.nodeid.replace("\\", "/")
        if not nodeid.startswith("tests/"):
            nodeid = "tests/" + nodeid
        if any(nodeid.startswith(p) for p in QUICK_PREFIXES):
            item.add_marker(pytest.mark.quick)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8 and devs[0].platform == "cpu", \
        f"expected 8 virtual CPU devices, got {devs}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    from learning_deep_neural_network_in_distributed_computing_environment_tpu import mesh as mesh_lib
    return mesh_lib.build_mesh({"data": 8})


@pytest.fixture(scope="session")
def assert_within_ulps():
    """The gate between two DIFFERENTLY SHAPED float32 programs that
    state the same arithmetic (XLA fuses, contracts into FMAs and orders
    reductions by program shape, so they differ in the last places):
    absolute, in float32 ulps of the reference tensor's largest
    magnitude.  Per-element ``rtol`` is the wrong measure there: an
    element that cancels to near zero still carries the rounding of its
    large terms.  One program run twice stays ``assert_array_equal``."""
    def check(actual, desired, ulps):
        actual, desired = np.asarray(actual), np.asarray(desired)
        assert actual.dtype == desired.dtype == np.float32
        atol = ulps * float(np.spacing(np.abs(desired).max()))
        np.testing.assert_allclose(actual, desired, rtol=0, atol=atol)
    return check
