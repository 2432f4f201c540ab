"""The round loop's span primitive (ISSUE 24): what the rows of
``round_timings`` carry in each pipeline mode, that the stamps and the
durations agree, which dispatch built a program, where the wait on the
sync is timed, the host spans in the profiler's trace, and set-up's phases.  Counts and
control flow only: nothing here is a time of a device."""

import glob
import re
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from learning_deep_neural_network_in_distributed_computing_environment_tpu import (
    probe,
    spans,
    train,
)
from learning_deep_neural_network_in_distributed_computing_environment_tpu.config import Config
from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import train_global

ROUNDS = 4
NEW_KEYS = ("t_dispatch_s", "t_ready_s", "wait_ms", "build_ms",
            "programs_built")
KEPT_KEYS = ("stage_ms", "compute_ms", "fetch_ms", "assemble_ms",
             "sync_bytes", "ckpt_snapshot_ms", "ckpt_write_ms")
SETUP_KEYS = ("data_s", "engine_s", "restore_s", "probe_s", "first_prep_s")


def cfg(**kw):
    base = dict(model="mlp", dataset="mnist", epochs_global=ROUNDS,
                epochs_local=1, batch_size=16, limit_train_samples=800,
                limit_eval_samples=100, compute_dtype="float32",
                augment=False, aggregation_by="weights", seed=1)
    base.update(kw)
    return Config(**base)


@pytest.fixture(scope="module", params=["serial", "overlap",
                                        "two_in_flight"])
def run(request, mesh8):
    """One call a mode.  Two rounds in flight is the TPU's path, reached
    here as ``benchmarks/tests/test_runner.py`` reaches it: by telling the
    driver it is not on a CPU (the sync then runs as its own program)."""
    mode = request.param
    mp = pytest.MonkeyPatch()
    if mode == "two_in_flight":
        mp.setattr(jax, "default_backend", lambda: "tpu")
    try:
        t0 = time.perf_counter()
        res = train_global(cfg(overlap_rounds=mode != "serial"), mesh=mesh8,
                           progress=False)
        wall = time.perf_counter() - t0
    finally:
        mp.undo()
    return mode, res, wall


def test_rows_carry_stamps_and_spans(run):
    mode, res, _ = run
    rows = res["round_timings"]
    assert len(rows) == ROUNDS
    for row in rows:
        for key in NEW_KEYS + KEPT_KEYS:
            assert key in row, f"{mode}: row lacks {key}"
        assert row["wait_ms"] >= 0.0
    for row in rows[:-1]:          # the last round prepares nothing
        assert {"prep_ms", "pack_ms", "h2d_ms"} <= set(row)
        assert row["prep_ms"] >= row["pack_ms"] + row["h2d_ms"] - 0.002
    assert "prep_ms" not in rows[-1]
    deferred = mode == "two_in_flight"
    for row in rows[:-1]:
        # gap_ms keeps its meaning: absent on a round left in flight
        assert ("gap_ms" not in row) == deferred
    for r, row in enumerate(rows):  # measured or counted, none modelled
        left_in_flight = deferred and r < ROUNDS - 1
        assert {k for k in row if k.startswith("sync_")} == {
            "sync_bytes", "sync_mode", "sync_hidden_ms",
            "sync_bytes_ici", "sync_bytes_dcn"} | (
                set() if left_in_flight else {"sync_ms"})


def test_stamps_are_ordered(run):
    _, res, _ = run
    rows = res["round_timings"]
    disp = [r["t_dispatch_s"] for r in rows]
    ready = [r["t_ready_s"] for r in rows]
    assert all(b > a for a, b in zip(disp, disp[1:]))
    assert all(b > a for a, b in zip(ready, ready[1:]))
    assert all(r >= d for d, r in zip(disp, ready))


def test_compute_ms_is_rebuilt_from_the_stamps(run):
    """compute_ms[r] = ready[r] - max(dispatch[r], ready[r-1]): the stamps
    are the very readings it was computed from."""
    _, res, _ = run
    rows = res["round_timings"]
    for r, row in enumerate(rows):
        start = row["t_dispatch_s"] if r == 0 else max(
            row["t_dispatch_s"], rows[r - 1]["t_ready_s"])
        assert row["compute_ms"] == round(
            (row["t_ready_s"] - start) * 1e3, 3)
    n = len(rows) - 1
    window = sum(r["compute_ms"] for r in rows[1:]) + sum(
        max(r.get("gap_ms", 0.0), 0.0) for r in rows[:-1])
    assert window / 1e3 == pytest.approx(
        rows[n]["t_ready_s"] - rows[0]["t_ready_s"], abs=1e-5 * n)


def test_round_0_alone_built_programs(run):
    mode, res, _ = run
    rows = res["round_timings"]
    built = ["round", "sync"] if mode == "two_in_flight" else ["round"]
    assert rows[0]["programs_built"] == built
    assert 0.0 < rows[0]["build_ms"] <= rows[0]["stage_ms"]
    for row in rows[1:]:
        assert row["programs_built"] == [] and row["build_ms"] == 0.0
    # the compiled module carries the program's label, not "stacked"
    programs = res["engine"].memory_programs()
    for label in built:
        text = programs[label].compiled.as_text()
        assert re.match(rf"HloModule jit_localsgd_{label}\b", text)


def test_sync_ms_is_timed_only_where_the_host_waits(run):
    """``sync_ms`` is the host's wait on the standalone sync.  To a round
    left in flight the host comes late and waits for nothing: its row has
    no ``sync_ms``, rather than a 0.0 under a name that reads as a wall."""
    mode, res, _ = run
    rows = res["round_timings"]
    if mode == "two_in_flight":
        assert all("sync_ms" not in r for r in rows[:-1])
        assert 0.0 < rows[-1]["sync_ms"] <= rows[-1]["wait_ms"]
    else:
        assert all(r["sync_ms"] == 0.0 for r in rows)   # fused on the CPU


def test_setup_timings(run):
    _, res, wall = run
    setup = res["setup_timings"]
    assert tuple(setup) == SETUP_KEYS
    assert all(v >= 0.0 for v in setup.values())
    assert setup["restore_s"] == 0.0 and setup["engine_s"] > 0.0
    assert sum(setup.values()) <= wall


def test_streamed_round_settles_on_the_handles_fence(mesh8):
    res = train_global(cfg(epochs_global=2, stream_chunk_steps=2),
                       mesh=mesh8, progress=False)
    for row in res["round_timings"]:
        assert row["sync_ms"] > 0.0 and row["wait_ms"] >= row["sync_ms"]
        assert "pack_ms" not in row      # a streamed round packs by chunk
    assert "sync" in res["round_timings"][0]["programs_built"]


class TestRoundWait:
    """``LocalSGDEngine.round_wait`` on stub markers: the round program's
    marker first, then the fence, and the second block is what is timed."""

    @pytest.fixture()
    def blocks(self, monkeypatch):
        order = []

        def block(x):
            order.append(x)
            if x == "fence":
                time.sleep(0.005)
            return x
        monkeypatch.setattr(train.jax, "block_until_ready", block)
        return order

    @staticmethod
    def wait(handle):
        eng = types.SimpleNamespace(
            last_sync_stats={"sync_ms": 0.0},
            round_markers=lambda h: train.LocalSGDEngine.round_markers(
                None, h))
        assert train.LocalSGDEngine.round_wait(eng, "state",
                                               handle) == "state"
        return eng.last_sync_stats

    def test_marker_then_fence_and_the_fence_is_timed(self, blocks):
        stats = self.wait(("packed", {"train_loss": "marker"}, None,
                           "fence", None))
        assert blocks == ["marker", "fence", "state"]
        assert 5.0 <= stats["sync_ms"] < 1000.0

    def test_no_fence_no_measurement(self, blocks):
        stats = self.wait(("packed", {"train_loss": "marker"}, None, None,
                           None))
        assert blocks == ["marker", "state"] and stats == {"sync_ms": 0.0}

    def test_streamed_round_has_no_round_marker(self, blocks):
        stats = self.wait(("streamed", [], "norm", None, "fence"))
        assert blocks == ["fence", "state"] and stats["sync_ms"] >= 5.0

    def test_markers_of_a_handle(self):
        eng = train.LocalSGDEngine
        packed = ("packed", {"train_loss": "m"}, None, "f", None)
        assert eng.round_markers(None, packed) == ("m", "f")
        fused = ("packed", {"train_loss": "m"}, None, None, None)
        assert eng.round_markers(None, fused) == ("m", None)
        streamed = ("streamed", [], "norm", None, "f")
        assert eng.round_markers(None, streamed) == (None, "f")


def test_profile_holds_the_rounds_host_spans(mesh8, tmp_path):
    """With ``profile_dir`` the same spans land on a host plane of the
    profiler's trace, one of each a round."""
    from jax.profiler import ProfileData
    rounds = 2
    train_global(cfg(epochs_global=rounds, profile_dir=str(tmp_path)),
                 mesh=mesh8, progress=False)
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    counts: dict[str, int] = {}
    ids: dict[str, list] = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("round.", "setup.")):
                    counts[e.name] = counts.get(e.name, 0) + 1
                    ids.setdefault(e.name, []).append(dict(e.stats))
    assert counts["round.dispatch"] == rounds
    assert counts["round.wait"] == rounds
    assert counts["round.prep"] == rounds - 1
    assert counts["round.fetch"] == counts["round.assemble"] == rounds
    assert counts["round.build"] == 1 and counts["setup.first_prep"] == 1
    assert sorted(d["round"] for d in ids["round.dispatch"]) == [0, 1]
    assert ids["round.build"] == [{"program": "round"}]


class TestPrimitive:
    def test_ms_and_s_keys(self):
        row = {}
        with spans.span("x", row, "a_ms", round=1):
            time.sleep(0.002)
        with spans.span("x", row, "b_s"):
            time.sleep(0.002)
        assert 2.0 <= row["a_ms"] < 500.0
        assert 0.002 <= row["b_s"] < 0.5
        assert row["a_ms"] == round(row["a_ms"], 3)

    def test_annotation_alone_and_a_raising_body(self):
        with spans.span("x", round=0):
            pass
        row = {}
        with pytest.raises(KeyError):
            with spans.span("x", row, "a_ms"):
                raise KeyError("boom")
        assert "a_ms" in row
        with pytest.raises(ValueError, match="names no unit"):
            with spans.span("x", row, "elapsed"):
                pass

    def test_nested_spans_share_a_row(self):
        row = {}
        with spans.span("x", row, "outer_ms", round=2):
            with spans.span("x.a", row, "a_ms", round=2):
                time.sleep(0.002)
            with spans.span("x.b", round=2):      # annotation alone
                pass
        assert set(row) == {"outer_ms", "a_ms"}
        assert 2.0 <= row["a_ms"] <= row["outer_ms"]

    def test_tracked_program_reports_its_build(self):
        built = []
        tp = probe.TrackedProgram("p", jax.jit(lambda a: a + 1),
                                  built=built)
        tp(jnp.ones(3))
        tp(jnp.ones(3))
        assert [name for name, _ in built] == ["p"]
        assert built[0][1] > 0.0


def test_flash_kernels_are_named():
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return pallas_ops.flash_attention(q, k, v, causal=True).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q))
    assert {"flash_fwd", "flash_bwd"} == set(re.findall(r"flash_\w+", text))
    assert np.isfinite(float(loss(q, q, q)))
