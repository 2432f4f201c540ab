"""The round loop's span primitive (ISSUE 24): what the rows of
``round_timings`` carry in each pipeline mode, that the stamps and the
durations agree, which dispatch built a program, where the wait on the
sync is timed, the host spans in the profiler's trace, and set-up's phases;
the allocator counter beside it and the build's three stages (ISSUE 34).
Counts and control flow only: nothing here is a time of a device, and the
allocator's numbers are a stub's."""

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
BUILD_PARTS = ("build_trace_ms", "build_lower_ms", "build_compile_ms")
BUILD_COUNTS = ("build_cache_hits", "build_cache_misses")
NEW_KEYS = ("t_dispatch_s", "t_ready_s", "wait_ms", "build_ms",
            "programs_built") + BUILD_PARTS + BUILD_COUNTS
HBM_KEYS = ("hbm_in_use_bytes", "hbm_peak_bytes")
PHASES = ["data", "engine", "restore", "probe", "first_prep"]
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


def test_round_0s_build_is_in_three_parts(run):
    """trace + lower + compile-or-load are the build: each part a span's
    duration, together the ``round.build`` span less the few lines between
    them; a round that built nothing reads 0.0 in each."""
    _, res, _ = run
    rows = res["round_timings"]
    parts = [rows[0][k] for k in BUILD_PARTS]
    assert all(p >= 0.0 for p in parts)
    assert sum(parts) <= rows[0]["build_ms"] + 0.002
    assert sum(parts) == pytest.approx(rows[0]["build_ms"], rel=0.05)
    # no compile cache is armed in the tests
    assert [rows[0][k] for k in BUILD_COUNTS] == [0, 0]
    for row in rows[1:]:
        assert [row[k] for k in BUILD_PARTS] == [0.0] * 3
        assert [row[k] for k in BUILD_COUNTS] == [0, 0]


def test_a_backend_without_statistics_writes_no_reading(run):
    """The CPU's ``memory_stats()`` is ``None``: no row has an allocator
    key, ``results["memory"]`` has no ``hbm``, nothing else changes."""
    assert jax.devices()[0].memory_stats() is None
    _, res, _ = run
    for row in res["round_timings"]:
        assert not set(HBM_KEYS) & set(row)
    assert "hbm" not in res["memory"]


class FakeAllocator:
    """Stands in for ``spans.device_stats`` on the eight devices of
    ``mesh8``: ``bytes_in_use`` follows a script a reading, the high mark
    never falls, device ``i`` holds ``i`` KiB more than device 0."""

    def __init__(self):
        self.calls = 0
        self.peak: dict[int, int] = {}

    def __call__(self, device):
        reading, self.calls = self.calls // 8, self.calls + 1
        in_use = (1 << 20) + 65536 * (reading % 7) + 1024 * device.id
        peak = self.peak[device.id] = max(self.peak.get(device.id, 0),
                                          in_use + 512)
        return {"bytes_in_use": in_use, "peak_bytes_in_use": peak,
                "bytes_limit": 1 << 34, "largest_alloc_size": 1 << 19,
                "num_allocs": self.calls, "note": "not a number"}


@pytest.fixture(scope="module")
def hbm_run(mesh8):
    """A call with two rounds in flight (both ``t_ready_s`` sites) under a
    stubbed allocator: the stub is the test's, the program has no option."""
    mp = pytest.MonkeyPatch()
    fake = FakeAllocator()
    mp.setattr(jax, "default_backend", lambda: "tpu")
    try:
        plain = train_global(cfg(), mesh=mesh8, progress=False)
        mp.setattr(spans, "device_stats", fake)
        res = train_global(cfg(), mesh=mesh8, progress=False)
    finally:
        mp.undo()
    return res, fake, plain


def test_every_row_and_every_phase_carries_the_allocators_reading(hbm_run):
    res, fake, _ = hbm_run
    rows = res["round_timings"]
    hbm = res["memory"]["hbm"]
    assert [p["phase"] for p in hbm["phases"]] == PHASES
    stamps = [hbm["on_entry"]] + hbm["phases"] + rows + [hbm["at_end"]]
    for stamp in stamps:
        assert all(isinstance(stamp[k], int) for k in HBM_KEYS)
        assert stamp["hbm_peak_bytes"] > stamp["hbm_in_use_bytes"]
    peaks = [s["hbm_peak_bytes"] for s in stamps]
    assert peaks == sorted(peaks)               # the mark never falls
    assert hbm["limit_bytes"] == 1 << 34
    # the end's reading holds every number the allocator gave, no more
    assert hbm["at_end"]["largest_alloc_size"] == 1 << 19
    assert hbm["at_end"]["num_allocs"] > 0 and "note" not in hbm["at_end"]
    # one reading a device at entry, a phase, a round and the end
    assert fake.calls == 8 * len(stamps)
    # the fullest of the mesh's eight devices is the one recorded
    assert all((s["hbm_in_use_bytes"] - (1 << 20)) % 65536 == 7 * 1024
               for s in stamps)


def test_the_rest_of_the_run_is_the_unstubbed_runs(hbm_run):
    res, _, plain = hbm_run
    assert res["all_epochs_losses"] == plain["all_epochs_losses"]
    for row, twin in zip(res["round_timings"], plain["round_timings"]):
        assert set(row) == set(twin) | set(HBM_KEYS)
    assert set(res["memory"]) == set(plain["memory"]) | {"hbm"}


class TestHbm:
    """``spans.hbm`` on stand-in devices."""

    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    def test_the_fullest_device_is_the_one_recorded(self):
        devs = [self.Dev({"bytes_in_use": 10, "peak_bytes_in_use": 90}),
                self.Dev({"bytes_in_use": 30, "peak_bytes_in_use": 70}),
                self.Dev(None)]
        row = {"compute_ms": 1.0}
        fullest = spans.hbm(row, devs)
        assert row == {"compute_ms": 1.0, "hbm_in_use_bytes": 30,
                       "hbm_peak_bytes": 90}
        assert fullest is devs[0].stats

    def test_no_statistics_no_key(self):
        row = {"compute_ms": 1.0}
        assert spans.hbm(row, [self.Dev(None), self.Dev({})]) is None
        assert spans.hbm(row, []) is None
        assert row == {"compute_ms": 1.0}

    def test_memory_report_carries_it_only_where_there_is_one(self):
        assert "hbm" not in probe.memory_report({})
        assert "hbm" not in probe.memory_report({}, hbm=None)
        hbm = {"phases": [], "at_end": {"hbm_peak_bytes": 3}}
        assert probe.memory_report({}, hbm=hbm)["hbm"] is hbm


def test_the_setup_line_names_the_last_rise_of_the_peak():
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.driver import _last_peak_rise
    gib = 2**30
    hbm = {"on_entry": {"hbm_peak_bytes": gib}, "phases": [
        {"phase": "data", "hbm_peak_bytes": gib},
        {"phase": "engine", "hbm_peak_bytes": 3 * gib},
        {"phase": "probe", "hbm_peak_bytes": 3 * gib + 2**21}]}
    rows = [{"hbm_peak_bytes": 3 * gib + 2**21}, {"compute_ms": 1.0}]
    assert _last_peak_rise(hbm, rows) == (
        "; HBM peak 3.002 GiB, last raised at 'probe' by 2.0 MiB")
    rows[1]["hbm_peak_bytes"] = 4 * gib
    assert _last_peak_rise(hbm, rows).endswith(
        "last raised at 'round 1' by 1022.0 MiB")
    assert _last_peak_rise(dict(hbm, phases=hbm["phases"][:1]), []) == (
        "; HBM peak 1.000 GiB, as on entry")
    assert _last_peak_rise({"on_entry": {}, "phases": []}, [{}]) == ""


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
    spans_at: dict[str, list] = {}
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("round.", "setup.")):
                    counts[e.name] = counts.get(e.name, 0) + 1
                    ids.setdefault(e.name, []).append(dict(e.stats))
                    spans_at.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
    assert counts["round.dispatch"] == rounds
    assert counts["round.wait"] == rounds
    assert counts["round.prep"] == rounds - 1
    assert counts["round.fetch"] == counts["round.assemble"] == rounds
    assert counts["round.build"] == 1 and counts["setup.first_prep"] == 1
    assert sorted(d["round"] for d in ids["round.dispatch"]) == [0, 1]
    assert ids["round.build"] == [{"program": "round"}]
    # the build's three stages, one after the other inside round.build,
    # which lies inside round 0's dispatch
    build, dispatch0 = spans_at["round.build"][0], min(
        spans_at["round.dispatch"])
    stages = [spans_at[f"round.build.{part}"] for part in (
        "trace", "lower", "compile")]
    assert [len(s) for s in stages] == [1, 1, 1]
    edges = [dispatch0[0], build[0]] + [t for s in stages for t in s[0]] + [
        build[1], dispatch0[1]]
    assert edges == sorted(edges)
    for part in ("trace", "lower", "compile"):
        assert ids[f"round.build.{part}"] == [{"program": "round"}]


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
        row = built[0][1]
        assert set(row) == {"build_ms", *BUILD_PARTS, *BUILD_COUNTS}
        assert row["build_ms"] > 0.0
        assert 0.0 <= sum(row[k] for k in BUILD_PARTS) <= (
            row["build_ms"] + 0.002)

    def test_the_cache_counts_are_the_compile_stages_delta(self,
                                                           monkeypatch):
        """What the persistent cache said between the start and the end
        of ``compile()``: a stubbed counter that another lookup has
        already moved, and that serves this program."""
        counts = iter([{"hits": 3, "misses": 5}, {"hits": 4, "misses": 5},
                       {"hits": 4, "misses": 5}, {"hits": 4, "misses": 7}])
        monkeypatch.setattr(probe, "compile_cache_counts",
                            lambda: next(counts))
        built = []
        for name in ("loaded", "compiled"):
            probe.TrackedProgram(name, jax.jit(lambda a: a + 1),
                                 built=built)(jnp.ones(3))
        assert [(r["build_cache_hits"], r["build_cache_misses"])
                for _, r in built] == [(1, 0), (0, 2)]
        folded = probe.fold_builds(built)
        assert folded["programs_built"] == ["loaded", "compiled"]
        assert (folded["build_cache_hits"],
                folded["build_cache_misses"]) == (1, 2)
        assert folded["build_ms"] == pytest.approx(
            sum(r["build_ms"] for _, r in built), abs=1e-3)
        assert probe.fold_builds([]) == {
            "build_ms": 0.0, "build_trace_ms": 0.0, "build_lower_ms": 0.0,
            "build_compile_ms": 0.0, "build_cache_hits": 0,
            "build_cache_misses": 0, "programs_built": []}


def test_flash_kernels_are_named():
    from learning_deep_neural_network_in_distributed_computing_environment_tpu.ops import pallas_ops
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return pallas_ops.flash_attention(q, k, v, causal=True).sum()
    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, q))
    # the two kernels, and the names a remat policy keeps the forward's
    # outputs by (ISSUE 35: identities, not calls)
    assert {"flash_fwd", "flash_bwd", "flash_out", "flash_lse"} == set(
        re.findall(r"flash_\w+", text))
    assert np.isfinite(float(loss(q, q, q)))
