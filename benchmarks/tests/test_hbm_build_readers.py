"""The five readers of ISSUE 34, each on hand-made rows: the allocator's
two (``hbm_held_gib``, ``hbm_transient_gib``) and the build's three
(``round0_trace_s``, ``round0_lower_s``, ``round0_compile_s``); the number
worked out by hand, nothing on rows without the key, and the rows of a
tiny cell on the CPU, whose allocator keeps no statistics.  Nothing here
is a reading of a device."""

import importlib
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.entries import train_global as tg
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny")
SEED = 2147483659
GIB = 2**30
NAMES = ("hbm_held_gib", "hbm_transient_gib", "round0_trace_s",
         "round0_lower_s", "round0_compile_s")


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


def ctx_of(rows, rounds):
    return {"results": {"round_timings": rows}, "rounds": rounds}


# round 0 is set-up: its dispatch built two programs, 9.5 s of tracing,
# 2.25 of lowering, 3 of loading from the cache.  Rounds 1..4 are the
# window: the fullest chip holds 8, 8.5, 8.25 and 8.5 GiB as each is seen
# ready, and the process's high mark, 12 GiB since set-up, stands at 12.5
# after round 3.
ROWS = [
    {"build_ms": 14800.0, "build_trace_ms": 9500.0, "build_lower_ms": 2250.0,
     "build_compile_ms": 3000.0, "build_cache_hits": 2,
     "build_cache_misses": 0, "programs_built": ["round", "sync"],
     "hbm_in_use_bytes": 5 * GIB, "hbm_peak_bytes": 12 * GIB},
] + [
    {"build_ms": 0.0, "build_trace_ms": 0.0, "build_lower_ms": 0.0,
     "build_compile_ms": 0.0, "build_cache_hits": 0, "build_cache_misses": 0,
     "programs_built": [], "hbm_in_use_bytes": int(held * GIB),
     "hbm_peak_bytes": int(peak * GIB)}
    for held, peak in ((8, 12), (8.5, 12), (8.25, 12.5), (8.5, 12.5))
]


@pytest.mark.parametrize("name,expected", [
    ("hbm_held_gib", 8.375),             # median of 8, 8.5, 8.25, 8.5
    ("hbm_transient_gib", 12.5 - 8.375),   # row 4's mark over that median
    ("round0_trace_s", 9.5),
    ("round0_lower_s", 2.25),
    ("round0_compile_s", 3.0),
])
def test_reads_the_number_worked_out_by_hand(name, expected):
    assert reader(name)(ctx_of(ROWS, 4)) == pytest.approx(expected, rel=1e-12)


def test_the_two_allocator_metrics_add_up_to_the_last_rows_mark():
    got = [reader(n)(ctx_of(ROWS, 4)) for n in NAMES[:2]]
    assert sum(got) == 12.5
    # a window shorter than the rows (the traced run's trace_rounds)
    assert reader("hbm_held_gib")(ctx_of(ROWS, 2)) == 8.25
    assert reader("hbm_transient_gib")(ctx_of(ROWS, 2)) == 12 - 8.25


@pytest.mark.parametrize("name,keys", [
    ("hbm_held_gib", ["hbm_in_use_bytes"]),
    ("hbm_transient_gib", ["hbm_in_use_bytes"]),
    ("hbm_transient_gib", ["hbm_peak_bytes"]),
    ("round0_trace_s", ["build_trace_ms"]),
    ("round0_lower_s", ["build_lower_ms"]),
    ("round0_compile_s", ["build_compile_ms"]),
])
def test_rows_without_the_key_read_nothing(name, keys):
    rows = [{k: v for k, v in r.items() if k not in keys} for r in ROWS]
    assert reader(name)(ctx_of(rows, 4)) is None


@pytest.mark.parametrize("name", NAMES)
def test_rows_of_the_parent_commit_read_nothing(name):
    """The parent's rows under this PR's benchmark files, and a call that
    recorded no round: nothing, and nothing raised."""
    bare = [{"compute_ms": 1.0, "build_ms": 3.0}] * 3
    assert reader(name)(ctx_of(bare, 2)) is None
    assert reader(name)(ctx_of([], 0)) is None


def test_one_window_row_without_a_reading_silences_the_median():
    rows = [dict(r) for r in ROWS]
    del rows[2]["hbm_in_use_bytes"]
    assert reader("hbm_held_gib")(ctx_of(rows, 4)) is None
    assert reader("hbm_transient_gib")(ctx_of(rows, 4)) is None
    assert reader("hbm_held_gib")(ctx_of(rows, 1)) == 8.0


def test_the_rows_of_a_tiny_cell_on_the_cpu():
    """Row 0 has the three parts, which the readers hand on in seconds;
    the CPU's allocator reports nothing, so the two HBM readers do not."""
    spec = bench_run.load_spec("lm1", TINY)
    c, w = spec["config"], spec["workload"]
    rows = traffic.generate(w["traffic"], c, SEED, 1)
    results = tg.timed_call(tg.build_argv(c, w, SEED, 3), rows,
                            c["vocab_size"])[0]
    ctx = {"results": {"round_timings": results["round_timings"],
                       "memory": dict(results["memory"])}, "rounds": 2}
    row0 = results["round_timings"][0]
    parts = [reader(n)(ctx) for n in NAMES[2:]]
    assert parts == [row0[k] / 1e3 for k in (
        "build_trace_ms", "build_lower_ms", "build_compile_ms")]
    assert all(p >= 0.0 for p in parts)
    assert sum(parts) == pytest.approx(
        reader("round0_build_s")(ctx), rel=0.05)
    assert [reader(n)(ctx) for n in NAMES[:2]] == [None, None]
    assert "hbm" not in ctx["results"]["memory"]


def test_every_new_metric_has_its_entry_and_its_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # by name, not by place: the next PR appends after them
    entries = {m["name"]: m for m in bench["per_layer"]}
    for m in (entries[name] for name in NAMES):
        hbm = m["name"].startswith("hbm_")
        assert m == {"name": m["name"], "unit": "GiB" if hbm else "s",
                     "better": "lower",
                     "source": "program_counter" if hbm else "program_span",
                     "layer": "round program",
                     "moves": "hbm_peak_gib" if hbm else "setup_s"}
        assert callable(reader(m["name"]))
    for cell in (w["name"] for w in bench["workloads"]):    # all six
        assert set(NAMES) <= {m["name"] for m in bench_run.metrics_of_cell(
            bench, cell, "per_layer")}
