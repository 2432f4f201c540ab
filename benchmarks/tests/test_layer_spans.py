"""The five readers of the round loop's spans, each on hand-made rows:
the number worked out by hand, and nothing on rows without the key."""

import importlib

import pytest


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}").read


def ctx_of(rows, rounds):
    return {"results": {"round_timings": rows}, "rounds": rounds}


# round 0 is set-up; rounds 1..4 are the window.  Dispatches 100 ms apart
# from 10.0 s, two rounds in flight: round r is settled in the iteration
# that dispatches round r+1, so its wait ends (t_ready_s) after that
# dispatch.  Inside the loop's time, 10.0 to 10.3 s, the main thread is
# blocked 90 ms settling round 0, 80 ms round 1, 95 ms round 2 and the
# first 10 of the 100 ms it takes to settle round 3, and spends 4 + 3 + 2
# ms in the puts of rounds 1 to 3 (round 0's lies before, round 4 has
# none).
ROWS = [
    {"stage_ms": 3600.0, "prep_ms": 9.0, "h2d_ms": 5.0, "wait_ms": 90.0,
     "t_dispatch_s": 6.0, "t_ready_s": 10.095, "build_ms": 3400.0,
     "programs_built": ["round"]},
    {"stage_ms": 2.0, "prep_ms": 8.0, "h2d_ms": 4.0, "wait_ms": 80.0,
     "t_dispatch_s": 10.0, "t_ready_s": 10.185, "build_ms": 0.0,
     "programs_built": []},
    {"stage_ms": 5.0, "prep_ms": 6.0, "h2d_ms": 3.0, "wait_ms": 95.0,
     "t_dispatch_s": 10.1, "t_ready_s": 10.298, "build_ms": 0.0,
     "programs_built": []},
    {"stage_ms": 3.0, "prep_ms": 7.0, "h2d_ms": 2.0, "wait_ms": 100.0,
     "t_dispatch_s": 10.2, "t_ready_s": 10.39, "build_ms": 0.0,
     "programs_built": []},
    {"stage_ms": 4.0, "wait_ms": 70.0,
     "t_dispatch_s": 10.3, "t_ready_s": 10.5, "build_ms": 0.0,
     "programs_built": []},
]


@pytest.mark.parametrize("name,expected", [
    ("round_dispatch_ms", 3.5),            # median of 2, 5, 3, 4
    ("round_prep_ms", 7.0),                # median of 8, 6, 7: round 4 has none
    ("round_h2d_ms", 3.0),                 # median of 4, 3, 2
    # waiting 90 + 80 + 95 + 10 ms and putting 4 + 3 + 2 ms of the 300 ms
    # from dispatch 1 to 4
    ("host_busy_share", 100 * (1 - 0.284 / 0.3)),
    ("round0_build_s", 3.4),
])
def test_reads_the_number_worked_out_by_hand(name, expected):
    assert reader(name)(ctx_of(ROWS, 4)) == pytest.approx(expected, rel=1e-9)


@pytest.mark.parametrize("name,keys", [
    ("round_dispatch_ms", ["stage_ms"]),
    ("round_prep_ms", ["prep_ms"]),
    ("host_busy_share", ["wait_ms"]),
    ("host_busy_share", ["t_dispatch_s"]),
    ("host_busy_share", ["t_ready_s"]),
    ("host_busy_share", ["h2d_ms"]),
    ("round_h2d_ms", ["h2d_ms"]),
    ("round0_build_s", ["build_ms"]),
])
def test_rows_without_the_key_read_nothing(name, keys):
    rows = [{k: v for k, v in r.items() if k not in keys} for r in ROWS]
    assert reader(name)(ctx_of(rows, 4)) is None


def test_rows_of_the_parent_commit_read_nothing_new():
    """The rows as they were before the spans: the two durations that
    were there are read, the three new quantities are not, none raises."""
    old = [{k: r[k] for k in ("stage_ms", "prep_ms") if k in r} | {
        "sync_ms": 0.0} for r in ROWS]
    got = {n: reader(n)(ctx_of(old, 4)) for n in (
        "round_dispatch_ms", "round_prep_ms", "round_h2d_ms",
        "host_busy_share", "round0_build_s")}
    assert got == {"round_dispatch_ms": 3.5, "round_prep_ms": 7.0,
                   "round_h2d_ms": None, "host_busy_share": None,
                   "round0_build_s": None}


def test_every_new_metric_has_its_entry_and_its_file():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name, moves in (("round_dispatch_ms", "train_tokens_per_s"),
                        ("round_prep_ms", "train_tokens_per_s"),
                        ("round_h2d_ms", "train_tokens_per_s"),
                        ("host_busy_share", "train_tokens_per_s"),
                        ("round0_build_s", "setup_s")):
        assert per_layer[name]["source"] == "program_span"
        assert per_layer[name]["moves"] == moves
        assert callable(reader(name))
        assert "workloads" not in per_layer[name]     # read in every cell


def test_host_busy_share_without_rounds_in_flight():
    """Serial and overlapped rounds are settled in their own iteration:
    40 ms blocked and 10 ms in the put in each of the three 100 ms
    iterations the loop's time holds, and the last round's wait lies
    after it."""
    rows = [{"t_dispatch_s": 9.0, "t_ready_s": 9.9, "wait_ms": 500.0,
             "h2d_ms": 300.0}]
    rows += [{"t_dispatch_s": 10.0 + 0.1 * i, "t_ready_s": 10.09 + 0.1 * i,
              "wait_ms": 40.0, "h2d_ms": 10.0} for i in range(3)]
    rows += [{"t_dispatch_s": 10.3, "t_ready_s": 10.39, "wait_ms": 40.0}]
    assert reader("host_busy_share")(ctx_of(rows, 4)) == pytest.approx(50.0)
