"""The trace reduction on small recorded traces kept beside it
(``data/trace_*.json``, cut from traced runs on the v5e in PR 23: every
module event, and the op events of the window's first milliseconds, of one
round boundary with whatever ran between the two rounds, of the window's
end and of the first call of each flash kernel; HLO text shortened): busy
union, window cut, collective and custom-call selection, each against a
count made another way."""

import json
import os

import pytest

from benchmarks.lib import trace

HERE = os.path.dirname(os.path.abspath(__file__))


RECORDED = sorted(f for f in os.listdir(os.path.join(HERE, "data"))
                  if f.startswith("trace_") and f.endswith(".json"))


@pytest.fixture(scope="module", params=RECORDED)
def recorded(request):
    with open(os.path.join(HERE, "data", request.param)) as f:
        d = json.load(f)
    planes = {p: {line: [tuple(e) for e in events]
                  for line, events in lines.items()}
              for p, lines in d["planes"].items()}
    return d["rounds"], planes


def test_a_recorded_trace_is_kept():
    assert RECORDED


def brute_union(intervals):
    """Union length by merging overlapping intervals pair by pair."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged)


def test_names_and_containers():
    text = ("%while.401 = (s32[]{:T(128)}, f32[12,768]{1,0}) while((s32[]) "
            "%tuple.446), condition=%c, body=%b")
    assert trace.op_name(text) == "while.401" and trace.is_container(text)
    assert not trace.is_container("%fusion.578 = f32[2]{0} fusion(f32[2] %x)")
    assert trace.op_name("%all-reduce.4 = f32[8] all-reduce(f32[8] %p)") \
        .startswith(trace.COLLECTIVES)


def test_kernels_are_picked_by_signature():
    kernels = trace.load_kernels()
    tail = '), custom_call_target="tpu_custom_call", operand_layout={}'
    ops = lambda n: ", ".join(f"bf16[4,12,1024,64]{{3,2,1,0}} %a.{i}"
                              for i in range(n))
    fwd = f"%attn.22 = (bf16[4], f32[4]) custom-call({ops(3)}{tail}"
    fwd_eval = f"%attn.25 = bf16[4] custom-call({ops(3)}{tail}"
    dq = f"%attn.23 = bf16[4] custom-call({ops(6)}{tail}"
    dkv = f"%attn.24 = (bf16[4], bf16[4]) custom-call({ops(6)}{tail}"
    other = f'%x.1 = bf16[4] custom-call({ops(6)}), custom_call_target="Sharding"'
    assert [trace.kernel_of(t, kernels) for t in
            (fwd, fwd_eval, dq, dkv, other, "%fusion.1 = f32[] fusion()")] == \
        ["flash_fwd", "flash_fwd", "flash_dq", "flash_dkv", None, None]


def test_window_cut_and_busy_union(recorded):
    rounds, planes = recorded
    out = trace.reduce_planes(planes, rounds)
    assert out["rounds_in_window"] == rounds - 1
    busy, windows = [], []
    for name in sorted(planes):
        mods = planes[name][trace.MODULE_LINE]
        runs = sorted((s, d) for n, s, d in mods if n == out["round_module"])
        assert len(runs) == rounds
        t0, t1 = runs[1][0], max(s + d for s, d in runs)
        windows.append((t1 - t0) / 1e9)
        leaf = [(max(s, t0), min(s + d, t1))
                for text, s, d in planes[name][trace.OPS_LINE]
                if not trace.is_container(text) and s + d > t0 and s < t1]
        busy.append(brute_union(leaf) / 1e9)
    assert out["window_s_by_chip"] == pytest.approx(windows)
    assert out["busy_s_by_chip"] == pytest.approx(busy)
    assert out["window_s"] == pytest.approx(sum(windows) / len(windows))
    assert 0 < out["busy_s"] < out["window_s"]
    # round 0 is cut off: nothing before the 2nd execution counts
    first = sorted(planes)[0]
    early = [(n, s, d) for n, s, d in planes[first][trace.MODULE_LINE]
             if n == out["round_module"]][0]
    assert early[1] + early[2] <= out["window_s_by_chip"][0] * 1e9 + early[1]


def test_collectives_and_kernels_of_the_recorded_trace(recorded):
    rounds, planes = recorded
    out = trace.reduce_planes(planes, rounds)
    first = sorted(planes)[0]
    mods = planes[first][trace.MODULE_LINE]
    runs = sorted((s, d) for n, s, d in mods if n == out["round_module"])
    t0, t1 = runs[1][0], max(s + d for s, d in runs)
    by_hand = sum(min(s + d, t1) - max(s, t0)
                  for text, s, d in planes[first][trace.OPS_LINE]
                  if text.lstrip("%").startswith(trace.COLLECTIVES)
                  and s + d > t0 and s < t1) / 1e9
    if len(planes) > 1:
        assert by_hand > 0, "several chips and no collective in the trace"
    assert out["collective_s_device0"] == pytest.approx(by_hand, rel=1e-6)
    assert set(out["kernels"]) == {"flash_fwd", "flash_dq", "flash_dkv"}
    calls = {k: sum(1 for text, *_ in planes[first][trace.OPS_LINE]
                    if trace.kernel_of(text, trace.load_kernels()) == k)
             for k in out["kernels"]}
    assert {k: v["calls"] for k, v in out["kernels"].items()} == calls
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    assert {w for w, _ in out["breakdown"]["idle_gaps"]} <= {
        "inside_round", "between_rounds"}


def test_no_such_module_is_an_error(recorded):
    _, planes = recorded
    with pytest.raises(ValueError):
        trace.reduce_planes(planes, 99)
