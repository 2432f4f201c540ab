"""From the profiler's trace to numbers: the benchmark's own reduction.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX.  What a v5e
trace looks like under libtpu 0.0.34 (looked at by hand in PR 23, PERF.md
section 3): one plane a chip, ``/device:TPU:<i>``, whose line ``XLA
Modules`` has one event for each execution of a compiled program
(``jit_<name>(<fingerprint>)``) and whose line ``XLA Ops`` has one event
for each operation that ran.  An event's name is the WHOLE text of its HLO
instruction (``%fusion.578 = (f32[50257,768]{...}) fusion(...), ...``), so
the instruction's own name is what stands before `` = ``.  Loops are events
too (``%while.401`` lasts a whole round and holds every operation of it),
so sums and the busy union take the operations that are not containers.  A
Mosaic kernel is a ``custom-call`` whose text holds
``custom_call_target="tpu_custom_call"``; its name (``%attn.22``) comes from
the flax scope and HLO numbering and is not stable, so a kernel is picked by
its signature, one data file a kernel under ``benchmarks/kernels/``.

The reduction works on plain event lists, ``{plane: {line: [(name,
start_ns, duration_ns), ...]}}``, so that the test beside it can hold a
small recorded trace as JSON.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
CONTAINERS = ("while", "conditional", "call")
KERNEL_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "kernels")


def op_name(text: str) -> str:
    """``%fusion.578 = ...`` -> ``fusion.578``."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_container(text: str) -> bool:
    return op_name(text).split(".")[0] in CONTAINERS


def load_kernels(directory: str = KERNEL_DIR) -> list[dict]:
    import json
    out = []
    for f in sorted(os.listdir(directory)):
        if f.endswith(".json"):
            with open(os.path.join(directory, f)) as fh:
                out.append(json.load(fh))
    return out


def kernel_of(text: str, kernels: list[dict]) -> str | None:
    """Which described kernel an op event is, by its custom call's target,
    operand count and output kind."""
    if " custom-call(" not in text:
        return None
    head, rest = text.split(" custom-call(", 1)
    for k in kernels:
        marker = f'), custom_call_target="{k["custom_call_target"]}"'
        if marker not in rest:
            continue
        # every operand reads "<type> %<name>"
        operands = rest.split(marker, 1)[0].count("%")
        if operands != k["operands"]:
            continue
        tuple_out = head.split(" = ", 1)[1].lstrip().startswith("(")
        if "tuple_output" in k and tuple_out != k["tuple_output"]:
            continue
        return k["kernel"]
    return None


def read_xplane(path: str) -> dict:
    """Device planes of one ``.xplane.pb`` as plain event lists."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {}
        for line in plane.lines:
            if line.name in (MODULE_LINE, OPS_LINE):
                lines[line.name] = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events]
        planes[plane.name] = lines
    return planes


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def round_executions(modules: list, rounds: int) -> list:
    """The executions of the round program: the module that ran exactly
    ``rounds`` times and took the most device time."""
    by_name: dict[str, list] = {}
    for name, start, dur in modules:
        by_name.setdefault(name, []).append((start, dur))
    candidates = {k: v for k, v in by_name.items() if len(v) == rounds}
    if not candidates:
        raise ValueError(
            f"no module ran exactly {rounds} times; counts: "
            f"{ {k: len(v) for k, v in by_name.items()} }")
    name = max(candidates, key=lambda k: sum(d for _, d in candidates[k]))
    return name, sorted(candidates[name])


def _union(intervals: list) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def _clip(events: list, t0: float, t1: float) -> list:
    out = []
    for name, start, dur in events:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            out.append((name, s, e))
    return out


def reduce_planes(planes: dict, rounds: int, kernels: list[dict] | None = None
                  ) -> dict:
    """Busy union, window cut, collective and kernel selection.

    The window runs from the start of the round program's 2nd execution
    to the end of its last, on each chip: round 0 re-traces and loads, and
    is set-up.  ``busy_s`` is the union of the intervals in which an
    operation that is not a container ran, averaged over the chips; sums
    by operation are chip 0's."""
    kernels = load_kernels() if kernels is None else kernels
    names = sorted(planes, key=lambda n: int(DEVICE_PLANE.match(n).group(1)))
    busy, windows = [], []
    first = None
    for pname in names:
        lines = planes[pname]
        module, execs = round_executions(lines[MODULE_LINE], rounds)
        t0 = execs[1][0]
        t1 = max(s + d for s, d in execs)
        ops = _clip([e for e in lines[OPS_LINE] if not is_container(e[0])],
                    t0, t1)
        busy.append(_union([(s, e) for _, s, e in ops]) / 1e9)
        windows.append((t1 - t0) / 1e9)
        if first is None:
            first = (module, execs, t0, t1, ops)
    module, execs, t0, t1, ops = first
    by_op: dict[str, float] = {}
    collective_s = 0.0
    found: dict[str, dict] = {}
    for text, s, e in ops:
        name = op_name(text)
        sec = (e - s) / 1e9
        by_op[name] = by_op.get(name, 0.0) + sec
        if name.startswith(COLLECTIVES):
            collective_s += sec
        kernel = kernel_of(text, kernels)
        if kernel:
            k = found.setdefault(kernel, {"seconds": 0.0, "calls": 0,
                                          "names": []})
            k["seconds"] += sec
            k["calls"] += 1
            if name not in k["names"]:
                k["names"].append(name)
    # idle gaps of chip 0, labelled by their place against the round
    # program's executions
    spans = sorted((s, e) for _, s, e in ops)
    inside = [(s, s + d) for s, d in execs[1:]]
    gaps, end = [], t0
    for s, e in spans:
        if s > end:
            mid = (s + end) / 2
            where = ("inside_round" if any(a <= mid <= b for a, b in inside)
                     else "between_rounds")
            gaps.append((where, (s - end) / 1e9))
        end = max(end, e)
    if t1 > end:
        gaps.append(("between_rounds", (t1 - end) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    # idle time of chip 0 between consecutive executions of the round
    # program (whatever else ran there, a sync program say, is not idle)
    between = []
    for (s0, d0), (s1, _) in zip(execs[1:], execs[2:]):
        lo, hi = s0 + d0, s1
        ran = _union([(max(s, lo), min(e, hi)) for s, e in spans
                      if e > lo and s < hi])
        between.append(max(hi - lo - ran, 0.0) / 1e9)
    return {
        "round_module": module,
        "rounds_in_window": rounds - 1,
        "window_s": sum(windows) / len(windows),
        "busy_s": sum(busy) / len(busy),
        "window_s_by_chip": windows,
        "busy_s_by_chip": busy,
        "collective_s_device0": collective_s,
        "kernels": found,
        "between_round_idle_s": between,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                by_op.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[w, sec] for w, sec in gaps[:10]],
        },
    }


def reduce_dir(trace_dir: str, rounds: int) -> dict:
    return reduce_planes(read_xplane(find_xplane(trace_dir)), rounds)
