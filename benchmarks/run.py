"""The benchmark's command: one run of one cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name from
``BENCHMARK.json``: ``benchmarks/configs/<config>.json`` (the sizes),
``benchmarks/workloads/<cell>.json`` (entry point, traffic parameters,
flags, limits of the comparison) and, for the traced run,
``benchmarks/layer_metrics/<metric>.py`` (one reader a metric).  PERF.md
says how to add each.  The run's last stdout line is its result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench_work")          # traces; gitignored


def load_spec(cell: str, root: str = ROOT) -> dict:
    """``root`` holds BENCHMARK.json and benchmarks/{configs,workloads}."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise SystemExit(f"unknown workload {cell!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    entry = cells[cell]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[entry["config"]]
    with open(os.path.join(root, cfg_file)) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmarks", "workloads",
                           f"{cell}.json")) as f:
        workload = json.load(f)
    return {"bench": bench, "cell": entry, "config": config,
            "workload": workload}


def metrics_of_cell(bench: dict, cell: str, group: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def find_devices(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"no accelerator: JAX found platform "
                         f"{devices[0].platform!r}; this benchmark measures "
                         "a TPU and has no other path")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s); JAX found "
                         f"{len(devices)}")
    if not require_tpu:
        return devices[:chips]       # tests: the fixture cells pin workers
    if len(devices) != chips:
        raise SystemExit(
            f"the cell asks for {chips} chip(s) and the program's default "
            f"mesh takes every device it finds ({len(devices)}): run it on "
            "a machine that holds exactly the chips the cell names")
    return devices


def device_block(devices, peak_bytes: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak_bytes)}


def run(argv=None, *, require_tpu: bool = True, root: str = ROOT
        ) -> tuple[int, dict | None]:
    """One run.  Returns (exit code, the result object or None).
    ``require_tpu=False`` and ``root`` are for the benchmark's own tests,
    which rehearse the control flow on the CPU at a tiny size; the command
    line cannot reach either."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload, root)
    chips = int(spec["cell"]["chips"])
    devices = find_devices(chips, require_tpu)
    entry = importlib.import_module(
        f"benchmarks.entries.{spec['workload']['entry']}")
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(WORK, f"trace_{args.workload}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    try:
        out = entry.run_cell(spec, args.seed, args.seconds, devices,
                             trace_dir=trace_dir, t_process=T_PROCESS)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if out.get("abort"):
        print(f"run aborted: {out['abort']}", file=sys.stderr)
        return 3, None
    group = "per_layer" if args.trace else "end_to_end"
    units = {m["name"]: m["unit"] for m in spec["bench"][group]}
    wanted = [m["name"] for m in
              metrics_of_cell(spec["bench"], args.workload, group)]
    metrics = {}
    if args.trace:
        ctx = out["layer_context"]
        for name in wanted:
            reader = importlib.import_module(
                f"benchmarks.layer_metrics.{name.replace('.', '_')}")
            value = reader.read(ctx)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": units[name]}
    else:
        for name in wanted:
            metrics[name] = {"value": float(out["end_to_end"][name]),
                             "unit": units[name]}
    device = device_block(devices, out["memory_peak_bytes"])
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if args.trace:
        device["busy_s"] = out["layer_context"]["trace"]["busy_s"]
        device["window_s"] = out["layer_context"]["trace"]["window_s"]
        result["breakdown"] = out["layer_context"]["trace"]["breakdown"]
    result["notes"] = out["notes"]
    result["compared"] = out["compared"]
    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g}) "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0, result


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(run()[0])
