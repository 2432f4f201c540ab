"""Readings the limits of a cell's comparison are set from, taken on the
chip at the cell's own size (PERF.md, "How `correct` is decided here").

    python3 benchmarks/tests/chip_controls.py <cell> <seed> [<seed> ...]

For every seed, in one process: the program's numbers (the runner's own two
short calls, no measured window: a training cell's readings need none), the
control's (the reference in the program's place in fp8 and in int8), and
each planted fault's (half of the batch left out; on several chips the
exchange left out; a state left unchanged reads 1 by construction and is
not run).  One JSON line a seed on stdout and in
``chiprun_out/controls_<cell>.jsonl``; every reading's per-leaf norms go to
``chiprun_out/leaves_<cell>.jsonl``, so that another number can be tried on
the same runs.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def reading_norms(reading: dict) -> dict:
    """A reading's per-leaf norms and losses as plain lists, kept beside
    the numbers so that another number can be tried on the same runs."""
    return {"losses": [list(map(float, row)) for row in reading["losses"]],
            "grad_norms": reading["grad_norms"],
            "update_norms": reading["update_norms"]}


def main(argv) -> int:
    from benchmarks import run as bench_run
    from benchmarks.entries import train_global as tg
    from benchmarks.lib import check, compare, traffic

    cell, seeds = argv[0], [int(s) for s in argv[1:]]
    spec = bench_run.load_spec(cell)
    config, workload = spec["config"], spec["workload"]
    t = workload["traffic"]
    devices = bench_run.find_devices(int(spec["cell"]["chips"]), True)
    workers = len(devices)
    steps = int(workload["check"]["steps"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"controls_{cell}.jsonl"), "a") as log:
        for seed in seeds:
            t0 = time.perf_counter()
            rows = traffic.generate(t, config, seed, workers)
            x, y = rows["train"]
            state = tg.expose_state(config, workload, rows, seed, workers)
            ref = check.reference_reading(config, t, x, y, seed, workers,
                                          steps, devices[:1])
            got = {"losses": state["losses_check"],
                   "grad_norms": state["grad_norms"],
                   "update_norms": compare.block_norms(compare.tree_sub(
                       state["params_after"], ref["p0"]))}
            line = {"cell": cell, "seed": seed,
                    "program": check.numbers(got, ref)}
            leaves = {"seed": seed, "reference": reading_norms(ref),
                      "program": reading_norms(got)}
            for precision in ("fp8", "int8"):
                ctl = check.reference_reading(
                    config, t, x, y, seed, workers, steps, devices[:1],
                    precision=precision)
                line[precision] = check.numbers(ctl, ref)
                leaves[precision] = reading_norms(ctl)
            faults = ["half_batch"] + (["no_exchange"] if workers > 1 else [])
            for fault in faults:
                bad = check.reference_reading(
                    config, t, x, y, seed, workers, steps, devices[:1],
                    fault=fault)
                line[fault] = check.numbers(bad, ref)
                leaves[fault] = reading_norms(bad)
            line["seconds"] = time.perf_counter() - t0
            text = json.dumps(line)
            print(text, flush=True)
            log.write(text + "\n")
            log.flush()
            with open(os.path.join(out_dir, f"leaves_{cell}.jsonl"),
                      "a") as f:
                f.write(json.dumps(leaves) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
