"""Readings of the reference's own controls for an AFMoE cell, taken on the
chip at the cell's size (PERF.md, section 4): the reference in fp8 in the
program's place, half of the batch left out, and a planted fault for each
of the architecture's own parts (``references/afmoe.py::FAULTS``: the gate
dropped, the rotary applied on the full layer too, the q / k norms
dropped, the bias added to the weights, the bias rule switched off), each
held against the float32 reference and the cell's limits.  For the rule
switched off the line also gives what no limit reads: how far the
reference's biases ended from the control's.

    python3 benchmarks/tests/chip_controls_afmoe.py <cell> <seed> [<seed> ...]

One JSON line a seed on stdout and in
``chiprun_out/controls_afmoe_<cell>.jsonl``.  The program's own numbers are
in every benchmark run's ``compared``; the benchmark's runs never run this
file.  One worker a cell (the rule is no matter of an exchange here).
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import numpy as np

    import jax

    from benchmarks import run as bench_run
    from benchmarks.lib import check, compare, traffic

    cell, seeds = argv[0], [int(s) for s in argv[1:]]
    spec = bench_run.load_spec(cell)
    config, workload = spec["config"], spec["workload"]
    t = workload["traffic"]
    bench_run.find_devices(1, True)
    reference = check.reference_of(config)
    controls = dict({"fp8": {"precision": "fp8"},
                     "half_batch": {"half_batch": True}},
                    **{f: {"fault": f} for f in reference.FAULTS})
    steps = int(workload["check"]["steps"])
    limits = workload["check"]["limits"]
    lr = float(config["recipe"]["lr"])
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    biases = lambda p: np.stack([np.asarray(layer["moe"]["select_bias"])
                                 for layer in p["layers"].values()])
    for seed in seeds:
        t0 = time.perf_counter()
        x, y = traffic.generate(t, config, seed, 1)["train"]
        xs, ys = check.check_rows(x, y, t, 1, steps)
        ref = check.reference_reading(config, t, x, y, seed, 1, steps,
                                      jax.devices()[:1])
        # the reference's own biases after the steps, for the rule's control
        _, _, after = reference.train_steps(config, ref["p0"], xs[0], ys[0],
                                            lr=lr)
        moved = biases(after)
        del after
        line = {"cell": cell, "seed": seed,
                "reference_s": time.perf_counter() - t0,
                "bias_moved_mean_abs": float(np.abs(
                    moved - biases(ref["p0"])).mean())}
        for name, kw in controls.items():
            losses, g1, after = reference.train_steps(
                config, ref["p0"], xs[0], ys[0], lr=lr, **kw)
            got = {"losses": np.asarray(losses, np.float64)[None],
                   "grad_norms": [compare.block_norms(g1)],
                   "update_norms": compare.block_norms(
                       compare.tree_sub(after, ref["p0"]))}
            values, where = check.numbers(got, ref)
            line[name] = {"values": values, "where": where, "over": sorted(
                k for k, v in values.items() if not v <= limits[k]),
                "bias_gap_max": float(np.abs(biases(after) - moved).max())}
            del g1, after
        del ref         # the next seed's reference needs the room
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        with open(os.path.join(out_dir, f"controls_afmoe_{cell}.jsonl"),
                  "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
