"""Readings of the reference's own controls for a latent-attention cell,
taken on the chip at the cell's size (PERF.md, section 4): the reference in
fp8 in the program's place, half of the batch left out, and the two planted
faults of a misplaced selection bias (added to the weights; dropped from the
choice), each held against the float32 reference and the cell's limits.

    python3 benchmarks/tests/chip_controls_mla.py <cell> <seed> [<seed> ...]

One JSON line a seed on stdout and in
``chiprun_out/controls_mla_<cell>.jsonl``.  The program's own numbers are
in every benchmark run's ``compared``; the benchmark's runs never run this
file.  One worker a cell (the bias is no matter of an exchange).
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONTROLS = {"fp8": {"precision": "fp8"}, "half_batch": {"half_batch": True},
            "bias_in_weights": {"bias": "in_weights"},
            "bias_dropped": {"bias": "dropped"}}


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
    steps = int(workload["check"]["steps"])
    limits = workload["check"]["limits"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for seed in seeds:
        t0 = time.perf_counter()
        x, y = traffic.generate(t, config, seed, 1)["train"]
        xs, ys = check.check_rows(x, y, t, 1, steps)
        ref = check.reference_reading(config, t, x, y, seed, 1, steps,
                                      jax.devices()[:1])
        line = {"cell": cell, "seed": seed,
                "reference_s": time.perf_counter() - t0}
        for name, kw in CONTROLS.items():
            losses, g1, after = reference.train_steps(
                config, ref["p0"], xs[0], ys[0],
                lr=float(config["recipe"]["lr"]), **kw)
            got = {"losses": np.asarray(losses, np.float64)[None],
                   "grad_norms": [compare.block_norms(g1)],
                   "update_norms": compare.block_norms(
                       compare.tree_sub(after, ref["p0"]))}
            values, where = check.numbers(got, ref)
            line[name] = {"values": values, "where": where, "over": sorted(
                k for k, v in values.items() if not v <= limits[k])}
            del g1, after
        del ref         # 2.1 GiB that the next seed's reference needs
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        with open(os.path.join(out_dir, f"controls_mla_{cell}.jsonl"),
                  "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
