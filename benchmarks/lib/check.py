"""Readings for the comparison: what the reference gives on the rows of a
run, and the numbers that come of holding the program's readings (or a
control's, or a planted fault's) against them.

A *reading* is ``{"losses": [workers, steps], "grad_norms": [one {block:
norm} a worker], "update_norms": {block: norm}}``: each worker's first
losses, the block norms of each worker's first gradient, and the block
norms of the parameters' change after ``steps`` steps, the workers'
parameters averaged as the round's synchronisation does.
"""

from __future__ import annotations

import numpy as np

import jax

import importlib

from benchmarks.lib import compare

FAULTS = ("half_batch", "no_exchange", "state_unchanged")


def reference_of(config: dict):
    """The configuration's plain reference, found by the name its file
    gives: ``benchmarks/references/<name>.py``."""
    return importlib.import_module(
        f"benchmarks.references.{config['reference']}")


def check_rows(x, y, traffic: dict, workers: int, steps: int):
    """[workers, steps, batch, L] blocks of the first ``steps`` batches."""
    shape = (workers, int(traffic["steps_per_round"]), int(traffic["batch"]),
             -1)
    return x.reshape(shape)[:, :steps], y.reshape(shape)[:, :steps]


def reference_reading(config: dict, traffic: dict, x, y, seed: int,
                      workers: int, steps: int, devices, *,
                      precision: str = "float32", fault: str | None = None
                      ) -> dict:
    """Drive the plain reference through each worker's first ``steps``
    batches.  ``precision`` other than float32 is the control; ``fault``
    plants one of ``FAULTS`` in it (both stand in the program's place)."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    reference = reference_of(config)
    lr = float(config["recipe"]["lr"])
    xs, ys = check_rows(x, y, traffic, workers, steps)
    p0 = reference.init_params(config, seed)
    losses, grad_norms, after = [], [], []
    for w in range(workers):
        dev = devices[w % len(devices)]
        put = lambda a: jax.device_put(a, dev)
        l, g1, p_after = reference.train_steps(
            config, put(p0), put(xs[w]), put(ys[w]), lr=lr,
            precision=precision, half_batch=fault == "half_batch")
        losses.append(l)
        grad_norms.append(compare.block_norms(g1))
        after.append(jax.device_put(p_after, devices[0]))
    p0 = jax.device_put(p0, devices[0])
    if fault == "no_exchange":
        final = after[0]
    elif fault == "state_unchanged":
        final = p0
    else:
        final = compare.tree_mean(after)
    return {"losses": np.stack([np.asarray(l, np.float64) for l in losses]),
            "grad_norms": grad_norms,
            "update_norms": compare.block_norms(compare.tree_sub(final, p0)),
            "block_sizes": compare.block_sizes(p0), "p0": p0}


def numbers(got: dict, ref: dict) -> tuple[dict, dict]:
    """The compared numbers of ``got`` (a reading) against the reference
    reading, and where the worst blocks were."""
    dead: set[str] = set()
    for g in ref["grad_norms"]:
        dead |= compare.dead_blocks(g)
    grad = max(compare.worst_gap(a, b)
               for a, b in zip(got["grad_norms"], ref["grad_norms"]))
    update = compare.worst_gap(got["update_norms"], ref["update_norms"], dead)
    large = [n for n, size in ref["block_sizes"].items()
             if size >= compare.LARGE_LEAF and n not in dead]
    out = {
        "loss_gap": float(np.max(np.abs(got["losses"] - ref["losses"])
                                 / np.abs(ref["losses"]))),
        "grad_norm_gap": grad[0],
        "update_norm_gap": update[0],
        "update_scatter_gap": compare.scatter_gap(
            got["update_norms"], ref["update_norms"], large),
    }
    where = {"worst_grad_leaf": grad[1], "worst_update_leaf": update[1],
             "left_out_leaves": len(dead)}
    return out, where
