"""Layer `kernels`: ``flash_roofline``'s measure where the layers' masks
differ: the least time for the flash forward, dq and dkv calls of a stack
that mixes window and full attention (the band for a sliding layer, the
half square for a full one, grouped K and V: ``lib/moe_flops.py``) over
their device time.  A training step calls each layer's three kernels once
(more under a remat policy: the time counts, the requirement does not), a
validation step the forward alone, a third of the work."""

from benchmarks.lib import flops, moe_flops

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx: dict):
    k = ctx["trace"]["kernels"]
    a = ctx["arch"]
    if "layer_types" not in a or not all(name in k for name in KERNELS):
        return None
    spent = sum(k[name]["seconds"] for name in KERNELS)
    if spent <= 0:
        return None
    t = ctx["traffic"]
    steps = ctx["rounds"] * (t["steps_per_round"] + t["val_steps"] / 3)
    cost = {"flops": 0.0, "bytes": 0.0}
    for kind in a["layer_types"]:
        one = moe_flops.window_flash_cost(t["batch"], t["seq_len"], a, kind)
        for key in cost:
            cost[key] += one[key] * steps
    least, _ = flops.roofline_seconds(cost, ctx["peaks"])
    return 100.0 * least / spent
