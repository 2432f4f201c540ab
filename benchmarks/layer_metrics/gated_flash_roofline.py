"""Layer `kernels`: the flash kernels' share of their roofline in a stack
of gated grouped-query attention whose layers' masks differ: the least time
for the flash forward and the ONE backward call of every layer (the band
for a sliding layer, the half square for a full one, grouped K and V:
``lib/afmoe_flops.py``) over their device time.  A training step calls
each layer's two kernels once (more under a remat policy: the time counts,
the requirement does not), a validation step the forward alone, a third of
the work.

The kernels are read under the names the accepted picker files give them
today: ``flash_fwd`` and, for the one backward kernel (``%flash_bwd``: six
operands and a tuple of outputs), ``flash_dkv``
(``kernels/flash_dkv.json``).  A `benchmark` PR that renames the backward's
picker renames it here.
"""

from benchmarks.lib import afmoe_flops, flops

KERNELS = ("flash_fwd", "flash_dkv")


def read(ctx: dict):
    k = ctx["trace"]["kernels"]
    a = ctx["arch"]
    if a.get("family") != "afmoe" or not all(name in k for name in KERNELS):
        return None
    spent = sum(k[name]["seconds"] for name in KERNELS)
    if spent <= 0:
        return None
    t = ctx["traffic"]
    steps = ctx["rounds"] * (t["steps_per_round"] + t["val_steps"] / 3)
    one = afmoe_flops.flash_cost(t["batch"], t["seq_len"], a)
    least, _ = flops.roofline_seconds(
        {key: one[key] * steps for key in one}, ctx["peaks"])
    return 100.0 * least / spent
