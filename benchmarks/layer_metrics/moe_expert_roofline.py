"""Layer `experts`: the routed experts' grouped products against their
roofline.  Required: nine products a trained layer and step (three
projections, each forward, for its rows' gradient and for its weights'
gradient) and three a validation step, each ``2 * rows * hidden * ffn``
operations on the held experts' matrices once and the rows in and out
(``lib/moe_flops.py``), ``rows`` being what the program's own counter
says landed on held experts.  Over the device time of the ``moe_gmm``
calls the trace shows (``kernels/moe_gmm.json``), which includes what a
remat policy computes twice."""

from benchmarks.layer_metrics import expert_rows_per_step
from benchmarks.lib import flops, moe_flops


def read(ctx: dict):
    k = ctx["trace"]["kernels"].get("moe_gmm")
    rows = expert_rows_per_step.read(ctx)
    if not k or k["seconds"] <= 0 or rows is None:
        return None
    t, a = ctx["traffic"], ctx["arch"]
    one = moe_flops.expert_product_cost(rows, a)
    products = ctx["rounds"] * a["layers"] * (
        9 * t["steps_per_round"] + 3 * t["val_steps"])
    least, _ = flops.roofline_seconds(
        {key: one[key] * products for key in one}, ctx["peaks"])
    return 100.0 * least / k["seconds"]
