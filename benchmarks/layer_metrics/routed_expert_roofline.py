"""Layer `experts`: ``moe_expert_roofline``'s measure for a stack in which
only some layers have routed experts (``arch["moe_layers"]`` of them): nine
grouped products a trained sparse layer and step, three a validation step,
each ``2 * rows * hidden * ffn`` operations on the held experts' matrices
once and the rows in and out (``lib/moe_flops.py``), ``rows`` from the
program's counter, over the device time of the ``moe_gmm`` calls."""

from benchmarks.layer_metrics import routed_rows_per_step
from benchmarks.lib import flops, moe_flops


def read(ctx: dict):
    k = ctx["trace"]["kernels"].get("moe_gmm")
    rows = routed_rows_per_step.read(ctx)
    a = ctx["arch"]
    if not k or k["seconds"] <= 0 or rows is None or "moe_layers" not in a:
        return None
    t = ctx["traffic"]
    one = moe_flops.expert_product_cost(rows, a)
    products = ctx["rounds"] * a["moe_layers"] * (
        9 * t["steps_per_round"] + 3 * t["val_steps"])
    least, _ = flops.roofline_seconds(
        {key: one[key] * products for key in one}, ctx["peaks"])
    return 100.0 * least / k["seconds"]
