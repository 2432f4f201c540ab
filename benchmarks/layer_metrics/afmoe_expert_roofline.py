"""Layer `experts`: ``routed_expert_roofline``'s measure for a cell of its
own: nine grouped products a trained sparse layer and step, three a
validation step, each ``2 * rows * hidden * ffn`` operations on the held
experts' matrices once and the rows in and out (``lib/moe_flops.py``),
``rows`` from the program's counter, over the device time of the
``moe_gmm`` calls."""

from benchmarks.layer_metrics.routed_expert_roofline import read  # noqa: F401
