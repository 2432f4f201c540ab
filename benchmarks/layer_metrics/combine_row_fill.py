"""Layer `experts`: how full the routed layer's way back into the tokens
runs, %: the mean of the ``combine_row_fill`` counter over the window's
rounds.  ``models/moe.py`` sows it a sparse layer and step: the rows on
held experts over the row slots that ``ops/moe_combine.py``'s products
walk in one forward pass (each token tile's rounds times the ``held x R``
slots of its buffer), averaged over the sparse layers.  The rest of the
slots are rows that the one-hot product and the copies carry for nothing.
None for a program that sows no such counter."""

from benchmarks.layer_metrics.expert_rows_per_step import window_mean


def read(ctx: dict):
    return window_mean(ctx, "combine_row_fill")
