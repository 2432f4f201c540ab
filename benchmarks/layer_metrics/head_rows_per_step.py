"""Layer `round program`: rows the position-wise head and its loss ran on,
a training step: the mean of the ``head_rows`` counter that the round
program returns with its metrics (``train.py``, ``_labelled_row_sums``; the
driver writes each round's mean into the round's row), over the window's
rounds.  The row buffer's size (a quarter of the step's positions) where
every step's labels fitted it; above it, the share of steps that filled
it more than once.  None for a model that labels every position."""

from benchmarks.layer_metrics.expert_rows_per_step import window_mean


def read(ctx: dict):
    return window_mean(ctx, "head_rows")
