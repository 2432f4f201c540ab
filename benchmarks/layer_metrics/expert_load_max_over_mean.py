"""Layer `experts`: the fullest held expert's rows over the held experts'
mean, a step and layer, from the round program's own counter
(``expert_load_max_over_mean`` in the rounds' rows), over the window's
rounds.  1 is an even spread; nothing is dropped at any value."""

from benchmarks.layer_metrics.expert_rows_per_step import window_mean


def read(ctx: dict):
    return window_mean(ctx, "expert_load_max_over_mean")
