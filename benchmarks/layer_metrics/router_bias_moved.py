"""Layer `experts`: how far a round's steps moved the routers' selection
biases, net: the mean over the sparse layers and ALL the router's experts
of ``|b after the round - b before it|``, from the row key
``select_bias_moved`` that the round program's counters carry
(``train.py``, beside the rule ``move_select_bias``), mean over the
window's rounds.  0 says the rule is dead; ``steps_per_round *
bias_step`` (0.008 at 8 steps of 0.001) that every expert was pushed one
way all round, still far from the mean load; a router near balance reads
little, because its signs alternate.  A program without the rule carries
no such key and the metric is left out."""

from benchmarks.layer_metrics.expert_rows_per_step import window_mean


def read(ctx: dict):
    return window_mean(ctx, "select_bias_moved")
