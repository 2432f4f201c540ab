"""Layer `experts`: ``expert_load_max_over_mean`` for a cell of its own:
the fullest held expert's rows over the held experts' mean, a step and
sparse layer, from the round program's own counter."""

from benchmarks.layer_metrics.expert_load_max_over_mean import read  # noqa: F401
