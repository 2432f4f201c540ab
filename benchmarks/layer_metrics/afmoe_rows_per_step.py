"""Layer `experts`: ``expert_rows_per_step`` for a cell of its own: the
token-expert rows that landed on this chip's held experts, a training step
and sparse layer (the dense layer sows no counter).  With 8 of 128 experts
a token and 8 held, about ``8 * tokens / 16``."""

from benchmarks.layer_metrics.expert_rows_per_step import read  # noqa: F401
