"""Layer `experts`: ``expert_rows_per_step`` for a cell of its own: the
token-expert rows that landed on this chip's held experts, a training step
and sparse layer (a layer without routed experts sows no counter, so the
mean is over the sparse layers alone).  With 6 of 128 experts a token and
16 held, about ``6 * tokens / 8``."""

from benchmarks.layer_metrics.expert_rows_per_step import read  # noqa: F401
