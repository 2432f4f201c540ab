"""Layer `orchestration`: packed steps that carry no row, a round: the mean
of the ``pad_steps`` row key (``driver.train_global`` writes, in the row of
the round that runs a pair of packs, the steps of its train and validation
packs beyond what each worker's rows need, summed over the workers and the
two packs) over the window's rounds.  Every packed step is computed, masked
or not, so 0 says the round program does no work on rows that do not exist.
None for a program that writes no such key."""

from benchmarks.layer_metrics.expert_rows_per_step import window_mean


def read(ctx: dict):
    return window_mean(ctx, "pad_steps")
