"""Layer `experts`: how often a routed layer's rows did not fit its row
buffer, a training step and sparse layer: the mean of the
``expert_overflow_chunks`` counter (``models/moe.py`` sows it beside
``expert_rows``: the passes over the buffer after the first that the
counted rows needed), over the window's rounds.  The buffer holds 1.25
times the rows that the held share of the router expects, so 0 says every
layer's rows fitted one pass in every step; 1 a second pass of the three
grouped products and their gathers in every layer and step.  None for a
program that sows no such counter."""

from benchmarks.layer_metrics.expert_rows_per_step import window_mean


def read(ctx: dict):
    return window_mean(ctx, "expert_overflow_chunks")
