"""Layer `round program`: the second part of ``round0_build_s``, in
seconds: ``build_lower_ms`` of the measured call's round 0, the
``round.build.lower`` spans: the jaxprs into StableHLO, Mosaic kernels
included.  No cache serves it."""

from benchmarks.layer_metrics.round0_trace_s import build_part_s


def read(ctx: dict):
    return build_part_s(ctx, "build_lower_ms")
