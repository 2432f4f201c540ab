"""Layer `round program`: the third part of ``round0_build_s``, in
seconds: ``build_compile_ms`` of the measured call's round 0, the
``round.build.compile`` spans: the backend's compile, or the persistent
cache's load of an executable it already holds.  Which of the two a run
paid is in the same row: ``build_cache_hits`` > 0 and
``build_cache_misses`` == 0 say every program was loaded; a miss says the
backend compiled (the benchmark's measured call follows two short calls
of the same program, so from their second run on it reads a load); both 0
say no cache is armed."""

from benchmarks.layer_metrics.round0_trace_s import build_part_s


def read(ctx: dict):
    return build_part_s(ctx, "build_compile_ms")
