"""Layer `round program`: the first part of ``round0_build_s``, in
seconds: ``build_trace_ms`` of the measured call's round 0, the
``round.build.trace`` spans: the host's Python running the round's
functions into jaxprs (a Pallas call is traced anew at every call site).
No cache serves it: a call pays it whole every time."""


def build_part_s(ctx: dict, key: str):
    """Row 0's ``key`` in seconds; None where the row lacks it."""
    rows = ctx["results"]["round_timings"]
    if not rows or key not in rows[0]:
        return None
    return rows[0][key] / 1e3


def read(ctx: dict):
    return build_part_s(ctx, "build_trace_ms")
