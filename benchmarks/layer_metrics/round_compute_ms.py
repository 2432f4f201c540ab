"""Layer `round program`: median of the driver's own ``compute_ms`` over
the window's rounds (its host clock from max(dispatch, previous round
ready) to ready)."""

import statistics


def read(ctx: dict):
    rows = ctx["results"]["round_timings"][1:ctx["rounds"] + 1]
    values = [r["compute_ms"] for r in rows if "compute_ms" in r]
    return statistics.median(values) if values else None
