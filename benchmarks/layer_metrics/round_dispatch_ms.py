"""Layer `orchestration`: median of the driver's ``stage_ms`` over the
window's rounds: its ``round.dispatch`` span, the main thread's time in
``engine.round_start`` (the packs were staged the round before, in
``round.prep``)."""

import statistics


def read(ctx: dict):
    rows = ctx["results"]["round_timings"][1:ctx["rounds"] + 1]
    values = [r["stage_ms"] for r in rows if "stage_ms" in r]
    return statistics.median(values) if values else None
