"""Layer `orchestration`: median of the driver's ``prep_ms`` over the
window's rounds that have one (the last prepares nothing): its
``round.prep`` span, re-partition + pack + host->device put of the next
round's rows, on the main thread while the device computes."""

import statistics


def read(ctx: dict):
    rows = ctx["results"]["round_timings"][1:ctx["rounds"] + 1]
    values = [r["prep_ms"] for r in rows if "prep_ms" in r]
    return statistics.median(values) if values else None
