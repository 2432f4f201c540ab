"""Layer `orchestration`: median of the driver's ``h2d_ms`` over the
window's rounds that have one (the last prepares nothing): the main
thread's time inside the ``device_put`` of the next round's rows, its
``round.prep.h2d`` span.  That is the host's time in the call, not the
copy's: a few ms where the put needs no device program, about a round
where it needs one and queues behind the round in flight, and the next
round is then dispatched only when it is through."""

import statistics


def read(ctx: dict):
    rows = ctx["results"]["round_timings"][1:ctx["rounds"] + 1]
    values = [r["h2d_ms"] for r in rows if "h2d_ms" in r]
    return statistics.median(values) if values else None
