"""Layer `orchestration`: median time chip 0 sat idle between the end of
one execution of the round program and the start of the next, from the
device trace.  (The driver's host-side ``gap_ms`` is not recorded for a
round it leaves in flight, which on a TPU is every round but the last:
PERF.md, section 3.)"""

import statistics


def read(ctx: dict):
    gaps = ctx["trace"].get("between_round_idle_s") or []
    return statistics.median(gaps) * 1e3 if gaps else None
