"""Layer `orchestration`: the share of the loop's own time in which the
main thread was neither waiting for a round (``round.wait``) nor inside
the ``device_put`` of the next round's rows (``round.prep.h2d``): its
Python, partitioning, packing and dispatching.  The loop's time runs
from the dispatch of round 1 to the dispatch of round N
(``t_dispatch_s``).  A ``round.wait`` span ends at its row's
``t_ready_s`` and lasts ``wait_ms``, and counts as far as it lies inside
that time; the put of row r runs between the dispatches of rounds r and
r+1, so ``h2d_ms`` of rows 1..N-1 lies inside it whole.

The put is left out because it cannot be told apart from a wait: where it
needs a device program it queues behind the round in flight and the
thread sits in it for a whole round (four chips), where it needs none it
takes a few ms.  Read ``round_h2d_ms`` beside this number: near 0 here
and a put of a few ms, the device sets the pace and an idle gap is the
device's own; a put of about a round, the next dispatch waits for it and
the gap is the loop's.

(The wait spans are laid on the clock and not summed by row because,
with two rounds in flight, round r is settled in the loop iteration that
dispatches round r+1.  A streamed round puts its chunks inside the
dispatch and has no ``h2d_ms``: this reads nothing there.)"""


def read(ctx: dict):
    rows = ctx["results"]["round_timings"][:ctx["rounds"] + 1]
    if len(rows) < 3 or not all(
            k in r for r in rows
            for k in ("t_dispatch_s", "t_ready_s", "wait_ms")):
        return None
    if not all("h2d_ms" in r for r in rows[1:-1]):
        return None
    t0, t1 = rows[1]["t_dispatch_s"], rows[-1]["t_dispatch_s"]
    if t1 <= t0:
        return None
    waiting = sum(
        max(0.0, min(r["t_ready_s"], t1)
            - max(r["t_ready_s"] - r["wait_ms"] / 1e3, t0))
        for r in rows)
    putting = sum(r["h2d_ms"] for r in rows[1:-1]) / 1e3
    return 100.0 * (1.0 - (waiting + putting) / (t1 - t0))
