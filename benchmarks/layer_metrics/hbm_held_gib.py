"""Layer `round program`: what the fullest chip holds while a round runs,
in GiB: the median over the window's rounds of the row key
``hbm_in_use_bytes``, the device allocator's ``bytes_in_use`` as the
driver reads it (``spans.hbm``) at the moment it sees a round ready.  With
two rounds in flight the next round is executing on the device then, so on
one chip this is a reading DURING a round: the state, the driver's own
copy of the parameters, the rows of the rounds staged and the programs'
text.  A running program's temporaries are NOT in this number (PERF.md
section 6, PR 34: on this chip ``bytes_in_use`` never holds them): the
allocator keeps them in a reservation of its own, read once a call as
``results["memory"]["hbm"]["at_end"]["peak_bytes_reserved"]``.  With
``hbm_transient_gib`` it splits ``hbm_peak_gib`` in two.  None for a
program whose rows carry no such reading."""

import statistics


def held_bytes(ctx: dict):
    """Median ``hbm_in_use_bytes`` of rounds 1..N; None where a row of
    the window lacks the key."""
    rows = ctx["results"]["round_timings"][1:ctx["rounds"] + 1]
    if not rows or any("hbm_in_use_bytes" not in r for r in rows):
        return None
    return statistics.median(r["hbm_in_use_bytes"] for r in rows)


def read(ctx: dict):
    held = held_bytes(ctx)
    return None if held is None else held / 2**30
