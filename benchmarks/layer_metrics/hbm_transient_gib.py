"""Layer `round program`: how far the process's peak stands above what a
running round holds, in GiB: the allocator's high mark in the window's last
row (``hbm_peak_bytes``: ``peak_bytes_in_use``, never lowered, so it is the
peak of everything the process has done up to there: the short calls, the
probe, every set-up phase and every round) minus ``hbm_held_gib``'s median.
It is what lived only inside a phase or a program.  About 0 says the peak
is held all round by long-lived buffers; a third of the peak says something
parameter-sized lived for a moment: ``results["memory"]["hbm"]["phases"]``
names the phase at which the mark rose.  None for a program whose rows
carry no such reading."""

from benchmarks.layer_metrics.hbm_held_gib import held_bytes


def read(ctx: dict):
    held = held_bytes(ctx)
    if held is None:
        return None
    last = ctx["results"]["round_timings"][1:ctx["rounds"] + 1][-1]
    if "hbm_peak_bytes" not in last:
        return None
    return (last["hbm_peak_bytes"] - held) / 2**30
