"""Layer `sync`: summed device time of the collective operations
(all-reduce, reduce-scatter, all-gather, collective-permute) on chip 0,
per round of the traced window."""


def read(ctx: dict):
    seconds = ctx["trace"]["collective_s_device0"]
    if not seconds:
        return None
    return seconds / ctx["trace"]["rounds_in_window"] * 1e3
