"""Layer `device`: 1 - (union of the intervals in which an operation ran)
/ window, averaged over the chips, the window cut from the start of the
round program's 2nd execution to the end of its last."""


def read(ctx: dict):
    tr = ctx["trace"]
    if tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
