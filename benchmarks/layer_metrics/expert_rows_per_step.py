"""Layer `experts`: token-expert rows that landed on this chip's held
experts, a training step and layer: the mean of the ``expert_rows`` counter
that the round program returns with its metrics (``models/moe.py`` sows it,
the driver writes each round's mean into the round's row), over the
window's rounds.  With 8 of 64 experts a token and 16 held, about a quarter
of ``8 * tokens``."""


def window_mean(ctx: dict, key: str):
    """Mean of a counter's row key over the window's rounds; None where
    the program returns no such counter."""
    rows = [r[key] for r in ctx["results"]["round_timings"][1:] if key in r]
    return sum(rows) / len(rows) if rows else None


def read(ctx: dict):
    return window_mean(ctx, "expert_rows")
