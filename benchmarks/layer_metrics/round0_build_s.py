"""Layer `round program`: ``build_ms`` of the measured call's round 0,
in seconds: the ``round.build`` spans of the programs its dispatch traced,
lowered and compiled or loaded from the compile cache.  A call pays it
because an engine cannot be handed from one call to the next."""


def read(ctx: dict):
    rows = ctx["results"]["round_timings"]
    if not rows or "build_ms" not in rows[0]:
        return None
    return rows[0]["build_ms"] / 1e3
