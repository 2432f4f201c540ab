"""Layer `sync`: bytes each round's synchronisation puts on the
interconnect, as the program counts them from its bucket plan
(``results["sync_engine"]["sync_bytes_ici"]``), in MB (10**6)."""


def read(ctx: dict):
    b = ctx["results"]["sync_bytes_ici"]
    return b / 1e6 if b else None
