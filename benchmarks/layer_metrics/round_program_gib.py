"""Layer `round program`: what XLA's ``memory_analysis()`` says the
compiled round program holds on a chip while it runs, in GiB: arguments +
temporaries + outputs that alias no argument (``results["memory"]``, an
exact count).  ``peak_bytes_in_use``, which ``hbm_peak_gib`` reads, counts
buffers and not a program's temporaries (PERF.md, section 6), so this is
the reading that moves when a change buys speed with scratch memory."""


def read(ctx: dict):
    rows = ctx["results"]["memory"].get("programs", {}).get("round") or []
    if not rows:
        return None
    return max(r["argument_bytes"] + r["temp_bytes"] + r["output_bytes"]
               - r["alias_bytes"] for r in rows) / 2**30
