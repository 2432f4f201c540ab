"""Layer `kernels`: the flash forward, dq and dkv kernels together against
their roofline.  The least time the chip could take for the calls the
trace shows (the larger of required operations over peak FLOP/s and
required bytes over peak HBM bytes/s, from ``lib/flops.py``) over the
device time those calls took.  A forward call with no backward twin is a
validation pass and needs the forward's third of the work."""

from benchmarks.lib import flops


def read(ctx: dict):
    k = ctx["trace"]["kernels"]
    if not all(name in k for name in ("flash_fwd", "flash_dq", "flash_dkv")):
        return None
    spent = sum(k[n]["seconds"] for n in ("flash_fwd", "flash_dq",
                                          "flash_dkv"))
    if spent <= 0:
        return None
    t, a = ctx["traffic"], ctx["arch"]
    whole = flops.flash_attention_cost(
        t["batch"], t["seq_len"], a["heads"], a["hidden"] // a["heads"],
        causal=t["objective"] == "causal_lm")
    trained = k["flash_dq"]["calls"]
    forward_only = k["flash_fwd"]["calls"] - trained
    cost = {"flops": whole["flops"] * (trained + forward_only / 3),
            "bytes": whole["bytes"] * (trained + forward_only / 3)}
    least, _ = flops.roofline_seconds(cost, ctx["peaks"])
    return 100.0 * least / spent
