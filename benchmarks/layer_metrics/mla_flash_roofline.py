"""Layer `kernels`: ``flash_roofline``'s measure at two widths: the least
time for the flash forward, dq and dkv calls of a stack of causal latent-
attention layers (scores ``qk_dim`` wide, values ``v_dim`` wide, over the
half square: ``lib/mla_flops.py``) over their device time.  A training
step calls each layer's three kernels once (more under a remat policy:
the time counts, the requirement does not), a validation step the forward
alone, a third of the work."""

from benchmarks.lib import flops, mla_flops

KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def read(ctx: dict):
    k = ctx["trace"]["kernels"]
    a = ctx["arch"]
    if "qk_dim" not in a or not all(name in k for name in KERNELS):
        return None
    spent = sum(k[name]["seconds"] for name in KERNELS)
    if spent <= 0:
        return None
    t = ctx["traffic"]
    calls = a["layers"] * ctx["rounds"] * (
        t["steps_per_round"] + t["val_steps"] / 3)
    one = mla_flops.mla_flash_cost(t["batch"], t["seq_len"], a)
    least, _ = flops.roofline_seconds(
        {key: one[key] * calls for key in one}, ctx["peaks"])
    return 100.0 * least / spent
