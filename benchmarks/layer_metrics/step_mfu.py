"""Layer `whole step`: required forward + backward operations a token
(``lib/flops.py``; no recomputation, no validation pass) times the
tokens a second of the traced window, over chips times the chip's
published bf16 peak."""


def read(ctx: dict):
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["workers"]
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"] / peak
