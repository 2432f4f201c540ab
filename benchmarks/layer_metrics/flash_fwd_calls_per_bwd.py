"""Layer `round program`: how often the flash forward kernel ran for each
run of the one backward kernel, over the traced window: the calls of
``flash_fwd`` over the calls of ``flash_dkv``, the names the accepted
picker files give the forward and the backward (``kernels/flash_fwd.json``;
``kernels/flash_dkv.json`` reads ``%flash_bwd``: six operands and a tuple
of outputs).  A training step calls each layer's backward once; its
forward once where the step keeps what the call produced, twice where a
remat policy makes the backward pass run it again; a validation step the
forward alone.  None where the trace shows no flash forward or no
backward.  The cells' levels: PERF.md section 3."""


def read(ctx: dict):
    k = ctx["trace"]["kernels"]
    if "flash_fwd" not in k or not k.get("flash_dkv", {}).get("calls"):
        return None
    return k["flash_fwd"]["calls"] / k["flash_dkv"]["calls"]
