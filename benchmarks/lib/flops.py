"""Operations and bytes the algorithm REQUIRES, from shapes alone.

Nothing here reads the compiled program: XLA's cost analysis counts what
was recomputed and reads zero for a Mosaic custom call, so neither an MFU
nor a kernel's roofline can stand on it.  Counted: 2 operations per
multiply-add of every matrix product of the forward pass, twice that again
for the backward pass (one product for the input's gradient, one for the
weight's).  Not counted: recomputation (the flash backward's second
QK^T, any remat), elementwise work, the embedding lookup.
"""

from __future__ import annotations


def matmul_params(arch: dict, head_share: float = 1.0) -> float:
    """Weights that sit in a matrix product, per token.  The output head
    is one such product (tied or not); ``head_share`` is the share of
    positions whose logits the loss needs (all of them for a causal LM,
    the masked ones for MLM)."""
    h, f, layers, v = arch["hidden"], arch["ffn"], arch["layers"], arch["vocab"]
    per_layer = h * 3 * h + h * h + 2 * h * f
    head = h * v
    if arch["family"] == "bert":
        head += h * h          # the MLM transform before the decoder
    return layers * per_layer + head_share * head


def attention_flops_per_token(arch: dict, seq_len: int, causal: bool) -> float:
    """Forward + backward: QK^T and PV forward (2 products), four products
    backward, each 2 * seq_len * hidden operations a token; a causal mask
    needs half of every one."""
    full = 6 * 2 * seq_len * arch["hidden"] * arch["layers"]
    return full / 2 if causal else full


def train_flops_per_token(arch: dict, seq_len: int, head_share: float,
                          causal: bool) -> float:
    return (6 * matmul_params(arch, head_share)
            + attention_flops_per_token(arch, seq_len, causal))


def flash_attention_cost(batch: int, seq_len: int, heads: int, head_dim: int,
                         causal: bool, dtype_bytes: int = 2) -> dict:
    """One layer's attention, forward + backward, as the three flash
    kernels together have to do it: operations as above, and the bytes of
    reading q, k, v and writing o forward, reading q, k, v, o, do and
    writing dq, dk, dv backward (the log-sum-exp rows are left out: 1/64th
    of a tensor)."""
    flops = 6 * 2 * batch * heads * seq_len * seq_len * head_dim
    if causal:
        flops /= 2
    tensor = batch * seq_len * heads * head_dim * dtype_bytes
    return {"flops": float(flops), "bytes": float(12 * tensor)}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound binds."""
    t_flops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
