"""Operations and bytes that a routed-expert, mixed-attention decoder
REQUIRES, from shapes alone (``lib/flops.py`` has the rules: 2 operations a
multiply-add, forward once and backward twice, no recomputation, no
elementwise work, no lookup).

The architecture is the dict ``references/mellum_moe.py::arch_of`` gives.
What is counted is what THIS chip is required to do: its held experts'
share of the routed products, its rows of the head.
"""

from __future__ import annotations


def visible_keys_mean(seq_len: int, window: int | None) -> float:
    """Keys a query attends, averaged over the positions of a sequence:
    position i sees min(i + 1, window) of them."""
    if window is None or window >= seq_len:
        return (seq_len + 1) / 2
    ramp = window * (window + 1) / 2            # positions 0 .. window-1
    return (ramp + (seq_len - window) * window) / seq_len


def layer_matmul_params(arch: dict) -> float:
    """Weights of one layer that a token meets in a matrix product here:
    attention's four projections, the router, and the expected share of
    its ``top_k`` experts that this chip holds."""
    h, hd = arch["hidden"], arch["head_dim"]
    attn = h * arch["heads"] * hd * 2 + h * arch["kv_heads"] * hd * 2
    router = h * arch["experts"]
    expert = 3 * h * arch["ffn"]
    held_share = arch["top_k"] * arch["held"][1] / arch["experts"]
    return attn + router + held_share * expert


def attention_flops_per_token(arch: dict, seq_len: int) -> float:
    """QK^T and PV forward, four products backward, each 2 * (visible
    keys) * heads * head_dim operations a token, summed over the layers by
    their kind: the band for a sliding layer, the half square for a full
    one."""
    per_key = 6 * 2 * arch["heads"] * arch["head_dim"]
    return sum(per_key * visible_keys_mean(
        seq_len, arch["window"] if kind == "sliding" else None)
        for kind in arch["layer_types"])


def train_flops_per_token(arch: dict, seq_len: int) -> float:
    weights = (arch["layers"] * layer_matmul_params(arch)
               + arch["hidden"] * arch["vocab"])
    return 6 * weights + attention_flops_per_token(arch, seq_len)


def window_flash_cost(batch: int, seq_len: int, arch: dict, kind: str,
                      dtype_bytes: int = 2) -> dict:
    """One layer's attention, forward + backward, as the three flash
    kernels together have to do it (``lib/flops.py::flash_attention_cost``
    with the mask's own count of visible keys, grouped K and V)."""
    window = arch["window"] if kind == "sliding" else None
    flops = (6 * 2 * batch * arch["heads"] * arch["head_dim"] * seq_len
             * visible_keys_mean(seq_len, window))
    q = batch * seq_len * arch["heads"] * arch["head_dim"] * dtype_bytes
    kv = batch * seq_len * arch["kv_heads"] * arch["head_dim"] * dtype_bytes
    # forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    # writes dq, dk, dv
    return {"flops": float(flops), "bytes": float(6 * q + 6 * kv)}


def expert_product_cost(rows: float, arch: dict, dtype_bytes: int = 2
                        ) -> dict:
    """ONE grouped product of the expert layer over ``rows`` token-expert
    rows (any of the three projections, forward or either gradient: all
    have the same shape): 2 * rows * hidden * ffn operations; bytes: the
    held experts' matrices once and the rows in and out."""
    h, f = arch["hidden"], arch["ffn"]
    return {"flops": 2.0 * rows * h * f,
            "bytes": float(dtype_bytes * (arch["held"][1] * h * f
                                          + rows * (h + f)))}
